"""Megastep ladder: K optimizer steps per compiled program, measured.

The dispatch-amortization rung ``bench.py --megastep`` runs: the SPMD
tiny-llama preset trained through ``make_train_step(megastep=K)`` for
K over the canonical ladder (``tune.megastep_options`` — the same axis
the planner sweeps), batches streamed through the sharding-aware
double-buffered prefetcher.  Reported per K: mean milliseconds per
OPTIMIZER step (wall clock over the timed window divided by
``megasteps x K``) — so the ladder isolates exactly what megastep
amortizes: per-step Python dispatch, host sync, and guard bookkeeping.

Measurement integrity: every timed window ends on
``block_until_ready`` of the final params leaf (no async laziness), a
warmup megastep per K keeps compiles out of the timed region, and the
SAME stacked batch values feed every K (losses must agree across the
ladder — asserted, since megastep(K) is bitwise K single steps).

Usage::

    env JAX_PLATFORMS=cpu python bench.py --megastep            # CPU ref
    env JAX_PLATFORMS=cpu python -m benchmarks.llama_megastep --steps 32
"""

from __future__ import annotations

import argparse
import json
import os
import time

import jax
import jax.numpy as jnp
import numpy as np

if os.environ.get("JAX_PLATFORMS", "").strip().lower() == "cpu":
    jax.config.update("jax_platforms", "cpu")


def main(argv=None) -> int:
    import optax

    from torchgpipe_tpu import tune
    from torchgpipe_tpu.models.transformer import (
        TransformerConfig,
        cross_entropy,
        llama_spmd,
    )
    from torchgpipe_tpu.spmd import SpmdGPipe, make_mesh
    from torchgpipe_tpu.utils.data import prefetch_to_pipe

    ap = argparse.ArgumentParser()
    ap.add_argument("--stages", type=int, default=2)
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--chunks", type=int, default=2)
    ap.add_argument("--steps", type=int, default=32,
                    help="timed OPTIMIZER steps per K (divisible by "
                         "every K in the ladder)")
    ap.add_argument("--json", action="store_true",
                    help="emit one JSON line (bench.py --megastep)")
    args = ap.parse_args(argv)

    # The canonical ladder, filtered to Ks dividing the timed window —
    # the same divisibility contract the planner's hook-cadence filter
    # enforces.
    ladder = tune.megastep_options(steps=args.steps)
    # CPU tiny preset (llama_speed PRESETS["tiny"]), scaled to the pp
    # mesh actually present.
    n = min(args.stages, len(jax.devices()))
    cfg = TransformerConfig(
        vocab=1024, dim=256, n_layers=2 * n, n_heads=8, n_kv_heads=4,
        mlp_ratio=4.0,
    )
    block, pre, post = llama_spmd(cfg, n)
    mesh = make_mesh(n, devices=jax.devices()[:n])
    pipe = SpmdGPipe(
        block, n, mesh, chunks=args.chunks, loss_fn=cross_entropy,
        pre=pre, post=post, checkpoint="except_last",
    )
    tokens = jax.random.randint(
        jax.random.PRNGKey(1), (args.batch, args.seq + 1), 0, cfg.vocab
    )
    inputs, labels = tokens[:, :-1], tokens[:, 1:]
    in_spec = jax.ShapeDtypeStruct(inputs.shape, inputs.dtype)
    opt = optax.adamw(3e-4)
    params0 = pipe.init(jax.random.PRNGKey(0), in_spec)

    results = []
    final_loss = {}
    for K in ladder:
        step = pipe.make_train_step(opt, donate=True, megastep=K)
        # [K, B, S]-stacked batches through the sharding-aware
        # prefetcher (leading K axis unsharded).
        stacked = (
            jnp.broadcast_to(inputs, (K,) + inputs.shape),
            jnp.broadcast_to(labels, (K,) + labels.shape),
        ) if K > 1 else (inputs, labels)
        batches = prefetch_to_pipe(
            iter(lambda: stacked, None), pipe, size=2, stacked=K > 1
        )
        megasteps = args.steps // K
        # Warmup (compile) on a THROWAWAY state so every K's timed
        # window starts from params0 and runs exactly --steps optimizer
        # steps — the cross-K loss-agreement gate below depends on it.
        wp = jax.tree_util.tree_map(jnp.copy, params0)
        wo = pipe.place_tree(opt.init(wp))
        x, y = next(batches)
        jax.block_until_ready(step(wp, wo, x, y)[1])
        params = jax.tree_util.tree_map(jnp.copy, params0)
        opt_state = pipe.place_tree(opt.init(params))
        t0 = time.perf_counter()
        for _ in range(megasteps):
            x, y = next(batches)
            out = step(params, opt_state, x, y)
            loss, params, opt_state = out[0], out[1], out[2]
        jax.block_until_ready(params)
        dt = time.perf_counter() - t0
        ms_per_step = dt * 1e3 / (megasteps * K)
        final_loss[K] = float(np.asarray(loss).reshape(-1)[-1])
        results.append({
            "megastep": K,
            "optimizer_steps": megasteps * K,
            "program_dispatches": megasteps,
            "ms_per_optimizer_step": ms_per_step,
        })
        print(
            f"megastep K={K:<3d}: {ms_per_step:8.2f} ms/step "
            f"({megasteps} dispatches for {megasteps * K} steps, "
            f"last loss {final_loss[K]:.4f})",
            flush=True,
        )
    # Same data + warmup step per K and megastep(K) == K single steps:
    # every ladder entry must land on the same trained loss.
    losses = {round(v, 3) for v in final_loss.values()}
    assert len(losses) == 1, (
        f"megastep ladder diverged across K: {final_loss} — the "
        "bitwise K-step contract is broken; not publishing"
    )
    base = results[0]["ms_per_optimizer_step"]
    for r in results:
        r["speedup_vs_k1"] = base / r["ms_per_optimizer_step"]
    line = {
        "bench": "megastep",
        "platform": jax.devices()[0].platform,
        "stages": n,
        "batch": args.batch,
        "seq": args.seq,
        "results": results,
    }
    if args.json:
        print("BENCH_JSON " + json.dumps(line), flush=True)
    best = max(results, key=lambda r: r["speedup_vs_k1"])
    print(
        f"FINAL | megastep ladder [{line['platform']}]: K={best['megastep']} "
        f"is {best['speedup_vs_k1']:.2f}x K=1 "
        f"({best['ms_per_optimizer_step']:.2f} vs {base:.2f} ms/step)",
        flush=True,
    )
    return 0


if __name__ == "__main__":
    from torchgpipe_tpu.utils.compile_cache import enable_compile_cache

    enable_compile_cache()
    raise SystemExit(main())
