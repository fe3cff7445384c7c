"""Serving decode throughput: continuous vs static batching (tokens/sec).

The measurement surface for :mod:`torchgpipe_tpu.serving` — the number
BENCH_NOTES.md's "no decode number exists" gap asked for, measured the
way a decode server runs: a burst of ragged-length requests through the
slot-pooled engine, tokens/sec over the whole burst, continuous
(iteration-level) batching against the static run-to-longest baseline
(``wave_admission=True`` — same compiled programs, no recycling).

Measurement integrity (the BENCH_NOTES.md:472 contract):

* **Host-fetch inside the timed region, by construction** — the engine
  host-fetches every step's sampled tokens (streaming is the product
  feature), so ``block_until_ready`` laziness cannot fake a timing; the
  timed region ends only after the LAST generated token materialized on
  the host.
* **Physical-floor gate** — generating N tokens costs at least
  ``2·n_params·N`` matmul FLOPs; a run faster than that at the chip's
  published bf16 peak is refused, not published (the decode twin of
  bench.py's mfu>1 check).

Usage::

    env JAX_PLATFORMS=cpu python -m benchmarks.llama_serving --preset tiny
    python -m benchmarks.llama_serving --preset 1b --slots 8   # on TPU
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

from torchgpipe_tpu.layers import sequential_init
from torchgpipe_tpu.models.transformer import TransformerConfig, llama
from torchgpipe_tpu.serving import Engine
from torchgpipe_tpu.utils.hw import chip_peak_bf16_flops

from benchmarks.llama_decode import PRESETS


def _workload(args: argparse.Namespace, vocab: int):
    """Ragged, skewed request mix (seeded): short interactive requests
    threaded between long generations — the shape continuous batching
    exists for."""
    rng = np.random.RandomState(args.seed)
    reqs = []
    for i in range(args.requests):
        plen = int(rng.randint(2, args.prompt_len + 1))
        if i % 3 == 0:   # every third request is a long generation
            new = int(args.new_tokens)
        else:
            new = int(rng.randint(2, max(3, args.new_tokens // 4)))
        prompt = np.mod(
            rng.randint(0, vocab, (plen,)), vocab
        ).astype(np.int32)
        reqs.append((prompt, new))
    return reqs


def _run(mode: str, cfg, params, reqs, args) -> dict:
    from torchgpipe_tpu.serving import ServingMetrics

    eng = Engine(
        cfg, params,
        num_slots=args.slots,
        max_len=args.prompt_len + args.new_tokens,
        prefill_chunk=args.prefill_chunk,
        kv_quant=args.kv_quant,
        wave_admission=(mode == "static"),
    )
    # Warmup on the SAME engine (jax.jit caches per closure, so a fresh
    # engine would re-trace and re-compile inside the timed region);
    # reset the metrics so the snapshot covers only the timed burst.
    for p, n in reqs:
        eng.submit(p, n)
    eng.run()
    eng.metrics = ServingMetrics()
    t0 = time.perf_counter()
    rids = [eng.submit(p, n) for p, n in reqs]
    eng.run()
    # The engine host-fetched every token already; materialize the result
    # arrays anyway so the timed region provably ends on host data.
    toks = int(sum(eng.result(r).size for r in rids))
    dt = time.perf_counter() - t0
    assert eng.compile_stats == {"prefill": 1, "decode": 1}, (
        eng.compile_stats
    )
    snap = eng.metrics.snapshot()
    return {
        "mode": mode,
        "tokens": toks,
        "seconds": dt,
        "tokens_per_sec": toks / dt,
        "engine_steps": snap["engine_steps"],
        "tokens_per_step": snap["tokens_per_step"],
        "occupancy": snap["occupancy"],
    }


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--preset", choices=sorted(PRESETS), default="tiny")
    ap.add_argument("--slots", type=int, default=4)
    ap.add_argument("--requests", type=int, default=12)
    ap.add_argument("--prompt-len", type=int, default=32)
    ap.add_argument("--new-tokens", type=int, default=64)
    ap.add_argument("--prefill-chunk", type=int, default=8)
    ap.add_argument("--bf16", action="store_true")
    ap.add_argument("--kv-quant", action="store_true",
                    help="int8 KV pool (half the bf16 footprint)")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--json", action="store_true",
                    help="emit one JSON line (bench.py --decode-serving)")
    args = ap.parse_args()

    dim, n_layers, nh, nkv, vocab = PRESETS[args.preset]
    cfg = TransformerConfig(
        vocab=vocab, dim=dim, n_layers=n_layers, n_heads=nh,
        n_kv_heads=nkv,
        dtype=jnp.bfloat16 if args.bf16 else jnp.float32,
    )
    spec = jax.ShapeDtypeStruct((1, args.prompt_len), jnp.int32)
    params, _, _ = sequential_init(llama(cfg), jax.random.PRNGKey(0), spec)
    reqs = _workload(args, vocab)

    results = {}
    for mode in ("continuous", "static"):
        # _run warms up (compiles both programs) and times a second
        # serving of the same burst on the same engine, steady-state.
        results[mode] = _run(mode, cfg, params, reqs, args)

    # Physical floor (decode twin of bench.py's mfu gate): refuse
    # sub-floor timings instead of publishing them.
    peak = chip_peak_bf16_flops(jax.devices()[0])
    gated = False
    if peak is not None:
        n_params = sum(
            l.size for l in jax.tree_util.tree_leaves(params)
            if hasattr(l, "size")
        )
        n_params = max(n_params - cfg.vocab * cfg.dim, 0)
        for r in results.values():
            floor_s = 2.0 * n_params * r["tokens"] / peak
            if r["seconds"] < floor_s:
                raise SystemExit(
                    f"IMPLAUSIBLE: {r['mode']} served {r['tokens']} tokens "
                    f"in {r['seconds'] * 1e3:.2f} ms, below the "
                    f"{floor_s * 1e3:.2f} ms physical floor — the backend "
                    "did not execute the timed programs; not publishing"
                )
        gated = True

    cont, stat = results["continuous"], results["static"]
    out = {
        "bench": "decode-serving",
        "preset": args.preset,
        "platform": jax.devices()[0].platform,
        "slots": args.slots,
        "requests": args.requests,
        "kv_quant": bool(args.kv_quant),
        "continuous_tokens_per_sec": round(cont["tokens_per_sec"], 2),
        "static_tokens_per_sec": round(stat["tokens_per_sec"], 2),
        "speedup": round(
            cont["tokens_per_sec"] / max(stat["tokens_per_sec"], 1e-9), 3
        ),
        "continuous_occupancy": round(cont["occupancy"], 3),
        "static_occupancy": round(stat["occupancy"], 3),
        # Steps/occupancy are the deterministic continuous-batching win
        # (scheduling, not machine noise): fewer compiled-step launches
        # for the same tokens.  tokens_per_sec on a contended host can
        # flip either way; on TPU, where decode steps are
        # HBM-bandwidth-bound at ~fixed cost, steps ~ time.
        "continuous_engine_steps": cont["engine_steps"],
        "static_engine_steps": stat["engine_steps"],
        "continuous_tokens_per_step": round(cont["tokens_per_step"], 3),
        "static_tokens_per_step": round(stat["tokens_per_step"], 3),
        "floor_gated": gated,
        "validated": gated,
    }
    if args.json:
        print(json.dumps(out), flush=True)
        return
    print(
        f"{args.preset}: {args.requests} ragged requests, {args.slots} "
        f"slots -> continuous {cont['tokens_per_sec']:.1f} tok/s "
        f"(occ {cont['occupancy']:.0%}, {cont['engine_steps']} steps) vs "
        f"static {stat['tokens_per_sec']:.1f} tok/s "
        f"(occ {stat['occupancy']:.0%}, {stat['engine_steps']} steps): "
        f"{out['speedup']:.2f}x, platform {out['platform']}",
        flush=True,
    )


if __name__ == "__main__":
    from torchgpipe_tpu.utils.compile_cache import enable_compile_cache

    enable_compile_cache()
    main()
