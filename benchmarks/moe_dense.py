"""MoE vs dense at matched parameters: sparsity's FLOP win, measured.

The ``bench.py --moe`` rung trains TWO tiny llamas with (near-)IDENTICAL
parameter counts through the SAME SpmdGPipe engine on the same token
stream and reports wall-clock tokens/s:

* **moe** — every block's MLP is an E-expert layer (each expert hidden
  ``mlp_ratio * dim``), token-choice top-k routing, ``dropless``
  dispatch (megablocks-style grouped matmuls: per-step FFN work is
  exactly ``k*t`` expert rows regardless of router balance, so the
  measured number is deterministic in shape — no capacity-drop noise);
* **dense** — the classic llama whose single MLP hidden is
  ``n_experts * mlp_ratio * dim``: the SAME total FFN weights as the E
  experts combined (the router's ``[dim, E]`` gate is the only extra,
  reported as ``param_ratio``).

Per token the MoE touches ``top_k / n_experts`` of the FFN weights the
dense model must drag through every matmul, so on a serialized CPU host
(where FLOPs ARE time) real tokens/s must move toward the
``1 / (attn_share + ffn_share * k/E)`` bound.  The benchmark prints the
measured speedup next to that bound; ``--gate`` enforces
``--min-speedup``.  Equivalence is NOT claimed — the two models compute
different functions by design; the exactness story for MoE itself
(ep-sharded vs single-chip) lives in tools/moe_verify.py.

Usage::

    env JAX_PLATFORMS=cpu python bench.py --moe              # CPU ref
    env JAX_PLATFORMS=cpu python -m benchmarks.moe_dense --json
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import os
import time

import jax
import jax.numpy as jnp
import numpy as np

if os.environ.get("JAX_PLATFORMS", "").strip().lower() == "cpu":
    jax.config.update("jax_platforms", "cpu")


def _n_params(params) -> int:
    return sum(int(a.size) for a in jax.tree_util.tree_leaves(params))


def _expert_params(params, n_experts: int) -> int:
    """Total weights living inside expert stacks: the pipe stacks each
    stage's blocks, so an ``[E, dim, hidden]`` expert weight appears as
    a ``[stages_per_rank*blocks, E, ...]`` 4-d leaf."""
    return sum(
        int(a.size) for a in jax.tree_util.tree_leaves(params)
        if getattr(a, "ndim", 0) == 4 and a.shape[1] == n_experts
    )


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--stages", type=int, default=2)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--dim", type=int, default=128)
    ap.add_argument("--vocab", type=int, default=512)
    ap.add_argument("--experts", type=int, default=8)
    ap.add_argument("--topk", type=int, default=2)
    ap.add_argument("--batches", type=int, default=4)
    ap.add_argument("--repeats", type=int, default=3)
    ap.add_argument("--min-speedup", type=float, default=1.1)
    ap.add_argument("--gate", action="store_true",
                    help="fail (exit 1) when MoE tokens/s misses "
                         "--min-speedup x dense")
    ap.add_argument("--json", action="store_true",
                    help="emit one JSON line (bench.py --moe)")
    args = ap.parse_args(argv)

    import optax

    from torchgpipe_tpu.models.moe import MoEConfig, llama_moe_spmd
    from torchgpipe_tpu.models.transformer import (
        TransformerConfig,
        cross_entropy,
        llama_spmd,
    )
    from torchgpipe_tpu.spmd import SpmdGPipe, make_mesh

    n = min(args.stages, len(jax.devices()))
    cfg = TransformerConfig(
        vocab=args.vocab, dim=args.dim, n_layers=2 * n, n_heads=4,
        n_kv_heads=2,
    )
    moe = MoEConfig(
        n_experts=args.experts, top_k=args.topk, dispatch="dropless"
    )
    # Matched FFN weights EXACTLY: the gated mlp_hidden rounds
    # ``2/3 * ratio * dim`` up to a 128 multiple, so scaling mlp_ratio
    # by E would not give E x the expert hidden — invert the formula
    # for the dense ratio that lands on ``E * expert_hidden`` (itself a
    # 128 multiple, so the round-up is the identity on it).
    dense_hidden = args.experts * cfg.mlp_hidden
    dense_cfg = dataclasses.replace(
        cfg, mlp_ratio=3.0 * dense_hidden / (2.0 * cfg.dim)
    )

    rng = np.random.RandomState(0)
    batches = [
        (jnp.asarray(rng.randint(0, args.vocab, (args.batch, args.seq)),
                     jnp.int32),
         jnp.asarray(rng.randint(0, args.vocab, (args.batch, args.seq)),
                     jnp.int32))
        for _ in range(args.batches)
    ]
    spec = jax.ShapeDtypeStruct((args.batch, args.seq), jnp.int32)
    mesh = make_mesh(n, devices=jax.devices()[:n])
    opt = optax.sgd(1e-3)

    def rung(parts):
        block, pre, post = parts
        pipe = SpmdGPipe(
            block, n, mesh, chunks=2, loss_fn=cross_entropy,
            pre=pre, post=post, checkpoint="except_last",
        )
        params = pipe.place(pipe.init(jax.random.PRNGKey(0), spec))
        step = pipe.make_train_step(opt, donate=False)
        opt_state = pipe.place_tree(opt.init(params))
        # Warmup (compile) outside the timed window, then stream the
        # whole batch list --repeats times.
        l, p, s = step(params, opt_state, *batches[0])
        jax.block_until_ready(l)
        t0 = time.perf_counter()
        for _ in range(args.repeats):
            for x, y in batches:
                l, p, s = step(p, s, x, y)
        jax.block_until_ready(l)
        dt = time.perf_counter() - t0
        tokens = args.repeats * args.batches * args.batch * args.seq
        return params, float(l), round(tokens / dt, 1)

    moe_params, moe_loss, moe_tok_s = rung(
        llama_moe_spmd(cfg, moe, n)
    )
    dense_params, dense_loss, dense_tok_s = rung(llama_spmd(dense_cfg, n))

    n_moe, n_dense = _n_params(moe_params), _n_params(dense_params)
    experts = _expert_params(moe_params, args.experts)
    active = n_moe - experts + experts * args.topk // args.experts
    out = {
        "bench": "moe_dense",
        "platform": jax.devices()[0].platform,
        "n_experts": args.experts,
        "top_k": args.topk,
        "dispatch": moe.dispatch,
        "moe_params": n_moe,
        "dense_params": n_dense,
        # ~1.0 by construction: the router gate is the only extra.
        "param_ratio": round(n_moe / n_dense, 4),
        "active_params": active,
        "active_fraction": round(active / n_moe, 4),
        "moe_tok_s": moe_tok_s,
        "dense_tok_s": dense_tok_s,
        "speedup": round(moe_tok_s / dense_tok_s, 3),
        "moe_loss": round(moe_loss, 4),
        "dense_loss": round(dense_loss, 4),
    }
    out["speedup_ok"] = out["speedup"] >= args.min_speedup

    if args.json:
        print(json.dumps(out))
    else:
        print(json.dumps(out, indent=2))
    if abs(out["param_ratio"] - 1.0) > 0.02:
        print(f"FAIL: parameter counts not matched "
              f"(ratio {out['param_ratio']})")
        return 1
    if args.gate and not out["speedup_ok"]:
        print(f"FAIL: MoE speedup {out['speedup']} < {args.min_speedup}")
        return 1
    return 0


if __name__ == "__main__":
    from torchgpipe_tpu.utils.compile_cache import enable_compile_cache

    enable_compile_cache()
    raise SystemExit(main())
