#!/usr/bin/env python
"""Frontier table for the static step autotuner (torchgpipe_tpu.tune).

Sweeps (remat policy × micro-batch count × CE chunk size) for a llama
pipeline preset and prints the predicted-MFU/residents frontier — no
accelerator is touched (HLO cost analysis + ``eval_shape`` on the host
CPU mesh), so the table is printable on any machine::

    python tools/tune_report.py --preset 1b --seq 4096 --stages 4 \
        --batch 8 --budget-gib 15.75

Preset names come from ``tools/presets.py``; ``--fused-ce``
swaps the lm head for the chunked-vocab CE loss layer so the CE chunk
axis of the sweep activates.  See docs/tuning.md.
"""

from __future__ import annotations

import argparse
import os
import sys
from typing import Sequence


def main(argv: Sequence[str] | None = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--preset", default="1b",
                    help="llama_speed preset (tiny|small|1b|llama3-8b)")
    ap.add_argument("--seq", type=int, default=4096)
    ap.add_argument("--stages", type=int, default=4)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--chunks", default=None,
                    help="comma-separated micro-batch counts (default: "
                         "divisors of the batch)")
    ap.add_argument("--budget-gib", type=float, default=15.75,
                    help="per-chip HBM budget (default: the v5e AOT limit)")
    ap.add_argument("--fused-ce", action="store_true",
                    help="chunked-vocab CE loss layer (activates the CE "
                         "chunk-size sweep axis)")
    ap.add_argument("--bf16", action=argparse.BooleanOptionalAction,
                    default=True,
                    help="bfloat16 block compute (--no-bf16 for float32; "
                         "f32 residuals are 2x the bytes)")
    args = ap.parse_args(argv)

    # The pp mesh needs --stages host devices; set the flag BEFORE the
    # first jax import in this process.
    os.environ["XLA_FLAGS"] = (
        os.environ.get("XLA_FLAGS", "")
        + f" --xla_force_host_platform_device_count={max(args.stages, 1)}"
    ).strip()
    import jax

    jax.config.update("jax_platforms", "cpu")
    import jax.numpy as jnp

    sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
    from tools.presets import PRESETS
    from torchgpipe_tpu import tune
    from torchgpipe_tpu.models.transformer import (
        TransformerConfig,
        chunked_lm_loss,
        cross_entropy,
        llama_spmd,
    )
    from torchgpipe_tpu.spmd import SpmdGPipe, make_mesh

    if args.preset not in PRESETS:
        print(f"unknown preset {args.preset!r}; known: {sorted(PRESETS)}",
              file=sys.stderr)
        return 2
    dim, n_layers, n_heads, n_kv, vocab, mlp_ratio = PRESETS[args.preset]
    cfg = TransformerConfig(
        vocab=vocab, dim=dim, n_layers=n_layers, n_heads=n_heads,
        n_kv_heads=n_kv, mlp_ratio=mlp_ratio,
        dtype=jnp.bfloat16 if args.bf16 else jnp.float32,
    )
    block, pre, post = llama_spmd(cfg, args.stages)
    mesh = make_mesh(args.stages, 1)
    if args.fused_ce:
        loss_fn, post = chunked_lm_loss(cfg), None
    else:
        def loss_fn(out: jnp.ndarray, tok: jnp.ndarray) -> jnp.ndarray:
            return cross_entropy(out, tok)

    pipe = SpmdGPipe(
        block, args.stages, mesh, chunks=4, loss_fn=loss_fn,
        pre=pre, post=post, checkpoint="always",
    )
    x = jax.ShapeDtypeStruct((args.batch, args.seq), jnp.int32)
    chunks_options = (
        tuple(int(c) for c in args.chunks.split(","))
        if args.chunks
        else None
    )
    report = tune.tune_step(
        pipe, x, hbm_budget_bytes=int(args.budget_gib * 2 ** 30),
        chunks_options=chunks_options,
    )
    print(
        f"# tune_report: preset={args.preset} seq={args.seq} "
        f"batch={args.batch} stages={args.stages} "
        f"budget={args.budget_gib} GiB"
    )
    print(report.table())
    best = report.best
    if best is None:
        print("\nNO feasible candidate under the budget", file=sys.stderr)
        return 1
    print(
        f"\nbest: checkpoint={best.checkpoint!r} policy={best.policy or '-'} "
        f"chunks={best.chunks}"
        + (f" ce_chunk={best.ce_chunk}" if best.ce_chunk else "")
    )
    return 0


if __name__ == "__main__":
    raise SystemExit(main(sys.argv[1:]))
