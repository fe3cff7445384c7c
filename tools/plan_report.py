#!/usr/bin/env python
"""Certified-plan frontier for the joint static planner
(torchgpipe_tpu.analysis.planner).

Searches balance × schedule × chunks × remat for a llama pipeline preset
and prints the certified frontier — no accelerator is touched (traced
jaxprs + ``eval_shape`` + pure-Python event graphs on the host CPU
mesh), so the table is printable on any machine::

    python tools/plan_report.py --preset 1b --seq 4096 --stages 4 \
        --batch 8 --budget-gib 15.75

Exit codes: 0 — a certified plan fits the budget; 1 — NO candidate fits
the HBM budget (or the top plan fails re-verification); 2 — bad usage.

``--verify`` re-runs the event-graph verifier (ordering + donation +
engine equivalence) on the top plan after the search — the belt-and-
braces check the ``plan-verify`` CI step runs; ``--ci`` loops the fast
llama presets (tiny, small) with --verify, which is what
``tools/ci_lint.py`` invokes.  See docs/analysis.md (planner section)
and docs/tuning.md.

``--cost-model IN.json`` is the replan half of the profile-guided loop:
load a measured cost model persisted by ``tools/trace_report.py
--cost-model`` and re-rank with MEASURED per-cell pricing
(``planner.plan(cost_model=...)``).  The pipe is rebuilt to the tiny
MPMD shape the trace tool measures (override with ``--mpmd-schedule`` /
``--mpmd-chunks`` / ``--mpmd-stages``); a cost model whose fingerprint
does not match that configuration is STALE and exits 1 — re-measure
rather than rank on a profile of a different plan.
"""

from __future__ import annotations

import argparse
import os
import sys
from typing import Optional, Sequence

# CI presets: small shapes whose whole search runs in seconds on a host.
_CI_PRESETS = (
    ("tiny", 128, 8),
    ("small", 128, 4),
)


def _plan_one(
    preset: str,
    seq: int,
    stages: int,
    batch: int,
    budget_gib: float,
    chunks: Optional[str],
    bf16: bool,
    verify: bool,
    quiet: bool = False,
) -> int:
    import jax
    import jax.numpy as jnp

    from tools.presets import PRESETS
    from torchgpipe_tpu.analysis import planner
    from torchgpipe_tpu.models.transformer import (
        TransformerConfig,
        cross_entropy,
        llama_spmd,
    )
    from torchgpipe_tpu.spmd import SpmdGPipe, make_mesh

    if preset not in PRESETS:
        print(f"unknown preset {preset!r}; known: {sorted(PRESETS)}",
              file=sys.stderr)
        return 2
    dim, n_layers, n_heads, n_kv, vocab, mlp_ratio = PRESETS[preset]
    cfg = TransformerConfig(
        vocab=vocab, dim=dim, n_layers=n_layers, n_heads=n_heads,
        n_kv_heads=n_kv, mlp_ratio=mlp_ratio,
        dtype=jnp.bfloat16 if bf16 else jnp.float32,
    )
    block, pre, post = llama_spmd(cfg, stages)
    mesh = make_mesh(stages, 1)

    def loss_fn(out: jnp.ndarray, tok: jnp.ndarray) -> jnp.ndarray:
        return cross_entropy(out, tok)

    pipe = SpmdGPipe(
        block, stages, mesh, chunks=4, loss_fn=loss_fn,
        pre=pre, post=post, checkpoint="always",
    )
    x = jax.ShapeDtypeStruct((batch, seq), jnp.int32)
    chunks_options = (
        tuple(int(c) for c in chunks.split(",")) if chunks else None
    )
    budget = int(budget_gib * 2 ** 30)
    report = planner.plan(
        pipe, x, hbm_budget_bytes=budget, chunks_options=chunks_options,
    )
    print(
        f"# plan_report: preset={preset} seq={seq} batch={batch} "
        f"stages={stages} budget={budget_gib} GiB"
    )
    if not quiet:
        print(report.table())
    best = report.best
    if best is None:
        print("\nNO certified candidate fits the HBM budget",
              file=sys.stderr)
        return 1
    print(
        f"best: schedule={best.schedule!r} checkpoint={best.checkpoint!r} "
        f"policy={best.policy or '-'} chunks={best.chunks} "
        f"mfu~{best.predicted_mfu:.4f} "
        f"hwm={best.hwm_bytes / 2 ** 30:.2f} GiB"
    )
    if verify:
        findings = planner.verify_plan(pipe, best)
        if findings:
            from torchgpipe_tpu.analysis import format_findings

            print(format_findings(findings), file=sys.stderr)
            return 1
        print("plan-verify: top plan clean "
              "(ordering + donation + equivalence)")
    return 0


def _plan_with_cost_model(
    path: str, schedule: str, chunks: int, stages: int, budget_gib: float,
) -> int:
    """Re-rank the tiny MPMD pipe with a persisted measured cost model
    (module docstring).  Exit 1 on a stale fingerprint."""
    import jax

    jax.config.update("jax_platforms", "cpu")

    from tools.trace_report import build_tiny
    from torchgpipe_tpu.analysis import planner
    from torchgpipe_tpu.obs.costmodel import CostModel

    cm = CostModel.load(path)
    pipe, x, _tracer = build_tiny(schedule, chunks, stages)
    stale = cm.stale_reason(pipe)
    if stale is not None:
        print(
            f"cost model {path} is STALE for this configuration "
            f"({stale}); re-measure with tools/trace_report.py "
            "--cost-model, or match --mpmd-schedule/--mpmd-chunks/"
            "--mpmd-stages to the measured run",
            file=sys.stderr,
        )
        return 1
    spec = jax.tree_util.tree_map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype), x
    )
    budget = int(budget_gib * 2 ** 30)
    report = planner.plan(
        pipe, spec, hbm_budget_bytes=budget, cost_model=cm,
        balance_options=[pipe.balance],
    )
    print(f"# plan_report: measured cost model {path}")
    print(cm.describe())
    print(report.table())
    best = report.best
    if best is None:
        print("\nNO certified candidate fits the HBM budget",
              file=sys.stderr)
        return 1
    print(
        f"best: schedule={best.schedule!r} checkpoint={best.checkpoint!r} "
        f"chunks={best.chunks} priced_by={best.priced_by} "
        f"mfu~{best.predicted_mfu:.4f}"
        + (
            f" measured-span={best.makespan_measured * 1e3:.2f}ms"
            if best.makespan_measured is not None else ""
        )
    )
    return 0


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
    ap.add_argument("--bf16", action=argparse.BooleanOptionalAction,
                    default=True)
    ap.add_argument("--verify", action="store_true",
                    help="re-run the event-graph verifier on the top plan")
    ap.add_argument("--ci", action="store_true",
                    help="plan-verify gate: search + verify the fast llama "
                         "presets (tiny, small) and exit non-zero on any "
                         "failure")
    ap.add_argument("--cost-model", metavar="IN.json",
                    help="re-rank with a measured cost model persisted "
                         "by tools/trace_report.py --cost-model (exit 1 "
                         "on a stale fingerprint)")
    ap.add_argument("--mpmd-schedule", choices=("gpipe", "1f1b"),
                    default="gpipe",
                    help="--cost-model pipe: schedule of the measured "
                         "tiny MPMD run")
    ap.add_argument("--mpmd-chunks", type=int, default=4,
                    help="--cost-model pipe: chunks of the measured run")
    ap.add_argument("--mpmd-stages", type=int, default=2,
                    help="--cost-model pipe: stages of the measured run")
    args = ap.parse_args(argv)

    if args.cost_model:
        sys.path.insert(
            0,
            os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        )
        return _plan_with_cost_model(
            args.cost_model, args.mpmd_schedule, args.mpmd_chunks,
            args.mpmd_stages, args.budget_gib,
        )

    # The pp mesh needs --stages host devices; set the flag BEFORE the
    # first jax import in this process.
    os.environ["XLA_FLAGS"] = (
        os.environ.get("XLA_FLAGS", "")
        + f" --xla_force_host_platform_device_count={max(args.stages, 1)}"
    ).strip()
    import jax

    jax.config.update("jax_platforms", "cpu")
    sys.path.insert(
        0, os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    )

    if args.ci:
        rc = 0
        for preset, seq, batch in _CI_PRESETS:
            rc = max(rc, _plan_one(
                preset, seq, args.stages, batch, args.budget_gib,
                None, args.bf16, verify=True, quiet=True,
            ))
        return rc
    return _plan_one(
        args.preset, args.seq, args.stages, args.batch, args.budget_gib,
        args.chunks, args.bf16, verify=args.verify,
    )


if __name__ == "__main__":
    raise SystemExit(main(sys.argv[1:]))
