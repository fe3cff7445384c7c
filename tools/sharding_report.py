#!/usr/bin/env python
"""Static 3D-layout report + the ``sharding-verify`` CI gate
(torchgpipe_tpu.analysis.sharding).

Resolves a llama preset's param layout through the unified
partition-rule layer, verifies it statically (rule coverage, mesh
validity, propagation — no device probes), runs the 3D planner over a
small (dp, tp) width grid, re-verifies the TOP plan's layout at its
widths, and re-verifies the top ZeRO-3 (fully-sharded, gather-at-use)
plan — its fsdp layout must certify at the plan's widths and a
re-planned singleton must reproduce the certified per-rank HWM::

    python tools/sharding_report.py --preset tiny --stages 4 --batch 8

Exit codes: 0 — the layout, the top 3D plan and the top ZeRO-3 plan
verify clean; 1 — an unmatched param leaf, a mesh-axis mismatch, an
implicit reshard, a per-device memory overrun (no certified candidate
fits the budget), an uncertified ZeRO-3 plan, or ZeRO-3
memory-certification drift; 2 — bad usage.

``--ci`` loops the fast llama presets (tiny, small) — the
``sharding-verify`` step in ``tools/ci_lint.py``, mirroring the
``plan-verify`` gate's shape.  See docs/analysis.md (sharding section).
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


def _report_one(
    preset: str,
    seq: int,
    stages: int,
    batch: int,
    budget_gib: float,
    mesh_options: Sequence[Sequence[int]],
    bf16: bool,
    quiet: bool = False,
) -> int:
    import jax
    import jax.numpy as jnp

    from tools.presets import PRESETS
    from torchgpipe_tpu.analysis import planner, sharding
    from torchgpipe_tpu.analysis.diagnostics import Severity, format_findings
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
        pre=pre, post=post, checkpoint="always", dp_axis="dp",
    )
    x = jax.ShapeDtypeStruct((batch, seq), jnp.int32)
    print(
        f"# sharding_report: preset={preset} seq={seq} batch={batch} "
        f"stages={stages} budget={budget_gib} GiB "
        f"widths={list(map(tuple, mesh_options))}"
    )

    # 1. The pipe's OWN layout must verify clean (rule coverage, mesh
    # validity, no implicit reshard in the propagated block).
    report = sharding.verify_layout(pipe, x)
    if not quiet:
        print(report.table.describe())
        print(
            f"layout: {len(report.table)} rule(s), per-device param "
            f"bytes {report.param_bytes_local / 2 ** 20:.1f} MiB, "
            f"priced comm {report.comm_bytes():.0f} B/cell, "
            f"propagated={report.propagated}"
        )
    errors = [
        f for f in report.findings if f.severity >= Severity.ERROR
    ]
    if errors or report.reshards():
        print(format_findings(report.findings), file=sys.stderr)
        print("\nlayout verification FAILED", file=sys.stderr)
        return 1

    # 2. The 3D planner over the width grid; the top plan must exist
    # (memory under budget) and re-verify at its widths.
    budget = int(budget_gib * 2 ** 30)
    # ONE search covers both gates: the top-3D-plan check (step 2) and
    # the ZeRO-3 certification (step 3) — the explicit level space
    # (0, 1, 3) adds the fully-sharded candidates to the same frontier
    # at a fraction of a second search's cost (traces are shared).
    plan_report = planner.plan(
        pipe, x, hbm_budget_bytes=budget,
        mesh_options=mesh_options, megastep_options=(1,),
        zero_options=(0, 1, 3),
    )
    best = plan_report.best
    if best is None:
        print("\nNO certified 3D candidate fits the HBM budget "
              "(per-device memory overrun)", file=sys.stderr)
        return 1
    print(
        f"top 3D plan: schedule={best.schedule!r} "
        f"checkpoint={best.checkpoint!r} m={best.chunks} "
        f"dpxtp={best.dp}x{best.tp} zero={best.zero} "
        f"opt-state={best.opt_state_bytes / 2 ** 20:.1f} MiB "
        f"hwm={best.hwm_bytes / 2 ** 30:.2f} GiB"
    )
    # Re-verify the winner's layout AT ITS WIDTHS (candidate meshes are
    # abstract, so this needs no extra devices); when the winner keeps
    # the pipe's own widths, the full event-graph verifier runs too.
    own_dp = pipe.mesh.shape[pipe.dp_axis] if pipe.dp_axis else 1
    own_tp = pipe.mesh.shape[pipe.tp_axis] if pipe.tp_axis else 1
    findings = list(sharding.verify_layout(
        pipe, x, mesh_sizes={
            (pipe.dp_axis or "dp"): best.dp,
            (pipe.tp_axis or "tp"): best.tp,
        },
    ).findings)
    if (best.dp, best.tp) == (own_dp, own_tp):
        findings.extend(planner.verify_plan(pipe, best, batch=x))
    errors = [f for f in findings if f.severity >= Severity.ERROR]
    if errors:
        print(format_findings(findings), file=sys.stderr)
        return 1
    print("sharding-verify: top 3D plan clean "
          "(rule coverage + mesh validity + memory)")

    # 3. The fully-sharded frontier: the top ZeRO-3 plan must certify,
    # its fsdp (gather-at-use) layout must re-verify at the plan's
    # widths, and a re-planned singleton at its exact coordinates must
    # reproduce the certified per-rank HWM — memory-certification
    # DRIFT, or an uncertified applied plan, fails the gate.
    import dataclasses as dc

    best3 = next(
        (p for p in plan_report.candidates
         if p.zero == 3 and p.certified and p.feasible),
        None,
    )
    if best3 is None:
        reasons = sorted({
            p.reason for p in plan_report.candidates if p.zero == 3
        })
        print("\nNO certified ZeRO-3 candidate "
              f"(reject reasons: {reasons[:3]})", file=sys.stderr)
        return 1
    layout3 = sharding.verify_layout(
        dc.replace(pipe, fsdp=True, zero_update=3), x,
        mesh_sizes={
            (pipe.dp_axis or "dp"): best3.dp,
            (pipe.tp_axis or "tp"): best3.tp,
        },
    )
    errors = [
        f for f in layout3.findings if f.severity >= Severity.ERROR
    ]
    if errors or layout3.reshards():
        print(format_findings(layout3.findings), file=sys.stderr)
        print("\nZeRO-3 layout verification FAILED", file=sys.stderr)
        return 1
    redo = planner.plan(
        pipe, x, hbm_budget_bytes=budget,
        mesh_options=[(best3.dp, best3.tp)],
        schedules=[best3.schedule], chunks_options=[best3.chunks],
        megastep_options=(1,), zero_options=(3,),
    )
    twin = next(
        (p for p in redo.candidates
         if p.zero == 3 and p.checkpoint == best3.checkpoint
         and p.policy == best3.policy
         and p.scan_unroll == best3.scan_unroll),
        None,
    )
    if (
        twin is None or not (twin.certified and twin.feasible)
        or twin.hwm_bytes != best3.hwm_bytes
    ):
        print(
            "\nZeRO-3 memory-certification DRIFT: the re-planned "
            f"candidate reads {getattr(twin, 'hwm_bytes', None)} bytes "
            f"vs the frontier's {best3.hwm_bytes}", file=sys.stderr,
        )
        return 1
    print(
        f"sharding-verify: top ZeRO-3 plan certified "
        f"(dpxtp={best3.dp}x{best3.tp} "
        f"hwm={best3.hwm_bytes / 2 ** 30:.2f} GiB, gathered window "
        f"{layout3.gathered_window_bytes / 2 ** 20:.1f} MiB, "
        f"{len(layout3.gather_paths)} gather-at-use leaves)"
    )
    return 0


def main(argv: Sequence[str] | None = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--preset", default="tiny",
                    help="llama_speed preset (tiny|small|1b|llama3-8b)")
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--stages", type=int, default=4)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--budget-gib", type=float, default=15.75,
                    help="per-chip HBM budget (default: the v5e AOT limit)")
    ap.add_argument("--widths", default="1,1;2,1",
                    help="semicolon-separated dp,tp width pairs for the "
                         "3D search (default '1,1;2,1')")
    ap.add_argument("--bf16", action=argparse.BooleanOptionalAction,
                    default=True)
    ap.add_argument("--ci", action="store_true",
                    help="sharding-verify gate: verify the fast llama "
                         "presets (tiny, small) and exit non-zero on any "
                         "failure")
    args = ap.parse_args(argv)

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

    mesh_options = [
        tuple(int(w) for w in pair.split(","))
        for pair in args.widths.split(";")
        if pair.strip()
    ]
    if args.ci:
        rc = 0
        for preset, seq, batch in _CI_PRESETS:
            rc = max(rc, _report_one(
                preset, seq, args.stages, batch, args.budget_gib,
                mesh_options, args.bf16, quiet=True,
            ))
        return rc
    return _report_one(
        args.preset, args.seq, args.stages, args.batch, args.budget_gib,
        mesh_options, args.bf16,
    )


if __name__ == "__main__":
    raise SystemExit(main(sys.argv[1:]))
