"""Predicted-vs-measured rank-order validation of the static planner.

The planner (:mod:`torchgpipe_tpu.analysis.planner`) promises its
predicted-MFU RANKING is trustworthy without ever timing a device.  This
rung closes the loop on hardware anyone has: on the CPU tiny-llama
preset it builds the three checkpoint-mode candidates whose measured
step time differs by REAL work (recompute — ``never`` replays nothing,
``except_last`` replays ``m-1`` of ``m`` micro-batches, ``always`` all
of them; at ``chunks=2`` the expected time ratios are 1 : 1.17 : 1.33,
far above CPU timing noise), measures each with blocking steps, and
checks that the measured fastest-to-slowest order matches the planner's
predicted best-to-worst order.

Schedule-bubble predictions are deliberately NOT validated here: a
single CPU host serializes the per-cell schedule, so bubble structure
never reaches the wall clock — only total executed work does.  The
recompute axis is exactly that.

**Profile-guided extension** (the observe → replan loop's gate): one of
the rungs is ALSO traced with a ``sync=True`` timeline, reconciled, and
distilled into a measured :class:`~torchgpipe_tpu.obs.costmodel.
CostModel`; the planner then re-ranks the same candidates with
``cost_model=`` and BOTH rankings are scored against the measured step
times by pairwise rank agreement (Kendall concordance: the fraction of
candidate pairs ordered the same way).  The gate requires the
measured-cost ranking to agree at least as well as the analytic one —
feeding the planner real measurements must never make its ranking
worse.

**ZeRO rung** (the fully-sharded planner axis's gate): the tiny-llama
SPMD pipe on a pp=2 × dp=2 CPU mesh is stepped replicated and fully
sharded (``zero=3`` — params/grads/state stored at the fsdp layout,
gathered at use) from MATCHED params.  The gate is BITWISE-equal loss
at the matched params (the fsdp forward gathers exact copies, so the
first step's loss must be bit-identical; later steps drift at ULP
through psum-vs-reduce-scatter summation order and are only checked
finite).  The record reports the certifier's resident-bytes delta
(replicated param bytes vs the sharded residents, window beside it)
next to the measured wall ratio — BENCH_NOTES carries both.

Emits one JSON line (the bench contract) and exits non-zero on a rank
mismatch, an agreement regression, or a ZeRO gate failure::

    env JAX_PLATFORMS=cpu python bench.py --plan-validate
"""

from __future__ import annotations

import json
import time
from typing import Any, Dict, List, Tuple

# The validated axis: checkpoint modes at chunks=2 (work ratios
# 1 : 7/6 : 4/3 — every adjacent gap is >= 14%).
MODES = ("never", "except_last", "always")
CHUNKS = 2


def _build(mode: str, tracer: Any = None) -> Tuple[Any, Any, Any]:
    import jax
    import jax.numpy as jnp

    from benchmarks.llama_speed import PRESETS
    from torchgpipe_tpu.gpipe import GPipe
    from torchgpipe_tpu.models.transformer import TransformerConfig, llama

    dim, n_layers, n_heads, n_kv, vocab, mlp_ratio = PRESETS["tiny"]
    cfg = TransformerConfig(
        vocab=vocab, dim=dim, n_layers=n_layers, n_heads=n_heads,
        n_kv_heads=n_kv, mlp_ratio=mlp_ratio,
    )
    layers = llama(cfg)
    n_stages = 2
    base, rem = len(layers) // n_stages, len(layers) % n_stages
    balance = [
        base + (1 if j >= n_stages - rem else 0) for j in range(n_stages)
    ]
    model = GPipe(layers, balance=balance, chunks=CHUNKS, checkpoint=mode,
                  tracer=tracer)
    x = jnp.zeros((8, 128), jnp.int32)
    return model, x, cfg


def _timed_step(model: Any, x: Any) -> Any:
    """Warm up (compile) and return ``run(i) -> seconds`` for one
    blocking training step of this model."""
    import jax

    from torchgpipe_tpu.models.transformer import cross_entropy

    def loss_fn(out: Any, tok: Any) -> Any:
        return cross_entropy(out[:, :-1, :], tok[:, 1:])

    in_spec = jax.ShapeDtypeStruct(x.shape, x.dtype)
    params, state = model.init(jax.random.PRNGKey(0), in_spec)
    rng = jax.random.PRNGKey(1)
    loss, grads, state, _ = model.value_and_grad(
        params, state, x, x, loss_fn, rng=rng
    )
    jax.block_until_ready((loss, grads))

    def run(i: int) -> float:
        t0 = time.perf_counter()
        loss, grads, _, _ = model.value_and_grad(
            params, state, x, x, loss_fn, rng=jax.random.fold_in(rng, i)
        )
        jax.block_until_ready((loss, grads))
        return time.perf_counter() - t0

    return run


def _measure(model: Any, x: Any, steps: int = 5) -> float:
    """Median per-step seconds with per-step blocking (no async loop can
    over-report) after one compile warmup."""
    run = _timed_step(model, x)
    times: List[float] = [run(i) for i in range(steps)]
    times.sort()
    return times[len(times) // 2]


def _measure_paired(steps: int = 7) -> Dict[str, float]:
    """Per-mode median step seconds over PAIRED rounds: all modes warm
    up first, then each round times one step of every mode
    back-to-back.  Host-load drift over the ~minute of measurement then
    shifts every mode's round together instead of penalizing whichever
    mode ran during the slow window — the flightrec-overhead rung's
    paired-rounds treatment (its unpaired medians drifted ±4-5% on the
    CI host, which is MORE than the ~17% never→except_last work gap
    divided across a ~40% fixed-overhead floor)."""
    runners = {}
    for mode in MODES:
        model, x, _ = _build(mode)
        runners[mode] = _timed_step(model, x)
    times: Dict[str, List[float]] = {m: [] for m in MODES}
    for i in range(steps):
        for mode in MODES:
            times[mode].append(runners[mode](i))
    out = {}
    for mode, ts in times.items():
        ts.sort()
        out[mode] = ts[len(ts) // 2]
    return out


def _rank_agreement(
    order: List[str], measured_times: Dict[str, float]
) -> float:
    """Pairwise (Kendall) concordance of a predicted best-to-worst
    ``order`` against measured step times: the fraction of candidate
    pairs the prediction orders the same way the clock does (1.0 =
    identical ranking)."""
    import itertools

    pairs = list(itertools.combinations(order, 2))
    ok = sum(
        1 for a, b in pairs if measured_times[a] <= measured_times[b]
    )
    return ok / len(pairs)


def _distill_cost_model(steps: int) -> Any:
    """Trace the MODES[0] rung with a sync=True timeline and distill
    the measured reconciliation into a CostModel (warm-up excluded —
    compile time must not contaminate the medians)."""
    from torchgpipe_tpu import obs
    from torchgpipe_tpu.analysis.events import events_for
    from torchgpipe_tpu.utils.tracing import Timeline

    tracer = Timeline(sync=True)
    model, x, _ = _build(MODES[0], tracer=tracer)
    run = _timed_step(model, x)  # warm-up compile happens here
    tracer.reset()  # drop the compile-contaminated warm-up spans
    for i in range(steps):
        run(i)
    report = obs.reconcile(tracer, events_for(model))
    return report.cost_model(model)


def _zero3_rung(steps: int = 5) -> Dict[str, Any]:
    """Replicated vs fully-sharded (``zero=3``) measured step time at
    MATCHED params on the pp=2 × dp=2 CPU mesh (module docstring, ZeRO
    rung).  Returns the rung's record; ``{"skipped": ...}`` when the
    host exposes fewer than 4 devices."""
    import dataclasses as dc

    import jax
    import numpy as np
    import optax

    from benchmarks.llama_speed import PRESETS
    from torchgpipe_tpu.analysis import sharding as shd
    from torchgpipe_tpu.models.transformer import (
        TransformerConfig, cross_entropy, llama_spmd,
    )
    from torchgpipe_tpu.spmd import SpmdGPipe, make_mesh

    if len(jax.devices()) < 4:
        return {"skipped": "needs >= 4 host devices (pp=2 x dp=2)"}
    dim, n_layers, n_heads, n_kv, vocab, mlp_ratio = PRESETS["tiny"]
    cfg = TransformerConfig(
        vocab=vocab, dim=dim, n_layers=n_layers, n_heads=n_heads,
        n_kv_heads=n_kv, mlp_ratio=mlp_ratio,
    )
    block, pre, post = llama_spmd(cfg, 2)
    mesh = make_mesh(2, 2)

    def loss_fn(out: Any, tok: Any) -> Any:
        return cross_entropy(out[:, :-1, :], tok[:, 1:])

    rep = SpmdGPipe(block, 2, mesh, chunks=CHUNKS, loss_fn=loss_fn,
                    pre=pre, post=post, dp_axis="dp")
    shp = dc.replace(rep, fsdp=True, zero_update=3)
    x = jax.random.randint(jax.random.PRNGKey(1), (8, 128), 0, vocab)
    spec = jax.ShapeDtypeStruct(x.shape, x.dtype)
    host = rep.init(jax.random.PRNGKey(0), spec)
    opt = optax.adamw(1e-3)
    tmap = jax.tree_util.tree_map

    runners: Dict[str, Any] = {}
    first_losses: Dict[str, Any] = {}
    for name, pipe, zero in (("replicated", rep, 0), ("zero3", shp, 3)):
        params = pipe.place(tmap(np.asarray, host))
        step = pipe.make_train_step(opt, donate=False, zero=zero)
        state = pipe.zero_opt_state(opt, params, zero=zero)
        # Compile + the matched-params step whose loss the gate pins.
        loss, params, state = step(params, state, x, x)
        first_losses[name] = np.asarray(jax.block_until_ready(loss))

        def run_one(
            i: int, _step: Any = step, _box: List[Any] = [params, state]
        ) -> Tuple[float, float]:
            t0 = time.perf_counter()
            loss, _box[0], _box[1] = _step(_box[0], _box[1], x, x)
            jax.block_until_ready(loss)
            return time.perf_counter() - t0, float(loss)

        runners[name] = run_one
    bitwise = bool(np.array_equal(
        first_losses["replicated"], first_losses["zero3"]
    ))
    # Paired rounds (the _measure_paired treatment): host-load drift
    # shifts both variants' round together.
    times: Dict[str, List[float]] = {n: [] for n in runners}
    finite = True
    for i in range(steps):
        for name, run_one in runners.items():
            dt, lv = run_one(i)
            times[name].append(dt)
            finite = finite and bool(np.isfinite(lv))
    med: Dict[str, float] = {}
    for name, ts in times.items():
        ts.sort()
        med[name] = ts[len(ts) // 2]
    # The certifier's resident-bytes story, reported beside the wall
    # ratio: replicated residents vs sharded residents (+ the transient
    # gathered window the memory certification charges).
    lay_r = shd.verify_layout(rep, spec)
    lay_s = shd.verify_layout(shp, spec)
    return {
        "bitwise_matched_loss": bitwise,
        "finite": finite,
        "step_s": {n: round(t, 4) for n, t in med.items()},
        "wall_ratio_zero3_over_replicated": round(
            med["zero3"] / med["replicated"], 3
        ),
        "resident_param_bytes": {
            "replicated": int(lay_r.param_bytes_local),
            "zero3_sharded": int(lay_s.param_bytes_local),
            "zero3_gathered_window": int(lay_s.gathered_window_bytes),
        },
        "resident_bytes_delta": int(
            lay_r.param_bytes_local - lay_s.param_bytes_local
        ),
    }


def run(steps: int = 5) -> Dict[str, Any]:
    """Plan, measure, compare.  Returns the result record (bench JSON)."""
    import jax

    from torchgpipe_tpu.analysis import planner

    model0, x, _ = _build(MODES[0])
    spec = jax.ShapeDtypeStruct(x.shape, x.dtype)
    options = {
        "chunks_options": (CHUNKS,),
        "balance_options": [model0.balance],
    }
    report = planner.plan(
        model0, spec, hbm_budget_bytes=64 * 2 ** 30, **options
    )

    def scored_of(rep: Any) -> Dict[str, Any]:
        out = {
            p.checkpoint: p for p in rep.candidates
            if p.schedule == "gpipe" and p.checkpoint in MODES
            and p.predicted_mfu is not None
        }
        missing = [m for m in MODES if m not in out]
        if missing:
            raise RuntimeError(
                f"planner scored no candidate for {missing}"
            )
        return out

    scored = scored_of(report)
    predicted = sorted(
        MODES, key=lambda m: -(scored[m].predicted_mfu or 0.0)
    )
    measured_times = _measure_paired(steps=max(steps, 7))
    measured = sorted(MODES, key=lambda m: measured_times[m])
    match = predicted == measured

    # Profile-guided half: re-rank the same candidates with a cost
    # model distilled from a traced run of the MODES[0] rung; the
    # measured ranking's pairwise agreement with the clock must not be
    # worse than the analytic ranking's (module docstring).
    cm = _distill_cost_model(steps=3)
    report_m = planner.plan(
        model0, spec, hbm_budget_bytes=64 * 2 ** 30, cost_model=cm,
        **options,
    )
    scored_m = scored_of(report_m)
    predicted_m = sorted(
        MODES, key=lambda m: -(scored_m[m].predicted_mfu or 0.0)
    )
    agree_analytic = _rank_agreement(predicted, measured_times)
    agree_measured = _rank_agreement(predicted_m, measured_times)
    no_regression = agree_measured >= agree_analytic
    priced_by = {m: scored_m[m].priced_by for m in MODES}
    zero3 = _zero3_rung(steps=steps)
    zero3_ok = (
        "skipped" in zero3
        or (zero3["bitwise_matched_loss"] and zero3["finite"])
    )
    ok = match and no_regression and zero3_ok
    return {
        "metric": "plan-validate rank-order [tiny llama, cpu]",
        "value": 1.0 if ok else 0.0,
        "unit": "match",
        "platform": "cpu",
        "validated": True,  # per-step blocking cannot over-report
        "match": match,
        "predicted_order": predicted,
        "measured_order": measured,
        "predicted_mfu": {
            m: round(scored[m].predicted_mfu or 0.0, 4) for m in MODES
        },
        "measured_step_s": {
            m: round(measured_times[m], 4) for m in MODES
        },
        "measured_cost_order": predicted_m,
        "measured_cost_mfu": {
            m: round(scored_m[m].predicted_mfu or 0.0, 4) for m in MODES
        },
        "priced_by": priced_by,
        "rank_agreement_analytic": round(agree_analytic, 4),
        "rank_agreement_measured": round(agree_measured, 4),
        "measured_not_worse": no_regression,
        "zero3": zero3,
    }


def main() -> int:
    import os
    import sys

    # The ZeRO rung needs a pp=2 x dp=2 host mesh; the flag only works
    # BEFORE the first jax import in this process (the rung degrades to
    # a skip note otherwise).
    if "jax" not in sys.modules:
        os.environ["XLA_FLAGS"] = (
            os.environ.get("XLA_FLAGS", "")
            + " --xla_force_host_platform_device_count=8"
        ).strip()
    import jax

    jax.config.update("jax_platforms", "cpu")
    result = run()
    print(json.dumps(result), flush=True)
    return 0 if result["value"] == 1.0 else 1


if __name__ == "__main__":
    from torchgpipe_tpu.utils.compile_cache import enable_compile_cache

    enable_compile_cache()
    raise SystemExit(main())
