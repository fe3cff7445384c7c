"""Fleet rung: a seeded synthetic trace through router + reuse + spec.

The fleet claims — prefix-reuse hit-rate → TTFT drop, speculation
acceptance → TPOT drop, failover that loses nothing — are MEASURED
here, on the production-shaped load :mod:`torchgpipe_tpu.fleet.trace`
generates (ragged lengths, bursty MMPP arrivals, Zipf-skewed
shared-prefix tenants, seeded), never asserted from a hand-picked
burst.  Four rungs serve the SAME trace:

* ``baseline``  — router over 2 plain engines (power-of-two-choices);
* ``prefix``    — 2 ``RadixPrefixCache``-backed replicas;
* ``spec``      — 2 ``SpeculativeEngine`` replicas (trained draft);
* ``failover``  — the baseline fleet with replica r0 killed mid-trace
  (``faults.inject(die_at_step=...)``).

A fifth **telemetry-overhead** rung then gates the request-tracing +
SLO layer (``obs.reqtrace``/``obs.slo``): ONE pre-warmed instrumented
fleet replays the SAME trace with its telemetry toggled ON (per-replica
flight recorders recording rid-threaded request spans + a ticking
``SloMonitor``) and OFF (the production off-switch: the attributes set
to None), in order-alternated gc-hygienic rounds, and the ratio of
median times must stay under the repo's established <2% telemetry
gate, outputs bitwise-identical.  Toggling one fleet rather than
comparing two separately built ones is deliberate: fleet-object
identity (allocator layout, history) measured 2-8% of noise on CPU —
far above the real per-event cost (see BENCH_NOTES round 19).

Measurement contract:

* **Exactness is the hard gate** — all four rungs must emit BITWISE
  identical per-request token streams (greedy decode is replica- and
  path-independent); any divergence exits non-zero, no numbers
  published.
* **No silent caps** — the trace generator's honesty counters
  (``skipped_too_long``, per-tenant counts, shareable fraction) are
  part of the published line; a run that dropped trace segments says
  so in the same JSON object as its wins.
* **Predictable-text regime, declared** — target AND draft are trained
  on the mod-vocab ring task (the ``examples/serve.py`` corpus), and
  trace prompts are mapped onto ring windows (tenant prefixes stay
  shared, suffix starts stay random) so the draft has real signal;
  acceptance is genuinely measured, not forced.  Random-prompt
  acceptance would be ~0 for any small draft — speculation's wins are
  a property of predictable text, and the bench says which regime it
  measures.
* **Latency inside the timed region** — TTFT/TPOT come from the shared
  :class:`~torchgpipe_tpu.serving.metrics.ServingMetrics` (one
  instance across both replicas), whose clocks tick at token-emission
  time; the engine host-fetches every token (streaming), so laziness
  cannot fake a timing.

Usage::

    env JAX_PLATFORMS=cpu python -m benchmarks.fleet_trace
    env JAX_PLATFORMS=cpu python bench.py --fleet      # one JSON line
"""

from __future__ import annotations

import argparse
import contextlib
import json
import os
import time
from typing import Dict, List, Tuple

import jax
import jax.numpy as jnp
import numpy as np

if os.environ.get("JAX_PLATFORMS", "").strip().lower() == "cpu":
    jax.config.update("jax_platforms", "cpu")

from torchgpipe_tpu import GPipe, fleet
from torchgpipe_tpu.models import mpmd_params_for_generation
from torchgpipe_tpu.models.transformer import (
    TransformerConfig,
    cross_entropy,
    llama,
)
from torchgpipe_tpu.resilience import faults
from torchgpipe_tpu.serving import Engine, ServingMetrics
from torchgpipe_tpu.serving.engine import Engine as _Engine

VOCAB = 64


def _train(cfg: TransformerConfig, balance: List[int],
           seed: int, steps: int):
    """Train one llama on the mod-vocab ring (the serve-example task):
    rows start every 4 tokens so the batch covers every v -> v+1
    transition — completions become predictable, which is the regime
    speculation exists for."""
    model = GPipe(llama(cfg), balance=balance, chunks=2)
    b, s = 8, 16
    data = jnp.mod(
        jnp.arange(s + 1)[None, :] + (4 * jnp.arange(b))[:, None], VOCAB
    )
    x, y = data[:, :-1], data[:, 1:]
    params, state = model.init(
        jax.random.PRNGKey(seed),
        jax.ShapeDtypeStruct(x.shape, x.dtype),
    )
    loss = None
    for _ in range(steps):
        loss, grads, state, _ = model.value_and_grad(
            params, state, x, y, cross_entropy
        )
        params = tuple(
            jax.tree_util.tree_map(lambda p, g: p - 0.5 * g, ps, gs)
            for ps, gs in zip(params, grads)
        )
    return mpmd_params_for_generation(model, params), float(loss)


def _ring_window(first: int, n: int) -> np.ndarray:
    return np.mod(first + np.arange(n), VOCAB).astype(np.int32)


def _ring_mapped(reqs: List[fleet.TraceRequest]) -> List[Tuple]:
    """Map each trace prompt onto ring windows: the tenant prefix keeps
    its first token (so every request of a tenant still shares the SAME
    prefix — the prefix cache's food) and the suffix keeps its first
    token (so suffixes stay diverse), but both continue along the
    trained ring — in-distribution text the draft can predict."""
    out = []
    for r in reqs:
        pre = _ring_window(int(r.prompt[0]), r.prefix_len)
        suf = _ring_window(
            int(r.prompt[r.prefix_len]), r.prompt.size - r.prefix_len
        )
        out.append((np.concatenate([pre, suf]), r.max_new_tokens,
                    r.session))
    return out


def _program_cache_sizes(engines: Dict[str, _Engine]) -> Dict[str, int]:
    """Per-(replica, program) XLA executable counts — the steady-state
    stability gate reads this before and after the timed region."""
    out: Dict[str, int] = {}
    for name, eng in engines.items():
        for kind, fn in eng._prefill_fns.items():
            out[f"{name}/{kind}"] = fn._cache_size()
        out[f"{name}/decode"] = eng._decode_fn._cache_size()
        if getattr(eng, "_prefix_copy_fn", None) is not None:
            out[f"{name}/prefix_copy"] = eng._prefix_copy_fn._cache_size()
        for kind, fn in getattr(eng, "_draft_fns", {}).items():
            out[f"{name}/{kind}"] = fn._cache_size()
        if getattr(eng, "_verify_fn", None) is not None:
            out[f"{name}/verify"] = eng._verify_fn._cache_size()
    return out


def _serve(mk_engine, reqs, label: str, *,
           die_at=None, seed: int = 1) -> Dict:
    """One rung: warm the fleet with a FULL untimed pass over the trace
    (every program — including the prefix-copy and draft programs, and
    every XLA layout variant a trained-params cache cycles through —
    compiles outside the timed region), then time the steady-state
    closed-loop replay (submit in arrival order, one router step
    between arrivals, run to idle)."""
    metrics = ServingMetrics()     # ONE instance: fleet-wide latencies
    engines = {n: mk_engine(n, metrics) for n in ("r0", "r1")}
    router = fleet.Router(engines, seed=seed)
    for i, (p, n, sess) in enumerate(reqs):
        router.submit(p, n, rid=f"warm-{label}{i}", session=sess)
        router.step()
    router.run()
    programs_before = _program_cache_sizes(engines)
    fleet_metrics = ServingMetrics()
    for rep in router.replicas.values():
        rep.engine.metrics = fleet_metrics    # timed region only
    # The warmup pass advanced the per-replica step clocks die_at_step
    # keys on; re-zero them so the failover rung's death step means
    # "step within the TIMED region" (mid-trace), not "since router
    # construction" (which would kill r0 at the first timed step).
    router.reset_replica_steps()
    # The speculative counters bind to the WARMUP metrics' registry at
    # engine construction; snapshot them so the published acceptance is
    # the timed region's delta, like every other counter here.
    spec_before = {
        n: (eng._c_proposed.value(), eng._c_accepted.value())
        for n, eng in engines.items() if hasattr(eng, "_c_proposed")
    }
    rids = []
    t0 = time.perf_counter()
    ctx = (
        faults.inject(die_at_step=die_at) if die_at is not None
        else contextlib.nullcontext()
    )
    with ctx:
        for i, (p, n, sess) in enumerate(reqs):
            rids.append(router.submit(
                p, n, rid=f"{label}{i}", session=sess
            ))
            router.step()
        router.run()
    outs = [router.result(r).tolist() for r in rids]
    dt = time.perf_counter() - t0
    toks = sum(len(o) for o in outs)
    snap = fleet_metrics.snapshot()
    acceptance = None
    if spec_before:
        proposed = sum(
            eng._c_proposed.value() - spec_before[n][0]
            for n, eng in engines.items()
        )
        accepted = sum(
            eng._c_accepted.value() - spec_before[n][1]
            for n, eng in engines.items()
        )
        acceptance = accepted / proposed if proposed else 0.0
    return {
        "outs": outs,
        "seconds": dt,
        "tokens": toks,
        "tokens_per_sec": toks / dt,
        "ttft_p50_ms": (snap["ttft_p50"] or 0.0) * 1e3,
        "tpot_p50_ms": (snap["tpot_p50"] or 0.0) * 1e3,
        "prefill_steps": snap["prefill_steps"],
        "decode_steps": snap["decode_steps"],
        "prefix_hits": snap["prefix_hits"],
        "prefix_reused_tokens": snap["prefix_reused_tokens"],
        # pooled timed-region acceptance (None for non-spec rungs)
        "acceptance": acceptance,
        # True iff the timed region compiled NOTHING new: the rung
        # measured the steady state, not a compile.
        "steady_state_stable": (
            die_at is not None      # failover legitimately compiles the
            # survivor's first post-restore shapes; exempt from the gate
            or _program_cache_sizes(engines) == programs_before
        ),
        "router": router,
        "engines": engines,
    }


def _replay(router: "fleet.Router", reqs: List[Tuple],
            label: str) -> Tuple[List[List[int]], float]:
    """One timed closed-loop replay of the trace through a pre-warmed
    fleet (submit in arrival order, one router step between arrivals,
    run to idle) — the telemetry-overhead rung's unit of work."""
    rids = []
    t0 = time.perf_counter()
    for i, (p, n, sess) in enumerate(reqs):
        rids.append(router.submit(p, n, rid=f"{label}{i}", session=sess))
        router.step()
    router.run()
    dt = time.perf_counter() - t0
    return [router.result(r).tolist() for r in rids], dt


def _telemetry_overhead(cfg, params, reqs, common, rounds: int) -> Dict:
    """Toggle-based A/B on ONE fleet: the same instrumented router
    replays the trace with its telemetry armed (per-replica
    FlightRecorders recording rid-threaded request spans + a ticking
    SloMonitor + the router recorder) and disarmed (the attributes set
    to None — the exact production off-switch), in order-alternated
    gc-hygienic rounds.  Sharing one fleet object between A and B is
    the point: two separately built fleets differ by allocator layout
    and object history, and that identity noise measured 2-8% on this
    CPU — far above the real telemetry cost (~1 µs per ring event).
    Ratio of median times, gated <2%."""
    import gc

    from torchgpipe_tpu import obs
    from torchgpipe_tpu.obs.flightrec import FlightRecorder

    shared = obs.MetricsRegistry()
    recorders = {n: FlightRecorder(worker=n) for n in ("r0", "r1")}
    engines = {
        n: Engine(cfg, params, registry=shared.labeled(replica=n),
                  recorder=recorders[n], **common)
        for n in ("r0", "r1")
    }
    # Thresholds far above any CPU latency here: the rung measures the
    # EVALUATION cost (throttled ticks, window math, exact over-
    # threshold counting), not alert handling — no eviction may fire.
    monitor = obs.SloMonitor(
        shared,
        [obs.Objective(name="ttft-p95", threshold=30.0,
                       target=0.95, series="serving_ttft_seconds"),
         obs.Objective(name="tpot-p95", threshold=30.0,
                       target=0.95, series="serving_tpot_seconds")],
        short_window=2.0, long_window=8.0,
    )
    router_rec = FlightRecorder(worker="router")
    router = fleet.Router(
        engines, registry=shared, seed=1, slo=monitor,
        recorder=router_rec,
    )

    def arm(on: bool) -> None:
        for n, rep in router.replicas.items():
            rep.engine.recorder = recorders[n] if on else None
        router.slo = monitor if on else None
        router.recorder = router_rec if on else None

    def timed(label: str) -> Tuple[List[List[int]], float]:
        # One collection BEFORE the timed region, none inside: a GC
        # pause landing in one variant's window is the largest single
        # noise source at this effect size.
        gc.collect()
        gc.disable()
        try:
            return _replay(router, reqs, label)
        finally:
            gc.enable()

    _replay(router, reqs, "tw")     # full warm pass: compiles out
    times_on: List[float] = []
    times_off: List[float] = []
    outs_on = outs_off = None
    for k in range(rounds):
        for phase in (0, 1):
            on = (k % 2 == 0) == (phase == 0)
            arm(on)
            outs, dt = timed(f"{'a' if on else 'b'}{k}-")
            if on:
                outs_on = outs
                times_on.append(dt)
            else:
                outs_off = outs
                times_off.append(dt)
    arm(True)
    if outs_on != outs_off:
        raise SystemExit(
            "EXACTNESS FAIL: telemetry changed an output stream"
        )
    if any(rep.degraded for rep in router.replicas.values()):
        raise SystemExit(
            "telemetry rung evicted a replica — the no-alert "
            "thresholds are wrong"
        )

    from statistics import median

    ratio = median(times_on) / median(times_off)
    ratios = [t / p for t, p in zip(times_on, times_off)]
    return {
        "rounds": rounds,
        "ratio_median": round(ratio, 4),
        "ratio_range": [round(min(ratios), 4), round(max(ratios), 4)],
        "overhead_pct_median": round((ratio - 1.0) * 100.0, 2),
        "within_gate": ratio < 1.02,
    }


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--requests", type=int, default=48)
    ap.add_argument("--seed", type=int, default=7)
    ap.add_argument("--slots", type=int, default=4)
    ap.add_argument("--max-len", type=int, default=48)
    ap.add_argument("--gamma", type=int, default=3)
    ap.add_argument("--train-steps", type=int, default=100)
    ap.add_argument("--die-at-step", type=int, default=None,
                    help="failover rung's (r0, step); default: "
                    "mid-trace (requests // 2)")
    ap.add_argument("--overhead-rounds", type=int, default=12,
                    help="paired A/B rounds for the telemetry-overhead "
                    "rung (0 disables it); run on an OTHERWISE IDLE "
                    "host — single-round CPU noise exceeds the effect "
                    "(BENCH_NOTES round 19)")
    ap.add_argument("--json", action="store_true",
                    help="emit one JSON line (bench.py --fleet)")
    args = ap.parse_args()

    # Target sized so a decode step is COMPUTE-dominated even on CPU
    # (dim 96 x 4 layers ~ 16x the draft's FLOPs): speculation's TPOT
    # win is target-vs-draft compute, and a dispatch-overhead-bound
    # toy target would hide it behind per-dispatch constants.
    cfg = TransformerConfig(
        vocab=VOCAB, dim=96, n_layers=4, n_heads=4, n_kv_heads=2
    )
    draft_cfg = TransformerConfig(
        vocab=VOCAB, dim=24, n_layers=1, n_heads=2, n_kv_heads=2
    )
    params, loss_t = _train(cfg, [3, 3], seed=0, steps=args.train_steps)
    draft_params, loss_d = _train(
        draft_cfg, [2, 1], seed=1, steps=args.train_steps
    )

    # The trace: shape from the generator, content ring-mapped; the
    # honesty counters ride into the published line.
    tcfg = fleet.TraceConfig(
        n_requests=args.requests, seed=args.seed, vocab=VOCAB,
        max_len=args.max_len, new_tokens=(4, 16),
    )
    stats = fleet.TraceStats()
    reqs = _ring_mapped(list(fleet.synthetic_trace(tcfg, stats)))

    common = dict(num_slots=args.slots, max_len=args.max_len,
                  prefill_chunk=8)

    def plain(name, metrics):
        return Engine(cfg, params, metrics=metrics, **common)

    def prefixed(name, metrics):
        return Engine(
            cfg, params, metrics=metrics,
            prefix_cache=fleet.RadixPrefixCache(min_prefix_len=4,
                                                max_entries=2),
            **common,
        )

    def speculative(name, metrics):
        return fleet.SpeculativeEngine(
            cfg, params, draft_cfg, draft_params, gamma=args.gamma,
            metrics=metrics, **common,
        )

    die_step = (
        args.die_at_step if args.die_at_step is not None
        else args.requests // 2
    )
    rungs = {
        "baseline": _serve(plain, reqs, "b"),
        "prefix": _serve(prefixed, reqs, "p"),
        "spec": _serve(speculative, reqs, "s"),
        "failover": _serve(plain, reqs, "f", die_at=(0, die_step)),
    }

    # HARD GATE 1: bitwise equality across every rung.
    base_outs = rungs["baseline"]["outs"]
    for name, r in rungs.items():
        if r["outs"] != base_outs:
            bad = next(
                i for i, (a, b) in enumerate(zip(r["outs"], base_outs))
                if a != b
            )
            raise SystemExit(
                f"EXACTNESS FAIL: rung {name!r} diverged from baseline "
                f"at request {bad}: {r['outs'][bad]} vs {base_outs[bad]}"
            )

    # HARD GATE 2: the rungs actually exercised their mechanisms
    # (counters below cover the TIMED pass only — warmup has its own
    # ServingMetrics).
    pref = rungs["prefix"]
    hits = pref["prefix_hits"]
    reused = pref["prefix_reused_tokens"]
    if hits < 1:
        raise SystemExit("prefix rung never hit the cache — the trace "
                         "lost its shared prefixes")
    if not pref["prefill_steps"] < rungs["baseline"]["prefill_steps"]:
        raise SystemExit(
            "prefix reuse did not reduce prefill dispatches "
            f"({pref['prefill_steps']} vs "
            f"{rungs['baseline']['prefill_steps']})"
        )
    for rep in pref["router"].replicas.values():
        rep.engine.pool.check_refcounts()
    acceptance = float(rungs["spec"]["acceptance"])
    if acceptance <= 0.0:
        raise SystemExit("speculation accepted nothing — the draft "
                         "carries no signal on this trace")
    fo = rungs["failover"]["router"]
    if fo._c_failovers.value() != 1 or fo._c_moved.value() < 1:
        raise SystemExit(
            f"failover rung did not fail over (failovers="
            f"{fo._c_failovers.value()}, moved={fo._c_moved.value()})"
        )

    # HARD GATE 3: request tracing + SLO evaluation must stay within
    # the repo's established <2% telemetry-overhead budget.
    telemetry = None
    if args.overhead_rounds > 0:
        telemetry = _telemetry_overhead(
            cfg, params, reqs, common, args.overhead_rounds
        )
        if not telemetry["within_gate"]:
            raise SystemExit(
                f"telemetry overhead {telemetry['overhead_pct_median']:+.2f}% "
                f"(median of {telemetry['rounds']} paired rounds, range "
                f"{telemetry['ratio_range']}) exceeds the 2% gate"
            )

    base, px, sp, fv = (
        rungs["baseline"], rungs["prefix"], rungs["spec"],
        rungs["failover"],
    )
    out = {
        "bench": "fleet-trace",
        "platform": jax.devices()[0].platform,
        "requests": args.requests,
        "seed": args.seed,
        "slots_per_replica": args.slots,
        "replicas": 2,
        "train_loss": {"target": round(loss_t, 4),
                       "draft": round(loss_d, 4)},
        # honesty counters: the trace as generated, drops included
        "trace": {
            "generated": stats.generated,
            "skipped_too_long": stats.skipped_too_long,
            "shareable_fraction": round(stats.shareable_fraction, 3),
            "burst_arrivals": stats.burst_arrivals,
            "per_tenant": {
                str(k): v for k, v in sorted(stats.per_tenant.items())
            },
        },
        "baseline": _pub(base),
        "prefix": {
            **_pub(px),
            "hits": int(hits),
            "reused_tokens": int(reused),
            "hit_rate": round(hits / max(stats.generated, 1), 3),
        },
        "spec": {
            **_pub(sp),
            "gamma": args.gamma,
            "acceptance": round(acceptance, 3),
        },
        "failover": {
            **_pub(fv),
            "moved_requests": int(fv["router"]._c_moved.value()),
            "overhead_seconds": round(
                fv["seconds"] - base["seconds"], 4
            ),
        },
        "speedups": {
            "prefix_ttft": round(
                base["ttft_p50_ms"] / max(px["ttft_p50_ms"], 1e-9), 3
            ),
            "spec_tpot": round(
                base["tpot_p50_ms"] / max(sp["tpot_p50_ms"], 1e-9), 3
            ),
            "spec_tokens_per_sec": round(
                sp["tokens_per_sec"] / max(base["tokens_per_sec"],
                                           1e-9), 3
            ),
        },
        "telemetry_overhead": telemetry,
        "exactness_gated": True,
        # every non-failover rung's timed region compiled nothing new
        "steady_state_stable": {
            name: r["steady_state_stable"] for name, r in rungs.items()
        },
        "validated": all(
            r["steady_state_stable"] for r in rungs.values()
        ) and (telemetry is None or telemetry["within_gate"]),
    }
    if args.json:
        print(json.dumps(out), flush=True)
        return
    print(
        f"fleet-trace: {stats.generated} requests "
        f"({stats.skipped_too_long} skipped-too-long, logged), "
        f"2 replicas x {args.slots} slots\n"
        f"  baseline  {base['tokens_per_sec']:8.1f} tok/s  "
        f"ttft {base['ttft_p50_ms']:6.1f}ms  "
        f"tpot {base['tpot_p50_ms']:5.2f}ms  "
        f"prefill {base['prefill_steps']}\n"
        f"  prefix    {px['tokens_per_sec']:8.1f} tok/s  "
        f"ttft {px['ttft_p50_ms']:6.1f}ms  "
        f"tpot {px['tpot_p50_ms']:5.2f}ms  "
        f"prefill {px['prefill_steps']} "
        f"(hit rate {out['prefix']['hit_rate']:.0%}, "
        f"{reused} tokens reused)\n"
        f"  spec      {sp['tokens_per_sec']:8.1f} tok/s  "
        f"ttft {sp['ttft_p50_ms']:6.1f}ms  "
        f"tpot {sp['tpot_p50_ms']:5.2f}ms  "
        f"(acceptance {acceptance:.0%} at gamma={args.gamma})\n"
        f"  failover  {fv['tokens_per_sec']:8.1f} tok/s  "
        f"moved {out['failover']['moved_requests']} requests, "
        f"overhead {out['failover']['overhead_seconds']:+.3f}s\n"
        f"  all rungs bitwise-identical outputs; "
        f"ttft x{out['speedups']['prefix_ttft']:.2f} (prefix), "
        f"tpot x{out['speedups']['spec_tpot']:.2f} / "
        f"throughput x{out['speedups']['spec_tokens_per_sec']:.2f} "
        f"(spec)"
        + (
            f"\n  telemetry  {telemetry['overhead_pct_median']:+.2f}% "
            f"median overhead over {telemetry['rounds']} paired rounds "
            f"(range {telemetry['ratio_range']}) — "
            f"{'within' if telemetry['within_gate'] else 'OVER'} the "
            f"2% gate"
            if telemetry is not None else ""
        ),
        flush=True,
    )


def _pub(r: Dict) -> Dict:
    return {
        "tokens_per_sec": round(r["tokens_per_sec"], 1),
        "seconds": round(r["seconds"], 4),
        "tokens": r["tokens"],
        "ttft_p50_ms": round(r["ttft_p50_ms"], 2),
        "tpot_p50_ms": round(r["tpot_p50_ms"], 3),
        "prefill_steps": r["prefill_steps"],
        "decode_steps": r["decode_steps"],
    }


if __name__ == "__main__":
    from torchgpipe_tpu.utils.compile_cache import enable_compile_cache

    enable_compile_cache()
    main()
