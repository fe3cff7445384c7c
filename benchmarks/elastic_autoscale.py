"""Elastic rung: the SLO-priced autoscaler vs a static peak fleet.

PR 17's serving claim — an autoscaled fleet rides a bursty MMPP trace
with LESS provisioned capacity than static peak provisioning, while
holding the declared latency objectives and never dropping in-flight
work — is MEASURED here on the same seeded synthetic trace the fleet
bench uses.  Two rungs serve the SAME trace:

* ``static``     — 2 replicas in rotation for the whole trace (peak
  provisioning: capacity sized for the burst, paid for in the calm);
* ``autoscaled`` — the same 2-replica fleet under
  :class:`~torchgpipe_tpu.fleet.autoscaler.Autoscaler` (Little's-law
  pricing at the declared per-request service time, hysteresis,
  floor 1), which parks a replica in the calm and re-opens it when the
  burst arrives.

Measurement contract:

* **Exactness is the hard gate** — both rungs must emit BITWISE
  identical per-request token streams (greedy decode is replica- and
  scale-event-independent); any divergence exits non-zero, no numbers
  published.  This is the "never drops an in-flight request" claim:
  scale-down rides the router's drain path, so a parked replica's
  live requests finish on the survivor with identical tokens.
* **Capacity is priced in trace time** — ``replica_seconds`` is the
  integral of the in-rotation replica count over the trace's VIRTUAL
  arrival clock (the clock the autoscaler's rate windows read), so the
  published saving is a property of the trace + policy, deterministic
  across runs.  The static rung's integral is by construction
  ``2 x trace duration`` — the peak-provisioned bill.
* **The SLO gate is the steady-state objective** — per-token latency
  (TPOT p95, wall clock, from the shared
  :class:`~torchgpipe_tpu.serving.metrics.ServingMetrics`) must stay
  under the declared objective on the AUTOSCALED rung: scaling to the
  floor may queue work but must not degrade the per-token service
  rate.  TTFT for both rungs is published for comparison (a compressed
  replay queues both rungs artificially, so TTFT is reported, not
  gated).
* **The fleet must actually breathe** — at least one scale-down AND
  one scale-up must occur, and the trajectory may never fall below
  the floor; a trace too calm (or a policy too damped) to exercise
  both directions fails rather than publishing a vacuous saving.

Usage::

    env JAX_PLATFORMS=cpu python -m benchmarks.elastic_autoscale
    env JAX_PLATFORMS=cpu python bench.py --elastic    # one JSON line
"""

from __future__ import annotations

import argparse
import json
import os
import time
from typing import Any, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

if os.environ.get("JAX_PLATFORMS", "").strip().lower() == "cpu":
    jax.config.update("jax_platforms", "cpu")

from torchgpipe_tpu import fleet
from torchgpipe_tpu.layers import sequential_init
from torchgpipe_tpu.models.transformer import TransformerConfig, llama
from torchgpipe_tpu.obs import MetricsRegistry
from torchgpipe_tpu.serving import Engine, ServingMetrics

VOCAB = 64


def _make_trace(args: argparse.Namespace) -> Tuple[
    List[fleet.TraceRequest], fleet.TraceStats
]:
    """The bursty MMPP trace both rungs serve: calm ~20 req/s, bursts
    >100 req/s — the regime where static provisioning pays for the
    burst all trace long."""
    stats = fleet.TraceStats()
    cfg = fleet.TraceConfig(
        n_requests=args.requests, seed=args.seed, vocab=VOCAB,
        max_len=24, new_tokens=(2, 6),
        calm_gap_s=0.05, burst_gap_s=0.002,
        p_enter_burst=0.2, p_exit_burst=0.2,
    )
    return list(fleet.synthetic_trace(cfg, stats)), stats


def _run_fleet(cfg: TransformerConfig, flat: Any,
               reqs: List[fleet.TraceRequest], *,
               autoscale: bool, slots: int,
               service_time_s: float) -> Dict[str, Any]:
    """One rung: warm the fleet with a full untimed pass (every program
    compiles outside the timed region), then replay the trace in
    arrival order — virtual clock driving the autoscaler's rate
    windows, wall clock driving the latency metrics."""
    clock_t = [0.0]
    reg = MetricsRegistry(clock=lambda: clock_t[0])
    warm_metrics = ServingMetrics()
    engines = {
        n: Engine(cfg, flat, num_slots=slots, max_len=32,
                  prefill_chunk=8, metrics=warm_metrics,
                  registry=reg.labeled(replica=n))
        for n in ("r0", "r1")
    }
    router = fleet.Router(engines, registry=reg, seed=0)
    for i, req in enumerate(reqs):
        clock_t[0] = req.arrival_s
        router.submit(req.prompt, req.max_new_tokens,
                      rid=f"warm-{i}", session=req.session)
        router.step()
    while router.run() != "idle":
        pass

    metrics = ServingMetrics()                 # timed region only
    for rep in router.replicas.values():
        rep.engine.metrics = metrics
    scaler = None
    if autoscale:
        # Priced so the calm rate fits one replica's slots and the
        # burst demands the second (same pricing the elastic-verify
        # gate pins).
        scaler = fleet.Autoscaler(
            router, service_time_s=service_time_s, headroom=1.0,
            window_s=0.05, hold_ticks=2, min_replicas=1,
        )

    rids: List[str] = []
    trajectory: List[int] = []
    actions: List[str] = []
    replica_seconds = 0.0
    cap = sum(1 for r in router.replicas.values() if r.in_rotation)
    prev_t: Optional[float] = None
    t0 = time.perf_counter()
    for i, req in enumerate(reqs):
        t = req.arrival_s
        if prev_t is not None:
            replica_seconds += cap * (t - prev_t)
        prev_t = t
        clock_t[0] = t
        if scaler is not None:
            scaler.observe_arrival(1)
        rids.append(router.submit(req.prompt, req.max_new_tokens,
                                  rid=f"q{i}", session=req.session))
        router.step()
        if scaler is not None:
            act = scaler.tick()
            if act is not None:
                actions.append(act)
        cap = sum(1 for r in router.replicas.values() if r.in_rotation)
        trajectory.append(cap)
    while router.run() != "idle":
        pass
    dt = time.perf_counter() - t0

    outs = [router.result(r).tolist() for r in rids]
    snap = metrics.snapshot()
    toks = sum(len(o) for o in outs)
    return {
        "outs": outs,
        "seconds": dt,
        "tokens": toks,
        "tokens_per_sec": toks / dt,
        "ttft_p50_ms": (snap["ttft_p50"] or 0.0) * 1e3,
        "ttft_p95_ms": (snap["ttft_p95"] or 0.0) * 1e3,
        "tpot_p50_ms": (snap["tpot_p50"] or 0.0) * 1e3,
        "tpot_p95_ms": (snap["tpot_p95"] or 0.0) * 1e3,
        "replica_seconds": replica_seconds,
        "trajectory": trajectory,
        "actions": actions,
    }


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--requests", type=int, default=40)
    ap.add_argument("--seed", type=int, default=2)
    ap.add_argument("--slots", type=int, default=4)
    ap.add_argument("--service-time-s", type=float, default=0.05,
                    help="declared per-request service time the "
                    "autoscaler prices capacity with")
    ap.add_argument("--slo-tpot-ms", type=float, default=250.0,
                    help="declared TPOT p95 objective the autoscaled "
                    "rung must hold (generous for CPU; the gate is "
                    "'scaling to the floor must not degrade the "
                    "per-token service rate')")
    ap.add_argument("--json", action="store_true",
                    help="emit one JSON line (bench.py --elastic)")
    args = ap.parse_args()

    cfg = TransformerConfig(
        vocab=VOCAB, dim=32, n_layers=2, n_heads=4, n_kv_heads=2
    )
    flat, _, _ = sequential_init(
        llama(cfg), jax.random.PRNGKey(0),
        jax.ShapeDtypeStruct((2, 8), jnp.int32),
    )
    reqs, stats = _make_trace(args)
    duration = reqs[-1].arrival_s - reqs[0].arrival_s

    static = _run_fleet(cfg, flat, reqs, autoscale=False,
                        slots=args.slots,
                        service_time_s=args.service_time_s)
    auto = _run_fleet(cfg, flat, reqs, autoscale=True,
                      slots=args.slots,
                      service_time_s=args.service_time_s)

    # HARD GATE 1: bitwise equality — scale events drop nothing.
    if auto["outs"] != static["outs"]:
        bad = next(
            i for i, (a, b) in enumerate(zip(auto["outs"],
                                             static["outs"]))
            if a != b
        )
        raise SystemExit(
            f"EXACTNESS FAIL: autoscaled rung diverged from static at "
            f"request {bad}: {auto['outs'][bad]} vs {static['outs'][bad]}"
        )

    # HARD GATE 2: the fleet breathed both ways and held the floor.
    downs = [a for a in auto["actions"] if a.startswith("down:")]
    ups = [a for a in auto["actions"] if a.startswith("up:")]
    if not downs or not ups:
        raise SystemExit(
            f"autoscaler did not breathe both ways on the bursty "
            f"trace: actions={auto['actions']}"
        )
    if min(auto["trajectory"]) < 1:
        raise SystemExit(
            f"trajectory dropped below the floor: {auto['trajectory']}"
        )

    # HARD GATE 3: less provisioned capacity than static peak.
    saved = static["replica_seconds"] - auto["replica_seconds"]
    if not saved > 0.0:
        raise SystemExit(
            f"autoscaling saved no capacity: "
            f"{auto['replica_seconds']:.3f} vs static "
            f"{static['replica_seconds']:.3f} replica-seconds"
        )
    saved_pct = 100.0 * saved / static["replica_seconds"]

    # HARD GATE 4: the declared per-token objective held while scaled.
    if auto["tpot_p95_ms"] > args.slo_tpot_ms:
        raise SystemExit(
            f"SLO FAIL: autoscaled TPOT p95 {auto['tpot_p95_ms']:.2f}ms "
            f"over the declared {args.slo_tpot_ms:.0f}ms objective"
        )

    out = {
        "bench": "elastic-autoscale",
        "platform": jax.devices()[0].platform,
        "requests": args.requests,
        "seed": args.seed,
        "slots_per_replica": args.slots,
        "replicas_peak": 2,
        "service_time_s": args.service_time_s,
        # honesty counters: the trace as generated, drops included
        "trace": {
            "generated": stats.generated,
            "skipped_too_long": stats.skipped_too_long,
            "burst_arrivals": stats.burst_arrivals,
            "duration_s": round(duration, 3),
        },
        "static": _pub(static),
        "autoscaled": {
            **_pub(auto),
            "actions": auto["actions"],
            "trajectory_min": min(auto["trajectory"]),
            "trajectory_max": max(auto["trajectory"]),
        },
        "capacity": {
            "static_replica_seconds": round(
                static["replica_seconds"], 3
            ),
            "autoscaled_replica_seconds": round(
                auto["replica_seconds"], 3
            ),
            "saved_pct": round(saved_pct, 1),
        },
        "slo": {
            "tpot_p95_objective_ms": args.slo_tpot_ms,
            "autoscaled_tpot_p95_ms": round(auto["tpot_p95_ms"], 3),
            "held": True,
        },
        "exactness_gated": True,
        "validated": True,
    }
    if args.json:
        print(json.dumps(out), flush=True)
        return
    print(
        f"elastic-autoscale: {stats.generated} requests "
        f"({stats.burst_arrivals} burst arrivals) over "
        f"{duration:.2f}s of trace time, 2 replicas x {args.slots} "
        f"slots\n"
        f"  static      {static['tokens_per_sec']:8.1f} tok/s  "
        f"ttft {static['ttft_p95_ms']:6.1f}ms p95  "
        f"tpot {static['tpot_p95_ms']:5.2f}ms p95  "
        f"{static['replica_seconds']:.2f} replica-s\n"
        f"  autoscaled  {auto['tokens_per_sec']:8.1f} tok/s  "
        f"ttft {auto['ttft_p95_ms']:6.1f}ms p95  "
        f"tpot {auto['tpot_p95_ms']:5.2f}ms p95  "
        f"{auto['replica_seconds']:.2f} replica-s "
        f"({len(downs)} down / {len(ups)} up, floor "
        f"{min(auto['trajectory'])})\n"
        f"  capacity saved {saved_pct:.1f}% vs static peak; outputs "
        f"bitwise-identical across scale events; TPOT p95 "
        f"{auto['tpot_p95_ms']:.2f}ms within the "
        f"{args.slo_tpot_ms:.0f}ms objective",
        flush=True,
    )


def _pub(r: Dict[str, Any]) -> Dict[str, Any]:
    return {
        "tokens_per_sec": round(r["tokens_per_sec"], 1),
        "seconds": round(r["seconds"], 4),
        "tokens": r["tokens"],
        "ttft_p50_ms": round(r["ttft_p50_ms"], 2),
        "ttft_p95_ms": round(r["ttft_p95_ms"], 2),
        "tpot_p50_ms": round(r["tpot_p50_ms"], 3),
        "tpot_p95_ms": round(r["tpot_p95_ms"], 3),
        "replica_seconds": round(r["replica_seconds"], 3),
    }


if __name__ == "__main__":
    from torchgpipe_tpu.utils.compile_cache import enable_compile_cache

    enable_compile_cache()
    main()
