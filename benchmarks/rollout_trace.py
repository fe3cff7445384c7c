"""Rollout rung: live weight rollouts under a mixed-tier MMPP trace.

PR 20's serving claim — a fleet can roll fresh weights replica-by-
replica THROUGH live traffic, survive a forced rollback, and the
interactive tier never notices — is MEASURED here.  Two rungs serve
the SAME tenant-tiered MMPP trace (tenant 0 → interactive, tenant 1 →
standard, tenants 2/3 → batch) on a 2-replica fleet with one shared
:class:`~torchgpipe_tpu.serving.qos.QosPolicy`:

* ``control`` — no rollout machinery touches the timed region;
* ``rollout`` — the :class:`~torchgpipe_tpu.fleet.rollout.
  RolloutController` completes TWO full rolling updates (v2, v3)
  mid-trace, then a third publish (v4) is force-rolled-back the
  moment the fleet is version-split — the operator "bad vibes" drill.

Every published version carries BIT-IDENTICAL param values, on
purpose: the bitwise gate then isolates the rollout *machinery*
(drain, swap, readmit, resubmit) — any divergence is a scheduling or
state-handoff bug, never a weights delta hiding it.

Measurement contract:

* **Zero drops is the hard gate** — every stream in both rungs must
  finish at its full token budget; a rollout that shed load exits
  non-zero, no numbers published.
* **Exactness is the hard gate** — the rollout rung's per-request
  streams must be BITWISE the control rung's.
* **The headline gate is the QoS claim** — interactive-tier TPOT p95
  (per-replica STEP clock, 1.0 per productive engine step —
  deterministic, host-speed-free) must stay within ``--margin``
  (default 1.1x) of the no-rollout control through two rollouts and
  the rollback.
* **The timed region is compile-free** — a warm pass (which also runs
  one untimed rollout, so the drain→swap→resubmit path compiles
  outside the window) precedes it; every program's trace count must
  be unchanged afterwards.
* **Honesty counters ride along** — the drill must actually witness a
  version-split fleet before rolling back, ``rollout_rollbacks_total``
  must be exactly 1, the fleet must end on v3, the generator's
  ``skipped_too_long`` must be 0, and each rung must produce enough
  interactive TPOT samples for the p95 to mean anything.

Usage::

    env JAX_PLATFORMS=cpu python -m benchmarks.rollout_trace
    env JAX_PLATFORMS=cpu python bench.py --rollout    # one JSON line
"""

from __future__ import annotations

import argparse
import json
import os
import time
from typing import Any, Dict, List, Tuple

import jax
import jax.numpy as jnp
import numpy as np

if os.environ.get("JAX_PLATFORMS", "").strip().lower() == "cpu":
    jax.config.update("jax_platforms", "cpu")

from torchgpipe_tpu import fleet
from torchgpipe_tpu.layers import sequential_init
from torchgpipe_tpu.models.transformer import TransformerConfig, llama
from torchgpipe_tpu.obs import MetricsRegistry
from torchgpipe_tpu.serving import Engine, QosConfig, QosPolicy, ServingMetrics

VOCAB = 64
MAX_LEN = 48
TIER_OF_TENANT = {0: "interactive", 1: "standard", 2: "batch", 3: "batch"}


class _StepClock:
    """A per-replica virtual clock: t advances 1.0 per productive step
    of the engine it is attached to."""

    def __init__(self) -> None:
        self.t = 0.0

    def __call__(self) -> float:
        return self.t


def _make_trace(args: argparse.Namespace) -> Tuple[
    List[fleet.TraceRequest], fleet.TraceStats
]:
    stats = fleet.TraceStats()
    cfg = fleet.TraceConfig(
        n_requests=args.requests, seed=args.seed, vocab=VOCAB,
        max_len=MAX_LEN, n_tenants=4,
    )
    return list(fleet.synthetic_trace(cfg, stats)), stats


def _run_rung(cfg: TransformerConfig, flat: Any,
              reqs: List[fleet.TraceRequest], *,
              rollout: bool, slots: int, seed: int) -> Dict[str, Any]:
    """One rung: build the QoS fleet, warm it with a full untimed pass
    (the rollout rung also completes one untimed v0→v1 rolling update,
    compiling the drain→swap→resubmit path outside the timed region),
    swap in fresh step-clock metrics, replay with the rollout schedule.
    """
    reg = MetricsRegistry()
    pol = QosPolicy(QosConfig(), registry=reg)
    warm_metrics = ServingMetrics()
    engines = {
        name: Engine(cfg, flat, num_slots=slots, max_len=MAX_LEN,
                     prefill_chunk=8, qos=pol, metrics=warm_metrics,
                     registry=reg.labeled(replica=name))
        for name in ("r0", "r1")
    }
    router = fleet.Router(engines, registry=reg, seed=seed)
    ctl = fleet.RolloutController(router) if rollout else None
    for i, req in enumerate(reqs):
        router.submit(req.prompt, req.max_new_tokens, rid=f"warm-{i}",
                      session=req.session,
                      tier=TIER_OF_TENANT[req.tenant],
                      tenant=f"t{req.tenant}")
        router.step()
        if ctl is not None:
            if i == len(reqs) // 2:
                ctl.publish(flat, 1)
            ctl.tick()
    while router.run() != "idle":
        pass
    if ctl is not None:
        while ctl.baseline != 1 or ctl._pending():
            router.step()
            ctl.tick()
        while router.run() != "idle":
            pass

    # Per-replica step clocks + fresh metrics: the timed region's TPOT
    # is engine-steps-per-token, deterministic across hosts.
    clocks: Dict[str, _StepClock] = {}
    for name, rep in router.replicas.items():
        clock = clocks[name] = _StepClock()
        rep.engine.metrics = ServingMetrics(clock=clock)

        def stepper(orig=rep.engine.step, c=clock):
            ran = orig()
            if ran:
                c.t += 1.0
            return ran

        rep.engine.step = stepper
    warm_traces = {
        name: dict(rep.engine.trace_counts)
        for name, rep in router.replicas.items()
    }

    n = len(reqs)
    # Two full rolling updates land mid-trace; the third publish is
    # the rollback drill, late enough that traffic is still flowing.
    publish_at = {n // 6: 2, n // 2: 3, (3 * n) // 4: 4}
    rids: List[str] = []
    events: List[Tuple[float, str]] = []
    awaiting_drill = False
    t0 = time.perf_counter()
    for i, req in enumerate(reqs):
        rids.append(router.submit(
            req.prompt, req.max_new_tokens, rid=f"q{i}",
            session=req.session, tier=TIER_OF_TENANT[req.tenant],
            tenant=f"t{req.tenant}"))
        router.step()
        if ctl is not None:
            version = publish_at.get(i)
            if version is not None:
                ctl.publish(flat, version)
                events.append((i, f"publish:v{version}"))
                awaiting_drill = version == 4
            act = ctl.tick()
            if act and act.startswith(("swap", "rollback", "complete")):
                events.append((i, act))
            # The drill: the moment the fleet is version-split on v4,
            # the operator pulls the cord.
            if awaiting_drill and len(set(ctl.versions().values())) == 2:
                events.append((i, ctl.rollback("forced drill")))
                awaiting_drill = False
    for _ in range(10_000):
        router.step()
        if ctl is not None:
            act = ctl.tick()
            if act and act.startswith(("swap", "rollback", "complete")):
                events.append((n, act))
        if router.idle and (
                ctl is None
                or (ctl.baseline == ctl.target and not ctl._pending())):
            break
    while router.run() != "idle":
        pass
    dt = time.perf_counter() - t0

    for name, rep in router.replicas.items():
        if dict(rep.engine.trace_counts) != warm_traces[name]:
            raise SystemExit(
                f"COMPILE-FREE FAIL: replica {name} traced a program "
                f"inside the timed region: {dict(rep.engine.trace_counts)}"
                f" vs warm {warm_traces[name]}"
            )

    outs = [router.result(r).tolist() for r in rids]
    dropped = [
        rids[i] for i, req in enumerate(reqs)
        if len(outs[i]) != req.max_new_tokens
    ]
    if dropped:
        raise SystemExit(
            f"ZERO-DROP FAIL ({'rollout' if rollout else 'control'} "
            f"rung): {len(dropped)} stream(s) short of budget: "
            f"{dropped[:5]}"
        )

    # Interactive-tier TPOT, step units: a request's decode gap lives
    # on the replica that finished its stream (migrated streams appear
    # on several replicas; only the finishing record counts).
    interactive = {
        f"q{i}" for i, req in enumerate(reqs)
        if TIER_OF_TENANT[req.tenant] == "interactive"
    }
    tpots = [
        r.tpot
        for rep in router.replicas.values()
        for rid, r in rep.engine.metrics.requests.items()
        if (rid in interactive and r.status == "finished"
            and r.tpot is not None)
    ]
    if len(tpots) < 8:
        raise SystemExit(
            f"only {len(tpots)} interactive TPOT samples — the p95 "
            "would be noise; raise --requests or pick another seed"
        )
    toks = sum(len(o) for o in outs)
    out = {
        "outs": outs,
        "seconds": dt,
        "tokens": toks,
        "tokens_per_sec": toks / dt,
        "interactive_tpot_p50": float(np.percentile(tpots, 50)),
        "interactive_tpot_p95": float(np.percentile(tpots, 95)),
        "interactive_samples": len(tpots),
        "steps": {nm: c.t for nm, c in clocks.items()},
        "preemptions": int(pol._c_preemptions.value()),
    }
    if ctl is not None:
        out["events"] = [f"{i}:{e}" for i, e in events]
        out["versions"] = ctl.versions()
        out["rollbacks"] = int(
            reg.get("rollout_rollbacks_total").value()
        )
        out["swaps"] = {
            name: int(reg.get("rollout_swaps_total")
                      .value(replica=name))
            for name in router.replicas
        }
    return out


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--requests", type=int, default=48)
    ap.add_argument("--seed", type=int, default=5)
    ap.add_argument("--slots", type=int, default=4)
    ap.add_argument("--margin", type=float, default=1.1,
                    help="rollout-rung interactive TPOT p95 must stay "
                    "within this factor of the no-rollout control — "
                    "the 'interactive tier never notices' claim")
    ap.add_argument("--json", action="store_true",
                    help="emit one JSON line (bench.py --rollout)")
    args = ap.parse_args()

    cfg = TransformerConfig(
        vocab=VOCAB, dim=64, n_layers=2, n_heads=4, n_kv_heads=2
    )
    flat, _, _ = sequential_init(
        llama(cfg), jax.random.PRNGKey(0),
        jax.ShapeDtypeStruct((2, 8), jnp.int32),
    )
    reqs, stats = _make_trace(args)
    if stats.skipped_too_long:
        raise SystemExit(
            f"trace generator skipped {stats.skipped_too_long} "
            f"requests — the mix must fit max_len={MAX_LEN}"
        )

    control = _run_rung(cfg, flat, reqs, rollout=False,
                        slots=args.slots, seed=args.seed)
    rollout = _run_rung(cfg, flat, reqs, rollout=True,
                        slots=args.slots, seed=args.seed)

    # HARD GATE 1: bitwise equality — two rollouts and a rollback
    # change nothing in any output stream.
    if rollout["outs"] != control["outs"]:
        bad = next(
            i for i, (a, b) in enumerate(zip(rollout["outs"],
                                             control["outs"]))
            if a != b
        )
        raise SystemExit(
            f"EXACTNESS FAIL: rollout rung diverged from control at "
            f"request {bad}: {rollout['outs'][bad]} vs "
            f"{control['outs'][bad]}"
        )

    # HARD GATE 2: the schedule actually happened — two completed
    # rollouts, one forced rollback, fleet ends on v3.
    if rollout["rollbacks"] != 1:
        raise SystemExit(
            f"rollout rung recorded {rollout['rollbacks']} rollbacks "
            "(want exactly 1) — the drill never fired"
        )
    if rollout["versions"] != {"r0": 3, "r1": 3}:
        raise SystemExit(
            f"fleet did not end on v3: {rollout['versions']} — the "
            "rollback drill did not converge"
        )
    if not any(":rollback" in e for e in rollout["events"]):
        raise SystemExit("no rollback event in the timed region")

    # HARD GATE 3 (headline): interactive-tier TPOT p95 holds within
    # the margin through two rollouts and the rollback.
    ceiling = args.margin * control["interactive_tpot_p95"]
    if rollout["interactive_tpot_p95"] > ceiling + 1e-9:
        raise SystemExit(
            f"QOS FAIL: rollout interactive TPOT p95 "
            f"{rollout['interactive_tpot_p95']:.3f} steps/token vs "
            f"control {control['interactive_tpot_p95']:.3f} x margin "
            f"{args.margin} — the rollout was not invisible to the "
            "interactive tier"
        )

    tiers = {t: 0 for t in ("interactive", "standard", "batch")}
    for req in reqs:
        tiers[TIER_OF_TENANT[req.tenant]] += 1
    out = {
        "bench": "rollout-trace",
        "platform": jax.devices()[0].platform,
        "requests": args.requests,
        "seed": args.seed,
        "slots_per_replica": args.slots,
        "replicas": 2,
        "tier_mix": tiers,
        "trace": {
            "generated": stats.generated,
            "skipped_too_long": stats.skipped_too_long,
            "burst_arrivals": stats.burst_arrivals,
        },
        "control": _pub(control),
        "rollout": {
            **_pub(rollout),
            "events": rollout["events"],
            "versions": rollout["versions"],
            "rollbacks": rollout["rollbacks"],
            "swaps": rollout["swaps"],
        },
        "qos": {
            "control_interactive_tpot_p95": round(
                control["interactive_tpot_p95"], 3
            ),
            "rollout_interactive_tpot_p95": round(
                rollout["interactive_tpot_p95"], 3
            ),
            "margin": args.margin,
            "held": True,
        },
        "zero_drops": True,
        "exactness_gated": True,
        "validated": True,
    }
    if args.json:
        print(json.dumps(out), flush=True)
        return
    print(
        f"rollout-trace: {stats.generated} requests "
        f"(tiers {tiers}) at 2 replicas x {args.slots} slots\n"
        f"  control  interactive tpot "
        f"{control['interactive_tpot_p50']:.3f}/"
        f"{control['interactive_tpot_p95']:.3f} steps p50/p95  "
        f"{control['tokens_per_sec']:8.1f} tok/s wall\n"
        f"  rollout  interactive tpot "
        f"{rollout['interactive_tpot_p50']:.3f}/"
        f"{rollout['interactive_tpot_p95']:.3f} steps p50/p95  "
        f"{rollout['tokens_per_sec']:8.1f} tok/s wall  "
        f"({sum(rollout['swaps'].values())} swaps, "
        f"{rollout['rollbacks']} rollback)\n"
        f"  events: {' '.join(rollout['events'])}\n"
        f"  two rollouts + forced rollback served mid-trace: zero "
        f"drops, streams bitwise vs control, interactive p95 within "
        f"{args.margin}x",
        flush=True,
    )


def _pub(r: Dict[str, Any]) -> Dict[str, Any]:
    return {
        "tokens_per_sec": round(r["tokens_per_sec"], 1),
        "seconds": round(r["seconds"], 4),
        "tokens": r["tokens"],
        "interactive_tpot_p50": round(r["interactive_tpot_p50"], 3),
        "interactive_tpot_p95": round(r["interactive_tpot_p95"], 3),
        "interactive_samples": r["interactive_samples"],
        "preemptions": r["preemptions"],
        "steps": r["steps"],
    }


if __name__ == "__main__":
    from torchgpipe_tpu.utils.compile_cache import enable_compile_cache

    enable_compile_cache()
    main()
