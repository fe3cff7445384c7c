"""Disaggregation rung: phase-split fleet vs unified under prefill bursts.

PR 18's serving claim — splitting the fleet into prefill and decode
pools isolates decode tail latency from prefill storms, at matched
replica count, with the output streams bitwise unchanged — is MEASURED
here on the prefill-heavy MMPP mix
(:func:`torchgpipe_tpu.fleet.trace.prefill_heavy_config`: a
short-prompt decode-dominated base load punctuated by bursts of LONG
prompts with small budgets).  Two rungs serve the SAME trace at the
same replica count:

* ``unified`` — 2 unified replicas: every replica interleaves burst
  prefill chunks with its live decode rounds, so each storm steals
  decode iterations from in-flight streams;
* ``disagg``  — 1 prefill + 1 decode replica: storms land in the
  prefill pool, finished prompts migrate (KV rows through the
  fixed-shape ``migrate_ingest`` program), and the decode replica runs
  NOTHING but decode rounds.

Measurement contract:

* **Exactness is the hard gate** — both rungs must emit BITWISE
  identical per-request token streams, and the disagg rung must
  actually migrate (``fleet_migrations`` > 0); any divergence exits
  non-zero, no numbers published.
* **Tail latency is measured on a per-replica STEP clock** — each
  engine's :class:`~torchgpipe_tpu.serving.metrics.ServingMetrics`
  reads a virtual clock that advances 1.0 per productive step of ITS
  OWN engine, so TPOT is "engine steps per emitted token": exactly 1.0
  when a replica runs only decode rounds, ~2.0 when prefill work
  interleaves.  Deterministic — a property of trace + routing, not of
  host speed (wall seconds are published unguarded alongside).
* **The headline gate is the isolation claim** — the disagg rung's
  decode TPOT p95 must stay at the 1 step/token floor under the burst,
  while the unified rung's must measurably degrade (>= 1.1x the
  disagg figure); a trace too calm to show the effect fails rather
  than publishing a vacuous win.
* **The timed region is compile-free** — a full warm pass precedes it
  and every program's trace count must be unchanged afterwards.
* **Honesty counters ride along** — the generator's
  ``skipped_too_long`` must be 0 (every generated request fits
  ``max_len``) and the trace must contain actual burst arrivals.

Usage::

    env JAX_PLATFORMS=cpu python -m benchmarks.disagg_trace
    env JAX_PLATFORMS=cpu python bench.py --disagg    # one JSON line
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
from torchgpipe_tpu.serving import Engine, ServingMetrics

VOCAB = 64
MAX_LEN = 48


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
    cfg = fleet.prefill_heavy_config(
        args.requests, seed=args.seed, max_len=MAX_LEN, vocab=VOCAB,
    )
    return list(fleet.synthetic_trace(cfg, stats)), stats


def _run_fleet(cfg: TransformerConfig, flat: Any,
               reqs: List[fleet.TraceRequest], *,
               roles: Dict[str, str], slots: int,
               seed: int) -> Dict[str, Any]:
    """One rung: build the fleet, warm it with a full untimed pass
    (every program — including ``migrate_ingest`` — compiles outside
    the timed region), swap in fresh step-clock metrics, replay."""
    reg = MetricsRegistry()
    warm_metrics = ServingMetrics()
    engines = {
        name: Engine(cfg, flat, num_slots=slots, max_len=MAX_LEN,
                     prefill_chunk=8, role=role, metrics=warm_metrics,
                     registry=reg.labeled(replica=name))
        for name, role in roles.items()
    }
    router = fleet.Router(engines, registry=reg, seed=seed)
    for i, req in enumerate(reqs):
        router.submit(req.prompt, req.max_new_tokens,
                      rid=f"warm-{i}", session=req.session)
        router.step()
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
    warm_migrations = int(reg.counter("fleet_migrations").value())
    warm_traces = {
        name: dict(rep.engine.trace_counts)
        for name, rep in router.replicas.items()
    }

    rids: List[str] = []
    t0 = time.perf_counter()
    for i, req in enumerate(reqs):
        rids.append(router.submit(req.prompt, req.max_new_tokens,
                                  rid=f"q{i}", session=req.session))
        router.step()
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
    # TPOT samples in step units, pooled across replicas: a request's
    # decode gap lives on the replica that finished its stream.
    tpots = [
        r.tpot
        for rep in router.replicas.values()
        for r in rep.engine.metrics.requests.values()
        if r.status == "finished" and r.tpot is not None
    ]
    if not tpots:
        raise SystemExit("no request produced a TPOT sample")
    toks = sum(len(o) for o in outs)
    return {
        "outs": outs,
        "seconds": dt,
        "tokens": toks,
        "tokens_per_sec": toks / dt,
        "tpot_steps_p50": float(np.percentile(tpots, 50)),
        "tpot_steps_p95": float(np.percentile(tpots, 95)),
        "tpot_samples": len(tpots),
        "migrations": int(reg.counter("fleet_migrations").value())
        - warm_migrations,
        "steps": {n: c.t for n, c in clocks.items()},
    }


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--requests", type=int, default=40)
    ap.add_argument("--seed", type=int, default=3)
    ap.add_argument("--slots", type=int, default=4)
    ap.add_argument("--margin", type=float, default=1.1,
                    help="unified decode TPOT p95 must exceed the "
                    "disagg figure by this factor — the 'unified "
                    "measurably degrades' half of the claim")
    ap.add_argument("--json", action="store_true",
                    help="emit one JSON line (bench.py --disagg)")
    args = ap.parse_args()

    cfg = TransformerConfig(
        vocab=VOCAB, dim=96, n_layers=4, n_heads=4, n_kv_heads=2
    )
    flat, _, _ = sequential_init(
        llama(cfg), jax.random.PRNGKey(0),
        jax.ShapeDtypeStruct((2, 8), jnp.int32),
    )
    reqs, stats = _make_trace(args)
    if stats.skipped_too_long:
        raise SystemExit(
            f"trace generator skipped {stats.skipped_too_long} "
            f"requests — the preset must fit max_len={MAX_LEN}"
        )
    if not stats.burst_arrivals:
        raise SystemExit(
            "trace contains no burst arrivals — the prefill-storm "
            "claim would be vacuous; pick another seed"
        )

    unified = _run_fleet(
        cfg, flat, reqs, slots=args.slots, seed=args.seed,
        roles={"u0": "unified", "u1": "unified"},
    )
    disagg = _run_fleet(
        cfg, flat, reqs, slots=args.slots, seed=args.seed,
        roles={"p0": "prefill", "d0": "decode"},
    )

    # HARD GATE 1: bitwise equality — the phase split changes nothing
    # in any output stream.
    if disagg["outs"] != unified["outs"]:
        bad = next(
            i for i, (a, b) in enumerate(zip(disagg["outs"],
                                             unified["outs"]))
            if a != b
        )
        raise SystemExit(
            f"EXACTNESS FAIL: disagg rung diverged from unified at "
            f"request {bad}: {disagg['outs'][bad]} vs "
            f"{unified['outs'][bad]}"
        )

    # HARD GATE 2: the split actually migrated every stream.
    if disagg["migrations"] < len(reqs):
        raise SystemExit(
            f"disagg rung migrated {disagg['migrations']} of "
            f"{len(reqs)} requests — the handoff path was not on"
        )

    # HARD GATE 3 (headline): the decode pool holds the 1 step/token
    # floor under the prefill burst; unified measurably degrades.
    if disagg["tpot_steps_p95"] > 1.0 + 1e-9:
        raise SystemExit(
            f"ISOLATION FAIL: disagg decode TPOT p95 "
            f"{disagg['tpot_steps_p95']:.3f} steps/token — the decode "
            "pool lost iterations to prefill work"
        )
    if unified["tpot_steps_p95"] < args.margin * disagg["tpot_steps_p95"]:
        raise SystemExit(
            f"unified rung did not measurably degrade "
            f"(p95 {unified['tpot_steps_p95']:.3f} vs disagg "
            f"{disagg['tpot_steps_p95']:.3f} x margin {args.margin}) — "
            "the trace shows no prefill pressure; pick another seed"
        )

    out = {
        "bench": "disagg-trace",
        "platform": jax.devices()[0].platform,
        "requests": args.requests,
        "seed": args.seed,
        "slots_per_replica": args.slots,
        "replicas": 2,
        "trace": {
            "generated": stats.generated,
            "skipped_too_long": stats.skipped_too_long,
            "burst_arrivals": stats.burst_arrivals,
            "burst_prompt_tokens": stats.burst_prompt_tokens,
            "total_prompt_tokens": stats.total_prompt_tokens,
        },
        "unified": _pub(unified),
        "disagg": {**_pub(disagg), "migrations": disagg["migrations"]},
        "isolation": {
            "unified_tpot_steps_p95": round(
                unified["tpot_steps_p95"], 3
            ),
            "disagg_tpot_steps_p95": round(
                disagg["tpot_steps_p95"], 3
            ),
            "margin": args.margin,
            "held": True,
        },
        "exactness_gated": True,
        "validated": True,
    }
    if args.json:
        print(json.dumps(out), flush=True)
        return
    print(
        f"disagg-trace: {stats.generated} requests "
        f"({stats.burst_arrivals} burst arrivals, "
        f"{stats.burst_prompt_tokens} burst prompt tokens) at 2 "
        f"replicas x {args.slots} slots\n"
        f"  unified  tpot {unified['tpot_steps_p50']:.3f}/"
        f"{unified['tpot_steps_p95']:.3f} steps p50/p95  "
        f"{unified['tokens_per_sec']:8.1f} tok/s wall\n"
        f"  disagg   tpot {disagg['tpot_steps_p50']:.3f}/"
        f"{disagg['tpot_steps_p95']:.3f} steps p50/p95  "
        f"{disagg['tokens_per_sec']:8.1f} tok/s wall  "
        f"({disagg['migrations']} handoffs)\n"
        f"  decode tail isolated: disagg holds the 1 step/token floor "
        f"under the burst, unified degrades "
        f"{unified['tpot_steps_p95'] / disagg['tpot_steps_p95']:.2f}x; "
        f"outputs bitwise-identical across the split",
        flush=True,
    )


def _pub(r: Dict[str, Any]) -> Dict[str, Any]:
    return {
        "tokens_per_sec": round(r["tokens_per_sec"], 1),
        "seconds": round(r["seconds"], 4),
        "tokens": r["tokens"],
        "tpot_steps_p50": round(r["tpot_steps_p50"], 3),
        "tpot_steps_p95": round(r["tpot_steps_p95"], 3),
        "tpot_samples": r["tpot_samples"],
        "steps": r["steps"],
    }


if __name__ == "__main__":
    from torchgpipe_tpu.utils.compile_cache import enable_compile_cache

    enable_compile_cache()
    main()
