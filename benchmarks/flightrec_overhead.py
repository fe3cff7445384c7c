"""Flight-recorder overhead rung: the always-on ring buffer on vs off.

The flight recorder's promise (docs/observability.md) is a black box
that is ALWAYS ON in multi-rank runs — which only holds if recording
costs nothing measurable.  This rung times a 2-rank LocalTransport
``DistributedGPipe`` training step (llama blocks, the trace_report
fixture's sizing so cells are ~1-4ms) twice: bare, and with a
:class:`~torchgpipe_tpu.obs.flightrec.FlightRecorder` per rank PLUS a
running :class:`~torchgpipe_tpu.obs.flightrec.StallWatchdog` — the full
always-on configuration, ~50 recorded events per step (send enqueues,
recv wait/match pairs with mailbox depth, per-cell completions, loop
boundaries, arrival events from the mailbox).

Protocol is ``--obs-overhead``'s A/B-interleaved family, hardened for
the noisier two-rank step: each round times one bare and one
instrumented step back-to-back (PAIRED, so host scheduling drift hits
both sides of a ratio equally), the per-round ratios are medianed, and
the gate is median ratio − 1 **< 2%** (``BENCH_NOTES.md`` records the
measured figure).  Emits one JSON line (the bench contract)::

    env JAX_PLATFORMS=cpu python bench.py --flightrec-overhead
"""

from __future__ import annotations

import json
import time
from typing import Any, Callable, Dict, List, Optional, Tuple

OVERHEAD_GATE = 0.02  # <2% instrumented-over-bare, the documented bound
CHUNKS = 4
N_STAGES = 2
ROUNDS = 16  # per-arm measured steps (paired A/B per round)


def _build(with_recorder: bool) -> Tuple[Any, Any, Any, Any]:
    """One complete 2-rank in-process pipeline (both rank objects over a
    shared LocalTransport — the serialized single-process drive the
    schedule-verifier fixtures use), optionally instrumented."""
    import jax.numpy as jnp

    from torchgpipe_tpu.distributed import DistributedGPipe, LocalTransport
    from torchgpipe_tpu.models.transformer import TransformerConfig, llama
    from torchgpipe_tpu.obs.flightrec import FlightRecorder, StallWatchdog

    cfg = TransformerConfig(
        vocab=256, dim=128, n_layers=2 * N_STAGES, n_heads=4,
        n_kv_heads=2, mlp_ratio=2.0,
    )
    blocks = llama(cfg)[1:-1]  # uniform stack: no embed/head imbalance
    workers = [f"w{r}" for r in range(N_STAGES)]
    tag = "rec" if with_recorder else "bare"
    transport = LocalTransport()
    ranks: List[Any] = []
    recs: List[Any] = []
    watchdogs: List[Any] = []
    for r in range(N_STAGES):
        box = transport.register(f"{tag}-{workers[r]}")
        rec = (
            FlightRecorder(rank=r, worker=workers[r])
            if with_recorder else None
        )
        if rec is not None:
            recs.append(rec)
            # The full always-on configuration includes the liveness
            # alarm (a 30s watchdog never fires here; its polling is
            # part of the measured cost).
            watchdogs.append(StallWatchdog(rec, timeout=30.0).start())
        ranks.append(DistributedGPipe(
            blocks, r, [f"{tag}-{w}" for w in workers],
            [2] * N_STAGES, chunks=CHUNKS,
            transport=transport, mailbox=box, recorder=rec,
        ))
    x = jnp.zeros((8, 32, cfg.dim), jnp.float32)
    return ranks, x, recs, watchdogs


def _stepper(ranks: Any, x: Any) -> Callable[[int], float]:
    """Returns ``run(i) -> seconds`` for one blocked 2-rank training
    step driven serially in this process (rank 0 forward -> rank 1
    forward -> loss -> rank 1 backward -> rank 0 backward)."""
    import jax
    import jax.numpy as jnp

    def loss_fn(out: Any, tgt: Any) -> Any:
        return jnp.mean((out.astype(jnp.float32) - tgt) ** 2)

    in_spec = jax.ShapeDtypeStruct(x.shape, x.dtype)
    ps = [rk.init(jax.random.PRNGKey(0), in_spec) for rk in ranks]

    def run(i: int) -> float:
        t0 = time.perf_counter()
        ranks[0].forward(ps[0][0], ps[0][1], x)
        outs = ranks[1].forward(ps[1][0], ps[1][1], None)
        loss, gouts, _ = ranks[1].loss_grads(outs, x, loss_fn)
        g1, _ = ranks[1].backward(gouts)
        g0, _ = ranks[0].backward(None)
        jax.block_until_ready((loss, g0, g1))
        return time.perf_counter() - t0

    run(0)  # compile warmup, outside the timed rounds
    return run


def run() -> Dict[str, Any]:
    bare_ranks, x, _, _ = _build(with_recorder=False)
    inst_ranks, _, recs, watchdogs = _build(with_recorder=True)
    bare = _stepper(bare_ranks, x)
    inst = _stepper(inst_ranks, x)
    bare_times: List[float] = []
    inst_times: List[float] = []
    ratios: List[float] = []
    for i in range(1, ROUNDS + 1):
        tb = bare(i)
        to = inst(i)
        bare_times.append(tb)
        inst_times.append(to)
        # Paired ratio: the two steps ran back-to-back, so a host
        # scheduling spike inflates both sides instead of one arm.
        ratios.append(to / tb)
    for w in watchdogs:
        w.stop()
    bare_times.sort()
    inst_times.sort()
    ratios.sort()
    b = bare_times[len(bare_times) // 2]
    o = inst_times[len(inst_times) // 2]
    overhead = ratios[len(ratios) // 2] - 1.0
    events_per_step = sum(len(r.events()) for r in recs) // (ROUNDS + 1)
    assert all(r.events() for r in recs), (
        "instrumented arm recorded no flight events"
    )
    return {
        "metric": "flightrec overhead "
                  "[2-rank llama blocks, cpu, recorder+watchdog]",
        "value": round(overhead * 100, 3),
        "unit": "percent",
        "platform": "cpu",
        # Per-step blocking in both arms: neither can over-report.
        "validated": True,
        "gate_percent": OVERHEAD_GATE * 100,
        "pass": overhead < OVERHEAD_GATE,
        "bare_step_ms": round(b * 1e3, 3),
        "instrumented_step_ms": round(o * 1e3, 3),
        "events_per_step": events_per_step,
    }


def main() -> int:
    import jax

    jax.config.update("jax_platforms", "cpu")
    result = run()
    print(json.dumps(result), flush=True)
    return 0 if result["pass"] else 1


if __name__ == "__main__":
    from torchgpipe_tpu.utils.compile_cache import enable_compile_cache

    enable_compile_cache()
    raise SystemExit(main())
