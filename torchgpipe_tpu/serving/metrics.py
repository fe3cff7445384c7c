"""Serving observability: per-request latency and engine-level counters.

The two layers a decode server is judged by (Orca, OSDI '22; vLLM,
arXiv:2309.06180):

* **Per-request latency** — :class:`RequestTimes` tracks arrival →
  admission → first token → finish, from which the standard quantities
  derive: queue wait (admitted − arrival), TTFT (first token − arrival),
  TPOT (decode time per subsequent token).
* **Engine throughput** — per-iteration counters: how many compiled
  steps of each kind ran, how many slot-steps were occupied vs idle
  (occupancy is THE continuous-batching win: recycled slots keep the
  batch dim full), tokens emitted, transient retries, drains and the
  requests they preempted.

Re-based on :class:`torchgpipe_tpu.obs.MetricsRegistry`: every counter
is a registry series and TTFT/TPOT/queue-wait stream into registry
histograms, so ``snapshot()`` now also reports **p50/p95/p99 TTFT and
TPOT** and the whole set exports as JSONL or Prometheus text through
``metrics.registry``.  The public API is unchanged — attributes read
and assign as plain numbers, ``snapshot()`` keeps every legacy key.

Everything is host-side bookkeeping around the engine loop — no device
work, no effect on the two compiled programs.  ``snapshot()`` returns a
plain-dict view the tests read; the
``clock`` is injectable so tests can drive deterministic time.
"""

from __future__ import annotations

import dataclasses
import time
from typing import Any, Callable, Dict, List, Optional, Tuple

from torchgpipe_tpu.obs.registry import (
    MetricsRegistry,
    counter_property as _counter_property,
)


@dataclasses.dataclass
class RequestTimes:
    """Wall-clock milestones of one request (``None`` = not reached)."""

    rid: str
    arrival: float
    admitted: Optional[float] = None
    first_token: Optional[float] = None
    finished: Optional[float] = None
    tokens: int = 0
    status: str = "queued"   # queued|active|finished|cancelled|preempted

    @property
    def queue_wait(self) -> Optional[float]:
        return None if self.admitted is None else self.admitted - self.arrival

    @property
    def ttft(self) -> Optional[float]:
        """Time to first token, from arrival (includes queue wait)."""
        if self.first_token is None:
            return None
        return self.first_token - self.arrival

    @property
    def tpot(self) -> Optional[float]:
        """Time per output token over the decode phase (tokens after the
        first); ``None`` until finished or for single-token outputs."""
        if self.finished is None or self.first_token is None:
            return None
        if self.tokens <= 1:
            return None
        return (self.finished - self.first_token) / (self.tokens - 1)


class ServingMetrics:
    """Counters the serving engine maintains; see the module docstring.

    Series names are fixed (``serving_*``): ONE engine per shared
    registry — a second engine on the same registry merges into the
    same series (its snapshot then reports combined totals).  Give each
    engine its own registry, or its own ``ServingMetrics``, when you
    need them separable.
    """

    def __init__(self, clock: Callable[[], float] = time.monotonic,
                 registry: Optional[MetricsRegistry] = None) -> None:
        self._clock = clock
        self.registry = registry or MetricsRegistry(clock=clock)
        self.requests: Dict[str, RequestTimes] = {}
        reg = self.registry
        self._c_prefill = reg.counter(
            "serving_prefill_steps", help="compiled prefill steps run")
        self._c_decode = reg.counter(
            "serving_decode_steps", help="compiled decode steps run")
        self._c_ahead = reg.counter(
            "serving_steps_launched_ahead",
            help="compiled steps launched while the step before them "
                 "was still in flight (their host work ran behind the "
                 "device); over all steps: how often the overlap engages")
        self._c_occupied = reg.counter(
            "serving_occupied_slot_steps",
            help="slot-steps doing useful work")
        self._c_total = reg.counter(
            "serving_total_slot_steps",
            help="rows the steps' programs had (a decode step: the "
                 "pool's slots; a prefill step: its row capacity R)")
        self._c_prefill_rows = reg.counter(
            "serving_prefill_rows",
            help="rows of the compact prefill program that carried a "
                 "prompt chunk (n_valid > 0), summed over prefill steps")
        self._c_prefill_capacity = reg.counter(
            "serving_prefill_row_capacity",
            help="rows the compact prefill program has (R a step); "
                 "rows over capacity is its fill share")
        self._c_prefill_deferred = reg.counter(
            "serving_prefill_deferred_rows",
            help="pending prompts a prefill step left for the next one "
                 "(more pending than R), summed over prefill steps")
        self._c_attend_read = reg.counter(
            "serving_attend_rows_read",
            help="cache rows (block-rounded) a layer's attention reads, "
                 "summed over the rows of every step: what the decode "
                 "kernel fetches on a TPU, the capacity where the dense "
                 "path runs")
        self._c_attend_capacity = reg.counter(
            "serving_attend_rows_capacity",
            help="rows of every step x max_len; read over capacity is "
                 "the share of the pool a step's attention reads")
        # The same pair by the kind of layer (one layer of that kind):
        # ``window`` (its banks a ring where the model mixes layer
        # types) and ``full``.  The pair above sums one layer of each.
        self._c_attend_kind = {
            (kind, what): reg.counter(
                f"serving_attend_rows_{what}_{kind}",
                help=f"serving_attend_rows_{what} of one {kind}-"
                     "attention layer")
            for kind in ("window", "full") for what in ("read", "capacity")
        }
        # A hybrid model's recurrent state (``kv_cache.HybridCache``):
        # the bytes of it each kind of step reads and writes (a row that
        # runs writes its slot's state, and reads it unless its frontier
        # is 0), and the slots whose state admission zeroed.
        self._c_state_bytes = {
            kind: reg.counter(
                f"serving_state_bytes_{kind}",
                help=f"recurrent-state bytes (conv tails and states of "
                     f"every mixer layer) the {kind} steps read and wrote")
            for kind in ("prefill", "decode")
        }
        self._c_state_zeroed = reg.counter(
            "serving_state_zeroed_slots",
            help="admissions whose slot's recurrent state starts from "
                 "zero (its last tenant's state is never read)")
        # Bytes the pool's banks pin, by the same kinds (the engine
        # sets it once: ``CachePool.bytes_by_kind``).
        self.pool_bytes: Dict[str, int] = {}
        self._c_tokens = reg.counter(
            "serving_tokens_out", help="tokens emitted")
        self._c_retries = reg.counter(
            "serving_retries", help="transient step retries")
        self._c_drains = reg.counter(
            "serving_drains", help="cooperative drains")
        self._c_preempted = reg.counter(
            "serving_preempted_requests",
            help="unfinished requests at drain time")
        self._c_prefix_hits = reg.counter(
            "serving_prefix_hits",
            help="admissions that reused a cached KV prefix")
        self._c_prefix_tokens = reg.counter(
            "serving_prefix_reused_tokens",
            help="prompt tokens absorbed by KV-prefix copies "
                 "(prefill FLOPs avoided)")
        self._c_migr_out = reg.counter(
            "serving_migrations_out",
            help="requests handed off to a decode replica at prompt "
                 "completion (phase-disaggregated fleets)")
        self._c_migr_in = reg.counter(
            "serving_migrations_in",
            help="requests ingested mid-stream from a prefill replica")
        self._c_moe_routed = reg.counter(
            "serving_moe_routed_assignments",
            help="(position, expert) assignments the routers made: "
                 "positions x top_k x expert layers")
        self._c_moe_held = reg.counter(
            "serving_moe_held_assignments",
            help="assignments to experts this engine holds "
                 "(MoEConfig.held): the routed work done here")
        # Per step kind: the sums over steps of a step's largest and
        # mean held-expert token count, and the steps summed.
        self._moe_load = {
            kind: [reg.counter(f"serving_moe_{kind}_expert_tokens_{what}",
                               help=f"sum over {kind} steps of {text}")
                   for what, text in (
                       ("max", "the fullest held expert's tokens"),
                       ("mean", "the mean held expert's tokens"),
                       ("steps", "1 (steps with expert counts)"),
                       ("touched", "the held experts given a token"))]
            for kind in ("prefill", "decode")
        }
        self._h_ttft = reg.histogram(
            "serving_ttft_seconds", help="time to first token (arrival→)")
        self._h_tpot = reg.histogram(
            "serving_tpot_seconds", help="time per output token (decode)")
        self._h_queue = reg.histogram(
            "serving_queue_wait_seconds", help="arrival→admission wait")
        self.started = clock()

    # Legacy attribute API (all read/assignable ints), registry-backed
    # through the shared facade (obs.registry.counter_property).
    prefill_steps = _counter_property("_c_prefill")
    decode_steps = _counter_property("_c_decode")
    steps_launched_ahead = _counter_property("_c_ahead")
    occupied_slot_steps = _counter_property("_c_occupied")
    total_slot_steps = _counter_property("_c_total")
    prefill_rows = _counter_property("_c_prefill_rows")
    prefill_row_capacity = _counter_property("_c_prefill_capacity")
    prefill_deferred_rows = _counter_property("_c_prefill_deferred")
    attend_rows_read = _counter_property("_c_attend_read")
    attend_rows_capacity = _counter_property("_c_attend_capacity")
    tokens_out = _counter_property("_c_tokens")
    retries = _counter_property("_c_retries")
    drains = _counter_property("_c_drains")
    preempted_requests = _counter_property("_c_preempted")
    prefix_hits = _counter_property("_c_prefix_hits")
    prefix_reused_tokens = _counter_property("_c_prefix_tokens")
    migrations_out = _counter_property("_c_migr_out")
    migrations_in = _counter_property("_c_migr_in")
    moe_routed_assignments = _counter_property("_c_moe_routed")
    moe_held_assignments = _counter_property("_c_moe_held")
    state_zeroed_slots = _counter_property("_c_state_zeroed")

    # ------------------------------------------------------------------ #
    # request lifecycle                                                  #
    # ------------------------------------------------------------------ #

    def now(self) -> float:
        return self._clock()

    def arrived(self, rid: str) -> None:
        self.requests[rid] = RequestTimes(rid=rid, arrival=self._clock())

    def admitted(self, rid: str) -> None:
        r = self.requests[rid]
        r.admitted = self._clock()
        r.status = "active"
        wait = r.queue_wait
        if wait is not None:
            self._h_queue.observe(wait)

    def token(self, rid: str) -> None:
        r = self.requests[rid]
        t = self._clock()
        if r.first_token is None:
            r.first_token = t
            ttft = r.ttft
            if ttft is not None:
                self._h_ttft.observe(ttft)
        r.tokens += 1
        self._c_tokens.inc()

    def ingested(self, rid: str) -> None:
        """A migrated request arriving mid-stream (disaggregated
        serving): its FIRST token was emitted on the donor prefill
        replica, so this engine's first emission must count toward
        TPOT, never as a second TTFT — ``first_token`` is stamped now
        and ``tokens`` starts at the one token already streamed."""
        r = self.requests[rid]
        t = self._clock()
        if r.admitted is None:
            r.admitted = t
        r.status = "active"
        r.first_token = t
        r.tokens = 1
        self._c_migr_in.inc()

    def migrated_out(self, rid: str) -> None:
        """The donor side of :meth:`ingested`: the request left this
        replica at prompt completion.  No latency histogram fires —
        the stream continues elsewhere; only the handoff is counted."""
        r = self.requests[rid]
        r.status = "migrated"
        self._c_migr_out.inc()

    def finished(self, rid: str, status: str = "finished") -> None:
        r = self.requests[rid]
        r.finished = self._clock()
        r.status = status
        tpot = r.tpot
        if tpot is not None and status == "finished":
            self._h_tpot.observe(tpot)

    # ------------------------------------------------------------------ #
    # engine iterations                                                  #
    # ------------------------------------------------------------------ #

    def step(self, kind: str, active_slots: int, num_slots: int,
             deferred: int = 0,
             attended: Optional[Dict[str, Tuple[int, int]]] = None,
             ahead: bool = False, state_bytes: int = 0) -> None:
        """One compiled step, counted when it is LAUNCHED (``ahead``:
        while the step before it was still in flight; the tokens it
        samples are counted when they are delivered, a step later):
        ``active_slots`` of the ``num_slots`` rows
        its program has did useful work.  A prefill step's program is
        COMPACT (``num_slots`` is its row capacity ``R``, not the
        pool's size) and may leave ``deferred`` pending prompts to the
        next prefill step.  ``attended`` is ``(rows read, row
        capacity)`` of a layer's cache attention in this step
        (``models.generation.attend_rows_counter``) by the kind of
        layer, ``{'window': (read, capacity), 'full': ...}``, one layer
        of each kind the model has.  ``state_bytes``: the recurrent
        state the step reads and writes (a hybrid model's)."""
        self._c_ahead.inc(int(ahead))
        self._c_state_bytes[kind].inc(state_bytes)
        for layers, (read, cap) in (attended or {}).items():
            self._c_attend_kind[layers, "read"].inc(read)
            self._c_attend_kind[layers, "capacity"].inc(cap)
            self._c_attend_read.inc(read)
            self._c_attend_capacity.inc(cap)
        if kind == "prefill":
            self._c_prefill.inc()
            self._c_prefill_rows.inc(active_slots)
            self._c_prefill_capacity.inc(num_slots)
            self._c_prefill_deferred.inc(deferred)
        else:
            self._c_decode.inc()
        self._c_occupied.inc(active_slots)
        self._c_total.inc(num_slots)

    def moe_step(self, kind: str, routed: int, counts: Any) -> None:
        """One step's expert load: ``routed`` assignments made, and
        ``counts [expert layers, held]`` the tokens each held expert
        received."""
        self._c_moe_routed.inc(routed)
        self._c_moe_held.inc(int(counts.sum()))
        peak, mean, steps, touched = self._moe_load[kind]
        peak.inc(float(counts.max()))
        mean.inc(float(counts.mean()))
        steps.inc()
        touched.inc(int((counts > 0).sum()))

    def moe_expert_tokens(self, kind: str) -> Dict[str, float]:
        """Per ``kind`` step ('prefill' | 'decode'): the fullest and the
        mean held expert's tokens a step (0 before any such step), and
        the ``steps`` they are means over, and the held experts a step
        gave a token (``touched``, over every expert layer)."""
        peak, mean, steps, touched = (c.value() for c in self._moe_load[kind])
        n = max(steps, 1)
        return {"max": peak / n, "mean": mean / n, "steps": steps,
                "touched": touched / n}

    def drained(self, unfinished: int) -> None:
        self._c_drains.inc()
        self._c_preempted.inc(unfinished)

    def prefix_hit(self, reused_tokens: int) -> None:
        """One admission reused ``reused_tokens`` prompt tokens from the
        KV prefix cache (prefill work avoided)."""
        self._c_prefix_hits.inc()
        self._c_prefix_tokens.inc(reused_tokens)

    # ------------------------------------------------------------------ #
    # snapshot                                                           #
    # ------------------------------------------------------------------ #

    @property
    def engine_steps(self) -> int:
        return self.prefill_steps + self.decode_steps

    @property
    def prefill_fill_share(self) -> float:
        """Fraction of the compact prefill program's rows that carried
        a prompt chunk, over all prefill steps."""
        if self.prefill_row_capacity == 0:
            return 0.0
        return self.prefill_rows / self.prefill_row_capacity

    @property
    def occupancy(self) -> float:
        """Mean fraction of slot-steps doing useful work."""
        if self.total_slot_steps == 0:
            return 0.0
        return self.occupied_slot_steps / self.total_slot_steps

    def snapshot(self) -> Dict[str, Any]:
        """A plain-dict view: engine aggregates, latency percentiles
        (p50/p95/p99 TTFT and TPOT from the registry histograms — None
        until a request reaches the milestone) + per-request rows."""
        now = self._clock()
        elapsed = max(now - self.started, 1e-9)
        per_request: List[Dict[str, Any]] = []
        for r in self.requests.values():
            per_request.append({
                "rid": r.rid,
                "status": r.status,
                "tokens": r.tokens,
                "queue_wait": r.queue_wait,
                "ttft": r.ttft,
                "tpot": r.tpot,
            })
        return {
            "engine_steps": self.engine_steps,
            "prefill_steps": self.prefill_steps,
            "decode_steps": self.decode_steps,
            "steps_launched_ahead": self.steps_launched_ahead,
            "tokens_out": self.tokens_out,
            "tokens_per_sec": self.tokens_out / elapsed,
            "tokens_per_step": (
                self.tokens_out / self.engine_steps
                if self.engine_steps else 0.0
            ),
            "occupancy": self.occupancy,
            "prefill_rows": self.prefill_rows,
            "prefill_row_capacity": self.prefill_row_capacity,
            "prefill_deferred_rows": self.prefill_deferred_rows,
            "prefill_fill_share": self.prefill_fill_share,
            "attend_rows_read": self.attend_rows_read,
            "attend_rows_capacity": self.attend_rows_capacity,
            "attend_rows_by_kind": {
                kind: {what: int(self._c_attend_kind[kind, what].value())
                       for what in ("read", "capacity")}
                for kind in ("window", "full")},
            "kv_pool_bytes": dict(self.pool_bytes),
            "state_bytes": {k: c.value()
                            for k, c in self._c_state_bytes.items()},
            "state_zeroed_slots": self.state_zeroed_slots,
            "retries": self.retries,
            "drains": self.drains,
            "preempted_requests": self.preempted_requests,
            "prefix_hits": self.prefix_hits,
            "prefix_reused_tokens": self.prefix_reused_tokens,
            "migrations_out": self.migrations_out,
            "migrations_in": self.migrations_in,
            "moe_routed_assignments": self.moe_routed_assignments,
            "moe_held_assignments": self.moe_held_assignments,
            "moe_expert_tokens_max": {
                k: self.moe_expert_tokens(k)["max"] for k in self._moe_load},
            "moe_expert_tokens_mean": {
                k: self.moe_expert_tokens(k)["mean"] for k in self._moe_load},
            "ttft_p50": self._h_ttft.percentile(0.50),
            "ttft_p95": self._h_ttft.percentile(0.95),
            "ttft_p99": self._h_ttft.percentile(0.99),
            "tpot_p50": self._h_tpot.percentile(0.50),
            "tpot_p95": self._h_tpot.percentile(0.95),
            "tpot_p99": self._h_tpot.percentile(0.99),
            "queue_wait_p50": self._h_queue.percentile(0.50),
            "queue_wait_p95": self._h_queue.percentile(0.95),
            "requests": per_request,
        }


__all__ = ["RequestTimes", "ServingMetrics"]
