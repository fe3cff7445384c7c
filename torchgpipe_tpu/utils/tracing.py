"""Pipeline timeline tracing.

Counterpart of the reference's timeline/ablation tooling (SURVEY.md §5:
benchmarks/unet-timeline samples GPU utilization from a side process;
the balancer has its own profiler).  TPU-native redesign: the engine itself
records per-cell (micro-batch, stage) dispatch/ready intervals — no side
process, no `nvidia-smi` — plus a thin wrapper over the JAX device profiler
for XLA-level traces viewable in TensorBoard/Perfetto.

Usage::

    tracer = Timeline()
    model = GPipe(layers, balance, chunks=8, tracer=tracer)
    model.value_and_grad(...)
    print(tracer.summary())
    tracer.events  # [(name, stage, mbatch, t_start, t_end), ...]

``Timeline.sync=True`` turns the tracer into the *ablation* tool: every cell
is forced to completion before the next is dispatched, serializing the
pipeline — measuring how much of the throughput comes from cross-stage
overlap (the question the reference's unet-timeline experiments answer by
monkey-patching deps/streams, benchmarks/unet-timeline/main.py:22-75).

The same object is the program's span spine.  ``Timeline.span(name)`` is a
context manager that opens a ``jax.profiler.TraceAnnotation`` (so the span
lands in any running profiler session, on the device trace's own clock)
and on exit appends one event with a sequence number and its parent's.
``serving.Engine`` and ``SpmdGPipe`` record into :func:`default_timeline`,
a bounded ring that is always on::

    with tracer.span("engine.step"):
        with tracer.span("engine.admit"):
            ...
            tracer.annotate(admitted=2)

Time the program did not choose to spend lands in the default timeline
too, as closed events (``Timeline.mark``): ``gc.collect`` for a
collection of the interpreter's, ``xla.compile`` for each phase of a
compile (:class:`_ProcessMarks`), each the child of the span it fell into.
"""

from __future__ import annotations

import collections
import contextlib
import dataclasses
import gc
import itertools
import threading
import time
from typing import (Any, Callable, Deque, Dict, Iterator, List, Optional,
                    Tuple, Union)

import jax


@dataclasses.dataclass(slots=True)
class TimelineEvent:
    name: str  # "fwd" | "bwd" | "loss" | "engine.step" | ...
    stage: int
    mbatch: int
    t_start: float
    t_end: float
    # Spans only (``Timeline.span``); a ``record``-ed cell keeps the
    # defaults.  ``seq`` counts spans in the order they OPENED, ``parent``
    # is the ``seq`` of the span open around this one on its thread.
    seq: int = -1
    parent: int = -1
    fields: Optional[Dict[str, Any]] = None

    @property
    def duration(self) -> float:
        return self.t_end - self.t_start


class _Span:
    """One open span (see :meth:`Timeline.span`).  A class, not a
    generator: entering and leaving cost two clock reads, one
    ``TraceAnnotation`` and one append."""

    __slots__ = ("_tl", "name", "stage", "mbatch", "fields", "seq",
                 "parent", "_t_start", "_ann", "_late", "_dropped")

    def __init__(self, tl: "Timeline", name: str, stage: int, mbatch: int,
                 fields: Optional[Dict[str, Any]]) -> None:
        self._tl = tl
        self.name = name
        self.stage = stage
        self.mbatch = mbatch
        self.fields = fields
        self._late: Optional[Dict[str, Any]] = None
        self._dropped = False

    def __enter__(self) -> "_Span":
        tl = self._tl
        stack = tl._stack()
        self.seq = next(tl._seq)
        self.parent = stack[-1].seq if stack else -1
        stack.append(self)
        # The fields go to the profiler too: TraceMe leaves keyword
        # arguments unformatted while no session runs (0.2 us for two).
        self._ann = (jax.profiler.TraceAnnotation(self.name, **self.fields)
                     if self.fields else
                     jax.profiler.TraceAnnotation(self.name))
        self._ann.__enter__()
        self._t_start = time.perf_counter() - tl._t0
        return self

    def wait(self, out: Any) -> Any:
        """Block on ``out`` inside the span where the timeline is
        ``sync`` (true device time), else return it at once."""
        if self._tl.sync and out is not None:
            jax.block_until_ready(out)
        return out

    def drop(self) -> None:
        """Leave no event behind (an iteration that ran nothing)."""
        self._dropped = True

    def __exit__(self, *exc: Any) -> None:
        tl = self._tl
        t_end = time.perf_counter() - tl._t0
        fields = self.fields
        if self._late:
            self._ann.set_metadata(**self._late)
            fields = dict(fields or (), **self._late)
        self._ann.__exit__(*exc)
        tl._stack().pop()
        if not self._dropped:
            tl._append(TimelineEvent(
                self.name, self.stage, self.mbatch, self._t_start, t_end,
                self.seq, self.parent, fields,
            ))


class Timeline:
    """The trace spine: per-cell dispatch recorder of the MPMD engine
    (:meth:`record`) and span recorder of the serving engine and the SPMD
    train step (:meth:`span`).  One thread records and reads at a time:
    spans of several threads nest per thread, but reading while another
    thread appends is not guarded.

    With ``sync=False`` (default) the recorded interval is the *dispatch*
    cost (JAX is async; device work overlaps).  With ``sync=True`` each cell
    is blocked to completion — true per-cell device time, zero overlap: the
    serialized-pipeline ablation baseline.
    """

    def __init__(self, sync: bool = False,
                 capacity: Optional[int] = None) -> None:
        self.sync = sync
        # Unbounded: a list.  ``capacity=N``: a ring of the newest N
        # events; ``since`` tells a reader whether what it asks for was
        # pushed out.
        self.events: Union[List[TimelineEvent], Deque[TimelineEvent]] = (
            [] if capacity is None else collections.deque(maxlen=capacity)
        )
        self.capacity = capacity
        self._open = threading.local()
        self.reset()

    def reset(self) -> None:
        self.events.clear()
        self._t0 = time.perf_counter()
        self._seq = itertools.count()
        self._dropped_seq = -1      # the highest ``seq`` the ring pushed out

    # ------------------------------------------------------------------ #
    # spans                                                              #
    # ------------------------------------------------------------------ #

    def span(self, name: str, stage: int = -1, mbatch: int = -1,
             **fields: Any) -> _Span:
        """A context manager recording one span: it opens a
        ``jax.profiler.TraceAnnotation(name, **fields)`` — the span shows
        in any running profiler session beside the device's lines — and
        on exit appends one :class:`TimelineEvent` carrying its ``seq``,
        its ``parent`` (the span open around it on this thread, -1 for
        none) and ``fields``."""
        return _Span(self, name, stage, mbatch, fields or None)

    def annotate(self, **fields: Any) -> None:
        """Add fields to the innermost span open on this thread: what is
        known only once the work is under way (rows admitted, tokens
        emitted).  No-op outside a span."""
        stack = self._stack()
        if stack:
            stack[-1]._late = dict(stack[-1]._late or (), **fields)

    def mark(self, name: str, t_start: float, t_end: float,
             **fields: Any) -> None:
        """Append one CLOSED event, for work whose end is the first the
        program hears of it (a collection, a compile): what a span's
        exit appends, with the next ``seq`` and, as ``parent``, the span
        open on the calling thread.  ``t_start`` and ``t_end`` are
        ``time.perf_counter()`` readings."""
        stack = self._stack()
        self._append(TimelineEvent(
            name, -1, -1, t_start - self._t0, t_end - self._t0,
            next(self._seq), stack[-1].seq if stack else -1, fields or None,
        ))

    def since(self, seq: int) -> Optional[List[TimelineEvent]]:
        """The spans whose ``seq`` is at least ``seq``, oldest first, or
        ``None`` where a bounded timeline has pushed one of them out."""
        if self._dropped_seq >= seq:
            return None
        return [e for e in list(self.events) if e.seq >= seq]

    def _stack(self) -> List[_Span]:
        try:
            return self._open.stack
        except AttributeError:
            self._open.stack = []
            return self._open.stack

    def _append(self, event: TimelineEvent) -> None:
        events = self.events
        if self.capacity is not None and len(events) == self.capacity:
            self._dropped_seq = max(self._dropped_seq, events[0].seq)
        events.append(event)

    def record(
        self,
        name: str,
        stage: int,
        mbatch: int,
        out: Any = None,
        settle: float = 0.0,
    ) -> Any:
        """Record one cell and return ``out`` (so engines can chain
        ``y = tracer.record("fwd", j, i, y)``); blocks on ``out`` when
        ``sync`` is set.  ``settle`` (seconds) sleeps INSIDE the span,
        after the block: the deterministic-straggler slot the MPMD
        schedulers feed from ``resilience.faults.cell_delay_s`` — a
        ``slow_at`` fault plan then both delays the run and shows up in
        the measured per-cell durations the reconciliation reads."""
        t_start = time.perf_counter() - self._t0
        if self.sync and out is not None:
            jax.block_until_ready(out)
        if settle > 0.0:
            time.sleep(settle)
        t_end = time.perf_counter() - self._t0
        self._append(TimelineEvent(name, stage, mbatch, t_start, t_end))
        return out

    # ------------------------------------------------------------------ #

    def to_chrome_trace(self, path: str) -> None:
        """Write the recorded cells as a Chrome trace-event JSON.

        Open in ``chrome://tracing`` or https://ui.perfetto.dev: one row
        (tid) per pipeline stage, one slice per (cell, phase) — the visual
        the reference approximates with its nvidia-smi utilization sampler
        (reference: benchmarks/unet-timeline/gpu_utils.py:8-69).  With
        ``sync=True`` slices are true per-cell device durations; without,
        they show the dispatch timeline (overlap visible as stacking).
        """
        import json

        trace = [
            {
                "name": "thread_name",
                "ph": "M",
                "pid": 0,
                "tid": stage,
                "args": {
                    # stage -1 is the SPMD engines' scan-granularity row
                    # (whole compiled-step spans; the scanned cells are
                    # not host-visible — obs.device_trace shows the
                    # XLA interior).
                    "name": f"stage {stage}" if stage >= 0 else "program",
                },
            }
            for stage in sorted({e.stage for e in self.events})
        ]
        trace += [
            {
                "name": f"{e.name} mb{e.mbatch}",
                "ph": "X",
                "pid": 0,
                "tid": e.stage,
                "ts": e.t_start * 1e6,   # microseconds
                "dur": max(e.duration * 1e6, 0.01),
                "args": {
                    "stage": e.stage,
                    "micro_batch": e.mbatch,
                    "kind": e.name,
                },
            }
            for e in self.events
        ]
        with open(path, "w") as f:
            json.dump({"traceEvents": trace, "displayTimeUnit": "ms"}, f)

    def by_stage(self) -> dict:
        out: dict = {}
        for ev in self.events:
            out.setdefault(ev.stage, []).append(ev)
        return out

    def summary(self) -> str:
        if not self.events:
            return "timeline: no events"
        total = max(ev.t_end for ev in self.events) - min(
            ev.t_start for ev in self.events
        )
        lines = [
            f"timeline: {len(self.events)} cells over {total * 1e3:.1f}ms "
            f"({'sync/serialized' if self.sync else 'async dispatch'})"
        ]
        for stage, evs in sorted(self.by_stage().items()):
            busy = sum(ev.duration for ev in evs)
            lines.append(
                f"  stage {stage}: {len(evs)} cells, "
                f"busy {busy * 1e3:.1f}ms ({100 * busy / total:.0f}%)"
            )
        return "\n".join(lines)


# The process-wide default: what ``serving.Engine`` and ``SpmdGPipe`` (with
# no ``tracer=``) record into.  Always on, so that a step that stalls
# outside any profiler session still names its phase.  Sized from the
# fastest serving step on record (``step_wall_ms.backlog`` 7.43 ms: ledger,
# PR 35, ``mistral-7b.serve-backlog``): 135 steps/s x 7 spans a step is
# 41.5k events in a 44 s window; 131,072 hold a window of steps down to
# 2.4 ms (set-up's events go first).  About 32 MiB when full.
DEFAULT_CAPACITY = 131072
_DEFAULT = Timeline(capacity=DEFAULT_CAPACITY)


def default_timeline() -> Timeline:
    """The one bounded, always-on timeline of this process."""
    return _DEFAULT


class _ProcessMarks:
    """The two process-wide sources of ``Timeline.mark``: time the program
    did not choose to spend, recorded where it fell.

    ``gc.collect`` (from ``gc.callbacks``): a generation-2 collection, and
    any collection of ``GC_FLOOR_S`` or more, with ``generation`` and
    ``collected``.  A generation-2 collection also holds a
    ``TraceAnnotation`` open from start to stop, so a profile shows it on
    the device trace's clock.  A short young collection, the common case,
    costs two clock reads and a compare and leaves nothing.

    ``xla.compile`` (from ``jax.monitoring``'s duration events): one event
    a phase of a compile, with ``phase`` (``trace`` / ``lower`` /
    ``backend``) and ``fun``; on ``backend`` also ``cache_hit``, 1 where
    the program was loaded from the persistent cache and not compiled.
    jax reports a phase when it ends, so the start is the end less the
    seconds reported.  jax traces a ``jit`` called inside a ``jit`` inside
    the outer trace: the inner event lies within the outer one's interval,
    and a sum over events counts that time twice.
    """

    GC_FLOOR_S = 1e-3
    PHASES = {
        "/jax/core/compile/jaxpr_trace_duration": "trace",
        "/jax/core/compile/jaxpr_to_mlir_module_duration": "lower",
        "/jax/core/compile/backend_compile_duration": "backend",
    }
    CACHE_HIT = "/jax/compilation_cache/cache_hits"

    def __init__(self, timeline: Timeline) -> None:
        self._tl = timeline
        self._installed = False
        self._gc_start = 0.0
        self._gc_ann: Optional[jax.profiler.TraceAnnotation] = None
        # Whether the backend phase now under way on a thread found its
        # program in the persistent cache.
        self._cache = threading.local()

    def install(self) -> None:
        """Start recording.  A second call changes nothing."""
        if self._installed:
            return
        self._installed = True
        gc.callbacks.append(self._on_gc)
        jax.monitoring.register_event_listener(self._on_event)
        jax.monitoring.register_event_duration_secs_listener(
            self._on_duration)

    def _on_gc(self, phase: str, info: Dict[str, int]) -> None:
        if phase == "start":
            if info["generation"] == 2:
                self._gc_ann = jax.profiler.TraceAnnotation(
                    "gc.collect", generation=2)
                self._gc_ann.__enter__()
            self._gc_start = time.perf_counter()
            return
        t_end = time.perf_counter()
        ann, self._gc_ann = self._gc_ann, None
        if ann is not None:
            ann.__exit__(None, None, None)
        elif t_end - self._gc_start < self.GC_FLOOR_S:
            return
        self._tl.mark("gc.collect", self._gc_start, t_end,
                      generation=info["generation"],
                      collected=info["collected"])

    def _on_event(self, event: str, **_: Any) -> None:
        if event == self.CACHE_HIT:
            self._cache.hit = True

    def _on_duration(self, event: str, secs: float, **kwargs: Any) -> None:
        phase = self.PHASES.get(event)
        if phase is None:
            return
        t_end = time.perf_counter()
        fields = {"phase": phase, "fun": kwargs.get("fun_name")}
        if phase == "backend":
            fields["cache_hit"] = int(getattr(self._cache, "hit", False))
            self._cache.hit = False
        self._tl.mark("xla.compile", t_end - secs, t_end, **fields)


# Installed with the ring they record into, when this module is first
# imported: set-up's compiles come before the first engine is built.
_PROCESS_MARKS = _ProcessMarks(_DEFAULT)
_PROCESS_MARKS.install()


@contextlib.contextmanager
def device_trace(logdir: str) -> Iterator[None]:
    """XLA-level device profile (TensorBoard `logdir`), wrapping
    :func:`jax.profiler.start_trace` — the TPU-native replacement for the
    reference's `nvidia-smi` sampler."""
    jax.profiler.start_trace(logdir)
    try:
        yield
    finally:
        jax.profiler.stop_trace()


def simulate_pipeline(
    events: List[TimelineEvent],
    n_stages: int,
    schedule: str = "fill_drain",
    virtual_stages: int = 1,
) -> Optional[Tuple[float, float, float]]:
    """Project measured per-cell times onto a pipeline schedule.

    Takes a *sync* timeline (true per-cell device durations) and computes
    the makespan the schedule would achieve with perfect overlap.  For
    ``'fill_drain'``: ``finish(i, j) = max(finish(i-1, j), finish(i, j-1))
    + t(i, j)`` per phase, forward and backward separated by the loss
    barrier.  For ``'1f1b'``: each stage executes its PipeDream-flush op
    order (warm-up ``min(m, n-j)`` forwards, then strict bwd/fwd
    alternation — the same order the MPMD engine dispatches,
    pipeline.py ``run_train_1f1b``) with no global barrier; an op starts
    when its stage is free AND its producer finished (fwd needs the
    upstream fwd; bwd needs the downstream bwd, or the same cell's fwd on
    the last stage).  For ``'interleaved'`` the measured stages are read
    as the ``n_stages`` GLOBAL blocks of a virtual-stage layout: pass
    ``virtual_stages=v`` and the projection lays block ``g`` on device
    ``g % (n_stages//v)`` as chunk ``g // (n_stages//v)`` (the Megatron
    wrap-around), answering "what would this measured run cost
    interleaved on n/v devices?".  For ``'zb'`` the measured fused
    backward is split into equal B/W halves and scheduled by the
    zero-bubble op order — "what would the split backward buy on this
    measured run?" (the 50/50 split is the dense-layer FLOP model; state
    it when quoting).  Returns ``(makespan_seconds,
    busy_fraction, bubble_fraction)``; the bubble can be compared against
    the analytic uniform-cell figure — the gap is stage imbalance.
    """
    if schedule not in ("fill_drain", "1f1b", "interleaved", "zb"):
        raise ValueError(
            "schedule must be 'fill_drain', '1f1b', 'interleaved' or 'zb'"
        )
    if schedule == "interleaved":
        if virtual_stages < 2:
            raise ValueError("interleaved projection needs virtual_stages >= 2")
        if n_stages % virtual_stages != 0:
            raise ValueError(
                f"n_stages ({n_stages}) must divide by virtual_stages "
                f"({virtual_stages}): measured stages become the global "
                "blocks of the virtual layout"
            )
    elif virtual_stages != 1:
        raise ValueError("virtual_stages only applies to 'interleaved'")
    # Aggregate/barrier spans (negative micro-batch or stage: the
    # fill-drain engine's gathered-loss barrier at mb -1, the SPMD
    # engines' whole-program "step" spans at stage -1) are not per-cell
    # observations — the projection is defined over cells only.
    events = [e for e in events if e.mbatch >= 0 and e.stage >= 0]
    if not events:
        return None
    # A timeline spanning several training steps observes each (i, j) cell
    # repeatedly; average the observations into one representative step so
    # makespan and busy time describe the same single step.
    sums: dict = {}
    counts: dict = {}
    for ev in events:
        key = (ev.name, ev.mbatch, ev.stage)
        sums[key] = sums.get(key, 0.0) + ev.duration
        counts[key] = counts.get(key, 0) + 1
    by_phase: dict = {}
    for (name, i, j), total in sums.items():
        by_phase.setdefault(name, {})[(i, j)] = total / counts[(name, i, j)]

    if schedule == "1f1b":
        makespan = _simulate_1f1b(by_phase, n_stages)
    elif schedule == "interleaved":
        makespan = _simulate_interleaved(by_phase, n_stages, virtual_stages)
    elif schedule == "zb":
        makespan = _simulate_zb(by_phase, n_stages)
    elif schedule == "fill_drain":
        makespan = 0.0
        for cells in by_phase.values():
            m = 1 + max(i for i, _ in cells)
            n = 1 + max(j for _, j in cells)
            finish = [[0.0] * n for _ in range(m)]
            for i in range(m):
                for j in range(n):
                    prev = max(
                        finish[i - 1][j] if i else 0.0,
                        finish[i][j - 1] if j else 0.0,
                    )
                    finish[i][j] = prev + cells.get((i, j), 0.0)
            makespan += finish[m - 1][n - 1]
    if makespan is None or makespan <= 0:
        return None
    # busy/bubble are per EXECUTION UNIT: devices (n/v of the measured
    # global blocks) for the interleaved projection, stages otherwise.
    units = (
        n_stages // virtual_stages if schedule == "interleaved" else n_stages
    )
    busy = sum(
        cell for cells in by_phase.values() for cell in cells.values()
    ) / (units * makespan)
    return makespan, busy, 1.0 - busy


@dataclasses.dataclass
class ScheduleProjection:
    """One row of :func:`recommend_schedule`'s ranking."""

    schedule: str  # 'fill_drain' | '1f1b' | 'zb' | 'interleaved'
    devices: int  # device count the projection assumes
    virtual_stages: int  # 1 except for 'interleaved'
    makespan: float
    busy: float
    bubble: float
    note: str  # memory character / projection caveat


def recommend_schedule(
    events: List[TimelineEvent],
    n_stages: int,
    virtual_stages: Tuple[int, ...] = (2,),
) -> List[ScheduleProjection]:
    """Rank the engine's schedules on one measured timeline.

    The reference auto-tunes *balance* from a profile
    (``torchgpipe/balance/__init__.py:38-80``) but offers a
    single schedule; this framework has four, and the right one depends on
    the measured cell times — so the schedule choice gets the same
    profile-then-decide treatment.  Feed the ``sync=True`` timeline of one
    training step (true per-cell device durations) and every applicable
    schedule is projected through :func:`simulate_pipeline`:

    * rows with ``devices == n_stages`` come first, sorted by projected
      makespan — ``rows[0]`` is the recommendation at the measured device
      count;
    * ``'interleaved'`` rows (one per ``v`` in ``virtual_stages`` that
      divides ``n_stages``) follow, also makespan-sorted: they answer
      "what if these measured stages were the global blocks of a
      virtual-stage layout on ``n_stages // v`` devices?" — fewer chips,
      not a same-budget alternative, hence ranked apart;
    * schedules whose projection needs phases the timeline lacks (no
      ``bwd`` events → no 1f1b/zb/interleaved projection: their op
      tables interleave backward cells) and interleaved configs the
      measurement cannot support (micro-batch count not divisible by the
      projected device count) are silently omitted.

    Each row's ``note`` carries the schedule's memory character and any
    projection caveat (zb's 50/50 B/W split model), so the ranking is
    never quoted without its assumptions.

    Only ``fwd``/``bwd`` cells enter the comparison: the 1f1b/zb/
    interleaved op tables schedule exactly those phases, so extra phases
    (e.g. ``loss``) would inflate only fill-drain's makespan — and the
    busy denominators — unevenly.  The rows rank schedule quality on the
    common cell set; quote absolute makespans from
    :func:`simulate_pipeline` if other phases matter.
    """
    events = [ev for ev in events if ev.name in ("fwd", "bwd")]
    rows: List[ScheduleProjection] = []
    same_device = (
        ("fill_drain", "peak in-flight activations grow with chunks m per "
                       "stage; all checkpoint modes"),
        ("1f1b", "peak in-flight <= min(m, n-j) per stage (flat in m); all "
                 "checkpoint modes"),
        ("zb", "split backward fills drain bubbles; projection models B/W "
               "as a 50/50 split of the measured fused backward; engine "
               "modes 'never' (stored residuals) or 'always' "
               "(recompute-in-B)"),
    )
    has_bwd = any(ev.name == "bwd" for ev in events)
    for sched, note in same_device:
        if sched in ("1f1b", "zb") and not has_bwd:
            # Their op orders interleave bwd cells; with no measured bwd
            # the projection would rank a fake (zero-backward) makespan.
            continue
        res = simulate_pipeline(events, n_stages, schedule=sched)
        if res is not None:
            rows.append(
                ScheduleProjection(sched, n_stages, 1, *res, note=note)
            )
    rows.sort(key=lambda r: r.makespan)
    inter: List[ScheduleProjection] = []
    for v in virtual_stages:
        if v < 2 or n_stages % v != 0 or n_stages // v < 2 or not has_bwd:
            continue
        try:
            res = simulate_pipeline(
                events, n_stages, schedule="interleaved", virtual_stages=v
            )
        except ValueError:
            # e.g. the measured micro-batch count not divisible by the
            # projected device count — inapplicable, same as a v that
            # doesn't divide n_stages.
            continue
        if res is not None:
            inter.append(
                ScheduleProjection(
                    "interleaved", n_stages // v, v, *res,
                    note=f"measured stages laid out as {n_stages} global "
                         f"blocks on {n_stages // v} devices (v={v}) — a "
                         "fewer-chips projection, not a same-budget "
                         "alternative",
                )
            )
    inter.sort(key=lambda r: r.makespan)
    return rows + inter


def _list_schedule(
    orders: Any,
    dep_fn: Callable,
    time_fn: Callable,
) -> Optional[float]:
    """Shared dependency-driven list scheduler for the per-schedule
    projections: each unit executes its ``orders`` row in order, an op
    starting when its unit is free AND ``dep_fn(op, j)`` (or None) has
    finished; ``time_fn(op, j)`` prices the op.  Returns the makespan, or
    None on deadlock (cyclic/missing data)."""
    n = len(orders)
    done: dict = {}
    pos = [0] * n
    unit_free = [0.0] * n
    total = sum(len(o) for o in orders)
    scheduled = 0
    while scheduled < total:
        progressed = False
        for j in range(n):
            while pos[j] < len(orders[j]):
                op = orders[j][pos[j]]
                dep = dep_fn(op, j)
                if dep is not None and dep not in done:
                    break
                start = max(
                    unit_free[j], done[dep] if dep is not None else 0.0
                )
                finish = start + time_fn(op, j)
                done[op + (j,)] = finish
                unit_free[j] = finish
                pos[j] += 1
                scheduled += 1
                progressed = True
        if not progressed:
            return None
    return max(unit_free)


def _simulate_interleaved(
    by_phase: dict, n_blocks: int, v: int
) -> Optional[float]:
    """Dependency-driven completion times for the interleaved
    (Megatron virtual pipeline stages) op order.

    Measured cells ``(i, j)`` are read as micro-batch ``i`` on GLOBAL
    block ``j``; the projection places block ``g = c·n + dev`` on device
    ``dev`` as chunk ``c`` (n = n_blocks // v devices) and executes each
    device's table order (:mod:`torchgpipe_tpu.parallel.interleaved`), an
    op starting when its device is free AND its producer finished
    (``_producer``: fwd g needs fwd g-1, bwd g needs bwd g+1, the last
    block's bwd needs its own fwd)."""
    from torchgpipe_tpu.parallel.interleaved import (
        BWD,
        FWD,
        _cell_sequence,
        _producer,
    )

    fwd = by_phase.get("fwd", {})
    bwd = by_phase.get("bwd", {})
    if not fwd:
        return None
    n = n_blocks // v
    m = 1 + max(i for i, _ in fwd)
    if m % n != 0:
        # Same rule the engine enforces (interleaved._check_args /
        # SpmdGPipe validation): Megatron's micro-batch grouping assumes
        # full groups — raise the clear error rather than deadlocking on
        # an inconsistent table into an indistinguishable None.
        raise ValueError(
            f"interleaved projection needs the measured micro-batch count "
            f"({m}) divisible by the device count n_stages//virtual_stages "
            f"({n})"
        )
    orders = [_cell_sequence(n, m, v, j) for j in range(n)]

    def dep_fn(op, j):
        kind, c, i = op
        dep = _producer(n, v, kind, c, i, j)
        if dep is None and kind == BWD:
            # The last global block's backward consumes its own forward
            # (the loss seed).
            return (FWD, c, i, j)
        return dep

    def time_fn(op, j):
        kind, c, i = op
        g = c * n + j  # global block index = the measured stage index
        return (fwd if kind == FWD else bwd).get((i, g), 0.0)

    return _list_schedule(orders, dep_fn, time_fn)


def _simulate_zb(by_phase: dict, n: int) -> Optional[float]:
    """Zero-bubble projection: the measured fused backward splits into a
    B half (activation gradient) and a W half (weight gradient), each
    HALF the measured bwd cell time — the dense-layer FLOP split, and the
    modeling assumption to state when quoting the result.  Op order and
    dependencies come from the zb tables
    (:mod:`torchgpipe_tpu.parallel.zerobubble`)."""
    from torchgpipe_tpu.parallel.zerobubble import (
        B as ZB_B,
        F as ZB_F,
        _dep,
        _zb_sequence,
    )

    fwd = by_phase.get("fwd", {})
    bwd = by_phase.get("bwd", {})
    if not fwd:
        return None
    m = 1 + max(i for i, _ in fwd)
    orders = [_zb_sequence(n, m, j) for j in range(n)]

    def dep_fn(op, j):
        kind, i = op
        dep = _dep(n, kind, i, j)
        if dep is not None:
            return dep  # (kind, i, dev) — already op + (device,) shaped
        if kind == ZB_B and j == n - 1:
            return (ZB_F, i, j)  # loss seed: own forward
        if kind not in (ZB_F, ZB_B):
            return (ZB_B, i, j)  # W after its own B
        return None

    def time_fn(op, j):
        kind, i = op
        if kind == ZB_F:
            return fwd.get((i, j), 0.0)
        return bwd.get((i, j), 0.0) / 2.0  # B and W halves

    return _list_schedule(orders, dep_fn, time_fn)


def _simulate_1f1b(by_phase: dict, n: int) -> Optional[float]:
    """Dependency-driven completion times for the PipeDream-flush order."""
    fwd = by_phase.get("fwd", {})
    bwd = by_phase.get("bwd", {})
    if not fwd:
        return None
    from torchgpipe_tpu.pipeline import one_f1b_orders

    m = 1 + max(i for i, _ in fwd)
    orders = one_f1b_orders(m, n)

    def dep_fn(op, j):
        kind, i = op
        if kind == "fwd":
            return ("fwd", i, j - 1) if j > 0 else None
        return ("bwd", i, j + 1) if j < n - 1 else ("fwd", i, j)

    def time_fn(op, j):
        kind, i = op
        return (fwd if kind == "fwd" else bwd).get((i, j), 0.0)

    return _list_schedule(orders, dep_fn, time_fn)
