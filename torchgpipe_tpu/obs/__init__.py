"""Unified runtime telemetry: metrics registry, trace spine, reconciliation.

The analysis stack (:mod:`torchgpipe_tpu.analysis`) *predicts* — makespan,
bubble fraction, per-rank memory, MFU — from static event graphs; this
package *measures* a real run in the same vocabulary and reconciles the
two (the runtime counterpart the reference approximates with an
``nvidia-smi`` side process, reference benchmarks/unet-timeline).  Three
layers:

* **Metrics registry** (:mod:`~torchgpipe_tpu.obs.registry`) — labeled
  counters / gauges / histograms with an injectable clock, JSONL and
  Prometheus-text exporters, and percentile summaries.
  :class:`~torchgpipe_tpu.serving.metrics.ServingMetrics` and
  :class:`~torchgpipe_tpu.resilience.guard.GuardStats` are re-based on
  it (public APIs unchanged).
* **Trace spine** — :class:`~torchgpipe_tpu.utils.tracing.Timeline`
  records per-cell spans in the MPMD engine, the ``engine.step`` span
  tree of :class:`~torchgpipe_tpu.serving.Engine` and scan-granularity
  ``step``/``megastep`` spans in :class:`~torchgpipe_tpu.spmd.SpmdGPipe`
  (compiled scan bodies are not host-visible; the honest granularity is
  the dispatch).  ``Timeline.span`` also opens a profiler annotation, so
  under :func:`device_trace` the spans sit beside the device's lines,
  whose operations carry the ``forward``/``backward``/``optimizer``/
  ``tick`` scopes and the flash kernels' names;
  :func:`overlay_chrome_trace` exports measured-vs-predicted Perfetto
  traces keyed by event-graph node ids ``(stage, micro_batch, phase)``.
* **Reconciliation** (:func:`reconcile`) — maps measured spans onto
  :mod:`analysis.events` nodes and reports measured-vs-predicted
  makespan / bubble fraction / per-stage busy time; its measured drift
  feeds the ``plan-drift`` lint rule.  :class:`StepReporter` is the
  training-loop face: step wall time, tokens/s, measured MFU, guard
  counters, periodic structured log lines.
* **Flight recorder + postmortem** (:mod:`~torchgpipe_tpu.obs.
  flightrec`, :mod:`~torchgpipe_tpu.obs.postmortem`) — a fixed-size
  per-rank event ring inside the multi-process engine and transports
  (dump on crash / SIGTERM / stall-watchdog timeout, cross-rank clock
  alignment), and the analyzer that replays the deadlock verifier's
  blocking-FIFO simulation from the recorded frontier to NAME the
  blocking edge of a live hang.

Full story: ``docs/observability.md``.
"""

from __future__ import annotations

from typing import Any

from torchgpipe_tpu.obs.flightrec import (
    FlightEvent,
    FlightRecorder,
    RankDump,
    StallWatchdog,
    align_clocks,
    load_dump,
    merged_chrome_trace,
)
from torchgpipe_tpu.obs.registry import (
    Counter,
    Gauge,
    Histogram,
    MetricsRegistry,
    read_jsonl,
)
from torchgpipe_tpu.obs.reporter import StepReporter, measured_step_flops
from torchgpipe_tpu.obs.reqtrace import (
    RequestTrace,
    Span,
    format_request_tree,
    request_chrome_trace,
    request_ids,
    stitch_request,
)
from torchgpipe_tpu.obs.slo import Objective, SloEvent, SloMonitor
from torchgpipe_tpu.utils.tracing import Timeline, device_trace

# The reconciliation and postmortem halves pull in the whole analysis
# stack (event graphs, planner, rules); the registry/reporter/flightrec
# half is what the RUNTIME modules (resilience.guard, serving.metrics,
# distributed.gpipe) import on their hot import path.  PEP 562 lazy
# attributes keep the latter light.  (The reconciliation submodule is
# deliberately NOT named ``reconcile``: a submodule sharing the public
# function's name would clobber ``obs.reconcile`` on any direct
# submodule import.  The postmortem analyzer keeps the standard layout
# instead — ``obs.postmortem`` IS the submodule; its entry point is
# ``obs.postmortem.postmortem(dumps)``, so the package never exports a
# same-named function attribute that an import could clobber.)
_LAZY_EXPORTS = {
    "BUBBLE_TOLERANCE": "torchgpipe_tpu.obs.reconciliation",
    "ReconcileReport": "torchgpipe_tpu.obs.reconciliation",
    "check_dispatch_only_timeline": "torchgpipe_tpu.obs.reconciliation",
    "overlay_chrome_trace": "torchgpipe_tpu.obs.reconciliation",
    "reconcile": "torchgpipe_tpu.obs.reconciliation",
    "uniform_cost": "torchgpipe_tpu.obs.reconciliation",
    "BlockingEdge": "torchgpipe_tpu.obs.postmortem",
    "PostmortemReport": "torchgpipe_tpu.obs.postmortem",
    # The profile-guided replanning layer (PR: observe -> replan) pulls
    # in the planner; lazy for the same hot-import-path reason.
    "COSTMODEL_VERSION": "torchgpipe_tpu.obs.costmodel",
    "CostModel": "torchgpipe_tpu.obs.costmodel",
    "check_stale_cost_model": "torchgpipe_tpu.obs.costmodel",
    "config_fingerprint": "torchgpipe_tpu.obs.costmodel",
    "ReplanEvent": "torchgpipe_tpu.obs.replan",
    "ReplanOnDrift": "torchgpipe_tpu.obs.replan",
    "ReplanResult": "torchgpipe_tpu.obs.replan",
}


def __getattr__(name: str) -> Any:
    modname = _LAZY_EXPORTS.get(name)
    if modname is not None:
        import importlib

        mod = importlib.import_module(modname)
        # Bind the resolved names into the package namespace so the
        # lookup happens once.
        for export, m in _LAZY_EXPORTS.items():
            if m == modname:
                globals()[export] = getattr(mod, export)
        return globals()[name]
    raise AttributeError(f"module {__name__!r} has no attribute {name!r}")

__all__ = [
    "BUBBLE_TOLERANCE",
    "BlockingEdge",
    "COSTMODEL_VERSION",
    "CostModel",
    "Counter",
    "FlightEvent",
    "FlightRecorder",
    "Gauge",
    "Histogram",
    "MetricsRegistry",
    "Objective",
    "PostmortemReport",
    "RankDump",
    "ReconcileReport",
    "ReplanEvent",
    "ReplanOnDrift",
    "ReplanResult",
    "RequestTrace",
    "SloEvent",
    "SloMonitor",
    "Span",
    "StallWatchdog",
    "StepReporter",
    "Timeline",
    "align_clocks",
    "check_dispatch_only_timeline",
    "check_stale_cost_model",
    "config_fingerprint",
    "device_trace",
    "format_request_tree",
    "load_dump",
    "measured_step_flops",
    "merged_chrome_trace",
    "overlay_chrome_trace",
    "read_jsonl",
    "reconcile",
    "request_chrome_trace",
    "request_ids",
    "stitch_request",
    "uniform_cost",
]
