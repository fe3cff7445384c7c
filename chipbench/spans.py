"""Readers of the per-layer metrics that come from the PROGRAM's own spans
(``layer_metrics/<metric>.json`` names one as ``"reader": "spans:<function>"``).

The program records spans into one bounded, always-on timeline
(``torchgpipe_tpu.utils.tracing.default_timeline()``): ``engine.step`` with
``engine.admit`` and the action (``engine.prefill`` / ``engine.decode``)
under it and ``engine.build`` / ``dispatch`` / ``fetch`` / ``emit`` under the
action; one ``step`` span a compiled train step, carrying the schedule it was
built with.  The readers run in the process that ran the window and read that
timeline as an operator would; ``run.py`` has deleted the profile by then, so
nothing here reads the trace but ``facts["trace"]["modules"]``.

Which spans are the window's:

* serving: the builder steps the engine no more once the window has closed, so
  the window's steps are the LAST ``facts["prefill_steps"] +
  facts["decode_steps"]`` ``engine.step`` spans, each with everything that
  opened under it;
* training: the last ``facts["steps"]`` ``step`` spans, after leaving out the
  ``trace_steps`` a traced run takes behind the window.  The set-up's steps
  (which may compile) come before them and are left out.

Every value is a mean over ALL steps of the window, as ``step_wall_ms.backlog``
is, so one stalled step moves it.  A reader returns ``None`` where it finds
nothing to read: a program from before the spans (the metrics' files say
``"optional": true`` for that: the parent commit has to run with these files),
no span of its name, or a ring that wrapped inside the window.
"""

from __future__ import annotations

from typing import Any, Dict, List, Optional

from chipbench import layers

ACTIONS = ("engine.prefill", "engine.decode")
# A phase is counted under the parent the program gives it, so that a
# dispatch inside admission (a prefix copy) is admission's time, once.
PARENTS = {"engine.admit": ("engine.step",), "engine.build": ACTIONS,
           "engine.dispatch": ACTIONS, "engine.fetch": ACTIONS, "engine.emit": ACTIONS}


def _timeline() -> Any:
    """The program's default timeline; ``None`` where the program has none."""
    from torchgpipe_tpu.utils import tracing

    get = getattr(tracing, "default_timeline", None)
    return get() if get is not None else None


def _engine_window(facts: Dict[str, Any]) -> Optional[List[Any]]:
    """The window's ``engine.step`` spans and all that opened under them,
    oldest first."""
    timeline = _timeline()
    count = facts["prefill_steps"] + facts["decode_steps"]
    if timeline is None or count <= 0:
        return None
    steps = [e for e in timeline.events if e.name == "engine.step"]
    if len(steps) < count:
        return None
    return timeline.since(steps[-count].seq)    # None: the ring pushed one of them out


def _train_window(facts: Dict[str, Any]) -> Optional[List[Any]]:
    """The window's ``step`` spans, oldest first."""
    timeline = _timeline()
    if timeline is None:
        return None
    cell = facts["cell"]
    steps = [e for e in timeline.events if e.name == "step"]
    if cell.trace:      # the steps a traced run takes behind the window
        steps = steps[:len(steps) - cell.config["train"]["trace_steps"]]
    window = steps[-facts["steps"]:]
    if len(window) < facts["steps"] or timeline.since(window[0].seq) is None:
        return None
    return window


def _phase_s(events: List[Any], span: str) -> float:
    """Seconds of the ``span`` phase, summed over the window's steps."""
    by_seq = {e.seq: e for e in events}
    return sum(e.duration for e in events if e.name == span
               and getattr(by_seq.get(e.parent), "name", None) in PARENTS[span])


def _steps(events: List[Any]) -> List[Any]:
    return [e for e in events if e.name == "engine.step"]


def engine_phase_ms(facts: Dict[str, Any], span: str) -> Optional[float]:
    """Time in ``span`` over all steps of the window, a step (a phase that a
    step skips, as a prefill step skips the fetch, counts as 0 there)."""
    events = _engine_window(facts)
    if events is None or not any(e.name == span for e in events):
        return None
    return 1e3 * _phase_s(events, span) / len(_steps(events))


def engine_self_ms(facts: Dict[str, Any]) -> Optional[float]:
    """``engine.step`` less admission and the four leaf phases, a step: the
    self time of the step and of its action together."""
    events = _engine_window(facts)
    if events is None:
        return None
    steps = _steps(events)
    covered = sum(_phase_s(events, span) for span in PARENTS)
    return 1e3 * (sum(e.duration for e in steps) - covered) / len(steps)


def engine_launch_gap_ms(facts: Dict[str, Any]) -> Optional[float]:
    """Mean ``engine.dispatch`` less the device time of the program it ran
    (the trace's per-call mean of each program, weighted by the window's
    step counts): what launching and the return of the wait cost."""
    dispatch = engine_phase_ms(facts, "engine.dispatch")
    prefill = layers.program_ms(facts, "prefill_body")
    decode = layers.program_ms(facts, "decode_body")
    if dispatch is None or prefill is None or decode is None:
        return None
    n_prefill, n_decode = facts["prefill_steps"], facts["decode_steps"]
    device = (n_prefill * prefill + n_decode * decode) / (n_prefill + n_decode)
    return dispatch - device


def train_dispatch_ms(facts: Dict[str, Any]) -> Optional[float]:
    """Mean of the ``step`` span: what the host pays to launch one compiled
    train step (the program runs on behind the call)."""
    steps = _train_window(facts)
    if not steps:
        return None
    return 1e3 * sum(e.duration for e in steps) / len(steps)


def bubble_tick_share(facts: Dict[str, Any]) -> Optional[float]:
    """The share of (stage, tick) slots that carry no micro-batch, from the
    fields of the window's last ``step`` span: the program counts them with
    the validity rule its tick body traces.  No formula lives here."""
    steps = _train_window(facts)
    fields = steps[-1].fields if steps else None
    if not fields or not fields.get("stage_ticks"):
        return None
    return 100.0 * (1.0 - fields["busy_stage_ticks"] / fields["stage_ticks"])
