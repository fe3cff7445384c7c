"""The window's account, read from the PROGRAM's own spans like ``spans.py``'s
(``layer_metrics/<metric>.json`` names a reader here as
``"reader": "spans_account:<function>"``): what an engine step carried and
what it left waiting, the collection that stalled it, and the compile seconds
of set-up.

Beside the spans ``spans.py`` describes, these read the FIELDS of
``engine.admit`` (``queued``, ``free``, ``slots``), of the actions (``rows``,
``rows_read``, ``rows_cap``; prefill also ``cap`` and ``deferred``), of
``engine.step`` (``ahead``) and of ``engine.emit`` (``tokens``), and the two
kinds of closed event the program marks for time it did not choose to spend:
``gc.collect`` and ``xla.compile`` (``phase``, ``fun``, ``cache_hit``).

Which events are the window's is ``spans.py``'s to say (``_engine_window``,
``_train_window``).  Every value is over ALL steps of the window, so a stall
moves it.  A reader returns ``None`` where its span or its field is not there:
a program from before the field (the metrics' files say ``"optional": true``:
the parent commit runs with these files), a ring that wrapped, a program
without spans.
"""

from __future__ import annotations

from typing import Any, Callable, Dict, List, Optional, Sequence, Tuple

from chipbench import spans
from chipbench.spans import ACTIONS, _engine_window, _steps, _train_window

MARKS = ("gc.collect", "xla.compile")


def _named(events: Sequence[Any], *names: str) -> List[Any]:
    return [e for e in events if e.name in names]


def _values(facts: Dict[str, Any], field: str, *names: str) -> Optional[List[float]]:
    """``field`` of each of the window's spans named ``names`` that carries
    it; ``None`` where the window cannot be read or no such span carries it."""
    events = _engine_window(facts)
    if events is None:
        return None
    values = [e.fields[field] for e in _named(events, *names) if field in (e.fields or {})]
    return values or None


def _share(top: Optional[List[float]], bottom: Optional[List[float]]) -> Optional[float]:
    if top is None or not bottom or not sum(bottom):
        return None
    return 100.0 * sum(top) / sum(bottom)


def _mean(values: Optional[List[float]]) -> Optional[float]:
    return sum(values) / len(values) if values else None


def tokens_per_step(facts: Dict[str, Any]) -> Optional[float]:
    """Tokens delivered in the window (the ``tokens`` of every ``engine.emit``
    that delivers, a settle's too) over the steps it launched."""
    tokens = _values(facts, "tokens", "engine.emit")
    return sum(tokens) / (facts["prefill_steps"] + facts["decode_steps"]) if tokens else None


def decode_rows_per_step(facts: Dict[str, Any]) -> Optional[float]:
    """Mean rows of the window's decode steps: slots that sampled a token."""
    return _mean(_values(facts, "rows", "engine.decode"))


def prefill_fill_share(facts: Dict[str, Any]) -> Optional[float]:
    """Rows the compact prefill program carried over the rows it has room for."""
    return _share(_values(facts, "rows", "engine.prefill"), _values(facts, "cap", "engine.prefill"))


def prefill_deferred_rows(facts: Dict[str, Any]) -> Optional[float]:
    """Pending prompts a prefill step left for the next one, a prefill step."""
    return _mean(_values(facts, "deferred", "engine.prefill"))


def steps_launched_ahead_share(facts: Dict[str, Any]) -> Optional[float]:
    """The share of steps launched while the step before was in flight."""
    ahead = _mean(_values(facts, "ahead", "engine.step"))
    return None if ahead is None else 100.0 * ahead


def attend_rows_read_share(facts: Dict[str, Any]) -> Optional[float]:
    """Cache rows a layer's attention read over the rows its steps span."""
    return _share(_values(facts, "rows_read", *ACTIONS), _values(facts, "rows_cap", *ACTIONS))


def longest_engine_step_ms(facts: Dict[str, Any]) -> Optional[float]:
    events = _engine_window(facts)
    if events is None:
        return None
    return 1e3 * max(e.duration for e in _steps(events))


def _admission_share(facts: Dict[str, Any],
                     weigh: Callable[[Dict[str, Any]], float]) -> Optional[float]:
    """100 x the mean of ``weigh(fields)`` over the window's steps, each step's
    ``engine.admit`` weighted by the step's duration."""
    events = _engine_window(facts)
    if events is None:
        return None
    steps = {e.seq: e for e in _steps(events)}
    total = share = 0.0
    for admit in _named(events, "engine.admit"):
        step, fields = steps.get(admit.parent), admit.fields or {}
        if step is None:
            continue
        if not fields.get("slots") or "free" not in fields or "queued" not in fields:
            return None
        total += step.duration
        share += step.duration * weigh(fields)
    return 100.0 * share / total if total else None


def slots_in_use_share(facts: Dict[str, Any]) -> Optional[float]:
    """Slots held after admission, of the pool's, over the window's step time."""
    return _admission_share(facts, lambda f: 1.0 - f["free"] / f["slots"])


def queue_dry_step_share(facts: Dict[str, Any]) -> Optional[float]:
    """The share of the window's step time in which admission left a slot
    free and nobody queued: room, and no traffic to fill it."""
    return _admission_share(facts, lambda f: float(f["queued"] == 0 and f["free"] > 0))


def _marks_around(facts: Dict[str, Any]) -> Optional[Tuple[List[Any], float, float]]:
    """(every event the ring holds, the start of the window's first step span,
    the end of its last), where the program marks collections and compiles at
    all and the ring has lost nothing; else ``None``.  A process that has run a
    jitted program has marked its compile, so a ring without a mark is a
    program from before them."""
    timeline = spans._timeline()
    events = timeline.since(0) if timeline is not None else None
    if events is None or not any(e.name in MARKS for e in events):
        return None
    if "decode_steps" in facts:
        window = _engine_window(facts)
        steps = _steps(window) if window is not None else None
    else:
        steps = _train_window(facts)
    return (events, steps[0].t_start, steps[-1].t_end) if steps else None


def gc_pause_ms(facts: Dict[str, Any]) -> Optional[float]:
    """Milliseconds of the interpreter's collections between the start of the
    window's first step span and the end of its last (0 where none was long
    enough, or old enough, to be marked)."""
    found = _marks_around(facts)
    if found is None:
        return None
    events, start, end = found
    return 1e3 * sum(e.duration for e in _named(events, "gc.collect")
                     if start <= e.t_start and e.t_end <= end)


def _setup_compiles(facts: Dict[str, Any]) -> Optional[List[Any]]:
    """The ``xla.compile`` events that closed before the window's first step."""
    found = _marks_around(facts)
    if found is None:
        return None
    return [e for e in _named(found[0], "xla.compile") if e.t_end <= found[1]]


def setup_compile_s(facts: Dict[str, Any]) -> Optional[float]:
    """Seconds of set-up that lay in a phase of a compile (trace, lower,
    backend): the length of the UNION of the events' intervals, since jax
    traces a ``jit`` called under a ``jit`` inside the outer trace."""
    compiles = _setup_compiles(facts)
    if compiles is None:
        return None
    total, covered = 0.0, float("-inf")
    for e in sorted(compiles, key=lambda e: e.t_start):
        total += max(0.0, e.t_end - max(e.t_start, covered))
        covered = max(covered, e.t_end)
    return total


def setup_cache_miss_s(facts: Dict[str, Any]) -> Optional[float]:
    """Seconds of set-up's backend phases that compiled and did not load from
    the persistent cache."""
    compiles = _setup_compiles(facts)
    if compiles is None:
        return None
    return sum(e.duration for e in compiles
               if e.fields["phase"] == "backend" and not e.fields["cache_hit"])
