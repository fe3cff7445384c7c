"""The reduction from a profiler trace to numbers.

``jax.profiler`` writes ``*.xplane.pb``; ``jax.profiler.ProfileData`` reads
it with nothing but jax.  Device planes are named ``/device:TPU:<n>``; their
line ``XLA Ops`` holds one event per executed operation and ``XLA Modules``
one per executed program.  Host spans are the benchmark's own
``TraceAnnotation``s, all named ``cb.<what>``, on the same clock.
"""

from __future__ import annotations

import collections
import pathlib
import re
from typing import Any, Dict, List, Sequence, Tuple

Interval = Tuple[float, float]

DEVICE_PREFIX = "/device:TPU:"
OPS_LINE, MODULES_LINE = "XLA Ops", "XLA Modules"
SPAN_PREFIX = "cb."


CONTAINERS = ("while", "conditional", "call")   # their bodies' ops are events too


def short_name(text: str) -> Tuple[str, str]:
    """(name, opcode) of a device event, whose name is the operation's whole
    HLO text: ``name result-shapes [tpu_custom_call/<operands>]``, layouts
    dropped and a long tuple cut to two shapes.  A Pallas kernel carries no
    name of its own in the trace, so its signature has to tell it apart."""
    if " = " not in text:
        return text[:120], ""
    lhs, rhs = text.split(" = ", 1)
    rhs = re.sub(r"/\*.*?\*/", "", re.sub(r"\{[^}]*\}", "", rhs))
    if rhs.startswith("("):
        result, rest = rhs[1:].split(")", 1)
        shapes = [x.strip() for x in result.split(", ")]
        result = "(" + ",".join(shapes[:2]) + (",..." if len(shapes) > 2 else "") + ")"
    else:
        result, _, rest = rhs.partition(" ")
    opcode = rest.strip().split("(", 1)[0]
    name = f"{lhs.lstrip('%')} {result}"
    if 'custom_call_target="tpu_custom_call"' in rest:
        operands = rest.split("custom-call(", 1)[1].split("), custom_call_target", 1)[0]
        name += f" tpu_custom_call/{operands.count('%')}"
    return name[:120], opcode


def union(intervals: Sequence[Interval]) -> List[Interval]:
    """Merged, sorted, disjoint intervals."""
    out: List[List[float]] = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return [(a, b) for a, b in out]


def covered(intervals: Sequence[Interval]) -> float:
    return sum(b - a for a, b in union(intervals))


def read_events(path: pathlib.Path) -> Dict[str, Any]:
    """{'devices': {plane: {'ops': [(name, start_s, end_s)], 'modules': [...]}},
    'spans': [(name, start_s, end_s)]} from one xplane file."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(str(path))
    devices: Dict[str, Dict[str, list]] = {}
    spans: List[Tuple[str, float, float]] = []
    for plane in data.planes:
        if plane.name.startswith(DEVICE_PREFIX):
            lines = devices.setdefault(plane.name, {"ops": [], "modules": []})
            for line in plane.lines:
                key = {OPS_LINE: "ops", MODULES_LINE: "modules"}.get(line.name)
                if key is None:
                    continue
                for e in line.events:
                    start = e.start_ns * 1e-9
                    lines[key].append((e.name, start, start + e.duration_ns * 1e-9))
        else:
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith(SPAN_PREFIX):
                        start = e.start_ns * 1e-9
                        spans.append((e.name, start, start + e.duration_ns * 1e-9))
    return {"devices": devices, "spans": spans}


def reduce_events(events: Dict[str, Any]) -> Dict[str, Any]:
    """Busy and window seconds, per-program and per-operation times, and the
    breakdown (top operations; idle gaps by what the host was doing)."""
    devices = {k: v for k, v in events["devices"].items() if v["ops"]}
    if not devices:
        raise RuntimeError("the trace holds no device operation")
    spans = events["spans"]
    starts = [s for d in devices.values() for _, s, _ in d["ops"]] + [s for _, s, _ in spans]
    ends = [e for d in devices.values() for _, _, e in d["ops"]] + [e for _, _, e in spans]
    w0, w1 = min(starts), max(ends)
    busy = {k: covered([(s, e) for _, s, e in d["ops"]]) for k, d in devices.items()}
    op_seconds: Dict[str, float] = collections.defaultdict(float)
    op_calls: Dict[str, int] = collections.defaultdict(int)
    modules: Dict[str, List[float]] = collections.defaultdict(list)
    for d in devices.values():
        for text, s, e in d["ops"]:
            name, opcode = short_name(text)
            if opcode in CONTAINERS:
                continue
            op_seconds[name] += e - s        # summed over the devices, as the calls are
            op_calls[name] += 1
        for name, s, e in d["modules"]:
            modules[name].append(e - s)
    # Idle gaps of every device, each charged to the host span that covers
    # most of it ("none" where the host was in no span of the benchmark's).
    gaps: Dict[str, float] = collections.defaultdict(float)
    for d in devices.values():
        merged = union([(s, e) for _, s, e in d["ops"]])
        edges = [(w0, w0)] + merged + [(w1, w1)]
        for (_, a), (b, _) in zip(edges, edges[1:]):
            if b - a <= 0:
                continue
            best, best_overlap = "none", 0.0
            for name, s, e in spans:
                overlap = min(e, b) - max(s, a)
                if overlap > best_overlap:
                    best, best_overlap = name, overlap
            gaps[best] += (b - a) / len(devices)
    top = lambda table: [[k, v] for k, v in sorted(table.items(), key=lambda kv: -kv[1])[:10]]
    per_device = {k: v / len(devices) for k, v in op_seconds.items()}
    return {
        "busy_s": sum(busy.values()) / len(busy),
        "busy_by_device": busy,
        "window_s": w1 - w0,
        "op_seconds": dict(op_seconds),
        "op_calls": dict(op_calls),
        "modules": dict(modules),
        "n_devices": len(devices),
        "breakdown": {"device_ops": top(per_device), "idle_gaps": top(gaps)},
    }


def find_xplane(trace_dir: pathlib.Path) -> pathlib.Path:
    found = sorted(pathlib.Path(trace_dir).rglob("*.xplane.pb"))
    if not found:
        raise RuntimeError(f"no *.xplane.pb under {trace_dir}")
    return found[-1]


def reduce_dir(trace_dir: pathlib.Path) -> Dict[str, Any]:
    return reduce_events(read_events(find_xplane(trace_dir)))

