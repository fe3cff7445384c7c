"""What every builder shares: the cell as read from the data files, the
compile counter, the clocks, the metric arithmetic and the checks' record."""

from __future__ import annotations

import dataclasses
import importlib
import json
import os
import pathlib
from typing import Any, Callable, Dict, List, Optional, Sequence

import jax
import numpy as np

HERE = pathlib.Path(__file__).resolve().parent
ROOT = HERE.parent


def load_json(path: pathlib.Path) -> Any:
    with open(path) as f:
        return json.load(f)


def resolve(spec: str) -> Callable[..., Any]:
    """'module:function' under chipbench: how a data file names its generator
    or its reader (a later PR adds modules, edits none)."""
    module, _, function = spec.partition(":")
    return getattr(importlib.import_module(f"chipbench.{module}"), function)


@dataclasses.dataclass
class Cell:
    """One run of one workload: the data files it names, and the arguments."""

    name: str
    config: Dict[str, Any]      # configs/<config>.json
    traffic: Dict[str, Any]     # traffic/<traffic>.json
    chips: int
    seed: int
    seconds: float
    trace: bool
    trace_dir: pathlib.Path
    meter: "CompileMeter"
    # Tests plant a fault here: fault(name, value) -> value, called by the
    # builders at the points where the timed path hands something on.
    fault: Any = None

    def tap(self, point: str, value: Any) -> Any:
        return value if self.fault is None else self.fault(point, value)


class CompileMeter:
    """Programs compiled (or loaded from the persistent cache), counted from
    jax's own monitoring event."""

    _EVENT = "/jax/core/compile/backend_compile_duration"

    def __init__(self) -> None:
        self.programs = 0
        jax.monitoring.register_event_duration_secs_listener(self._on_event)

    def _on_event(self, event: str, secs: float, **_: Any) -> None:
        if event == self._EVENT:
            self.programs += 1


def process_age_s() -> float:
    """Seconds since this process started, by the kernel's record (imports
    and the interpreter's start are part of set-up)."""
    with open("/proc/self/stat") as f:
        start_ticks = int(f.read().rsplit(")", 1)[1].split()[19])
    with open("/proc/uptime") as f:
        uptime = float(f.read().split()[0])
    return uptime - start_ticks / os.sysconf("SC_CLK_TCK")


def percentile(values: Sequence[float], q: float) -> float:
    """numpy's default (linear) percentile over ALL the values given."""
    return float(np.percentile(np.asarray(values, np.float64), q))


def peak_memory_bytes(devices: Sequence[Any]) -> int:
    """The fullest chip's high-water mark (0 where the backend reports none)."""
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0) for d in devices]
    return int(max(peaks))


@dataclasses.dataclass
class Check:
    """One number compared with its limit (passes where value <= limit)."""

    name: str
    value: float
    limit: float

    @property
    def ok(self) -> bool:
        return bool(np.isfinite(self.value)) and self.value <= self.limit


@dataclasses.dataclass
class Outcome:
    """What a builder hands back to run.py."""

    attempted: int
    failed: int
    end_to_end: Dict[str, float]
    checks: List[Check]
    facts: Dict[str, Any]          # counters and sizes the layer readers use
    memory_peak_bytes: int


def worst_leaf_gap(got: Sequence[float], ref: Sequence[float],
                   skip: Optional[Sequence[bool]] = None) -> float:
    """The widest gap between two sets of per-leaf norms: |got - ref| over
    the larger of that leaf's reference norm and the median leaf's."""
    got, ref = np.asarray(got, np.float64), np.asarray(ref, np.float64)
    keep = np.ones(len(ref), bool) if skip is None else ~np.asarray(skip, bool)
    floor = float(np.median(ref[keep]))
    gaps = np.abs(got - ref) / np.maximum(ref, floor)
    return float(np.max(gaps[keep]))
