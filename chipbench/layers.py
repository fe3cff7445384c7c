"""Readers of the per-layer metrics.  ``layer_metrics/<metric>.json`` names
one of these (``"reader": "layers:<function>"``) and its arguments; a later
PR adds a metric as a new json file, and a new reader as a new module.

Each takes the run's ``facts`` (the builder's counters and sizes, the
reduced trace, the end-to-end numbers, the chip's peaks) and returns the
value, or ``None`` where it finds nothing to read; a share of a peak or of a
roofline is never returned as 0.
"""

from __future__ import annotations

import re
from typing import Any, Dict, List, Optional, Sequence

from chipbench import peaks as pk


def _model(facts: Dict[str, Any]) -> Dict[str, Any]:
    return facts["cell"].config


def train_mfu(facts: Dict[str, Any]) -> Optional[float]:
    """Forward+backward FLOPs the tokens need (no recompute counted) per
    second of the whole window, over the chips' bf16 peak."""
    if facts.get("peaks") is None:
        return None
    per_token = pk.train_flops_per_token(_model(facts), facts["depth"], facts["seq"])
    rate = facts["end_to_end"]["train_tokens_per_s"]
    return 100.0 * per_token * rate / (facts["cell"].chips * facts["peaks"]["flops_bf16"])


def serve_mfu(facts: Dict[str, Any]) -> Optional[float]:
    """Forward FLOPs the tokens processed in the window need, per second of
    the window, over the chip's bf16 peak."""
    if facts.get("peaks") is None:
        return None
    flops = pk.serve_flops(_model(facts), facts["depth"], facts["processed_tokens"],
                           facts["output_tokens"], facts["key_sum"])
    return 100.0 * flops / facts["elapsed_s"] / facts["peaks"]["flops_bf16"]


def device_idle_share(facts: Dict[str, Any]) -> float:
    t = facts["trace"]
    return 100.0 * (1.0 - t["busy_s"] / t["window_s"])


def hbm_peak_gib(facts: Dict[str, Any]) -> Optional[float]:
    peak = facts["memory_peak_bytes"]
    return peak / 2.0 ** 30 if peak else None


def _matching(table: Dict[str, Any], patterns: Sequence[str]) -> Dict[str, Any]:
    return {k: v for k, v in table.items() if any(re.search(p, k) for p in patterns)}


def program_ms(facts: Dict[str, Any], module: str) -> Optional[float]:
    """Mean device time of one call of the program whose name holds ``module``."""
    calls: List[float] = [d for ds in _matching(facts["trace"]["modules"], [module]).values()
                          for d in ds]
    return 1e3 * sum(calls) / len(calls) if calls else None


def kernel_roofline(facts: Dict[str, Any], kernels: Sequence[str], count_by: str,
                    backward: bool) -> Optional[float]:
    """The least time the chip could take for the flash calls that ran, over
    the time their kernels took.  One call handles one micro-batch of one
    layer; ``count_by`` names the kernel whose events count the calls (the
    backward is two kernels, counted once)."""
    t = facts["trace"]
    seconds = sum(_matching(t["op_seconds"], kernels).values())    # both over all devices
    calls = sum(_matching(t["op_calls"], [count_by]).values())
    if not calls or seconds <= 0 or facts.get("peaks") is None:
        return None
    rows = facts["rows"] // facts["chunks"]
    least = pk.roofline_seconds(
        pk.flash_call(_model(facts), rows, facts["seq"], backward), facts["peaks"])
    return 100.0 * calls * least["seconds"] / seconds


def counter_share(facts: Dict[str, Any], part: str, whole: Sequence[str]) -> Optional[float]:
    total = sum(facts[k] for k in whole)
    return 100.0 * facts[part] / total if total else None


def fact_value(facts: Dict[str, Any], key: str, sub: Optional[str] = None,
               scale: float = 1.0) -> Optional[float]:
    """A number the builder recorded (``facts[key]``, or ``facts[key][sub]``)."""
    value = facts.get(key)
    if sub is not None and value is not None:
        value = value.get(sub)
    return scale * value if value is not None else None
