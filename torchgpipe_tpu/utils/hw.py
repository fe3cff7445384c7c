"""Chip capability tables for measurement integrity and MFU reporting.

The published bf16 peak matters for two things: computing MFU
(model FLOPs / step time / peak) and *refusing to print impossible
numbers* — a throughput that implies more than the chip's peak FLOP/s
can only come from a timed region that did not hold the work it counted
(a loop that never waited for its results).

No reference counterpart (the reference publishes wall-clock numbers
only, reference: docs/benchmarks.rst).  A ``device_kind`` that matches no
row gives ``None`` and the callers print no utilization; making that an
error belongs to the benchmark (ROADMAP A1).
"""

from __future__ import annotations

from typing import Any, Optional

# Published bf16 peak FLOP/s per chip, keyed by device_kind substring
# (checked in order, so the more specific names come first — e.g. 'v4 lite'
# must hit the v4i row before the plain 'v4' row halves-understates it).
PEAK_BF16_FLOPS = (
    ("v6 lite", 918e12),  # Trillium device_kind is 'TPU v6 lite'
    ("v6e", 918e12),
    ("v5 lite", 197e12),  # v5e device_kind is 'TPU v5 lite'
    ("v5e", 197e12),
    ("v5p", 459e12),
    ("v4 lite", 138e12),  # v4i
    ("v4i", 138e12),
    ("v4", 275e12),
    ("v3", 123e12),
    ("v2", 45e12),
)


def chip_peak_bf16_flops(device: Any) -> Optional[float]:
    """Published bf16 peak FLOP/s for ``device``, or None if unknown."""
    kind = getattr(device, "device_kind", "").lower()
    for key, peak in PEAK_BF16_FLOPS:
        if key in kind:
            return peak
    return None
