"""Readers of the per-layer metrics of a ``mellum2`` training cell
(``layer_metrics/<metric>.json`` names one as ``"reader":
"layers_mellum2:<function>"``).  Each returns ``None`` where it finds nothing
to read: a program whose ``step`` spans carry no expert counts, no kernel of
the pattern in the trace.
"""

from __future__ import annotations

from typing import Any, Dict, Optional, Sequence

from chipbench import layers
from chipbench import peaks as pk
from chipbench import peaks_mellum2 as pm


def train_mfu(facts: Dict[str, Any]) -> Optional[float]:
    """Forward+backward FLOPs the window's tokens and its COUNTED held
    assignments need (no recompute counted) per second of the whole window,
    over the chip's bf16 peak."""
    if facts.get("peaks") is None or facts.get("moe_held_assignments") is None:
        return None
    flops = pm.train_flops(facts["cell"].config, facts["seq"], facts["tokens"],
                           facts["moe_held_assignments"])
    return 100.0 * flops / facts["elapsed_s"] / (
        facts["cell"].chips * facts["peaks"]["flops_bf16"])


def flash_roofline(facts: Dict[str, Any], kernels: Sequence[str], count_by: str,
                   backward: bool) -> Optional[float]:
    """``layers.kernel_roofline`` with the least time of a call summed over
    the layer types: a window call's FLOPs over its band, not the triangle."""
    t = facts["trace"]
    seconds = sum(layers._matching(t["op_seconds"], kernels).values())
    calls = sum(layers._matching(t["op_calls"], [count_by]).values())
    if not calls or seconds <= 0 or facts.get("peaks") is None:
        return None
    least = pm.flash_least_seconds(facts["cell"].config, facts["rows"] // facts["chunks"],
                                   facts["seq"], backward, facts["peaks"])
    return 100.0 * calls * least / seconds


def expert_dot_roofline(facts: Dict[str, Any], kernels: Sequence[str]) -> Optional[float]:
    """The least time the chip could take for the grouped expert products
    that ran in the traced steps, over the time their events took.  Every
    matching event is one WHOLE product over one (layer, micro-batch)'s held
    assignment rows, whose mean the traced steps' counts give: forward form
    or transposed, a product is 2 x rows x hidden x width.  The compiler
    splits none (chip run, PR 32: 117 distinct products a micro-batch, each
    called once a micro-batch; 8 layers x 15 less the last layer's three in
    the stage's recomputation, which nothing reads); its ``ragged-dot-metadata``
    events (3 us each) are no products and the metric's pattern leaves them
    out."""
    t = facts["trace"]
    found = layers._matching(t["op_seconds"], kernels)
    events = sum(layers._matching(t["op_calls"], kernels).values())
    rows = facts.get("moe_traced_rows_per_product")
    if not events or rows is None or facts.get("peaks") is None:
        return None
    least = pk.roofline_seconds(pm.expert_product(facts["cell"].config, rows), facts["peaks"])
    return 100.0 * events * least["seconds"] / sum(found.values())
