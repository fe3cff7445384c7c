"""Readers of the per-layer metrics of a latent-attention, routed-expert cell
(``layer_metrics/<metric>.json`` names one as ``"reader":
"layers_latent_moe:<function>"``).  Each returns ``None`` where it finds
nothing to read: a program without the expert counters, no decode program in
the trace.
"""

from __future__ import annotations

from typing import Any, Dict, Optional

from chipbench import layers
from chipbench import peaks_latent_moe as pk


def serve_mfu(facts: Dict[str, Any]) -> Optional[float]:
    """Forward FLOPs the processed tokens need in the published form, per
    second of the window, over the chip's bf16 peak."""
    if facts.get("peaks") is None or facts.get("moe_held_assignments") is None:
        return None
    flops = pk.serve_flops(facts["cell"].config, facts["processed_tokens"],
                           facts["output_tokens"], facts["key_sum"],
                           facts["moe_held_assignments"])
    return 100.0 * flops / facts["elapsed_s"] / facts["peaks"]["flops_bf16"]


def decode_hbm_roofline(facts: Dict[str, Any]) -> Optional[float]:
    """The least time the chip's memory could take for a decode step's
    unavoidable bytes, over the decode program's device time."""
    step_ms = layers.program_ms(facts, "decode_body")
    if step_ms is None or facts.get("peaks") is None or facts.get("kv_live_rows") is None:
        return None
    least = pk.decode_step_bytes(facts["cell"].config, facts["kv_live_rows"]) / (
        facts["peaks"]["hbm_bytes_per_s"])
    return 100.0 * least / (1e-3 * step_ms)


def fact_ratio(facts: Dict[str, Any], part: str, whole: str,
               scale: float = 1.0) -> Optional[float]:
    """``scale * facts[part] / facts[whole]`` (None where either is missing or
    the whole is 0)."""
    a, b = facts.get(part), facts.get(whole)
    return scale * a / b if a is not None and b else None
