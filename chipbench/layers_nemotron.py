"""Readers of the per-layer metrics of the Nemotron cell
(``layer_metrics/<metric>.json`` names one as ``"reader":
"layers_nemotron:<function>"``).  Each returns ``None`` where it finds nothing
to read: a program without the expert or recurrent-state counters, no decode
program in the trace.
"""

from __future__ import annotations

from typing import Any, Dict, Optional

from chipbench import layers
from chipbench import peaks_nemotron as pk


def serve_mfu(facts: Dict[str, Any]) -> Optional[float]:
    """Forward FLOPs the window's prefill and decode positions need, the
    mixers' scan among them, per second of the window, over the chip's bf16
    peak."""
    if facts.get("peaks") is None or facts.get("moe_held_assignments") is None:
        return None
    flops = pk.serve_flops(facts["cell"].config, facts["processed_tokens"],
                           facts["output_tokens"], facts["key_sum"],
                           facts["moe_held_assignments"])
    return 100.0 * flops / facts["elapsed_s"] / facts["peaks"]["flops_bf16"]


def decode_hbm_roofline(facts: Dict[str, Any]) -> Optional[float]:
    """The least time the chip's memory could take for the bytes a decode step
    must move (``peaks_nemotron.decode_step_bytes``: weights, the held experts
    given a token, the recurrent state read and written, the attention rows
    read), averaged over the window's decode steps, over the decode program's
    device time."""
    step_ms = layers.program_ms(facts, "decode_body")
    per_step = facts.get("decode_step_bytes")
    if step_ms is None or per_step is None or facts.get("peaks") is None:
        return None
    least = per_step / facts["peaks"]["hbm_bytes_per_s"]
    return 100.0 * least / (1e-3 * step_ms)
