"""Readers of the per-layer metrics of the Trinity cell
(``layer_metrics/<metric>.json`` names one as ``"reader":
"layers_trinity:<function>"``).  Each returns ``None`` where it finds nothing
to read: a program without the counters by kind of layer, no decode kernel in
the trace.
"""

from __future__ import annotations

import re
from typing import Any, Dict, Optional

from chipbench import peaks_trinity as pk


def serve_mfu(facts: Dict[str, Any]) -> Optional[float]:
    """Forward FLOPs the window's prefill and decode positions need, per
    second of the window, over the chip's bf16 peak."""
    if facts.get("peaks") is None or facts.get("pairs_by_kind") is None:
        return None
    flops = pk.serve_flops(facts["cell"].config, facts["processed_tokens"],
                           facts["output_tokens"], facts["pairs_by_kind"],
                           facts["moe_held_assignments"])
    return 100.0 * flops / facts["elapsed_s"] / facts["peaks"]["flops_bf16"]


def flash_decode_roofline(facts: Dict[str, Any]) -> Optional[float]:
    """The least time the chip's memory could take for the cache rows the
    decode kernel fetched in the traced steps (block-rounded, as the engine
    counted them for those steps), over the time its events took.  Memory
    bounds it: at one query a row the kernel does 4 FLOPs a fetched value."""
    rows = facts.get("traced_rows_read")
    seconds = sum(v for k, v in facts["trace"]["op_seconds"].items()
                  if re.match(r"flash_decode", k))
    if not rows or seconds <= 0 or facts.get("peaks") is None:
        return None
    least = pk.decode_kernel_bytes(facts["cell"].config, rows) / facts["peaks"]["hbm_bytes_per_s"]
    return 100.0 * least / seconds


def rows_read_share(facts: Dict[str, Any]) -> Optional[float]:
    """Rows the layers' attention read in the window over the rows they
    would have read had every layer been a full one at the same frontiers:
    the band's saving."""
    read = facts.get("attend_rows_read")
    if not read or not read.get("full"):
        return None
    kinds = pk.layer_kinds(facts["cell"].config)
    return 100.0 * sum(kinds[k] * read[k] for k in kinds) / (sum(kinds.values()) * read["full"])
