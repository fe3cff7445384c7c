"""Closed forms for a ``mellum2`` configuration (window and full attention
mixed by layer, softmax-routed experts on a chip's share): the FLOPs a
training step needs, and the FLOPs and bytes of one flash call a layer type
and of one grouped expert product.  Computed from the configuration's shapes
and the COUNTED held assignments, never from the program's HLO.
"""

from __future__ import annotations

from typing import Any, Dict, Mapping

from chipbench import peaks as pk
from chipbench.weights_axk1 import published

SLIDING = "sliding_attention"


def attention_params(m: Mapping[str, Any]) -> int:
    """W_q, W_k, W_v and W_o of one block."""
    d, hd = m["hidden_size"], m["head_dim"]
    return 2 * d * m["num_attention_heads"] * hd + 2 * d * m["num_key_value_heads"] * hd


def expert_params(m: Mapping[str, Any]) -> int:
    """One expert's SwiGLU."""
    return 3 * m["hidden_size"] * m["moe_intermediate_size"]


def router_params(m: Mapping[str, Any]) -> int:
    return m["hidden_size"] * published(m, "num_experts")


def as_kind(m: Mapping[str, Any], kind: str) -> Dict[str, Any]:
    """The configuration as ``peaks.py``'s closed forms read one layer type:
    the window on a sliding layer, none on a full one."""
    return dict(m, sliding_window=m["sliding_window"] if kind == SLIDING else None)


def kind_shares(m: Mapping[str, Any]) -> Dict[str, float]:
    """The share of the layers that each layer type is."""
    kinds = m["layer_types"]
    return {k: kinds.count(k) / len(kinds) for k in sorted(set(kinds))}


def train_flops(m: Mapping[str, Any], seq: int, tokens: int, held_assignments: int) -> float:
    """Forward and backward of ``tokens`` tokens in sequences of ``seq``,
    recomputation not counted: 6 a weight of the attention projections, the
    router and the head a token; 6 an expert's weight a COUNTED held
    assignment; three times the forward attention products, a window layer
    over its band (``mean_keys(seq, window)``) and a full one over the
    triangle."""
    depth = m["num_hidden_layers"]
    dense = depth * (attention_params(m) + router_params(m)) + pk.head_matmul_params(m)
    attention = sum(
        depth * share * pk.attention_flops_per_query(
            m, pk.mean_keys(seq, as_kind(m, kind)["sliding_window"]))
        for kind, share in kind_shares(m).items())
    return (6.0 * dense + 3.0 * attention) * tokens + 6.0 * expert_params(m) * held_assignments


def flash_least_seconds(m: Mapping[str, Any], rows: int, seq: int, backward: bool,
                        peak: Mapping[str, float]) -> float:
    """The least time of ONE flash call averaged over the layer types in
    their published ratio: a call's type cannot be read off its event, and
    every micro-batch makes one call a layer."""
    return sum(
        share * pk.roofline_seconds(pk.flash_call(as_kind(m, kind), rows, seq, backward),
                                    peak)["seconds"]
        for kind, share in kind_shares(m).items())


def expert_product(m: Mapping[str, Any], rows: float) -> Dict[str, float]:
    """One grouped product of an expert layer over ``rows`` held assignment
    rows (``[rows, hidden] x [held, hidden, width]`` or its transposes, all
    of 2 x rows x hidden x width): the held experts' matrix once, the rows
    in and out once, bf16."""
    d, w = m["hidden_size"], m["moe_intermediate_size"]
    return {"flops": 2.0 * rows * d * w,
            "bytes": 2.0 * (m["num_experts"] * d * w + rows * (d + w))}
