"""Closed forms for a Solar-Open2 configuration (``model_type: solar_open2``):
parameters by kind of block, the pool's bytes by kind of state, the FLOPs the
served tokens need and the bytes a decode step must move.  Computed from the
configuration's shapes and from what the engine COUNTED (held assignments,
experts given a token, recurrent-state bytes, cache rows), never from the
program's HLO or its kernels' code, so a change to the program cannot move a
denominator.
"""

from __future__ import annotations

from typing import Any, Dict, Mapping

from chipbench.peaks_nemotron import SERVED
from chipbench.weights_axk1 import published
from chipbench.weights_solar import kda_sizes, pattern


def letters(m: Mapping[str, Any]) -> Dict[str, int]:
    """Blocks of each kind: ``K`` delta-rule mixers, ``E`` expert layers,
    ``*`` attention."""
    p = pattern(m)
    return {k: p.count(k) for k in "KE*"}


def kda_params(m: Mapping[str, Any]) -> int:
    """One delta-rule mixer: q/k/v, the decay's and the gate's low-rank
    pairs, beta's projection, the output projection, the conv, ``dt_bias``,
    ``A_log`` and the output norm's scale."""
    d, s = m["hidden_size"], kda_sizes(m)
    w, r, h = s["width"], s["rank"], s["heads"]
    return (d * 3 * w + 2 * (d * r + r * w) + d * h + w * d + s["conv_kernel"] * 3 * w
            + w + h + s["head_dim"])


def attention_params(m: Mapping[str, Any]) -> int:
    """q, k, v, the output gate and the output projection."""
    d, hd = m["hidden_size"], m["head_dim"]
    q, kv = m["num_attention_heads"] * hd, m["num_key_value_heads"] * hd
    return 3 * d * q + 2 * d * kv


def expert_params(m: Mapping[str, Any]) -> int:
    """One routed expert's SwiGLU gate, up and down."""
    return 3 * m["hidden_size"] * m["moe_intermediate_size"]


def shared_params(m: Mapping[str, Any]) -> int:
    return m["n_shared_experts"] * expert_params(m)


def router_params(m: Mapping[str, Any]) -> int:
    return m["hidden_size"] * published(m, "n_routed_experts")


def head_params(m: Mapping[str, Any]) -> int:
    return m["hidden_size"] * m["vocab_size"]


def weight_params(m: Mapping[str, Any]) -> int:
    """Every parameter this chip holds (norm scales and the bias left out)."""
    n = letters(m)
    return (n["K"] * kda_params(m) + n["*"] * attention_params(m)
            + n["E"] * (m["n_routed_experts"] * expert_params(m) + shared_params(m)
                        + router_params(m))
            + 2 * head_params(m))


def cache_row_bytes(m: Mapping[str, Any]) -> int:
    """Bytes of one cached position of ONE attention layer: K and V."""
    return 2 * m["num_key_value_heads"] * m["head_dim"] * SERVED


def slot_state_bytes(m: Mapping[str, Any]) -> int:
    """Recurrent-state bytes ONE slot keeps over every delta-rule layer: the
    conv tail (``conv_kernel - 1`` inputs of q, k and v, bf16) and the float32
    state ``[H, d, d]``."""
    s = kda_sizes(m)
    per_layer = ((s["conv_kernel"] - 1) * 3 * s["width"] * SERVED
                 + s["heads"] * s["head_dim"] ** 2 * 4)
    return letters(m)["K"] * per_layer


def pool_bytes(m: Mapping[str, Any], serve: Mapping[str, Any]) -> Dict[str, int]:
    """Bytes the pool reserves: ``full`` (the attention layers' rows) and
    ``state`` (the delta-rule layers' tails and states)."""
    slots = serve["num_slots"]
    return {"full": slots * letters(m)["*"] * serve["max_len"] * cache_row_bytes(m),
            "state": slots * slot_state_bytes(m)}


def kda_flops_per_token(m: Mapping[str, Any]) -> float:
    """The recurrence at one position of ONE delta-rule layer: the state's
    decay (1 a state value), the read along ``k`` (2), the rank-one update
    (2) and the read-out along ``q`` (2); the conv, and the norms and gate of
    the heads' output.  The chunked form's own products are an implementation
    of it."""
    s = kda_sizes(m)
    state = s["heads"] * s["head_dim"] ** 2
    return 7.0 * state + 2.0 * s["conv_kernel"] * 3 * s["width"] + 4.0 * s["width"]


def attention_flops_per_pair(m: Mapping[str, Any]) -> float:
    """One (query, key) pair of one attention layer, every query head."""
    return 4.0 * m["num_attention_heads"] * m["head_dim"]


def serve_flops(m: Mapping[str, Any], processed: int, sampled: int, key_sum: float,
                held_assignments: int) -> float:
    """Forward FLOPs the served tokens need: ``processed`` tokens through every
    delta-rule mixer (projections and recurrence), attention layer
    (projections) and expert layer (router and shared expert);
    ``held_assignments`` (token, held expert) pairs through one routed expert
    each, as the program counted them; ``key_sum`` (query, key) pairs of ONE
    attention layer; ``sampled`` positions through the head.  Norms are left
    out."""
    n = letters(m)
    per_token = (2.0 * (n["K"] * kda_params(m) + n["*"] * attention_params(m)
                        + n["E"] * (shared_params(m) + router_params(m)))
                 + n["K"] * kda_flops_per_token(m))
    return (per_token * processed + 2.0 * expert_params(m) * held_assignments
            + n["*"] * attention_flops_per_pair(m) * key_sum
            + 2.0 * head_params(m) * sampled)


def decode_step_bytes(m: Mapping[str, Any], experts_touched: float, state_bytes: float,
                      rows_read: float) -> float:
    """Bytes a decode step must move: the weights every step reads (mixers,
    attention, shared experts, routers in float32, head) and ``experts_touched``
    held experts (those given a token, over every expert layer); the
    recurrent state and tails read and written, ``state_bytes``; the K/V of
    ``rows_read`` block-rounded cache rows in every attention layer.  The
    activations (a few rows) are left out."""
    n = letters(m)
    weights = (SERVED * (n["K"] * kda_params(m) + n["*"] * attention_params(m)
                         + n["E"] * shared_params(m) + head_params(m))
               + 4 * n["E"] * router_params(m))
    return (weights + SERVED * expert_params(m) * experts_touched + state_bytes
            + n["*"] * rows_read * cache_row_bytes(m))
