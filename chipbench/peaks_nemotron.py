"""Closed forms for a Nemotron-H configuration with experts (``model_type:
nemotron_h``): parameters by kind of layer, the pool's bytes by kind of state,
the FLOPs the served tokens need and the bytes a decode step must move.
Computed from the configuration's shapes and from what the engine COUNTED
(held assignments, experts given a token, recurrent-state bytes, cache rows),
never from the program's HLO or its kernels' code, so a change to the program
cannot move a denominator.
"""

from __future__ import annotations

from typing import Any, Dict, Mapping

from chipbench.weights_axk1 import published
from chipbench.weights_nemotron import ssm_sizes

SERVED = 2              # bytes of a weight, an activation, a K/V value, a conv tail value (bf16)


def letters(m: Mapping[str, Any]) -> Dict[str, int]:
    """Layers of each kind: ``M`` mixers, ``E`` expert layers, ``*`` attention."""
    pattern = m["hybrid_override_pattern"]
    return {k: pattern.count(k) for k in "ME*"}


def mixer_params(m: Mapping[str, Any]) -> int:
    """One mixer layer: the two projections, the conv, and the per-head and
    per-channel vectors."""
    d, s, heads = m["hidden_size"], ssm_sizes(m), m["mamba_num_heads"]
    return (d * s["in_width"] + s["d_inner"] * d + (m["conv_kernel"] + 1) * s["conv_dim"]
            + 3 * heads + s["d_inner"])


def attention_params(m: Mapping[str, Any]) -> int:
    d, hd = m["hidden_size"], m["head_dim"]
    q, kv = m["num_attention_heads"] * hd, m["num_key_value_heads"] * hd
    return 2 * d * q + 2 * d * kv


def expert_params(m: Mapping[str, Any]) -> int:
    """One routed expert's ungated up and down."""
    return 2 * m["hidden_size"] * m["moe_intermediate_size"]


def shared_params(m: Mapping[str, Any]) -> int:
    return 2 * m["hidden_size"] * m["moe_shared_expert_intermediate_size"]


def router_params(m: Mapping[str, Any]) -> int:
    return m["hidden_size"] * published(m, "n_routed_experts")


def head_params(m: Mapping[str, Any]) -> int:
    return m["hidden_size"] * m["vocab_size"]


def weight_params(m: Mapping[str, Any]) -> int:
    """Every parameter this chip holds (norm scales and the bias left out)."""
    n = letters(m)
    return (n["M"] * mixer_params(m) + n["*"] * attention_params(m)
            + n["E"] * (m["n_routed_experts"] * expert_params(m) + shared_params(m)
                        + router_params(m))
            + 2 * head_params(m))


def cache_row_bytes(m: Mapping[str, Any]) -> int:
    """Bytes of one cached position of ONE attention layer: K and V."""
    return 2 * m["num_key_value_heads"] * m["head_dim"] * SERVED


def slot_state_bytes(m: Mapping[str, Any]) -> int:
    """Recurrent-state bytes ONE slot keeps over every mixer layer: the conv
    tail (``conv_kernel - 1`` inputs, bf16) and the float32 state."""
    s = ssm_sizes(m)
    per_layer = ((m["conv_kernel"] - 1) * s["conv_dim"] * SERVED
                 + m["mamba_num_heads"] * m["mamba_head_dim"] * m["ssm_state_size"] * 4)
    return letters(m)["M"] * per_layer


def pool_bytes(m: Mapping[str, Any], serve: Mapping[str, Any]) -> Dict[str, int]:
    """Bytes the pool reserves: ``full`` (the attention layers' rows) and
    ``state`` (the mixer layers' tails and states)."""
    slots = serve["num_slots"]
    return {"full": slots * letters(m)["*"] * serve["max_len"] * cache_row_bytes(m),
            "state": slots * slot_state_bytes(m)}


def scan_flops_per_token(m: Mapping[str, Any]) -> float:
    """The recurrence at one position of ONE mixer layer: the state's decay
    and inflow (3 a state value) and its read-out (2), the conv, the D skip and
    the gate; the chunked form's own products are an implementation of it."""
    s = ssm_sizes(m)
    state = m["mamba_num_heads"] * m["mamba_head_dim"] * m["ssm_state_size"]
    return 5.0 * state + 2.0 * m["conv_kernel"] * s["conv_dim"] + 4.0 * s["d_inner"]


def attention_flops_per_pair(m: Mapping[str, Any]) -> float:
    """One (query, key) pair of one attention layer, every query head."""
    return 4.0 * m["num_attention_heads"] * m["head_dim"]


def serve_flops(m: Mapping[str, Any], processed: int, sampled: int, key_sum: float,
                held_assignments: int) -> float:
    """Forward FLOPs the served tokens need: ``processed`` tokens through every
    mixer (projections and scan), attention layer (projections) and expert
    layer (router and shared expert); ``held_assignments`` (token, held
    expert) pairs through one routed expert each, as the program counted them;
    ``key_sum`` (query, key) pairs of ONE attention layer; ``sampled``
    positions through the head.  Norms are left out."""
    n = letters(m)
    per_token = (2.0 * (n["M"] * mixer_params(m) + n["*"] * attention_params(m)
                        + n["E"] * (shared_params(m) + router_params(m)))
                 + n["M"] * scan_flops_per_token(m))
    return (per_token * processed + 2.0 * expert_params(m) * held_assignments
            + n["*"] * attention_flops_per_pair(m) * key_sum
            + 2.0 * head_params(m) * sampled)


def decode_step_bytes(m: Mapping[str, Any], experts_touched: float, state_bytes: float,
                      rows_read: float) -> float:
    """Bytes a decode step must move: the weights every step reads (mixers,
    attention, shared experts, routers in float32, head) and ``experts_touched``
    held experts (those given a token, over every expert layer); the
    recurrent state read and written, ``state_bytes``; the K/V of
    ``rows_read`` block-rounded cache rows in every attention layer.  The
    activations (a few rows) are left out."""
    n = letters(m)
    weights = (SERVED * (n["M"] * mixer_params(m) + n["*"] * attention_params(m)
                         + n["E"] * shared_params(m) + head_params(m))
               + 4 * n["E"] * router_params(m))
    return (weights + SERVED * expert_params(m) * experts_touched + state_bytes
            + n["*"] * rows_read * cache_row_bytes(m))
