"""Closed forms for a latent-attention, routed-expert configuration (the
``axk1`` key set): FLOPs the served tokens need and the bytes a decode step
cannot avoid.  Computed from the configuration's shapes, never from the
program's HLO, so a change to the program cannot move a denominator.
"""

from __future__ import annotations

from typing import Any, Mapping

from chipbench.weights_axk1 import attention_shapes, published

CACHE_BYTES = 2         # a cache value and a weight in the served type (bfloat16)


def _size(shape) -> int:
    n = 1
    for s in shape:
        n *= s
    return n


def attention_params(m: Mapping[str, Any]) -> int:
    """The five attention matrices of one block."""
    return sum(_size(s) for s in attention_shapes(m).values())


def expert_params(m: Mapping[str, Any]) -> int:
    """One expert's (routed or shared) SwiGLU."""
    return 3 * m["hidden_size"] * m["moe_intermediate_size"]


def dense_ff_params(m: Mapping[str, Any]) -> int:
    return 3 * m["hidden_size"] * m["intermediate_size"]


def router_params(m: Mapping[str, Any]) -> int:
    return m["hidden_size"] * published(m, "n_routed_experts")


def expert_layers(m: Mapping[str, Any]) -> int:
    return m["num_hidden_layers"] - m["first_k_dense_replace"]


def cache_row_bytes(m: Mapping[str, Any]) -> int:
    """Bytes of one token's cache rows over all layers: the KV latent and the
    shared rotary key head, a layer."""
    return (m["kv_lora_rank"] + m["qk_rope_head_dim"]) * CACHE_BYTES * m["num_hidden_layers"]


def attention_flops_per_pair(m: Mapping[str, Any]) -> float:
    """One (query, key) pair of one layer in the PUBLISHED form: the score
    over ``n + r`` dims and the value sum over ``v`` dims, for every head.
    The absorbed form's wider products (over the latent) are not counted."""
    return 2.0 * m["num_attention_heads"] * (
        m["qk_nope_head_dim"] + m["qk_rope_head_dim"] + m["v_head_dim"])


def serve_flops(m: Mapping[str, Any], processed: int, sampled: int, key_sum: float,
                held_assignments: int) -> float:
    """Forward FLOPs the served tokens need in the published form:
    ``processed`` tokens through every block's projections (each once a
    token, ``W_kvb`` included), the dense blocks' SwiGLU, the expert blocks'
    router and shared expert; ``held_assignments`` (token, held expert) pairs
    through one routed expert each, as the program counted them;
    ``key_sum`` (query, key) pairs a layer; ``sampled`` positions through
    the head."""
    depth, dense = m["num_hidden_layers"], m["first_k_dense_replace"]
    per_token = (depth * attention_params(m) + dense * dense_ff_params(m)
                 + expert_layers(m) * (router_params(m)
                                       + m["n_shared_experts"] * expert_params(m)))
    return (2.0 * per_token * processed
            + 2.0 * expert_params(m) * held_assignments
            + depth * attention_flops_per_pair(m) * key_sum
            + 2.0 * m["hidden_size"] * m["vocab_size"] * sampled)


def decode_weight_bytes(m: Mapping[str, Any]) -> float:
    """Every held weight a decode step's products read, once: the blocks
    (attention, dense SwiGLU, shared and held routed experts; the float32
    router) and the head.  The embedding is a gather of a row a token and
    is left out."""
    depth, dense = m["num_hidden_layers"], m["first_k_dense_replace"]
    held = m["n_routed_experts"] + m["n_shared_experts"]
    return (CACHE_BYTES * (depth * attention_params(m) + dense * dense_ff_params(m)
                           + expert_layers(m) * held * expert_params(m)
                           + m["hidden_size"] * m["vocab_size"])
            + 4.0 * expert_layers(m) * router_params(m))


def decode_step_bytes(m: Mapping[str, Any], live_rows: float) -> float:
    """Bytes one decode step cannot avoid: the weights once and the LIVE
    cache rows (those that hold a token) once."""
    return decode_weight_bytes(m) + live_rows * cache_row_bytes(m)
