"""Closed forms for a Trinity (``model_type: afmoe``) configuration: FLOPs the
served tokens need, the cache's bytes by kind of layer, and the bytes the
decode kernel fetches.  Computed from the configuration's shapes and from what
the engine COUNTED (held assignments, rows read), never from the program's
HLO or its kernels' code, so a change to the program cannot move a
denominator.
"""

from __future__ import annotations

from typing import Any, Dict, Mapping

from chipbench.weights_axk1 import published
from chipbench.weights_trinity import attention_shapes

CACHE_BYTES = 2         # a cache value and a weight in the served type (bfloat16)
RING_GRANULE = 512      # a ring's length is a multiple of the decode kernel's block


def _size(shape) -> int:
    n = 1
    for s in shape:
        n *= s
    return n


def attention_params(m: Mapping[str, Any]) -> int:
    """The five attention matrices of one block (q, k, v, the gate, o)."""
    return sum(_size(s) for s in attention_shapes(m).values())


def expert_params(m: Mapping[str, Any]) -> int:
    """One expert's (routed or shared) SwiGLU."""
    return 3 * m["hidden_size"] * m["moe_intermediate_size"]


def dense_ff_params(m: Mapping[str, Any]) -> int:
    return 3 * m["hidden_size"] * m["intermediate_size"]


def router_params(m: Mapping[str, Any]) -> int:
    return m["hidden_size"] * published(m, "num_experts")


def layer_kinds(m: Mapping[str, Any]) -> Dict[str, int]:
    """Layers of each kind: ``window`` (sliding_attention) and ``full``."""
    window = sum(t == "sliding_attention" for t in m["layer_types"])
    return {"window": window, "full": len(m["layer_types"]) - window}


def weight_params(m: Mapping[str, Any]) -> int:
    """Every parameter this chip holds (norm scales and the selection's bias
    left out: 0.03 M)."""
    depth, dense = m["num_hidden_layers"], m["num_dense_layers"]
    held = m["num_experts"] + m["num_shared_experts"]
    return (depth * attention_params(m) + dense * dense_ff_params(m)
            + (depth - dense) * (held * expert_params(m) + router_params(m))
            + 2 * m["hidden_size"] * m["vocab_size"])


def cache_row_bytes(m: Mapping[str, Any]) -> int:
    """Bytes of one cached position of ONE layer: K and V of every KV head."""
    return 2 * m["num_key_value_heads"] * m["head_dim"] * CACHE_BYTES


def ring_rows(m: Mapping[str, Any], chunk: int) -> int:
    """Rows of a window layer's ring: the window and a prefill chunk less one,
    rounded up to the kernel's block."""
    return -(-(m["sliding_window"] + chunk - 1) // RING_GRANULE) * RING_GRANULE


def slot_rows(m: Mapping[str, Any], serve: Mapping[str, Any]) -> Dict[str, int]:
    """Rows one slot reserves in ONE layer of each kind."""
    return {"window": min(ring_rows(m, serve["prefill_chunk"]), serve["max_len"]),
            "full": serve["max_len"]}


def pool_bytes(m: Mapping[str, Any], serve: Mapping[str, Any]) -> Dict[str, int]:
    """Bytes the pool reserves, by kind of layer."""
    rows, kinds = slot_rows(m, serve), layer_kinds(m)
    return {k: serve["num_slots"] * kinds[k] * rows[k] * cache_row_bytes(m) for k in kinds}


def one_length_slots(m: Mapping[str, Any], serve: Mapping[str, Any]) -> int:
    """Slots the same bytes would hold with ``max_len`` rows in every layer."""
    slot = m["num_hidden_layers"] * serve["max_len"] * cache_row_bytes(m)
    return sum(pool_bytes(m, serve).values()) // slot


def live_bytes(m: Mapping[str, Any], live_rows: Mapping[str, float]) -> Dict[str, float]:
    """Bytes of the rows that hold a token, by kind: ``live_rows[kind]`` is
    the rows of ONE layer of that kind, summed over the slots."""
    kinds = layer_kinds(m)
    return {k: kinds[k] * live_rows[k] * cache_row_bytes(m) for k in kinds}


def attention_flops_per_pair(m: Mapping[str, Any]) -> float:
    """One (query, key) pair of one layer: the score and the value sum over
    ``head_dim``, for every query head."""
    return 4.0 * m["num_attention_heads"] * m["head_dim"]


def pairs(n: int, window: int = 0) -> float:
    """(query, key) pairs of a context of ``n`` positions in one layer:
    causal over the whole context, or over ``min(context, window)``."""
    if not window or n <= window:
        return n * (n + 1) / 2.0
    return window * (window + 1) / 2.0 + (n - window) * float(window)


def serve_flops(m: Mapping[str, Any], processed: int, sampled: int, pairs_by_kind: Mapping[str, float],
                held_assignments: int) -> float:
    """Forward FLOPs the served tokens need: ``processed`` tokens through
    every block's five attention matrices, the dense blocks' SwiGLU, the
    expert blocks' router and shared expert; ``held_assignments`` (token, held
    expert) pairs through one routed expert each, as the program counted them;
    ``pairs_by_kind[kind]`` (query, key) pairs of ONE layer of that kind (a
    window layer's over ``min(context, sliding_window)``); ``sampled``
    positions through the head.  Norms, rotation and the gate's sigmoid are
    left out."""
    depth, dense, kinds = m["num_hidden_layers"], m["num_dense_layers"], layer_kinds(m)
    per_token = (depth * attention_params(m) + dense * dense_ff_params(m)
                 + (depth - dense) * (router_params(m)
                                      + m["num_shared_experts"] * expert_params(m)))
    return (2.0 * per_token * processed
            + 2.0 * expert_params(m) * held_assignments
            + attention_flops_per_pair(m) * sum(kinds[k] * pairs_by_kind[k] for k in kinds)
            + 2.0 * m["hidden_size"] * m["vocab_size"] * sampled)


def decode_kernel_bytes(m: Mapping[str, Any], rows_read: Mapping[str, float]) -> float:
    """Bytes the decode kernel fetches from the cache: ``rows_read[kind]``
    block-rounded rows of ONE layer of that kind, as the engine counted them,
    K and V of every one, in every layer of the kind.  The queries and the
    outputs (a few rows a step) are left out."""
    kinds = layer_kinds(m)
    return sum(kinds[k] * rows_read[k] for k in kinds) * float(cache_row_bytes(m))
