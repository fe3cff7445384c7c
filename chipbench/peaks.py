"""The yardstick's constants and closed forms: chip peaks, FLOPs and bytes.

Everything here is computed from shapes, never from the program's HLO, so a
change to the program cannot move the denominator of a utilization.
"""

from __future__ import annotations

from typing import Any, Dict, Mapping, Optional

# Published peaks of one chip, keyed by jax's ``device_kind``.
# Source: Google Cloud documentation, "TPU v5e" (system architecture):
# 197 TFLOP/s bf16, 16 GB HBM2e at 819 GB/s.
PEAKS: Dict[str, Dict[str, float]] = {
    "TPU v5 lite": {"flops_bf16": 197e12, "hbm_bytes_per_s": 819e9,
                    "hbm_bytes": 16e9},
    "TPU v5e": {"flops_bf16": 197e12, "hbm_bytes_per_s": 819e9,
                "hbm_bytes": 16e9},
}


def peaks_for(device_kind: str) -> Dict[str, float]:
    """The peaks of ``device_kind``; an unknown device is an error."""
    if device_kind not in PEAKS:
        raise KeyError(
            f"no published peaks for device kind {device_kind!r}; add it to "
            "chipbench/peaks.py with its source"
        )
    return PEAKS[device_kind]


def head_dim(m: Mapping[str, Any]) -> int:
    return int(m.get("head_dim") or m["hidden_size"] // m["num_attention_heads"])


def layer_matmul_params(m: Mapping[str, Any]) -> int:
    """Weights of one block that sit in a matrix product."""
    d, hd = m["hidden_size"], head_dim(m)
    q = d * m["num_attention_heads"] * hd
    kv = d * m["num_key_value_heads"] * hd
    return 2 * q + 2 * kv + 3 * d * m["intermediate_size"]


def head_matmul_params(m: Mapping[str, Any]) -> int:
    return m["hidden_size"] * m["vocab_size"]


def keys_seen(position: int, window: Optional[int]) -> int:
    """Keys a causal query at 0-based ``position`` attends."""
    n = position + 1
    return n if not window else min(n, window)


def mean_keys(seq: int, window: Optional[int]) -> float:
    return sum(keys_seen(i, window) for i in range(seq)) / seq


def attention_flops_per_query(m: Mapping[str, Any], keys: float) -> float:
    """Forward QK^T and PV of one layer for one query position."""
    return 4.0 * m["num_attention_heads"] * head_dim(m) * keys


def train_flops_per_token(m: Mapping[str, Any], depth: int, seq: int) -> float:
    """Forward and backward, recomputation not counted: 6 per matmul weight
    plus three times the forward attention products over the causal window."""
    mm = depth * layer_matmul_params(m) + head_matmul_params(m)
    att = attention_flops_per_query(m, mean_keys(seq, m.get("sliding_window")))
    return 6.0 * mm + 3.0 * depth * att


def serve_flops(m: Mapping[str, Any], depth: int, processed: int,
                sampled: int, key_sum: float) -> float:
    """Forward FLOPs the served tokens need: ``processed`` tokens through
    the blocks, ``sampled`` positions through the head, and ``key_sum`` the
    sum over processed tokens of the keys each attends."""
    return (2.0 * depth * layer_matmul_params(m) * processed
            + 2.0 * head_matmul_params(m) * sampled
            + depth * attention_flops_per_query(m, 1.0) * key_sum)


def flash_call(m: Mapping[str, Any], rows: int, seq: int,
               backward: bool) -> Dict[str, float]:
    """FLOPs and HBM bytes one layer's flash attention needs for ``rows``
    sequences of ``seq`` tokens (bf16 operands).  Forward: QK^T and PV over
    the causal window, reads q, k, v and writes o.  Backward (no recompute
    counted beyond what the algorithm needs): the score product again plus
    dP, dV, dK, dQ = 5 products of the forward's 2, reads q, k, v, o, do and
    writes dq, dk, dv."""
    h, kvh, hd = m["num_attention_heads"], m["num_key_value_heads"], head_dim(m)
    pairs = rows * seq * mean_keys(seq, m.get("sliding_window"))
    product = 2.0 * h * hd * pairs
    q_bytes = 2.0 * rows * seq * h * hd
    kv_bytes = 2.0 * rows * seq * kvh * hd
    if backward:
        return {"flops": 5.0 * product,
                "bytes": 4.0 * q_bytes + 2.0 * kv_bytes + 2.0 * kv_bytes}
    return {"flops": 2.0 * product, "bytes": 2.0 * q_bytes + 2.0 * kv_bytes}


def roofline_seconds(work: Mapping[str, float], peak: Mapping[str, float]) -> Dict[str, Any]:
    """The least time the chip could take for ``work`` and which bound holds."""
    t_flops = work["flops"] / peak["flops_bf16"]
    t_bytes = work["bytes"] / peak["hbm_bytes_per_s"]
    return {"seconds": max(t_flops, t_bytes),
            "bound": "compute" if t_flops >= t_bytes else "memory"}
