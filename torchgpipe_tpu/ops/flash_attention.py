"""Flash attention as Pallas TPU kernels (forward + backward).

The framework's hot op: fused online-softmax attention that never
materializes the ``[s, s]`` score matrix in HBM — scores live in VMEM one
``[block_q, block_k]`` tile at a time, with f32 accumulation on the MXU.
Backward follows the standard flash decomposition (Dao, FlashAttention-2;
public algorithm, implemented here from the math against
/opt/skills/guides/pallas_guide.md):

* forward saves only ``O`` and the per-row logsumexp ``L``,
* ``dQ`` kernel re-streams K/V tiles; ``dK/dV`` kernel re-streams Q tiles,
* ``D = rowsum(dO * O)`` is precomputed outside the kernels (cheap
  elementwise reduce that XLA fuses).

Supports causal masking and grouped-query attention (K/V at ``g`` heads,
queries at ``h = g*r``); the kernels are gridded over ``(batch*heads,
sequence blocks)`` so each program works on MXU-aligned ``[block, d]``
tiles.  ``torchgpipe_tpu.parallel.attention`` dispatches here on TPU when
shapes meet the tiling constraints (``d`` and ``s`` multiples of 128),
falling back to the XLA path otherwise; ``interpret=True`` runs the same
kernels on CPU for the test oracle.

The reference has no kernel of any kind — its attention story is absent
entirely (SURVEY.md §2.2); this module is TPU-native new capability.
"""

from __future__ import annotations

import functools
from typing import Any, Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_NEG = -1e30

# K/V rows resident in VMEM per program beyond roughly this many bytes tip
# the kernels into the streaming (third-grid-dimension) variants, which keep
# only one [block, d] tile of K/V in VMEM at a time.
_STREAM_BYTES = 4 * 1024 * 1024


def _validate_window(causal: bool, window: Optional[int]) -> None:
    """Shared entry-point validation for sliding-window attention."""
    if window is None:
        return
    if not causal:
        raise ValueError(
            "window (sliding-window attention) requires causal=True"
        )
    if window < 1:
        raise ValueError("window must be >= 1")


def _kv_index(i: jax.Array, h: int, g: int) -> jax.Array:
    """Row in the [b*g, s, d] K/V array for query row ``i`` of [b*h, s, d]."""
    r = h // g
    return (i // h) * g + (i % h) // r


# --------------------------------------------------------------------- #
# forward                                                               #
# --------------------------------------------------------------------- #


def _fwd_kernel(
    q_ref: Any,
    k_ref: Any,
    v_ref: Any,
    o_ref: Any,
    lse_ref: Any,
    *,
    causal: bool,
    sm_scale: float,
    block_q: int,
    block_k: int,
    seq_k: int,
    window: Optional[int],
) -> None:
    j = pl.program_id(1)
    qb = q_ref[0].astype(jnp.float32) * sm_scale  # [Bq, d]
    nk = seq_k // block_k
    jk0 = 0
    if causal:
        # Only KV blocks overlapping the causal triangle (banded by the
        # sliding window when set) of this Q block.
        nk = lax.min(nk, lax.div((j + 1) * block_q + block_k - 1, block_k))
        if window is not None:
            jk0 = _first_valid_kv(j, block_q, block_k, window)

    def body(jb, carry):
        m, l, acc = carry
        kb = k_ref[0, pl.ds(jb * block_k, block_k), :].astype(jnp.float32)
        vb = v_ref[0, pl.ds(jb * block_k, block_k), :].astype(jnp.float32)
        s = lax.dot_general(
            qb, kb, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        )  # [Bq, Bk]
        if causal:
            s = _mask_causal(s, j, jb, block_q, block_k, window)
        m_new = jnp.maximum(m, jnp.max(s, axis=1, keepdims=True))
        p = jnp.exp(s - m_new)
        corr = jnp.exp(m - m_new)
        l_new = l * corr + jnp.sum(p, axis=1, keepdims=True)
        acc_new = acc * corr + lax.dot_general(
            p, vb, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        return m_new, l_new, acc_new

    m0 = jnp.full((block_q, 1), _NEG, jnp.float32)
    l0 = jnp.zeros((block_q, 1), jnp.float32)
    acc0 = jnp.zeros((block_q, q_ref.shape[-1]), jnp.float32)
    m, l, acc = lax.fori_loop(jk0, nk, body, (m0, l0, acc0))
    o_ref[0] = (acc / l).astype(o_ref.dtype)
    lse_ref[0] = m + jnp.log(l)  # [Bq, 1]


def _flash_fwd_call(
    q: jnp.ndarray,
    k: jnp.ndarray,
    v: jnp.ndarray,
    h: int,
    g: int,
    causal: bool,
    sm_scale: float,
    block_q: int,
    block_k: int,
    interpret: bool,
    window: Optional[int] = None,
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    bh, s, d = q.shape
    grid = (bh, s // block_q)
    kv_spec = pl.BlockSpec(
        (1, k.shape[1], d), lambda i, j: (_kv_index(i, h, g), 0, 0)
    )
    o, lse = pl.pallas_call(
        functools.partial(
            _fwd_kernel, causal=causal, sm_scale=sm_scale,
            block_q=block_q, block_k=block_k, seq_k=k.shape[1],
            window=window,
        ),
        name="flash_fwd",
        out_shape=(
            jax.ShapeDtypeStruct(q.shape, q.dtype),
            jax.ShapeDtypeStruct((bh, s, 1), jnp.float32),
        ),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, block_q, d), lambda i, j: (i, j, 0)),
            kv_spec,
            kv_spec,
        ],
        out_specs=(
            pl.BlockSpec((1, block_q, d), lambda i, j: (i, j, 0)),
            pl.BlockSpec((1, block_q, 1), lambda i, j: (i, j, 0)),
        ),
        interpret=interpret,
    )(q, k, v)
    return o, lse


# --------------------------------------------------------------------- #
# streaming variants: K/V (or Q) tiles stream from HBM on a third grid  #
# dimension, with the online-softmax state carried in VMEM scratch —    #
# per-program VMEM is O(block·d) regardless of sequence length, which   #
# is what very long single-chip sequences (≳32k) need.  The TPU grid    #
# iterates its trailing dimension sequentially, so scratch accumulates  #
# correctly across the K/V steps of one (row, q-block) cell.            #
# --------------------------------------------------------------------- #


def _causal_overlap(
    jq: jax.Array,
    jk: jax.Array,
    block_q: int,
    block_k: int,
    window: Optional[int] = None,
) -> jax.Array:
    """Whether q block jq has any unmasked position against k block jk
    under causal masking, optionally banded by a sliding ``window``
    (attend iff ``0 <= qpos - kpos < window``)."""
    ok = (jq + 1) * block_q - 1 >= jk * block_k
    if window is not None:
        # Block-level band check: some (qpos, kpos) pair in the blocks has
        # qpos - kpos < window, i.e. the SMALLEST difference in the pair of
        # blocks (first q row vs last k col) is below the window.
        ok = ok & (jq * block_q - ((jk + 1) * block_k - 1) < window)
    return ok


def _last_valid_kv(jq: jax.Array, block_q: int, block_k: int) -> jax.Array:
    """Largest K/V block index with any unmasked position for q block
    ``jq`` under causal masking (== the diagonal block)."""
    return ((jq + 1) * block_q - 1) // block_k


def _first_valid_kv(
    jq: jax.Array,
    block_q: int,
    block_k: int,
    window: Optional[int] = None,
) -> jax.Array:
    """Smallest K/V block index inside the sliding window for q block
    ``jq`` (0 without a window)."""
    if window is None:
        return 0
    lo = jq * block_q - (window - 1)  # kpos of the oldest visible key
    return jnp.maximum(lo, 0) // block_k


def _first_valid_q(jk: jax.Array, block_q: int, block_k: int) -> jax.Array:
    """Smallest q block index with any unmasked position against K/V
    block ``jk`` under causal masking."""
    return (jk * block_k) // block_q


def _last_valid_q(
    jk: jax.Array,
    block_q: int,
    block_k: int,
    nq: int,
    window: Optional[int] = None,
) -> jax.Array:
    """Largest q block index inside the sliding window for K/V block
    ``jk`` (``nq - 1`` without a window)."""
    if window is None:
        return nq - 1
    hi = (jk + 1) * block_k - 1 + window - 1  # newest query seeing block jk
    return jnp.minimum(hi // block_q, nq - 1)


# Causal block-skipping for the streaming grids: the TPU grid is
# rectangular, but clamping the BLOCK INDEX MAP to the last/first valid
# block makes every fully-masked cell re-request the tile already in
# VMEM — Pallas's pipelining skips the HBM copy when the block index is
# unchanged between iterations, and ``pl.when`` skips the compute.  Net:
# masked cells cost one grid bump, no bandwidth, no FLOPs (the reason
# streaming used to lose to dense at moderate causal lengths —
# BENCH_NOTES round-2 table, 87.1 vs 64.8 ms @4k).


def _clamped_kv_block(
    j: jax.Array,
    jk: jax.Array,
    block_q: int,
    block_k: int,
    causal: bool,
    window: Optional[int] = None,
) -> jax.Array:
    """K/V block to FETCH at streaming grid cell (q block j, step jk):
    clipped into the valid causal/window band so masked cells re-request
    a resident tile."""
    if not causal:
        return jk
    return jnp.clip(
        jk,
        _first_valid_kv(j, block_q, block_k, window),
        _last_valid_kv(j, block_q, block_k),
    )


def _clamped_q_block(
    jk: jax.Array,
    jq: jax.Array,
    block_q: int,
    block_k: int,
    causal: bool,
    nq: int,
    window: Optional[int] = None,
) -> jax.Array:
    """Q block to FETCH at streaming dK/dV grid cell (kv block jk, step
    jq), clipped into the valid causal/window band."""
    if not causal:
        return jq
    return jnp.clip(
        jq,
        _first_valid_q(jk, block_q, block_k),
        _last_valid_q(jk, block_q, block_k, nq, window),
    )


def _mask_causal(
    s: jnp.ndarray,
    jq: jax.Array,
    jk: jax.Array,
    block_q: int,
    block_k: int,
    window: Optional[int] = None,
) -> jnp.ndarray:
    qpos = jq * block_q + lax.broadcasted_iota(jnp.int32, s.shape, 0)
    kpos = jk * block_k + lax.broadcasted_iota(jnp.int32, s.shape, 1)
    m = qpos >= kpos
    if window is not None:
        m = m & (qpos - kpos < window)
    return jnp.where(m, s, _NEG)


def _fwd_stream_kernel(
    q_ref: Any,
    k_ref: Any,
    v_ref: Any,
    o_ref: Any,
    lse_ref: Any,
    m_sc: Any,
    l_sc: Any,
    acc_sc: Any,
    *,
    causal: bool,
    sm_scale: float,
    block_q: int,
    block_k: int,
    nk: int,
    window: Optional[int],
) -> None:
    j = pl.program_id(1)
    jk = pl.program_id(2)

    @pl.when(jk == 0)
    def _init():
        m_sc[...] = jnp.full_like(m_sc, _NEG)
        l_sc[...] = jnp.zeros_like(l_sc)
        acc_sc[...] = jnp.zeros_like(acc_sc)

    run = (
        _causal_overlap(j, jk, block_q, block_k, window)
        if causal else jk >= 0
    )

    @pl.when(run)
    def _body():
        qb = q_ref[0].astype(jnp.float32) * sm_scale
        kb = k_ref[0].astype(jnp.float32)
        vb = v_ref[0].astype(jnp.float32)
        s = lax.dot_general(
            qb, kb, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        if causal:
            s = _mask_causal(s, j, jk, block_q, block_k, window)
        m_prev = m_sc[...]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        p = jnp.exp(s - m_new)
        corr = jnp.exp(m_prev - m_new)
        l_sc[...] = l_sc[...] * corr + jnp.sum(p, axis=1, keepdims=True)
        acc_sc[...] = acc_sc[...] * corr + lax.dot_general(
            p, vb, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        m_sc[...] = m_new

    @pl.when(jk == nk - 1)
    def _finish():
        o_ref[0] = (acc_sc[...] / l_sc[...]).astype(o_ref.dtype)
        lse_ref[0] = m_sc[...] + jnp.log(l_sc[...])


def _flash_fwd_call_stream(
    q: jnp.ndarray,
    k: jnp.ndarray,
    v: jnp.ndarray,
    h: int,
    g: int,
    causal: bool,
    sm_scale: float,
    block_q: int,
    block_k: int,
    interpret: bool,
    window: Optional[int] = None,
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    bh, s, d = q.shape
    sk = k.shape[1]
    nk = sk // block_k
    grid = (bh, s // block_q, nk)
    kv_im = lambda i, j, jk: (  # noqa: E731
        _kv_index(i, h, g),
        _clamped_kv_block(j, jk, block_q, block_k, causal, window),
        0,
    )
    o, lse = pl.pallas_call(
        functools.partial(
            _fwd_stream_kernel, causal=causal, sm_scale=sm_scale,
            block_q=block_q, block_k=block_k, nk=nk, window=window,
        ),
        name="flash_fwd_stream",
        out_shape=(
            jax.ShapeDtypeStruct(q.shape, q.dtype),
            jax.ShapeDtypeStruct((bh, s, 1), jnp.float32),
        ),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, block_q, d), lambda i, j, jk: (i, j, 0)),
            pl.BlockSpec((1, block_k, d), kv_im),
            pl.BlockSpec((1, block_k, d), kv_im),
        ],
        out_specs=(
            pl.BlockSpec((1, block_q, d), lambda i, j, jk: (i, j, 0)),
            pl.BlockSpec((1, block_q, 1), lambda i, j, jk: (i, j, 0)),
        ),
        scratch_shapes=[
            pltpu.VMEM((block_q, 1), jnp.float32),
            pltpu.VMEM((block_q, 1), jnp.float32),
            pltpu.VMEM((block_q, d), jnp.float32),
        ],
        interpret=interpret,
    )(q, k, v)
    return o, lse


def _dq_stream_kernel(
    q_ref: Any,
    k_ref: Any,
    v_ref: Any,
    do_ref: Any,
    lse_ref: Any,
    delta_ref: Any,
    dq_ref: Any,
    dq_sc: Any,
    *,
    causal: bool,
    sm_scale: float,
    block_q: int,
    block_k: int,
    nk: int,
    window: Optional[int],
) -> None:
    j = pl.program_id(1)
    jk = pl.program_id(2)

    @pl.when(jk == 0)
    def _init():
        dq_sc[...] = jnp.zeros_like(dq_sc)

    run = (
        _causal_overlap(j, jk, block_q, block_k, window)
        if causal else jk >= 0
    )

    @pl.when(run)
    def _body():
        qb = q_ref[0].astype(jnp.float32)
        kb = k_ref[0].astype(jnp.float32)
        vb = v_ref[0].astype(jnp.float32)
        dob = do_ref[0].astype(jnp.float32)
        s = lax.dot_general(
            qb, kb, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        ) * sm_scale
        if causal:
            s = _mask_causal(s, j, jk, block_q, block_k, window)
        p = jnp.exp(s - lse_ref[0])
        dp = lax.dot_general(
            dob, vb, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        ds = p * (dp - delta_ref[0])
        dq_sc[...] = dq_sc[...] + lax.dot_general(
            ds, kb, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )

    @pl.when(jk == nk - 1)
    def _finish():
        dq_ref[0] = (dq_sc[...] * sm_scale).astype(dq_ref.dtype)


def _dkv_stream_kernel(
    q_ref: Any,
    k_ref: Any,
    v_ref: Any,
    do_ref: Any,
    lse_ref: Any,
    delta_ref: Any,
    dk_ref: Any,
    dv_ref: Any,
    dk_sc: Any,
    dv_sc: Any,
    *,
    causal: bool,
    sm_scale: float,
    block_q: int,
    block_k: int,
    nq: int,
    window: Optional[int],
) -> None:
    jk = pl.program_id(1)
    jq = pl.program_id(2)

    @pl.when(jq == 0)
    def _init():
        dk_sc[...] = jnp.zeros_like(dk_sc)
        dv_sc[...] = jnp.zeros_like(dv_sc)

    run = (
        _causal_overlap(jq, jk, block_q, block_k, window)
        if causal else jq >= 0
    )

    @pl.when(run)
    def _body():
        qb = q_ref[0].astype(jnp.float32)
        kb = k_ref[0].astype(jnp.float32)
        vb = v_ref[0].astype(jnp.float32)
        dob = do_ref[0].astype(jnp.float32)
        s = lax.dot_general(
            qb, kb, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        ) * sm_scale
        if causal:
            s = _mask_causal(s, jq, jk, block_q, block_k, window)
        p = jnp.exp(s - lse_ref[0])
        dv_sc[...] = dv_sc[...] + lax.dot_general(
            p, dob, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        dp = lax.dot_general(
            dob, vb, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        ds = p * (dp - delta_ref[0])
        dk_sc[...] = dk_sc[...] + lax.dot_general(
            ds, qb, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )

    @pl.when(jq == nq - 1)
    def _finish():
        dk_ref[0] = (dk_sc[...] * sm_scale).astype(dk_ref.dtype)
        dv_ref[0] = dv_sc[...].astype(dv_ref.dtype)


# --------------------------------------------------------------------- #
# backward                                                              #
# --------------------------------------------------------------------- #


def _dq_kernel(
    q_ref: Any,
    k_ref: Any,
    v_ref: Any,
    do_ref: Any,
    lse_ref: Any,
    delta_ref: Any,
    dq_ref: Any,
    *,
    causal: bool,
    sm_scale: float,
    block_q: int,
    block_k: int,
    seq_k: int,
    window: Optional[int],
) -> None:
    j = pl.program_id(1)
    qb = q_ref[0].astype(jnp.float32)
    dob = do_ref[0].astype(jnp.float32)
    lse_b = lse_ref[0]      # [Bq, 1]
    delta_b = delta_ref[0]  # [Bq, 1]
    nk = seq_k // block_k
    jk0 = 0
    if causal:
        nk = lax.min(nk, lax.div((j + 1) * block_q + block_k - 1, block_k))
        if window is not None:
            jk0 = _first_valid_kv(j, block_q, block_k, window)

    def body(jb, dq):
        kb = k_ref[0, pl.ds(jb * block_k, block_k), :].astype(jnp.float32)
        vb = v_ref[0, pl.ds(jb * block_k, block_k), :].astype(jnp.float32)
        s = lax.dot_general(
            qb, kb, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        ) * sm_scale
        if causal:
            s = _mask_causal(s, j, jb, block_q, block_k, window)
        p = jnp.exp(s - lse_b)  # [Bq, Bk]
        dp = lax.dot_general(
            dob, vb, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        ds = p * (dp - delta_b)
        return dq + lax.dot_general(
            ds, kb, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )

    dq = lax.fori_loop(
        jk0, nk, body, jnp.zeros((block_q, q_ref.shape[-1]), jnp.float32)
    )
    dq_ref[0] = (dq * sm_scale).astype(dq_ref.dtype)


def _dkv_kernel(
    q_ref: Any,
    k_ref: Any,
    v_ref: Any,
    do_ref: Any,
    lse_ref: Any,
    delta_ref: Any,
    dk_ref: Any,
    dv_ref: Any,
    *,
    causal: bool,
    sm_scale: float,
    block_q: int,
    block_k: int,
    seq_q: int,
    window: Optional[int],
) -> None:
    jk = pl.program_id(1)
    kb = k_ref[0].astype(jnp.float32)  # [Bk, d]
    vb = v_ref[0].astype(jnp.float32)
    nq = seq_q // block_q
    jq0 = lax.div(jk * block_k, block_q) if causal else 0
    jq_hi = (
        _last_valid_q(jk, block_q, block_k, nq, window) + 1
        if causal else nq
    )

    def body(jq, carry):
        dk, dv = carry
        qb = q_ref[0, pl.ds(jq * block_q, block_q), :].astype(jnp.float32)
        dob = do_ref[0, pl.ds(jq * block_q, block_q), :].astype(jnp.float32)
        lse_b = lse_ref[0, pl.ds(jq * block_q, block_q), :]      # [Bq, 1]
        delta_b = delta_ref[0, pl.ds(jq * block_q, block_q), :]  # [Bq, 1]
        s = lax.dot_general(
            qb, kb, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        ) * sm_scale
        if causal:
            s = _mask_causal(s, jq, jk, block_q, block_k, window)
        p = jnp.exp(s - lse_b)  # [Bq, Bk]
        dv_new = dv + lax.dot_general(
            p, dob, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        dp = lax.dot_general(
            dob, vb, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        ds = p * (dp - delta_b)
        dk_new = dk + lax.dot_general(
            ds, qb, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        return dk_new, dv_new

    d = k_ref.shape[-1]
    dk, dv = lax.fori_loop(
        jq0, jq_hi, body,
        (jnp.zeros((block_k, d), jnp.float32),
         jnp.zeros((block_k, d), jnp.float32)),
    )
    dk_ref[0] = (dk * sm_scale).astype(dk_ref.dtype)
    dv_ref[0] = dv.astype(dv_ref.dtype)


# --------------------------------------------------------------------- #
# custom_vjp wiring                                                     #
# --------------------------------------------------------------------- #


@functools.partial(
    jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7, 8, 9, 10)
)
def _flash(
    q: jnp.ndarray,
    k: jnp.ndarray,
    v: jnp.ndarray,
    h: int,
    g: int,
    causal: bool,
    sm_scale: float,
    blocks: Optional[Tuple[int, int]],
    interpret: bool,
    streaming: bool,
    window: Optional[int],
) -> jnp.ndarray:
    fwd = _flash_fwd_call_stream if streaming else _flash_fwd_call
    o, _ = fwd(
        q, k, v, h, g, causal, sm_scale, blocks[0], blocks[1], interpret,
        window,
    )
    return o


def _flash_vjp_fwd(
    q: jnp.ndarray,
    k: jnp.ndarray,
    v: jnp.ndarray,
    h: int,
    g: int,
    causal: bool,
    sm_scale: float,
    blocks: Optional[Tuple[int, int]],
    interpret: bool,
    streaming: bool,
    window: Optional[int],
) -> Tuple:
    from jax.ad_checkpoint import checkpoint_name

    fwd = _flash_fwd_call_stream if streaming else _flash_fwd_call
    o, lse = fwd(
        q, k, v, h, g, causal, sm_scale, blocks[0], blocks[1], interpret,
        window,
    )
    # Checkpoint-named so remat policies compose with the kernel: a policy
    # saving "flash_out"/"flash_stats" keeps (or host-offloads) the vjp
    # residuals and the backward never replays the forward kernel; a
    # policy dropping them recomputes the kernel once in the backward
    # (checkpoint.NAMED_SAVE_POINTS; docs/tuning.md).
    o = checkpoint_name(o, "flash_out")
    lse = checkpoint_name(lse, "flash_stats")
    return o, (q, k, v, o, lse)


def _flash_vjp_bwd(
    h: int,
    g: int,
    causal: bool,
    sm_scale: float,
    blocks: Optional[Tuple[int, int]],
    interpret: bool,
    streaming: bool,
    window: Optional[int],
    res: Tuple,
    do: jnp.ndarray,
) -> Tuple:
    if streaming:
        return _flash_bwd_stream(
            h, g, causal, sm_scale, blocks, interpret, res, do, window
        )
    return _flash_bwd_resident(
        h, g, causal, sm_scale, blocks, interpret, res, do, window
    )


def _flash_bwd_stream(
    h: int,
    g: int,
    causal: bool,
    sm_scale: float,
    blocks: Optional[Tuple[int, int]],
    interpret: bool,
    res: Tuple,
    do: jnp.ndarray,
    window: Optional[int] = None,
) -> Tuple:
    q, k, v, o, lse = res
    block_q, block_k = blocks
    bh, s, d = q.shape
    bg = k.shape[0]
    sk = k.shape[1]
    delta = jnp.sum(
        do.astype(jnp.float32) * o.astype(jnp.float32), axis=-1,
        keepdims=True,
    )

    kernel_args = (q, k, v, do, lse, delta)
    row3 = pl.BlockSpec((1, block_q, d), lambda i, j, jk: (i, j, 0))
    row2 = pl.BlockSpec((1, block_q, 1), lambda i, j, jk: (i, j, 0))
    kv3 = pl.BlockSpec(
        (1, block_k, d),
        lambda i, j, jk: (
            _kv_index(i, h, g),
            _clamped_kv_block(j, jk, block_q, block_k, causal, window),
            0,
        ),
    )
    dq = pl.pallas_call(
        functools.partial(
            _dq_stream_kernel, causal=causal, sm_scale=sm_scale,
            block_q=block_q, block_k=block_k, nk=sk // block_k,
            window=window,
        ),
        name="flash_bwd_dq_stream",
        out_shape=jax.ShapeDtypeStruct(q.shape, q.dtype),
        grid=(bh, s // block_q, sk // block_k),
        in_specs=[row3, kv3, kv3, row3, row2, row2],
        out_specs=row3,
        scratch_shapes=[pltpu.VMEM((block_q, d), jnp.float32)],
        interpret=interpret,
    )(*kernel_args)

    # dK/dV per QUERY head (expanded), summed over the group afterwards;
    # grid streams Q blocks on the trailing dimension.  Invalid steps sit
    # BEFORE the first diagonal block (plain causal) and, with a window,
    # also AFTER the band's last q block — hence the two-sided clip in
    # _clamped_q_block.
    nq_s = s // block_q
    q_im = lambda i, jk, jq: (  # noqa: E731
        i, _clamped_q_block(jk, jq, block_q, block_k, causal, nq_s, window), 0
    )
    qrow3 = pl.BlockSpec((1, block_q, d), q_im)
    qrow2 = pl.BlockSpec((1, block_q, 1), q_im)
    kvb = pl.BlockSpec(
        (1, block_k, d), lambda i, jk, jq: (_kv_index(i, h, g), jk, 0)
    )
    out_kvb = pl.BlockSpec((1, block_k, d), lambda i, jk, jq: (i, jk, 0))
    dk_exp, dv_exp = pl.pallas_call(
        functools.partial(
            _dkv_stream_kernel, causal=causal, sm_scale=sm_scale,
            block_q=block_q, block_k=block_k, nq=nq_s, window=window,
        ),
        name="flash_bwd_dkv_stream",
        out_shape=(
            jax.ShapeDtypeStruct((bh, sk, d), jnp.float32),
            jax.ShapeDtypeStruct((bh, sk, d), jnp.float32),
        ),
        grid=(bh, sk // block_k, s // block_q),
        in_specs=[qrow3, kvb, kvb, qrow3, qrow2, qrow2],
        out_specs=(out_kvb, out_kvb),
        scratch_shapes=[
            pltpu.VMEM((block_k, d), jnp.float32),
            pltpu.VMEM((block_k, d), jnp.float32),
        ],
        interpret=interpret,
    )(*kernel_args)

    r = h // g
    b = bh // h
    dk = dk_exp.reshape(b, g, r, sk, d).sum(axis=2).reshape(bg, sk, d)
    dv = dv_exp.reshape(b, g, r, sk, d).sum(axis=2).reshape(bg, sk, d)
    return dq, dk.astype(k.dtype), dv.astype(v.dtype)


def _flash_bwd_resident(
    h: int,
    g: int,
    causal: bool,
    sm_scale: float,
    blocks: Optional[Tuple[int, int]],
    interpret: bool,
    res: Tuple,
    do: jnp.ndarray,
    window: Optional[int] = None,
) -> Tuple:
    q, k, v, o, lse = res
    block_q, block_k = blocks
    bh, s, d = q.shape
    bg = k.shape[0]
    sk = k.shape[1]
    delta = jnp.sum(
        do.astype(jnp.float32) * o.astype(jnp.float32), axis=-1,
        keepdims=True,
    )  # [bh, s, 1]

    kernel_args = (q, k, v, do, lse, delta)
    kv_spec = pl.BlockSpec(
        (1, sk, d), lambda i, j: (_kv_index(i, h, g), 0, 0)
    )
    row_spec3 = pl.BlockSpec((1, block_q, d), lambda i, j: (i, j, 0))
    row_spec2 = pl.BlockSpec((1, block_q, 1), lambda i, j: (i, j, 0))

    dq = pl.pallas_call(
        functools.partial(
            _dq_kernel, causal=causal, sm_scale=sm_scale,
            block_q=block_q, block_k=block_k, seq_k=sk, window=window,
        ),
        name="flash_bwd_dq",
        out_shape=jax.ShapeDtypeStruct(q.shape, q.dtype),
        grid=(bh, s // block_q),
        in_specs=[row_spec3, kv_spec, kv_spec, row_spec3, row_spec2,
                  row_spec2],
        out_specs=row_spec3,
        interpret=interpret,
    )(*kernel_args)

    # dK/dV per QUERY head (expanded), summed over the group afterwards.
    full_row3 = pl.BlockSpec((1, s, d), lambda i, j: (i, 0, 0))
    full_row2 = pl.BlockSpec((1, s, 1), lambda i, j: (i, 0, 0))
    kvb_spec = pl.BlockSpec(
        (1, block_k, d), lambda i, j: (_kv_index(i, h, g), j, 0)
    )
    out_kvb = pl.BlockSpec((1, block_k, d), lambda i, j: (i, j, 0))
    dk_exp, dv_exp = pl.pallas_call(
        functools.partial(
            _dkv_kernel, causal=causal, sm_scale=sm_scale,
            block_q=block_q, block_k=block_k, seq_q=s, window=window,
        ),
        name="flash_bwd_dkv",
        out_shape=(
            jax.ShapeDtypeStruct((bh, sk, d), jnp.float32),
            jax.ShapeDtypeStruct((bh, sk, d), jnp.float32),
        ),
        grid=(bh, sk // block_k),
        in_specs=[full_row3, kvb_spec, kvb_spec, full_row3, full_row2,
                  full_row2],
        out_specs=(out_kvb, out_kvb),
        interpret=interpret,
    )(*kernel_args)

    r = h // g
    b = bh // h
    dk = dk_exp.reshape(b, g, r, sk, d).sum(axis=2).reshape(bg, sk, d)
    dv = dv_exp.reshape(b, g, r, sk, d).sum(axis=2).reshape(bg, sk, d)
    return dq, dk.astype(k.dtype), dv.astype(v.dtype)


_flash.defvjp(_flash_vjp_fwd, _flash_vjp_bwd)


# --------------------------------------------------------------------- #
# public API                                                            #
# --------------------------------------------------------------------- #


# Minimum sequence length at which the PADDED-head kernel (head_dim < 128
# zero-padded to the 128-lane tile) is preferred over dense XLA attention
# by the auto-picker: the MXU pads the lane dim to 128 either way, but the
# kernel's fixed overheads only amortize at the lengths where flash was
# measured faster (resident kernels: 14.5 vs 18.9 ms at seq 2048, 43.8 vs
# 64.7 ms at 4096 fwd+bwd on v5e — BENCH_NOTES.md flash table).  Exact
# 128-multiple heads keep using the kernel at any supported length.
PADDED_HEAD_MIN_SEQ = 2048


def supports(q_shape: Tuple[int, ...], k_shape: Tuple[int, ...],
             block: int = 128) -> bool:
    """Whether shapes meet the kernel's TPU tiling constraints.

    Head dims that are not a 128 multiple are supported up to 128 by
    zero-padding the head dimension to one lane tile (the Llama-1B-class
    ``head_dim=64``): q/k padding adds zero to every score and v padding
    zeros the padded output dims, so the math is exact, and the MXU pads
    the lane dimension to 128 regardless — see :func:`flash_attention`.
    """
    b, s, h, d = q_shape
    g = k_shape[2]
    return (
        (d % 128 == 0 or d < 128)
        and s % block == 0
        and k_shape[1] % block == 0
        and h % g == 0
    )


def flash_attention(
    q: jnp.ndarray,
    k: jnp.ndarray,
    v: jnp.ndarray,
    *,
    causal: bool = True,
    sm_scale: Optional[float] = None,
    block_q: int = 128,
    block_k: int = 128,
    interpret: bool = False,
    streaming: Optional[bool] = None,
    window: Optional[int] = None,
) -> jnp.ndarray:
    """Fused flash attention.  ``q``: ``[b, s, h, d]``; ``k, v``:
    ``[b, s_k, g, d]`` with ``g`` dividing ``h`` (GQA).  Returns
    ``[b, s, h, d]`` in ``q.dtype``.  Requires ``d % 128 == 0`` or
    ``d < 128`` (the head dim is zero-padded to one 128-lane tile — exact,
    see :func:`supports`) and sequence lengths divisible by the block
    sizes; ``interpret=True`` runs the kernels on any backend for testing.

    ``window`` (requires ``causal``) is Mistral-style sliding-window
    attention: attend iff ``0 <= qpos - kpos < window``.  Every kernel
    variant skips COMPUTE for blocks outside the band (the resident
    loops run ``jk0..diagonal``; the streaming grids clamp their index
    maps on both sides).  HBM traffic scales with the window only in the
    STREAMING variants — the resident kernels still stage the full K/V
    row in VMEM per program — so prefer ``streaming=True`` for
    long-sequence/small-window workloads.

    ``streaming`` selects the third-grid-dimension kernel variants whose
    per-program VMEM is O(block·d) — K/V (and, in the dK/dV kernel, Q/dO)
    tiles stream from HBM instead of residing whole — enabling very long
    single-chip sequences.  ``None`` picks automatically from the K/V row
    footprint.  Under causal masking the streaming grids skip
    fully-masked cells' work: clamped block index maps re-request the
    tile already resident (no HBM copy — Pallas elides same-index
    refetches) and ``pl.when`` skips the compute, so masked cells cost
    one grid bump (see ``_clamped_kv_block``; asserted in
    tests/test_flash_attention.py::test_streaming_causal_skips_masked_fetches).
    """
    b, s, h, d = q.shape
    g = k.shape[2]
    sm_scale = d ** -0.5 if sm_scale is None else sm_scale
    _validate_window(causal, window)
    if s % block_q or k.shape[1] % block_k:
        # The grids are ``s // block``: a remainder would leave the tail
        # rows unwritten (garbage out, no error from the kernel).
        raise ValueError(
            f"flash_attention requires sequence lengths divisible by "
            f"the block sizes (got q {s} / block_q {block_q}, k "
            f"{k.shape[1]} / block_k {block_k}); see supports()"
        )
    d_pad = (-d) % 128
    if d_pad:
        if d > 128:
            raise ValueError(
                f"flash_attention requires head_dim % 128 == 0 or "
                f"head_dim < 128 (got {d}); see supports()"
            )
        # Zero-pad head_dim to the 128-lane tile (Mosaic's last-dim tile
        # is always 128; the MXU pads the lane dim to 128 regardless, so
        # the extra MACs are largely free).  Exactness: sm_scale above is
        # computed from the ORIGINAL d; padded q/k dims contribute zero
        # to every score; padded v dims make the extra output dims
        # exactly zero and are sliced off below.  Autodiff through the
        # pad/slice routes gradients back to the unpadded operands.
        widths = ((0, 0), (0, 0), (0, 0), (0, d_pad))
        q = jnp.pad(q, widths)
        k = jnp.pad(k, widths)
        v = jnp.pad(v, widths)
        d = d + d_pad
    if streaming is None:
        # K+V rows of one head resident in the non-streaming kernels, in
        # the input dtype (the per-block f32 cast is transient).
        streaming = (
            2 * k.shape[1] * d * jnp.dtype(k.dtype).itemsize > _STREAM_BYTES
        )
    qr = jnp.transpose(q, (0, 2, 1, 3)).reshape(b * h, s, d)
    kr = jnp.transpose(k, (0, 2, 1, 3)).reshape(b * g, k.shape[1], d)
    vr = jnp.transpose(v, (0, 2, 1, 3)).reshape(b * g, v.shape[1], d)
    o = _flash(
        qr, kr, vr, h, g, causal, sm_scale,
        (block_q, block_k), interpret, streaming, window,
    )
    o = jnp.transpose(o.reshape(b, h, s, d), (0, 2, 1, 3))
    return o[..., : d - d_pad] if d_pad else o


# --------------------------------------------------------------------- #
# decode: few-query attention against a KV cache                        #
# --------------------------------------------------------------------- #


def _decode_block_k(s: int) -> Optional[int]:
    """Largest standard block size dividing cache length ``s``."""
    return next((c for c in (512, 256, 128) if s % c == 0), None)


def _decode_kernel(
    len_ref: Any,
    q_ref: Any,
    k_ref: Any,
    v_ref: Any,
    *rest: Any,
    g: int,
    r: int,
    hd: int,
    sm_scale: float,
    block_k: int,
    window: Optional[int],
    quant: bool,
) -> None:
    """One (batch, kv-head, K-block) grid cell: ``g*r`` query rows
    against one streamed K/V block, online softmax carried in VMEM
    scratch across the (sequential, innermost) block dimension.

    The live region depends on the RUNTIME cache length (scalar-prefetch
    ``len_ref``): blocks outside it are skipped — ``pl.when`` elides the
    compute and the clamped index maps re-request the resident tile so
    no HBM fetch is issued (the same machinery as the streaming causal
    kernels).  Per-step cost — bandwidth AND compute — follows the
    generated prefix, not the cache allocation.  Forward only (decode
    has no backward).

    Operand layouts are HEAD-FOLDED: Mosaic requires a block's last two
    dims to be (8k, 128k)-tileable or full axes, so a width-1 block over
    a ``nkv`` axis cannot lower (caught on real TPU; interpret mode
    does not enforce tiling).  K/V arrive as ``[1, Bk, hd]`` tiles of a
    ``[b, s, nkv*hd]`` view — the kv head is picked by the index map as
    a lane-axis block offset, so the fetch stays one head's tile.

    ``quant=True``: K/V refs are int8 with f32 per-(position, head)
    scales — dequantized ONE BLOCK AT A TIME in VMEM, so HBM moves half
    the bytes of a bf16 cache (the actual int8-KV bandwidth win; the
    dense path dequantizes the whole cache in HBM first and forfeits
    it).  ``ks_ref``/``vs_ref`` are the head's whole scale row viewed
    ``[1, 1, nkb, Bk]`` (s floats — fetched once per (batch, head), ~s·4
    bytes, negligible next to the K tiles); the current block's row is
    selected with an iota/where reduction because the row index ``jb``
    is a runtime value and Mosaic has no dynamic sublane indexing."""
    if quant:
        ks_ref, vs_ref, o_ref, m_sc, l_sc, acc_sc = rest
    else:
        o_ref, m_sc, l_sc, acc_sc = rest
    jb = pl.program_id(2)
    nkb = pl.num_programs(2)
    length = len_ref[0]
    pos0 = length - g
    rows = g * r
    last = lax.div(length - 1, block_k)
    if window is None:
        first = jnp.int32(0)
    else:
        first = lax.div(
            lax.max(pos0 - window + 1, jnp.int32(0)), block_k
        )

    @pl.when(jb == 0)
    def _init():
        m_sc[...] = jnp.full_like(m_sc, _NEG)
        l_sc[...] = jnp.zeros_like(l_sc)
        acc_sc[...] = jnp.zeros_like(acc_sc)

    @pl.when((jb >= first) & (jb <= last))
    def _body():
        qb = (
            q_ref[0].reshape(rows, hd).astype(jnp.float32) * sm_scale
        )
        kb = k_ref[0].astype(jnp.float32)   # [Bk, hd]
        vb = v_ref[0].astype(jnp.float32)
        if quant:
            def row_of(sref):
                # [nkb, Bk] → row jb (the fetched K tile's block).
                all_rows = sref[0, 0]
                sel = (
                    lax.broadcasted_iota(jnp.int32, all_rows.shape, 0)
                    == jb
                )
                return jnp.sum(
                    jnp.where(sel, all_rows, 0.0), axis=0
                )

            kb = kb * row_of(ks_ref).reshape(block_k, 1)
            vb = vb * row_of(vs_ref).reshape(block_k, 1)
        s = lax.dot_general(
            qb, kb, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        )  # [rows, Bk]
        # Row i is query position pos0 + i // r (r grouped query heads
        # per kv head, consecutive).
        qpos = pos0 + lax.broadcasted_iota(jnp.int32, (rows, 1), 0) // r
        col = jb * block_k + lax.broadcasted_iota(
            jnp.int32, (rows, block_k), 1
        )
        valid = col <= qpos
        if window is not None:
            valid &= col > qpos - window
        s = jnp.where(valid, s, _NEG)
        m_prev = m_sc[...]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        p = jnp.exp(s - m_new)
        corr = jnp.exp(m_prev - m_new)
        l_sc[...] = l_sc[...] * corr + jnp.sum(p, axis=1, keepdims=True)
        acc_sc[...] = acc_sc[...] * corr + lax.dot_general(
            p, vb, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        m_sc[...] = m_new

    @pl.when(jb == nkb - 1)
    def _finish():
        o_ref[0] = (acc_sc[...] / l_sc[...]).reshape(g, r * hd)


def supports_decode(
    q_shape: Tuple[int, ...], k_shape: Tuple[int, ...],
    window: Optional[int],
) -> bool:
    """Static eligibility for :func:`flash_decode_attention` (the
    auto-dispatch gate in ``models.generation._attend_chunk``): the same
    conditions the kernel entry validates, answered as a bool.  K/V
    stream one block at a time, so there is NO cache-length VMEM cap —
    only tiling/grouping constraints and a floor under which the dense
    read is not worth a kernel dispatch."""
    b, g, nh, hd = q_shape
    s, nkv = k_shape[1], k_shape[2]
    if hd % 128 != 0 or nkv == 0 or nh % nkv != 0:
        return False
    if s < 256 or _decode_block_k(s) is None:
        return False
    return window is None or window >= 1


def flash_decode_attention(
    q: jnp.ndarray,              # [b, g, nh, hd] — rope'd queries at
                                 # consecutive positions pos0..pos0+g-1
    ck: jnp.ndarray,             # [b, max_len, nkv, hd] KV cache
    cv: jnp.ndarray,
    pos0: jnp.ndarray,           # [] int32 — first query's position
    *,
    window: Optional[int] = None,
    block_k: Optional[int] = None,
    k_scale: Optional[jnp.ndarray] = None,  # f32 [b, nkv, max_len]
    v_scale: Optional[jnp.ndarray] = None,
    interpret: bool = False,
) -> jnp.ndarray:
    """Decode-side flash attention: ``g`` consecutive queries against the
    LIVE PREFIX of a KV cache — the Pallas twin of the dense
    ``models.generation._attend_chunk`` (g=1 is the plain per-token
    decode read; g=γ+1 is speculative verification).

    Unlike the prefill kernels (static causal geometry), the masked
    region here depends on a RUNTIME scalar: the cache is ``max_len``
    rows but only ``pos0+g`` are live.  The length rides in as a
    scalar-prefetch operand, visible to BOTH the block index maps
    (clamped — tiles outside the live/banded region re-request the
    resident tile, so no HBM fetch is issued) and the kernel
    (``pl.when`` skips their compute): per-step bandwidth and FLOPs
    follow the generated length, not the cache allocation.  K/V stream
    one ``[block_k, hd]`` tile at a time, so any ``max_len`` tiles the
    grid can express is supported.  Output is f32 ``[b, g, nh*hd]``,
    numerically the dense path\'s (same f32 accumulation; oracle-tested
    in tests/test_flash_attention.py).

    ``k_scale``/``v_scale`` (both or neither): the cache is int8 with
    per-(position, head) symmetric scales in the QuantKVCache
    ``[b, nkv, max_len]`` layout (positions last = the kernel's lane
    dim, no transpose needed) — dequantized block-wise in VMEM, so the
    HBM side moves int8 bytes."""
    b, g, nh, hd = q.shape
    s, nkv = ck.shape[1], ck.shape[2]
    if nh % nkv != 0:
        raise ValueError(f"nh={nh} not divisible by nkv={nkv}")
    r = nh // nkv
    if block_k is None:
        block_k = _decode_block_k(s)
        if block_k is None:
            raise ValueError(
                f"cache length {s} has no 128/256/512 block divisor; "
                "pass block_k or use the dense path"
            )
    elif s % block_k != 0:
        raise ValueError(f"cache length {s} not divisible by {block_k}")
    if window is not None and window < 1:
        raise ValueError("window must be >= 1")
    quant = k_scale is not None
    if quant != (v_scale is not None):
        raise ValueError("pass both k_scale and v_scale, or neither")
    # Head-folded views (pure reshapes — the head axis is contiguous with
    # hd, so no copy): Mosaic requires a block's last two dims to be
    # (8k, 128k)-tileable or full axes, which a width-1 nkv-axis block is
    # not.  The kv head becomes a lane-axis block offset instead.
    qf = q.reshape(b, g, nh * hd)
    ckf = ck.reshape(b, s, nkv * hd)
    cvf = cv.reshape(b, s, nkv * hd)
    length = jnp.reshape(pos0 + g, (1,)).astype(jnp.int32)
    nkb = s // block_k

    def kv_im(i: Any, h: Any, jb: Any, len_ref: Any) -> Tuple:
        # Clamp into the live (and, with a window, banded) block range:
        # out-of-range grid steps re-request whatever tile the clamp
        # lands on — already resident, so Pallas elides the fetch.
        length = len_ref[0]
        last = lax.div(length - 1, block_k)
        if window is None:
            first = jnp.int32(0)
        else:
            first = lax.div(
                lax.max(length - g - window + 1, jnp.int32(0)), block_k
            )
        return (i, lax.clamp(first, jb, last), h)

    q_im = lambda i, h, jb, len_ref: (i, 0, h)  # noqa: E731
    in_specs = [
        pl.BlockSpec((1, g, r * hd), q_im),
        pl.BlockSpec((1, block_k, hd), kv_im),
        pl.BlockSpec((1, block_k, hd), kv_im),
    ]
    operands = [length, qf, ckf, cvf]
    if quant:
        # One head's whole scale row [nkb, Bk] per (batch, head) cell —
        # s floats, fetched once per (i, h) (the index map is constant
        # over jb, so Pallas elides per-block refetches); full-axis
        # last-two dims keep it tileable for any nkb.
        in_specs += [
            pl.BlockSpec(
                (1, 1, nkb, block_k),
                lambda i, h, jb, len_ref: (i, h, 0, 0),
            ),
        ] * 2
        operands += [
            k_scale.reshape(b, nkv, nkb, block_k),
            v_scale.reshape(b, nkv, nkb, block_k),
        ]
    out = pl.pallas_call(
        functools.partial(
            _decode_kernel, g=g, r=r, hd=hd, sm_scale=hd ** -0.5,
            block_k=block_k, window=window, quant=quant,
        ),
        name="flash_decode",
        out_shape=jax.ShapeDtypeStruct((b, g, nh * hd), jnp.float32),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(b, nkv, nkb),
            in_specs=in_specs,
            out_specs=pl.BlockSpec((1, g, r * hd), q_im),
            scratch_shapes=[
                pltpu.VMEM((g * r, 1), jnp.float32),
                pltpu.VMEM((g * r, 1), jnp.float32),
                pltpu.VMEM((g * r, hd), jnp.float32),
            ],
        ),
        interpret=interpret,
    )(*operands)
    return out
