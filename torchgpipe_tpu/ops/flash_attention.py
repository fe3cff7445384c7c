"""Flash attention as Pallas TPU kernels (forward + backward).

The framework's hot op: fused online-softmax attention that never
materializes the ``[s, s]`` score matrix in HBM — scores live in VMEM one
``[block_q, block_k]`` tile at a time, with f32 accumulation on the MXU.
The MXU is fed the tiles of q, k, v and dO as they are stored: a bf16
tile goes into its product as bf16 (one pass), scores, softmax
statistics, ``delta`` and every accumulator stay f32, and the f32
intermediates ``p`` and ``dS`` are rounded to the tile's type before
their product, as the dense path rounds ``p.astype(v.dtype)``; float32
inputs keep products of float32 tiles (:func:`_dot_tile`, the one place
that decides).
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

# Scoped VMEM for the six training kernels (v5e has 128 MiB).  The
# compiler's default of 16 MiB holds 512-blocks at 4096 in the SPMD step
# and misses them by 0.26 MiB in the MPMD engine's stored-residual
# backward program (dK/dV: whole q / dO rows, the lane-padded lse / delta
# columns and five f32 score planes; described-chip compile, PR 31).
_TRAIN_VMEM = pltpu.CompilerParams(vmem_limit_bytes=32 * 1024 * 1024)


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


def _largest_block(s: int) -> Optional[int]:
    """Largest standard block size dividing sequence length ``s``."""
    return next((c for c in (512, 256, 128) if s % c == 0), None)


def _kv_index(i: jax.Array, h: int, g: int) -> jax.Array:
    """Row in the [b*g, s, d] K/V array for query row ``i`` of [b*h, s, d]."""
    r = h // g
    return (i // h) * g + (i % h) // r


# --------------------------------------------------------------------- #
# products: what a grid step hands the MXU                              #
# --------------------------------------------------------------------- #

# dot_general dimension numbers of the kernels' 2-D products.
_NT = (((1,), (1,)), ((), ()))  # x . y^T   (Q K^T, dO V^T)
_NN = (((1,), (0,)), ((), ()))  # x . y     (P V, dS K)
_TN = (((0,), (0,)), ((), ()))  # x^T . y   (P^T dO, dS^T Q)


def _dot_rows(x: jnp.ndarray, y: jnp.ndarray, dims: Any) -> jnp.ndarray:
    """``x . y`` into f32 with no operand rounded.  Against a bf16 ``y``
    (the cache as stored) an f32 ``x`` goes through the MXU as three
    bf16 terms whose sum it is, stacked as rows of ONE bf16 product:
    every partial product is exact and the accumulator is f32.  (The
    other exact form, a product of f32 tiles at ``Precision.HIGHEST``,
    makes six passes over the block; at the default precision Mosaic
    rounds f32 operands to bf16 and makes one: chip runs, PR 31.)  A
    bf16 ``x`` is one term; any other ``y`` takes the f32 product."""
    if y.dtype != jnp.bfloat16:
        return lax.dot_general(
            x.astype(jnp.float32), y.astype(jnp.float32), dims,
            preferred_element_type=jnp.float32,
        )
    if x.dtype == jnp.bfloat16:
        return lax.dot_general(
            x, y, dims, preferred_element_type=jnp.float32
        )
    x = x.astype(jnp.float32)
    hi = x.astype(jnp.bfloat16)
    rest = x - hi.astype(jnp.float32)
    mid = rest.astype(jnp.bfloat16)
    lo = (rest - mid.astype(jnp.float32)).astype(jnp.bfloat16)
    n = x.shape[0]
    out = lax.dot_general(
        jnp.concatenate([hi, mid, lo], axis=0), y, dims,
        preferred_element_type=jnp.float32,
    )
    return out[:n] + out[n:2 * n] + out[2 * n:]


def _dot_tile(x: jnp.ndarray, y: jnp.ndarray, dims: Any) -> jnp.ndarray:
    """``x . y`` into f32 as the six training kernels take it.  ``y`` is
    a tile of q, k, v or dO AS STORED; ``x`` is another such tile or an
    f32 intermediate (``p``, ``dS``).  Against a bf16 ``y`` the product
    is one bf16 pass of the MXU with an f32 accumulator: a stored bf16
    ``x`` goes in as it is (bf16 x bf16 is exact in f32), an f32 ``x``
    is rounded to bf16 first — the model's own arithmetic,
    ``p.astype(v.dtype)`` in the dense path
    (``ring_attention.full_attention``) and in the published
    implementations — and one that contracts its rows (``P^T dO``,
    ``dS^T Q``) is transposed while it is still f32: a bf16 transposed
    operand read 5 % slower in the dK/dV kernel (chip run, PR 31).  Any
    other ``y`` (a float32 caller) keeps the product of f32 tiles it
    had.  The operand's dtype decides, nothing else.

    A product of f32 tiles at the default precision is ALSO one bf16
    pass, the operands rounded on the way in (against an f32
    ``highest`` reference it reads this form's error, 2.5e-3 of a
    gradient's norm; ``Precision.HIGHEST`` costs four times the
    kernel): stating the type keeps the arithmetic under any default,
    it does not buy MXU time (chip runs, PR 31)."""
    if y.dtype == jnp.bfloat16:
        if dims == _TN:
            x, dims = x.T, _NN
        x = x.astype(jnp.bfloat16)
    return _dot_rows(x, y, dims)


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
    qb = q_ref[0]  # [Bq, d], as stored
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
        kb = k_ref[0, pl.ds(jb * block_k, block_k), :]
        vb = v_ref[0, pl.ds(jb * block_k, block_k), :]
        # The scale goes on the f32 scores: on a bf16 q it would round.
        s = _dot_tile(qb, kb, _NT) * sm_scale  # [Bq, Bk]
        if causal:
            s = _mask_causal(s, j, jb, block_q, block_k, window)
        m_new = jnp.maximum(m, jnp.max(s, axis=1, keepdims=True))
        p = jnp.exp(s - m_new)
        corr = jnp.exp(m - m_new)
        l_new = l * corr + jnp.sum(p, axis=1, keepdims=True)
        acc_new = acc * corr + _dot_tile(p, vb, _NN)
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
        compiler_params=_TRAIN_VMEM,
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
# streaming used to lose to dense at moderate causal lengths).


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
        s = _dot_tile(q_ref[0], k_ref[0], _NT) * sm_scale
        if causal:
            s = _mask_causal(s, j, jk, block_q, block_k, window)
        m_prev = m_sc[...]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        p = jnp.exp(s - m_new)
        corr = jnp.exp(m_prev - m_new)
        l_sc[...] = l_sc[...] * corr + jnp.sum(p, axis=1, keepdims=True)
        acc_sc[...] = acc_sc[...] * corr + _dot_tile(p, v_ref[0], _NN)
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
        compiler_params=_TRAIN_VMEM,
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
        kb = k_ref[0]
        s = _dot_tile(q_ref[0], kb, _NT) * sm_scale
        if causal:
            s = _mask_causal(s, j, jk, block_q, block_k, window)
        p = jnp.exp(s - lse_ref[0])
        dp = _dot_tile(do_ref[0], v_ref[0], _NT)
        ds = p * (dp - delta_ref[0])
        dq_sc[...] = dq_sc[...] + _dot_tile(ds, kb, _NN)

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
        qb = q_ref[0]
        dob = do_ref[0]
        s = _dot_tile(qb, k_ref[0], _NT) * sm_scale
        if causal:
            s = _mask_causal(s, jq, jk, block_q, block_k, window)
        p = jnp.exp(s - lse_ref[0])
        dv_sc[...] = dv_sc[...] + _dot_tile(p, dob, _TN)
        dp = _dot_tile(dob, v_ref[0], _NT)
        ds = p * (dp - delta_ref[0])
        dk_sc[...] = dk_sc[...] + _dot_tile(ds, qb, _TN)

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
    qb = q_ref[0]
    dob = do_ref[0]
    lse_b = lse_ref[0]      # [Bq, 1]
    delta_b = delta_ref[0]  # [Bq, 1]
    nk = seq_k // block_k
    jk0 = 0
    if causal:
        nk = lax.min(nk, lax.div((j + 1) * block_q + block_k - 1, block_k))
        if window is not None:
            jk0 = _first_valid_kv(j, block_q, block_k, window)

    def body(jb, dq):
        kb = k_ref[0, pl.ds(jb * block_k, block_k), :]
        vb = v_ref[0, pl.ds(jb * block_k, block_k), :]
        s = _dot_tile(qb, kb, _NT) * sm_scale
        if causal:
            s = _mask_causal(s, j, jb, block_q, block_k, window)
        p = jnp.exp(s - lse_b)  # [Bq, Bk]
        dp = _dot_tile(dob, vb, _NT)
        ds = p * (dp - delta_b)
        return dq + _dot_tile(ds, kb, _NN)

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
    kb = k_ref[0]  # [Bk, d]
    vb = v_ref[0]
    nq = seq_q // block_q
    jq0 = lax.div(jk * block_k, block_q) if causal else 0
    jq_hi = (
        _last_valid_q(jk, block_q, block_k, nq, window) + 1
        if causal else nq
    )

    def body(jq, carry):
        dk, dv = carry
        qb = q_ref[0, pl.ds(jq * block_q, block_q), :]
        dob = do_ref[0, pl.ds(jq * block_q, block_q), :]
        lse_b = lse_ref[0, pl.ds(jq * block_q, block_q), :]      # [Bq, 1]
        delta_b = delta_ref[0, pl.ds(jq * block_q, block_q), :]  # [Bq, 1]
        s = _dot_tile(qb, kb, _NT) * sm_scale
        if causal:
            s = _mask_causal(s, jq, jk, block_q, block_k, window)
        p = jnp.exp(s - lse_b)  # [Bq, Bk]
        dv_new = dv + _dot_tile(p, dob, _TN)
        dp = _dot_tile(dob, vb, _NT)
        ds = p * (dp - delta_b)
        dk_new = dk + _dot_tile(ds, qb, _TN)
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
        compiler_params=_TRAIN_VMEM,
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
        compiler_params=_TRAIN_VMEM,
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
        compiler_params=_TRAIN_VMEM,
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
        compiler_params=_TRAIN_VMEM,
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
# 64.7 ms at 4096 fwd+bwd on v5e, while the kernels still multiplied f32
# tiles; not measured since: ROADMAP A4).  Exact 128-multiple heads keep
# using the kernel at any supported length.
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
    block_q: Optional[int] = None,
    block_k: Optional[int] = None,
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

    ``block_q`` / ``block_k`` left out are the largest of 512 / 256 / 128
    that divides the sequence: a grid step costs about 0.3 us before it
    touches an element (one dependent chain of product, row maximum,
    exp, product), so at 4096 a forward call takes 6.4 ms in 128-blocks,
    2.4 in 256 and 1.4 in 512, whatever the MXU is fed; 1024 does not
    fit VMEM (chip runs and described-chip compiles, PERF.md, PR 31).

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
    # A length no block divides keeps 128 and meets the check below.
    if block_q is None:
        block_q = _largest_block(s) or 128
    if block_k is None:
        block_k = _largest_block(k.shape[1]) or 128
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
        # the input dtype (the tiles go into the MXU as they are stored).
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


# The f32 score plane of one product, ``[query rows, block rows x heads]``,
# up to which a step of the decode kernel takes ALL kv heads of a block
# through one product (``g = 1``: 32 x 4096 x 4 bytes at Mistral's heads);
# above it a step takes the heads of one 32-bit word of the tile (two
# bf16 heads), four such groups a block, and a long chunk a shorter block.
_DECODE_PLANE_BYTES = 1024 * 1024
# Scoped VMEM for the decode kernel: two double-buffered tiles of a
# block's K and V, a chunk's score plane, its probabilities and their
# three-term bf16 form, queries, output and accumulator (v5e: 128 MiB).
_DECODE_VMEM_BYTES = 48 * 1024 * 1024


def _decode_tiling(
    g: int, nh: int, nkv: int, itemsize: int, s: int,
) -> Optional[Tuple[int, int]]:
    """``(block_k, hw)`` of the decode kernel for ``g`` queries a row,
    ``nh`` / ``nkv`` heads and a cache of ``s`` rows of ``itemsize``
    bytes an element: the largest block dividing ``s`` whose score
    plane fits, with ``hw`` kv heads a product — all ``nkv`` while the
    plane stays under ``_DECODE_PLANE_BYTES``, else the heads that
    share a 32-bit sublane word of the cache tile (twice the plane is
    then allowed: a prompt chunk's).  None: no block fits."""
    per_word = max(4 // itemsize, 1)
    for block_k in (512, 256, 128):
        if s % block_k:
            continue
        if g * nh * block_k * nkv * 4 <= _DECODE_PLANE_BYTES:
            return block_k, nkv
        if nkv % per_word:
            continue
        plane = g * (nh // nkv) * per_word * block_k * per_word * 4
        if plane <= 2 * _DECODE_PLANE_BYTES:
            return block_k, per_word
    return None


def _decode_plan(
    pos0: jnp.ndarray, lengths: jnp.ndarray, window: Optional[int],
    block_k: int,
) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """``(first, last, count)`` per row: the cache blocks a row's
    queries read (the band of the FIRST query opens it, the row's
    length closes it) and how many grid steps the row takes — one at
    least, in which a row of length 0 writes its zeros.  Blocks are
    counted in POSITIONS: block ``j`` is positions ``[j * block_k, (j +
    1) * block_k)``, which a ring holds in its block ``j % (ring rows
    // block_k)`` (``lengths`` is then the row's frontier, not clipped
    to the ring)."""
    last = jnp.maximum(lengths - 1, 0) // block_k
    first = jnp.zeros_like(last)
    if window is not None:
        first = jnp.minimum(
            jnp.maximum(pos0 - window + 1, 0) // block_k, last
        )
    return first, last, jnp.where(lengths > 0, last - first + 1, 1)


def decode_rows_read(
    pos0: Any, lengths: Any, window: Optional[int], block_k: int,
) -> int:
    """Cache rows, block-rounded, that :func:`flash_decode_attention`
    fetches for per-row ``pos0`` / ``lengths`` at ``block_k``:
    :func:`_decode_plan`'s blocks of the rows that read anything, in
    numpy on the host and in as few operations as it takes (the serving
    engine counts them before every step:
    ``serving_attend_rows_read``)."""
    import numpy as np

    lengths = np.asarray(lengths)
    blocks = (lengths - 1) // block_k + 1       # 0 for a row of length 0
    if window is not None:
        first = np.maximum(np.asarray(pos0) - window + 1, 0) // block_k
        blocks = np.maximum(blocks - first, lengths > 0)
    return int(blocks.sum()) * block_k


def _decode_steps(
    pos0: jnp.ndarray, lengths: jnp.ndarray, slots: jnp.ndarray,
    window: Optional[int], block_k: int, steps: int,
) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """The decode kernel's grid as a flat list: ``(row, slot, block)`` of
    each grid step, in arrays of ``steps`` entries (the list's capacity:
    every block of every row), and ``ends [b]``, one past each row's
    last step (its last entry is the grid's length).
    Row ``i`` takes the blocks of :func:`_decode_plan` in order; the one
    step of a row that reads nothing names the tile of the last step
    that did — resident, so nothing is fetched for it.  Masked sums
    over a ``[steps, b]`` plane, no gather: the TPU compiler unrolls a
    gather of scalars into a slice an element."""
    b = lengths.shape[0]
    first, last, count = _decode_plan(pos0, lengths, window, block_k)
    ends = jnp.cumsum(count)
    starts = ends - count
    i = jnp.arange(b)
    live = lengths > 0
    # The last row at or before each row that reads something (row 0
    # where none does), and its slot and last block.
    prev = jnp.max(
        jnp.where(live[None, :] & (i[None, :] <= i[:, None]), i[None, :], 0),
        axis=1,
    )
    pick = prev[:, None] == i[None, :]

    def of_prev(x: jnp.ndarray) -> jnp.ndarray:
        return jnp.sum(jnp.where(pick, x[None, :], 0), axis=1)

    resident = of_prev(last)
    first = jnp.where(live, first, resident)
    last = jnp.where(live, last, resident)
    t = jnp.arange(steps)[:, None]
    hot = (t >= starts[None, :]) & (t < ends[None, :])

    def of_step(x: jnp.ndarray) -> jnp.ndarray:
        return jnp.sum(jnp.where(hot, x, 0), axis=1).astype(jnp.int32)

    return (
        of_step(i[None, :]),
        of_step(of_prev(slots)[None, :]),
        of_step(jnp.minimum(first[None, :] + t - starts[None, :],
                            last[None, :])),
        ends.astype(jnp.int32),
    )


def _decode_kernel(
    row_ref: Any,    # [steps] query row of each grid step
    slot_ref: Any,   # [steps] bank row of its tile (the index maps')
    blk_ref: Any,    # [steps] cache block of its tile
    end_ref: Any,    # [b] one past a row's last step (running sum)
    pos_ref: Any,    # [b] first query's position
    len_ref: Any,    # [b] cache rows the row reads; 0: none
    q_ref: Any,      # [1, groups, g*hw*r, hd]
    k_ref: Any,      # [1, block_k, nkv, hd]
    v_ref: Any,
    o_ref: Any,      # [1, groups, g*hw*r, hd] f32
    m_sc: Any,
    l_sc: Any,
    acc_sc: Any,
    *,
    r: int,
    hw: int,
    block_k: int,
    window: Optional[int],
    sm_scale: float,
) -> None:
    """One grid step: ALL kv heads of one cache block of one query row,
    online softmax carried in VMEM scratch across the row's steps.

    The grid is a flat LIST of (row, block) steps made from the runtime
    lengths (scalar prefetch, visible to the index maps and the kernel):
    a row takes the blocks from its band's first to its length's last
    and no other, so bandwidth and compute follow the rows that hold a
    token, and the next row's first tile is fetched while this row's
    last is computed.  The grid's length is the list's, a runtime value
    (a grid sized for a full cache, its tail doing nothing, read 0.54 ms
    against 0.49 a layer at the serving cell's shapes: chip run, PR 29).

    The tile is the bank's own ``[block_k, nkv, hd]`` (full last dims:
    no head-folded view, which on the chip is a relayout COPY of the
    bank).  One product covers ``hw`` heads: the tile collapses to
    ``[block_k * hw, hd]`` — for all heads a pure view, for the heads
    of one 32-bit word (two bf16 heads) a sublane-strided load of the
    tile seen as words — and the columns a query's own head does not
    own are masked like the rows past its position.  The MXU takes
    each cache element once either way (it is the stationary operand);
    only the softmax pays for the masked columns.

    Row ``x`` of a group is query ``x // (hw * r)`` of the chunk, local
    kv head ``x % (hw * r) // r``; column ``c`` is cache row
    ``c // hw`` of the block, local head ``c % hw``."""
    t = pl.program_id(0)
    i = row_ref[t]
    jb = blk_ref[t]
    end = end_ref[i]
    start = jnp.where(i > 0, end_ref[jnp.maximum(i - 1, 0)], 0)
    pos0 = pos_ref[i]
    groups, rows, hd = acc_sc.shape
    nkv = k_ref.shape[2]
    cols = block_k * hw

    def tile(ref: Any, grp: int) -> jnp.ndarray:
        if hw == nkv:
            return ref[0].reshape(cols, hd)
        if hw == 1:
            return ref[0, :, grp, :]
        words = ref.bitcast(jnp.uint32)[0, :, grp, :]
        return pltpu.bitcast(words, ref.dtype)

    @pl.when(t == start)
    def _init():
        m_sc[...] = jnp.full_like(m_sc, _NEG)
        l_sc[...] = jnp.zeros_like(l_sc)
        acc_sc[...] = jnp.zeros_like(acc_sc)

    @pl.when(len_ref[i] > 0)
    def _body():
        x = lax.broadcasted_iota(jnp.int32, (rows, 1), 0)
        qpos = pos0 + x // (hw * r)
        c = lax.broadcasted_iota(jnp.int32, (1, cols), 1)
        col = jb * block_k + c // hw
        valid = (col <= qpos) & (c % hw == x % (hw * r) // r)
        if window is not None:
            valid &= col > qpos - window
        for grp in range(groups):
            s = _dot_rows(
                q_ref[0, grp], tile(k_ref, grp), (((1,), (1,)), ((), ()))
            ) * sm_scale
            s = jnp.where(valid, s, _NEG)
            m_prev = m_sc[grp]
            m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
            p = jnp.where(valid, jnp.exp(s - m_new), 0.0)
            corr = jnp.exp(m_prev - m_new)
            l_sc[grp] = l_sc[grp] * corr + jnp.sum(p, axis=1, keepdims=True)
            acc_sc[grp] = acc_sc[grp] * corr + _dot_rows(
                p, tile(v_ref, grp), (((1,), (0,)), ((), ()))
            )
            m_sc[grp] = m_new

    @pl.when(t == end - 1)
    def _finish():
        # A row that read nothing has l == 0: zeros, not 0/0 — its
        # output still flows through the block's products.
        l = l_sc[...]
        o_ref[0] = jnp.where(
            l > 0, acc_sc[...] / jnp.where(l > 0, l, 1.0), 0.0
        )


def _decode_quant_kernel(
    len_ref: Any,
    q_ref: Any,
    k_ref: Any,
    v_ref: Any,
    ks_ref: Any,
    vs_ref: Any,
    o_ref: Any,
    m_sc: Any,
    l_sc: Any,
    acc_sc: Any,
    *,
    g: int,
    r: int,
    hd: int,
    sm_scale: float,
    block_k: int,
    window: Optional[int],
) -> None:
    """The int8 cache's kernel: one (batch, kv-head, K-block) grid cell,
    ``g*r`` query rows against one streamed K/V block of ONE head, one
    scalar length for the batch (``generate`` with ``kv_quant``).

    Operand layouts are HEAD-FOLDED: K/V arrive as ``[1, Bk, hd]`` tiles
    of a ``[b, s, nkv*hd]`` view, the kv head picked by the index map as
    a lane-axis block offset.  (On the chip that view is a relayout of
    the cache — the bf16 kernel above reads the bank's own layout; an
    int8 tile packs four heads a word and its scales lie positions-last,
    so it keeps this form.)  K/V are int8 with f32 per-(position, head)
    scales, dequantized ONE BLOCK AT A TIME in VMEM.  ``ks_ref`` /
    ``vs_ref`` are the head's whole scale row viewed ``[1, 1, nkb, Bk]``
    (s floats, fetched once per (batch, head)); the current block's row
    is selected with an iota/where reduction because the row index
    ``jb`` is a runtime value and Mosaic has no dynamic sublane
    indexing."""
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

        def row_of(sref):
            # [nkb, Bk] → row jb (the fetched K tile's block).
            all_rows = sref[0, 0]
            sel = (
                lax.broadcasted_iota(jnp.int32, all_rows.shape, 0) == jb
            )
            return jnp.sum(jnp.where(sel, all_rows, 0.0), axis=0)

        kb = k_ref[0].astype(jnp.float32) * row_of(ks_ref).reshape(block_k, 1)
        vb = v_ref[0].astype(jnp.float32) * row_of(vs_ref).reshape(block_k, 1)
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
    window: Optional[int], itemsize: int = 2,
) -> bool:
    """Static eligibility for :func:`flash_decode_attention` (the
    auto-dispatch gate in ``models.generation._attend_chunk``): the same
    conditions the kernel entry validates, answered as a bool.  K/V
    stream one block at a time, so there is NO cache-length VMEM cap —
    only tiling/grouping constraints, a floor under which the dense
    read is not worth a kernel dispatch, and a ceiling on the score
    plane of one product (``itemsize`` is the cache's: it sets how many
    heads a product takes once the chunk is long)."""
    b, g, nh, hd = q_shape
    s, nkv = k_shape[1], k_shape[2]
    if hd % 128 != 0 or nkv == 0 or nh % nkv != 0:
        return False
    if s < 256 or _decode_tiling(g, nh, nkv, itemsize, s) is None:
        return False
    return window is None or window >= 1


def flash_decode_attention(
    q: jnp.ndarray,              # [b, g, nh, hd] — rope'd queries at
                                 # consecutive positions pos0..pos0+g-1
    ck: jnp.ndarray,             # [slots, max_len, nkv, hd] KV cache
    cv: jnp.ndarray,
    pos0: jnp.ndarray,           # [] or [b] int32 — first query's position
    *,
    window: Optional[int] = None,
    block_k: Optional[int] = None,
    k_scale: Optional[jnp.ndarray] = None,  # f32 [b, nkv, max_len]
    v_scale: Optional[jnp.ndarray] = None,
    slots: Optional[jnp.ndarray] = None,    # [b] int32 — row i reads
                                            # cache row slots[i]
    lengths: Optional[jnp.ndarray] = None,  # [b] int32 — cache rows a
                                            # row reads (0: none)
    ring: bool = False,
    interpret: bool = False,
) -> jnp.ndarray:
    """Decode-side flash attention: ``g`` consecutive queries against the
    LIVE PREFIX of a KV cache — the Pallas twin of the dense
    ``models.generation._attend_chunk`` (g=1 is the plain per-token
    decode read; g=γ+1 is speculative verification; g=prefill_chunk a
    serving engine's prompt chunk).

    Unlike the prefill kernels (static causal geometry), the masked
    region here depends on RUNTIME values: the cache is ``max_len`` rows
    but row ``i`` has only ``pos0[i]+g`` live (``pos0`` a scalar: every
    row alike).  They ride in as scalar-prefetch operands, visible to
    BOTH the block index maps and the kernel: the grid is the list of
    the (row, block) pairs inside each row's live and banded range, so
    per-step bandwidth and FLOPs follow what the rows hold, not the
    cache allocation.  K/V stream one ``[block_k, nkv, hd]`` tile of
    the cache's own layout at a time, so any ``max_len`` tiles the grid
    can express is supported.  Output is f32 ``[b, g, nh*hd]``,
    numerically the dense path\'s (f32 scores, statistics and
    accumulator, no operand rounded; oracle-tested in
    tests/test_flash_attention.py).

    ``slots`` makes the cache a BANK that the rows index: row ``i``
    reads ``ck[slots[i]]`` through the index map (no gather, no slice;
    a slot may repeat).  ``lengths`` says how many cache rows a row
    reads where that is not ``pos0+g`` (clipped to ``max_len`` either
    way); a row of length 0 fetches nothing and returns zeros.

    ``ring`` (needs ``window``): the cache's ``s`` rows are a RING,
    position ``p`` in row ``p % s``, ``s`` at least ``window + g - 1``
    so that the band of every query of the chunk is in it.  The grid
    still walks the band's blocks of POSITIONS, first to last; the
    index map fetches block ``j % (s // block_k)`` of the ring and the
    mask goes, as ever, by the position a row holds (``j * block_k +``
    its offset in the block): a ring row that an older or a newer
    position's block shares is masked in that block's step by the band
    or by causality.  ``lengths`` is then the row's frontier ``pos0 +
    g``, not clipped to ``s``.

    ``k_scale``/``v_scale`` (both or neither): the cache is int8 with
    per-(position, head) symmetric scales in the QuantKVCache
    ``[b, nkv, max_len]`` layout — dequantized block-wise in VMEM, so
    the HBM side moves int8 bytes.  One scalar ``pos0``, no ``slots``:
    the int8 kernel is ``generate``'s, a pool of int8 slots attends
    dense."""
    b, g, nh, hd = q.shape
    s, nkv = ck.shape[1], ck.shape[2]
    if nh % nkv != 0:
        raise ValueError(f"nh={nh} not divisible by nkv={nkv}")
    r = nh // nkv
    quant = k_scale is not None
    if quant != (v_scale is not None):
        raise ValueError("pass both k_scale and v_scale, or neither")
    if window is not None and window < 1:
        raise ValueError("window must be >= 1")
    if block_k is not None and s % block_k != 0:
        raise ValueError(f"cache length {s} not divisible by {block_k}")
    if block_k is not None:
        # A block of the caller's: every head through one product.
        tiling = (block_k, nkv)
    elif quant:
        one_head = _largest_block(s)
        tiling = None if one_head is None else (one_head, 1)
    else:
        tiling = _decode_tiling(g, nh, nkv, ck.dtype.itemsize, s)
    if not tiling:
        raise ValueError(
            f"cache length {s} has no 128/256/512 block divisor that "
            f"fits {g} queries a row; pass block_k or use the dense path"
        )
    block_k, hw = tiling
    if ring and (quant or window is None or s < window + g - 1):
        raise ValueError(
            f"a ring of {s} rows is read under a window it covers with "
            f"the chunk (window + g - 1 = "
            f"{None if window is None else window + g - 1}), bf16 / f32 "
            "rows only"
        )
    if quant:
        if slots is not None or lengths is not None or jnp.ndim(pos0):
            raise ValueError(
                "the int8 decode kernel takes one scalar pos0 and no "
                "slots / lengths; use the dense path per row"
            )
        return _flash_decode_quant(
            q, ck, cv, pos0, k_scale, v_scale, window, block_k, interpret
        )
    if slots is None:
        if ck.shape[0] != b:
            raise ValueError(
                f"{b} query rows against {ck.shape[0]} cache rows: pass "
                "slots"
            )
        slots = jnp.arange(b)
    pos0 = jnp.broadcast_to(jnp.asarray(pos0, jnp.int32), (b,))
    lengths = jnp.clip(
        pos0 + g if lengths is None else lengths, 0, None if ring else s
    ).astype(jnp.int32)
    return _flash_decode_rows(
        q, ck, cv, pos0, lengths, slots.astype(jnp.int32), window=window,
        block_k=block_k, hw=hw, ring=ring, interpret=interpret,
    )


@functools.partial(
    jax.jit, static_argnames=("window", "block_k", "hw", "ring", "interpret")
)
def _flash_decode_rows(
    q: jnp.ndarray, ck: jnp.ndarray, cv: jnp.ndarray, pos0: jnp.ndarray,
    lengths: jnp.ndarray, slots: jnp.ndarray, *, window: Optional[int],
    block_k: int, hw: int, ring: bool = False, interpret: bool,
) -> jnp.ndarray:
    """:func:`flash_decode_attention` over a bf16 / f32 cache, every
    operand per row.  Jitted so that a model's layers (same shapes,
    every layer) trace and lower the kernel ONCE and call one function:
    bare, six layers of the two serving programs added 1.6 s to every
    start (trace + lower 1.0 -> 2.6 s; described-chip lowering, PR 29)."""
    b, g, nh, hd = q.shape
    s, nkv = ck.shape[1], ck.shape[2]
    r = nh // nkv
    groups, rows = nkv // hw, g * hw * r
    # Rows of a group: (query, local head) — a transpose of q's few
    # rows, nothing of the cache's.
    qf = q.reshape(b, g, groups, hw * r, hd)
    qf = jnp.transpose(qf, (0, 2, 1, 3, 4)).reshape(b, groups, rows, hd)
    # A band of ``s`` positions that starts inside a block touches one
    # block of positions more than the ring has.
    nb = s // block_k
    row, slot, blk, ends = _decode_steps(
        pos0, lengths, slots, window, block_k, b * (nb + 1 if ring else nb)
    )

    def kv_im(t: Any, row_ref: Any, slot_ref: Any, blk_ref: Any,
              *_: Any) -> Tuple:
        if ring:
            return (slot_ref[t], lax.rem(blk_ref[t], nb), 0, 0)
        return (slot_ref[t], blk_ref[t], 0, 0)

    def q_im(t: Any, row_ref: Any, *_: Any) -> Tuple:
        return (row_ref[t], 0, 0, 0)

    out = pl.pallas_call(
        functools.partial(
            _decode_kernel, r=r, hw=hw, block_k=block_k, window=window,
            sm_scale=hd ** -0.5,
        ),
        name="flash_decode",
        out_shape=jax.ShapeDtypeStruct((b, groups, rows, hd), jnp.float32),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=6,
            grid=(ends[-1],),
            in_specs=[
                pl.BlockSpec((1, groups, rows, hd), q_im),
                pl.BlockSpec((1, block_k, nkv, hd), kv_im),
                pl.BlockSpec((1, block_k, nkv, hd), kv_im),
            ],
            out_specs=pl.BlockSpec((1, groups, rows, hd), q_im),
            scratch_shapes=[
                pltpu.VMEM((groups, rows, 1), jnp.float32),
                pltpu.VMEM((groups, rows, 1), jnp.float32),
                pltpu.VMEM((groups, rows, hd), jnp.float32),
            ],
        ),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=_DECODE_VMEM_BYTES,
        ),
        interpret=interpret,
    )(row, slot, blk, ends, pos0, lengths, qf, ck, cv)
    out = out.reshape(b, groups, g, hw * r, hd)
    return jnp.transpose(out, (0, 2, 1, 3, 4)).reshape(b, g, nh * hd)


def _flash_decode_quant(
    q: jnp.ndarray, ck: jnp.ndarray, cv: jnp.ndarray, pos0: jnp.ndarray,
    k_scale: jnp.ndarray, v_scale: jnp.ndarray, window: Optional[int],
    block_k: int, interpret: bool,
) -> jnp.ndarray:
    """:func:`flash_decode_attention` over an int8 cache."""
    b, g, nh, hd = q.shape
    s, nkv = ck.shape[1], ck.shape[2]
    r = nh // nkv
    nkb = s // block_k
    qf = q.reshape(b, g, nh * hd)
    ckf = ck.reshape(b, s, nkv * hd)
    cvf = cv.reshape(b, s, nkv * hd)
    length = jnp.reshape(pos0 + g, (1,)).astype(jnp.int32)

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
    # One head's whole scale row [nkb, Bk] per (batch, head) cell — s
    # floats, fetched once per (i, h) (the index map is constant over
    # jb, so Pallas elides per-block refetches); full-axis last-two
    # dims keep it tileable for any nkb.
    scale_spec = pl.BlockSpec(
        (1, 1, nkb, block_k), lambda i, h, jb, len_ref: (i, h, 0, 0)
    )
    return pl.pallas_call(
        functools.partial(
            _decode_quant_kernel, g=g, r=r, hd=hd, sm_scale=hd ** -0.5,
            block_k=block_k, window=window,
        ),
        name="flash_decode",
        out_shape=jax.ShapeDtypeStruct((b, g, nh * hd), jnp.float32),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(b, nkv, nkb),
            in_specs=[
                pl.BlockSpec((1, g, r * hd), q_im),
                pl.BlockSpec((1, block_k, hd), kv_im),
                pl.BlockSpec((1, block_k, hd), kv_im),
                scale_spec,
                scale_spec,
            ],
            out_specs=pl.BlockSpec((1, g, r * hd), q_im),
            scratch_shapes=[
                pltpu.VMEM((g * r, 1), jnp.float32),
                pltpu.VMEM((g * r, 1), jnp.float32),
                pltpu.VMEM((g * r, hd), jnp.float32),
            ],
        ),
        interpret=interpret,
    )(
        length, qf, ckf, cvf,
        k_scale.reshape(b, nkv, nkb, block_k),
        v_scale.reshape(b, nkv, nkb, block_k),
    )


# --------------------------------------------------------------------- #
# decode over a LATENT cache (``models.mla``, absorbed form)            #
# --------------------------------------------------------------------- #


# Query rows a product of the latent kernel takes (a chunk's ``g x H``
# rows go through a grid step in groups of this many: the f32 score
# plane of a group at a 512-block is 1 MiB; at 25 rows of 32 x 64
# heads groups of 128 / 256 / 512 / 1024 read 0.64 / 0.57 / 0.56 / 0.54
# ms, chip run, PR 35), and the f32 accumulator ``[g x H, c]`` up to
# which a row's queries stay resident across its blocks (with them
# their latent queries and the output tile, twice each: 15 MiB of the
# 48 at a chunk of 32 x 64 heads x 512).
_LATENT_GROUP_ROWS = 512
_LATENT_ACC_BYTES = 8 * 1024 * 1024


def _latent_tiling(rows: int, c: int, s: int) -> Optional[Tuple[int, int]]:
    """``(block_k, rq)`` of the latent decode kernel for ``rows`` query
    rows a cache row (``g`` queries x ``H`` heads), a latent of ``c``
    and a cache of ``s`` rows: the largest block dividing ``s``, and the
    query rows a product takes.  None: no block divides ``s``, the rows
    do not split into whole groups, or their accumulator is too large
    to stay resident."""
    block_k = _largest_block(s)
    if block_k is None or rows * c * 4 > _LATENT_ACC_BYTES:
        return None
    if rows <= _LATENT_GROUP_ROWS:
        return block_k, rows
    for rq in (_LATENT_GROUP_ROWS, 256, 128):
        if rows % rq == 0:
            return block_k, rq
    return None


def supports_latent_decode(
    q_shape: Tuple[int, ...], bank_shape: Tuple[int, ...], rope_dim: int,
) -> bool:
    """Static eligibility for :func:`latent_decode_attention` (the gate
    of ``models.generation._attend_latent``): latent queries ``[b, g, H,
    c]`` against a bank ``[slots, s, c]`` with a shared key head of
    ``rope_dim``.  The latent is whole lane tiles, the query rows of a
    cache row whole sublane tiles, the cache long enough to be worth a
    dispatch (:func:`supports_decode`'s floor) and tiled by
    :func:`_latent_tiling`."""
    _, g, H, c = q_shape
    s = bank_shape[1]
    if c % 128 != 0 or bank_shape[2] != c or rope_dim < 1:
        return False
    if (g * H) % 8 != 0 or s < 256:
        return False
    return _latent_tiling(g * H, c, s) is not None


def _latent_decode_kernel(
    row_ref: Any,    # [steps] query row of each grid step
    slot_ref: Any,   # [steps] bank row of its tiles (the index maps')
    blk_ref: Any,    # [steps] cache block of its tiles
    end_ref: Any,    # [b] one past a row's last step (running sum)
    pos_ref: Any,    # [b] first query's position
    len_ref: Any,    # [b] cache rows the row reads; 0: none
    ql_ref: Any,     # [1, g*H, c]   queries in the latent space
    qp_ref: Any,     # [1, g*H, r]   their rotated part
    ckv_ref: Any,    # [1, block_k, c]  the latent rows: keys AND values
    kpe_ref: Any,    # [1, r, block_k]  the shared rotated key head,
                     # positions minor
    o_ref: Any,      # [1, g*H, c]
    m_sc: Any,
    l_sc: Any,
    acc_sc: Any,
    *,
    heads: int,
    rq: int,
    block_k: int,
    sm_scale: float,
) -> None:
    """One grid step of latent attention in its absorbed form: one cache
    block of one query row's slot, ``s = (q_lat . ckv^T + q_pe . kpe^T)
    * sm_scale`` under the causal mask, online softmax carried in VMEM
    scratch across the row's steps, ``acc += p . ckv``.  It is attention
    with ONE key/value head whose key is ``[latent | rotated head]`` and
    whose value is the latent again: the ``[block_k, c]`` tile is
    fetched once and feeds both products, for every head.

    The grid is :func:`_decode_kernel`'s flat list of (row, block) steps
    (:func:`_decode_steps`, no window): a row takes the blocks up to its
    own frontier and no other.  Row ``x`` of the ``g*H`` is query ``x //
    heads`` (position ``pos0 + x // heads``), head ``x % heads``; they
    go through the products ``rq`` at a time, so that a prompt chunk's
    score plane stays ``[rq, block_k]`` while its 2,048 rows share the
    one fetch of the tile.  Arithmetic is ``mla.attend``'s: operands in
    the cache's type, ``p`` rounded to it, f32 accumulation and softmax
    (:func:`_dot_tile`)."""
    t = pl.program_id(0)
    i = row_ref[t]
    jb = blk_ref[t]
    end = end_ref[i]
    start = jnp.where(i > 0, end_ref[jnp.maximum(i - 1, 0)], 0)
    pos0 = pos_ref[i]
    rows = acc_sc.shape[0]

    @pl.when(t == start)
    def _init():
        m_sc[...] = jnp.full_like(m_sc, _NEG)
        l_sc[...] = jnp.zeros_like(l_sc)
        acc_sc[...] = jnp.zeros_like(acc_sc)

    @pl.when(len_ref[i] > 0)
    def _body():
        ckv, kpe = ckv_ref[0], kpe_ref[0]
        col = jb * block_k + lax.broadcasted_iota(jnp.int32, (1, block_k), 1)
        for lo in range(0, rows, rq):
            grp = pl.ds(lo, rq)
            x = lo + lax.broadcasted_iota(jnp.int32, (rq, 1), 0)
            valid = col <= pos0 + x // heads
            s = (
                _dot_tile(ql_ref[0, grp], ckv, _NT)
                + _dot_tile(qp_ref[0, grp], kpe, _NN)
            ) * sm_scale
            s = jnp.where(valid, s, _NEG)
            m_prev = m_sc[grp]
            m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
            p = jnp.where(valid, jnp.exp(s - m_new), 0.0)
            corr = jnp.exp(m_prev - m_new)
            l_sc[grp] = l_sc[grp] * corr + jnp.sum(p, axis=1, keepdims=True)
            acc_sc[grp] = acc_sc[grp] * corr + _dot_tile(p, ckv, _NN)
            m_sc[grp] = m_new

    @pl.when(t == end - 1)
    def _finish():
        # A row that read nothing has l == 0: zeros, not 0/0.
        l = l_sc[...]
        o_ref[0] = jnp.where(
            l > 0, acc_sc[...] / jnp.where(l > 0, l, 1.0), 0.0
        ).astype(o_ref.dtype)


def latent_decode_attention(
    q_lat: jnp.ndarray,          # [b, g, H, c] — queries through W_kvb^K
    q_pe: jnp.ndarray,           # [b, g, H, r] — rotated, positions
                                 # pos0..pos0+g-1
    ckv: jnp.ndarray,            # [slots, max_len, c] latent cache
    kpe: jnp.ndarray,            # [slots, max_len, r] shared key head
    pos0: jnp.ndarray,           # [] or [b] int32 — first query's position
    *,
    sm_scale: float,
    slots: Optional[jnp.ndarray] = None,    # [b] — row i reads bank row
                                            # slots[i]
    lengths: Optional[jnp.ndarray] = None,  # [b] — cache rows a row
                                            # reads (0: none)
    block_k: Optional[int] = None,
    interpret: bool = False,
) -> jnp.ndarray:
    """Decode-side attention over the LIVE PREFIX of a latent cache
    (``models.kv_cache.LatentCache``): the Pallas twin of the absorbed
    ``models.mla.attend`` between its two einsums — ``o_lat [b, g, H,
    c]`` in the cache's type, the softmax of ``(q_lat . ckv^T + q_pe .
    kpe^T) * sm_scale`` over each row's own cache rows times ``ckv``.
    ``q_lat = q_nope . W_kvb^K`` and ``o_lat . W_kvb^V`` stay the
    caller's einsums.

    Rows, slots and lengths are :func:`flash_decode_attention`'s: row
    ``i`` reads bank row ``slots[i]`` (the two banks as they lie,
    through the index maps: no slice, no gather; a slot may repeat) up
    to ``lengths[i]`` rows (``pos0 + g`` where not given, clipped to
    ``max_len``), block by block, and a row of length 0 fetches nothing
    and returns zeros.  The queries take the cache's type, as
    ``mla.attend`` rounds them."""
    b, g, H, c = q_lat.shape
    s = ckv.shape[1]
    if block_k is not None and s % block_k != 0:
        raise ValueError(f"cache length {s} not divisible by {block_k}")
    tiling = _latent_tiling(g * H, c, s)
    if tiling is None:
        raise ValueError(
            f"no latent decode tiling for {g} x {H} query rows of {c} "
            f"against {s} cache rows; use the dense path (mla.attend)"
        )
    if slots is None:
        if ckv.shape[0] != b:
            raise ValueError(
                f"{b} query rows against {ckv.shape[0]} cache rows: pass "
                "slots"
            )
        slots = jnp.arange(b)
    pos0 = jnp.broadcast_to(jnp.asarray(pos0, jnp.int32), (b,))
    lengths = jnp.clip(
        pos0 + g if lengths is None else lengths, 0, s
    ).astype(jnp.int32)
    return _latent_decode_rows(
        q_lat.astype(ckv.dtype), q_pe.astype(kpe.dtype), ckv, kpe, pos0,
        lengths, slots.astype(jnp.int32), sm_scale=float(sm_scale),
        block_k=block_k or tiling[0], rq=tiling[1], interpret=interpret,
    )


@functools.partial(
    jax.jit, static_argnames=("sm_scale", "block_k", "rq", "interpret")
)
def _latent_decode_rows(
    q_lat: jnp.ndarray, q_pe: jnp.ndarray, ckv: jnp.ndarray,
    kpe: jnp.ndarray, pos0: jnp.ndarray, lengths: jnp.ndarray,
    slots: jnp.ndarray, *, sm_scale: float, block_k: int, rq: int,
    interpret: bool,
) -> jnp.ndarray:
    """:func:`latent_decode_attention`, every operand per row.  Jitted
    for :func:`_flash_decode_rows`' reason: a model's layers lower the
    kernel once."""
    b, g, H, c = q_lat.shape
    s, r = ckv.shape[1], kpe.shape[2]
    rows = g * H
    row, slot, blk, ends = _decode_steps(
        pos0, lengths, slots, None, block_k, b * (s // block_k)
    )

    def bank_im(t: Any, row_ref: Any, slot_ref: Any, blk_ref: Any,
                *_: Any) -> Tuple:
        return (slot_ref[t], blk_ref[t], 0)

    def head_im(t: Any, row_ref: Any, slot_ref: Any, blk_ref: Any,
                *_: Any) -> Tuple:
        return (slot_ref[t], 0, blk_ref[t])

    def q_im(t: Any, row_ref: Any, *_: Any) -> Tuple:
        return (row_ref[t], 0, 0)

    out = pl.pallas_call(
        functools.partial(
            _latent_decode_kernel, heads=H, rq=rq, block_k=block_k,
            sm_scale=sm_scale,
        ),
        name="latent_decode",
        out_shape=jax.ShapeDtypeStruct((b, rows, c), ckv.dtype),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=6,
            grid=(ends[-1],),
            in_specs=[
                pl.BlockSpec((1, rows, c), q_im),
                pl.BlockSpec((1, rows, r), q_im),
                pl.BlockSpec((1, block_k, c), bank_im),
                pl.BlockSpec((1, r, block_k), head_im),
            ],
            out_specs=pl.BlockSpec((1, rows, c), q_im),
            scratch_shapes=[
                pltpu.VMEM((rows, 1), jnp.float32),
                pltpu.VMEM((rows, 1), jnp.float32),
                pltpu.VMEM((rows, c), jnp.float32),
            ],
        ),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=_DECODE_VMEM_BYTES,
        ),
        interpret=interpret,
    )(
        row, slot, blk, ends, pos0, lengths,
        q_lat.reshape(b, rows, c), q_pe.reshape(b, rows, r), ckv,
        jnp.swapaxes(kpe, 1, 2),
    )
    return out.reshape(b, g, H, c)
