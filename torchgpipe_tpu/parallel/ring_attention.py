"""Ring attention: exact attention over sequence shards on a device ring.

Long sequences are sharded over an ``sp`` mesh axis; each device holds
``[b, s/sp, h, d]`` of Q, K, V.  K/V blocks rotate around the ring via
``lax.ppermute`` (neighbor ICI transfers on TPU) while each device
accumulates its queries' attention with the streaming (online-softmax)
recurrence — numerically exact, never materializing the full ``[s, s]``
score matrix.  Each ring step is ``jax.checkpoint``-ed, so backward
recomputes one block at a time: activation memory is O(s/sp) per device,
which is what makes million-token contexts feasible (Liu et al., "Ring
Attention with Blockwise Transformers", arXiv:2310.01889 — public
technique, implemented here from the math).

The reference framework has no sequence/context parallelism at all
(SURVEY.md §5); this module is the TPU-native new capability that composes
with the pipeline (``pp``) and data (``dp``) axes in
:class:`~torchgpipe_tpu.spmd.SpmdGPipe`.

Differentiable end-to-end: the ``ppermute`` transposes route K/V cotangents
backwards around the ring automatically.
"""

from __future__ import annotations

import contextlib
import os
from typing import Any, Iterator, Optional

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import PartitionSpec as P


def partition_rules(sp_axis: str, pp_axis: str = "pp") -> Any:
    """Ring attention's param layout as a rule table (the unified layer
    of :mod:`torchgpipe_tpu.analysis.partition_rules`): like Ulysses,
    the ring shards the SEQUENCE (K/V blocks rotate over ``sp``), never
    parameters — every param leaf replicates over ``sp`` (stage dim
    over ``pp``)."""
    from torchgpipe_tpu.analysis.partition_rules import (
        PartitionRule,
        RuleTable,
    )

    del sp_axis  # declared for symmetry: no param leaf mentions it
    return RuleTable(
        name="ring-attention-sequence-parallel",
        rules=(
            PartitionRule(
                r".*", P(pp_axis),
                note="sp shards activations, not params",
            ),
        ),
    )

_NEG = -1e30  # large negative instead of -inf: keeps grads NaN-free


def _group(q: jnp.ndarray, n_kv: int) -> jnp.ndarray:
    """[b, s, h, d] -> [b, s, g, r, d] with h = g*r grouped onto kv heads.

    GQA support at the compute site: K/V stay at their n_kv heads (so the
    ring only moves n_kv-head blocks) and queries are grouped to match.
    Query head ``h`` maps to kv head ``h // r`` — the same pairing as
    ``jnp.repeat(k, r, axis=2)``.
    """
    b, s, h, d = q.shape
    return q.reshape(b, s, n_kv, h // n_kv, d)


def _scores(q: jnp.ndarray, k: jnp.ndarray, sm_scale: float) -> jnp.ndarray:
    # q [b, sq, h, d] x k [b, sk, g, d] (g divides h) -> [b, h, sq, sk];
    # f32 accumulation on the MXU (inputs may be bf16).
    g = k.shape[2]
    qg = _group(q, g)
    s = jnp.einsum(
        "bqgrd,bkgd->bgrqk", qg, k, preferred_element_type=jnp.float32
    ) * sm_scale
    b, _, r, sq, sk = s.shape
    return s.reshape(b, g * r, sq, sk)


def _weighted_v(p: jnp.ndarray, v: jnp.ndarray) -> jnp.ndarray:
    # p [b, h, sq, sk] x v [b, sk, g, d] -> [b, h, sq, d]
    b, h, sq, sk = p.shape
    g = v.shape[2]
    pg = p.reshape(b, g, h // g, sq, sk)
    o = jnp.einsum(
        "bgrqk,bkgd->bgrqd", pg, v, preferred_element_type=jnp.float32
    )
    return o.reshape(b, h, sq, v.shape[-1])


def full_attention(
    q: jnp.ndarray,
    k: jnp.ndarray,
    v: jnp.ndarray,
    *,
    causal: bool = True,
    sm_scale: Optional[float] = None,
    window: Optional[int] = None,
    seg: Optional[jnp.ndarray] = None,
) -> jnp.ndarray:
    """Plain dense attention (single-device oracle / sp-disabled path).

    ``q``: ``[b, s, h, d]``; ``k, v``: ``[b, s, g, d]`` with ``g`` dividing
    ``h`` (grouped-query attention; ``g == h`` is plain MHA).  Returns
    ``[b, s, h, d]``.  ``window`` (requires ``causal``) keeps only the
    last ``window`` positions: attend iff ``0 <= qpos - kpos < window``
    (Mistral-style sliding-window attention).

    ``seg`` (``[b, s]`` int segment ids, 0 = pad) folds the SEQUENCE-
    PACKING mask in: position ``i`` attends ``j`` only when
    ``seg[i] == seg[j]`` — the block-diagonal term that keeps packed
    documents from attending each other
    (:func:`torchgpipe_tpu.utils.data.pack_documents`).  All-masked pad
    rows soften to a uniform distribution (``_NEG``, not ``-inf``), so
    their garbage outputs stay finite; the packed loss weights them out.
    """
    from torchgpipe_tpu.ops.flash_attention import _validate_window

    d = q.shape[-1]
    sm_scale = d ** -0.5 if sm_scale is None else sm_scale
    _validate_window(causal, window)
    s = _scores(q, k, sm_scale)
    sq, sk = q.shape[1], k.shape[1]
    mask = None
    if causal:
        diff = jnp.arange(sq)[:, None] - jnp.arange(sk)[None, :]
        mask = diff >= 0
        if window is not None:
            mask = mask & (diff < window)
        mask = mask[None]  # [1, sq, sk]
    if seg is not None:
        seg_mask = seg[:, :, None] == seg[:, None, :]  # [b, sq, sk]
        mask = seg_mask if mask is None else (mask & seg_mask)
    if mask is not None:
        s = jnp.where(mask[:, None], s, _NEG)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.transpose(
        _weighted_v(p.astype(v.dtype), v), (0, 2, 1, 3)
    ).astype(q.dtype)


def ring_attention(
    q: jnp.ndarray,
    k: jnp.ndarray,
    v: jnp.ndarray,
    axis_name: str,
    *,
    causal: bool = True,
    sm_scale: Optional[float] = None,
    kv_block_size: int = 2048,
) -> jnp.ndarray:
    """Exact attention over sequence shards on the ``axis_name`` ring.

    Must be called inside a ``shard_map`` (or other collective context) where
    ``axis_name`` is bound; ``q, k, v`` are the local shards
    ``[b, s_local, h, d]`` of a global ``[b, s, h, d]``, all shards equal
    size.  Returns the local output shard.

    Each ring step is itself *blockwise* (the "blockwise transformers" half
    of Liu et al.): the arriving K/V shard is consumed in sub-blocks of at
    most ``kv_block_size`` through the same online-softmax recurrence (each
    sub-step ``jax.checkpoint``-ed, so the backward recomputes one
    sub-block at a time too), keeping transient AND residual score buffers
    at ``[b, h, s_local, sub]`` instead of ``[b, h, s_local, s_local]`` —
    large per-device shards (tens of k tokens) stay memory-feasible.  The
    sub count is the smallest divisor split of the shard with sub-blocks ≤
    ``kv_block_size`` (exact for any shard length).
    """
    b, sq, h, d = q.shape
    sm_scale = d ** -0.5 if sm_scale is None else sm_scale
    sp = lax.psum(1, axis_name)
    rank = lax.axis_index(axis_name)
    perm = [(j, (j + 1) % sp) for j in range(sp)]

    qpos = rank * sq + jnp.arange(sq)
    n_sub = 1
    if sq > kv_block_size:
        n_sub = -(-sq // kv_block_size)  # ceil
        while sq % n_sub != 0:  # nearest even split (worst case n_sub=sq)
            n_sub += 1
    sub = sq // n_sub

    def sub_update(o, l, m, kc, vc, kpos0):
        """Online-softmax accumulation of one K/V sub-block whose global
        positions start at ``kpos0``."""
        s = _scores(q, kc, sm_scale)  # [b, h, sq, sub] f32
        if causal:
            kpos = kpos0 + jnp.arange(kc.shape[1])
            mask = qpos[:, None] >= kpos[None, :]
            s = jnp.where(mask[None, None], s, _NEG)
        m_new = jnp.maximum(m, jnp.max(s, axis=-1))
        p = jnp.exp(s - m_new[..., None])
        corr = jnp.exp(m - m_new)  # [b, h, sq]
        l_new = l * corr + jnp.sum(p, axis=-1)
        o_new = o * corr[..., None] + _weighted_v(p.astype(vc.dtype), vc)
        return o_new, l_new, m_new

    def block_update(o, l, m, kc, vc, i):
        """One ring step: accumulate the K/V shard that originated on
        rank - i (equal shard sizes give its positions), sub-block by
        sub-block."""
        src = (rank - i) % sp
        if n_sub == 1:
            return sub_update(o, l, m, kc, vc, src * sq)

        def body(carry, jb):
            o, l, m = carry
            ks = lax.dynamic_slice_in_dim(kc, jb * sub, sub, 1)
            vs = lax.dynamic_slice_in_dim(vc, jb * sub, sub, 1)
            # checkpoint: without it the scan's backward would stack one
            # [b, h, sq, sub] softmax residual per sub-step — re-assembling
            # the full score matrix this sub-blocking exists to avoid.
            o, l, m = jax.checkpoint(sub_update)(
                o, l, m, ks, vs, src * sq + jb * sub
            )
            return (o, l, m), ()

        (o, l, m), _ = lax.scan(body, (o, l, m), jnp.arange(n_sub))
        return o, l, m

    def step(carry, i):
        o, l, m, kc, vc = carry
        o, l, m = block_update(o, l, m, kc, vc, i)
        k_next = lax.ppermute(kc, axis_name, perm)
        v_next = lax.ppermute(vc, axis_name, perm)
        return (o, l, m, k_next, v_next), ()

    # Step 0 processes the local (diagonal) block, so every causal query row
    # sees at least itself before any fully-masked block arrives; the running
    # max is finite from the first step on.
    o0 = jnp.zeros((b, h, sq, d), jnp.float32)
    l0 = jnp.zeros((b, h, sq), jnp.float32)
    m0 = jnp.full((b, h, sq), _NEG, jnp.float32)

    # Rotate only sp-1 times: the last block needs no onward hand-off, so its
    # ppermute pair never enters the program (it would sit on the critical
    # path of every attention call).
    (o, l, m, kc, vc), _ = lax.scan(
        jax.checkpoint(step), (o0, l0, m0, k, v), jnp.arange(sp - 1)
    )
    o, l, m = jax.checkpoint(block_update)(o, l, m, kc, vc, sp - 1)
    out = o / l[..., None]
    return jnp.transpose(out, (0, 2, 1, 3)).astype(q.dtype)


def axis_bound(name: Optional[str]) -> bool:
    """True if ``name`` is a collective axis bound in the current trace.

    Layers use this so one ``apply`` serves both deployment shapes: inside a
    ``shard_map`` over ``name`` the sequence is sharded (ring path); outside
    — including init-time shape inference — the local array IS the whole
    sequence (dense path, same shapes).
    """
    if name is None:
        return False
    try:
        lax.psum(1, name)
    except NameError:
        return False
    return True


@contextlib.contextmanager
def dense_attention_only() -> Iterator[None]:
    """Pin :func:`attention` to the dense path for programs TRACED inside
    the context (it reads ``TGPU_DISABLE_FLASH`` at trace time, and a
    compiled program keeps its branch): reference programs, and lowerings
    a Pallas kernel cannot serve — a CPU-targeted lowering on a TPU host,
    an HLO cost analysis (a custom call counts as zero FLOPs)."""
    before = os.environ.get("TGPU_DISABLE_FLASH")
    os.environ["TGPU_DISABLE_FLASH"] = "1"
    try:
        yield
    finally:
        if before is None:
            del os.environ["TGPU_DISABLE_FLASH"]
        else:
            os.environ["TGPU_DISABLE_FLASH"] = before


def attention(
    q: jnp.ndarray,
    k: jnp.ndarray,
    v: jnp.ndarray,
    *,
    axis_name: Optional[str] = None,
    causal: bool = True,
    sm_scale: Optional[float] = None,
    kv_block_size: int = 2048,
    impl: str = "ring",
    window: Optional[int] = None,
    seg: Optional[jnp.ndarray] = None,
) -> jnp.ndarray:
    """Dispatch: sequence-parallel attention when an sp axis is bound —
    ``impl='ring'`` (blockwise ring, O(s/sp) memory) or ``'ulysses'``
    (all_to_all head swap, full-sequence local compute; see
    :mod:`torchgpipe_tpu.parallel.ulysses`); on TPU the Pallas
    flash-attention kernel when shapes meet its tiling constraints
    (``TGPU_DISABLE_FLASH=1`` opts out); dense XLA attention otherwise.
    One call site serves every deployment shape.

    ``seg`` (``[b, s]`` segment ids — sequence packing, see
    :func:`full_attention`) takes the DENSE path unconditionally: the
    Pallas flash kernel has no segment-mask hook yet, so the packed
    training path falls back didactically to the masked XLA einsum
    (documented in docs/tuning.md; the dense mask is the oracle the
    kernel will be tested against when it grows the hook), and the
    sequence-parallel impls do not compose with packing (shards would
    need cross-shard segment routing)."""
    from torchgpipe_tpu.ops.flash_attention import _validate_window

    if impl not in ("ring", "ulysses"):
        raise ValueError("attention impl must be 'ring' or 'ulysses'")
    _validate_window(causal, window)
    if seg is not None:
        if axis_bound(axis_name):
            raise ValueError(
                "segment-packed attention does not compose with a bound "
                "sequence-parallel axis (ring/ulysses shards would need "
                "cross-shard segment routing); drop sp_axis for packed "
                "training"
            )
        return full_attention(
            q, k, v, causal=causal, sm_scale=sm_scale, window=window,
            seg=seg,
        )
    if not axis_bound(axis_name):
        from torchgpipe_tpu.ops import flash_attention as _fa

        dense = lambda q, k, v: full_attention(  # noqa: E731
            q, k, v, causal=causal, sm_scale=sm_scale, window=window
        )
        # Exact-tile heads (d % 128 == 0) take the kernel at any supported
        # length; padded heads (d < 128, e.g. the Llama-1B-class head_dim
        # 64) only where flash is measured to win over dense XLA
        # (seq >= PADDED_HEAD_MIN_SEQ) — this is what puts the kernel in
        # the TRAINING path at seq >= 2048 for the 1B preset.
        if (
            not os.environ.get("TGPU_DISABLE_FLASH")
            and _fa.supports(q.shape, k.shape)
            and (
                q.shape[3] % 128 == 0
                or q.shape[1] >= _fa.PADDED_HEAD_MIN_SEQ
            )
        ):
            def flash(q, k, v, interpret):
                return _fa.flash_attention(
                    q, k, v, causal=causal, sm_scale=sm_scale,
                    window=window, interpret=interpret,
                )

            if jax.default_backend() == "tpu":
                # On a TPU host the kernel is chosen at TRACE time, not
                # by platform_dependent: a cond's partial evaluation
                # makes the vjp carry EVERY branch's residuals
                # (zero-filled for the branch not taken), and the MPMD
                # engine's forward and backward are separate programs,
                # so nothing can prune them — the dense branch's
                # [b, h, s, s] f32 scores were allocated per block per
                # stored micro-batch (2 GiB each at 32 heads x 4096,
                # RESOURCE_EXHAUSTED on the chip).
                return flash(q, k, v, False)
            # Off-TPU hosts: resolved at RUN time by platform_index,
            # which executes the dense branch.  The kernel branch is
            # traced with interpret=True — this jax lowers EVERY
            # platform_dependent branch for the current platform, and
            # Mosaic has no CPU lowering — so the training jaxpr carries
            # the real pallas_call on every host (statically checkable
            # on CPU) and is dead code at runtime.  Known hole: a
            # CPU-TARGETED lowering on a TPU-backend host (CPU oracle
            # under jax.default_device(cpu)) lowers the Mosaic kernel
            # for CPU and fails — run such oracles under
            # TGPU_DISABLE_FLASH=1.
            return lax.platform_dependent(
                q, k, v,
                tpu=lambda q, k, v: flash(q, k, v, True),
                default=dense,
            )
        return dense(q, k, v)
    if impl == "ulysses":
        from torchgpipe_tpu.parallel.ulysses import ulysses_attention

        return ulysses_attention(
            q, k, v, axis_name, causal=causal, sm_scale=sm_scale,
            window=window,
        )
    if window is not None:
        raise ValueError(
            "sliding-window attention does not compose with the ring sp "
            "path yet (the ring would need per-step band skipping); use "
            "sp_impl='ulysses' — its local full-sequence attention "
            "windows exactly — or drop the sp axis"
        )
    return ring_attention(
        q, k, v, axis_name, causal=causal, sm_scale=sm_scale,
        kv_block_size=kv_block_size,
    )
