"""Flash-attention Pallas kernels vs the dense XLA oracle (interpret mode
runs the same kernel code on the CPU backend)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from torchgpipe_tpu.ops.flash_attention import flash_attention, supports
from torchgpipe_tpu.parallel.ring_attention import full_attention


def _rand(key, shape):
    return jax.random.normal(key, shape, jnp.float32)


@pytest.mark.parametrize("causal", [True, False])
@pytest.mark.parametrize("gqa", [False, True])
def test_forward_matches_dense(causal, gqa):
    b, s, h, d = 2, 64, 4, 16
    g = 2 if gqa else h
    ks = jax.random.split(jax.random.PRNGKey(0), 3)
    q = _rand(ks[0], (b, s, h, d))
    k = _rand(ks[1], (b, s, g, d))
    v = _rand(ks[2], (b, s, g, d))
    ref = full_attention(q, k, v, causal=causal)
    out = flash_attention(q, k, v, causal=causal, block_q=16, block_k=16,
                          interpret=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("causal", [True, False])
def test_grads_match_dense(causal):
    b, s, h, d = 1, 32, 2, 8
    g = 1  # GQA with 2 query heads per kv head
    ks = jax.random.split(jax.random.PRNGKey(1), 4)
    q = _rand(ks[0], (b, s, h, d))
    k = _rand(ks[1], (b, s, g, d))
    v = _rand(ks[2], (b, s, g, d))
    cot = _rand(ks[3], (b, s, h, d))

    def loss_flash(q, k, v):
        o = flash_attention(q, k, v, causal=causal, block_q=8, block_k=8,
                            interpret=True)
        return jnp.sum(o * cot)

    def loss_ref(q, k, v):
        return jnp.sum(full_attention(q, k, v, causal=causal) * cot)

    gf = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
    gr = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    for a, b_ in zip(gf, gr):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b_),
                                   rtol=2e-4, atol=2e-4)


def test_uneven_blocks_and_long_kv():
    # block_q != block_k and s_q != s_k (non-causal cross-attention shape).
    b, sq, sk, h, d = 1, 32, 64, 2, 8
    ks = jax.random.split(jax.random.PRNGKey(2), 3)
    q = _rand(ks[0], (b, sq, h, d))
    k = _rand(ks[1], (b, sk, h, d))
    v = _rand(ks[2], (b, sk, h, d))
    ref = full_attention(q, k, v, causal=False)
    out = flash_attention(q, k, v, causal=False, block_q=16, block_k=32,
                          interpret=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)


def test_supports_gate():
    assert supports((2, 1024, 16, 128), (2, 1024, 8, 128))
    # head_dim < 128 is supported via zero-padding to one lane tile (the
    # Llama-1B-class d=64 — what puts the kernel in the training path).
    assert supports((2, 1024, 16, 64), (2, 1024, 8, 64))
    assert not supports((2, 1024, 16, 192), (2, 1024, 8, 192))  # d % 128
    assert not supports((2, 1000, 16, 128), (2, 1000, 8, 128))  # s % block


def test_padded_head_dim_matches_dense():
    # d=64 rides the kernel with the head dim zero-padded to 128: scores
    # and outputs must be EXACT vs the unpadded dense oracle (q/k padding
    # adds zero to every score; v padding zeros the sliced-off dims), and
    # gradients must flow back through the pad/slice unchanged.
    b, s, h, g, d = 1, 128, 4, 2, 64
    ks = jax.random.split(jax.random.PRNGKey(11), 3)
    q = _rand(ks[0], (b, s, h, d))
    k = _rand(ks[1], (b, s, g, d))
    v = _rand(ks[2], (b, s, g, d))
    out = flash_attention(q, k, v, causal=True, interpret=True)
    ref = full_attention(q, k, v, causal=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)

    def loss_flash(q, k, v):
        return jnp.sum(jnp.sin(
            flash_attention(q, k, v, causal=True, interpret=True)
        ))

    def loss_dense(q, k, v):
        return jnp.sum(jnp.sin(full_attention(q, k, v, causal=True)))

    gf = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
    gd = jax.grad(loss_dense, argnums=(0, 1, 2))(q, k, v)
    for a, b_ in zip(gf, gd):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b_),
                                   rtol=2e-4, atol=2e-4)


def test_auto_picker_padded_head_seq_gate():
    # The auto-picker puts the kernel in the jaxpr for padded heads only
    # at seq >= PADDED_HEAD_MIN_SEQ (where flash is measured to win);
    # exact-tile heads keep the kernel at any supported length.
    from torchgpipe_tpu.parallel.ring_attention import attention

    def has_pallas(d, s):
        q = jax.ShapeDtypeStruct((1, s, 4, d), jnp.float32)
        k = jax.ShapeDtypeStruct((1, s, 2, d), jnp.float32)
        jaxpr = jax.make_jaxpr(
            lambda q, k, v: attention(q, k, v, causal=True)
        )(q, k, k)
        return "pallas_call" in str(jaxpr)

    assert has_pallas(64, 2048)       # padded head at the gate
    assert not has_pallas(64, 1024)   # padded head below the gate: dense
    assert has_pallas(128, 256)       # exact tile: any supported length


@pytest.mark.parametrize("s,block", [(256, 256), (384, 128), (512, 512)])
def test_default_blocks_follow_the_length(s, block):
    """``block_q`` / ``block_k`` left out are the largest of 512 / 256 /
    128 dividing the sequence (a grid step's fixed cost is what the
    kernels pay most for: PERF.md, PR 31); the result is the dense
    oracle's whatever the block."""
    from torchgpipe_tpu.ops.flash_attention import _largest_block

    assert _largest_block(s) == block
    assert _largest_block(100) is None
    b, h, g, d = 1, 2, 1, 8
    ks = jax.random.split(jax.random.PRNGKey(29), 3)
    q = _rand(ks[0], (b, s, h, d))
    k = _rand(ks[1], (b, s, g, d))
    v = _rand(ks[2], (b, s, g, d))

    def fn(q, k, v):
        return flash_attention(
            q, k, v, causal=True, window=200, interpret=True
        )

    np.testing.assert_allclose(
        np.asarray(fn(q, k, v)),
        np.asarray(full_attention(q, k, v, causal=True, window=200)),
        rtol=2e-5, atol=2e-5,
    )
    # The forward's grid: (batch * heads, sequence / block).
    assert f"grid=({b * h}, {s // block})" in str(
        jax.make_jaxpr(fn)(q, k, v)
    )


def test_bf16_inputs():
    b, s, h, d = 1, 32, 2, 8
    ks = jax.random.split(jax.random.PRNGKey(3), 3)
    q = _rand(ks[0], (b, s, h, d)).astype(jnp.bfloat16)
    k = _rand(ks[1], (b, s, h, d)).astype(jnp.bfloat16)
    v = _rand(ks[2], (b, s, h, d)).astype(jnp.bfloat16)
    ref = full_attention(q, k, v, causal=True)
    out = flash_attention(q, k, v, causal=True, block_q=8, block_k=8,
                          interpret=True)
    assert out.dtype == jnp.bfloat16
    np.testing.assert_allclose(
        np.asarray(out, np.float32), np.asarray(ref, np.float32),
        rtol=5e-2, atol=5e-2,
    )


@pytest.mark.parametrize("causal", [True, False])
@pytest.mark.parametrize("gqa", [False, True])
def test_streaming_forward_matches_dense(causal, gqa):
    """Third-grid-dimension variant (K/V tiles stream, scratch-carried
    online softmax) must be exact too."""
    b, s, h, d = 2, 64, 4, 16
    g = 2 if gqa else h
    ks = jax.random.split(jax.random.PRNGKey(5), 3)
    q = _rand(ks[0], (b, s, h, d))
    k = _rand(ks[1], (b, s, g, d))
    v = _rand(ks[2], (b, s, g, d))
    ref = full_attention(q, k, v, causal=causal)
    out = flash_attention(q, k, v, causal=causal, block_q=16, block_k=16,
                          interpret=True, streaming=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("causal", [True, False])
def test_streaming_grads_match_dense(causal):
    b, s, h, d = 1, 32, 2, 8
    g = 1
    ks = jax.random.split(jax.random.PRNGKey(6), 4)
    q = _rand(ks[0], (b, s, h, d))
    k = _rand(ks[1], (b, s, g, d))
    v = _rand(ks[2], (b, s, g, d))
    cot = _rand(ks[3], (b, s, h, d))

    def loss_flash(q, k, v):
        o = flash_attention(q, k, v, causal=causal, block_q=8, block_k=8,
                            interpret=True, streaming=True)
        return jnp.sum(o * cot)

    def loss_ref(q, k, v):
        return jnp.sum(full_attention(q, k, v, causal=causal) * cot)

    gf = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
    gr = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    for a, b_ in zip(gf, gr):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b_),
                                   rtol=2e-4, atol=2e-4)


def test_streaming_uneven_blocks_and_long_kv():
    b, sq, sk, h, d = 1, 32, 64, 2, 8
    ks = jax.random.split(jax.random.PRNGKey(7), 3)
    q = _rand(ks[0], (b, sq, h, d))
    k = _rand(ks[1], (b, sk, h, d))
    v = _rand(ks[2], (b, sk, h, d))
    ref = full_attention(q, k, v, causal=False)
    out = flash_attention(q, k, v, causal=False, block_q=16, block_k=32,
                          interpret=True, streaming=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)


def test_streaming_causal_skips_masked_fetches():
    """Causal block-skipping in the streaming grids: the clamped index
    maps must re-request the SAME block for every fully-masked grid cell
    (Pallas skips the HBM copy when the block index is unchanged), so the
    number of distinct K/V (resp. Q) fetches per row equals the causal
    triangle, not the full rectangle."""
    from torchgpipe_tpu.ops.flash_attention import (
        _causal_overlap,
        _clamped_kv_block,
        _clamped_q_block,
        _first_valid_q,
        _last_valid_kv,
    )

    bq = bk = 16
    nq, nk = 8, 8
    # Forward/dQ grids: trailing dim streams K/V for a fixed q block j.
    kv_fetches = rect = tri = 0
    for j in range(nq):
        prev = None
        for jk in range(nk):
            idx = int(_clamped_kv_block(j, jk, bq, bk, True))
            valid = _causal_overlap(j, jk, bq, bk)
            tri += bool(valid)
            rect += 1
            if valid:
                assert idx == jk  # real cells fetch their own block
            else:
                assert idx == int(_last_valid_kv(j, bq, bk))  # clamped
            kv_fetches += idx != prev
            prev = idx
    assert kv_fetches == tri < rect

    # dK/dV grid: trailing dim streams Q for a fixed kv block jk; the
    # masked cells sit BEFORE the diagonal.
    q_fetches = tri_q = 0
    for jk in range(nk):
        prev = None
        for jq in range(nq):
            idx = int(_clamped_q_block(jk, jq, bq, bk, True, nq))
            valid = _causal_overlap(jq, jk, bq, bk)
            tri_q += bool(valid)
            if valid:
                assert idx == jq
            else:
                assert idx == int(_first_valid_q(jk, bq, bk))
            q_fetches += idx != prev
            prev = idx
    assert q_fetches == tri_q

    # Non-causal: no clamping, every cell fetches its own block.
    assert int(_clamped_kv_block(0, 5, bq, bk, False)) == 5
    assert int(_clamped_q_block(5, 0, bq, bk, False, nq)) == 0

    # Sliding window: the band clamps BOTH sides — per-row distinct
    # fetches equal the band width in blocks, not the triangle.
    w = 32  # 2 blocks
    band = fetches_w = 0
    for j in range(nq):
        prev = None
        for jk in range(nk):
            idx = int(_clamped_kv_block(j, jk, bq, bk, True, w))
            valid = bool(_causal_overlap(j, jk, bq, bk, w))
            band += valid
            if valid:
                assert idx == jk
            fetches_w += idx != prev
            prev = idx
    assert fetches_w == band < tri


@pytest.mark.slow  # fast-gate budget (VERDICT r5 #6): covered by the CI full job
def test_streaming_causal_grads_with_uneven_blocks():
    """Clamped index maps with block_q != block_k and causal masking:
    values and gradients must still match the dense oracle (the clamp
    arithmetic must agree with the mask arithmetic at ragged diagonal
    boundaries)."""
    b, s, h, d = 1, 64, 2, 8
    ks = jax.random.split(jax.random.PRNGKey(11), 4)
    q = _rand(ks[0], (b, s, h, d))
    k = _rand(ks[1], (b, s, h, d))
    v = _rand(ks[2], (b, s, h, d))
    cot = _rand(ks[3], (b, s, h, d))

    def loss_flash(q, k, v):
        return jnp.sum(
            flash_attention(q, k, v, causal=True, block_q=16, block_k=32,
                            interpret=True, streaming=True) * cot
        )

    def loss_ref(q, k, v):
        return jnp.sum(full_attention(q, k, v, causal=True) * cot)

    gf = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
    gr = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    for a, b_ in zip(gf, gr):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b_),
                                   rtol=2e-4, atol=2e-4)


@pytest.mark.parametrize("streaming", [False, True])
@pytest.mark.parametrize("window", [16, 24, 64])
@pytest.mark.slow  # fast-gate budget (VERDICT r5 #6): covered by the CI full job
def test_sliding_window_matches_dense(streaming, window):
    """Sliding-window flash attention (both kernel families) vs the dense
    masked oracle: values and gradients, including a window that is not a
    block multiple (24) and one covering the whole sequence (64)."""
    b, s, h, d = 1, 64, 2, 8
    ks = jax.random.split(jax.random.PRNGKey(13), 4)
    q = _rand(ks[0], (b, s, h, d))
    k = _rand(ks[1], (b, s, h, d))
    v = _rand(ks[2], (b, s, h, d))
    cot = _rand(ks[3], (b, s, h, d))

    def loss_flash(q, k, v):
        return jnp.sum(
            flash_attention(q, k, v, causal=True, window=window,
                            block_q=16, block_k=16, interpret=True,
                            streaming=streaming) * cot
        )

    def loss_ref(q, k, v):
        return jnp.sum(
            full_attention(q, k, v, causal=True, window=window) * cot
        )

    vf = loss_flash(q, k, v)
    vr = loss_ref(q, k, v)
    np.testing.assert_allclose(float(vf), float(vr), rtol=2e-4)
    gf = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
    gr = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    for a, b_ in zip(gf, gr):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b_),
                                   rtol=2e-4, atol=2e-4)


def test_sliding_window_gqa_uneven_blocks():
    """window with GQA and block_q != block_k."""
    b, s, h, g, d = 1, 64, 4, 2, 8
    ks = jax.random.split(jax.random.PRNGKey(17), 3)
    q = _rand(ks[0], (b, s, h, d))
    k = _rand(ks[1], (b, s, g, d))
    v = _rand(ks[2], (b, s, g, d))
    ref = full_attention(q, k, v, causal=True, window=20)
    out = flash_attention(q, k, v, causal=True, window=20, block_q=16,
                          block_k=32, interpret=True, streaming=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)


def test_sliding_window_validation():
    b, s, h, d = 1, 32, 2, 8
    ks = jax.random.split(jax.random.PRNGKey(19), 3)
    q, k, v = (_rand(ks[i], (b, s, h, d)) for i in range(3))
    with pytest.raises(ValueError, match="requires causal"):
        flash_attention(q, k, v, causal=False, window=8, interpret=True)
    from torchgpipe_tpu.parallel.ring_attention import attention
    with pytest.raises(ValueError, match="requires causal"):
        attention(q, k, v, causal=False, window=8)


# --------------------------------------------------------------------- #
# bf16 tiles go into the MXU as stored                                  #
# --------------------------------------------------------------------- #

# Unit roundoff of bfloat16 (8 significant bits).  On bf16 inputs the
# kernels and the dense path do the same arithmetic in another order:
# bf16 x bf16 products summed in f32 (exact up to f32 rounding), an f32
# softmax, the probabilities (in the backward: dS) rounded to bf16 ONCE
# before their product, the result rounded to bf16 ONCE.  Four roundings
# of at most ``u`` between the two paths: elementwise they differ by at
# most 4u of the array's largest element, and by half of that in norm
# (independent roundings add in quadrature; measured 1.9u and 0.9u).
_BF16_U = 2.0 ** -8


def _close_in_bf16(got, ref):
    got = np.asarray(got, np.float32)
    ref = np.asarray(ref, np.float32)
    assert np.abs(got - ref).max() <= 4 * _BF16_U * np.abs(ref).max()
    assert np.linalg.norm(got - ref) <= 2 * _BF16_U * np.linalg.norm(ref)


@pytest.mark.parametrize("blocks", [(16, 32), (32, 16)],
                         ids=["bq16-bk32", "bq32-bk16"])
@pytest.mark.parametrize("g", [4, 2], ids=["mha", "gqa"])
@pytest.mark.parametrize("window", [None, 24], ids=["causal", "window"])
@pytest.mark.parametrize("streaming", [False, True],
                         ids=["resident", "streaming"])
def test_bf16_matches_dense_on_the_same_inputs(streaming, window, g, blocks):
    """bf16 q / k / v / cotangent through both kernel families (tiles
    into the MXU as stored, p and dS rounded to the tile's type):
    forward and all three gradients against ``full_attention`` on the
    SAME bf16 arrays, at a tolerance counted in bf16 roundings."""
    b, s, h, d = 1, 64, 4, 16
    ks = jax.random.split(jax.random.PRNGKey(23), 4)
    q, k, v, cot = (
        _rand(key, (b, s, heads, d)).astype(jnp.bfloat16)
        for key, heads in zip(ks, (h, g, g, h))
    )

    def flash(q, k, v):
        return flash_attention(
            q, k, v, causal=True, window=window, block_q=blocks[0],
            block_k=blocks[1], interpret=True, streaming=streaming,
        )

    def dense(q, k, v):
        return full_attention(q, k, v, causal=True, window=window)

    def loss(fn):
        return lambda q, k, v: jnp.sum(
            fn(q, k, v).astype(jnp.float32) * cot.astype(jnp.float32)
        )

    out = flash(q, k, v)
    assert out.dtype == jnp.bfloat16
    _close_in_bf16(out, dense(q, k, v))
    gf = jax.grad(loss(flash), argnums=(0, 1, 2))(q, k, v)
    gr = jax.grad(loss(dense), argnums=(0, 1, 2))(q, k, v)
    for a, b_ in zip(gf, gr):
        assert a.dtype == jnp.bfloat16
        _close_in_bf16(a, b_)


def _kernel_dots(jaxpr):
    """``(lhs dtype, rhs dtype, result dtype)`` of every ``dot_general``
    inside the ``pallas_call`` kernels of a jaxpr, by kernel name."""
    from torchgpipe_tpu.analysis.jaxpr import subjaxprs

    found = {}

    def walk(jp, kernel):
        for eqn in jp.eqns:
            if eqn.primitive.name == "dot_general" and kernel is not None:
                found.setdefault(kernel, []).append((
                    *(x.aval.dtype for x in eqn.invars),
                    eqn.outvars[0].aval.dtype,
                ))
            inner = kernel
            if eqn.primitive.name == "pallas_call":
                inner = eqn.params["name"]
            for sub in subjaxprs(eqn):
                walk(sub, inner)

    walk(jaxpr.jaxpr, None)
    return found


@pytest.mark.parametrize("streaming", [False, True],
                         ids=["resident", "streaming"])
@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32],
                         ids=["bf16", "f32"])
def test_kernel_products_take_the_tiles_as_stored(dtype, streaming):
    """The cast must not come back unnoticed: every ``dot_general`` of
    the three kernels of a bf16 call has bf16 operands and an f32
    result (one pass of the MXU; a product of f32 tiles makes six), and
    a float32 call still multiplies float32 tiles."""
    b, s, h, g, d = 1, 64, 4, 2, 16
    q = jnp.zeros((b, s, h, d), dtype)
    k = jnp.zeros((b, s, g, d), dtype)

    def loss(q, k, v):
        return jnp.sum(flash_attention(
            q, k, v, causal=True, window=24, block_q=16, block_k=32,
            interpret=True, streaming=streaming,
        ).astype(jnp.float32))

    dots = _kernel_dots(
        jax.make_jaxpr(jax.grad(loss, argnums=(0, 1, 2)))(q, k, k)
    )
    tail = "_stream" if streaming else ""
    assert {name: len(v) for name, v in dots.items()} == {
        "flash_fwd" + tail: 2,       # Q K^T, P V
        "flash_bwd_dq" + tail: 3,    # Q K^T, dO V^T, dS K
        "flash_bwd_dkv" + tail: 4,   # Q K^T, P^T dO, dO V^T, dS^T Q
    }
    want = (jnp.dtype(dtype), jnp.dtype(dtype), jnp.dtype(jnp.float32))
    for name, products in dots.items():
        assert all(p == want for p in products), (name, products)


# --------------------------------------------------------------------- #
# decode kernel                                                          #
# --------------------------------------------------------------------- #


@pytest.mark.parametrize("g,pos0,window", [
    (1, 0, None),        # first generated token, empty-prefix edge
    (1, 7, None),        # short live prefix inside block 0
    (4, 100, None),      # speculative-verify chunk mid-cache
    (1, 510, None),      # live prefix ends at the cache's last block
    (4, 200, 64),        # banded chunk
    (1, 300, 32),        # window smaller than a block
    (1, 300, 1000),      # window larger than the prefix (no-op band)
])
@pytest.mark.parametrize("r", [1, 4])
def test_decode_kernel_matches_dense_oracle(g, pos0, window, r):
    """flash_decode_attention == the dense _attend_chunk einsum on the
    live prefix, with DEAD cache rows randomized (the kernel's
    length-bounded loop must never read them)."""
    from torchgpipe_tpu.models.generation import _attend_chunk
    from torchgpipe_tpu.ops.flash_attention import flash_decode_attention

    b, S, nkv, hd = 2, 512, 2, 128
    nh = nkv * r
    ks = jax.random.split(jax.random.PRNGKey(pos0 + g + r), 3)
    q = jax.random.normal(ks[0], (b, g, nh, hd), jnp.float32)
    ck = jax.random.normal(ks[1], (b, S, nkv, hd), jnp.float32)
    cv = jax.random.normal(ks[2], (b, S, nkv, hd), jnp.float32)
    ref = _attend_chunk(q, ck, cv, jnp.int32(pos0), window, use_flash=False)
    got = flash_decode_attention(
        q, ck, cv, jnp.int32(pos0), window=window, interpret=True
    )
    np.testing.assert_allclose(
        np.asarray(got), np.asarray(ref), rtol=2e-5, atol=2e-5
    )


def test_decode_kernel_under_jit_with_traced_length():
    """The cache length is a TRACED scalar inside generate's scan — one
    compiled kernel must serve every step."""
    from torchgpipe_tpu.models.generation import _attend_chunk
    from torchgpipe_tpu.ops.flash_attention import flash_decode_attention

    b, S, nkv, r, hd = 1, 256, 1, 2, 128
    ks = jax.random.split(jax.random.PRNGKey(0), 3)
    q = jax.random.normal(ks[0], (b, 1, nkv * r, hd), jnp.float32)
    ck = jax.random.normal(ks[1], (b, S, nkv, hd), jnp.float32)
    cv = jax.random.normal(ks[2], (b, S, nkv, hd), jnp.float32)

    fn = jax.jit(
        lambda p: flash_decode_attention(q, ck, cv, p, interpret=True)
    )
    for pos0 in (0, 3, 200, 255):
        ref = _attend_chunk(
            q, ck, cv, jnp.int32(pos0), None, use_flash=False
        )
        np.testing.assert_allclose(
            np.asarray(fn(jnp.int32(pos0))), np.asarray(ref),
            rtol=2e-5, atol=2e-5,
        )


@pytest.mark.slow  # fast-gate budget (VERDICT r5 #6): covered by the CI full job
def test_decode_flash_wiring_through_generate(monkeypatch):
    """Forcing the decode kernel through the full generate() scan (greedy,
    trained-free tiny model) reproduces the dense decode token-for-token."""
    import functools

    from torchgpipe_tpu.layers import sequential_init
    from torchgpipe_tpu.models import generation
    from torchgpipe_tpu.models.generation import generate
    from torchgpipe_tpu.models.transformer import TransformerConfig, llama

    cfg = TransformerConfig(
        vocab=64, dim=256, n_layers=2, n_heads=2, n_kv_heads=1
    )  # head_dim 128: kernel-eligible
    layers = llama(cfg)
    b, s = 2, 4
    spec = jax.ShapeDtypeStruct((b, s), jnp.int32)
    params, _, _ = sequential_init(layers, jax.random.PRNGKey(0), spec)
    tokens = jnp.mod(jnp.arange(b * s).reshape(b, s), cfg.vocab)

    dense = generate(cfg, params, tokens, max_new_tokens=6, max_len=256)
    orig = generation._attend_chunk
    monkeypatch.setattr(
        generation, "_attend_chunk",
        functools.partial(orig, use_flash=True),
    )
    flash = generate(cfg, params, tokens, max_new_tokens=6, max_len=256)
    np.testing.assert_array_equal(np.asarray(flash), np.asarray(dense))


def test_supports_decode_gate():
    from torchgpipe_tpu.ops.flash_attention import supports_decode

    ok = ((2, 1, 4, 128), (2, 512, 2, 128))
    assert supports_decode(*ok, None)
    assert supports_decode(*ok, 64)
    assert not supports_decode((2, 1, 4, 64), (2, 512, 2, 64), None)  # hd
    assert not supports_decode((2, 1, 3, 128), (2, 512, 2, 128), None)  # gqa
    assert not supports_decode((2, 1, 4, 128), (2, 96, 2, 128), None)  # short
    assert not supports_decode(
        (2, 1, 4, 128), (2, 500, 2, 128), None
    )  # no block divisor
    assert supports_decode(
        (2, 1, 4, 128), (2, 65536, 2, 128), None
    )  # K/V stream block-wise: no cache-length VMEM cap


@pytest.mark.parametrize("g,pos0,window", [
    (1, 100, None), (4, 200, 64), (1, 511, None),
])
def test_decode_kernel_quant_matches_dense_dequant(g, pos0, window):
    """int8 cache + scales through the kernel (block-wise VMEM dequant)
    == dequantize-then-dense — the QuantKVCache attend contract."""
    from torchgpipe_tpu.models.generation import _attend_chunk
    from torchgpipe_tpu.models.kv_cache import _quant_rows
    from torchgpipe_tpu.ops.flash_attention import flash_decode_attention

    b, S, nkv, r, hd = 2, 512, 2, 2, 128
    nh = nkv * r
    ks = jax.random.split(jax.random.PRNGKey(pos0 + g), 3)
    q = jax.random.normal(ks[0], (b, g, nh, hd), jnp.float32)
    kf = jax.random.normal(ks[1], (b, S, nkv, hd), jnp.float32)
    vf = jax.random.normal(ks[2], (b, S, nkv, hd), jnp.float32)
    ck, cks = _quant_rows(kf)
    cv, cvs = _quant_rows(vf)
    # QuantKVCache stores scales positions-last ([b, nkv, L]).
    cks = jnp.transpose(cks, (0, 2, 1))
    cvs = jnp.transpose(cvs, (0, 2, 1))
    ref = _attend_chunk(
        q, ck, cv, jnp.int32(pos0), window,
        use_flash=False, k_scale=cks, v_scale=cvs,
    )
    got = flash_decode_attention(
        q, ck, cv, jnp.int32(pos0), window=window,
        k_scale=cks, v_scale=cvs, interpret=True,
    )
    np.testing.assert_allclose(
        np.asarray(got), np.asarray(ref), rtol=2e-5, atol=2e-5
    )


def test_decode_flash_quant_wiring_through_generate(monkeypatch):
    """kv_quant decode through generate() with the kernel forced equals
    the dense quant path token-for-token."""
    import functools

    from torchgpipe_tpu.layers import sequential_init
    from torchgpipe_tpu.models import generation
    from torchgpipe_tpu.models.generation import generate
    from torchgpipe_tpu.models.transformer import TransformerConfig, llama

    cfg = TransformerConfig(
        vocab=64, dim=256, n_layers=2, n_heads=2, n_kv_heads=1
    )
    layers = llama(cfg)
    b, s = 2, 4
    spec = jax.ShapeDtypeStruct((b, s), jnp.int32)
    params, _, _ = sequential_init(layers, jax.random.PRNGKey(0), spec)
    tokens = jnp.mod(jnp.arange(b * s).reshape(b, s), cfg.vocab)

    dense = generate(
        cfg, params, tokens, max_new_tokens=6, max_len=256, kv_quant=True
    )
    orig = generation._attend_chunk
    monkeypatch.setattr(
        generation, "_attend_chunk",
        functools.partial(orig, use_flash=True),
    )
    flash = generate(
        cfg, params, tokens, max_new_tokens=6, max_len=256, kv_quant=True
    )
    np.testing.assert_array_equal(np.asarray(flash), np.asarray(dense))


# --------------------------------------------------------------------- #
# decode kernel, a length (and a slot) per row                          #
# --------------------------------------------------------------------- #

_ROW_L, _ROW_HD, _ROW_NKV, _ROW_R = 1024, 128, 4, 2   # two 512-blocks


def _row_frontiers(g):
    """First-query positions of the rows of one call: a row whose
    ``pos0 + g`` is 0 (nothing to read: None), g (``pos0`` 0), 511, 512,
    513, ``max_len - g + 1`` and ``max_len``."""
    lengths = [0, g, 511, 512, 513, _ROW_L - g + 1, _ROW_L]
    return [None if n == 0 else max(n - g, 0) for n in lengths]


@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32],
                         ids=["bf16", "f32"])
@pytest.mark.parametrize("slots_kind", ["pool", "perm", "subset", "padded"])
@pytest.mark.parametrize("window", [None, 300])
@pytest.mark.parametrize("g", [1, 32])
def test_decode_kernel_per_row(g, window, slots_kind, dtype):
    """``flash_decode_attention`` with a ``[b]`` ``pos0`` (and ``slots``)
    == the dense ``_attend_chunk`` with the same ``[b]`` ``pos0`` over
    the rows' slots, at frontiers on both sides of a block edge and at
    the cache's end; a row of length 0 returns zeros, and the other
    rows come out bit-equal to a call that leaves it out.  ``g`` = 32
    takes the heads of a 32-bit word a product (two bf16 heads, one f32
    head), ``g`` = 1 all of them."""
    from torchgpipe_tpu.models.generation import _attend_chunk
    from torchgpipe_tpu.ops.flash_attention import (
        _decode_tiling, flash_decode_attention,
    )

    nh = _ROW_NKV * _ROW_R
    per_word = 4 // jnp.dtype(dtype).itemsize
    assert _decode_tiling(g, nh, _ROW_NKV, 4 // per_word, _ROW_L) == (
        512, _ROW_NKV if g == 1 else per_word
    )
    frontiers = _row_frontiers(g)
    b = len(frontiers)
    dead = frontiers.index(None)
    banks = {"pool": b, "perm": b, "subset": 12, "padded": 12}[slots_kind]
    rng = np.random.default_rng(g + banks)
    slots = {
        "pool": np.arange(b),
        "perm": rng.permutation(b),
        "subset": rng.choice(banks, b, replace=False),
        "padded": rng.choice(banks, b, replace=False),
    }[slots_kind].astype(np.int32)
    if slots_kind == "padded":
        slots[dead] = slots[2]      # the padded row repeats a live slot
    ks = jax.random.split(jax.random.PRNGKey(g + banks), 3)
    q = jax.random.normal(ks[0], (b, g, nh, _ROW_HD), jnp.float32)
    q = q.astype(dtype)
    shape = (banks, _ROW_L, _ROW_NKV, _ROW_HD)
    ck = jax.random.normal(ks[1], shape, jnp.float32).astype(dtype)
    cv = jax.random.normal(ks[2], shape, jnp.float32).astype(dtype)
    pos0 = np.array([p or 0 for p in frontiers], np.int32)
    lengths = np.minimum(pos0 + g, _ROW_L).astype(np.int32)
    lengths[dead] = 0

    def kernel(rows):
        return np.asarray(flash_decode_attention(
            q[rows], ck, cv, jnp.asarray(pos0[rows]), window=window,
            slots=None if slots_kind == "pool" and len(rows) == b
            else jnp.asarray(slots[rows]),
            lengths=jnp.asarray(lengths[rows]), interpret=True,
        ))

    every = np.arange(b)
    live = every[every != dead]
    got = kernel(every)
    ref = np.asarray(_attend_chunk(
        q, ck[slots], cv[slots], jnp.asarray(pos0), window, use_flash=False
    ))
    np.testing.assert_allclose(got[live], ref[live], rtol=2e-5, atol=2e-5)
    assert not got[dead].any()
    np.testing.assert_array_equal(kernel(live), got[live])


def test_decode_rows_read_counts_the_blocks_of_live_rows():
    """The host's count of what the kernel fetches: block-rounded rows
    from the band's first block to the length's last, nothing for a row
    of length 0."""
    from torchgpipe_tpu.ops.flash_attention import decode_rows_read

    pos0 = np.array([0, 0, 510, 511, 1023, 700])
    lengths = np.array([0, 1, 511, 512, 1024, 733])
    assert decode_rows_read(pos0, lengths, None, 512) == 512 * (
        0 + 1 + 1 + 1 + 2 + 2
    )
    # A window of 100 drops the blocks behind the first query's band.
    assert decode_rows_read(pos0, lengths, 100, 512) == 512 * (
        0 + 1 + 1 + 1 + 1 + 1
    )
    # The host's count is the kernel's own plan (the grid steps of the
    # rows that read anything), frontier by frontier.
    from torchgpipe_tpu.ops.flash_attention import _decode_plan

    rng = np.random.default_rng(0)
    for window in (None, 1, 100, 700, 4096):
        pos0 = rng.integers(0, 4096, 64)
        lengths = np.where(rng.random(64) < 0.2, 0,
                           np.minimum(pos0 + rng.integers(1, 33), 4096))
        _, _, count = _decode_plan(
            jnp.asarray(pos0), jnp.asarray(lengths), window, 512
        )
        assert decode_rows_read(pos0, lengths, window, 512) == 512 * int(
            np.asarray(count)[lengths > 0].sum()
        )


def test_decode_tiling_by_shape():
    """Block and heads a product, from the shapes alone (Mistral's
    heads): one token a row takes every head of a 512-block, a chunk
    of 32 the two bf16 heads of a word, a chunk of 128 a shorter block
    besides; an f32 cache one head a product; nothing fits 1024."""
    from torchgpipe_tpu.ops.flash_attention import (
        _decode_tiling, supports_decode,
    )

    assert _decode_tiling(1, 32, 8, 2, 4096) == (512, 8)
    assert _decode_tiling(32, 32, 8, 2, 4096) == (512, 2)
    assert _decode_tiling(128, 32, 8, 2, 4096) == (256, 2)
    assert _decode_tiling(32, 32, 8, 4, 4096) == (512, 1)
    assert _decode_tiling(1024, 32, 8, 2, 4096) is None
    assert not supports_decode((8, 1024, 32, 128), (8, 4096, 8, 128), None)
    assert _decode_tiling(1, 32, 8, 2, 384) == (128, 8)


# --------------------------------------------------------------------- #
# decode kernel over a latent cache                                      #
# --------------------------------------------------------------------- #

_LAT_L, _LAT_H, _LAT_C, _LAT_R, _LAT_N = 1024, 8, 128, 16, 16


def _latent_model(dtype):
    """A latent-attention config at the kernel's toy widths and a
    float32 ``W_kvb`` (the CPU has no batched bf16 product; the banks
    and the kernel's operands keep ``dtype``)."""
    from torchgpipe_tpu.models.transformer import (
        MLAConfig, TransformerConfig,
    )

    cfg = TransformerConfig(
        dim=64, n_layers=1, n_heads=_LAT_H, dtype=dtype,
        mla=MLAConfig(q_lora_rank=8, kv_lora_rank=_LAT_C,
                      qk_nope_head_dim=_LAT_N, qk_rope_head_dim=_LAT_R,
                      v_head_dim=_LAT_N),
    )
    w = jax.random.normal(
        jax.random.PRNGKey(3), (_LAT_C, _LAT_H * 2 * _LAT_N), jnp.float32
    )
    return cfg, {"wkv_b": w / np.sqrt(_LAT_C)}


def _latent_rows(g, block_k, slots_kind):
    """Rows of one call: frontiers on and off a block edge and at the
    cache's end, with a row that reads nothing BETWEEN live rows; their
    slots, the pool's own or a subset of 12 banks in which the dead row
    and one live row repeat another live row's slot."""
    lengths = np.array(
        [g, block_k - 1, 0, block_k, block_k + 1, _LAT_L - g + 1, _LAT_L]
    )
    pos0 = np.maximum(lengths - g, 0).astype(np.int32)
    b = len(lengths)
    if slots_kind == "pool":
        return pos0, lengths.astype(np.int32), np.arange(b, dtype=np.int32), b
    slots = np.random.default_rng(g + block_k).choice(12, b, replace=False)
    slots[2] = slots[1]      # the dead row repeats a live row's slot
    slots[4] = slots[3]      # and so does a live one
    return pos0, lengths.astype(np.int32), slots.astype(np.int32), 12


@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32],
                         ids=["bf16", "f32"])
@pytest.mark.parametrize("slots_kind", ["pool", "repeated"])
@pytest.mark.parametrize("block_k", [128, 256, 512])
@pytest.mark.parametrize("g", [1, 32])
def test_latent_decode_kernel_matches_mla_attend(g, block_k, slots_kind,
                                                 dtype):
    """``latent_decode_attention`` between ``mla.attend``'s own absorbed
    einsums == ``mla.attend`` (absorbed) over the rows' slots of the
    same banks, for one token a row and a chunk of 32 (whose 256 query
    rows a cache row share one fetch of the tile), per-row ``pos0``,
    frontiers on both sides of a block edge and at the cache's end, at
    every block size.  A row of length 0 returns zeros, and the live
    rows come out bit-equal to a call that leaves it out.  bf16 banks
    against the oracle on the same values in float32: the kernel rounds
    ``q_lat`` and ``p`` to the banks' type, as ``mla.attend`` does on a
    bf16 cache."""
    from torchgpipe_tpu.models import mla
    from torchgpipe_tpu.ops.flash_attention import latent_decode_attention

    cfg, p = _latent_model(dtype)
    pos0, lengths, slots, banks = _latent_rows(g, block_k, slots_kind)
    b = len(pos0)
    ks = jax.random.split(jax.random.PRNGKey(g + block_k), 4)
    q_nope = jax.random.normal(ks[0], (b, g, _LAT_H, _LAT_N), jnp.float32)
    q_pe = jax.random.normal(ks[1], (b, g, _LAT_H, _LAT_R), jnp.float32)
    q_pe = q_pe.astype(dtype)
    ckv = jax.random.normal(ks[2], (banks, _LAT_L, _LAT_C), jnp.float32)
    kpe = jax.random.normal(ks[3], (banks, _LAT_L, _LAT_R), jnp.float32)
    ckv, kpe = ckv.astype(dtype), kpe.astype(dtype)

    wk, wv = mla.absorbed_halves(cfg, p)
    q_lat = mla.absorb_queries(q_nope, wk, dtype)

    def kernel(rows):
        return latent_decode_attention(
            q_lat[rows], q_pe[rows], ckv, kpe, jnp.asarray(pos0[rows]),
            sm_scale=mla.score_scale(cfg.mla),
            slots=None if slots_kind == "pool" and len(rows) == b
            else jnp.asarray(slots[rows]),
            lengths=jnp.asarray(lengths[rows]), block_k=block_k,
            interpret=True,
        )

    f32 = jnp.float32
    ref = np.asarray(mla.attend(
        cfg, p, q_nope, q_pe.astype(f32), ckv[slots].astype(f32),
        kpe[slots].astype(f32), jnp.asarray(pos0), absorbed=True,
    ))
    every = np.arange(b)
    live = every[lengths > 0]
    o_lat = kernel(every)
    assert o_lat.dtype == dtype
    got = np.asarray(mla.expand_output(o_lat, wv))
    if dtype == jnp.float32:
        np.testing.assert_allclose(got[live], ref[live], rtol=2e-5, atol=2e-5)
    else:
        _close_in_bf16(got[live], ref[live])
    o_lat = np.asarray(o_lat, np.float32)
    assert not o_lat[lengths == 0].any()
    np.testing.assert_array_equal(
        np.asarray(kernel(live), np.float32), o_lat[live]
    )


@pytest.mark.parametrize("block_k", [128, 256, 512])
def test_latent_decode_grid_visits_the_rows_the_host_counts(block_k):
    """``decode_rows_read`` (no window) == the blocks of the latent
    kernel's grid, frontier by frontier: a live row takes the blocks up
    to its length, and the one step of a row that reads nothing names
    the tile already resident (the last live row's slot and last
    block), so nothing is fetched for it."""
    from torchgpipe_tpu.ops.flash_attention import (
        _decode_steps, decode_rows_read,
    )

    rng = np.random.default_rng(block_k)
    b, nkb = 24, 4096 // block_k
    pos0 = rng.integers(0, 4096 - 32, b)
    lengths = np.where(rng.random(b) < 0.25, 0, pos0 + rng.integers(1, 33, b))
    lengths[0], lengths[5:7] = 700, 0
    slots = rng.permutation(64)[:b]
    row, slot, blk, ends = (np.asarray(a) for a in _decode_steps(
        jnp.asarray(pos0), jnp.asarray(lengths), jnp.asarray(slots), None,
        block_k, b * nkb,
    ))
    n = int(ends[-1])
    live = lengths[row[:n]] > 0
    assert decode_rows_read(pos0, lengths, None, block_k) == (
        block_k * int(live.sum())
    )
    for i in range(b):
        steps = np.flatnonzero(row[:n] == i)
        if lengths[i] > 0:
            blocks = -(-lengths[i] // block_k)
            assert blk[steps].tolist() == list(range(blocks))
            assert (slot[steps] == slots[i]).all()
        else:
            (t,) = steps
            assert (slot[t], blk[t]) == (slot[t - 1], blk[t - 1])


def test_latent_tiling_by_shape():
    """Block and query rows a product from the shapes alone (A.X-K1's
    heads): one token a row puts its 64 heads through one product of a
    512-block, a chunk of 32 its 2,048 rows 512 at a time; a chunk
    whose accumulator does not stay resident, and a length no block
    divides, have no tiling."""
    from torchgpipe_tpu.ops.flash_attention import (
        _latent_tiling, latent_decode_attention,
    )

    assert _latent_tiling(64, 512, 4096) == (512, 64)
    assert _latent_tiling(32 * 64, 512, 4096) == (512, 512)
    assert _latent_tiling(6 * 128, 512, 4096) == (512, 256)
    assert _latent_tiling(5 * 128, 512, 384) == (128, 128)
    assert _latent_tiling(128 * 64, 512, 4096) is None
    assert _latent_tiling(64, 512, 1000) is None
    with pytest.raises(ValueError, match="no latent decode tiling"):
        latent_decode_attention(
            jnp.zeros((1, 1, 8, 128)), jnp.zeros((1, 1, 8, 16)),
            jnp.zeros((1, 1000, 128)), jnp.zeros((1, 1000, 16)),
            jnp.zeros((1,), jnp.int32), sm_scale=1.0, interpret=True,
        )
