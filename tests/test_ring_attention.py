"""Ring attention & sequence parallelism tests: exactness vs the dense
oracle, gradient parity, and composition with the SPMD pipeline (new
TPU-native capability — SURVEY.md §5 notes the reference has none)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from torchgpipe_tpu.spmd import _shard_map as shard_map
from torchgpipe_tpu.parallel import full_attention, ring_attention
from torchgpipe_tpu.spmd import SpmdGPipe, make_mesh
from torchgpipe_tpu.models.transformer import (
    TransformerConfig,
    cross_entropy,
    llama_spmd,
)

SP = 4


def _qkv(key, b=2, s=32, h=4, d=8, dtype=jnp.float32):
    kq, kk, kv = jax.random.split(key, 3)
    q = jax.random.normal(kq, (b, s, h, d), dtype)
    k = jax.random.normal(kk, (b, s, h, d), dtype)
    v = jax.random.normal(kv, (b, s, h, d), dtype)
    return q, k, v


def _ring_mesh():
    return Mesh(np.array(jax.devices()[:SP]), ("sp",))


def _run_ring(q, k, v, causal):
    mesh = _ring_mesh()
    shard = NamedSharding(mesh, P(None, "sp"))

    def local(q, k, v):
        return ring_attention(q, k, v, "sp", causal=causal)

    fn = jax.jit(
        shard_map(
            local,
            mesh=mesh,
            in_specs=(P(None, "sp"),) * 3,
            out_specs=P(None, "sp"),
        )
    )
    return fn(
        jax.device_put(q, shard), jax.device_put(k, shard), jax.device_put(v, shard)
    )


@pytest.mark.parametrize("causal", [True, False])
def test_ring_attention_matches_dense(causal):
    q, k, v = _qkv(jax.random.PRNGKey(0))
    ref = full_attention(q, k, v, causal=causal)
    out = _run_ring(q, k, v, causal)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=2e-5, atol=2e-5)


def test_ring_attention_grads_match_dense():
    q, k, v = _qkv(jax.random.PRNGKey(1))
    mesh = _ring_mesh()
    cot = jax.random.normal(jax.random.PRNGKey(2), q.shape)

    def dense_loss(q, k, v):
        return jnp.sum(full_attention(q, k, v, causal=True) * cot)

    def ring_loss(q, k, v):
        local = shard_map(
            lambda a, b, c: ring_attention(a, b, c, "sp", causal=True),
            mesh=mesh,
            in_specs=(P(None, "sp"),) * 3,
            out_specs=P(None, "sp"),
        )
        return jnp.sum(local(q, k, v) * cot)

    ref_g = jax.grad(dense_loss, argnums=(0, 1, 2))(q, k, v)
    got_g = jax.jit(jax.grad(ring_loss, argnums=(0, 1, 2)))(q, k, v)
    for a, b in zip(got_g, ref_g):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=5e-5, atol=5e-5)


def test_ring_attention_gqa_matches_repeated_dense():
    """K/V at n_kv heads ride the ring; grouping at the compute site must
    equal the repeat-heads construction."""
    key = jax.random.PRNGKey(9)
    kq, kk, kv = jax.random.split(key, 3)
    b, s, h, g, d = 2, 32, 4, 2, 8
    q = jax.random.normal(kq, (b, s, h, d))
    k = jax.random.normal(kk, (b, s, g, d))
    v = jax.random.normal(kv, (b, s, g, d))
    rep = h // g
    ref = full_attention(
        q, jnp.repeat(k, rep, axis=2), jnp.repeat(v, rep, axis=2), causal=True
    )
    got_dense = full_attention(q, k, v, causal=True)
    np.testing.assert_allclose(np.asarray(got_dense), np.asarray(ref), rtol=2e-5, atol=2e-5)
    out = _run_ring(q, k, v, True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=2e-5, atol=2e-5)


def test_spmd_rejects_sp_axis_mismatch():
    pp = 2
    mesh = make_mesh(pp, dp=1, sp=2)
    cfg = TransformerConfig(vocab=64, dim=32, n_layers=pp, n_heads=4)  # no sp
    block, pre, post = llama_spmd(cfg, pp)
    with pytest.raises(ValueError, match="declare sp_axis"):
        SpmdGPipe(
            block, pp, mesh, chunks=2, loss_fn=cross_entropy,
            pre=pre, post=post, sp_axis="sp",
        )


def test_spmd_sp_rejects_indivisible_target():
    pp = 2
    mesh = make_mesh(pp, dp=1, sp=2)
    pipe = _spmd_llama("sp", mesh, pp)
    tokens = jnp.zeros((4, 16), jnp.int32)
    params = pipe.init(
        jax.random.PRNGKey(0), jax.ShapeDtypeStruct((4, 16), jnp.int32)
    )
    with pytest.raises(ValueError, match="target leaf shape"):
        pipe.train_step(params, tokens, jnp.zeros((4, 15), jnp.int32))


def test_ring_attention_uneven_heads_and_long_seq():
    # More shards than heads, longer sequence; still exact.
    q, k, v = _qkv(jax.random.PRNGKey(3), b=1, s=64, h=2, d=4)
    ref = full_attention(q, k, v, causal=True)
    out = _run_ring(q, k, v, True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=2e-5, atol=2e-5)


# --------------------------------------------------------------------------- #
# sp inside the SPMD pipeline                                                 #
# --------------------------------------------------------------------------- #


def _spmd_llama(sp_axis, mesh, pp, chunks=2):
    cfg = TransformerConfig(
        vocab=64, dim=32, n_layers=pp, n_heads=4, n_kv_heads=2,
        sp_axis=sp_axis,
    )
    block, pre, post = llama_spmd(cfg, pp)
    return SpmdGPipe(
        block, pp, mesh, chunks=chunks, loss_fn=cross_entropy,
        pre=pre, post=post, checkpoint="always",
        dp_axis=None, sp_axis=sp_axis,
    )


@pytest.mark.slow  # fast-gate budget (VERDICT r5 #6): covered by the CI full job
def test_spmd_pipeline_with_sequence_parallelism_matches_pp_only():
    """pp=2 x sp=2 must compute the same loss/grads as pp=2 alone — the
    sequence axis is a pure parallelization, not a model change."""
    pp = 2
    rng = jax.random.PRNGKey(0)
    tokens = jax.random.randint(jax.random.PRNGKey(1), (4, 16), 0, 64)
    labels = jax.random.randint(jax.random.PRNGKey(2), (4, 16), 0, 64)
    in_spec = jax.ShapeDtypeStruct(tokens.shape, tokens.dtype)

    mesh_pp = Mesh(np.array(jax.devices()[:pp]).reshape(pp, 1), ("pp", "dp"))
    ref_pipe = _spmd_llama(None, mesh_pp, pp)
    ref_params = ref_pipe.init(rng, in_spec)
    ref_loss, ref_grads = ref_pipe.train_step(ref_params, tokens, labels)

    mesh_sp = make_mesh(pp, dp=1, sp=2)
    sp_pipe = _spmd_llama("sp", mesh_sp, pp)
    sp_params = sp_pipe.init(rng, in_spec)
    sp_loss, sp_grads = sp_pipe.train_step(sp_params, tokens, labels)

    np.testing.assert_allclose(float(sp_loss), float(ref_loss), rtol=1e-5)
    for a, b in zip(
        jax.tree_util.tree_leaves(sp_grads), jax.tree_util.tree_leaves(ref_grads)
    ):
        np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), rtol=1e-4, atol=1e-5
        )


def test_spmd_sp_rejects_indivisible_sequence():
    pp = 2
    mesh = make_mesh(pp, dp=1, sp=2)
    pipe = _spmd_llama("sp", mesh, pp)
    tokens = jnp.zeros((4, 15), jnp.int32)
    params = pipe.init(jax.random.PRNGKey(0), jax.ShapeDtypeStruct((4, 16), jnp.int32))
    with pytest.raises(ValueError, match="sequence parallelism shards"):
        pipe.train_step(params, tokens, tokens)


def test_spmd_sp_requires_decomposable_loss():
    pp = 2
    mesh = make_mesh(pp, dp=1, sp=2)
    cfg = TransformerConfig(vocab=64, dim=32, n_layers=pp, n_heads=4, sp_axis="sp")
    block, pre, post = llama_spmd(cfg, pp)
    with pytest.raises(ValueError, match="decomposable"):
        SpmdGPipe(
            block, pp, mesh, chunks=2, loss_fn=cross_entropy,
            pre=pre, post=post, sp_axis="sp", loss_reduction=None,
        )


@pytest.mark.parametrize("causal", [True, False])
@pytest.mark.parametrize("kv_block", [4, 3])  # 3 does not divide shard 8:
# the divisor search falls to sub=2 instead of silently skipping sub-blocking
def test_ring_attention_blockwise_substeps_exact(causal, kv_block):
    """kv_block_size smaller than the shard engages the nested blockwise
    recurrence — still exact vs the dense oracle, grads included."""
    q, k, v = _qkv(jax.random.PRNGKey(21))  # s=32, SP=4 -> shard 8
    mesh = _ring_mesh()
    cot = jax.random.normal(jax.random.PRNGKey(22), q.shape)

    def ring_loss(q, k, v):
        local = shard_map(
            lambda a, b, c: ring_attention(
                a, b, c, "sp", causal=causal, kv_block_size=kv_block
            ),
            mesh=mesh,
            in_specs=(P(None, "sp"),) * 3,
            out_specs=P(None, "sp"),
        )
        return jnp.sum(local(q, k, v) * cot)

    def dense_loss(q, k, v):
        return jnp.sum(full_attention(q, k, v, causal=causal) * cot)

    lv, gv = jax.jit(jax.value_and_grad(ring_loss, argnums=(0, 1, 2)))(q, k, v)
    lr, gr = jax.value_and_grad(dense_loss, argnums=(0, 1, 2))(q, k, v)
    np.testing.assert_allclose(float(lv), float(lr), rtol=1e-5)
    for a, b in zip(gv, gr):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=5e-5, atol=5e-5)


def test_spmd_sp_inference_matches_oracle():
    """Pipelined inference with sequence parallelism (pp2 x sp2): apply()
    returns full-sequence logits equal to the dense single-device forward."""
    pp, sp = 2, 2
    cfg = TransformerConfig(
        vocab=64, dim=32, n_layers=pp, n_heads=4, n_kv_heads=2, sp_axis="sp"
    )
    block, pre, post = llama_spmd(cfg, pp)
    mesh = make_mesh(pp, dp=1, sp=sp)
    pipe = SpmdGPipe(
        block, pp, mesh, chunks=2, loss_fn=cross_entropy,
        pre=pre, post=post, sp_axis="sp",
    )
    tokens = jax.random.randint(jax.random.PRNGKey(3), (4, 32), 0, cfg.vocab)
    params = pipe.init(
        jax.random.PRNGKey(0), jax.ShapeDtypeStruct(tokens.shape, tokens.dtype)
    )
    out = pipe.apply(params, tokens)

    cfg_d = TransformerConfig(
        vocab=64, dim=32, n_layers=pp, n_heads=4, n_kv_heads=2
    )
    block_d, pre_d, post_d = llama_spmd(cfg_d, pp)
    dev0 = jax.devices()[0]
    p0 = jax.device_put(params, dev0)
    h, _ = pre_d.apply(p0["pre"], (), jax.device_put(tokens, dev0), train=False)
    for j in range(pp):
        pj = jax.tree_util.tree_map(lambda a: a[j], p0["blocks"])
        h, _ = block_d.apply(pj, (), h, train=False)
    ref, _ = post_d.apply(p0["post"], (), h, train=False)
    np.testing.assert_allclose(
        np.asarray(out), np.asarray(ref), rtol=2e-4, atol=2e-4
    )
