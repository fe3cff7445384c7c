"""Ulysses sequence parallelism (all_to_all head/sequence swap): exactness
vs the dense oracle, gradient parity, GQA head-pairing under the contiguous
split, engine composition, and the head-divisibility validation.  New
TPU-native capability — SURVEY.md §2.2 lists Ulysses as absent from the
reference."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from torchgpipe_tpu.spmd import _shard_map as shard_map
from torchgpipe_tpu.models.transformer import (
    TransformerConfig,
    cross_entropy,
    llama_spmd,
)
from torchgpipe_tpu.parallel import full_attention
from torchgpipe_tpu.parallel.ulysses import ulysses_attention
from torchgpipe_tpu.spmd import SpmdGPipe, make_mesh

SP = 4


def _qkv(key, b=2, s=32, h=4, g=4, d=8):
    kq, kk, kv = jax.random.split(key, 3)
    q = jax.random.normal(kq, (b, s, h, d))
    k = jax.random.normal(kk, (b, s, g, d))
    v = jax.random.normal(kv, (b, s, g, d))
    return q, k, v


def _mesh():
    return Mesh(np.array(jax.devices()[:SP]), ("sp",))


def _run_ulysses(q, k, v, causal):
    mesh = _mesh()
    shard = NamedSharding(mesh, P(None, "sp"))
    fn = jax.jit(
        shard_map(
            lambda a, b, c: ulysses_attention(a, b, c, "sp", causal=causal),
            mesh=mesh,
            in_specs=(P(None, "sp"),) * 3,
            out_specs=P(None, "sp"),
        )
    )
    return fn(
        jax.device_put(q, shard),
        jax.device_put(k, shard),
        jax.device_put(v, shard),
    )


@pytest.mark.parametrize("causal", [True, False])
def test_ulysses_matches_dense(causal):
    q, k, v = _qkv(jax.random.PRNGKey(0))
    ref = full_attention(q, k, v, causal=causal)
    out = _run_ulysses(q, k, v, causal)
    np.testing.assert_allclose(
        np.asarray(out), np.asarray(ref), rtol=2e-5, atol=2e-5
    )


def test_ulysses_gqa_head_pairing():
    """h=8 query heads over g=4 kv heads with sp=4: each lane computes 2 q
    heads against exactly its 1 kv head — the contiguous all_to_all split
    must preserve the global i -> i // (h/g) pairing."""
    q, k, v = _qkv(jax.random.PRNGKey(3), h=8, g=4)
    ref = full_attention(q, k, v, causal=True)
    out = _run_ulysses(q, k, v, True)
    np.testing.assert_allclose(
        np.asarray(out), np.asarray(ref), rtol=2e-5, atol=2e-5
    )


@pytest.mark.slow  # fast-gate budget (VERDICT r5 #6): covered by the CI full job
def test_ulysses_grads_match_dense():
    q, k, v = _qkv(jax.random.PRNGKey(1))
    mesh = _mesh()
    cot = jax.random.normal(jax.random.PRNGKey(2), q.shape)

    def dense_loss(q, k, v):
        return jnp.sum(full_attention(q, k, v, causal=True) * cot)

    def uly_loss(q, k, v):
        local = shard_map(
            lambda a, b, c: ulysses_attention(a, b, c, "sp", causal=True),
            mesh=mesh,
            in_specs=(P(None, "sp"),) * 3,
            out_specs=P(None, "sp"),
        )
        return jnp.sum(local(q, k, v) * cot)

    gd = jax.grad(dense_loss, argnums=(0, 1, 2))(q, k, v)
    gu = jax.grad(uly_loss, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(gd, gu):
        np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), rtol=2e-5, atol=2e-5
        )


@pytest.mark.slow  # fast-gate budget (VERDICT r5 #6): covered by the CI full job
def test_spmd_engine_with_ulysses_matches_ring(cpu_devices):
    """The full pipelined training step with sp_impl='ulysses' must produce
    the same loss/gradients as sp_impl='ring' (both are exact, so they
    agree with each other through the whole engine stack)."""
    pp, sp, m = 2, 2, 2
    mesh = make_mesh(pp, 1, sp, devices=cpu_devices[:4])
    tokens = jnp.arange(4 * 16, dtype=jnp.int32).reshape(4, 16) % 64
    labels = (tokens + 1) % 64
    res = {}
    for impl in ("ring", "ulysses"):
        cfg = TransformerConfig(
            vocab=64, dim=32, n_layers=pp, n_heads=4, n_kv_heads=2,
            sp_axis="sp", sp_impl=impl,
        )
        block, pre, post = llama_spmd(cfg, pp)
        eng = SpmdGPipe(
            block, pp, mesh, chunks=m, loss_fn=cross_entropy,
            pre=pre, post=post, sp_axis="sp",
        )
        params = eng.init(
            jax.random.PRNGKey(0),
            jax.ShapeDtypeStruct(tokens.shape, tokens.dtype),
        )
        res[impl] = eng.train_step(
            params, tokens, labels, jax.random.PRNGKey(1)
        )
    lr, gr = res["ring"]
    lu, gu = res["ulysses"]
    assert abs(float(lr) - float(lu)) < 1e-5
    for a, b in zip(
        jax.tree_util.tree_leaves(gr), jax.tree_util.tree_leaves(gu)
    ):
        np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), rtol=1e-4, atol=1e-5
        )


def test_ulysses_head_divisibility_validated_at_engine_init(cpu_devices):
    """kv_heads=2 with sp=4 cannot shard heads: the engine's mesh
    validation must reject it eagerly with the didactic error, not fail
    inside shard_map."""
    pp, sp = 2, 4
    mesh = make_mesh(pp, 1, sp, devices=cpu_devices[:8])
    cfg = TransformerConfig(
        vocab=64, dim=32, n_layers=pp, n_heads=4, n_kv_heads=2,
        sp_axis="sp", sp_impl="ulysses",
    )
    block, pre, post = llama_spmd(cfg, pp)
    with pytest.raises(ValueError, match="ulysses.*shards attention heads"):
        SpmdGPipe(
            block, pp, mesh, chunks=2, loss_fn=cross_entropy,
            pre=pre, post=post, sp_axis="sp",
        )


def test_ulysses_rejects_bad_impl():
    from torchgpipe_tpu.parallel.ring_attention import attention

    q, k, v = _qkv(jax.random.PRNGKey(4))
    with pytest.raises(ValueError, match="'ring' or 'ulysses'"):
        attention(q, k, v, impl="flash")


def test_ulysses_sliding_window_matches_dense():
    """window composes with Ulysses: the local full-sequence compute
    windows exactly (the ring path rejects window — also asserted)."""
    from torchgpipe_tpu.parallel.ring_attention import attention

    q, k, v = _qkv(jax.random.PRNGKey(5))
    ref = full_attention(q, k, v, causal=True, window=12)
    mesh = _mesh()
    shard = NamedSharding(mesh, P(None, "sp"))
    fn = jax.jit(
        shard_map(
            lambda a, b, c: ulysses_attention(
                a, b, c, "sp", causal=True, window=12
            ),
            mesh=mesh,
            in_specs=(P(None, "sp"),) * 3,
            out_specs=P(None, "sp"),
        )
    )
    out = fn(jax.device_put(q, shard), jax.device_put(k, shard),
             jax.device_put(v, shard))
    np.testing.assert_allclose(
        np.asarray(out), np.asarray(ref), rtol=2e-5, atol=2e-5
    )

    # Ring + window is rejected with the didactic pointer to ulysses.
    def ring_windowed(a, b, c):
        return attention(a, b, c, axis_name="sp", causal=True, window=12)

    with pytest.raises(ValueError, match="ulysses"):
        jax.jit(
            shard_map(
                ring_windowed, mesh=mesh,
                in_specs=(P(None, "sp"),) * 3,
                out_specs=P(None, "sp"),
            )
        )(q, k, v)


def test_window_ring_rejected_eagerly_at_engine_init(cpu_devices):
    """attn_window + sp_impl='ring' + bound sp axis is statically invalid:
    the engine's mesh validation rejects it at init (clean error), not
    inside shard_map tracing."""
    pp, sp = 2, 2
    mesh = make_mesh(pp, 1, sp, devices=cpu_devices[:4])
    cfg = TransformerConfig(
        vocab=64, dim=32, n_layers=pp, n_heads=4, n_kv_heads=2,
        sp_axis="sp", sp_impl="ring", attn_window=8,
    )
    block, pre, post = llama_spmd(cfg, pp)
    with pytest.raises(ValueError, match="attn_window does not compose"):
        SpmdGPipe(
            block, pp, mesh, chunks=2, loss_fn=cross_entropy,
            pre=pre, post=post, sp_axis="sp",
        )


def test_window_zero_rejected_everywhere():
    from torchgpipe_tpu.parallel.ring_attention import attention

    q, k, v = _qkv(jax.random.PRNGKey(6))
    with pytest.raises(ValueError, match=">= 1"):
        full_attention(q, k, v, causal=True, window=0)
    with pytest.raises(ValueError, match=">= 1"):
        attention(q, k, v, causal=True, window=0)
