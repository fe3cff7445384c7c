"""Tensor parallelism: Megatron-style head/hidden sharding over a tp mesh
axis (new TPU-native capability — SURVEY.md §2.2 lists TP as ABSENT in the
reference).

Oracle discipline: a tp-sharded pipeline run must produce the same loss and
gradients as (a) the unsharded SPMD run and (b) the sequential single-device
model — weight sharding is an execution detail, never a math change.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax import lax
from jax.sharding import Mesh, PartitionSpec as P

from torchgpipe_tpu.spmd import _shard_map as shard_map
from torchgpipe_tpu.models.transformer import (
    TransformerConfig,
    cross_entropy,
    llama_spmd,
    vocab_parallel_cross_entropy,
)
from torchgpipe_tpu.parallel.tensor import psum_grad
from torchgpipe_tpu.spmd import SpmdGPipe, make_mesh


def _cfg(tp_axis=None, n_layers=2):
    return TransformerConfig(
        vocab=64,
        dim=32,
        n_layers=n_layers,
        n_heads=4,
        n_kv_heads=2,
        tp_axis=tp_axis,
    )


def _data(batch=4, seq=8, vocab=64):
    k1, k2 = jax.random.split(jax.random.PRNGKey(7))
    tokens = jax.random.randint(k1, (batch, seq), 0, vocab)
    labels = jax.random.randint(k2, (batch, seq), 0, vocab)
    return tokens, labels


def _seq_oracle(cfg, pp, params, tokens, labels):
    """Sequential single-device run of the same stacked params."""
    block, pre, post = llama_spmd(cfg, pp)
    dev0 = jax.devices()[0]
    params = jax.device_put(params, dev0)
    tokens, labels = jax.device_put((tokens, labels), dev0)

    def loss_of(p):
        h, _ = pre.apply(p["pre"], (), tokens, rng=None, train=True)
        for j in range(pp):
            pj = jax.tree_util.tree_map(lambda a: a[j], p["blocks"])
            h, _ = block.apply(pj, (), h, rng=None, train=True)
        h, _ = post.apply(p["post"], (), h, rng=None, train=True)
        return cross_entropy(h, labels)

    return jax.value_and_grad(loss_of)(params)


def _assert_trees_close(a, b, rtol=1e-4, atol=1e-5):
    jax.tree_util.tree_map(
        lambda x, y: np.testing.assert_allclose(
            np.asarray(x), np.asarray(y), rtol=rtol, atol=atol
        ),
        a,
        b,
    )


def test_psum_grad_sums_cotangent(cpu_devices):
    """Identity forward; backward psums over the axis: each lane's partial
    cotangent is reassembled into the full gradient."""
    mesh = Mesh(np.array(cpu_devices[:4]), ("tp",))

    def local(x):
        lane = lax.axis_index("tp").astype(x.dtype)

        def f(x):
            y = psum_grad(x, "tp")
            # Each lane contributes lane-dependent scaling; the psum'd
            # input cotangent must be sum_lane (lane+1) = 1+2+3+4 = 10.
            return jnp.sum(y * (lane + 1.0))

        val, g = jax.value_and_grad(f)(x)
        return lax.psum(val, "tp"), g

    x = jnp.ones((4, 2))
    fn = jax.jit(
        shard_map(
            local, mesh=mesh, in_specs=P(), out_specs=(P(), P())
        )
    )
    _, g = fn(x)
    np.testing.assert_allclose(np.asarray(g), 10.0 * np.ones((4, 2)))


@pytest.mark.slow
def test_spmd_tp_transparency(cpu_devices):
    """pp=2 x tp=2 sharded run == unsharded pp=2 run == sequential oracle,
    for loss and every gradient leaf."""
    pp, tp = 2, 2
    tokens, labels = _data()

    # tp-sharded engine.
    cfg_tp = _cfg(tp_axis="tp")
    block, pre, post = llama_spmd(cfg_tp, pp)
    mesh = make_mesh(pp, dp=1, tp=tp, devices=cpu_devices[: pp * tp])
    pipe = SpmdGPipe(
        block, pp, mesh, chunks=2, loss_fn=cross_entropy,
        pre=pre, post=post, tp_axis="tp",
    )
    in_spec = jax.ShapeDtypeStruct(tokens.shape, tokens.dtype)
    params = pipe.init(jax.random.PRNGKey(0), in_spec)
    loss, grads = pipe.train_step(params, tokens, labels)

    # Unsharded engine, same params (tp_axis changes no init math).
    cfg_ref = _cfg(tp_axis=None)
    block_r, pre_r, post_r = llama_spmd(cfg_ref, pp)
    mesh_r = make_mesh(pp, dp=1, devices=cpu_devices[:pp])
    pipe_r = SpmdGPipe(
        block_r, pp, mesh_r, chunks=2, loss_fn=cross_entropy,
        pre=pre_r, post=post_r,
    )
    params_r = pipe_r.init(jax.random.PRNGKey(0), in_spec)
    _assert_trees_close(params, params_r)
    loss_r, grads_r = pipe_r.train_step(params_r, tokens, labels)

    np.testing.assert_allclose(float(loss), float(loss_r), rtol=1e-5)
    _assert_trees_close(grads, grads_r)

    # Sequential oracle.
    ref_loss, ref_grads = _seq_oracle(cfg_ref, pp, params_r, tokens, labels)
    np.testing.assert_allclose(float(loss), float(ref_loss), rtol=1e-5)
    _assert_trees_close(grads, ref_grads)


@pytest.mark.slow
def test_spmd_tp_with_dp(cpu_devices):
    """tp composes with dp: pp=2 x dp=2 x tp=2 on 8 devices."""
    pp, dp, tp = 2, 2, 2
    tokens, labels = _data(batch=8)
    cfg = _cfg(tp_axis="tp")
    block, pre, post = llama_spmd(cfg, pp)
    mesh = make_mesh(pp, dp=dp, tp=tp, devices=cpu_devices)
    pipe = SpmdGPipe(
        block, pp, mesh, chunks=2, loss_fn=cross_entropy,
        pre=pre, post=post, dp_axis="dp", tp_axis="tp",
    )
    in_spec = jax.ShapeDtypeStruct(tokens.shape, tokens.dtype)
    params = pipe.init(jax.random.PRNGKey(0), in_spec)
    loss, grads = pipe.train_step(params, tokens, labels)

    ref_loss, ref_grads = _seq_oracle(_cfg(), pp, params, tokens, labels)
    np.testing.assert_allclose(float(loss), float(ref_loss), rtol=1e-5)
    _assert_trees_close(grads, ref_grads)


def test_spmd_tp_sharded_logits_loss(cpu_devices):
    """gather_logits=False keeps logits vocab-sharded through the loss;
    vocab_parallel_cross_entropy must reproduce the full-logits run exactly
    (loss and all grads) — Megatron's parallel cross-entropy."""
    pp, tp = 2, 2
    tokens, labels = _data()
    cfg = _cfg(tp_axis="tp")
    mesh = make_mesh(pp, dp=1, tp=tp, devices=cpu_devices[: pp * tp])
    in_spec = jax.ShapeDtypeStruct(tokens.shape, tokens.dtype)

    runs = {}
    for gather in (True, False):
        block, pre, post = llama_spmd(cfg, pp, gather_logits=gather)
        loss_fn = (
            cross_entropy if gather else vocab_parallel_cross_entropy("tp")
        )
        pipe = SpmdGPipe(
            block, pp, mesh, chunks=2, loss_fn=loss_fn,
            pre=pre, post=post, tp_axis="tp",
        )
        params = pipe.init(jax.random.PRNGKey(0), in_spec)
        runs[gather] = pipe.train_step(params, tokens, labels)

    loss_g, grads_g = runs[True]
    loss_s, grads_s = runs[False]
    np.testing.assert_allclose(float(loss_s), float(loss_g), rtol=1e-5)
    _assert_trees_close(grads_s, grads_g)


def test_spmd_tp_sharded_head_inference_gathers(cpu_devices):
    """apply() on a gather_logits=False model returns FULL logits (the
    engine gathers the declared output sharding) — never one lane's shard."""
    pp, tp = 2, 2
    tokens, _ = _data()
    cfg = _cfg(tp_axis="tp")
    mesh = make_mesh(pp, dp=1, tp=tp, devices=cpu_devices[: pp * tp])
    in_spec = jax.ShapeDtypeStruct(tokens.shape, tokens.dtype)

    outs = {}
    for gather in (True, False):
        block, pre, post = llama_spmd(cfg, pp, gather_logits=gather)
        pipe = SpmdGPipe(
            block, pp, mesh, chunks=2,
            loss_fn=cross_entropy if gather else vocab_parallel_cross_entropy("tp"),
            pre=pre, post=post, tp_axis="tp",
        )
        params = pipe.init(jax.random.PRNGKey(0), in_spec)
        outs[gather] = pipe.apply(params, tokens)

    assert outs[False].shape == (*tokens.shape, cfg.vocab)
    np.testing.assert_allclose(
        np.asarray(outs[False]), np.asarray(outs[True]), rtol=1e-5, atol=1e-6
    )


def test_vocab_parallel_ce_outside_mesh_is_plain_ce():
    logits = jax.random.normal(jax.random.PRNGKey(0), (2, 4, 16))
    labels = jax.random.randint(jax.random.PRNGKey(1), (2, 4), 0, 16)
    a = vocab_parallel_cross_entropy("tp")(logits, labels)
    b = cross_entropy(logits, labels)
    np.testing.assert_allclose(float(a), float(b), rtol=1e-6)


@pytest.mark.slow  # fast-gate budget (VERDICT r5 #6): covered by the CI full job
def test_spmd_tp_with_sp(cpu_devices):
    """tp composes with sequence parallelism: pp=2 x sp=2 x tp=2 — ring
    attention runs over sp with tp-local head shards."""
    pp, sp, tp = 2, 2, 2
    tokens, labels = _data(batch=4, seq=8)
    cfg = TransformerConfig(
        vocab=64, dim=32, n_layers=2, n_heads=4, n_kv_heads=2,
        tp_axis="tp", sp_axis="sp",
    )
    block, pre, post = llama_spmd(cfg, pp)
    mesh = make_mesh(pp, dp=1, sp=sp, tp=tp, devices=cpu_devices)
    pipe = SpmdGPipe(
        block, pp, mesh, chunks=2, loss_fn=cross_entropy,
        pre=pre, post=post, sp_axis="sp", tp_axis="tp",
    )
    in_spec = jax.ShapeDtypeStruct(tokens.shape, tokens.dtype)
    params = pipe.init(jax.random.PRNGKey(0), in_spec)
    loss, grads = pipe.train_step(params, tokens, labels)

    ref_loss, ref_grads = _seq_oracle(_cfg(), pp, params, tokens, labels)
    np.testing.assert_allclose(float(loss), float(ref_loss), rtol=1e-5)
    _assert_trees_close(grads, ref_grads, rtol=2e-4, atol=2e-5)


def test_spmd_tp_param_placement(cpu_devices):
    """Attention/MLP weight leaves are physically sharded over tp; norm
    scales replicated."""
    pp, tp = 2, 2
    cfg = _cfg(tp_axis="tp")
    block, pre, post = llama_spmd(cfg, pp)
    mesh = make_mesh(pp, dp=1, tp=tp, devices=cpu_devices[: pp * tp])
    pipe = SpmdGPipe(
        block, pp, mesh, chunks=2, loss_fn=cross_entropy,
        pre=pre, post=post, tp_axis="tp",
    )
    params = pipe.init(
        jax.random.PRNGKey(0), jax.ShapeDtypeStruct((4, 8), jnp.int32)
    )
    def axes_of(spec):
        out = set()
        for ax in spec:
            if ax is None:
                continue
            out.update(ax if isinstance(ax, tuple) else (ax,))
        return out

    # chain params: tuple of per-sublayer dicts.
    stage0 = params["blocks"][0]
    assert "tp" in axes_of(stage0["wq"].sharding.spec)
    assert "tp" in axes_of(stage0["w_down"].sharding.spec)
    assert "tp" not in axes_of(stage0["ln1"].sharding.spec)


def test_spmd_rejects_tp_axis_mismatch(cpu_devices):
    pp = 2
    mesh = make_mesh(pp, dp=1, tp=2, devices=cpu_devices[:4])
    cfg = _cfg(tp_axis=None)  # model not tp-aware
    block, pre, post = llama_spmd(cfg, pp)
    with pytest.raises(ValueError, match="declare tp_axis"):
        SpmdGPipe(
            block, pp, mesh, chunks=2, loss_fn=cross_entropy,
            pre=pre, post=post, tp_axis="tp",
        )


def test_spmd_tp_rejects_indivisible_heads(cpu_devices):
    """kv_heads=2 cannot shard over tp=4 — didactic error at engine
    construction (flat-dim divisibility alone would split a head)."""
    pp, tp = 2, 4
    cfg = _cfg(tp_axis="tp")  # n_kv_heads=2
    block, pre, post = llama_spmd(cfg, pp)
    mesh = make_mesh(pp, dp=1, tp=tp, devices=cpu_devices)
    with pytest.raises(ValueError, match="kv_heads.*not divisible"):
        SpmdGPipe(
            block, pp, mesh, chunks=2, loss_fn=cross_entropy,
            pre=pre, post=post, tp_axis="tp",
        )


def test_vocab_parallel_ce_extreme_logits_stable(cpu_devices):
    """The tp-collective log-sum-exp must stay finite and shift-invariant
    under large-magnitude logits (the pmax shift doing its job)."""
    mesh = Mesh(np.array(cpu_devices[:4]), ("tp",))
    V = 32
    logits = jax.random.normal(jax.random.PRNGKey(0), (2, 4, V)) * 3.0
    labels = jax.random.randint(jax.random.PRNGKey(1), (2, 4), 0, V)
    loss_fn = vocab_parallel_cross_entropy("tp")

    def run(shift):
        local = shard_map(
            lambda lg, lb: loss_fn(lg, lb),
            mesh=mesh,
            in_specs=(P(None, None, "tp"), P()),
            out_specs=P(),
        )
        return float(jax.jit(local)(logits + shift, labels))

    base = run(0.0)
    big = run(5e4)
    want = float(cross_entropy(logits, labels))
    np.testing.assert_allclose(base, want, rtol=1e-5)
    assert np.isfinite(big)
    # f32 representation of (logits + 5e4) quantizes at ~3e-3 per entry —
    # the comparison tolerance reflects the input encoding, not the CE.
    np.testing.assert_allclose(big, want, rtol=1e-3)


def test_eval_loss_with_vocab_parallel_ce(cpu_devices):
    """eval_loss's mapped per-micro-batch loss path under tp-sharded
    logits: the head keeps lane-local vocab shards (gather_logits=False)
    and vocab_parallel_cross_entropy assembles the full-vocab softmax with
    tp collectives INSIDE the eval program — must equal the train loss."""
    from torchgpipe_tpu.models.transformer import (
        TransformerConfig,
        llama_spmd,
        vocab_parallel_cross_entropy,
    )
    from torchgpipe_tpu.spmd import SpmdGPipe, make_mesh

    pp, tp, m = 2, 2, 2
    cfg = TransformerConfig(
        vocab=64, dim=32, n_layers=pp, n_heads=4, n_kv_heads=2, tp_axis="tp"
    )
    block, pre, post = llama_spmd(cfg, pp, gather_logits=False)
    mesh = make_mesh(pp, 1, tp=tp, devices=cpu_devices[: pp * tp])
    pipe = SpmdGPipe(
        block, pp, mesh, chunks=m,
        loss_fn=vocab_parallel_cross_entropy("tp"),
        pre=pre, post=post, tp_axis="tp",
    )
    tokens = jnp.mod(jnp.arange(4 * 8).reshape(4, 8), 64).astype(jnp.int32)
    labels = jnp.mod(tokens + 1, 64)
    params = pipe.init(
        jax.random.PRNGKey(0), jax.ShapeDtypeStruct(tokens.shape, tokens.dtype)
    )
    l_train, _ = pipe.train_step(params, tokens, labels)
    l_eval = pipe.eval_loss(params, tokens, labels)
    assert abs(float(l_train) - float(l_eval)) < 1e-5


def test_spmd_tp_classic_arch_transparency(cpu_devices):
    """The classic (GPT-2-class) architecture knobs — LayerNorm with
    biases, learned positions, biased projections, non-gated MLP — ride
    tp like the Llama layout: pp=2 x tp=2 loss/grads == the sequential
    oracle (validates the new param_specs: b_fc shards with hidden,
    bo/b_proj/ln biases replicate and add post-psum)."""
    pp, tp = 2, 2
    tokens, labels = _data(seq=8)
    cfg = TransformerConfig(
        vocab=64, dim=32, n_layers=2, n_heads=4, n_kv_heads=4,
        norm="layernorm", pos_emb="learned", max_pos=16,
        mlp_impl="classic", act="gelu_tanh",
        attn_bias=True, attn_out_bias=True, tp_axis="tp",
    )
    block, pre, post = llama_spmd(cfg, pp)
    mesh = make_mesh(pp, dp=1, tp=tp, devices=cpu_devices[: pp * tp])
    pipe = SpmdGPipe(
        block, pp, mesh, chunks=2, loss_fn=cross_entropy,
        pre=pre, post=post, tp_axis="tp",
    )
    in_spec = jax.ShapeDtypeStruct(tokens.shape, tokens.dtype)
    params = pipe.init(jax.random.PRNGKey(0), in_spec)
    # Biases init to zero; perturb them so the oracle can catch a
    # dropped/missharded bias, not just a missing weight.
    params = jax.tree_util.tree_map(
        lambda a: a + 0.01 * jnp.arange(a.size, dtype=a.dtype).reshape(a.shape)
        if a.ndim == 1 else a,
        params,
    )
    loss, grads = pipe.train_step(params, tokens, labels)

    import dataclasses
    cfg_ref = dataclasses.replace(cfg, tp_axis=None)
    ref_loss, ref_grads = _seq_oracle(cfg_ref, pp, params, tokens, labels)
    np.testing.assert_allclose(float(loss), float(ref_loss), rtol=1e-5)
    _assert_trees_close(grads, ref_grads)
