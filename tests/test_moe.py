"""Mixture-of-experts + expert parallelism (new TPU-native capability —
SURVEY.md §2.2 lists EP/MoE as ABSENT in the reference).

Oracle discipline: the dense-dispatch einsum formulation must equal a
per-token loop over the selected experts; the ep-sharded pipeline run must
equal the unsharded run and the sequential single-device model.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from torchgpipe_tpu.models.moe import (
    MoEConfig,
    llama_moe,
    llama_moe_spmd,
    moe_mlp,
    router_stats,
)
from torchgpipe_tpu.models.transformer import (
    TransformerConfig,
    cross_entropy,
)
from torchgpipe_tpu.spmd import SpmdGPipe, make_mesh


def _cfg(**kw):
    return TransformerConfig(
        vocab=64, dim=16, n_layers=2, n_heads=2, n_kv_heads=2, **kw
    )


def _assert_trees_close(a, b, rtol=1e-4, atol=1e-5):
    jax.tree_util.tree_map(
        lambda x, y: np.testing.assert_allclose(
            np.asarray(x), np.asarray(y), rtol=rtol, atol=atol
        ),
        a,
        b,
    )


def test_moe_mlp_matches_per_token_loop():
    """Dense dispatch einsums == explicit per-token top-k expert loop (no
    capacity pressure)."""
    cfg = _cfg()
    moe = MoEConfig(n_experts=4, top_k=2, capacity_factor=8.0)  # no drops
    layer = moe_mlp(cfg, moe)
    b, s = 2, 4
    x = jax.random.normal(jax.random.PRNGKey(1), (b, s, cfg.dim))
    params, _ = layer.init(
        jax.random.PRNGKey(0), jax.ShapeDtypeStruct(x.shape, x.dtype)
    )
    y, _ = layer.apply(params, (), x)

    def expert_ffn(e, v):
        h = jax.nn.silu(v @ params["w_gate"][e]) * (v @ params["w_up"][e])
        return h @ params["w_down"][e]

    xf = np.asarray(x.reshape(-1, cfg.dim))
    probs = np.asarray(
        jax.nn.softmax(x.reshape(-1, cfg.dim) @ params["router"], -1)
    )
    want = np.zeros_like(xf)
    for t in range(xf.shape[0]):
        order = np.argsort(-probs[t])[: moe.top_k]
        denom = probs[t][order].sum() + 1e-9
        for e in order:
            want[t] += (
                probs[t][e] / denom
            ) * np.asarray(expert_ffn(int(e), jnp.asarray(xf[t])))
    np.testing.assert_allclose(
        np.asarray(y).reshape(-1, cfg.dim), want, rtol=1e-4, atol=1e-5
    )


@pytest.mark.parametrize("top_k", [1, 2])
@pytest.mark.slow  # fast-gate budget (VERDICT r5 #6): covered by the CI full job
def test_sparse_dispatch_matches_dense(top_k):
    """The sort-based scatter/gather dispatch must equal the dense one-hot
    einsum dispatch bit-for-bit in outputs AND gradients — including under
    capacity pressure, where FCFS drop order is what differs if the slot
    assignment is wrong."""
    cfg = _cfg()
    x = jax.random.normal(jax.random.PRNGKey(3), (2, 16, cfg.dim))

    def run(dispatch):
        moe = MoEConfig(n_experts=4, top_k=top_k, capacity_factor=0.5,
                        dispatch=dispatch)  # tight capacity: real drops
        layer = moe_mlp(cfg, moe)
        params, _ = layer.init(
            jax.random.PRNGKey(0), jax.ShapeDtypeStruct(x.shape, x.dtype)
        )

        def loss(p):
            y, _ = layer.apply(p, (), x)
            return jnp.sum(y**2)

        val, grads = jax.value_and_grad(loss)(params)
        return val, grads

    dense_val, dense_grads = run("dense")
    sparse_val, sparse_grads = run("sparse")
    np.testing.assert_allclose(
        float(dense_val), float(sparse_val), rtol=1e-6
    )
    _assert_trees_close(sparse_grads, dense_grads, rtol=1e-5, atol=1e-6)


@pytest.mark.parametrize("top_k", [1, 2])
def test_dropless_matches_capacity_paths_when_nothing_drops(top_k):
    """dispatch='dropless' (ragged_dot grouped matmuls) must equal the
    dense one-hot path in outputs AND gradients whenever capacity is
    generous enough that the capacity paths drop nothing — identical
    routing, identical gate normalization, different matmul plumbing."""
    cfg = _cfg()
    x = jax.random.normal(jax.random.PRNGKey(5), (2, 16, cfg.dim))

    def run(dispatch, capacity_factor):
        moe = MoEConfig(n_experts=4, top_k=top_k,
                        capacity_factor=capacity_factor, dispatch=dispatch)
        layer = moe_mlp(cfg, moe)
        params, _ = layer.init(
            jax.random.PRNGKey(0), jax.ShapeDtypeStruct(x.shape, x.dtype)
        )

        def loss(p):
            y, _ = layer.apply(p, (), x)
            return jnp.sum(y**2)

        return jax.value_and_grad(loss)(params)

    dense_val, dense_grads = run("dense", 8.0)  # no drops at this factor
    drop_val, drop_grads = run("dropless", 8.0)
    np.testing.assert_allclose(float(dense_val), float(drop_val), rtol=1e-5)
    _assert_trees_close(drop_grads, dense_grads, rtol=1e-4, atol=1e-5)


def test_dropless_never_drops_under_imbalance():
    """Where the capacity paths drop overflowing tokens, dropless must
    process every assignment: with a router biased hard toward one expert
    and a tight capacity factor, the two outputs must DIFFER, and the
    dropless output must match a generous-capacity dense run (the
    no-drop semantics)."""
    cfg = _cfg()
    moe_kw = dict(n_experts=4, top_k=1)
    x = jax.random.normal(jax.random.PRNGKey(7), (2, 16, cfg.dim))

    def run(dispatch, capacity_factor, params=None):
        moe = MoEConfig(capacity_factor=capacity_factor, dispatch=dispatch,
                        **moe_kw)
        layer = moe_mlp(cfg, moe)
        if params is None:
            params, _ = layer.init(
                jax.random.PRNGKey(0), jax.ShapeDtypeStruct(x.shape, x.dtype)
            )
        # Bias the router so nearly all tokens pick expert 0 — guaranteed
        # overflow at capacity_factor < 1.
        params = dict(params)
        params["router"] = params["router"].at[:, 0].add(10.0)
        y, _ = layer.apply(params, (), x)
        return y

    y_dropless = run("dropless", 0.25)
    y_tight = run("sparse", 0.25)
    y_oracle = run("dense", 8.0)
    np.testing.assert_allclose(
        np.asarray(y_dropless), np.asarray(y_oracle), rtol=1e-4, atol=1e-5
    )
    assert np.max(np.abs(np.asarray(y_tight) - np.asarray(y_oracle))) > 1e-3


def test_expert_choice_matches_per_expert_loop():
    """router='expert_choice' == an explicit numpy loop where each expert
    gathers its top-capacity tokens by router score and scatter-adds its
    gated FFN output back (Zhou et al. arXiv:2202.09368 formulation)."""
    cfg = _cfg()
    moe = MoEConfig(n_experts=4, capacity_factor=2.0, router="expert_choice")
    layer = moe_mlp(cfg, moe)
    b, s = 2, 8
    x = jax.random.normal(jax.random.PRNGKey(9), (b, s, cfg.dim))
    params, _ = layer.init(
        jax.random.PRNGKey(0), jax.ShapeDtypeStruct(x.shape, x.dtype)
    )
    y, _ = layer.apply(params, (), x)

    t = b * s
    E = moe.n_experts
    capacity = int(np.ceil(moe.capacity_factor * t / E))
    xf = np.asarray(x.reshape(t, cfg.dim))
    probs = np.asarray(
        jax.nn.softmax(x.reshape(t, cfg.dim) @ params["router"], -1)
    )
    want = np.zeros_like(xf)
    for e in range(E):
        picked = np.argsort(-probs[:, e], kind="stable")[:capacity]
        for tok in picked:
            v = jnp.asarray(xf[tok])
            h = jax.nn.silu(v @ params["w_gate"][e]) * (v @ params["w_up"][e])
            want[tok] += probs[tok, e] * np.asarray(h @ params["w_down"][e])
    np.testing.assert_allclose(
        np.asarray(y).reshape(t, cfg.dim), want, rtol=1e-4, atol=1e-5
    )


def test_expert_choice_router_receives_gradient():
    """The router weights must receive gradient through the EC gates."""
    cfg = _cfg()
    moe = MoEConfig(n_experts=4, capacity_factor=2.0, router="expert_choice")
    layer = moe_mlp(cfg, moe)
    x = jax.random.normal(jax.random.PRNGKey(2), (2, 8, cfg.dim))
    params, _ = layer.init(
        jax.random.PRNGKey(0), jax.ShapeDtypeStruct(x.shape, x.dtype)
    )

    def loss(p):
        y, _ = layer.apply(p, (), x)
        return jnp.sum(y**2)

    grads = jax.grad(loss)(params)
    assert float(jnp.max(jnp.abs(grads["router"]))) > 0.0


def test_expert_choice_validation():
    cfg = _cfg()
    with pytest.raises(ValueError, match="local experts"):
        moe_mlp(cfg, MoEConfig(n_experts=4, router="expert_choice",
                               ep_axis="ep"))
    with pytest.raises(ValueError, match="balanced by"):
        moe_mlp(cfg, MoEConfig(n_experts=4, router="expert_choice",
                               balance_weight=0.1))
    with pytest.raises(ValueError, match="'topk' or 'expert_choice'"):
        moe_mlp(cfg, MoEConfig(n_experts=4, router="soft"))


def test_dropless_rejects_ep_axis():
    cfg = _cfg()
    moe = MoEConfig(n_experts=4, top_k=2, dispatch="dropless", ep_axis="ep")
    with pytest.raises(ValueError, match="local experts"):
        moe_mlp(cfg, moe)


@pytest.mark.slow  # fast-gate budget (VERDICT r5 #6): covered by the CI full job
def test_sparse_dispatch_matches_dense_under_ep(cpu_devices):
    """Sparse dispatch composed with expert parallelism: the scatter/gather
    buffers feed the same [E, C, d] all_to_all round trip as the dense
    einsums, so a pp x ep pipeline must produce identical loss/grads with
    either dispatch.  (The realistic scales where dispatch='auto' picks
    sparse are exactly the scales where ep is on — this is the composition
    that must not ship untested.)"""
    pp, ep = 2, 2
    cfg = _cfg()

    def run(dispatch):
        moe = MoEConfig(n_experts=4, top_k=2, capacity_factor=8.0,
                        ep_axis="ep", dispatch=dispatch)
        block, pre, post = llama_moe_spmd(cfg, moe, pp)
        mesh = make_mesh(pp, dp=1, ep=ep, devices=cpu_devices[: pp * ep])
        pipe = SpmdGPipe(
            block, pp, mesh, chunks=2, loss_fn=cross_entropy,
            pre=pre, post=post, ep_axis="ep",
        )
        tokens = jax.random.randint(jax.random.PRNGKey(5), (8, 4), 0, cfg.vocab)
        labels = jax.random.randint(jax.random.PRNGKey(6), (8, 4), 0, cfg.vocab)
        params = pipe.init(
            jax.random.PRNGKey(0),
            jax.ShapeDtypeStruct(tokens.shape, tokens.dtype),
        )
        return pipe.train_step(params, tokens, labels)

    dense_loss, dense_grads = run("dense")
    sparse_loss, sparse_grads = run("sparse")
    np.testing.assert_allclose(float(dense_loss), float(sparse_loss), rtol=1e-6)
    _assert_trees_close(sparse_grads, dense_grads, rtol=1e-5, atol=1e-6)


@pytest.mark.slow
def test_sparse_dispatch_scales_to_realistic_shapes():
    """8k tokens x 64 experts (VERDICT: the dense [t, E, C] tensors would be
    ~670MB there).  The auto policy must pick the sparse path, the step must
    run fwd+bwd, and no single intermediate array may come anywhere near the
    dense dispatch tensor's size."""
    cfg = TransformerConfig(
        vocab=64, dim=64, n_layers=1, n_heads=2, n_kv_heads=2, mlp_ratio=2.0
    )
    moe = MoEConfig(n_experts=64, top_k=2, capacity_factor=1.25)  # auto
    layer = moe_mlp(cfg, moe)
    b, s = 8, 1024  # t = 8192
    t, E = b * s, moe.n_experts
    capacity = int(np.ceil(moe.capacity_factor * moe.top_k * t / E))
    x = jax.random.normal(jax.random.PRNGKey(1), (b, s, cfg.dim))
    params, _ = layer.init(
        jax.random.PRNGKey(0), jax.ShapeDtypeStruct(x.shape, x.dtype)
    )

    def loss(p):
        y, _ = layer.apply(p, (), x)
        return jnp.sum(y**2)

    # Bound every intermediate in the traced program: nothing within an
    # order of magnitude of the dense [t, E, C] tensor.
    from tests.jaxpr_utils import max_eqn_output_bytes

    dense_bytes = t * E * capacity * 4
    jaxpr = jax.make_jaxpr(jax.value_and_grad(loss))(params)
    biggest = max_eqn_output_bytes(jaxpr.jaxpr)
    assert biggest < dense_bytes / 10, (biggest, dense_bytes)

    val, grads = jax.value_and_grad(loss)(params)
    assert np.isfinite(float(val))
    assert all(
        np.isfinite(np.asarray(g)).all()
        for g in jax.tree_util.tree_leaves(grads)
    )


def test_moe_capacity_drops_tokens():
    """E=1, C=1: only the first token gets a slot; every later token falls
    back to the residual (zero MLP output)."""
    cfg = _cfg()
    moe = MoEConfig(n_experts=1, top_k=1, capacity_factor=1e-9)
    layer = moe_mlp(cfg, moe)
    x = jax.random.normal(jax.random.PRNGKey(1), (1, 6, cfg.dim))
    params, _ = layer.init(
        jax.random.PRNGKey(0), jax.ShapeDtypeStruct(x.shape, x.dtype)
    )
    y, _ = layer.apply(params, (), x)
    y = np.asarray(y)[0]
    assert np.abs(y[0]).max() > 0
    np.testing.assert_allclose(y[1:], 0.0, atol=1e-7)


def test_top1_router_receives_gradient():
    """Switch-style k=1 keeps the raw softmax probability as the gate, so
    router logits get real gradient (normalizing over one selection would
    pin the gate to ~1.0 and freeze the router at init)."""
    cfg = _cfg()
    moe = MoEConfig(n_experts=4, top_k=1, capacity_factor=8.0)
    layer = moe_mlp(cfg, moe)
    x = jax.random.normal(jax.random.PRNGKey(2), (2, 8, cfg.dim))
    params, _ = layer.init(
        jax.random.PRNGKey(0), jax.ShapeDtypeStruct(x.shape, x.dtype)
    )

    def loss(p):
        y, _ = layer.apply(p, (), x)
        return jnp.sum(y**2)

    g = jax.grad(loss)(params)
    assert float(jnp.abs(g["router"]).max()) > 1e-3


def test_balance_weight_injects_exact_aux_gradient():
    """Training with balance_weight=w must produce EXACTLY the gradients of
    task_loss + w * balance_penalty (explicitly differentiated oracle) —
    while the loss value stays the task loss."""
    cfg = _cfg()
    w = 0.3
    moe_on = MoEConfig(n_experts=4, top_k=2, capacity_factor=8.0, balance_weight=w)
    moe_off = MoEConfig(n_experts=4, top_k=2, capacity_factor=8.0)
    x = jax.random.normal(jax.random.PRNGKey(4), (2, 8, cfg.dim))
    layer_on = moe_mlp(cfg, moe_on)
    layer_off = moe_mlp(cfg, moe_off)
    params, _ = layer_on.init(
        jax.random.PRNGKey(0), jax.ShapeDtypeStruct(x.shape, x.dtype)
    )

    def task_loss(p, layer):
        y, _ = layer.apply(p, (), x, train=True)
        return jnp.sum(y**2)

    def penalty(p):
        _, _, balance = router_stats(p["router"], x, moe_off)
        return balance

    loss_on = task_loss(params, layer_on)
    loss_off = task_loss(params, layer_off)
    np.testing.assert_allclose(float(loss_on), float(loss_off), rtol=1e-6)

    got = jax.grad(lambda p: task_loss(p, layer_on))(params)
    want = jax.grad(lambda p: task_loss(p, layer_off) + w * penalty(p))(params)
    # The two sides are the same mathematical gradient but different
    # float32 programs: the injection adds w to the aux cotangent inside
    # ONE traced graph, the oracle differentiates task and penalty
    # separately and sums — XLA fuses/accumulates them in different
    # orders (observed: ~1.5e-5 max relative drift on the router grads).
    _assert_trees_close(got, want, rtol=5e-5, atol=1e-6)


def _aux_probe_layer(w):
    """Identity layer injecting aux = its scalar param with weight ``w``.

    d(objective)/d(param) through the engines must equal exactly ``w``:
    each of the m micro-batch cells injects w * aux_scale, and the engine
    sets aux_scale = 1/m — so the result is chunk-count-invariant."""
    from torchgpipe_tpu.layers import Layer
    from torchgpipe_tpu.models.moe import add_aux_grad

    def init(rng, in_spec):
        del rng, in_spec
        return {"p": jnp.zeros(())}, ()

    def apply(params, state, x, *, rng=None, train=True):
        del rng
        if train:
            x = add_aux_grad(x, params["p"], w)
        return x, state

    return Layer(name="aux_probe", init=init, apply=apply)


@pytest.mark.parametrize(
    "batch,chunks,fused",
    [(8, 2, False), (8, 4, False), (8, 4, True), (6, 4, False)],
)
def test_aux_grad_scale_is_chunk_invariant(batch, chunks, fused):
    """The injected auxiliary gradient is weighted 1/m per micro-batch cell,
    so the optimized coefficient does not change with the chunk count, the
    fused vs per-cell path, or a ragged batch (m < chunks)."""
    from torchgpipe_tpu import GPipe
    from torchgpipe_tpu.ops import dense

    w = 0.25
    layers = [dense(8, name="d0"), _aux_probe_layer(w), dense(8, name="d1")]
    model = GPipe(layers, balance=[3], chunks=chunks, fused=fused)
    in_spec = jax.ShapeDtypeStruct((batch, 8), jnp.float32)
    params, state = model.init(jax.random.PRNGKey(0), in_spec)
    x = jax.random.normal(jax.random.PRNGKey(1), (batch, 8))
    tgt = jax.random.normal(jax.random.PRNGKey(2), (batch, 8))

    _, grads, _, _ = model.value_and_grad(
        params, state, x, tgt, lambda o, t: jnp.mean((o - t) ** 2)
    )
    got = float(grads[0][1]["p"])  # stage 0, layer index 1 (probe)
    np.testing.assert_allclose(got, w, rtol=1e-6)


def test_aux_grad_scale_spmd_chunk_invariant(cpu_devices):
    """Same invariance for the SPMD engine: router-style injection through
    the scanned schedule weights the penalty 1/m."""
    from torchgpipe_tpu.layers import chain
    from torchgpipe_tpu.ops import dense
    from torchgpipe_tpu.spmd import SpmdGPipe, make_mesh

    w = 0.25
    grads_p = []
    for chunks in (2, 4):
        block = chain(
            [dense(8, name="fc"), _aux_probe_layer(w)], name="blk"
        )
        mesh = make_mesh(2, 1, devices=cpu_devices[:2])
        pipe = SpmdGPipe(
            block, 2, mesh, chunks=chunks,
            loss_fn=lambda o, t: jnp.mean((o - t) ** 2),
        )
        params = pipe.init(
            jax.random.PRNGKey(0), jax.ShapeDtypeStruct((8, 8), jnp.float32)
        )
        x = jax.random.normal(jax.random.PRNGKey(1), (8, 8))
        tgt = jax.random.normal(jax.random.PRNGKey(2), (8, 8))
        _, grads = pipe.train_step(params, x, tgt)
        # blocks params: tuple(per-sublayer dicts), stacked over 2 stages;
        # each stage's probe injects w/m once per micro-batch => w per
        # stage lane.
        grads_p.append(np.asarray(grads["blocks"][1]["p"]))
    np.testing.assert_allclose(grads_p[0], grads_p[1], rtol=1e-6)
    np.testing.assert_allclose(grads_p[0], w, rtol=1e-6)


def test_aux_grad_exact_under_except_last(cpu_devices):
    """The injected aux coefficient must be identical across checkpoint
    modes — in particular through except_last's peeled tail, where the
    validity scale runs inside the stage-conditional cond branches."""
    from torchgpipe_tpu.layers import chain
    from torchgpipe_tpu.ops import dense
    from torchgpipe_tpu.spmd import SpmdGPipe, make_mesh

    w = 0.25
    grads_by_mode = {}
    for mode in ("always", "except_last", "never"):
        block = chain([dense(8, name="fc"), _aux_probe_layer(w)], name="blk")
        mesh = make_mesh(2, 1, devices=cpu_devices[:2])
        pipe = SpmdGPipe(
            block, 2, mesh, chunks=3,
            loss_fn=lambda o, t: jnp.mean((o - t) ** 2),
            checkpoint=mode,
        )
        params = pipe.init(
            jax.random.PRNGKey(0), jax.ShapeDtypeStruct((4, 8), jnp.float32)
        )
        x = jax.random.normal(jax.random.PRNGKey(1), (12, 8))
        tgt = jax.random.normal(jax.random.PRNGKey(2), (12, 8))
        _, grads = pipe.train_step(params, x, tgt)
        grads_by_mode[mode] = np.asarray(grads["blocks"][1]["p"])
    np.testing.assert_allclose(
        grads_by_mode["except_last"], grads_by_mode["always"], rtol=1e-6
    )
    np.testing.assert_allclose(
        grads_by_mode["never"], grads_by_mode["always"], rtol=1e-6
    )
    np.testing.assert_allclose(grads_by_mode["always"], w, rtol=1e-6)


def test_router_stats_balance():
    cfg = _cfg()
    moe = MoEConfig(n_experts=4, top_k=1)
    layer = moe_mlp(cfg, moe)
    x = jax.random.normal(jax.random.PRNGKey(3), (2, 8, cfg.dim))
    params, _ = layer.init(
        jax.random.PRNGKey(0), jax.ShapeDtypeStruct(x.shape, x.dtype)
    )
    load, imp, balance = router_stats(params["router"], x, moe)
    np.testing.assert_allclose(float(load.sum()), 1.0, rtol=1e-6)
    np.testing.assert_allclose(float(imp.sum()), 1.0, rtol=1e-6)
    assert float(balance) >= 1.0 - 1e-6  # 1.0 iff perfectly balanced


def _moe_seq_oracle(cfg, moe_cfg, pp, params, tokens, labels):
    block, pre, post = llama_moe_spmd(cfg, moe_cfg, pp)
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


@pytest.mark.slow
def test_spmd_moe_ep_transparency(cpu_devices):
    """pp=2 x ep=2 run == unsharded pp=2 run == sequential oracle.

    capacity_factor is set high enough that no token drops in either the
    per-lane (t/ep tokens) or the full-batch capacity computation, so the
    only difference between configs is where experts live.
    """
    pp, ep = 2, 2
    cfg = _cfg()
    moe_ep = MoEConfig(n_experts=4, top_k=2, capacity_factor=8.0, ep_axis="ep")
    moe_ref = MoEConfig(n_experts=4, top_k=2, capacity_factor=8.0)
    k1, k2 = jax.random.split(jax.random.PRNGKey(7))
    tokens = jax.random.randint(k1, (8, 4), 0, cfg.vocab)
    labels = jax.random.randint(k2, (8, 4), 0, cfg.vocab)
    in_spec = jax.ShapeDtypeStruct(tokens.shape, tokens.dtype)

    block, pre, post = llama_moe_spmd(cfg, moe_ep, pp)
    mesh = make_mesh(pp, dp=1, ep=ep, devices=cpu_devices[: pp * ep])
    pipe = SpmdGPipe(
        block, pp, mesh, chunks=2, loss_fn=cross_entropy,
        pre=pre, post=post, ep_axis="ep",
    )
    params = pipe.init(jax.random.PRNGKey(0), in_spec)
    loss, grads = pipe.train_step(params, tokens, labels)

    # Unsharded run, same params (ep_axis changes no init math).
    block_r, pre_r, post_r = llama_moe_spmd(cfg, moe_ref, pp)
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
    ref_loss, ref_grads = _moe_seq_oracle(cfg, moe_ref, pp, params_r, tokens, labels)
    np.testing.assert_allclose(float(loss), float(ref_loss), rtol=1e-5)
    _assert_trees_close(grads, ref_grads)


@pytest.mark.slow
def test_spmd_moe_ep_with_dp(cpu_devices):
    """ep composes with dp: pp=2 x dp=2 x ep=2 on 8 devices."""
    pp, dp, ep = 2, 2, 2
    cfg = _cfg()
    moe = MoEConfig(n_experts=4, top_k=1, capacity_factor=8.0, ep_axis="ep")
    k1, k2 = jax.random.split(jax.random.PRNGKey(5))
    tokens = jax.random.randint(k1, (8, 4), 0, cfg.vocab)
    labels = jax.random.randint(k2, (8, 4), 0, cfg.vocab)
    in_spec = jax.ShapeDtypeStruct(tokens.shape, tokens.dtype)

    block, pre, post = llama_moe_spmd(cfg, moe, pp)
    mesh = make_mesh(pp, dp=dp, ep=ep, devices=cpu_devices)
    pipe = SpmdGPipe(
        block, pp, mesh, chunks=2, loss_fn=cross_entropy,
        pre=pre, post=post, dp_axis="dp", ep_axis="ep",
    )
    params = pipe.init(jax.random.PRNGKey(0), in_spec)
    loss, grads = pipe.train_step(params, tokens, labels)

    moe_ref = MoEConfig(n_experts=4, top_k=1, capacity_factor=8.0)
    ref_loss, ref_grads = _moe_seq_oracle(cfg, moe_ref, pp, params, tokens, labels)
    np.testing.assert_allclose(float(loss), float(ref_loss), rtol=1e-5)
    _assert_trees_close(grads, ref_grads)


@pytest.mark.slow
def test_spmd_moe_full_composition_sharded_logits(cpu_devices):
    """The README's flagship combination: pp x tp x ep MoE with
    vocab-sharded logits + vocab_parallel_cross_entropy + balance_weight —
    loss matches the dense unsharded oracle (balance injection is
    gradient-only, so the loss value is the task loss)."""
    from torchgpipe_tpu.models.transformer import (
        vocab_parallel_cross_entropy,
    )

    pp, tp, ep = 2, 2, 2
    cfg = TransformerConfig(
        vocab=64, dim=16, n_layers=pp, n_heads=2, n_kv_heads=2, tp_axis="tp"
    )
    moe = MoEConfig(
        n_experts=4, top_k=2, capacity_factor=8.0, ep_axis="ep",
        balance_weight=0.01,
    )
    k1, k2 = jax.random.split(jax.random.PRNGKey(17))
    tokens = jax.random.randint(k1, (8, 4), 0, cfg.vocab)
    labels = jax.random.randint(k2, (8, 4), 0, cfg.vocab)
    in_spec = jax.ShapeDtypeStruct(tokens.shape, tokens.dtype)

    mesh = make_mesh(pp, 1, tp=tp, ep=ep, devices=cpu_devices)
    runs = {}
    for gather in (False, True):
        block, pre, post = llama_moe_spmd(cfg, moe, pp, gather_logits=gather)
        pipe = SpmdGPipe(
            block, pp, mesh, chunks=2,
            loss_fn=cross_entropy if gather else vocab_parallel_cross_entropy("tp"),
            pre=pre, post=post, tp_axis="tp", ep_axis="ep",
        )
        params = pipe.init(jax.random.PRNGKey(0), in_spec)
        runs[gather] = (params, *pipe.train_step(params, tokens, labels))

    params, loss, grads = runs[False]
    _, loss_g, grads_g = runs[True]
    # Sharded-logits loss/grads == gathered-logits run (same balance
    # injection on both; isolates the vocab-parallel CE path end to end).
    np.testing.assert_allclose(float(loss), float(loss_g), rtol=1e-5)
    _assert_trees_close(grads, grads_g)

    moe_ref = MoEConfig(n_experts=4, top_k=2, capacity_factor=8.0)
    cfg_ref = TransformerConfig(
        vocab=64, dim=16, n_layers=pp, n_heads=2, n_kv_heads=2
    )
    ref_loss, _ = _moe_seq_oracle(cfg_ref, moe_ref, pp, params, tokens, labels)
    np.testing.assert_allclose(float(loss), float(ref_loss), rtol=1e-5)


def test_spmd_moe_rejects_indivisible_experts(cpu_devices):
    pp, ep = 2, 4
    cfg = _cfg()
    moe = MoEConfig(n_experts=6, top_k=1, ep_axis="ep")
    block, pre, post = llama_moe_spmd(cfg, moe, pp)
    mesh = make_mesh(pp, dp=1, ep=ep, devices=cpu_devices)
    with pytest.raises(ValueError, match="n_experts.*not divisible"):
        SpmdGPipe(
            block, pp, mesh, chunks=2, loss_fn=cross_entropy,
            pre=pre, post=post, ep_axis="ep",
        )


def test_spmd_moe_rejects_ep_axis_mismatch(cpu_devices):
    """Model routed for ep but engine not told — fail loudly."""
    pp = 2
    cfg = _cfg()
    moe = MoEConfig(n_experts=4, top_k=1, ep_axis="ep")
    block, pre, post = llama_moe_spmd(cfg, moe, pp)
    mesh = make_mesh(pp, dp=1, ep=2, devices=cpu_devices[:4])
    with pytest.raises(ValueError, match="declare ep_axis"):
        SpmdGPipe(
            block, pp, mesh, chunks=2, loss_fn=cross_entropy,
            pre=pre, post=post,
        )


@pytest.mark.slow
def test_mpmd_moe_transparency():
    """The flat llama_moe list runs on the MPMD GPipe engine and matches the
    sequential oracle (experts all local — ep axis unbound)."""
    from torchgpipe_tpu import GPipe
    from torchgpipe_tpu.layers import sequential_apply

    cfg = _cfg()
    moe = MoEConfig(n_experts=4, top_k=2, capacity_factor=8.0)
    layers = llama_moe(cfg, moe)
    k1, k2 = jax.random.split(jax.random.PRNGKey(11))
    tokens = jax.random.randint(k1, (4, 4), 0, cfg.vocab)
    labels = jax.random.randint(k2, (4, 4), 0, cfg.vocab)
    in_spec = jax.ShapeDtypeStruct(tokens.shape, tokens.dtype)

    model = GPipe(layers, balance=[2, 2], chunks=2, checkpoint="except_last")
    params, state = model.init(jax.random.PRNGKey(0), in_spec)
    loss, grads, _, _ = model.value_and_grad(
        params, state, tokens, labels, cross_entropy
    )

    dev0 = jax.devices()[0]
    flat_p = jax.device_put([leaf for stage in params for leaf in stage], dev0)
    flat_s = jax.device_put([leaf for stage in state for leaf in stage], dev0)
    tokens0, labels0 = jax.device_put((tokens, labels), dev0)

    def loss_of(p):
        out, _ = sequential_apply(layers, p, flat_s, tokens0, rng=None, train=True)
        return cross_entropy(out, labels0)

    ref_loss, ref_grads = jax.value_and_grad(loss_of)(flat_p)
    np.testing.assert_allclose(float(loss), float(ref_loss), rtol=1e-5)
    _assert_trees_close(
        [leaf for stage in grads for leaf in stage], ref_grads
    )


@pytest.mark.slow
def test_moe_training_soak_stays_finite():
    """Short soak: tiny MoE llama trains 30 steps with adamw + balance
    weight; loss decreases monotonically-ish and never goes non-finite
    (catches slow numeric blowups the single-step tests cannot)."""
    import optax

    from torchgpipe_tpu import GPipe

    cfg = _cfg()
    moe = MoEConfig(
        n_experts=4, top_k=2, capacity_factor=2.0, balance_weight=0.02
    )
    layers = llama_moe(cfg, moe)
    model = GPipe(layers, balance=[len(layers)], chunks=2)
    tokens = jax.random.randint(jax.random.PRNGKey(1), (8, 16), 0, cfg.vocab)
    params, state = model.init(
        jax.random.PRNGKey(0), jax.ShapeDtypeStruct(tokens.shape, tokens.dtype)
    )
    opt = optax.adamw(3e-3)
    opt_state = opt.init(params)
    losses = []
    for _ in range(30):
        loss, grads, state, _ = model.value_and_grad(
            params, state, tokens, tokens, cross_entropy
        )
        updates, opt_state = opt.update(grads, opt_state, params)
        params = optax.apply_updates(params, updates)
        losses.append(float(loss))
    assert all(np.isfinite(losses)), losses
    assert losses[-1] < losses[0] * 0.7, losses


def test_router_stats_expert_choice_reports_uniform_load():
    """EC load is exactly capacity per expert by construction; the
    token-choice selection metrics would mislead, so stats report the
    uniform load and a unit penalty (importance stays informative)."""
    cfg = _cfg()
    moe = MoEConfig(n_experts=4, router="expert_choice")
    x = jax.random.normal(jax.random.PRNGKey(3), (2, 8, cfg.dim))
    layer = moe_mlp(cfg, moe)
    params, _ = layer.init(
        jax.random.PRNGKey(0), jax.ShapeDtypeStruct(x.shape, x.dtype)
    )
    from torchgpipe_tpu.models.moe import router_stats

    load, importance, penalty = router_stats(params["router"], x, moe)
    np.testing.assert_allclose(np.asarray(load), 0.25)
    assert float(penalty) == 1.0
    assert importance.shape == (4,)


def test_spmd_engine_with_dropless_moe(cpu_devices):
    """The dropless (ragged_dot) dispatch composes with the SPMD engine's
    compiled schedules: same loss/grads as the generous-capacity dense
    dispatch with identical weights, under fill-drain AND 1F1B."""
    pp, m = 2, 2
    cfg = _cfg()  # n_layers=2 == pp
    tokens = jnp.mod(jnp.arange(4 * 8).reshape(4, 8), 64).astype(jnp.int32)
    labels = jnp.mod(tokens + 1, 64)
    spec = jax.ShapeDtypeStruct(tokens.shape, tokens.dtype)
    mesh = make_mesh(pp, 1, devices=cpu_devices[:pp])

    def run(dispatch, capacity_factor, schedule):
        moe = MoEConfig(n_experts=4, top_k=2,
                        capacity_factor=capacity_factor, dispatch=dispatch)
        block, pre, post = llama_moe_spmd(cfg, moe, pp)
        eng = SpmdGPipe(
            block, pp, mesh, chunks=m, loss_fn=cross_entropy,
            pre=pre, post=post, checkpoint="always", schedule=schedule,
        )
        params = eng.init(jax.random.PRNGKey(0), spec)
        return eng.train_step(params, tokens, labels)

    for schedule in ("fill_drain", "1f1b"):
        l_dense, g_dense = run("dense", 8.0, schedule)
        l_drop, g_drop = run("dropless", 8.0, schedule)
        assert abs(float(l_dense) - float(l_drop)) < 1e-5, schedule
        _assert_trees_close(g_drop, g_dense, rtol=1e-4, atol=1e-5)


@pytest.mark.slow
def test_ragged_batch_composes_with_ep(cpu_devices):
    """Ragged batch with ep=2 (the ep axis shards the batch like dp): the
    masked-loss machinery's dp·ep scale and the expert all_to_alls must
    still produce the exact loss over the real rows — compared against
    the same engine on the padded-to-divisible batch restricted to real
    rows via an ep=1 run."""
    pp, ep, m = 2, 2, 2
    cfg = _cfg(tp_axis=None)
    moe = MoEConfig(n_experts=4, top_k=2, capacity_factor=8.0, ep_axis="ep")
    block, pre, post = llama_moe_spmd(cfg, moe, pp)
    B = 7  # q = chunks*ep = 4 -> pad 1
    tokens = jnp.mod(jnp.arange(B * 8).reshape(B, 8), 64).astype(jnp.int32)
    labels = jnp.mod(tokens + 1, 64)
    spec = jax.ShapeDtypeStruct(tokens.shape, tokens.dtype)

    mesh = make_mesh(pp, 1, ep=ep, devices=cpu_devices[: pp * ep])
    eng = SpmdGPipe(
        block, pp, mesh, chunks=m, loss_fn=cross_entropy,
        pre=pre, post=post, ep_axis="ep",
    )
    params = eng.init(jax.random.PRNGKey(0), spec)
    loss, grads = eng.train_step(params, tokens, labels)

    # Oracle: the SAME model on a single-lane (no-ep) engine, which runs
    # the ragged batch through the already-oracle-tested dp=1 masked path.
    moe1 = MoEConfig(n_experts=4, top_k=2, capacity_factor=8.0)
    block1, pre1, post1 = llama_moe_spmd(cfg, moe1, pp)
    mesh1 = make_mesh(pp, 1, devices=cpu_devices[:pp])
    eng1 = SpmdGPipe(
        block1, pp, mesh1, chunks=m, loss_fn=cross_entropy,
        pre=pre1, post=post1,
    )
    params1 = eng1.init(jax.random.PRNGKey(0), spec)
    # The host-side init is layout-independent, so both engines hold the
    # SAME weights (asserted via tree_map, which fails loudly on any
    # structure mismatch) — the losses and gathered gradients must then
    # agree exactly across ep=2 vs ep=1.
    jax.tree_util.tree_map(
        lambda a, b: np.testing.assert_array_equal(
            np.asarray(a), np.asarray(b)
        ),
        params,
        params1,
    )
    loss1, grads1 = eng1.train_step(params1, tokens, labels)
    assert abs(float(loss) - float(loss1)) < 1e-5
    _assert_trees_close(grads, grads1, rtol=1e-4, atol=1e-5)


# --------------------------------------------------------------------- #
# dispatch-assignment edges (the sort-based bookkeeping under overflow) #
# --------------------------------------------------------------------- #


def _one_expert_probs(t=8, E=4, expert=2):
    """Router probabilities where EVERY token's top choice is `expert` —
    the worst-case load skew the capacity machinery must survive."""
    logits = jnp.zeros((t, E)).at[:, expert].add(10.0)
    return jax.nn.softmax(logits, axis=-1)


def test_sparse_assignment_full_overflow_is_fcfs():
    """All 8 tokens route to expert 2 with capacity 2: exactly the first
    `capacity` tokens keep their slot (first-come-first-served in token
    order — the dense `_top_k_dispatch` contract) and dropped tokens
    park at slot 0 with keep=False."""
    from torchgpipe_tpu.models.moe import _sparse_assignment

    probs = _one_expert_probs()
    experts, gates, keep, slot = _sparse_assignment(probs, k=1, capacity=2)
    np.testing.assert_array_equal(np.asarray(experts), np.full(8, 2))
    assert int(keep.sum()) == 2
    np.testing.assert_array_equal(
        np.asarray(keep), [True, True] + [False] * 6
    )
    np.testing.assert_array_equal(
        np.asarray(slot), [0, 1, 0, 0, 0, 0, 0, 0]
    )
    # k=1 keeps the RAW softmax probability as the gate (Switch) — the
    # GShard normalization would pin it to 1.0 and kill router grads.
    np.testing.assert_allclose(
        np.asarray(gates), np.asarray(probs[:, 2]), rtol=1e-6
    )


def test_sparse_assignment_capacity_equals_tokens_boundary():
    """capacity == t is the no-drop boundary even under total skew:
    every assignment keeps, and slots are exactly arrival order."""
    from torchgpipe_tpu.models.moe import _sparse_assignment

    probs = _one_expert_probs(t=8)
    _, _, keep, slot = _sparse_assignment(probs, k=1, capacity=8)
    assert bool(keep.all())
    np.testing.assert_array_equal(np.asarray(slot), np.arange(8))


def test_dropless_assignment_counts_and_k_major_order():
    """The dropless path under total skew: group_sizes put all tokens in
    one segment, the expert-stable sort preserves token order, and with
    k=2 the second-choice round sorts strictly by expert id (k-major
    flat layout — round 2's uniform-tie argmax picks expert 0, which
    sorts BEFORE the round-1 expert-2 segment)."""
    from torchgpipe_tpu.models.moe import _dropless_assignment

    probs = _one_expert_probs(t=8)
    order, tok_sorted, counts, gates = _dropless_assignment(probs, k=1)
    np.testing.assert_array_equal(np.asarray(counts), [0, 0, 8, 0])
    np.testing.assert_array_equal(np.asarray(order), np.arange(8))
    np.testing.assert_array_equal(np.asarray(tok_sorted), np.arange(8))
    np.testing.assert_allclose(
        np.asarray(gates), np.asarray(probs[:, 2]), rtol=1e-6
    )

    order2, tok2, counts2, _ = _dropless_assignment(probs, k=2)
    np.testing.assert_array_equal(np.asarray(counts2), [8, 0, 8, 0])
    # Expert 0 (every token's round-2 pick, k-major indices 8..15) sorts
    # ahead of expert 2 (round-1 picks, indices 0..7); within each
    # segment token order is preserved.
    np.testing.assert_array_equal(
        np.asarray(tok2), np.concatenate([np.arange(8), np.arange(8)])
    )
    np.testing.assert_array_equal(
        np.asarray(order2),
        np.concatenate([np.arange(8, 16), np.arange(8)]),
    )


# --- the served dropless sum combines by gathers (PR 37) ----------------- #


def _dropless_args(k, held, masked, t=12, d=8, h=6):
    """The dropless expert sum's arguments as ``moe_mlp`` routes ``t``
    tokens of ``k`` distinct choices among 16 experts: ``held`` computes
    experts 4..9 here (else all 16), ``masked`` leaves every third
    position out.  Returns the differentiable arguments (the gates in
    expert order last), ``(tok_sorted, group_sizes)`` and the sort key."""
    E = 16
    first, n_held = (4, 6) if held else (0, E)
    ks = jax.random.split(jax.random.PRNGKey(k * 4 + 2 * held + masked), 6)
    experts = jnp.argsort(jax.random.uniform(ks[0], (t, E)), axis=1)[:, :k]
    local = experts.T.reshape(-1) - first  # k-major
    mine = (local >= 0) & (local < n_held)
    if masked:
        mine = mine & jnp.tile(jnp.arange(t) % 3 != 0, k)
    key = jnp.where(mine, local, n_held)
    order = jnp.argsort(key, stable=True)
    gs = jnp.bincount(key, length=n_held + 1)[:n_held].astype(jnp.int32)
    gates = jax.random.uniform(ks[5], (k * t,))
    diff = (
        jax.random.normal(ks[1], (t, d)),
        jax.random.normal(ks[2], (n_held, d, h)) * d ** -0.5,
        jax.random.normal(ks[3], (n_held, d, h)) * d ** -0.5,
        jax.random.normal(ks[4], (n_held, h, d)) * h ** -0.5,
        jnp.where(mine, gates, 0.0)[order],
    )
    return diff, (order % t, gs), key


def _scatter_oracle(xf, w_gate, w_up, w_down, gate_sorted, tok_sorted,
                    group_sizes):
    """The dropless expert sum as PR 36 wrote it: a gather of the
    expert-sorted rows and a scatter-add combine (the CPU's grouped
    product writes zeros to the rows of no group)."""
    xs = xf[tok_sorted]
    hs = (jax.nn.silu(jax.lax.ragged_dot(xs, w_gate, group_sizes))
          * jax.lax.ragged_dot(xs, w_up, group_sizes))
    ys = jax.lax.ragged_dot(hs, w_down, group_sizes)
    return jnp.zeros(xf.shape, ys.dtype).at[tok_sorted].add(
        ys * gate_sorted[:, None])


def _sum_and_grads(fn, diff, cot):
    y, vjp = jax.vjp(fn, *diff)
    return y, vjp(cot)


@pytest.mark.parametrize("k", [2, 8])
@pytest.mark.parametrize("held,masked", [(False, False), (True, False),
                                         (False, True), (True, True)],
                         ids=["all", "held", "valid", "held-valid"])
def test_dropless_expert_sum_matches_scatter_oracle(k, held, masked):
    """The dropless expert sum equals the scatter form served (under
    ``held`` or a ``valid`` mask the gather combine: back to assignment
    order by the inverse sort, the k choices summed in float32) and in
    the gradient of every differentiable argument, on the path
    ``moe_mlp`` takes: ``_held_expert_sum`` under ``held`` or a mask, the
    plain ``_expert_sum`` otherwise."""
    from torchgpipe_tpu.models import moe

    diff, route, key = _dropless_args(k, held, masked)
    if held or masked:
        fn = lambda *a: moe._held_expert_sum(*a, *route, key)  # noqa: E731
    else:
        fn = lambda *a: moe._expert_sum(*a, *route)  # noqa: E731
    cot = jax.random.normal(jax.random.PRNGKey(9), diff[0].shape)
    want = _sum_and_grads(lambda *a: _scatter_oracle(*a, *route), diff, cot)
    _assert_trees_close(_sum_and_grads(fn, diff, cot), want,
                        rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(np.asarray(fn(*diff)), np.asarray(want[0]),
                               rtol=1e-5, atol=1e-6)


def _stale_ragged_dot(real):
    """``real`` (a grouped product) as the TPU's kernel leaves it: rows
    of no group hold whatever was in memory, here NaN, in the result and
    in the transposed product's result alike."""

    def stale(a, gs):
        return jnp.where(jnp.arange(a.shape[0])[:, None] < jnp.sum(gs),
                         a, jnp.nan)

    @jax.custom_vjp
    def ragged_dot(x, w, gs):
        return stale(real(x, w, gs), gs)

    def fwd(x, w, gs):
        return ragged_dot(x, w, gs), (x, w, gs)

    def bwd(res, g):
        x, w, gs = res
        _, vjp = jax.vjp(lambda x, w: real(x, w, gs), x, w)
        dx, dw = vjp(g)
        return stale(dx, gs), dw, None

    ragged_dot.defvjp(fwd, bwd)
    return ragged_dot


@pytest.mark.parametrize("held,masked", [(True, False), (False, True),
                                         (True, True)],
                         ids=["held", "valid", "held-valid"])
def test_dropless_expert_sum_ignores_stale_rows(held, masked, monkeypatch):
    """Rows past ``sum(group_sizes)`` of every grouped product and of its
    transposes hold NaN (what the TPU may leave there: ``PERF.md`` §6,
    PR 32 (2)): the served gather combine and the trained scatter form
    keep them out of the output and of every gradient, which equal the
    oracle run on clean products."""
    from torchgpipe_tpu.models import moe

    diff, route, key = _dropless_args(8, held, masked)
    assert int(jnp.sum(route[1])) < key.shape[0]
    cot = jax.random.normal(jax.random.PRNGKey(9), diff[0].shape)
    want = _sum_and_grads(lambda *a: _scatter_oracle(*a, *route), diff, cot)
    monkeypatch.setattr(moe.lax, "ragged_dot",
                        _stale_ragged_dot(jax.lax.ragged_dot))
    got = _sum_and_grads(
        lambda *a: moe._held_expert_sum(*a, *route, key), diff, cot)
    served = moe._held_expert_sum(*diff, *route, key)
    assert all(bool(jnp.isfinite(g).all())
               for g in jax.tree.leaves((got, served)))
    _assert_trees_close(got, want, rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(np.asarray(served), np.asarray(want[0]),
                               rtol=1e-5, atol=1e-6)


@pytest.mark.parametrize("held,masked", [(True, False), (False, True)],
                         ids=["held", "valid"])
def test_served_dropless_sum_lowers_without_scatter(held, masked):
    """The held expert sum as served (undifferentiated) holds no scatter:
    its combine gathers back by the inverse sort.  PR 36's held one, the
    combine's scatter-add."""
    from torchgpipe_tpu.models import moe

    diff, route, key = _dropless_args(8, held, masked)
    text = jax.jit(lambda *d: moe._held_expert_sum(*d, *route, key)).lower(
        *diff).as_text()
    assert "stablehlo.dot_general" in text and "stablehlo.scatter" not in text


# sha256 of ``jit(value_and_grad(...)).lower(...).as_text()`` of a dropless
# expert layer's loss, pinned on the PARENT commit (fcb42e2, PR 36; jax
# 0.9.0, CPU): every differentiated dropless path (training) keeps PR 36's
# scatter form, because the gather form read a wrong loss in
# ``mellum2.train-4x8192`` on the chip (PR 37; ``PERF.md`` section 7).
PARENT_GRAD_PROGRAMS = {
    "plain": "75f395c0fe9fa9c518ba3b039fe79605ad9192cccda3facb59bf4245169fe9a3",
    "held": "ff5757b7217f6731c1a242cf01f207b14e2645c8d3c8d14690b40b1b5c6d3efc",
    "valid": "dc6d18e22ed09e3d48584c70383bc5d19341db881c2d752433eb311895d398c9",
}


@pytest.mark.parametrize("form", sorted(PARENT_GRAD_PROGRAMS))
def test_differentiated_dropless_layer_lowers_as_the_parent(form):
    """A dropless expert layer's loss and gradient (no ``held``; ``held``;
    a ``valid`` mask) lower byte-identically to PR 36's: the gather
    combine is the served forward's alone."""
    import hashlib

    cfg = _cfg()
    layer = moe_mlp(cfg, MoEConfig(n_experts=8, top_k=2, dispatch="dropless",
                                   held=(2, 4) if form == "held" else None))
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 8, cfg.dim))
    params, _ = layer.init(
        jax.random.PRNGKey(0), jax.ShapeDtypeStruct(x.shape, x.dtype))
    valid = (jnp.arange(16).reshape(2, 8) % 3 != 0) if form == "valid" else None

    def loss(p, x):
        y, _ = layer.meta["forward_counts"](p, x, valid)
        return jnp.sum(y ** 2)

    text = jax.jit(jax.value_and_grad(loss, argnums=(0, 1))).lower(
        params, x).as_text()
    assert hashlib.sha256(text.encode()).hexdigest() == PARENT_GRAD_PROGRAMS[form]


@pytest.mark.parametrize("act", ["swiglu", "relu2"])
def test_served_held_sum_through_the_kernel(act, monkeypatch):
    """The served held sum with its grouped products through the Pallas
    ``grouped_matmul`` (what a TPU runs: ``_on_tpu`` patched, so the
    kernel runs interpreted) equals its ``lax.ragged_dot`` form within
    bf16's rounding, for SwiGLU and for relu² experts (no ``w_gate``)."""
    from torchgpipe_tpu.models import moe

    diff, route, key = _dropless_args(8, True, True, t=24, d=16, h=12)
    x, w_gate, w_up, w_down, gates = diff
    weights = [w.astype(jnp.bfloat16) for w in (x, w_gate, w_up, w_down)]
    if act == "relu2":
        weights[1] = None
    args = (*weights, gates, *route, key)
    want = moe._held_expert_sum(*args)
    monkeypatch.setattr(moe, "_on_tpu", lambda: True)
    text = str(jax.make_jaxpr(moe._held_expert_sum)(*args))
    assert "pallas_call" in text and "ragged_dot" not in text
    got = moe._held_expert_sum(*args)
    assert got.dtype == want.dtype == jnp.bfloat16
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32),
                               rtol=2e-2, atol=2e-2)


@pytest.mark.parametrize("form", ["held", "valid"])
def test_differentiated_dropless_layer_keeps_ragged_dot(form, monkeypatch):
    """Where the served forward takes the Pallas kernel (``_on_tpu``
    patched), ``jax.grad`` through a dropless expert layer (``held``; a
    ``valid`` mask) still computes every product by ``lax.ragged_dot``:
    the kernel is the undifferentiated forward's alone."""
    from torchgpipe_tpu.models import moe

    monkeypatch.setattr(moe, "_on_tpu", lambda: True)
    cfg = _cfg()
    layer = moe_mlp(cfg, MoEConfig(n_experts=8, top_k=2, dispatch="dropless",
                                   held=(2, 4) if form == "held" else None))
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 8, cfg.dim))
    params, _ = layer.init(
        jax.random.PRNGKey(0), jax.ShapeDtypeStruct(x.shape, x.dtype))
    valid = (jnp.arange(16).reshape(2, 8) % 3 != 0) if form == "valid" else None

    def loss(p, x):
        y, _ = layer.meta["forward_counts"](p, x, valid)
        return jnp.sum(y ** 2)

    trained = str(jax.make_jaxpr(jax.grad(loss, argnums=(0, 1)))(params, x))
    assert "ragged_dot" in trained and "pallas_call" not in trained
    served = str(jax.make_jaxpr(loss)(params, x))
    assert "pallas_call" in served and "ragged_dot" not in served


@pytest.mark.parametrize("t,k,n", [(12, 2, 3), (800, 8, 12), (576, 4, 32)])
def test_inverse_order_is_the_sorts(t, k, n):
    """``_inverse_order`` (a cumulative sum over the key's one-hot, no
    second sort) inverts the stable sort of the keys."""
    from torchgpipe_tpu.models import moe

    key = jax.random.randint(jax.random.PRNGKey(t), (k * t,), 0, n + 1)
    order = np.argsort(np.asarray(key), kind="stable")
    inv = np.asarray(moe._inverse_order(key, n))
    np.testing.assert_array_equal(order[inv], np.arange(k * t))


def test_router_stats_counts_selections_pre_capacity():
    """`router_stats` load is the PRE-capacity selection fraction: a
    router that sends everything to expert 0 reports load[0] == 1.0 and
    penalty == E * importance[0] regardless of how tight the capacity
    factor is (capacity drops depend on token order and would make the
    monitoring metric discontinuous in it)."""
    dim, E = 16, 4
    router = jnp.zeros((dim, E)).at[:, 0].set(1.0)
    x = jnp.ones((2, 4, dim))
    tight = MoEConfig(n_experts=E, top_k=1, capacity_factor=0.25)
    load, importance, penalty = router_stats(router, x, tight)
    np.testing.assert_allclose(np.asarray(load), [1.0, 0, 0, 0])
    assert float(jnp.sum(load)) == pytest.approx(1.0)
    assert float(penalty) == pytest.approx(E * float(importance[0]))
    # Identical stats under a generous factor — capacity plays no part.
    loose = MoEConfig(n_experts=E, top_k=1, capacity_factor=8.0)
    load2, importance2, penalty2 = router_stats(router, x, loose)
    np.testing.assert_array_equal(np.asarray(load), np.asarray(load2))
    np.testing.assert_array_equal(
        np.asarray(importance), np.asarray(importance2)
    )
    assert float(penalty) == float(penalty2)


def test_moe_capacity_formula_edges():
    """`events.moe_capacity` re-derives the layer's static per-expert
    budget without a trace: expert-choice clamps to the token count,
    token-choice floors at 1 slot, dropless reports no capacity at all —
    and the formula agrees with the real `moe_mlp` layer's meta."""
    import math

    from torchgpipe_tpu.analysis import events as ev

    ec = {"n_experts": 4, "top_k": 1, "capacity_factor": 100.0,
          "router": "expert_choice"}
    assert ev.moe_capacity(ec, 8) == 8  # ceil(100*8/4)=200, clamped to t
    tc = {"n_experts": 4, "top_k": 2, "capacity_factor": 1.0}
    assert ev.moe_capacity(tc, 8) == 4  # ceil(1*2*8/4)
    tiny = {"n_experts": 4, "top_k": 1, "capacity_factor": 0.01}
    assert ev.moe_capacity(tiny, 8) == 1  # floored — never a 0-slot buffer
    dl = {"n_experts": 4, "top_k": 2, "capacity_factor": 1.0,
          "dispatch": "dropless"}
    assert ev.moe_capacity(dl, 8) == 0

    layer = moe_mlp(_cfg(), MoEConfig(n_experts=4, top_k=2,
                                      capacity_factor=2.0))
    (meta,) = ev.find_moe_meta(layer)
    assert ev.moe_capacity(meta, 64) == math.ceil(2.0 * 2 * 64 / 4)
