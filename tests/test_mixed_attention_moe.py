"""Window and full attention mixed by layer (YaRN on the full layers), softmax-
routed experts on a chip's share, trained through ``SpmdGPipe``: the program
against the plain float32 reference of ``chipbench/reference_mellum2.py`` at
toy width on the CPU, seeded weights.

The toy is the published record's shape: one period ``s s s f`` (two of them
for the two-stage test), a window shorter than the sequence, YaRN from an
original length shorter than the sequence, 16 experts of which 4 a token are
chosen and 4 are held.
"""

import dataclasses
import types

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest
from jax import lax

from chipbench import reference_mellum2 as ref
from chipbench import weights_mellum2
from chipbench.reference import head_logits
from torchgpipe_tpu.models import generation
from torchgpipe_tpu.models import hf_interop
from torchgpipe_tpu.models.hf_interop import config_from_hf_mixed_moe
from torchgpipe_tpu.models.moe import MoEConfig, llama_moe, llama_moe_spmd, moe_mlp
from torchgpipe_tpu.models.transformer import (AttnLayer, TransformerConfig, YarnRope, _maybe_rope,
                                                cross_entropy, layers_per_stage, llama_spmd,
                                                transformer_block)
from torchgpipe_tpu.spmd import SpmdGPipe, make_mesh
from torchgpipe_tpu.utils.tracing import Timeline

SEQ, WINDOW, HELD_FIRST, HELD = 48, 8, 4, 4
ROPES = {
    "full_attention": {"rope_type": "yarn", "rope_theta": 500000, "factor": 16,
                       "original_max_position_embeddings": 16, "beta_fast": 32, "beta_slow": 1,
                       "attention_factor": 1.2772588722239782},
    "sliding_attention": {"rope_type": "default", "rope_theta": 500000},
}


def record(depth=4, held=HELD, held_first=HELD_FIRST):
    """The configuration file's keys at toy width (the reference reads
    these; ``published`` experts stay 16 whatever is held)."""
    kinds = (["sliding_attention"] * 3 + ["full_attention"]) * (depth // 4)
    return {
        "hidden_size": 64, "intermediate_size": 128, "num_attention_heads": 4,
        "num_key_value_heads": 2, "head_dim": 16, "vocab_size": 128, "num_hidden_layers": depth,
        "rms_norm_eps": 1e-6, "tie_word_embeddings": False, "attention_bias": False,
        "hidden_act": "silu", "layer_types": kinds, "mlp_layer_types": ["sparse"] * depth,
        "sliding_window": WINDOW, "rope_parameters": ROPES, "num_experts": held,
        "held_first": held_first, "num_experts_per_tok": 4, "moe_intermediate_size": 32,
        "norm_topk_prob": True, "torch_dtype": "float32",
        "reduced": {"num_experts": {"published": 16}}, "draw": {"seed": 11},
    }


def program(m):
    hf = types.SimpleNamespace(**dict(m, num_experts=16))
    cfg, moe = config_from_hf_mixed_moe(hf, held=(m["held_first"], m["num_experts"]))
    return dataclasses.replace(cfg, dtype=jnp.float32), moe


def pipe_of(m, n_stages, chunks=2, **kwargs):
    cfg, moe = program(m)
    block, pre, post = llama_moe_spmd(cfg, moe, n_stages)
    return SpmdGPipe(block, n_stages, make_mesh(n_stages, devices=jax.devices()[:n_stages]),
                     chunks=chunks, loss_fn=cross_entropy, pre=pre, post=post, **kwargs)


def tokens_of(m, rows=4, seed=1):
    t = jax.random.randint(jax.random.PRNGKey(seed), (rows, SEQ + 1), 0, m["vocab_size"])
    return t[:, :-1], t[:, 1:]


def reference_logits(m, flat, x):
    """The plain forward: every row through every block, then the head."""
    flat = jax.tree_util.tree_map(lambda a: a.astype(jnp.float32), flat)
    rows = []
    for tokens in x:
        h = flat[0]["table"][tokens]
        for kind, p in zip(m["layer_types"], flat[1:-1]):
            h = ref.block(m, kind, p, h, False)
        rows.append(head_logits(m, flat[-1], h, False))
    return jnp.stack(rows)


def reference_loss(m, flat, x, y):
    logp = jax.nn.log_softmax(reference_logits(m, flat, x), -1)
    return -jnp.mean(jnp.take_along_axis(logp, y[..., None], -1))


@pytest.fixture(scope="module")
def model():
    m = record()
    return m, weights_mellum2.make_flat(m, 7)


def test_logits_loss_and_every_gradient_match_the_plain_reference(model):
    m, flat = model
    pipe = pipe_of(m, 1)
    params = pipe.place(weights_mellum2.stack_for_stages(flat, 1))
    x, y = tokens_of(m)
    np.testing.assert_allclose(np.asarray(pipe.apply(params, x)),
                               np.asarray(reference_logits(m, flat, x)), atol=2e-4)
    loss, grads = pipe.train_step(params, x, y)
    want_loss, want = jax.value_and_grad(lambda f: reference_loss(m, f, x, y))(flat)
    assert abs(float(loss) - float(want_loss)) < 1e-5
    want = weights_mellum2.stack_for_stages(want, 1)
    got_leaves = jax.tree_util.tree_flatten_with_path(grads)[0]
    want_leaves = jax.tree_util.tree_leaves(want)
    assert len(got_leaves) == len(want_leaves) == 1 + 4 * 12 + 2
    for (path, got), wanted in zip(got_leaves, want_leaves):
        scale = float(jnp.max(jnp.abs(wanted))) + 1e-12
        assert float(jnp.max(jnp.abs(got - wanted))) < 2e-4 * scale + 1e-7, path


def test_the_four_shares_add_up_to_the_uncut_layer(model):
    """Expert parallelism's contract: the partial sums of the four chips'
    shares are the whole layer's output (no expert is shared here)."""
    m, _ = model
    whole = record(held=16, held_first=0)
    p = weights_mellum2.make_flat(whole, 3)[1]["mlp"]
    u = jax.random.normal(jax.random.PRNGKey(5), (2, SEQ, 64), jnp.float32)
    cfg, _ = program(m)
    uncut = jnp.stack([ref.experts(whole, p, row, False) for row in u])
    total = jnp.zeros_like(u)
    for first in range(0, 16, 4):
        mine = {k: v if k == "router" else v[first:first + 4] for k, v in p.items()}
        moe = MoEConfig(n_experts=16, top_k=4, dispatch="dropless", norm_topk=True,
                        expert_hidden=32, held=(first, 4))
        part, _ = moe_mlp(cfg, moe).apply(mine, (), u)
        share = dict(whole, num_experts=4, held_first=first)
        np.testing.assert_allclose(
            np.asarray(part), np.asarray(jnp.stack([ref.experts(share, mine, r, False) for r in u])),
            atol=1e-5)
        total = total + part
    np.testing.assert_allclose(np.asarray(total), np.asarray(uncut), atol=2e-5)


@pytest.mark.parametrize("layer,sees", [(0, False), (3, True)], ids=["sliding", "full"])
def test_a_sliding_layer_ignores_a_key_a_window_behind_and_a_full_layer_does_not(layer, sees):
    cfg, _ = program(record())
    block = transformer_block(cfg, layer=layer)
    params, _ = block.init(jax.random.PRNGKey(0), None)
    x = jax.random.normal(jax.random.PRNGKey(1), (1, SEQ, 64), jnp.float32)
    moved = x.at[0, 0].add(1.0)                 # position 0's key and value change
    a, _ = block.apply(params, (), x)
    b, _ = block.apply(params, (), moved)
    gap = np.abs(np.asarray(a - b))[0].max(-1)  # by query position
    assert gap[:WINDOW].min() > 1e-4            # inside the window every query sees it
    assert (gap[WINDOW:].max() > 1e-4) == sees
    if not sees:
        assert gap[WINDOW:].max() == 0.0


def test_yarn_frequencies_and_factor_against_the_closed_form():
    cfg, _ = program(record())
    full, plain = cfg.attn_layer(3), cfg.attn_layer(0)
    assert plain.yarn is None and plain.window == WINDOW and full.window is None
    x = jax.random.normal(jax.random.PRNGKey(2), (1, SEQ, 4, 16), jnp.float32)
    got = _maybe_rope(cfg, x, 0, 3)
    np.testing.assert_allclose(np.asarray(got[0]),
                               np.asarray(ref.rope(record(), "full_attention", x[0])), atol=1e-5)
    np.testing.assert_allclose(np.asarray(_maybe_rope(cfg, x, 0, 0)[0]),
                               np.asarray(ref.rope(record(), "sliding_attention", x[0])), atol=1e-5)
    # Closed form (Peng et al.): position 0 is scaled by the factor alone,
    # 0.1 ln(16) + 1; the fastest pair keeps theta**0 = 1 radian a position,
    # the slowest turns 16 times more slowly than plain rope's.
    factor = 0.1 * np.log(16.0) + 1.0
    np.testing.assert_allclose(np.asarray(got[0, 0]), factor * np.asarray(x[0, 0]), rtol=1e-6)
    freqs = ref.yarn_inv_freq(16, ROPES["full_attention"])
    assert freqs[0] == pytest.approx(1.0)
    assert freqs[-1] == pytest.approx(500000.0 ** (-14 / 16) / 16.0, rel=1e-6)
    # The model-global description is the one-entry case of the same period.
    old = TransformerConfig(attn_window=5, rope_theta=1e4)
    assert old.attn_period == (AttnLayer(5, 1e4),) and old.attn_layer(7).window == 5
    with pytest.raises(ValueError, match="attn_window"):
        TransformerConfig(attn_window=5, attn_layers=(AttnLayer(5, 1e4),)).validate_arch()
    # A record's stated factor has to be the one its factor and mscales give.
    with pytest.raises(ValueError, match="attention_factor"):
        hf_interop._rope_entry(dict(ROPES["full_attention"], attention_factor=1.5), None)


def test_two_stages_of_one_period_match_one_stage_of_two():
    m = record(depth=8)
    flat = weights_mellum2.make_flat(m, 9)
    x, y = tokens_of(m)
    out = {}
    for n in (1, 2):
        pipe = pipe_of(m, n)
        params = pipe.place(weights_mellum2.stack_for_stages(flat, n))
        loss, grads = pipe.train_step(params, x, y)
        out[n] = (float(loss), jax.device_get(grads))
    assert out[1][0] == pytest.approx(out[2][0], abs=1e-5)
    one, two = out[1][1]["blocks"], out[2][1]["blocks"]
    for layer in range(8):
        a = jax.tree_util.tree_leaves(jax.tree_util.tree_map(lambda v: v[0], one[layer]))
        b = jax.tree_util.tree_leaves(
            jax.tree_util.tree_map(lambda v: v[layer // 4], two[layer % 4]))
        for u, v in zip(a, b):
            np.testing.assert_allclose(u, v, atol=2e-5)


def test_a_stage_holds_whole_periods():
    cfg, moe = program(record(depth=8))
    assert layers_per_stage(cfg, 2) == 4
    for build in (lambda: llama_moe_spmd(cfg, moe, 4), lambda: llama_spmd(cfg, 4)):
        with pytest.raises(ValueError, match="whole periods"):
            build()
    assert len(llama_moe(cfg, moe)) == 10       # the flat list takes any depth


def test_the_published_record_reads_into_the_period():
    hf = types.SimpleNamespace(
        attention_bias=False, head_dim=128, hidden_act="silu", hidden_size=2304,
        intermediate_size=7168, layer_types=(["sliding_attention"] * 3 + ["full_attention"]) * 7,
        mlp_layer_types=["sparse"] * 28, moe_intermediate_size=896, norm_topk_prob=True,
        num_attention_heads=32, num_experts=64, num_experts_per_tok=8, num_hidden_layers=28,
        num_key_value_heads=4, rms_norm_eps=1e-6, sliding_window=1024, tie_word_embeddings=False,
        vocab_size=98304,
        rope_parameters=dict(ROPES, full_attention=dict(
            ROPES["full_attention"], original_max_position_embeddings=8192)))
    cfg, moe = config_from_hf_mixed_moe(hf, held=(0, 16))
    assert [e.window for e in cfg.attn_period] == [1024, 1024, 1024, None]
    assert [e.yarn is not None for e in cfg.attn_period] == [False, False, False, True]
    assert cfg.attn_layer(27).yarn == YarnRope(16.0, 8192, 32.0, 1.0, 1.0, 0.0)
    assert (cfg.dim, cfg.head_dim, cfg.n_heads, cfg.kv_heads, cfg.qk_norm) == (2304, 128, 32, 4, True)
    assert moe == MoEConfig(n_experts=64, top_k=8, dispatch="dropless", scoring="softmax",
                            norm_topk=True, expert_hidden=896, held=(0, 16))
    with pytest.raises(ValueError, match="sparse"):
        config_from_hf_mixed_moe(types.SimpleNamespace(**dict(vars(hf), mlp_layer_types=["dense"])))


@pytest.mark.parametrize("path", ["prefill", "decode_slots", "engine"])
def test_generation_and_the_engine_serve_a_mixed_period(model, path, monkeypatch):
    """Since PR 34 a model that mixes layer types is generated from and
    served (it was refused by name): the window layers' rows in rings of
    the toy window, YaRN on the full layers, against the plain forward."""
    from torchgpipe_tpu.models import kv_cache
    from torchgpipe_tpu.serving import Engine

    monkeypatch.setattr(kv_cache, "RING_GRANULE", 4)
    m, flat = model
    cfg, moe = program(m)
    x, _ = tokens_of(m, rows=2)
    want = np.asarray(reference_logits(m, flat, x))
    if path == "prefill":
        logits, cache = generation.prefill(cfg, flat, x, SEQ + 8, moe=moe)
        assert kv_cache.bank_rows(cache) == [WINDOW] * 3 + [SEQ + 8]
        np.testing.assert_allclose(np.asarray(logits), want[:, -1], atol=2e-4)
    elif path == "decode_slots":
        cache = generation.init_cache(cfg, 2, SEQ, chunk=4)
        assert kv_cache.bank_rows(cache) == [12] * 3 + [SEQ]
        lengths = jnp.zeros((2,), jnp.int32)
        for at in range(0, SEQ, 4):                     # the rings wrap four times
            logits, cache, lengths = generation.decode_slots(
                cfg, flat, x[:, at:at + 4], cache, lengths, jnp.full((2,), 4, jnp.int32),
                moe=moe)[:3]
            np.testing.assert_allclose(np.asarray(logits), want[:, at:at + 4], atol=2e-4)
    else:
        eng = Engine(cfg, flat, num_slots=2, max_len=SEQ + 8, prefill_chunk=4, moe=moe)
        rid = eng.submit(np.asarray(x[0, :40]), 6)
        assert eng.run() == "idle"
        out = eng.result(rid)
        full = jnp.concatenate([x[0, :40], jnp.asarray(out)])[None]
        chose = np.asarray(reference_logits(m, flat, full))[0, 39:-1]
        assert (chose.max(-1) - chose[np.arange(6), out]).max() < 2e-4
    # One entry is the model-global window, wherever it is written.
    one = dataclasses.replace(cfg, attn_layers=(AttnLayer(WINDOW, 1e4),))
    assert generation._window(one) == WINDOW and not kv_cache.ring_layer(one, 0)


def test_the_steps_held_counts_are_the_layers_own(model):
    """The counts a train step returns fourth are ``forward_counts``' on the
    same batch at the same weights."""
    m, flat = model
    timeline = Timeline()
    pipe = pipe_of(m, 1, tracer=timeline)
    params = pipe.place(weights_mellum2.stack_for_stages(flat, 1))
    opt = optax.sgd(0.0)
    step = pipe.make_train_step(opt, donate=False)
    x, y = tokens_of(m)
    loss, _, _, counts = step(params, pipe.place_tree(opt.init(params)), x, y)
    assert counts.shape == (1, 4, HELD) and counts.dtype == jnp.int32
    apply_counts = pipe.block.meta["apply_counts"]
    stage = jax.tree_util.tree_map(lambda a: a[0], params["blocks"])
    want = sum(apply_counts(stage, flat[0]["table"][rows])[1] for rows in (x[:2], x[2:]))
    np.testing.assert_array_equal(np.asarray(counts[0]), np.asarray(want))
    assert float(loss) == pytest.approx(float(pipe.train_step(params, x, y)[0]), abs=1e-6)
    # The uniform share is a quarter; a router with seeded weights is near it.
    assert 0.15 < int(want.sum()) / (4 * SEQ * 4 * 4) < 0.35
    # A dense block declares no counts and its step returns three results.
    cfg, _ = program(m)
    block, pre, post = llama_spmd(dataclasses.replace(cfg, n_layers=4), 1)
    dense = SpmdGPipe(block, 1, make_mesh(1, devices=jax.devices()[:1]), chunks=2,
                      loss_fn=cross_entropy, pre=pre, post=post, tracer=timeline)
    dp = dense.init(jax.random.PRNGKey(0), jax.ShapeDtypeStruct((4, SEQ), jnp.int32))
    assert len(dense.make_train_step(opt, donate=False)(
        dp, dense.place_tree(opt.init(dp)), x, y)) == 3


def test_counts_leave_out_the_bubble_of_a_two_stage_pipe():
    """On two stages a lane's fill and drain ticks compute masked garbage:
    their counts are left out, so the two stages' counts are the one-stage
    pipe's layer by layer."""
    m = record(depth=8)
    flat = weights_mellum2.make_flat(m, 9)
    x, y = tokens_of(m)
    opt = optax.sgd(0.0)
    got = {}
    for n in (1, 2):
        pipe = pipe_of(m, n, tracer=Timeline())
        params = pipe.place(weights_mellum2.stack_for_stages(flat, n))
        got[n] = np.asarray(pipe.make_train_step(opt, donate=False)(
            params, pipe.place_tree(opt.init(params)), x, y)[3])
    assert got[1].shape == (1, 8, HELD) and got[2].shape == (2, 4, HELD)
    np.testing.assert_array_equal(got[1].reshape(8, HELD), got[2].reshape(8, HELD))


def test_held_routing_is_recomputed_under_every_checkpoint_mode(model):
    m, flat = model
    x, y = tokens_of(m)
    base = None
    for mode in ("always", "except_last", "never"):
        pipe = pipe_of(m, 1, checkpoint=mode, tracer=Timeline())
        params = pipe.place(weights_mellum2.stack_for_stages(flat, 1))
        opt = optax.sgd(0.0)
        loss, _, _, counts = pipe.make_train_step(opt, donate=False)(
            params, pipe.place_tree(opt.init(params)), x, y)
        grads = pipe.train_step(params, x, y)[1]
        leaves = [np.asarray(a) for a in jax.tree_util.tree_leaves(grads)]
        if base is None:
            base = (float(loss), np.asarray(counts), leaves)
            continue
        assert float(loss) == pytest.approx(base[0], abs=1e-6)
        np.testing.assert_array_equal(np.asarray(counts), base[1])
        for a, b in zip(leaves, base[2]):
            np.testing.assert_allclose(a, b, atol=1e-5)


def test_reference_top_k_is_the_programs_selection(model):
    """The reference takes ``lax.top_k``, the program an iterative argmax:
    the same 4 of 16 on seeded weights, with the same renormalised gates."""
    from torchgpipe_tpu.models.moe import _route

    probs = jax.nn.softmax(jax.random.normal(jax.random.PRNGKey(4), (64, 16)), -1)
    idx, _, gates = _route(probs, 4, MoEConfig(n_experts=16, top_k=4, norm_topk=True))
    top, chosen = lax.top_k(probs, 4)
    np.testing.assert_array_equal(np.asarray(idx.T), np.asarray(chosen))
    np.testing.assert_allclose(np.asarray(gates.T), np.asarray(top / top.sum(-1, keepdims=True)),
                               rtol=1e-6)
