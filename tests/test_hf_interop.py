"""HF Llama import: converted weights must reproduce the live HF model's
logits and greedy decode — the numerical proof of every convention the
importer claims (transposes, rotary layout, GQA pairing, RMSNorm math).

transformers runs torch on CPU in this container; the models are tiny
random-init (no network)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

torch = pytest.importorskip("torch")
transformers = pytest.importorskip("transformers")

from torchgpipe_tpu.layers import sequential_apply  # noqa: E402
from torchgpipe_tpu.models.generation import generate  # noqa: E402
from torchgpipe_tpu.models.hf_interop import (  # noqa: E402
    config_from_hf,
    from_hf_llama,
)
from torchgpipe_tpu.models.transformer import (  # noqa: E402
    cross_entropy as cross_entropy_,
    llama,
)


def _hf_model(nkv=2):
    cfg = transformers.LlamaConfig(
        vocab_size=64, hidden_size=32, intermediate_size=128,
        num_hidden_layers=2, num_attention_heads=4,
        num_key_value_heads=nkv, rope_theta=10000.0, rms_norm_eps=1e-5,
    )
    torch.manual_seed(0)
    m = transformers.LlamaForCausalLM(cfg)
    m.eval()
    return m


@pytest.mark.parametrize("nkv", [2, 4])
def test_logits_match_hf(nkv):
    m = _hf_model(nkv)
    cfg, params = from_hf_llama(m)
    b, s = 2, 7
    tokens = np.arange(b * s).reshape(b, s) % cfg.vocab

    with torch.no_grad():
        ref = m(torch.tensor(tokens)).logits.numpy()

    out, _ = sequential_apply(
        llama(cfg), params, [() for _ in range(cfg.n_layers + 2)],
        jnp.asarray(tokens, jnp.int32), rng=None, train=False,
    )
    np.testing.assert_allclose(
        np.asarray(out, np.float32), ref, rtol=2e-4, atol=2e-4
    )


def test_greedy_decode_matches_hf():
    m = _hf_model()
    cfg, params = from_hf_llama(m)
    b, s, new = 2, 5, 4
    tokens = (np.arange(b * s).reshape(b, s) * 3 + 1) % cfg.vocab

    ours = np.asarray(
        generate(cfg, params, jnp.asarray(tokens, jnp.int32),
                 max_new_tokens=new)
    )
    with torch.no_grad():
        hf = m.generate(
            torch.tensor(tokens), max_new_tokens=new, do_sample=False,
        ).numpy()[:, s:]
    assert (ours == hf).all(), (ours, hf)


def test_converted_weights_pipeline_trainable():
    """Imported weights splice into GPipe(llama(cfg)) and train."""
    from torchgpipe_tpu.gpipe import GPipe
    from torchgpipe_tpu.models.transformer import cross_entropy

    m = _hf_model()
    cfg, flat = from_hf_llama(m)
    model = GPipe(llama(cfg), balance=[2, 2], chunks=2)
    b, s = 2, 6
    spec = jax.ShapeDtypeStruct((b, s), jnp.int32)
    params, state = model.init(jax.random.PRNGKey(0), spec)
    # Splice the imported per-layer params into the per-stage layout.
    it = iter(flat)
    params = tuple(tuple(next(it) for _ in stage) for stage in params)
    x = jnp.asarray(np.arange(b * s).reshape(b, s) % cfg.vocab, jnp.int32)
    loss, grads, state, _ = model.value_and_grad(
        model.place(params), state, x, x, cross_entropy
    )
    assert np.isfinite(float(loss))


def test_unsupported_layouts_rejected():
    from torchgpipe_tpu.models.hf_interop import params_from_hf

    m = _hf_model()
    cfg = config_from_hf(m.config)
    sd = {"model.layers.0.block_sparse_moe.experts.0.w1.weight": None}
    with pytest.raises(ValueError, match="MoE"):
        params_from_hf(sd, cfg)

    bad = transformers.LlamaConfig(
        vocab_size=64, hidden_size=32, intermediate_size=100,  # not 128-aligned
        num_hidden_layers=1, num_attention_heads=4, num_key_value_heads=2,
    )
    with pytest.raises(ValueError, match="intermediate_size"):
        config_from_hf(bad)


def test_roundtrip_to_hf():
    """from_hf -> to_hf loads back into a live HF model bit-compatibly
    (logits unchanged)."""
    from torchgpipe_tpu.models.hf_interop import state_dict_to_hf

    m = _hf_model()
    cfg, params = from_hf_llama(m)
    sd = state_dict_to_hf(params, cfg)
    m2 = _hf_model()
    missing, unexpected = m2.load_state_dict(sd, strict=False)
    assert not unexpected, unexpected
    b, s = 2, 6
    tokens = torch.tensor(np.arange(b * s).reshape(b, s) % cfg.vocab)
    with torch.no_grad():
        ref = m(tokens).logits.numpy()
        got = m2(tokens).logits.numpy()
    np.testing.assert_allclose(got, ref, rtol=1e-5, atol=1e-6)


def test_to_hf_preserves_dtype():
    """A bf16 checkpoint exports back as bf16 torch tensors with exactly
    the original values — not silently widened to f32 (doubling the
    published state dict)."""
    from torchgpipe_tpu.models.hf_interop import state_dict_to_hf

    m = _hf_model()
    cfg, params = from_hf_llama(m)
    bf16 = jax.tree_util.tree_map(
        lambda a: a.astype(jnp.bfloat16)
        if jnp.issubdtype(a.dtype, jnp.floating)
        else a,
        params,
    )
    sd = state_dict_to_hf(bf16, cfg)
    assert all(t.dtype == torch.bfloat16 for t in sd.values()), {
        k: t.dtype for k, t in sd.items() if t.dtype != torch.bfloat16
    }
    # Value-exact: the f32 numpy bridge is lossless for bf16.
    sd32 = state_dict_to_hf(params, cfg)
    for k, t in sd.items():
        np.testing.assert_array_equal(
            t.to(torch.float32).numpy(),
            sd32[k].numpy().astype(jnp.bfloat16).astype(np.float32),
            err_msg=k,
        )


def test_tied_hf_checkpoint_native_tie():
    """A tie_word_embeddings HF checkpoint imports as the framework's
    native tie (one shared table, no 'w'), decodes teacher-forced equal
    to the HF model, and exports back WITHOUT an lm_head.weight entry."""
    from torchgpipe_tpu.models.generation import generate
    from torchgpipe_tpu.models.hf_interop import state_dict_to_hf

    cfg_hf = transformers.LlamaConfig(
        vocab_size=64, hidden_size=32, intermediate_size=128,
        num_hidden_layers=2, num_attention_heads=4, num_key_value_heads=2,
        tie_word_embeddings=True,
    )
    torch.manual_seed(0)
    m = transformers.LlamaForCausalLM(cfg_hf).eval()
    cfg, params = from_hf_llama(m)
    assert cfg.tie_embeddings
    head = params[-1]
    assert "w" not in head and head["table"] is params[0]["table"]

    b, s = 2, 6
    tokens = np.arange(b * s).reshape(b, s) % cfg.vocab
    with torch.no_grad():
        ref = m(torch.tensor(tokens)).logits.numpy()
    # Greedy decode's first token == HF argmax at the last position.
    got = generate(cfg, params, jnp.asarray(tokens), max_new_tokens=1)
    np.testing.assert_array_equal(
        np.asarray(got[:, 0]), ref[:, -1].argmax(-1)
    )

    sd = state_dict_to_hf(params, cfg)
    assert "lm_head.weight" not in sd
    m2 = transformers.LlamaForCausalLM(cfg_hf)
    missing, unexpected = m2.load_state_dict(sd, strict=False)
    assert not unexpected, unexpected
    m2.tie_weights()
    with torch.no_grad():
        got2 = m2(torch.tensor(tokens)).logits.numpy()
    np.testing.assert_allclose(got2, ref, rtol=1e-5, atol=1e-6)


def test_tied_checkpoint_untie_for_mpmd():
    """untie=True imports a tied checkpoint as an untied copy that the
    MPMD GPipe(llama(cfg)) path accepts, logits unchanged."""
    cfg_hf = transformers.LlamaConfig(
        vocab_size=64, hidden_size=32, intermediate_size=128,
        num_hidden_layers=2, num_attention_heads=4, num_key_value_heads=2,
        tie_word_embeddings=True,
    )
    torch.manual_seed(0)
    m = transformers.LlamaForCausalLM(cfg_hf).eval()
    cfg, params = from_hf_llama(m, untie=True)
    assert not cfg.tie_embeddings and "w" in params[-1]
    b, s = 2, 6
    tokens = np.arange(b * s).reshape(b, s) % cfg.vocab
    with torch.no_grad():
        ref = m(torch.tensor(tokens)).logits.numpy()
    out, _ = sequential_apply(
        llama(cfg), params, [() for _ in range(cfg.n_layers + 2)],
        jnp.asarray(tokens, jnp.int32), rng=None, train=False,
    )
    np.testing.assert_allclose(
        np.asarray(out, np.float32), ref, rtol=2e-4, atol=2e-4
    )


def test_mixtral_logits_match_hf():
    """MoE import: a live MixtralForCausalLM's logits must be reproduced
    by llama_moe(cfg, moe) under the dropless dispatch (Mixtral drops no
    tokens; HF's renormalized top-k == the GShard gate normalization)."""
    from torchgpipe_tpu.models.hf_interop import from_hf_mixtral
    from torchgpipe_tpu.models.moe import llama_moe

    cfg_hf = transformers.MixtralConfig(
        vocab_size=64, hidden_size=32, intermediate_size=128,
        num_hidden_layers=2, num_attention_heads=4, num_key_value_heads=2,
        num_local_experts=4, num_experts_per_tok=2,
        rope_theta=10000.0, rms_norm_eps=1e-5,
    )
    torch.manual_seed(0)
    m = transformers.MixtralForCausalLM(cfg_hf).eval()
    cfg, moe, params = from_hf_mixtral(m)
    assert moe.n_experts == 4 and moe.top_k == 2
    assert moe.dispatch == "dropless"

    b, s = 2, 7
    tokens = np.arange(b * s).reshape(b, s) % cfg.vocab
    with torch.no_grad():
        ref = m(torch.tensor(tokens)).logits.numpy()
    out, _ = sequential_apply(
        llama_moe(cfg, moe), params,
        [() for _ in range(cfg.n_layers + 2)],
        jnp.asarray(tokens, jnp.int32), rng=None, train=False,
    )
    np.testing.assert_allclose(
        np.asarray(out, np.float32), ref, rtol=2e-4, atol=2e-4
    )


def test_mixtral_decode_and_k1_rejection():
    from torchgpipe_tpu.models.generation import generate
    from torchgpipe_tpu.models.hf_interop import (
        config_from_hf_mixtral,
        from_hf_mixtral,
    )

    cfg_hf = transformers.MixtralConfig(
        vocab_size=64, hidden_size=32, intermediate_size=128,
        num_hidden_layers=2, num_attention_heads=4, num_key_value_heads=2,
        num_local_experts=4, num_experts_per_tok=2,
    )
    torch.manual_seed(0)
    m = transformers.MixtralForCausalLM(cfg_hf).eval()
    cfg, moe, params = from_hf_mixtral(m)
    b, s, new = 2, 5, 3
    tokens = (np.arange(b * s).reshape(b, s) * 3 + 1) % cfg.vocab
    ours = np.asarray(generate(
        cfg, params, jnp.asarray(tokens, jnp.int32),
        max_new_tokens=new, moe=moe,
    ))
    with torch.no_grad():
        hf = m.generate(
            torch.tensor(tokens), max_new_tokens=new, do_sample=False,
        ).numpy()[:, s:]
    assert (ours == hf).all(), (ours, hf)

    bad = transformers.MixtralConfig(
        vocab_size=64, hidden_size=32, intermediate_size=128,
        num_hidden_layers=1, num_attention_heads=4, num_key_value_heads=2,
        num_local_experts=4, num_experts_per_tok=1,
    )
    with pytest.raises(ValueError, match="k=1"):
        config_from_hf_mixtral(bad)


def test_mixtral_sliding_window_maps_to_attn_window():
    """Mixtral's sliding_window imports as cfg.attn_window; logits match
    the HF model at a sequence LONGER than the window (the config where
    full-causal attention would silently diverge)."""
    from torchgpipe_tpu.models.hf_interop import from_hf_mixtral
    from torchgpipe_tpu.models.moe import llama_moe

    cfg_hf = transformers.MixtralConfig(
        vocab_size=64, hidden_size=32, intermediate_size=128,
        num_hidden_layers=2, num_attention_heads=4, num_key_value_heads=2,
        num_local_experts=4, num_experts_per_tok=2, sliding_window=3,
        attn_implementation="eager",
    )
    torch.manual_seed(0)
    m = transformers.MixtralForCausalLM(cfg_hf).eval()
    cfg, moe, params = from_hf_mixtral(m)
    assert cfg.attn_window == 3
    b, s = 2, 7  # s > window: the band actually bites
    tokens = np.arange(b * s).reshape(b, s) % cfg.vocab
    with torch.no_grad():
        ref = m(torch.tensor(tokens)).logits.numpy()
    out, _ = sequential_apply(
        llama_moe(cfg, moe), params,
        [() for _ in range(cfg.n_layers + 2)],
        jnp.asarray(tokens, jnp.int32), rng=None, train=False,
    )
    np.testing.assert_allclose(
        np.asarray(out, np.float32), ref, rtol=2e-4, atol=2e-4
    )


def test_tied_mixtral_imports_consistently():
    """A tie_word_embeddings Mixtral imports with the tie honored: head
    carries the shared table (no stale untied 'w'), and the MPMD list
    rejects the config at construction with a pointer."""
    from torchgpipe_tpu.models.hf_interop import from_hf_mixtral
    from torchgpipe_tpu.models.moe import llama_moe

    cfg_hf = transformers.MixtralConfig(
        vocab_size=64, hidden_size=32, intermediate_size=128,
        num_hidden_layers=1, num_attention_heads=4, num_key_value_heads=2,
        num_local_experts=4, num_experts_per_tok=2,
        tie_word_embeddings=True,
    )
    torch.manual_seed(0)
    m = transformers.MixtralForCausalLM(cfg_hf).eval()
    cfg, moe, params = from_hf_mixtral(m)
    assert cfg.tie_embeddings
    assert "w" not in params[-1] and params[-1]["table"] is params[0]["table"]
    with pytest.raises(ValueError, match="llama_moe_spmd"):
        llama_moe(cfg, moe)


def test_mixtral_roundtrip_to_hf():
    """from_hf_mixtral -> state_dict_to_hf_mixtral loads back into a live
    Mixtral bit-compatibly (logits unchanged)."""
    from torchgpipe_tpu.models.hf_interop import (
        from_hf_mixtral,
        state_dict_to_hf_mixtral,
    )

    cfg_hf = transformers.MixtralConfig(
        vocab_size=64, hidden_size=32, intermediate_size=128,
        num_hidden_layers=2, num_attention_heads=4, num_key_value_heads=2,
        num_local_experts=4, num_experts_per_tok=2,
    )
    torch.manual_seed(0)
    m = transformers.MixtralForCausalLM(cfg_hf).eval()
    cfg, moe, params = from_hf_mixtral(m)
    sd = state_dict_to_hf_mixtral(params, cfg, moe)
    m2 = transformers.MixtralForCausalLM(cfg_hf)
    missing, unexpected = m2.load_state_dict(sd, strict=False)
    assert not unexpected, unexpected
    b, s = 2, 6
    tokens = torch.tensor(np.arange(b * s).reshape(b, s) % cfg.vocab)
    with torch.no_grad():
        ref = m(tokens).logits.numpy()
        got = m2(tokens).logits.numpy()
    np.testing.assert_allclose(got, ref, rtol=1e-5, atol=1e-6)


def test_mixtral_bf16_roundtrip_uniform_dtype():
    """A bf16 Mixtral param tree exports with EVERY tensor bf16 —
    including the router, which the importer keeps f32 in-framework."""
    from torchgpipe_tpu.models.hf_interop import (
        from_hf_mixtral,
        state_dict_to_hf_mixtral,
    )

    cfg_hf = transformers.MixtralConfig(
        vocab_size=64, hidden_size=32, intermediate_size=128,
        num_hidden_layers=1, num_attention_heads=4, num_key_value_heads=2,
        num_local_experts=4, num_experts_per_tok=2,
    )
    torch.manual_seed(0)
    m = transformers.MixtralForCausalLM(cfg_hf).eval()
    cfg, moe, params = from_hf_mixtral(m)
    bf16 = jax.tree_util.tree_map(
        lambda a: a.astype(jnp.bfloat16)
        if jnp.issubdtype(a.dtype, jnp.floating)
        else a,
        params,
    )
    sd = state_dict_to_hf_mixtral(bf16, cfg, moe)
    assert all(t.dtype == torch.bfloat16 for t in sd.values()), {
        k: t.dtype for k, t in sd.items() if t.dtype != torch.bfloat16
    }


def test_qwen2_logits_and_decode_match_hf():
    """Qwen2 import (Llama layout + always-on q/k/v biases): logits AND
    greedy decode match the live Qwen2ForCausalLM; the export round-trips
    the biases."""
    from torchgpipe_tpu.models.hf_interop import (
        from_hf_qwen2,
        state_dict_to_hf,
    )

    cfg_hf = transformers.Qwen2Config(
        vocab_size=64, hidden_size=32, intermediate_size=128,
        num_hidden_layers=2, num_attention_heads=4, num_key_value_heads=2,
        rope_theta=10000.0, rms_norm_eps=1e-5,
    )
    torch.manual_seed(0)
    m = transformers.Qwen2ForCausalLM(cfg_hf).eval()
    cfg, params = from_hf_qwen2(m)
    assert cfg.attn_bias and "bq" in params[1]

    b, s = 2, 7
    tokens = np.arange(b * s).reshape(b, s) % cfg.vocab
    with torch.no_grad():
        ref = m(torch.tensor(tokens)).logits.numpy()
    out, _ = sequential_apply(
        llama(cfg), params, [() for _ in range(cfg.n_layers + 2)],
        jnp.asarray(tokens, jnp.int32), rng=None, train=False,
    )
    np.testing.assert_allclose(
        np.asarray(out, np.float32), ref, rtol=2e-4, atol=2e-4
    )

    ours = np.asarray(generate(
        cfg, params, jnp.asarray(tokens[:, :5], jnp.int32),
        max_new_tokens=3,
    ))
    with torch.no_grad():
        hf = m.generate(
            torch.tensor(tokens[:, :5]), max_new_tokens=3, do_sample=False,
        ).numpy()[:, 5:]
    assert (ours == hf).all(), (ours, hf)

    sd = state_dict_to_hf(params, cfg)
    m2 = transformers.Qwen2ForCausalLM(cfg_hf)
    missing, unexpected = m2.load_state_dict(sd, strict=True)
    with torch.no_grad():
        got = m2(torch.tensor(tokens)).logits.numpy()
    np.testing.assert_allclose(got, ref, rtol=1e-5, atol=1e-6)


def test_qwen2_trains_through_pipeline(cpu_devices):
    """Imported Qwen2 weights train through the SPMD pipeline (biases
    get gradients)."""
    from torchgpipe_tpu.models.hf_interop import from_hf_qwen2
    from torchgpipe_tpu.models.transformer import cross_entropy, llama_spmd
    from torchgpipe_tpu.spmd import SpmdGPipe, make_mesh

    cfg_hf = transformers.Qwen2Config(
        vocab_size=64, hidden_size=32, intermediate_size=128,
        num_hidden_layers=2, num_attention_heads=4, num_key_value_heads=2,
    )
    torch.manual_seed(0)
    m = transformers.Qwen2ForCausalLM(cfg_hf).eval()
    cfg, flat = from_hf_qwen2(m)
    block, pre, post = llama_spmd(cfg, 2)
    mesh = make_mesh(2, 1, devices=cpu_devices[:2])
    pipe = SpmdGPipe(block, 2, mesh, chunks=2, loss_fn=cross_entropy,
                     pre=pre, post=post)
    params = pipe.place({
        "pre": flat[0],
        # Stack the per-stage chain params (a 1-tuple of block dicts per
        # stage here) into the engine's [n_stages, ...] block layout.
        "blocks": jax.tree_util.tree_map(
            lambda *xs: jnp.stack(xs), *[(bp,) for bp in flat[1:-1]]
        ),
        "post": flat[-1],
    })
    tokens = jax.random.randint(jax.random.PRNGKey(1), (4, 8), 0, cfg.vocab)
    loss, grads = pipe.train_step(params, tokens, tokens)
    assert np.isfinite(float(loss))
    assert np.abs(np.asarray(grads["blocks"][0]["bq"])).sum() > 0


def test_bias_mismatch_rejected_and_mixed_window_imported():
    """A biased checkpoint through the plain Llama importer raises with a
    pointer at from_hf_qwen2; a Qwen2 config mixing windowed and full
    layers imports into the per-layer attention description, and the
    logits match HF at a sequence longer than the window."""
    from torchgpipe_tpu.models.hf_interop import from_hf_qwen2, params_from_hf
    from torchgpipe_tpu.models.transformer import AttnLayer

    cfg_hf = transformers.Qwen2Config(
        vocab_size=64, hidden_size=32, intermediate_size=128,
        num_hidden_layers=2, num_attention_heads=4, num_key_value_heads=2,
    )
    torch.manual_seed(0)
    m = transformers.Qwen2ForCausalLM(cfg_hf).eval()
    with pytest.raises(ValueError, match="from_hf_qwen2"):
        params_from_hf(m.state_dict(), config_from_hf(cfg_hf))

    mixed = transformers.Qwen2Config(
        vocab_size=64, hidden_size=32, intermediate_size=128,
        num_hidden_layers=4, num_attention_heads=4, num_key_value_heads=2,
        use_sliding_window=True, sliding_window=3, max_window_layers=2,
        attn_implementation="eager",
    )
    torch.manual_seed(0)
    m2 = transformers.Qwen2ForCausalLM(mixed).eval()
    cfg, params = from_hf_qwen2(m2)
    types = list(getattr(mixed, "layer_types", []))
    if "sliding_attention" in types and "full_attention" in types:
        theta = cfg.rope_theta
        assert cfg.attn_window is None and cfg.attn_layers == tuple(
            AttnLayer(3 if t == "sliding_attention" else None, theta)
            for t in types
        )
        b, s = 2, 9  # s > window
        tokens = np.arange(b * s).reshape(b, s) % cfg.vocab
        with torch.no_grad():
            ref = m2(torch.tensor(tokens)).logits.numpy()
        out, _ = sequential_apply(
            llama(cfg), params, [() for _ in range(cfg.n_layers + 2)],
            jnp.asarray(tokens, jnp.int32), rng=None, train=False,
        )
        np.testing.assert_allclose(
            np.asarray(out, np.float32), ref, rtol=2e-4, atol=2e-4
        )
    # else: a transformers version without mixed layer_types maps (or
    # ignores) the window uniformly, and the import above has worked.


def test_mistral_sliding_window_imported():
    """MistralForCausalLM (Llama layout + always-on sliding window, no
    max_window_layers gate): from_hf_llama maps the window and logits
    match HF at a sequence longer than it."""
    cfg_hf = transformers.MistralConfig(
        vocab_size=64, hidden_size=32, intermediate_size=128,
        num_hidden_layers=2, num_attention_heads=4, num_key_value_heads=2,
        sliding_window=3, attn_implementation="eager",
    )
    torch.manual_seed(0)
    m = transformers.MistralForCausalLM(cfg_hf).eval()
    cfg, params = from_hf_llama(m)
    assert cfg.attn_window == 3
    b, s = 2, 7  # s > window
    tokens = np.arange(b * s).reshape(b, s) % cfg.vocab
    with torch.no_grad():
        ref = m(torch.tensor(tokens)).logits.numpy()
    out, _ = sequential_apply(
        llama(cfg), params, [() for _ in range(cfg.n_layers + 2)],
        jnp.asarray(tokens, jnp.int32), rng=None, train=False,
    )
    np.testing.assert_allclose(
        np.asarray(out, np.float32), ref, rtol=2e-4, atol=2e-4
    )


def _gemma_model():
    cfg_hf = transformers.GemmaConfig(
        vocab_size=64, hidden_size=32, intermediate_size=128,
        num_hidden_layers=2, num_attention_heads=4, num_key_value_heads=2,
        head_dim=16, attn_implementation="eager",
    )
    torch.manual_seed(0)
    return transformers.GemmaForCausalLM(cfg_hf).eval(), cfg_hf


def test_gemma_decode_and_spmd_logits_match_hf(cpu_devices):
    """Gemma-1 import (explicit head_dim, GeGLU, sqrt(dim) embedding
    scale, (1+w) norms folded into scales, always-tied head): greedy
    decode matches the live GemmaForCausalLM, and the SPMD engine's
    apply (the tie-capable training path) reproduces its logits."""
    from torchgpipe_tpu.models.hf_interop import from_hf_gemma
    from torchgpipe_tpu.models.transformer import llama_spmd
    from torchgpipe_tpu.spmd import SpmdGPipe, make_mesh

    m, cfg_hf = _gemma_model()
    cfg, params = from_hf_gemma(m)
    assert cfg.n_head_dim == 16 and cfg.act == "gelu_tanh"
    assert cfg.tie_embeddings and "w" not in params[-1]

    b, s = 2, 7
    tokens = np.arange(b * s).reshape(b, s) % cfg.vocab
    with torch.no_grad():
        ref = m(torch.tensor(tokens)).logits.numpy()

    ours = np.asarray(generate(
        cfg, params, jnp.asarray(tokens, jnp.int32), max_new_tokens=3,
    ))
    with torch.no_grad():
        hf = m.generate(
            torch.tensor(tokens), max_new_tokens=3, do_sample=False,
        ).numpy()[:, s:]
    assert (ours == hf).all(), (ours, hf)

    # SPMD engine logits (pipe the two blocks over pp=2).
    from torchgpipe_tpu.models.generation import spmd_params_from_flat

    block, pre, post = llama_spmd(cfg, 2)
    mesh = make_mesh(2, 1, devices=cpu_devices[:2])
    pipe = SpmdGPipe(block, 2, mesh, chunks=2, loss_fn=cross_entropy_,
                     pre=pre, post=post)
    placed = spmd_params_from_flat(pipe, params)
    out = pipe.apply(placed, jnp.asarray(tokens, jnp.int32))
    np.testing.assert_allclose(
        np.asarray(out, np.float32), ref, rtol=2e-4, atol=2e-4
    )


def test_gemma_roundtrip_and_rejections():
    """Export shifts the norm scales back to HF's (1+w) convention and
    strict-loads into a live Gemma with logits unchanged; Gemma-2 class
    configs are rejected."""
    from torchgpipe_tpu.models.hf_interop import (
        from_hf_gemma,
        state_dict_to_hf,
    )

    m, cfg_hf = _gemma_model()
    cfg, params = from_hf_gemma(m)
    sd = state_dict_to_hf(params, cfg)
    assert "lm_head.weight" not in sd  # tied
    m2 = transformers.GemmaForCausalLM(cfg_hf)
    missing, unexpected = m2.load_state_dict(sd, strict=False)
    assert not unexpected, unexpected
    m2.tie_weights()
    b, s = 2, 6
    tokens = torch.tensor(np.arange(b * s).reshape(b, s) % cfg.vocab)
    with torch.no_grad():
        np.testing.assert_allclose(
            m2(tokens).logits.numpy(), m(tokens).logits.numpy(),
            rtol=1e-5, atol=1e-6,
        )

    if hasattr(transformers, "Gemma2ForCausalLM"):
        g2 = transformers.Gemma2Config(
            vocab_size=64, hidden_size=32, intermediate_size=128,
            num_hidden_layers=1, num_attention_heads=4,
            num_key_value_heads=2, head_dim=16,
        )
        torch.manual_seed(0)
        with pytest.raises(ValueError, match="Gemma-1"):
            from_hf_gemma(transformers.Gemma2ForCausalLM(g2))


def test_gemma_untie_and_exact_gelu_rejection():
    from torchgpipe_tpu.models.hf_interop import from_hf_gemma

    m, _ = _gemma_model()
    cfg, params = from_hf_gemma(m, untie=True)
    assert not cfg.tie_embeddings and "w" in params[-1]
    # Untied import runs the MPMD flat path end-to-end.
    b, s = 2, 6
    tokens = np.arange(b * s).reshape(b, s) % cfg.vocab
    with torch.no_grad():
        ref = m(torch.tensor(tokens)).logits.numpy()
    out, _ = sequential_apply(
        llama(cfg), params, [() for _ in range(cfg.n_layers + 2)],
        jnp.asarray(tokens, jnp.int32), rng=None, train=False,
    )
    np.testing.assert_allclose(
        np.asarray(out, np.float32), ref, rtol=2e-4, atol=2e-4
    )

    bad = transformers.GemmaConfig(
        vocab_size=64, hidden_size=32, intermediate_size=128,
        num_hidden_layers=1, num_attention_heads=4, num_key_value_heads=2,
        head_dim=16, hidden_activation="gelu",
    )
    torch.manual_seed(0)
    with pytest.raises(ValueError, match="tanh-approximate"):
        from_hf_gemma(transformers.GemmaForCausalLM(bad))


def test_gemma_bf16_norm_fold_keeps_precision():
    """bf16 Gemma checkpoints fold (1+w) in f32: tiny w must survive the
    import (bf16 near 1.0 would quantize |w| < ~2^-8 away) and export
    back exactly."""
    from torchgpipe_tpu.models.hf_interop import (
        from_hf_gemma,
        state_dict_to_hf,
    )

    m, _ = _gemma_model()
    m = m.to(torch.bfloat16)
    with torch.no_grad():
        # Gemma stores w (scale = 1 + w); make one entry tiny but nonzero.
        m.model.layers[0].input_layernorm.weight.fill_(0.001)
    cfg, params = from_hf_gemma(m)
    assert params[1]["ln1"].dtype == jnp.float32
    # f32 fold keeps the tiny shift (1.001 != 1.0 in f32; bf16 would
    # collapse it).
    assert float(jnp.max(jnp.abs(params[1]["ln1"] - 1.0))) > 5e-4
    sd = state_dict_to_hf(params, cfg)
    w = sd["model.layers.0.input_layernorm.weight"]
    assert w.dtype == torch.bfloat16
    np.testing.assert_allclose(
        w.to(torch.float32).numpy(),
        np.full((cfg.dim,), 0.001, np.float32),
        rtol=1e-2,
    )


def test_llama_explicit_head_dim_imported():
    """A LlamaConfig pinning head_dim != dim//n_heads imports via
    n_head_dim with logits matching the live model (modern HF attention
    honors the explicit head_dim)."""
    cfg_hf = transformers.LlamaConfig(
        vocab_size=64, hidden_size=32, intermediate_size=128,
        num_hidden_layers=2, num_attention_heads=4, num_key_value_heads=2,
        head_dim=16, attn_implementation="eager",
    )
    torch.manual_seed(0)
    m = transformers.LlamaForCausalLM(cfg_hf).eval()
    cfg, params = from_hf_llama(m)
    assert cfg.n_head_dim == 16 and cfg.head_dim == 16
    b, s = 2, 7
    tokens = np.arange(b * s).reshape(b, s) % cfg.vocab
    with torch.no_grad():
        ref = m(torch.tensor(tokens)).logits.numpy()
    out, _ = sequential_apply(
        llama(cfg), params, [() for _ in range(cfg.n_layers + 2)],
        jnp.asarray(tokens, jnp.int32), rng=None, train=False,
    )
    np.testing.assert_allclose(
        np.asarray(out, np.float32), ref, rtol=2e-4, atol=2e-4
    )


def test_qwen3_logits_decode_roundtrip():
    """Qwen3 import (per-head q/k RMSNorm + explicit head_dim + tie):
    logits and greedy decode match the live Qwen3ForCausalLM; the export
    round-trips the q/k norm weights."""
    from torchgpipe_tpu.models.hf_interop import (
        from_hf_qwen3,
        state_dict_to_hf,
    )

    if not hasattr(transformers, "Qwen3ForCausalLM"):
        pytest.skip("transformers too old for Qwen3")
    cfg_hf = transformers.Qwen3Config(
        vocab_size=64, hidden_size=32, intermediate_size=128,
        num_hidden_layers=2, num_attention_heads=4, num_key_value_heads=2,
        head_dim=16, tie_word_embeddings=True, attn_implementation="eager",
    )
    torch.manual_seed(0)
    m = transformers.Qwen3ForCausalLM(cfg_hf).eval()
    cfg, params = from_hf_qwen3(m)
    assert cfg.qk_norm and cfg.n_head_dim == 16 and cfg.tie_embeddings
    assert "qn" in params[1] and "w" not in params[-1]

    b, s = 2, 7
    tokens = np.arange(b * s).reshape(b, s) % cfg.vocab
    with torch.no_grad():
        ref = m(torch.tensor(tokens)).logits.numpy()
    ours = np.asarray(generate(
        cfg, params, jnp.asarray(tokens, jnp.int32), max_new_tokens=3,
    ))
    with torch.no_grad():
        hf = m.generate(
            torch.tensor(tokens), max_new_tokens=3, do_sample=False,
        ).numpy()[:, s:]
    assert (ours == hf).all(), (ours, hf)
    # First-token parity doubles as a logits check through the tied head.
    np.testing.assert_array_equal(ours[:, 0], ref[:, -1].argmax(-1))

    sd = state_dict_to_hf(params, cfg)
    assert "model.layers.0.self_attn.q_norm.weight" in sd
    m2 = transformers.Qwen3ForCausalLM(cfg_hf)
    missing, unexpected = m2.load_state_dict(sd, strict=False)
    assert not unexpected, unexpected
    m2.tie_weights()
    with torch.no_grad():
        got = m2(torch.tensor(tokens)).logits.numpy()
    np.testing.assert_allclose(got, ref, rtol=1e-5, atol=1e-6)


def test_qwen3_untied_trains_mpmd():
    from torchgpipe_tpu.models.hf_interop import from_hf_qwen3
    from torchgpipe_tpu.gpipe import GPipe
    from torchgpipe_tpu.models.transformer import cross_entropy

    if not hasattr(transformers, "Qwen3ForCausalLM"):
        pytest.skip("transformers too old for Qwen3")
    cfg_hf = transformers.Qwen3Config(
        vocab_size=64, hidden_size=32, intermediate_size=128,
        num_hidden_layers=2, num_attention_heads=4, num_key_value_heads=2,
        head_dim=16, tie_word_embeddings=True,
    )
    torch.manual_seed(0)
    m = transformers.Qwen3ForCausalLM(cfg_hf).eval()
    cfg, flat = from_hf_qwen3(m, untie=True)
    model = GPipe(llama(cfg), balance=[2, 2], chunks=2)
    spec = jax.ShapeDtypeStruct((4, 8), jnp.int32)
    params, state = model.init(jax.random.PRNGKey(0), spec)
    it = iter(flat)
    params = model.place(
        tuple(tuple(next(it) for _ in stage) for stage in params)
    )
    x = jnp.asarray(np.arange(32).reshape(4, 8) % cfg.vocab, jnp.int32)
    loss, grads, state, _ = model.value_and_grad(
        params, state, x, x, cross_entropy
    )
    assert np.isfinite(float(loss))
    # qk-norm weights receive gradients.
    qn_grads = [
        g["qn"] for st in grads for g in st
        if isinstance(g, dict) and "qn" in g
    ]
    assert qn_grads and sum(
        float(jnp.abs(g).sum()) for g in qn_grads
    ) > 0


def test_qwen3_through_wrong_importer_rejected():
    if not hasattr(transformers, "Qwen3ForCausalLM"):
        pytest.skip("transformers too old for Qwen3")
    from torchgpipe_tpu.models.hf_interop import from_hf_qwen2

    cfg_hf = transformers.Qwen3Config(
        vocab_size=64, hidden_size=32, intermediate_size=128,
        num_hidden_layers=1, num_attention_heads=4, num_key_value_heads=2,
        head_dim=16,
    )
    torch.manual_seed(0)
    m = transformers.Qwen3ForCausalLM(cfg_hf).eval()
    with pytest.raises(ValueError, match="from_hf_qwen3"):
        from_hf_qwen2(m)
