"""HuggingFace Llama checkpoint import.

The practical on-ramp for "switch to this framework": weights trained or
published in the HF ``LlamaForCausalLM`` layout load straight into the
``models.transformer.llama`` schema — pipeline-train them with either
engine or decode with :mod:`torchgpipe_tpu.models.generation`.  (The
reference has no interop story at all; this is surplus capability.)

Conventions verified against ``transformers`` (tested numerically in
``tests/test_hf_interop.py`` — logits match a live HF model):

* torch ``Linear`` stores ``[out, in]`` → every projection transposes;
* HF ``rotate_half`` rotary == this repo's half-split ``_rope`` (same
  frequency layout ``cat(freqs, freqs)``);
* GQA query→kv pairing ``h // (nh/nkv)`` matches;
* ``RMSNorm`` math (f32 accumulation, eps inside rsqrt) matches.

Eleven families, one importer each (see docs/migration.md for the
matrix; every mapping is verified numerically against the live
``transformers`` model in CI):

* decoder / RMSNorm+rotary class: Llama 1-3 + Mistral (sliding window)
  via :func:`from_hf_llama`; Qwen2 (:func:`from_hf_qwen2`, q/k/v
  biases); Qwen3 (:func:`from_hf_qwen3`, per-head q/k norms); Gemma 1
  (:func:`from_hf_gemma`, GeGLU/scaled embeddings/folded norms);
  Mixtral MoE (:func:`from_hf_mixtral`, dropless dispatch — HF's
  renormalized top-k IS the GShard gate normalization for k >= 2);
* decoder / classic class: GPT-2 (:func:`from_hf_gpt2` — LayerNorm,
  learned positions, fused ``c_attn``, Conv1D orientation), GPT-NeoX/
  Pythia (:func:`from_hf_neox` — partial rotary, parallel residual,
  per-head-interleaved qkv), OPT (:func:`from_hf_opt` — offset position
  table, relu);
* encoder class: BERT (:func:`from_hf_bert` — post-norm blocks,
  embedding LayerNorm, bidirectional) and RoBERTa
  (:func:`from_hf_roberta` — + reserved position rows).

f32/bf16 checkpoints import at their own width (no fused/quantized HF
layouts); decoder families also EXPORT back via their
``state_dict_to_hf*`` mirrors.
"""

from __future__ import annotations

import math
from typing import Any, Dict, List, Tuple

import jax.numpy as jnp

from torchgpipe_tpu.models.transformer import TransformerConfig

Pytree = Any


def config_from_hf(hf_config: Any) -> TransformerConfig:
    """A :class:`TransformerConfig` equivalent to an HF ``LlamaConfig``.

    ``mlp_hidden`` is derived from ``mlp_ratio`` here, so the HF
    ``intermediate_size`` must round-trip through the SwiGLU 2/3 formula
    (every published Llama size does — they are multiples of 128); a
    size that cannot be expressed raises instead of silently reshaping.
    """
    dim = hf_config.hidden_size
    inter = hf_config.intermediate_size
    ratio = 3.0 * inter / (2.0 * dim)
    cfg = TransformerConfig(
        vocab=hf_config.vocab_size,
        dim=dim,
        n_layers=hf_config.num_hidden_layers,
        n_heads=hf_config.num_attention_heads,
        n_kv_heads=getattr(hf_config, "num_key_value_heads", None),
        mlp_ratio=ratio,
        rope_theta=float(getattr(hf_config, "rope_theta", 10000.0)),
        norm_eps=float(hf_config.rms_norm_eps),
        # Llama-3.2-class checkpoints tie the lm head to the embedding;
        # imported as this framework's native tie (one table, shared),
        # not an untied copy.
        tie_embeddings=bool(
            getattr(hf_config, "tie_word_embeddings", False)
        ),
        # LlamaConfig.attention_bias; Qwen2 hardcodes q/k/v biases with
        # no config attribute — from_hf_qwen2 flips this from the state
        # dict instead.
        attn_bias=bool(getattr(hf_config, "attention_bias", False)),
        # Mistral-class configs carry sliding_window (default 4096, every
        # layer windowed, no max_window_layers) — ignoring it would
        # silently diverge from HF past the window.  HF masks keys with
        # q - k >= sliding_window, exactly this attn_window band (attend
        # iff 0 <= q - k < window).  Qwen2's gated per-layer variant is
        # handled by from_hf_qwen2 instead.
        attn_window=(
            int(hf_config.sliding_window)
            if getattr(hf_config, "sliding_window", None)
            and not hasattr(hf_config, "max_window_layers")
            else None
        ),
        # Modern HF configs may pin head_dim explicitly (and the HF
        # attention honors it); silently deriving dim//n_heads would
        # mis-shape the heads with no error when the sizes still divide.
        n_head_dim=(
            int(hf_config.head_dim)
            if getattr(hf_config, "head_dim", None)
            and int(hf_config.head_dim)
            != dim // hf_config.num_attention_heads
            else None
        ),
    )
    if cfg.mlp_hidden != inter:
        raise ValueError(
            f"intermediate_size={inter} cannot be expressed by this "
            f"config's 128-aligned SwiGLU formula (got {cfg.mlp_hidden}); "
            "published Llama sizes are 128-aligned — is this a custom "
            "checkpoint?"
        )
    return cfg


def _from_torch(w: Any) -> jnp.ndarray:
    """torch/array-like -> jnp, dtype-faithful.

    torch cannot hand numpy a bf16 array, so bf16 tensors bridge through
    f32 (lossless) and land as jnp.bfloat16 — published bf16 checkpoints
    import at their own width, matching the export side's
    ``_torch_cast``."""
    import numpy as np

    if hasattr(w, "detach"):
        w = w.detach().cpu()
        if str(w.dtype) == "torch.bfloat16":
            return jnp.asarray(w.float().numpy(), jnp.bfloat16)
        return jnp.asarray(w.numpy())
    return jnp.asarray(np.asarray(w))


def _t(w: Any) -> jnp.ndarray:
    """torch [out, in] -> jnp [in, out]."""
    return _from_torch(w).T


def _v(w: Any) -> jnp.ndarray:
    return _from_torch(w)


def _torch_cast(a: jnp.ndarray) -> Any:
    """Dtype-faithful jnp -> torch: numpy-native dtypes (f16/f32/f64)
    convert directly; only bfloat16 — which numpy lacks — bridges through
    f32 (lossless: every bf16 value is exactly representable) and is cast
    back on the torch side.  Exports are the same width and values as the
    import, never silently widened to f32."""
    import numpy as np
    import torch

    if jnp.dtype(a.dtype).name == "bfloat16":
        return torch.from_numpy(np.asarray(a, np.float32)).to(torch.bfloat16)
    # .copy(): np.asarray of a jax array can be a read-only view;
    # torch.from_numpy shares memory and warns on non-writable input.
    return torch.from_numpy(np.asarray(a).copy())


def _torch_t(a: jnp.ndarray) -> Any:  # jnp [in, out] -> torch [out, in]
    return _torch_cast(a.T)


def _torch_v(a: jnp.ndarray) -> Any:
    return _torch_cast(a)


def _check_attn_param_consistency(
    sd: Dict[str, Any], cfg: TransformerConfig
) -> None:
    """``cfg.attn_bias`` / ``cfg.qk_norm`` must agree with the
    checkpoint: a silent mismatch would either drop trained weights or
    leave a params tree the engines' specs (gated on the cfg) don't
    cover."""
    has = "model.layers.0.self_attn.q_proj.bias" in sd
    if has and not cfg.attn_bias:
        raise ValueError(
            "this checkpoint carries q/k/v projection biases but "
            "cfg.attn_bias is False — import Qwen2-family models with "
            "from_hf_qwen2 (which detects them), or set "
            "TransformerConfig(attn_bias=True)"
        )
    if cfg.attn_bias and not has:
        raise ValueError(
            "cfg.attn_bias=True but the checkpoint has no q/k/v "
            "projection biases"
        )
    has_qk = "model.layers.0.self_attn.q_norm.weight" in sd
    if has_qk and not cfg.qk_norm:
        raise ValueError(
            "this checkpoint carries per-head q/k norms but "
            "cfg.qk_norm is False — import Qwen3-family models with "
            "from_hf_qwen3 (which sets it), or set "
            "TransformerConfig(qk_norm=True); importing without them "
            "would silently drop trained weights"
        )
    if cfg.qk_norm and not has_qk:
        raise ValueError(
            "cfg.qk_norm=True but the checkpoint has no q/k norm weights"
        )


def _attn_entries(
    sd: Dict[str, Any], p: str, cfg: TransformerConfig
) -> Dict[str, jnp.ndarray]:
    """The per-block attention + norm mapping shared by the Llama and
    Mixtral importers (identical layouts; only the MLP differs).
    Q/K/V biases (Llama ``attention_bias`` / the always-biased Qwen2
    family) map to ``bq/bk/bv`` under ``cfg.attn_bias`` — the same gate
    ``transformer_block`` inits and shards by, kept consistent with the
    checkpoint by ``_check_attn_param_consistency``."""
    out = {
        "ln1": _v(sd[p + "input_layernorm.weight"]),
        "wq": _t(sd[p + "self_attn.q_proj.weight"]),
        "wk": _t(sd[p + "self_attn.k_proj.weight"]),
        "wv": _t(sd[p + "self_attn.v_proj.weight"]),
        "wo": _t(sd[p + "self_attn.o_proj.weight"]),
        "ln2": _v(sd[p + "post_attention_layernorm.weight"]),
    }
    if cfg.attn_bias:
        out["bq"] = _v(sd[p + "self_attn.q_proj.bias"])
        out["bk"] = _v(sd[p + "self_attn.k_proj.bias"])
        out["bv"] = _v(sd[p + "self_attn.v_proj.bias"])
    if cfg.qk_norm:
        out["qn"] = _v(sd[p + "self_attn.q_norm.weight"])
        out["kn"] = _v(sd[p + "self_attn.k_norm.weight"])
    return out


def _head_entry(
    sd: Dict[str, Any], cfg: TransformerConfig, embed: Pytree
) -> Pytree:
    """Final-norm + head mapping shared by both importers, honoring the
    tie: a tied cfg's head carries the SAME array as the embedding
    (decode reads it via ``_head_w``; the SPMD engine splices it via
    ``meta['tie_pre']`` — no duplicated ``[vocab, dim]`` table)."""
    if cfg.tie_embeddings:
        return {
            "scale": _v(sd["model.norm.weight"]),
            "table": embed["table"],
        }
    head_w = (
        sd["lm_head.weight"]
        if "lm_head.weight" in sd
        else sd["model.embed_tokens.weight"]  # tied ckpt, untied cfg
    )
    return {"scale": _v(sd["model.norm.weight"]), "w": _t(head_w)}


def params_from_hf(
    state_dict: Dict[str, Any], cfg: TransformerConfig
) -> List[Pytree]:
    """Per-layer params in ``llama(cfg)`` order (embed, blocks, head) from
    an HF ``LlamaForCausalLM`` state dict."""
    if any(".block_sparse_moe." in k or ".experts." in k for k in state_dict):
        raise ValueError(
            "MoE (Mixtral-style) HF layout: use from_hf_mixtral / "
            "params_from_hf_mixtral (imports into the llama_moe family); "
            "this importer covers the dense Llama family"
        )
    _check_attn_param_consistency(state_dict, cfg)
    sd = state_dict
    out: List[Pytree] = [{"table": _v(sd["model.embed_tokens.weight"])}]
    for i in range(cfg.n_layers):
        p = f"model.layers.{i}."
        out.append({
            **_attn_entries(sd, p, cfg),
            "w_gate": _t(sd[p + "mlp.gate_proj.weight"]),
            "w_up": _t(sd[p + "mlp.up_proj.weight"]),
            "w_down": _t(sd[p + "mlp.down_proj.weight"]),
        })
    out.append(_head_entry(sd, cfg, out[0]))
    return out


def from_hf_llama(model: Any, *, untie: bool = False) -> tuple:
    """(cfg, per-layer params) from a live HF ``LlamaForCausalLM`` — ready
    for ``GPipe(llama(cfg))`` init-splicing or ``generation.generate``.

    ``tie_word_embeddings`` checkpoints (the Llama-3.2 class) import as
    the framework's NATIVE tie by default (one shared table; SPMD-engine
    training + decode).  The MPMD ``GPipe(llama(cfg))`` path cannot
    express the tie — pass ``untie=True`` to import such a checkpoint as
    an untied COPY (head ``w = table.T``, independently trainable), the
    layout every engine accepts."""
    import dataclasses

    cfg = config_from_hf(model.config)
    if untie and cfg.tie_embeddings:
        cfg = dataclasses.replace(cfg, tie_embeddings=False)
    return cfg, params_from_hf(model.state_dict(), cfg)


def _export_common(
    params: List[Pytree], cfg: TransformerConfig
) -> Tuple[Dict[str, Any], List[Pytree]]:
    """Embed/norm/head export + per-block attention keys shared by the
    Llama and Mixtral exporters (mirror of ``_attn_entries``/
    ``_head_entry`` on the import side).  Returns the partially-filled
    state dict and the block param list; tied heads (no ``'w'``) omit
    ``lm_head.weight`` — HF tied checkpoints share the embedding tensor
    itself."""
    t, v = _torch_t, _torch_v
    embed, blocks, head = params[0], params[1:-1], params[-1]
    if len(blocks) != cfg.n_layers:
        raise ValueError(
            f"expected {cfg.n_layers} block params, got {len(blocks)}"
        )
    if any(isinstance(bp, dict) and "lora" in bp for bp in blocks):
        raise ValueError(
            "block params carry unmerged 'lora' adapters; exporting "
            "would silently publish the BASE model without the "
            "fine-tune — fold them first with models.lora.merge_lora"
        )
    sd: Dict[str, Any] = {
        "model.embed_tokens.weight": v(embed["table"]),
        "model.norm.weight": v(head["scale"]),
    }
    if "w" in head:
        sd["lm_head.weight"] = t(head["w"])
    for i, bp in enumerate(blocks):
        p = f"model.layers.{i}."
        sd[p + "input_layernorm.weight"] = v(bp["ln1"])
        sd[p + "self_attn.q_proj.weight"] = t(bp["wq"])
        sd[p + "self_attn.k_proj.weight"] = t(bp["wk"])
        sd[p + "self_attn.v_proj.weight"] = t(bp["wv"])
        sd[p + "self_attn.o_proj.weight"] = t(bp["wo"])
        sd[p + "post_attention_layernorm.weight"] = v(bp["ln2"])
        if "bq" in bp:
            sd[p + "self_attn.q_proj.bias"] = v(bp["bq"])
            sd[p + "self_attn.k_proj.bias"] = v(bp["bk"])
            sd[p + "self_attn.v_proj.bias"] = v(bp["bv"])
        if "qn" in bp:
            sd[p + "self_attn.q_norm.weight"] = v(bp["qn"])
            sd[p + "self_attn.k_norm.weight"] = v(bp["kn"])
    return sd, blocks


def from_hf_qwen2(model: Any, *, untie: bool = False) -> tuple:
    """(cfg, per-layer params) from a live HF ``Qwen2ForCausalLM``.

    The Qwen2 family is the Llama layout plus always-on q/k/v projection
    biases (hardcoded in the HF implementation, no config attribute) and
    an optional sliding window — both detected here and mapped onto
    ``attn_bias`` / ``attn_window``.  Everything else (RMSNorm, SwiGLU,
    rotary, GQA, tying) flows through the Llama importer unchanged.

    HF Qwen2 windows only the layers past ``max_window_layers``
    (``config.layer_types``): a layout in which every layer is windowed
    maps to the model-global ``attn_window``, a mixed one to the per-layer
    description ``attn_layers`` (training path; generation and serving
    refuse a mixed period)."""
    import dataclasses

    hfc = model.config
    cfg = config_from_hf(hfc)
    sd = model.state_dict()
    if "model.layers.0.self_attn.q_proj.bias" in sd and not cfg.attn_bias:
        cfg = dataclasses.replace(cfg, attn_bias=True)
    cfg = _apply_qwen_window(cfg, hfc)
    if untie and cfg.tie_embeddings:
        cfg = dataclasses.replace(cfg, tie_embeddings=False)
    return cfg, params_from_hf(sd, cfg)


def _attn_period(types: List[str], entries: Dict[str, Any]) -> tuple:
    """The shortest repeating period of ``layer_types`` as
    ``TransformerConfig.attn_layers``: layer ``i`` is of type
    ``types[i % len(period)]``, each type's entry from ``entries``."""
    unknown = sorted(set(types) - set(entries))
    if unknown:
        raise ValueError(
            f"layer_types {unknown} are not computed here "
            f"({sorted(entries)} are)"
        )
    period = next(
        p for p in range(1, len(types) + 1)
        if all(t == types[i % p] for i, t in enumerate(types))
    )
    return tuple(entries[t] for t in types[:period])


def _apply_qwen_window(
    cfg: TransformerConfig, hfc: Any
) -> TransformerConfig:
    """Qwen-family sliding windows (``layer_types``: the layers past
    ``max_window_layers`` are windowed): the model-global ``attn_window``
    when EVERY layer is windowed, else the per-layer description
    ``attn_layers`` with the published layout as its period."""
    import dataclasses

    from torchgpipe_tpu.models.transformer import AttnLayer

    if not (
        getattr(hfc, "use_sliding_window", False)
        and getattr(hfc, "sliding_window", None)
    ):
        return cfg
    types = list(
        getattr(hfc, "layer_types", None)
        or ["sliding_attention"] * cfg.n_layers
    )
    window = int(hfc.sliding_window)
    if all(t == "sliding_attention" for t in types):
        return dataclasses.replace(cfg, attn_window=window)
    if not any(t == "sliding_attention" for t in types):
        return cfg  # every layer full attention — nothing to map
    return dataclasses.replace(cfg, attn_layers=_attn_period(types, {
        "sliding_attention": AttnLayer(window, cfg.rope_theta),
        "full_attention": AttnLayer(None, cfg.rope_theta),
    }))


def from_hf_qwen3(model: Any, *, untie: bool = False) -> tuple:
    """(cfg, per-layer params) from a live HF ``Qwen3ForCausalLM``.

    Qwen3 is the Llama layout plus per-head q/k RMSNorm before rotary
    (``qk_norm`` -> params ``qn``/``kn``), an explicit ``head_dim``
    (auto-wired by :func:`config_from_hf`), no projection biases, and
    tied embeddings on the small sizes.  Sliding windows follow the
    Qwen2 rule (``max_window_layers``-gated; mixed layouts rejected by
    the shared helper)."""
    import dataclasses

    hfc = model.config
    cfg = config_from_hf(hfc)
    cfg = dataclasses.replace(cfg, qk_norm=True)
    cfg = _apply_qwen_window(cfg, hfc)
    if untie and cfg.tie_embeddings:
        cfg = dataclasses.replace(cfg, tie_embeddings=False)
    return cfg, params_from_hf(model.state_dict(), cfg)


def from_hf_gemma(model: Any, *, untie: bool = False) -> tuple:
    """(cfg, per-layer params) from a live HF ``GemmaForCausalLM``
    (Gemma 1).

    Gemma differences, each mapped onto an existing config knob:

    * explicit ``head_dim`` (n_heads*head_dim != dim on the 7B) ->
      ``n_head_dim``;
    * GeGLU feed-forward -> ``act='gelu_tanh'``;
    * embeddings scaled by sqrt(dim) -> ``embed_scale`` (the tied head
      reads the unscaled table, as HF does);
    * RMSNorm computes ``x_norm * (1 + w)`` -> folded into the stored
      scales at import (``scale = 1 + w``; fresh-init equivalence holds:
      this framework inits scales to 1, Gemma inits w to 0) and
      subtracted back by :func:`state_dict_to_hf` under
      ``cfg.act == 'gelu_tanh'``;
    * always-tied head -> the native tie.

    Gemma-2/3 (attention softcapping, pre+post block norms, alternating
    windows) are NOT this layout and are rejected, as are checkpoints
    configured with EXACT gelu (``hidden_activation='gelu'``) — this
    family computes the tanh approximation only, and a silent substitute
    would drift.  ``untie=True`` imports an untied copy (head
    ``w = table.T``) for the MPMD ``GPipe(llama(cfg))`` path, like the
    sibling importers."""
    import dataclasses
    import math

    hfc = model.config
    if type(hfc).__name__ not in ("GemmaConfig",):
        raise ValueError(
            f"from_hf_gemma supports the Gemma-1 layout (GemmaConfig); "
            f"got {type(hfc).__name__} — Gemma-2/3 add softcapping and "
            "post-block norms this model family does not compute"
        )
    act_attr = getattr(hfc, "hidden_activation", None) or getattr(
        hfc, "hidden_act", None
    )
    if act_attr not in (None, "gelu_pytorch_tanh"):
        raise ValueError(
            f"this Gemma checkpoint is configured with "
            f"hidden_activation={act_attr!r}; only the tanh-approximate "
            "gelu ('gelu_pytorch_tanh', the published Gemma convention) "
            "is computed here — a silent substitute would drift"
        )
    cfg = config_from_hf(hfc)
    cfg = dataclasses.replace(
        cfg,
        n_head_dim=int(hfc.head_dim),
        act="gelu_tanh",
        embed_scale=math.sqrt(hfc.hidden_size),
        tie_embeddings=not untie,  # Gemma always ties; untie for MPMD
    )
    params = params_from_hf(model.state_dict(), cfg)
    return cfg, _fold_gemma_norms(params, 1.0)


def _fold_gemma_norms(
    params: List[Pytree], sign: float, dtype: Any = jnp.float32
) -> List[Pytree]:
    """Shift every RMSNorm scale by ``sign`` (+1 on import: Gemma stores
    ``w`` with ``x_norm * (1 + w)``; -1 on export).

    Always computed and (by default) STORED in f32: HF's GemmaRMSNorm
    evaluates ``1 + w.float()`` in f32 at runtime, so folding a bf16
    ``w`` into a bf16 scale would quantize away any ``|w| < ~2^-8``
    (bf16's resolution near 1.0).  f32 norm scales are also this
    framework's own precision-policy convention.  The export path passes
    the checkpoint's dtype so ``w = scale - 1`` goes back at the
    original width."""
    shift = lambda a: (  # noqa: E731
        a.astype(jnp.float32) + jnp.float32(sign)
    ).astype(dtype)
    out = [params[0]]
    for bp in params[1:-1]:
        bp = dict(bp, ln1=shift(bp["ln1"]), ln2=shift(bp["ln2"]))
        out.append(bp)
    head = dict(params[-1])
    head["scale"] = shift(head["scale"])
    out.append(head)
    return out


def state_dict_to_hf(
    params: List[Pytree], cfg: TransformerConfig
) -> Dict[str, Any]:
    """The inverse map: ``llama(cfg)`` per-layer params -> an HF
    ``LlamaForCausalLM`` state dict (torch tensors) — train here,
    publish to the HF ecosystem.  Exact inverse of
    :func:`params_from_hf` (round-trip tested; Gemma-family params —
    ``cfg.act == 'gelu_tanh'`` — get their norm scales shifted back to
    HF's ``1 + w`` convention)."""
    if cfg.act == "gelu_tanh":
        # w = scale - 1 back at the checkpoint's uniform dtype.
        params = _fold_gemma_norms(
            params, -1.0, dtype=params[0]["table"].dtype
        )
    t = _torch_t
    sd, blocks = _export_common(params, cfg)
    for i, bp in enumerate(blocks):
        p = f"model.layers.{i}."
        sd[p + "mlp.gate_proj.weight"] = t(bp["w_gate"])
        sd[p + "mlp.up_proj.weight"] = t(bp["w_up"])
        sd[p + "mlp.down_proj.weight"] = t(bp["w_down"])
    return sd


def config_from_hf_gpt2(hf_config: Any) -> TransformerConfig:
    """A :class:`TransformerConfig` equivalent to an HF ``GPT2Config`` —
    the classic architecture: LayerNorm (centered, biased), learned
    absolute positions, biased projections, a non-gated 4x gelu MLP, and
    an always-tied head."""
    dim = hf_config.n_embd
    inner = getattr(hf_config, "n_inner", None) or 4 * dim
    act = getattr(hf_config, "activation_function", "gelu_new")
    act_map = {"gelu_new": "gelu_tanh", "gelu_pytorch_tanh": "gelu_tanh",
               "gelu": "gelu"}
    if act not in act_map:
        raise ValueError(
            f"GPT-2 activation_function={act!r} is not computed here "
            "(gelu_new / gelu_pytorch_tanh / gelu are)"
        )
    # Published attention variants this framework does not compute — a
    # silent import would make every logit wrong with no error (the
    # sibling importers' didactic-rejection discipline).
    for knob in ("scale_attn_by_inverse_layer_idx", "reorder_and_upcast_attn"):
        if getattr(hf_config, knob, False):
            raise ValueError(
                f"this GPT-2 checkpoint sets {knob}=True; that attention "
                "variant (per-layer score scaling / upcast-reordered "
                "matmul) is not computed here — importing would silently "
                "diverge from HF"
            )
    cfg = TransformerConfig(
        vocab=hf_config.vocab_size,
        dim=dim,
        n_layers=hf_config.n_layer,
        n_heads=hf_config.n_head,
        n_kv_heads=None,                       # MHA
        mlp_ratio=inner / dim,
        norm_eps=float(hf_config.layer_norm_epsilon),
        norm="layernorm",
        pos_emb="learned",
        max_pos=int(hf_config.n_positions),
        mlp_impl="classic",
        act=act_map[act],
        attn_bias=True,
        attn_out_bias=True,
        tie_embeddings=True,                   # GPT-2 always ties
    )
    if cfg.mlp_hidden != inner:
        raise ValueError(
            f"n_inner={inner} did not survive the mlp_ratio round-trip "
            f"(got {cfg.mlp_hidden}) — custom checkpoint?"
        )
    return cfg


def params_from_hf_gpt2(
    state_dict: Dict[str, Any], cfg: TransformerConfig
) -> List[Pytree]:
    """Per-layer params in ``llama(cfg)`` order from a
    ``GPT2LMHeadModel`` state dict.

    Layout notes (verified numerically in ``tests/test_gpt2_interop.py``):
    HF GPT-2 uses ``Conv1D`` modules whose weights are ALREADY
    ``[in, out]`` (unlike ``Linear``'s ``[out, in]``), so projections map
    without transposing; ``c_attn`` is the fused ``[dim, 3*dim]`` q/k/v
    projection, split here; the per-head layout of each third matches
    this framework's ``[..., n_heads, head_dim]`` reshape.  The
    ``attn.bias`` causal-mask buffers in the state dict are masks, not
    parameters, and are ignored."""
    sd = state_dict
    dim = cfg.dim
    embed: Dict[str, Any] = {
        "table": _v(sd["transformer.wte.weight"]),
        "pos": _v(sd["transformer.wpe.weight"]),
    }
    out: List[Pytree] = [embed]
    for i in range(cfg.n_layers):
        p = f"transformer.h.{i}."
        ca_w = _v(sd[p + "attn.c_attn.weight"])   # [dim, 3*dim]
        ca_b = _v(sd[p + "attn.c_attn.bias"])     # [3*dim]
        out.append({
            "ln1": _v(sd[p + "ln_1.weight"]),
            "ln1b": _v(sd[p + "ln_1.bias"]),
            "wq": ca_w[:, :dim],
            "wk": ca_w[:, dim : 2 * dim],
            "wv": ca_w[:, 2 * dim :],
            "bq": ca_b[:dim],
            "bk": ca_b[dim : 2 * dim],
            "bv": ca_b[2 * dim :],
            "wo": _v(sd[p + "attn.c_proj.weight"]),
            "bo": _v(sd[p + "attn.c_proj.bias"]),
            "ln2": _v(sd[p + "ln_2.weight"]),
            "ln2b": _v(sd[p + "ln_2.bias"]),
            "w_fc": _v(sd[p + "mlp.c_fc.weight"]),
            "b_fc": _v(sd[p + "mlp.c_fc.bias"]),
            "w_proj": _v(sd[p + "mlp.c_proj.weight"]),
            "b_proj": _v(sd[p + "mlp.c_proj.bias"]),
        })
    head: Dict[str, Any] = {
        "scale": _v(sd["transformer.ln_f.weight"]),
        "bias": _v(sd["transformer.ln_f.bias"]),
    }
    if cfg.tie_embeddings:
        head["table"] = embed["table"]
    else:
        head["w"] = embed["table"].T  # untied copy for the MPMD path
    out.append(head)
    return out


def from_hf_gpt2(model: Any, *, untie: bool = False) -> tuple:
    """(cfg, per-layer params) from a live HF ``GPT2LMHeadModel`` —
    the classic-architecture on-ramp (GPT-2 and its layout family).
    ``untie=True`` imports the always-tied head as an untied copy for
    the MPMD ``GPipe(llama(cfg))`` path, like the sibling importers."""
    import dataclasses

    cfg = config_from_hf_gpt2(model.config)
    if untie:
        cfg = dataclasses.replace(cfg, tie_embeddings=False)
    return cfg, params_from_hf_gpt2(model.state_dict(), cfg)


def state_dict_to_hf_gpt2(
    params: List[Pytree], cfg: TransformerConfig
) -> Dict[str, Any]:
    """Export back to the ``GPT2LMHeadModel`` layout (mirror of
    :func:`params_from_hf_gpt2`; Conv1D weights stay ``[in, out]``, the
    fused ``c_attn`` is re-concatenated).  Tied heads omit
    ``lm_head.weight`` — HF shares the embedding tensor itself.  An
    UNTIED export (head ``w`` trained away from the table, e.g. after
    ``untie=True`` fine-tuning) carries ``lm_head.weight``; load it into
    a ``GPT2Config(tie_word_embeddings=False)`` model — the default tied
    config would re-tie on load and silently discard the trained head."""
    v = _torch_v
    embed, blocks, head = params[0], params[1:-1], params[-1]
    if len(blocks) != cfg.n_layers:
        raise ValueError(
            f"expected {cfg.n_layers} block params, got {len(blocks)}"
        )
    sd: Dict[str, Any] = {
        "transformer.wte.weight": v(embed["table"]),
        "transformer.wpe.weight": v(embed["pos"]),
        "transformer.ln_f.weight": v(head["scale"]),
        "transformer.ln_f.bias": v(head["bias"]),
    }
    if "w" in head:
        sd["lm_head.weight"] = _torch_t(head["w"])
    for i, bp in enumerate(blocks):
        p = f"transformer.h.{i}."
        sd[p + "ln_1.weight"] = v(bp["ln1"])
        sd[p + "ln_1.bias"] = v(bp["ln1b"])
        sd[p + "attn.c_attn.weight"] = v(
            jnp.concatenate([bp["wq"], bp["wk"], bp["wv"]], axis=1)
        )
        sd[p + "attn.c_attn.bias"] = v(
            jnp.concatenate([bp["bq"], bp["bk"], bp["bv"]])
        )
        sd[p + "attn.c_proj.weight"] = v(bp["wo"])
        sd[p + "attn.c_proj.bias"] = v(bp["bo"])
        sd[p + "ln_2.weight"] = v(bp["ln2"])
        sd[p + "ln_2.bias"] = v(bp["ln2b"])
        sd[p + "mlp.c_fc.weight"] = v(bp["w_fc"])
        sd[p + "mlp.c_fc.bias"] = v(bp["b_fc"])
        sd[p + "mlp.c_proj.weight"] = v(bp["w_proj"])
        sd[p + "mlp.c_proj.bias"] = v(bp["b_proj"])
    return sd


def config_from_hf_neox(hf_config: Any) -> TransformerConfig:
    """A :class:`TransformerConfig` equivalent to an HF ``GPTNeoXConfig``
    (the Pythia family): LayerNorm + biased projections + classic MLP
    like GPT-2, but ROTARY positions — usually PARTIAL
    (``rotary_pct=0.25`` on every published Pythia) — and the
    ``use_parallel_residual`` block shape ``x + attn(ln1 x) +
    mlp(ln2 x)``."""
    dim = hf_config.hidden_size
    act = getattr(hf_config, "hidden_act", "gelu")
    act_map = {"gelu": "gelu", "gelu_new": "gelu_tanh",
               "gelu_pytorch_tanh": "gelu_tanh"}
    if act not in act_map:
        raise ValueError(
            f"GPT-NeoX hidden_act={act!r} is not computed here "
            "(gelu / gelu_new / gelu_pytorch_tanh are)"
        )
    if getattr(hf_config, "attention_bias", True) is False:
        raise ValueError(
            "this GPT-NeoX checkpoint disables attention biases; the "
            "importer maps the standard always-biased Pythia layout"
        )
    return TransformerConfig(
        vocab=hf_config.vocab_size,
        dim=dim,
        n_layers=hf_config.num_hidden_layers,
        n_heads=hf_config.num_attention_heads,
        n_kv_heads=None,                         # MHA
        mlp_ratio=hf_config.intermediate_size / dim,
        rope_theta=float(getattr(hf_config, "rotary_emb_base", 10000)),
        rope_pct=float(getattr(hf_config, "rotary_pct", 1.0)),
        norm_eps=float(hf_config.layer_norm_eps),
        norm="layernorm",
        mlp_impl="classic",
        act=act_map[act],
        attn_bias=True,
        attn_out_bias=True,
        parallel_residual=bool(
            getattr(hf_config, "use_parallel_residual", True)
        ),
        tie_embeddings=bool(
            getattr(hf_config, "tie_word_embeddings", False)
        ),
    )


def _neox_split_qkv(
    w: jnp.ndarray, b: jnp.ndarray, nh: int, hd: int
) -> Tuple[jnp.ndarray, ...]:
    """De-interleave GPT-NeoX's fused ``query_key_value``: the torch
    Linear weight is ``[3*dim, dim]`` with the OUTPUT organized per head
    as ``[nh, 3, hd]`` (q/k/v interleaved WITHIN each head — the classic
    NeoX gotcha; a flat ``[:dim]`` slice would shuffle heads)."""
    dim = nh * hd
    wq, wk, wv = (
        w.reshape(nh, 3, hd, dim)[:, i].reshape(dim, dim).T
        for i in range(3)
    )
    bq, bk, bv = (
        b.reshape(nh, 3, hd)[:, i].reshape(dim) for i in range(3)
    )
    return wq, wk, wv, bq, bk, bv


def params_from_hf_neox(
    state_dict: Dict[str, Any], cfg: TransformerConfig
) -> List[Pytree]:
    """Per-layer params in ``llama(cfg)`` order from a
    ``GPTNeoXForCausalLM`` state dict (verified numerically in
    ``tests/test_neox_interop.py``)."""
    sd = state_dict
    nh, hd = cfg.n_heads, cfg.head_dim
    embed = {"table": _v(sd["gpt_neox.embed_in.weight"])}
    out: List[Pytree] = [embed]
    for i in range(cfg.n_layers):
        p = f"gpt_neox.layers.{i}."
        wq, wk, wv, bq, bk, bv = _neox_split_qkv(
            _v(sd[p + "attention.query_key_value.weight"]),
            _v(sd[p + "attention.query_key_value.bias"]),
            nh, hd,
        )
        out.append({
            "ln1": _v(sd[p + "input_layernorm.weight"]),
            "ln1b": _v(sd[p + "input_layernorm.bias"]),
            "wq": wq, "wk": wk, "wv": wv,
            "bq": bq, "bk": bk, "bv": bv,
            "wo": _t(sd[p + "attention.dense.weight"]),
            "bo": _v(sd[p + "attention.dense.bias"]),
            "ln2": _v(sd[p + "post_attention_layernorm.weight"]),
            "ln2b": _v(sd[p + "post_attention_layernorm.bias"]),
            "w_fc": _t(sd[p + "mlp.dense_h_to_4h.weight"]),
            "b_fc": _v(sd[p + "mlp.dense_h_to_4h.bias"]),
            "w_proj": _t(sd[p + "mlp.dense_4h_to_h.weight"]),
            "b_proj": _v(sd[p + "mlp.dense_4h_to_h.bias"]),
        })
    head: Dict[str, Any] = {
        "scale": _v(sd["gpt_neox.final_layer_norm.weight"]),
        "bias": _v(sd["gpt_neox.final_layer_norm.bias"]),
    }
    if cfg.tie_embeddings:
        head["table"] = embed["table"]
    else:
        head["w"] = _t(sd["embed_out.weight"])
    out.append(head)
    return out


def from_hf_neox(model: Any, *, untie: bool = False) -> tuple:
    """(cfg, per-layer params) from a live HF ``GPTNeoXForCausalLM`` —
    the Pythia family on-ramp (partial rotary + parallel residual).
    ``untie=True`` forces an untied import of a tied checkpoint, like
    the sibling importers (most Pythia checkpoints are untied
    already)."""
    import dataclasses

    cfg = config_from_hf_neox(model.config)
    if untie and cfg.tie_embeddings:
        cfg = dataclasses.replace(cfg, tie_embeddings=False)
    return cfg, params_from_hf_neox(model.state_dict(), cfg)


def state_dict_to_hf_neox(
    params: List[Pytree], cfg: TransformerConfig
) -> Dict[str, Any]:
    """Export back to the ``GPTNeoXForCausalLM`` layout (mirror of
    :func:`params_from_hf_neox`; the fused per-head-interleaved
    ``query_key_value`` is re-assembled)."""
    t, v = _torch_t, _torch_v
    embed, blocks, head = params[0], params[1:-1], params[-1]
    if len(blocks) != cfg.n_layers:
        raise ValueError(
            f"expected {cfg.n_layers} block params, got {len(blocks)}"
        )
    nh, hd = cfg.n_heads, cfg.head_dim
    dim = cfg.dim
    sd: Dict[str, Any] = {
        "gpt_neox.embed_in.weight": v(embed["table"]),
        "gpt_neox.final_layer_norm.weight": v(head["scale"]),
        "gpt_neox.final_layer_norm.bias": v(head["bias"]),
    }
    if "w" in head:
        sd["embed_out.weight"] = t(head["w"])
    for i, bp in enumerate(blocks):
        p = f"gpt_neox.layers.{i}."
        # [dim, dim] jnp columns -> torch [nh, 3, hd, dim] rows.
        qkv = jnp.stack(
            [bp["wq"].T.reshape(nh, hd, dim),
             bp["wk"].T.reshape(nh, hd, dim),
             bp["wv"].T.reshape(nh, hd, dim)],
            axis=1,
        ).reshape(3 * dim, dim)
        qkv_b = jnp.stack(
            [bp["bq"].reshape(nh, hd), bp["bk"].reshape(nh, hd),
             bp["bv"].reshape(nh, hd)],
            axis=1,
        ).reshape(3 * dim)
        sd[p + "attention.query_key_value.weight"] = v(qkv)
        sd[p + "attention.query_key_value.bias"] = v(qkv_b)
        sd[p + "input_layernorm.weight"] = v(bp["ln1"])
        sd[p + "input_layernorm.bias"] = v(bp["ln1b"])
        sd[p + "attention.dense.weight"] = t(bp["wo"])
        sd[p + "attention.dense.bias"] = v(bp["bo"])
        sd[p + "post_attention_layernorm.weight"] = v(bp["ln2"])
        sd[p + "post_attention_layernorm.bias"] = v(bp["ln2b"])
        sd[p + "mlp.dense_h_to_4h.weight"] = t(bp["w_fc"])
        sd[p + "mlp.dense_h_to_4h.bias"] = v(bp["b_fc"])
        sd[p + "mlp.dense_4h_to_h.weight"] = t(bp["w_proj"])
        sd[p + "mlp.dense_4h_to_h.bias"] = v(bp["b_proj"])
    return sd


def config_from_hf_opt(hf_config: Any) -> TransformerConfig:
    """A :class:`TransformerConfig` equivalent to an HF ``OPTConfig``:
    pre-norm LayerNorm blocks, learned positions with OPT's 2-row table
    offset, separate biased q/k/v/out projections, relu classic MLP,
    tied head.  The 350m-style variants (``do_layer_norm_before=False``
    post-norm, ``word_embed_proj_dim != hidden_size`` factorized
    embeddings) are different computations and are rejected."""
    dim = hf_config.hidden_size
    if not getattr(hf_config, "do_layer_norm_before", True):
        raise ValueError(
            "this OPT checkpoint is POST-norm (do_layer_norm_before="
            "False, the 350m layout); only the pre-norm OPT family is "
            "computed here"
        )
    proj = getattr(hf_config, "word_embed_proj_dim", dim)
    if proj != dim:
        raise ValueError(
            f"this OPT checkpoint factorizes its embeddings "
            f"(word_embed_proj_dim={proj} != hidden_size={dim}); that "
            "projection pair is not computed here"
        )
    act = getattr(hf_config, "activation_function", "relu")
    if act != "relu":
        raise ValueError(
            f"OPT activation_function={act!r}; only relu (the published "
            "OPT convention) is mapped here"
        )
    if not getattr(hf_config, "enable_bias", True):
        raise ValueError(
            "this OPT-layout checkpoint disables projection biases "
            "(enable_bias=False, the Galactica variant); the importer "
            "maps the standard always-biased OPT layout"
        )
    if not getattr(hf_config, "layer_norm_elementwise_affine", True):
        raise ValueError(
            "this OPT-layout checkpoint disables LayerNorm affine "
            "params (layer_norm_elementwise_affine=False); the importer "
            "maps the standard affine-LayerNorm OPT layout"
        )
    return TransformerConfig(
        vocab=hf_config.vocab_size,
        dim=dim,
        n_layers=hf_config.num_hidden_layers,
        n_heads=hf_config.num_attention_heads,
        n_kv_heads=None,
        mlp_ratio=hf_config.ffn_dim / dim,
        norm_eps=1e-5,
        norm="layernorm",
        pos_emb="learned",
        # OPT's table carries max_position_embeddings + 2 rows; every
        # lookup shifts by 2 (HF OPTLearnedPositionalEmbedding.offset).
        max_pos=int(hf_config.max_position_embeddings) + 2,
        pos_emb_offset=2,
        mlp_impl="classic",
        act="relu",
        attn_bias=True,
        attn_out_bias=True,
        tie_embeddings=bool(
            getattr(hf_config, "tie_word_embeddings", True)
        ),
    )


def params_from_hf_opt(
    state_dict: Dict[str, Any], cfg: TransformerConfig
) -> List[Pytree]:
    """Per-layer params in ``llama(cfg)`` order from an
    ``OPTForCausalLM`` state dict (verified numerically in
    ``tests/test_opt_interop.py``)."""
    sd = state_dict
    embed = {
        "table": _v(sd["model.decoder.embed_tokens.weight"]),
        "pos": _v(sd["model.decoder.embed_positions.weight"]),
    }
    out: List[Pytree] = [embed]
    for i in range(cfg.n_layers):
        p = f"model.decoder.layers.{i}."
        out.append({
            "ln1": _v(sd[p + "self_attn_layer_norm.weight"]),
            "ln1b": _v(sd[p + "self_attn_layer_norm.bias"]),
            "wq": _t(sd[p + "self_attn.q_proj.weight"]),
            "wk": _t(sd[p + "self_attn.k_proj.weight"]),
            "wv": _t(sd[p + "self_attn.v_proj.weight"]),
            "bq": _v(sd[p + "self_attn.q_proj.bias"]),
            "bk": _v(sd[p + "self_attn.k_proj.bias"]),
            "bv": _v(sd[p + "self_attn.v_proj.bias"]),
            "wo": _t(sd[p + "self_attn.out_proj.weight"]),
            "bo": _v(sd[p + "self_attn.out_proj.bias"]),
            "ln2": _v(sd[p + "final_layer_norm.weight"]),
            "ln2b": _v(sd[p + "final_layer_norm.bias"]),
            "w_fc": _t(sd[p + "fc1.weight"]),
            "b_fc": _v(sd[p + "fc1.bias"]),
            "w_proj": _t(sd[p + "fc2.weight"]),
            "b_proj": _v(sd[p + "fc2.bias"]),
        })
    head: Dict[str, Any] = {
        "scale": _v(sd["model.decoder.final_layer_norm.weight"]),
        "bias": _v(sd["model.decoder.final_layer_norm.bias"]),
    }
    if cfg.tie_embeddings:
        head["table"] = embed["table"]
    else:
        head["w"] = _t(sd["lm_head.weight"])
    out.append(head)
    return out


def from_hf_opt(model: Any, *, untie: bool = False) -> tuple:
    """(cfg, per-layer params) from a live HF ``OPTForCausalLM``.
    ``untie=True`` imports the (always-tied) head as an untied copy for
    the MPMD ``GPipe(llama(cfg))`` path, like the sibling importers."""
    import dataclasses

    cfg = config_from_hf_opt(model.config)
    if untie and cfg.tie_embeddings:
        cfg = dataclasses.replace(cfg, tie_embeddings=False)
    return cfg, params_from_hf_opt(model.state_dict(), cfg)


def state_dict_to_hf_opt(
    params: List[Pytree], cfg: TransformerConfig
) -> Dict[str, Any]:
    """Export back to the ``OPTForCausalLM`` layout (mirror of
    :func:`params_from_hf_opt`).  Tied heads omit ``lm_head.weight``;
    load untied exports into an untied-config model, as with the GPT-2
    exporter."""
    t, v = _torch_t, _torch_v
    embed, blocks, head = params[0], params[1:-1], params[-1]
    if len(blocks) != cfg.n_layers:
        raise ValueError(
            f"expected {cfg.n_layers} block params, got {len(blocks)}"
        )
    sd: Dict[str, Any] = {
        "model.decoder.embed_tokens.weight": v(embed["table"]),
        "model.decoder.embed_positions.weight": v(embed["pos"]),
        "model.decoder.final_layer_norm.weight": v(head["scale"]),
        "model.decoder.final_layer_norm.bias": v(head["bias"]),
    }
    if "w" in head:
        sd["lm_head.weight"] = t(head["w"])
    for i, bp in enumerate(blocks):
        p = f"model.decoder.layers.{i}."
        sd[p + "self_attn_layer_norm.weight"] = v(bp["ln1"])
        sd[p + "self_attn_layer_norm.bias"] = v(bp["ln1b"])
        sd[p + "self_attn.q_proj.weight"] = t(bp["wq"])
        sd[p + "self_attn.q_proj.bias"] = v(bp["bq"])
        sd[p + "self_attn.k_proj.weight"] = t(bp["wk"])
        sd[p + "self_attn.k_proj.bias"] = v(bp["bk"])
        sd[p + "self_attn.v_proj.weight"] = t(bp["wv"])
        sd[p + "self_attn.v_proj.bias"] = v(bp["bv"])
        sd[p + "self_attn.out_proj.weight"] = t(bp["wo"])
        sd[p + "self_attn.out_proj.bias"] = v(bp["bo"])
        sd[p + "final_layer_norm.weight"] = v(bp["ln2"])
        sd[p + "final_layer_norm.bias"] = v(bp["ln2b"])
        sd[p + "fc1.weight"] = t(bp["w_fc"])
        sd[p + "fc1.bias"] = v(bp["b_fc"])
        sd[p + "fc2.weight"] = t(bp["w_proj"])
        sd[p + "fc2.bias"] = v(bp["b_proj"])
    return sd


def config_from_hf_bert(hf_config: Any) -> TransformerConfig:
    """A :class:`TransformerConfig` equivalent to an HF ``BertConfig``:
    the ENCODER class — bidirectional attention (``causal=False``),
    POST-norm blocks (``LN(x + branch(x))``), a LayerNorm on the summed
    embeddings, learned positions, separate biased projections, exact
    gelu classic MLP.  Only absolute positions are computed here."""
    mt = getattr(hf_config, "model_type", "bert")
    if mt != "bert":
        raise ValueError(
            f"from_hf_bert maps the BertModel layout; got model_type="
            f"{mt!r} — RoBERTa-class checkpoints share the key names but "
            "reserve the first padding_idx+1 position rows, so importing "
            "them here would be silently misaligned; use from_hf_roberta "
            "(which applies the pos_emb_offset)"
        )
    return _bert_like_config(hf_config)


def _bert_like_config(hf_config: Any) -> TransformerConfig:
    """The BERT-layout field mapping + shared didactic guards (BERT and
    RoBERTa call this after their own model_type checks)."""
    if getattr(hf_config, "is_decoder", False) or getattr(
        hf_config, "add_cross_attention", False
    ):
        raise ValueError(
            "this BERT-layout config is a DECODER (is_decoder/"
            "add_cross_attention set): HF applies a causal mask and may "
            "carry cross-attention weights — neither matches this "
            "bidirectional encoder import"
        )
    if getattr(hf_config, "position_embedding_type", "absolute") != "absolute":
        raise ValueError(
            "this BERT-layout checkpoint uses "
            f"position_embedding_type={hf_config.position_embedding_type!r};"
            " only the absolute learned-table variant is computed here"
        )
    act = getattr(hf_config, "hidden_act", "gelu")
    act_map = {"gelu": "gelu", "gelu_new": "gelu_tanh",
               "gelu_pytorch_tanh": "gelu_tanh", "relu": "relu"}
    if act not in act_map:
        raise ValueError(f"BERT hidden_act={act!r} is not computed here")
    dim = hf_config.hidden_size
    return TransformerConfig(
        vocab=hf_config.vocab_size,
        dim=dim,
        n_layers=hf_config.num_hidden_layers,
        n_heads=hf_config.num_attention_heads,
        n_kv_heads=None,
        mlp_ratio=hf_config.intermediate_size / dim,
        norm_eps=float(hf_config.layer_norm_eps),
        norm="layernorm",
        norm_position="post",
        causal=False,
        pos_emb="learned",
        max_pos=int(hf_config.max_position_embeddings),
        embed_layernorm=True,
        mlp_impl="classic",
        act=act_map[act],
        attn_bias=True,
        attn_out_bias=True,
    )


def params_from_hf_bert(
    state_dict: Dict[str, Any], cfg: TransformerConfig
) -> List[Pytree]:
    """Per-layer params in ``llama(cfg, head=False)`` order (embed,
    blocks — BERT is an encoder; pair with your own task head) from a
    ``BertModel`` state dict.

    Single-segment convention: the token-type (segment) table's ROW 0 is
    added to every position in single-sentence use, so it FOLDS into the
    position table (``pos[i] += token_type[0]``) — no segment input is
    needed at run time.  Two-segment inputs are out of scope.  The
    pooler (a CLS-position head for NSP) is not imported; the encoder
    output is the per-token hidden states."""
    sd = state_dict
    pre = "bert." if any(k.startswith("bert.") for k in sd) else ""
    e = pre + "embeddings."
    pos = _v(sd[e + "position_embeddings.weight"])
    tt0 = _v(sd[e + "token_type_embeddings.weight"])[0]
    embed: Dict[str, Any] = {
        "table": _v(sd[e + "word_embeddings.weight"]),
        "pos": pos + tt0[None, :],
        "eln": _v(sd[e + "LayerNorm.weight"]),
        "elnb": _v(sd[e + "LayerNorm.bias"]),
    }
    out: List[Pytree] = [embed]
    for i in range(cfg.n_layers):
        p = f"{pre}encoder.layer.{i}."
        out.append({
            "wq": _t(sd[p + "attention.self.query.weight"]),
            "bq": _v(sd[p + "attention.self.query.bias"]),
            "wk": _t(sd[p + "attention.self.key.weight"]),
            "bk": _v(sd[p + "attention.self.key.bias"]),
            "wv": _t(sd[p + "attention.self.value.weight"]),
            "bv": _v(sd[p + "attention.self.value.bias"]),
            "wo": _t(sd[p + "attention.output.dense.weight"]),
            "bo": _v(sd[p + "attention.output.dense.bias"]),
            "ln1": _v(sd[p + "attention.output.LayerNorm.weight"]),
            "ln1b": _v(sd[p + "attention.output.LayerNorm.bias"]),
            "w_fc": _t(sd[p + "intermediate.dense.weight"]),
            "b_fc": _v(sd[p + "intermediate.dense.bias"]),
            "w_proj": _t(sd[p + "output.dense.weight"]),
            "b_proj": _v(sd[p + "output.dense.bias"]),
            "ln2": _v(sd[p + "output.LayerNorm.weight"]),
            "ln2b": _v(sd[p + "output.LayerNorm.bias"]),
        })
    return out


def from_hf_bert(model: Any) -> tuple:
    """(cfg, per-layer params) from a live HF ``BertModel`` (or a
    ``Bert*`` task model whose state dict prefixes ``bert.``) — the
    encoder family: train/fine-tune through the pipelines with your own
    task head appended; there is no decode path (the generation API
    rejects ``causal=False`` and post-norm didactically)."""
    cfg = config_from_hf_bert(model.config)
    return cfg, params_from_hf_bert(model.state_dict(), cfg)


def from_hf_roberta(model: Any) -> tuple:
    """(cfg, per-layer params) from a live HF ``RobertaModel`` — the
    BERT layout with RoBERTa's position convention: position ids start
    at ``padding_idx + 1`` (= 2), so the table reserves its first two
    rows and every lookup shifts — exactly OPT's ``pos_emb_offset``
    mechanism, applied here so the import is aligned (the plain
    :func:`from_hf_bert` rejects RoBERTa for this reason).

    PAD-FREE inputs only: HF RoBERTa computes positions as a cumsum
    over non-pad tokens, so a sequence CONTAINING the pad id (1) gets
    shifted positions there while this import assigns sequential ones —
    feed unpadded batches (or uniform-length ones with no pad tokens),
    the convention the parity test pins."""
    import dataclasses

    hfc = model.config
    if getattr(hfc, "model_type", "") != "roberta":
        raise ValueError(
            f"from_hf_roberta maps RobertaModel; got model_type="
            f"{getattr(hfc, 'model_type', None)!r} — plain BERT imports "
            "via from_hf_bert"
        )
    offset = int(getattr(hfc, "pad_token_id", 1)) + 1
    cfg = dataclasses.replace(
        _bert_like_config(hfc), pos_emb_offset=offset
    )
    sd = model.state_dict()
    if any(k.startswith("roberta.") for k in sd):
        sd = {
            k[len("roberta."):]: v
            for k, v in sd.items()
            if k.startswith("roberta.")
        }
    return cfg, params_from_hf_bert(sd, cfg)


__all__ = [
    "config_from_hf",
    "config_from_hf_bert",
    "config_from_hf_gpt2",
    "config_from_hf_mixtral",
    "config_from_hf_neox",
    "config_from_hf_opt",
    "params_from_hf",
    "params_from_hf_bert",
    "params_from_hf_gpt2",
    "params_from_hf_mixtral",
    "params_from_hf_neox",
    "params_from_hf_opt",
    "from_hf_bert",
    "from_hf_gemma",
    "from_hf_gpt2",
    "from_hf_llama",
    "from_hf_mixtral",
    "from_hf_neox",
    "from_hf_opt",
    "from_hf_roberta",
    "from_hf_qwen2",
    "from_hf_qwen3",
    "state_dict_to_hf",
    "state_dict_to_hf_gpt2",
    "state_dict_to_hf_mixtral",
    "state_dict_to_hf_neox",
    "state_dict_to_hf_opt",
]


def config_from_hf_mixtral(hf_config: Any) -> tuple:
    """(TransformerConfig, MoEConfig) equivalent to an HF
    ``MixtralConfig``.

    Router-semantics note (verified against ``transformers``' Mixtral
    forward): HF computes ``softmax(router_logits)``, takes top-k, and
    renormalizes the selected weights — exactly this framework's GShard
    normalization for ``top_k >= 2`` (``moe._gate_denom``).  ``top_k=1``
    differs (we keep the raw Switch-style probability; HF would pin the
    gate to 1.0) and is rejected rather than silently mismatched.
    """
    from torchgpipe_tpu.models.moe import MoEConfig

    k = int(hf_config.num_experts_per_tok)
    if k < 2:
        raise ValueError(
            "Mixtral import requires num_experts_per_tok >= 2: at k=1 "
            "HF renormalizes the single gate to 1.0 while this "
            "framework keeps the Switch-style raw probability — the "
            "models would silently disagree"
        )
    # config_from_hf maps sliding_window -> attn_window for
    # Mistral-class configs (MixtralConfig included: sliding window on
    # every layer, no max_window_layers gate).
    cfg = config_from_hf(hf_config)
    moe = MoEConfig(
        n_experts=int(hf_config.num_local_experts),
        top_k=k,
        dispatch="dropless",  # Mixtral drops no tokens; exact parity
    )
    return cfg, moe


def params_from_hf_mixtral(
    state_dict: Dict[str, Any], cfg: TransformerConfig, moe: Any
) -> List[Pytree]:
    """Per-layer params in ``llama_moe(cfg, moe)`` order (embed, MoE
    blocks, head) from an HF ``MixtralForCausalLM`` state dict.

    Layout mapping (torch ``Linear`` stores ``[out, in]`` → transpose):
    ``block_sparse_moe.gate.weight [E, dim]`` → ``router [dim, E]``
    (f32, matching the framework's f32 routing); per-expert ``w1/w3/w2``
    → stacked ``w_gate/w_up [E, dim, hidden]`` / ``w_down [E, hidden,
    dim]`` (same SwiGLU: ``silu(x@w_gate) * (x@w_up) @ w_down``)."""
    _check_attn_param_consistency(state_dict, cfg)
    sd = state_dict
    out: List[Pytree] = [{"table": _v(sd["model.embed_tokens.weight"])}]
    for i in range(cfg.n_layers):
        p = f"model.layers.{i}."
        e = p + "block_sparse_moe."
        mlp = {
            "router": _t(sd[e + "gate.weight"]).astype(jnp.float32),
            "w_gate": jnp.stack([
                _t(sd[f"{e}experts.{x}.w1.weight"])
                for x in range(moe.n_experts)
            ]),
            "w_up": jnp.stack([
                _t(sd[f"{e}experts.{x}.w3.weight"])
                for x in range(moe.n_experts)
            ]),
            "w_down": jnp.stack([
                _t(sd[f"{e}experts.{x}.w2.weight"])
                for x in range(moe.n_experts)
            ]),
        }
        out.append({**_attn_entries(sd, p, cfg), "mlp": mlp})
    out.append(_head_entry(sd, cfg, out[0]))
    return out


def from_hf_mixtral(model: Any) -> tuple:
    """(cfg, moe, per-layer params) from a live HF
    ``MixtralForCausalLM`` — ready for ``GPipe(llama_moe(cfg, moe))``
    init-splicing or ``generation.generate(..., moe=moe)``."""
    cfg, moe = config_from_hf_mixtral(model.config)
    return cfg, moe, params_from_hf_mixtral(model.state_dict(), cfg, moe)


def state_dict_to_hf_mixtral(
    params: List[Pytree], cfg: TransformerConfig, moe: Any
) -> Dict[str, Any]:
    """The inverse map: ``llama_moe(cfg, moe)`` per-layer params -> an HF
    ``MixtralForCausalLM`` state dict.  Exact inverse of
    :func:`params_from_hf_mixtral` (round-trip tested); tied heads omit
    ``lm_head.weight`` like the dense export."""
    t = _torch_t
    sd, blocks = _export_common(params, cfg)
    table_dtype = params[0]["table"].dtype
    for i, bp in enumerate(blocks):
        e = f"model.layers.{i}.block_sparse_moe."
        mlp = bp["mlp"]
        # The router was cast to f32 on import (f32 routing is the
        # framework's convention); export it back at the checkpoint's
        # uniform dtype so a bf16 checkpoint round-trips bf16 throughout.
        sd[e + "gate.weight"] = t(mlp["router"].astype(table_dtype))
        for x in range(moe.n_experts):
            sd[f"{e}experts.{x}.w1.weight"] = t(mlp["w_gate"][x])
            sd[f"{e}experts.{x}.w3.weight"] = t(mlp["w_up"][x])
            sd[f"{e}experts.{x}.w2.weight"] = t(mlp["w_down"][x])
    return sd


# --------------------------------------------------------------------- #
# T5 (encoder-decoder family — models/t5.py)                             #
# --------------------------------------------------------------------- #


def config_from_hf_t5(hf_config: Any) -> Any:
    """``T5Config`` equivalent to an HF ``T5Config``.

    Covers both the v1.0 class (relu DenseReluDense, tied embeddings —
    t5-small/base/...) and the v1.1 class (gated GeLU, untied —
    google/t5-v1_1-*, FLAN-T5) via HF's parsed ``is_gated_act`` /
    ``dense_act_fn``."""
    from .t5 import T5Config

    acts = {
        "relu": "relu", "gelu_new": "gelu_tanh", "gelu": "gelu",
        "silu": "silu",
    }
    if hf_config.dense_act_fn not in acts:
        raise ValueError(
            f"T5 dense_act_fn {hf_config.dense_act_fn!r} is not supported "
            f"(expected one of {sorted(acts)})"
        )
    act = acts[hf_config.dense_act_fn]
    return T5Config(
        vocab=hf_config.vocab_size,
        dim=hf_config.d_model,
        n_enc_layers=hf_config.num_layers,
        n_dec_layers=hf_config.num_decoder_layers,
        n_heads=hf_config.num_heads,
        head_dim=hf_config.d_kv,
        mlp_hidden=hf_config.d_ff,
        act=act,
        gated_mlp=bool(hf_config.is_gated_act),
        rel_buckets=hf_config.relative_attention_num_buckets,
        rel_max_distance=hf_config.relative_attention_max_distance,
        norm_eps=hf_config.layer_norm_epsilon,
        tie_word_embeddings=bool(hf_config.tie_word_embeddings),
        decoder_start_id=hf_config.decoder_start_token_id,
    )


def _t5_ff_entry(sd: Dict[str, Any], prefix: str, gated: bool) -> Dict:
    if gated:
        return {
            "wi0": _t(sd[prefix + "DenseReluDense.wi_0.weight"]),
            "wi1": _t(sd[prefix + "DenseReluDense.wi_1.weight"]),
            "wo": _t(sd[prefix + "DenseReluDense.wo.weight"]),
        }
    return {
        "wi": _t(sd[prefix + "DenseReluDense.wi.weight"]),
        "wo": _t(sd[prefix + "DenseReluDense.wo.weight"]),
    }


def _t5_attn_entry(sd: Dict[str, Any], prefix: str) -> Dict:
    return {
        "wq": _t(sd[prefix + "q.weight"]),
        "wk": _t(sd[prefix + "k.weight"]),
        "wv": _t(sd[prefix + "v.weight"]),
        "wo": _t(sd[prefix + "o.weight"]),
    }


def params_from_hf_t5(state_dict: Dict[str, Any], cfg: Any) -> List[Pytree]:
    """Per-layer params in ``t5_layers(cfg)`` order (embed, enc blocks,
    enc final, dec blocks, final) from a ``T5ForConditionalGeneration``
    state dict.

    Tied checkpoints (v1.0): the shared table is COPIED into the head's
    ``w`` (transposed), and ``cfg.logit_scale`` preserves HF's tied-head
    ``d_model**-0.5`` rescale — forward and decode are exactly the HF
    model; under pipeline fine-tuning the two copies train independently
    (see models/t5.py docstring)."""
    sd = state_dict
    out: List[Pytree] = [{"table": _v(sd["shared.weight"])}]
    for i in range(cfg.n_enc_layers):
        p = f"encoder.block.{i}."
        entry = {
            "ln1": _v(sd[p + "layer.0.layer_norm.weight"]),
            "attn": _t5_attn_entry(sd, p + "layer.0.SelfAttention."),
            "ln2": _v(sd[p + "layer.1.layer_norm.weight"]),
            "ff": _t5_ff_entry(sd, p + "layer.1.", cfg.gated_mlp),
        }
        if i == 0:
            entry["rel"] = _v(sd[
                p + "layer.0.SelfAttention.relative_attention_bias.weight"
            ])
        out.append(entry)
    out.append({"ln": _v(sd["encoder.final_layer_norm.weight"])})
    for i in range(cfg.n_dec_layers):
        p = f"decoder.block.{i}."
        entry = {
            "ln1": _v(sd[p + "layer.0.layer_norm.weight"]),
            "attn": _t5_attn_entry(sd, p + "layer.0.SelfAttention."),
            "ln2": _v(sd[p + "layer.1.layer_norm.weight"]),
            "xattn": _t5_attn_entry(sd, p + "layer.1.EncDecAttention."),
            "ln3": _v(sd[p + "layer.2.layer_norm.weight"]),
            "ff": _t5_ff_entry(sd, p + "layer.2.", cfg.gated_mlp),
        }
        if i == 0:
            entry["rel"] = _v(sd[
                p + "layer.0.SelfAttention.relative_attention_bias.weight"
            ])
        out.append(entry)
    head = _t(sd[
        "shared.weight" if cfg.tie_word_embeddings else "lm_head.weight"
    ])
    out.append({
        "ln": _v(sd["decoder.final_layer_norm.weight"]),
        "w": head,
    })
    return out


def from_hf_t5(model: Any) -> tuple:
    """(cfg, per-layer params) from a live HF
    ``T5ForConditionalGeneration`` — the encoder-decoder family: logits
    and greedy decode verified against the HF model in
    tests/test_t5.py."""
    cfg = config_from_hf_t5(model.config)
    return cfg, params_from_hf_t5(model.state_dict(), cfg)


__all__ += ["config_from_hf_t5", "params_from_hf_t5", "from_hf_t5"]


def state_dict_to_hf_t5(
    params: List[Pytree], cfg: Any, *, untie: bool = False
) -> Dict[str, Any]:
    """The inverse map: ``t5_layers(cfg)`` per-layer params -> an HF
    ``T5ForConditionalGeneration`` state dict (torch tensors) — exact
    inverse of :func:`params_from_hf_t5` (round-trip tested).

    Tied configs (v1.0): the head was imported as a COPY of the shared
    table; if pipeline fine-tuning has made the copies drift (their
    gradients are not summed — see models/t5.py), a tied export would
    silently discard the trained head, so drift is rejected.  Pass
    ``untie=True`` to export the drifted pair as an UNTIED checkpoint
    instead: the training-time tied-head ``d_model**-0.5`` logit rescale
    is baked into the emitted ``lm_head.weight`` (an untied HF T5 applies
    no rescale), so the exported model's logits — not just its argmax —
    match the framework model; load it with an HF config whose
    ``tie_word_embeddings=False``."""
    import numpy as np

    t, v = _torch_t, _torch_v
    ne, nd = cfg.n_enc_layers, cfg.n_dec_layers
    if len(params) != ne + nd + 3:
        raise ValueError(
            f"expected {ne + nd + 3} per-layer params "
            f"(embed, {ne} enc blocks, enc final, {nd} dec blocks, "
            f"final), got {len(params)}"
        )
    embed = params[0]
    enc, enc_final = params[1:1 + ne], params[1 + ne]
    dec, final = params[2 + ne:2 + ne + nd], params[2 + ne + nd]
    table = embed["table"]
    head_w = final["w"]
    if cfg.tie_word_embeddings and untie and cfg.logit_scale is not None:
        # The tied framework model scales hidden states by d_model**-0.5
        # before the head; an untied HF T5 applies no such rescale, so
        # bake it into the exported head weights (logits, not just
        # argmax, must match).
        head_w = head_w * cfg.logit_scale
    if cfg.tie_word_embeddings and not untie:
        if not np.array_equal(
            np.asarray(table, np.float32),
            np.asarray(final["w"].T, np.float32),
        ):
            raise ValueError(
                "cfg.tie_word_embeddings=True but the head 'w' has "
                "drifted from the shared table (pipeline fine-tuning "
                "trains the two copies independently); a tied export "
                "would discard the trained head — pass untie=True to "
                "export an untied checkpoint (bakes the tied-head "
                "logit rescale into lm_head.weight) or re-tie the "
                "weights first"
            )
    sd: Dict[str, Any] = {
        "shared.weight": v(table),
        "encoder.embed_tokens.weight": v(table),
        "decoder.embed_tokens.weight": v(table),
        "encoder.final_layer_norm.weight": v(enc_final["ln"]),
        "decoder.final_layer_norm.weight": v(final["ln"]),
    }
    # HF state dicts materialize the head tensor even when tied (it
    # aliases shared.weight); for tied configs the no-drift check above
    # guarantees final['w'] IS the shared table.
    sd["lm_head.weight"] = t(head_w)

    def put_attn(prefix: str, ap: Dict[str, Any]) -> None:
        sd[prefix + "q.weight"] = t(ap["wq"])
        sd[prefix + "k.weight"] = t(ap["wk"])
        sd[prefix + "v.weight"] = t(ap["wv"])
        sd[prefix + "o.weight"] = t(ap["wo"])

    def put_ff(prefix: str, fp: Dict[str, Any]) -> None:
        if cfg.gated_mlp:
            sd[prefix + "DenseReluDense.wi_0.weight"] = t(fp["wi0"])
            sd[prefix + "DenseReluDense.wi_1.weight"] = t(fp["wi1"])
        else:
            sd[prefix + "DenseReluDense.wi.weight"] = t(fp["wi"])
        sd[prefix + "DenseReluDense.wo.weight"] = t(fp["wo"])

    for i, bp in enumerate(enc):
        p = f"encoder.block.{i}."
        sd[p + "layer.0.layer_norm.weight"] = v(bp["ln1"])
        put_attn(p + "layer.0.SelfAttention.", bp["attn"])
        if i == 0:
            sd[
                p + "layer.0.SelfAttention.relative_attention_bias.weight"
            ] = v(bp["rel"])
        sd[p + "layer.1.layer_norm.weight"] = v(bp["ln2"])
        put_ff(p + "layer.1.", bp["ff"])
    for i, bp in enumerate(dec):
        p = f"decoder.block.{i}."
        sd[p + "layer.0.layer_norm.weight"] = v(bp["ln1"])
        put_attn(p + "layer.0.SelfAttention.", bp["attn"])
        if i == 0:
            sd[
                p + "layer.0.SelfAttention.relative_attention_bias.weight"
            ] = v(bp["rel"])
        sd[p + "layer.1.layer_norm.weight"] = v(bp["ln2"])
        put_attn(p + "layer.1.EncDecAttention.", bp["xattn"])
        sd[p + "layer.2.layer_norm.weight"] = v(bp["ln3"])
        put_ff(p + "layer.2.", bp["ff"])
    return sd


__all__ += ["state_dict_to_hf_t5"]


def config_from_hf_latent_moe(
    hf_config: Any, held: Any = None
) -> tuple:
    """(TransformerConfig, MoEConfig) for the published config of a
    latent-attention, sigmoid- or softmax-routed expert model (the
    DeepSeek-V2/V3 key set: ``q_lora_rank``, ``kv_lora_rank``,
    ``qk_nope_head_dim``, ``qk_rope_head_dim``, ``v_head_dim``,
    ``rope_scaling`` of type yarn; ``n_routed_experts``,
    ``num_experts_per_tok``, ``moe_intermediate_size``,
    ``n_shared_experts``, ``scoring_func``, ``norm_topk_prob``,
    ``routed_scaling_factor``, ``topk_method``).  ``hf_config`` is any
    object with those attributes, as they stand in ``config.json``.

    ``held=(first, count)`` makes the expert layers one chip's share
    (``MoEConfig.held``).  The leading ``first_k_dense_replace`` blocks
    are dense SwiGLUs of ``intermediate_size`` and are told apart by
    their params (``generation._mlp_out``), not by a config.  Serving
    path only: there is no weight importer or training block for this
    family yet.  An unknown ``topk_method`` or ``scoring_func`` raises
    instead of falling back to plain softmax top-k."""
    from torchgpipe_tpu.models.moe import _SELECT, MoEConfig
    from torchgpipe_tpu.models.transformer import MLAConfig, YarnRope

    hf = hf_config
    scoring = getattr(hf, "scoring_func", "softmax")
    if scoring not in ("softmax", "sigmoid"):
        raise ValueError(
            f"scoring_func={scoring!r} is not computed here "
            "('softmax' and 'sigmoid' are)"
        )
    select = getattr(hf, "topk_method", "greedy")
    if select not in _SELECT:
        raise ValueError(
            f"topk_method={select!r} is not computed here "
            f"({sorted(_SELECT)} are); a grouped selection is one "
            "more entry of models.moe._SELECT"
        )
    if getattr(hf, "attention_bias", False):
        raise ValueError("attention_bias=True: MLA here is bias-free")
    if getattr(hf, "moe_layer_freq", 1) != 1:
        raise ValueError(
            f"moe_layer_freq={hf.moe_layer_freq}: every layer after the "
            "leading dense ones is taken to be an expert layer"
        )
    yarn = None
    rs = getattr(hf, "rope_scaling", None)
    if rs:
        kind = rs.get("type", rs.get("rope_type"))
        if kind != "yarn":
            raise ValueError(
                f"rope_scaling type {kind!r} is not computed here "
                "('yarn' is)"
            )
        yarn = YarnRope(
            factor=float(rs["factor"]),
            original_max_pos=int(rs["original_max_position_embeddings"]),
            beta_fast=float(rs.get("beta_fast", 32)),
            beta_slow=float(rs.get("beta_slow", 1)),
            mscale=float(rs.get("mscale", 1)),
            mscale_all_dim=float(rs.get("mscale_all_dim", 0)),
        )
    dim, inter = hf.hidden_size, hf.intermediate_size
    cfg = TransformerConfig(
        vocab=hf.vocab_size,
        dim=dim,
        n_layers=hf.num_hidden_layers,
        n_heads=hf.num_attention_heads,
        mlp_ratio=3.0 * inter / (2.0 * dim),
        rope_theta=float(getattr(hf, "rope_theta", 10000.0)),
        norm_eps=float(hf.rms_norm_eps),
        tie_embeddings=bool(getattr(hf, "tie_word_embeddings", False)),
        mla=MLAConfig(
            q_lora_rank=int(hf.q_lora_rank),
            kv_lora_rank=int(hf.kv_lora_rank),
            qk_nope_head_dim=int(hf.qk_nope_head_dim),
            qk_rope_head_dim=int(hf.qk_rope_head_dim),
            v_head_dim=int(hf.v_head_dim),
            rope_scaling=yarn,
        ),
    )
    if cfg.mlp_hidden != inter:
        raise ValueError(
            f"intermediate_size={inter} cannot be expressed by this "
            f"config's 128-aligned SwiGLU formula (got {cfg.mlp_hidden})"
        )
    moe = MoEConfig(
        n_experts=int(hf.n_routed_experts),
        top_k=int(hf.num_experts_per_tok),
        dispatch="dropless",
        scoring=scoring,
        norm_topk=bool(getattr(hf, "norm_topk_prob", False)),
        route_scale=float(getattr(hf, "routed_scaling_factor", 1.0)),
        n_shared=int(getattr(hf, "n_shared_experts", 0) or 0),
        expert_hidden=int(hf.moe_intermediate_size),
        held=None if held is None else (int(held[0]), int(held[1])),
        select=select,
    )
    return cfg, moe


__all__ += ["config_from_hf_latent_moe"]


def _rope_entry(record: Any, window: Any) -> Any:
    """One layer type's :class:`AttnLayer` from its ``rope_parameters``
    record (``rope_type`` 'default' or 'yarn')."""
    from torchgpipe_tpu.models.mla import yarn_amplitude
    from torchgpipe_tpu.models.transformer import AttnLayer, YarnRope

    kind = record.get("rope_type", record.get("type", "default"))
    theta = float(record["rope_theta"])
    if kind == "default":
        return AttnLayer(window, theta)
    if kind != "yarn":
        raise ValueError(
            f"rope_type {kind!r} is not computed here ('default' and "
            "'yarn' are)"
        )
    yarn = YarnRope(
        factor=float(record["factor"]),
        original_max_pos=int(record["original_max_position_embeddings"]),
        beta_fast=float(record.get("beta_fast", 32)),
        beta_slow=float(record.get("beta_slow", 1)),
        mscale=float(record.get("mscale", 1)),
        mscale_all_dim=float(record.get("mscale_all_dim", 0)),
    )
    stated = record.get("attention_factor")
    if stated is not None and not math.isclose(
            float(stated), yarn_amplitude(yarn), rel_tol=1e-6):
        raise ValueError(
            f"attention_factor {stated} is not what the record's factor "
            f"and mscales give ({yarn_amplitude(yarn)}); a free factor on "
            "cos and sin is not computed here"
        )
    return AttnLayer(window, theta, yarn)


def config_from_hf_mixed_moe(hf_config: Any, held: Any = None) -> tuple:
    """(TransformerConfig, MoEConfig) for the published config of a GQA
    model whose layers mix window and full attention and whose
    feed-forwards are softmax-routed experts (the key set
    ``layer_types``, ``sliding_window``, ``rope_parameters`` by layer
    type, ``mlp_layer_types``, ``num_experts``, ``num_experts_per_tok``,
    ``moe_intermediate_size``, ``norm_topk_prob``).  ``hf_config`` is any
    object with those attributes, as they stand in ``config.json``.

    ``layer_types`` becomes the repeating period ``cfg.attn_layers``
    (window and rope record a type; a YaRN record's
    ``attention_factor``, where stated, has to be what its factor and
    mscales give).  The lineage's attention norms q and k per
    head without a key of its own (``qk_norm``).  ``held=(first,
    count)`` makes the expert layers one chip's share
    (``MoEConfig.held``); no expert is shared and none is dropped
    (``dispatch='dropless'``).  Every ``mlp_layer_types`` entry has to be
    ``'sparse'``: a dense layer among them would take
    ``intermediate_size``, which is recorded and otherwise unused."""
    from torchgpipe_tpu.models.moe import MoEConfig

    hf = hf_config
    if getattr(hf, "attention_bias", False):
        raise ValueError("attention_bias=True is not read by this importer")
    dense = sorted(set(hf.mlp_layer_types) - {"sparse"})
    if dense:
        raise ValueError(
            f"mlp_layer_types {dense}: every layer is taken to be an "
            "expert layer ('sparse')"
        )
    dim, inter = hf.hidden_size, hf.intermediate_size
    window = int(hf.sliding_window)
    cfg = TransformerConfig(
        vocab=hf.vocab_size,
        dim=dim,
        n_layers=hf.num_hidden_layers,
        n_heads=hf.num_attention_heads,
        n_kv_heads=hf.num_key_value_heads,
        n_head_dim=int(hf.head_dim),
        mlp_ratio=3.0 * inter / (2.0 * dim),
        norm_eps=float(hf.rms_norm_eps),
        tie_embeddings=bool(getattr(hf, "tie_word_embeddings", False)),
        qk_norm=True,
        act=hf.hidden_act,
        attn_layers=_attn_period(list(hf.layer_types), {
            "sliding_attention": _rope_entry(
                hf.rope_parameters["sliding_attention"], window),
            "full_attention": _rope_entry(
                hf.rope_parameters["full_attention"], None),
        }),
    )
    moe = MoEConfig(
        n_experts=int(hf.num_experts),
        top_k=int(hf.num_experts_per_tok),
        dispatch="dropless",
        scoring="softmax",
        norm_topk=bool(hf.norm_topk_prob),
        expert_hidden=int(hf.moe_intermediate_size),
        held=None if held is None else (int(held[0]), int(held[1])),
    )
    return cfg, moe


__all__ += ["config_from_hf_mixed_moe"]


def config_from_hf_afmoe(hf_config: Any, held: Any = None) -> tuple:
    """(TransformerConfig, MoEConfig) for a published ``model_type:
    afmoe`` config (Arcee's Trinity family; the key set ``layer_types``,
    ``sliding_window``, ``num_dense_layers``, ``mup_enabled``,
    ``num_experts``, ``num_experts_per_tok``, ``num_shared_experts``,
    ``moe_intermediate_size``, ``score_func``, ``route_norm``,
    ``route_scale``, ``n_group`` / ``topk_group`` / ``num_limited_groups``).
    ``hf_config`` is any object with those attributes, as they stand in
    ``config.json``.

    What the record's keys do not say is the ``afmoe`` block itself: an
    output gate on the attention (``attn_gate``), q and k RMS-normed per
    head (``qk_norm``), a norm on each branch's output as well as its
    input (``sandwich_norm``), sliding layers rotated and windowed and
    full layers NOT rotated (``layer_types`` becomes the period
    ``cfg.attn_layers``), the embedding scaled by ``sqrt(hidden_size)``
    under ``mup_enabled``, and experts chosen by score plus a per-expert
    bias and weighted by the score alone (``select='bias'``).  The
    leading ``num_dense_layers`` blocks are dense SwiGLUs of
    ``intermediate_size``, told apart by their params
    (``generation._mlp_out``).  ``held=(first, count)`` makes the expert
    layers one chip's share (``MoEConfig.held``).  Serving path only: the
    training block refuses the gate and the sandwich norms by name.
    Grouped selection (``n_group`` or ``num_limited_groups`` other than
    1), an unknown ``score_func`` and a rope scaling raise instead of
    being ignored; ``load_balance_coeff`` is a training term and unused."""
    from torchgpipe_tpu.models.moe import MoEConfig
    from torchgpipe_tpu.models.transformer import AttnLayer

    hf = hf_config
    score = getattr(hf, "score_func", "sigmoid")
    if score not in ("softmax", "sigmoid"):
        raise ValueError(
            f"score_func={score!r} is not computed here "
            "('softmax' and 'sigmoid' are)"
        )
    for key in ("n_group", "num_limited_groups", "num_expert_groups",
                "topk_group"):
        if getattr(hf, key, 1) != 1:
            raise ValueError(
                f"{key}={getattr(hf, key)}: a grouped selection is not "
                "computed here (the bias-corrected top-k runs over all "
                "experts: 1 group, 1 of them taken)"
            )
    if getattr(hf, "rope_scaling", None):
        raise ValueError(
            f"rope_scaling={hf.rope_scaling}: the sliding layers rotate "
            "at rope_theta unscaled here"
        )
    if getattr(hf, "attention_bias", False):
        raise ValueError("attention_bias=True is not read by this importer")
    dim, inter = hf.hidden_size, hf.intermediate_size
    theta = float(hf.rope_theta)
    cfg = TransformerConfig(
        vocab=hf.vocab_size,
        dim=dim,
        n_layers=hf.num_hidden_layers,
        n_heads=hf.num_attention_heads,
        n_kv_heads=hf.num_key_value_heads,
        n_head_dim=int(hf.head_dim),
        mlp_ratio=3.0 * inter / (2.0 * dim),
        rope_theta=theta,
        norm_eps=float(hf.rms_norm_eps),
        tie_embeddings=bool(getattr(hf, "tie_word_embeddings", False)),
        qk_norm=True,
        attn_gate=True,
        sandwich_norm=True,
        act=hf.hidden_act,
        embed_scale=(float(dim) ** 0.5
                     if getattr(hf, "mup_enabled", False) else None),
        attn_layers=_attn_period(list(hf.layer_types), {
            "sliding_attention": AttnLayer(int(hf.sliding_window), theta),
            "full_attention": AttnLayer(None, theta, rope=False),
        }),
    )
    if cfg.mlp_hidden != inter:
        raise ValueError(
            f"intermediate_size={inter} cannot be expressed by this "
            f"config's 128-aligned SwiGLU formula (got {cfg.mlp_hidden})"
        )
    moe = MoEConfig(
        n_experts=int(hf.num_experts),
        top_k=int(hf.num_experts_per_tok),
        dispatch="dropless",
        scoring=score,
        norm_topk=bool(getattr(hf, "route_norm", False)),
        route_scale=float(getattr(hf, "route_scale", 1.0)),
        n_shared=int(getattr(hf, "num_shared_experts", 0) or 0),
        expert_hidden=int(hf.moe_intermediate_size),
        held=None if held is None else (int(held[0]), int(held[1])),
        select="bias",
    )
    return cfg, moe


__all__ += ["config_from_hf_afmoe"]


def config_from_hf_nemotron_h(hf_config: Any, held: Any = None) -> tuple:
    """(TransformerConfig, MoEConfig) for a published ``model_type:
    nemotron_h`` config with experts (NVIDIA's Nemotron-3-Nano family:
    the key set ``hybrid_override_pattern``, ``mamba_num_heads``,
    ``mamba_head_dim``, ``n_groups``, ``ssm_state_size``,
    ``conv_kernel``, ``chunk_size``, ``n_routed_experts``,
    ``num_experts_per_tok``, ``moe_intermediate_size``,
    ``moe_shared_expert_intermediate_size``, ``norm_topk_prob``,
    ``routed_scaling_factor``).  ``hf_config`` is any object with those
    attributes, as they stand in ``config.json``.

    A layer is ONE of a Mamba-2 mixer (``M``: ``cfg.ssm``, its inner
    width ``mamba_num_heads x mamba_head_dim``; ``expand`` is not read),
    an expert layer (``E``) or attention (``*``), each ``x + F(norm(x))``
    (``cfg.layer_pattern``).  Attention is causal and full and rotates
    nothing (the family's attention has no rotary embedding;
    ``rope_theta`` is not read).  Experts are ungated ``relu2`` (routed
    and shared alike, the shared expert of its own width), chosen by
    sigmoid score plus ``e_score_correction_bias`` (the layer's
    ``router_bias``) and weighted by the score, normalised and scaled.
    ``held=(first, count)`` makes the expert layers one chip's share
    (``MoEConfig.held``).  Serving path only: the training block refuses
    the pattern by name.  A pattern letter other than ``M``, ``E`` and
    ``*`` (the family's dense ``-`` among them), grouped selection, an
    activation other than the record's and a projection bias raise
    instead of being ignored."""
    from torchgpipe_tpu.models.moe import MoEConfig
    from torchgpipe_tpu.models.transformer import (
        LAYER_LETTERS, AttnLayer, SSMConfig,
    )

    hf = hf_config
    pattern = str(hf.hybrid_override_pattern)
    unknown = sorted(set(pattern) - set(LAYER_LETTERS))
    if unknown:
        raise ValueError(
            f"hybrid_override_pattern letters {unknown} are not computed "
            f"here ({sorted(LAYER_LETTERS)} are)"
        )
    for key, want in (("mlp_hidden_act", "relu2"),
                      ("mamba_hidden_act", "silu"),
                      ("attention_bias", False), ("mlp_bias", False),
                      ("mamba_proj_bias", False), ("use_bias", False),
                      ("n_group", 1), ("topk_group", 1),
                      ("residual_in_fp32", False), ("use_conv_bias", True)):
        if getattr(hf, key, want) != want:
            raise ValueError(
                f"{key}={getattr(hf, key)!r} is not computed here "
                f"({want!r} is)"
            )
    limit = list(getattr(hf, "time_step_limit", None) or (0.0, None))
    if limit[0] or limit[1] is not None:
        raise ValueError(
            f"time_step_limit={limit}: the step is not clipped here "
            "((0, None), or none given, is computed)"
        )
    ssm = SSMConfig(
        n_heads=int(hf.mamba_num_heads),
        head_dim=int(hf.mamba_head_dim),
        n_groups=int(hf.n_groups),
        state=int(hf.ssm_state_size),
        conv_kernel=int(hf.conv_kernel),
        chunk=int(getattr(hf, "chunk_size", 128)),
    )
    if ssm.n_heads % ssm.n_groups:
        raise ValueError(
            f"mamba_num_heads={ssm.n_heads} is not a multiple of "
            f"n_groups={ssm.n_groups}"
        )
    eps = float(getattr(hf, "layer_norm_epsilon",
                        getattr(hf, "norm_eps", 1e-5)))
    cfg = TransformerConfig(
        vocab=hf.vocab_size,
        dim=hf.hidden_size,
        n_layers=hf.num_hidden_layers,
        n_heads=hf.num_attention_heads,
        n_kv_heads=hf.num_key_value_heads,
        n_head_dim=int(hf.head_dim),
        rope_theta=float(getattr(hf, "rope_theta", 10000.0)),
        norm_eps=eps,
        tie_embeddings=bool(getattr(hf, "tie_word_embeddings", False)),
        attn_layers=(AttnLayer(None, float(getattr(hf, "rope_theta",
                                                   10000.0)), rope=False),),
        layer_pattern=pattern,
        ssm=ssm if "M" in pattern else None,
    )
    cfg.validate_arch()
    moe = MoEConfig(
        n_experts=int(hf.n_routed_experts),
        top_k=int(hf.num_experts_per_tok),
        dispatch="dropless",
        scoring="sigmoid",
        norm_topk=bool(getattr(hf, "norm_topk_prob", False)),
        route_scale=float(getattr(hf, "routed_scaling_factor", 1.0)),
        n_shared=int(getattr(hf, "n_shared_experts", 0) or 0),
        expert_hidden=int(hf.moe_intermediate_size),
        shared_hidden=int(hf.moe_shared_expert_intermediate_size),
        held=None if held is None else (int(held[0]), int(held[1])),
        select="bias",
        act="relu2",
    )
    return cfg, moe


__all__ += ["config_from_hf_nemotron_h"]


# Solar-Open2's full layers stand one in four, from layer 0 (its 3:1 of
# delta-rule to full layers); ``gqa_interval`` is not read.
SOLAR_GQA_PERIOD = 4


def config_from_hf_solar_open2(hf_config: Any, held: Any = None) -> tuple:
    """(TransformerConfig, MoEConfig) for a published ``model_type:
    solar_open2`` config (Upstage's Solar-Open2: the key set
    ``gqa_layers``, ``use_gqa_gate``, ``use_rope``,
    ``linear_attn_config`` {``num_heads``, ``head_dim``,
    ``num_kv_heads``, ``short_conv_kernel_size``}, ``kda_use_full_proj``,
    ``kda_allow_neg_eigval``, ``n_routed_experts``, ``n_shared_experts``,
    ``num_experts_per_tok``, ``moe_intermediate_size``,
    ``norm_topk_prob``, ``routed_scaling_factor``,
    ``first_k_dense_replace``).  ``hf_config`` is any object with those
    attributes, as they stand in ``config.json``.

    A published layer is a mixer, then experts, each ``x + F(norm(x))``:
    two letters of ``cfg.layer_pattern``, ``*E`` for a layer of
    ``gqa_layers`` (gated GQA, causal and full, nothing rotated) and
    ``KE`` for the others (the gated delta-rule mixer of
    ``cfg.linear_attn``, ``models.kda``; its low-rank projections of
    ``head_dim``).  Experts are SwiGLU, chosen by sigmoid score plus
    ``e_score_correction_bias`` (the layer's ``router_bias``) and
    weighted by the score, normalised and scaled, beside one shared
    expert of ``n_shared_experts x moe_intermediate_size``.
    ``held=(first, count)`` makes the expert layers one chip's share
    (``MoEConfig.held``).  Serving path only: the training block refuses
    the pattern by name.  ``kda_use_full_proj: true`` (full-rank decay
    and gate projections), ``use_rope: true``, leading dense layers, a
    ``num_kv_heads`` other than ``num_heads`` and ``gqa_layers`` off the
    one-in-four pattern raise instead of being ignored."""
    from torchgpipe_tpu.models.moe import MoEConfig
    from torchgpipe_tpu.models.transformer import AttnLayer, LinearAttnConfig

    hf = hf_config
    la = dict(hf.linear_attn_config)
    n = int(hf.num_hidden_layers)
    for key, want in (("kda_use_full_proj", False), ("use_rope", False),
                      ("first_k_dense_replace", 0)):
        if getattr(hf, key, want) != want:
            raise ValueError(
                f"{key}={getattr(hf, key)!r} is not computed here "
                f"({want!r} is)"
            )
    full = sorted(int(i) for i in hf.gqa_layers)
    if full != list(range(0, n, SOLAR_GQA_PERIOD)):
        raise ValueError(
            f"gqa_layers={full} is off the pattern computed here: one full "
            f"layer in {SOLAR_GQA_PERIOD}, from layer 0, of "
            f"num_hidden_layers={n} ({list(range(0, n, SOLAR_GQA_PERIOD))})"
        )
    heads = int(la["num_heads"])
    if la.get("num_kv_heads") not in (None, heads):
        raise ValueError(
            f"linear_attn_config num_kv_heads={la['num_kv_heads']} is not "
            f"computed here (None, or num_heads={heads}, is)"
        )
    head_dim = int(la["head_dim"])
    pattern = "".join("*E" if i in full else "KE" for i in range(n))
    theta = float(getattr(hf, "rope_theta", 10000.0))
    cfg = TransformerConfig(
        vocab=hf.vocab_size,
        dim=hf.hidden_size,
        n_layers=len(pattern),
        n_heads=hf.num_attention_heads,
        n_kv_heads=hf.num_key_value_heads,
        n_head_dim=int(hf.head_dim),
        rope_theta=theta,
        norm_eps=float(getattr(hf, "rms_norm_eps", 1e-5)),
        tie_embeddings=bool(getattr(hf, "tie_word_embeddings", False)),
        attn_gate=bool(getattr(hf, "use_gqa_gate", False)),
        attn_layers=(AttnLayer(None, theta, rope=False),),
        layer_pattern=pattern,
        linear_attn=LinearAttnConfig(
            n_heads=heads,
            head_dim=head_dim,
            conv_kernel=int(la.get("short_conv_kernel_size", 4)),
            neg_eigval=bool(getattr(hf, "kda_allow_neg_eigval", False)),
        ),
    )
    cfg.validate_arch()
    moe = MoEConfig(
        n_experts=int(hf.n_routed_experts),
        top_k=int(hf.num_experts_per_tok),
        dispatch="dropless",
        scoring="sigmoid",
        norm_topk=bool(getattr(hf, "norm_topk_prob", False)),
        route_scale=float(getattr(hf, "routed_scaling_factor", 1.0)),
        n_shared=int(getattr(hf, "n_shared_experts", 0) or 0),
        expert_hidden=int(hf.moe_intermediate_size),
        held=None if held is None else (int(held[0]), int(held[1])),
        select="bias",
    )
    return cfg, moe


__all__ += ["config_from_hf_solar_open2"]
