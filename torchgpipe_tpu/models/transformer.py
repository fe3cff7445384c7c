"""Llama-style transformer as a sequential layer list / stacked pipeline block.

The flagship model family for the TPU build (BASELINE.json: "Llama-3-8B as
nn.Sequential of transformer blocks, 8-stage pipeline on v5p-8").  Design is
MXU-first: all heavy math is batched einsum/matmul in (optionally) bfloat16,
static shapes, rotary embeddings computed from shape, grouped-query attention
(GQA) as in Llama 3.

Two consumption modes:

* :func:`llama` — a flat ``List[Layer]`` (embedding, n blocks, head) for the
  MPMD :class:`~torchgpipe_tpu.gpipe.GPipe` engine with an explicit balance.
* :func:`llama_spmd` — ``(block, pre, post)`` for the compiled
  :class:`~torchgpipe_tpu.spmd.SpmdGPipe` engine: blocks must be stacked, so
  each pipeline stage runs ``layers_per_stage`` identical blocks.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Callable, List, Optional, Tuple

import jax
import jax.numpy as jnp

from jax.ad_checkpoint import checkpoint_name
from jax.sharding import PartitionSpec as P

from torchgpipe_tpu.layers import Layer, chain
from torchgpipe_tpu.models.recurrent import MIXERS
from torchgpipe_tpu.parallel import attention
from torchgpipe_tpu.parallel.ring_attention import axis_bound
from torchgpipe_tpu.parallel.tensor import (
    all_gather_value,
    pmax_stop,
    psum_grad,
    psum_value,
)


@dataclasses.dataclass(frozen=True)
class YarnRope:
    """YaRN rotary frequency scaling (Peng et al., arXiv:2309.00071) as
    the published ``rope_scaling`` record of ``type: yarn`` states it.
    ``models.mla.yarn_inv_freq`` / ``yarn_mscale`` compute from it."""

    factor: float
    original_max_pos: int
    beta_fast: float = 32.0
    beta_slow: float = 1.0
    mscale: float = 1.0
    mscale_all_dim: float = 0.0


@dataclasses.dataclass(frozen=True)
class AttnLayer:
    """The attention of one layer TYPE of a K/V-family model: its window
    (attend iff ``0 <= qpos - kpos < window``; None is full causal
    attention) and its rope record.  ``TransformerConfig.attn_layers``
    holds one entry a type, in the order the types repeat.  ``rope=False``
    is a layer whose q and k are NOT rotated (position reaches it through
    the causal mask and the rotated layers around it)."""

    window: Optional[int]
    rope_theta: float
    yarn: Optional[YarnRope] = None
    rope: bool = True


@dataclasses.dataclass(frozen=True)
class MLAConfig:
    """Multi-head latent attention (DeepSeek-V2, arXiv:2405.04434): the
    five sizes and the rope-scaling record.  Queries go through a
    ``q_lora_rank`` bottleneck; keys and values are expanded from ONE
    ``kv_lora_rank`` latent a token plus one rotary key head of
    ``qk_rope_head_dim`` shared by all heads — and that pair is what the
    cache holds (``models.kv_cache.LatentCache``), not K and V."""

    q_lora_rank: int
    kv_lora_rank: int
    qk_nope_head_dim: int
    qk_rope_head_dim: int
    v_head_dim: int
    rope_scaling: Optional[YarnRope] = None

    @property
    def qk_head_dim(self) -> int:
        return self.qk_nope_head_dim + self.qk_rope_head_dim

    @property
    def cache_row(self) -> int:
        """Values cached a token a layer."""
        return self.kv_lora_rank + self.qk_rope_head_dim


@dataclasses.dataclass(frozen=True)
class SSMConfig:
    """A Mamba-2 mixer (Dao & Gu, arXiv:2405.21060) as Nemotron-H's
    record states it: ``n_heads`` heads of ``head_dim``, their
    ``n_groups`` groups of B and C of ``state`` each (head ``h`` reads
    group ``h // (n_heads // n_groups)``), a causal depthwise conv of
    ``conv_kernel`` taps over ``[x | B | C]``, and the gated norm over
    groups of ``d_inner // n_groups``.  What a slot keeps of it
    (``models.kv_cache.HybridCache``) is the conv's last ``conv_kernel -
    1`` inputs and the f32 state ``[n_heads, head_dim, state]``, neither
    of which grows with the context.  ``chunk`` is the length of a block
    of the chunked (SSD) form a prompt is absorbed in; it changes the
    order of the sums, not the mathematics."""

    n_heads: int
    head_dim: int
    n_groups: int
    state: int
    conv_kernel: int = 4
    chunk: int = 128

    @property
    def d_inner(self) -> int:
        return self.n_heads * self.head_dim

    @property
    def conv_dim(self) -> int:
        """Channels of the conv: x, then B and C of every group."""
        return self.d_inner + 2 * self.n_groups * self.state


@dataclasses.dataclass(frozen=True)
class LinearAttnConfig:
    """A gated delta-rule mixer with per-channel decay (Kimi Delta
    Attention, arXiv:2510.26692; ``models.kda``) as Solar-Open2's
    ``linear_attn_config`` states it: ``n_heads`` heads of ``head_dim``
    for q, k and v alike, each through a causal depthwise conv of
    ``conv_kernel`` taps, the decay and the output gate through low-rank
    projections (their width is that of the weights), ``neg_eigval`` the
    factor 2 on ``beta`` that lets the transition's eigenvalue along
    ``k`` reach (-1, 1).  What a slot keeps of it
    (``models.kv_cache.HybridCache``) is the convs' last ``conv_kernel -
    1`` inputs and the f32 state ``[n_heads, head_dim, head_dim]``,
    neither of which grows with the context."""

    n_heads: int
    head_dim: int
    conv_kernel: int = 4
    neg_eigval: bool = True

    @property
    def width(self) -> int:
        """Channels of q, of k and of v: ``n_heads * head_dim``."""
        return self.n_heads * self.head_dim

    @property
    def conv_dim(self) -> int:
        """Channels of the conv: q, k and v side by side."""
        return 3 * self.width


# The letters of a hybrid layer pattern (``TransformerConfig.layer_pattern``):
# the one thing a layer computes besides its pre-norm and residual.  Every
# mixer that keeps a state a slot (``recurrent.MIXERS``) is a ``'mixer'``.
LAYER_LETTERS = {**{letter: "mixer" for letter in MIXERS},
                 "E": "experts", "*": "attention"}


@dataclasses.dataclass(frozen=True)
class TransformerConfig:
    vocab: int = 32000
    dim: int = 512
    n_layers: int = 8
    n_heads: int = 8
    n_kv_heads: Optional[int] = None  # None -> MHA; < n_heads -> GQA
    mlp_ratio: float = 4.0
    rope_theta: float = 500000.0  # Llama-3 default
    norm_eps: float = 1e-5
    dtype: jnp.dtype = jnp.float32  # bfloat16 for TPU benches
    # Sequence/context parallelism: name of the mesh axis the sequence is
    # sharded over (+ sp-offset rotary positions).  None = single-shard
    # sequences.  See torchgpipe_tpu.parallel.ring_attention.
    sp_axis: Optional[str] = None
    # How sp attention is computed: 'ring' (blockwise ring attention,
    # O(s/sp) attention memory — extreme lengths) or 'ulysses' (all_to_all
    # head swap, full-sequence local compute so the flash kernel applies —
    # moderate lengths; needs head counts divisible by the sp size).  See
    # torchgpipe_tpu.parallel.ulysses.
    sp_impl: str = "ring"
    # Sliding-window (Mistral-style local) attention: attend iff
    # 0 <= qpos - kpos < attn_window.  None = full causal attention.
    # Composes with sp_impl='ulysses' (full-seq local compute windows
    # exactly) but not the ring path.
    attn_window: Optional[int] = None
    # Attention described per layer: a repeating PERIOD of
    # :class:`AttnLayer` entries, layer ``i`` taking entry ``i % len``
    # (window and full layers mixed, each kind with its own rope record).
    # None is the one-entry period ``(AttnLayer(attn_window,
    # rope_theta),)``: every reader goes through :meth:`attn_layer`, so a
    # model-global window is the same description, not a second path.
    # Trained through ``transformer_block``; generated from and served
    # through a cache whose window layers hold a ring and whose full
    # layers hold ``max_len`` rows (``models.kv_cache.layer_rows``).
    attn_layers: Optional[Tuple[AttnLayer, ...]] = None
    # Tensor parallelism: name of the mesh axis attention heads and MLP
    # hidden units are sharded over (Megatron-style; see
    # torchgpipe_tpu.parallel.tensor).  None = no weight sharding.  The tp
    # size must divide n_heads, kv_heads and mlp_hidden (the engine checks
    # against the actual mesh at init).
    tp_axis: Optional[str] = None
    # Qwen2-style additive biases on the q/k/v projections (params
    # bq/bk/bv; wo stays bias-free, matching that family).  Composes with
    # tp (biases shard with their head dim).
    attn_bias: bool = False
    # Qwen3-style per-head RMSNorm on q and k (params ``qn``/``kn``,
    # [head_dim], applied before rotary).
    qk_norm: bool = False
    # Output gate on the attention (the ``afmoe`` block): param ``wg``
    # ``[dim, n_heads * head_dim]``; the heads' output is multiplied by
    # ``sigmoid(ln1(x) @ wg)`` before ``wo``.  Serving path only: the
    # training block refuses it by name.
    attn_gate: bool = False
    # Sandwich norms: each branch's OUTPUT is normed too before it joins
    # the residual stream, ``x + ln1p(attn(ln1(x)))`` and ``x +
    # ln2p(ff(ln2(x)))`` (params ``ln1p`` / ``ln2p``).  Serving path
    # only: the training block refuses it by name.
    sandwich_norm: bool = False
    # Explicit per-head dimension (Gemma/Qwen3-class checkpoints where
    # n_heads * head_dim != dim; the attention output projection maps
    # n_heads*head_dim back to dim).  None -> dim // n_heads.
    n_head_dim: Optional[int] = None
    # Feed-forward gate activation: 'silu' (Llama-family SwiGLU) or
    # 'gelu_tanh' (Gemma-family GeGLU; also GPT-2's gelu_new).
    act: str = "silu"
    # ---- classic (GPT-2/Pythia-class) architecture knobs ------------- #
    # Normalization: 'rms' (Llama family) or 'layernorm' (mean-centered,
    # with bias params ``ln1b``/``ln2b`` per block and ``bias`` on the
    # final norm — the GPT-2/OPT/Pythia class).
    norm: str = "rms"
    # Positions: 'rope' (rotary, the default) or 'learned' (absolute
    # position embedding table ``pos`` [max_pos, dim] added at the
    # embedding — GPT-2 class; requires ``max_pos``, the TABLE size).
    pos_emb: str = "rope"
    max_pos: Optional[int] = None
    # Learned-table row offset: position p reads row p + offset (OPT
    # reserves the first 2 rows, so its table has max_positions + 2 rows
    # and every lookup shifts by 2).
    pos_emb_offset: int = 0
    # Feed-forward shape: 'gated' (SwiGLU/GeGLU two-matrix gate) or
    # 'classic' (fc -> act -> proj with biases ``b_fc``/``b_proj``;
    # hidden = mlp_ratio * dim exactly — GPT-2's 4x).
    mlp_impl: str = "gated"
    # Bias on the attention output projection (param ``bo`` — GPT-2 has
    # biases on every projection; pair with attn_bias for q/k/v).
    attn_out_bias: bool = False
    # GPT-NeoX/Pythia-style PARALLEL residual: x + attn(ln1(x)) +
    # mlp(ln2(x)) — both branches read the SAME input instead of
    # chaining (one residual add, better overlap).
    parallel_residual: bool = False
    # Causal masking.  False = bidirectional (encoder-style) attention —
    # the ViT family; the KV-cache generation API is causal by
    # construction and rejects non-causal configs.
    causal: bool = True
    # Residual-norm placement: 'pre' (norm the branch INPUT — every
    # decoder family here) or 'post' (norm the residual SUM,
    # ``LN(x + branch(x))`` — the BERT/original-transformer class).
    norm_position: str = "pre"
    # BERT-style LayerNorm applied to the summed embeddings (token +
    # position) before the first block (embed params ``eln``/``elnb``).
    embed_layernorm: bool = False
    # Partial rotary (GPT-NeoX rotary_pct): only the first
    # ``int(head_dim * rope_pct)`` dims of each head rotate; the rest
    # pass through position-free.  1.0 = full rotary (Llama).
    rope_pct: float = 1.0
    # Multiply embedding outputs by this factor (Gemma scales by
    # sqrt(dim); the TIED head still reads the unscaled table, matching
    # that family).  None -> no scaling.
    embed_scale: Optional[float] = None
    # LoRA (Hu et al., arXiv:2106.09685) low-rank adapters on the
    # attention projections (q/k/v/o): rank of the adapters, or None for
    # no adapters.  Params live under the block's ``"lora"`` subdict
    # (A ~ N(0, 1/sqrt(dim)), B zero-init — the delta starts at 0, so a
    # freshly-adapted model computes exactly the base model).  Train
    # adapters only via ``models.lora.lora_optimizer`` (NOT
    # ``optax.masked``, which leaks raw gradients into the base); fold
    # them into the base weights with ``models.lora.merge_lora``.
    lora_rank: Optional[int] = None
    lora_alpha: float = 16.0
    # GPT-2/Gemma-style weight tying: the lm head reuses the embedding
    # table (logits = h @ table.T) instead of owning a separate ``w``.
    # The classic pipeline-parallel pain point — the two uses live on
    # opposite pipeline ends, so MPMD frameworks need a cross-stage grad
    # reduction — dissolves in the SPMD engine: pre params are replicated
    # across pp lanes and the head reads the SAME traced array, so
    # autodiff sums both gradient paths and the engine's existing
    # pre-grad psum over pp collects them.  Supported by ``llama_spmd``
    # + ``SpmdGPipe`` (fill-drain schedule) and by decode; the flat
    # ``llama()`` MPMD list rejects it with a pointer.
    tie_embeddings: bool = False
    # Attention kind: None is the K/V family above (MHA/GQA); an
    # :class:`MLAConfig` makes every block latent attention, whose cache
    # row is the latent and not K and V.  Serving path only
    # (``models.generation``, ``serving.Engine``): the training block
    # refuses it.
    mla: Optional[MLAConfig] = None
    # A hybrid model (Nemotron-H's ``hybrid_override_pattern``): one
    # letter a layer of :data:`LAYER_LETTERS`, each layer ``x + F(norm(x))``
    # with ``F`` ONE of a Mamba-2 mixer (``M``, of ``ssm``), a gated
    # delta-rule mixer (``K``, of ``linear_attn``), an expert
    # feed-forward (``E``) or attention (``*``): nothing pairs attention
    # with a feed-forward, so a published layer of attention and experts
    # is two letters.  None: every layer is attention then a
    # feed-forward.  Serving path only (``models.generation``,
    # ``serving.Engine``): the training block refuses it.
    layer_pattern: Optional[str] = None
    ssm: Optional[SSMConfig] = None
    linear_attn: Optional[LinearAttnConfig] = None

    def layer_type(self, layer: int) -> str:
        """What layer ``layer`` computes: ``'block'`` (attention, then a
        feed-forward), or under a ``layer_pattern`` its letter's
        ``'mixer'`` / ``'experts'`` / ``'attention'``."""
        if self.layer_pattern is None:
            return "block"
        return LAYER_LETTERS[self.layer_pattern[layer]]

    @property
    def keeps_state(self) -> bool:
        """Whether some layer keeps a recurrent state a slot (a letter of
        ``recurrent.MIXERS`` in the pattern)."""
        return self.layer_pattern is not None and any(
            c in MIXERS for c in self.layer_pattern)

    @property
    def attn_kind(self) -> str:
        return "gqa" if self.mla is None else "mla"

    @property
    def attn_period(self) -> Tuple[AttnLayer, ...]:
        """The repeating per-layer attention description."""
        if self.attn_layers is not None:
            return self.attn_layers
        return (AttnLayer(self.attn_window, self.rope_theta),)

    def attn_layer(self, layer: int) -> AttnLayer:
        """The entry that layer ``layer`` (0-based, model-global) takes."""
        period = self.attn_period
        return period[layer % len(period)]

    @property
    def kv_heads(self) -> int:
        return self.n_kv_heads or self.n_heads

    @property
    def head_dim(self) -> int:
        return self.n_head_dim or self.dim // self.n_heads

    @property
    def mlp_hidden(self) -> int:
        if self.mlp_impl == "classic":
            # GPT-2-style: exactly ratio * dim (published sizes are
            # MXU-friendly already: 4 * 768 = 3072, ...).  round() — not
            # int() — so a ratio stored as n_inner/dim survives float
            # round-trip (int() truncates 472.9999... to 472).
            return int(round(self.mlp_ratio * self.dim))
        # Llama-style 2/3 * 4 * dim, rounded to a multiple of 128 (MXU tile).
        h = int(2 * self.mlp_ratio * self.dim / 3)
        return max(128, ((h + 127) // 128) * 128)

    def validate_arch(self) -> None:
        """Fail fast on unknown/inconsistent architecture knobs — called
        by the layer builders so a typo'd config errors at model build,
        not deep inside a trace."""
        if self.norm not in ("rms", "layernorm"):
            raise ValueError(
                f"norm={self.norm!r}: expected 'rms' or 'layernorm'"
            )
        if self.pos_emb not in ("rope", "learned"):
            raise ValueError(
                f"pos_emb={self.pos_emb!r}: expected 'rope' or 'learned'"
            )
        if self.mlp_impl not in ("gated", "classic"):
            raise ValueError(
                f"mlp_impl={self.mlp_impl!r}: expected 'gated' or 'classic'"
            )
        if self.pos_emb == "learned" and not self.max_pos:
            raise ValueError(
                "pos_emb='learned' needs max_pos (the position table "
                "size — HF GPT2Config.n_positions)"
            )
        if self.norm_position not in ("pre", "post"):
            raise ValueError(
                f"norm_position={self.norm_position!r}: expected 'pre' "
                "or 'post'"
            )
        if self.norm_position == "post" and self.parallel_residual:
            raise ValueError(
                "norm_position='post' and parallel_residual do not "
                "compose (no published family; the parallel form is "
                "defined on pre-norm branches)"
            )
        if self.attn_layers is not None and (
            not self.attn_layers or self.attn_window is not None
        ):
            raise ValueError(
                "attn_layers is a non-empty period of AttnLayer entries "
                "and carries the windows itself: leave attn_window None"
            )
        if self.layer_pattern is not None:
            unknown = sorted(set(self.layer_pattern) - set(LAYER_LETTERS))
            if unknown:
                raise ValueError(
                    f"layer_pattern letters {unknown}: a layer is one of "
                    f"{LAYER_LETTERS}"
                )
            if len(self.layer_pattern) != self.n_layers:
                raise ValueError(
                    f"layer_pattern has {len(self.layer_pattern)} letters "
                    f"for n_layers={self.n_layers}"
                )
            for letter, mixer in MIXERS.items():
                if ((letter in self.layer_pattern)
                        != (getattr(self, mixer.config) is not None)):
                    raise ValueError(
                        f"a layer_pattern with mixer layers ({letter!r}) "
                        f"needs cfg.{mixer.config}, and cfg.{mixer.config} "
                        "needs them"
                    )
            la = self.linear_attn
            if la is not None and la.conv_kernel < 1:
                raise ValueError(
                    f"linear_attn conv_kernel={la.conv_kernel} must be at "
                    "least 1"
                )
        else:
            for letter, mixer in MIXERS.items():
                if getattr(self, mixer.config) is not None:
                    raise ValueError(
                        f"cfg.{mixer.config} describes the {letter!r} layers "
                        "of a layer_pattern: set one")
        if not 0.0 < self.rope_pct <= 1.0:
            raise ValueError(f"rope_pct={self.rope_pct} must be in (0, 1]")
        if self.rope_pct < 1.0 and int(self.head_dim * self.rope_pct) % 2:
            raise ValueError(
                f"rope_pct={self.rope_pct} rotates "
                f"{int(self.head_dim * self.rope_pct)} of {self.head_dim} "
                "head dims — the rotated count must be even (half-split "
                "rotary)"
            )
        _act_fn(self.act)  # raises on unknown activation names


def _normal(
    rng: jax.Array,
    shape: Tuple[int, ...],
    std: float,
    dtype: Any,
) -> jnp.ndarray:
    return (std * jax.random.normal(rng, shape)).astype(dtype)


def _norm(
    x: jnp.ndarray,
    scale: jnp.ndarray,
    eps: float,
    bias: Optional[jnp.ndarray] = None,
    centered: bool = False,
) -> jnp.ndarray:
    """Trailing-dim normalization, f32 accumulation: RMS by default;
    ``centered=True`` subtracts the mean first (LayerNorm), ``bias`` adds
    the affine offset.  The un-centered bias-free path is bit-identical
    to the historical ``_rms``."""
    xf = x.astype(jnp.float32)
    if centered:
        xf = xf - jnp.mean(xf, -1, keepdims=True)
    var = jnp.mean(jnp.square(xf), -1, keepdims=True)
    y = (xf.astype(x.dtype) if centered else x)
    y = y * jax.lax.rsqrt(var + eps).astype(x.dtype)
    y = y * scale.astype(x.dtype)
    if bias is not None:
        y = y + bias.astype(x.dtype)
    return y


def _rms(x: jnp.ndarray, scale: jnp.ndarray, eps: float) -> jnp.ndarray:
    """RMS normalization over the trailing dim (f32 accumulation)."""
    return _norm(x, scale, eps)


def _block_norm(
    cfg: TransformerConfig, p: Any, key: str, x: jnp.ndarray
) -> jnp.ndarray:
    """The block's configured normalization at param ``key`` (``ln1``/
    ``ln2``/head ``scale``): RMS, or LayerNorm when ``cfg.norm ==
    'layernorm'`` (bias param ``key + 'b'`` if present, ``'bias'`` for
    the head's ``scale``).  ONE definition shared by the training block
    and every generation path."""
    bkey = "bias" if key == "scale" else key + "b"
    return _norm(
        x, p[key], cfg.norm_eps,
        bias=p.get(bkey), centered=cfg.norm == "layernorm",
    )


def _lora_delta(
    cfg: TransformerConfig,
    lo: Any,
    x: jnp.ndarray,
    a: str,
    b: str,
) -> jnp.ndarray:
    """One adapter's contribution ``(x @ A) @ B * alpha/rank`` — the
    single definition of the LoRA math shared by the training block and
    the generation prefill/decode paths."""
    return ((x @ lo[a]) @ lo[b]) * (cfg.lora_alpha / cfg.lora_rank)


def _act_fn(act: str) -> Callable[[jnp.ndarray], jnp.ndarray]:
    """Feed-forward gate activation by config name."""
    if act == "silu":
        return jax.nn.silu
    if act == "gelu_tanh":
        return lambda x: jax.nn.gelu(x, approximate=True)
    if act == "gelu":  # exact (erf) variant — Pythia/GPT-NeoX class
        return lambda x: jax.nn.gelu(x, approximate=False)
    if act == "relu":  # OPT class
        return jax.nn.relu
    raise ValueError(
        f"unknown act {act!r}: expected 'silu', 'gelu_tanh', 'gelu', "
        "or 'relu'"
    )


def rms_norm(dim: int, *, eps: float = 1e-5, name: str = "rmsnorm") -> Layer:
    def init(rng, in_spec):
        del rng, in_spec
        return {"scale": jnp.ones((dim,))}, ()

    def apply(params, state, x, *, rng=None, train=True):
        del rng, train
        return _rms(x, params["scale"], eps), state

    return Layer(
        name=name, init=init, apply=apply, meta={"kind": "rms_norm", "eps": eps}
    )


def _rope(x: jnp.ndarray, theta: float, pos_offset: Any = 0,
          freqs: Optional[Any] = None, amplitude: float = 1.0) -> jnp.ndarray:
    """Rotary position embedding over the trailing head_dim, positions from
    shape plus ``pos_offset`` (x: [b, s, heads, head_dim]).  A non-zero
    offset gives sequence-parallel shards their *global* token positions;
    a ``[b]``-shaped offset gives every batch row its OWN base position —
    the slot-pooled serving decode, where each slot sits at a different
    sequence frontier.  A ``[b, s]``-shaped offset is taken as ABSOLUTE
    per-token positions (sequence packing: each packed document's
    positions restart at 0 — ``utils.data.pack_documents``).  ``freqs``
    ``[head_dim // 2]`` replaces ``theta``'s inverse frequencies and
    ``amplitude`` scales cos and sin (YaRN: ``models.mla.rope``)."""
    b, s, h, d = x.shape
    half = d // 2
    if freqs is None:
        freqs = 1.0 / (
            theta ** (jnp.arange(0, half, dtype=jnp.float32) / half))
    # [B', s] positions with B' = b (per-row offset / per-token packed
    # positions) or 1 (shared) — one rotation body either way; the B'=1
    # case broadcasts exactly as the pre-per-row [1, s, 1, half] cos/sin
    # did.
    off = jnp.asarray(pos_offset, jnp.float32)
    if off.ndim == 2:
        positions = off                       # absolute per-token [b, s]
    else:
        positions = off.reshape(-1, 1) + jnp.arange(s, dtype=jnp.float32)
    ang = positions[..., None] * freqs  # [B', s, half]
    cos = jnp.cos(ang)[:, :, None, :]
    sin = jnp.sin(ang)[:, :, None, :]
    if amplitude != 1.0:
        cos, sin = amplitude * cos, amplitude * sin
    x1, x2 = x[..., :half], x[..., half:]
    out = jnp.concatenate(
        [
            x1 * cos - x2 * sin,
            x2 * cos + x1 * sin,
        ],
        axis=-1,
    )
    return out.astype(x.dtype)


def _maybe_rope(
    cfg: TransformerConfig, x: jnp.ndarray, pos_offset: Any, layer: int = 0
) -> jnp.ndarray:
    """The config's position treatment for a ``[b, s, heads, head_dim]``
    projection of layer ``layer``: full rotary, PARTIAL rotary
    (``rope_pct < 1`` — GPT-NeoX rotates only the leading
    ``int(head_dim * rope_pct)`` dims), or nothing (``pos_emb='learned'``
    models position at the embedding); theta, and YaRN's frequencies and
    amplitude where the layer's rope record has them, from
    ``cfg.attn_layer(layer)``.  ONE definition shared by the training
    block and every generation path."""
    entry = cfg.attn_layer(layer)
    if cfg.pos_emb != "rope" or not entry.rope:
        return x
    rot = x.shape[-1] if cfg.rope_pct >= 1.0 else int(
        x.shape[-1] * cfg.rope_pct)
    freqs, amplitude = None, 1.0
    if entry.yarn is not None:
        from torchgpipe_tpu.models.mla import yarn_amplitude, yarn_inv_freq

        freqs = jnp.asarray(yarn_inv_freq(rot, entry.rope_theta, entry.yarn))
        amplitude = yarn_amplitude(entry.yarn)

    def rotate(part):
        return _rope(part, entry.rope_theta, pos_offset, freqs=freqs,
                     amplitude=amplitude)

    if cfg.rope_pct >= 1.0:
        return rotate(x)
    return jnp.concatenate([rotate(x[..., :rot]), x[..., rot:]], axis=-1)


# --------------------------------------------------------------------- #
# sequence packing: the packed activation contract                      #
#                                                                       #
# A packed batch enters the model as a dict                             #
# {"tokens", "segment_ids", "positions"} (utils.data.pack_documents);   #
# token_embedding turns it into the PACKED ACTIVATION TUPLE             #
# (hidden [b, s, dim], segment_ids [b, s], positions [b, s]) that rides #
# unchanged through every transformer_block — each block folds the      #
# block-diagonal segment mask into its attention and rotates queries at #
# the packed per-token positions — until lm_head consumes the tuple and #
# emits plain logits.  Both pipeline engines move activations as        #
# pytrees, so the tuple flows through scatter/ring/remat machinery with #
# no engine changes.                                                    #
# --------------------------------------------------------------------- #


def _is_packed_batch(x: Any) -> bool:
    """A raw packed input batch (the packer's dict contract)."""
    return isinstance(x, dict) and "tokens" in x and "segment_ids" in x


def _is_packed_act(x: Any) -> bool:
    """A packed activation tuple between layers: (hidden, seg, pos)."""
    return isinstance(x, tuple) and len(x) == 3


def transformer_block(
    cfg: TransformerConfig, *, name: str = "block",
    mlp: Optional[Layer] = None, layer: int = 0,
) -> Layer:
    """One pre-norm block: x + attn(norm(x)); x + mlp(norm(x)).

    Residuals are internal to the layer, so a pipeline can split the model at
    any block boundary without skip routing.

    ``layer`` is the block's 0-based position in the model: its window and
    rope record are ``cfg.attn_layer(layer)``'s (one entry for a model-
    global window, a repeating period where window and full layers mix).

    ``mlp`` swaps the dense SwiGLU feed-forward for a custom layer on the
    normalized hidden states (e.g. :func:`torchgpipe_tpu.models.moe.moe_mlp`
    for a mixture-of-experts block); its params live under the ``"mlp"`` key
    and its ``meta`` (param_specs / validate_mesh / ep_axis) is composed into
    the block's.
    """
    cfg.validate_arch()
    if cfg.mla is not None:
        raise NotImplementedError(
            "latent attention (cfg.mla) is computed on the serving path "
            "only (models.generation.prefill / decode_slots, "
            "serving.Engine; block params from models.mla.init_block); "
            "the training block has no MLA forward"
        )
    if cfg.layer_pattern is not None:
        raise NotImplementedError(
            f"layer_pattern={cfg.layer_pattern!r} (mixer-only, expert-only "
            "and attention-only layers; the Mamba-2 and delta-rule mixers' "
            "state) is computed on the serving path only "
            "(models.generation.prefill / decode_slots, serving.Engine); "
            "the training block has no mixer forward or backward"
        )
    for on, what in ((cfg.attn_gate, "attn_gate (the attention's output "
                      "gate)"),
                     (cfg.sandwich_norm, "sandwich_norm (a norm on each "
                      "branch's output)")):
        if on:
            raise NotImplementedError(
                f"{what} is computed on the serving path only "
                "(models.generation.prefill / decode_slots, "
                "serving.Engine); the training block does not compute it "
                "and will not ignore it"
            )
    dim, hd = cfg.dim, cfg.head_dim
    nh, nkv = cfg.n_heads, cfg.kv_heads
    hidden = cfg.mlp_hidden
    dt = cfg.dtype
    window = cfg.attn_layer(layer).window
    # The operation table tells the two kinds of layer apart by this scope.
    attn_scope = "attn.window" if window is not None else "attn.full"
    mlp_meta = mlp.meta if (mlp is not None and isinstance(mlp.meta, dict)) else {}
    # An expert layer that counts its held experts' tokens (the dropless
    # path): the block can hand the counts out beside its output.
    counted = mlp_meta.get("counts_held") is not None

    def init(rng, in_spec):
        ks = jax.random.split(rng, 9)
        std = dim ** -0.5
        params = {
            "ln1": jnp.ones((dim,)),
            "wq": _normal(ks[0], (dim, nh * hd), std, dt),
            "wk": _normal(ks[1], (dim, nkv * hd), std, dt),
            "wv": _normal(ks[2], (dim, nkv * hd), std, dt),
            "wo": _normal(ks[3], (nh * hd, dim), std, dt),
            "ln2": jnp.ones((dim,)),
        }
        if cfg.norm == "layernorm":
            params.update(
                ln1b=jnp.zeros((dim,)), ln2b=jnp.zeros((dim,))
            )
        if cfg.attn_out_bias:
            params["bo"] = jnp.zeros((dim,), dt)
        if cfg.attn_bias:
            params.update(
                bq=jnp.zeros((nh * hd,), dt),
                bk=jnp.zeros((nkv * hd,), dt),
                bv=jnp.zeros((nkv * hd,), dt),
            )
        if cfg.qk_norm:
            params.update(qn=jnp.ones((hd,)), kn=jnp.ones((hd,)))
        if cfg.lora_rank:
            r = cfg.lora_rank
            lk = jax.random.split(ks[7], 4)
            std = dim ** -0.5
            params["lora"] = {
                "qa": _normal(lk[0], (dim, r), std, dt),
                "qb": jnp.zeros((r, nh * hd), dt),
                "ka": _normal(lk[1], (dim, r), std, dt),
                "kb": jnp.zeros((r, nkv * hd), dt),
                "va": _normal(lk[2], (dim, r), std, dt),
                "vb": jnp.zeros((r, nkv * hd), dt),
                "oa": _normal(lk[3], (nh * hd, r), std, dt),
                "ob": jnp.zeros((r, dim), dt),
            }
        if mlp is None and cfg.mlp_impl == "classic":
            params.update(
                w_fc=_normal(ks[4], (dim, hidden), std, dt),
                b_fc=jnp.zeros((hidden,), dt),
                w_proj=_normal(ks[6], (hidden, dim), hidden ** -0.5, dt),
                b_proj=jnp.zeros((dim,), dt),
            )
        elif mlp is None:
            params.update(
                w_gate=_normal(ks[4], (dim, hidden), std, dt),
                w_up=_normal(ks[5], (dim, hidden), std, dt),
                w_down=_normal(ks[6], (hidden, dim), hidden ** -0.5, dt),
            )
        else:
            mp, ms = mlp.init(ks[8], in_spec)
            if jax.tree_util.tree_leaves(ms):
                raise ValueError(
                    f"transformer_block mlp {mlp.name!r} must be stateless"
                )
            params["mlp"] = mp
        return params, ()

    def apply(params, state, x, *, rng=None, train=True):
        return forward(params, x, rng, train)[0], state

    def apply_counts(params, x, *, rng=None, train=True):
        """``(y, counts)``: the block's output and the expert layer's
        held experts' token counts (``moe_mlp``'s ``forward_counts``)."""
        return forward(params, x, rng, train, counts=True)

    def forward(params, x, rng, train, counts=False):
        held = None
        # Sequence packing: a packed activation tuple carries the block-
        # diagonal mask term (segment_ids) and per-token positions through
        # the residual stream; both ride out unchanged.
        packed = _is_packed_act(x)
        seg = pk_pos = None
        if packed:
            x, seg, pk_pos = x
        b, s, _ = x.shape

        # Sequence parallelism: when the sp axis is bound (inside the SPMD
        # engine's shard_map), shards carry global rotary positions and run
        # ring attention; unbound (init-time inference, single-device use)
        # the local array is the whole sequence.
        sp_active = axis_bound(cfg.sp_axis)
        if packed and sp_active:
            raise ValueError(
                "packed batches (segment_ids) do not compose with a bound "
                "sequence-parallel axis; drop cfg.sp_axis for packed "
                "training"
            )
        pos_offset = (
            jax.lax.axis_index(cfg.sp_axis) * s if sp_active else 0
        )
        if packed:
            pos_offset = pk_pos  # [b, s] per-token packed positions
        # Tensor parallelism: inside the engine's shard_map the weight leaves
        # arrive pre-sliced (wq holds this lane's heads, w_gate this lane's
        # hidden units), so head counts come from the *local* weight shapes —
        # the same code runs the full weights when tp is off or unbound.
        tp_active = axis_bound(cfg.tp_axis)
        nh_loc = params["wq"].shape[1] // hd
        nkv_loc = params["wk"].shape[1] // hd

        post = cfg.norm_position == "post"
        # Post-norm (BERT class): the attention branch reads RAW x; ln1
        # normalizes the residual SUM below instead.
        h = x if post else _block_norm(cfg, params, "ln1", x)
        if tp_active:
            h = psum_grad(h, cfg.tp_axis)  # region entry: full grad upstream
        q, k, v = h @ params["wq"], h @ params["wk"], h @ params["wv"]
        if "lora" in params:
            lo = params["lora"]
            q = q + _lora_delta(cfg, lo, h, "qa", "qb")
            k = k + _lora_delta(cfg, lo, h, "ka", "kb")
            v = v + _lora_delta(cfg, lo, h, "va", "vb")
        if "bq" in params:  # Qwen2-style projection biases
            q, k, v = q + params["bq"], k + params["bk"], v + params["bv"]
        q = q.reshape(b, s, nh_loc, hd)
        k = k.reshape(b, s, nkv_loc, hd)
        v = v.reshape(b, s, nkv_loc, hd)
        if "qn" in params:  # Qwen3-style per-head q/k RMSNorm, pre-rope
            q = _rms(q, params["qn"], cfg.norm_eps)
            k = _rms(k, params["kn"], cfg.norm_eps)
        q = _maybe_rope(cfg, q, pos_offset, layer)
        k = _maybe_rope(cfg, k, pos_offset, layer)
        # GQA: K/V stay at n_kv heads — the attention kernel groups queries
        # at the compute site, so the sp ring only moves n_kv-head blocks.
        # Under tp, lanes hold contiguous head ranges, so the local q→kv
        # pairing (h // r with r = nh_loc/nkv_loc = nh/nkv) matches global.
        with jax.named_scope(attn_scope):
            attn = attention(
                q, k, v, axis_name=cfg.sp_axis if sp_active else None,
                causal=cfg.causal, impl=cfg.sp_impl, window=window,
                seg=seg,
            )
        attn_flat = attn.reshape(b, s, nh_loc * hd)
        attn_out = attn_flat @ params["wo"]
        if "lora" in params:
            attn_out = attn_out + _lora_delta(
                cfg, params["lora"], attn_flat, "oa", "ob"
            )
        if tp_active:
            attn_out = psum_value(attn_out, cfg.tp_axis)  # region exit
        if "bo" in params:
            # After the tp psum: the bias is per-output-feature, added
            # once — inside the region each lane would contribute a copy.
            attn_out = attn_out + params["bo"]
        # Named save point (checkpoint.NAMED_SAVE_POINTS): a remat policy
        # like checkpoint.policies.save_attn_out keeps (or offloads) this
        # one [b, s, dim] tensor per block and recomputes everything else.
        attn_out = checkpoint_name(attn_out, "attn_out")
        # GPT-NeoX-style parallel residual: the MLP branch reads the
        # BLOCK INPUT (ln2 of x, not of x + attn_out) and both branch
        # outputs land in one residual add at the end.
        x_in = x
        if post:
            x = _block_norm(cfg, params, "ln1", x + attn_out)
            h = x  # post-norm MLP branch reads the normalized sum raw
        else:
            x = x + attn_out
            h = _block_norm(
                cfg, params, "ln2", x_in if cfg.parallel_residual else x
            )
        if counts:
            mlp_out, held = mlp_meta["forward_counts"](
                params["mlp"], h, train=train)
        elif mlp is not None:
            mlp_out, _ = mlp.apply(params["mlp"], (), h, rng=rng, train=train)
        elif "w_fc" in params:
            # Classic (GPT-2-style) feed-forward: fc -> act -> proj.
            if tp_active:
                h = psum_grad(h, cfg.tp_axis)
            hid = _act_fn(cfg.act)(h @ params["w_fc"] + params["b_fc"])
            # Named save point: keeping the [b, s, hidden] activation lets
            # the backward recompute only the down-projection.
            hid = checkpoint_name(hid, "mlp_hidden")
            mlp_out = hid @ params["w_proj"]
            if tp_active:
                mlp_out = psum_value(mlp_out, cfg.tp_axis)
            mlp_out = mlp_out + params["b_proj"]  # once, post-psum
        else:
            if tp_active:
                h = psum_grad(h, cfg.tp_axis)
            gate = _act_fn(cfg.act)(h @ params["w_gate"])
            up = h @ params["w_up"]
            hid = checkpoint_name(gate * up, "mlp_hidden")
            mlp_out = hid @ params["w_down"]
            if tp_active:
                mlp_out = psum_value(mlp_out, cfg.tp_axis)
        if post:
            x = _block_norm(cfg, params, "ln2", x + mlp_out)
        else:
            x = x + mlp_out
        if packed:
            return (x, seg, pk_pos), held
        return x, held

    tp = cfg.tp_axis

    def validate_mesh(mesh):
        if tp is not None and tp in mesh.axis_names:
            size = mesh.shape[tp]
            checks = [("n_heads", nh), ("kv_heads", nkv)]
            if mlp is None:
                checks.append(("mlp_hidden", hidden))
            for what, count in checks:
                if count % size != 0:
                    raise ValueError(
                        f"{what}={count} is not divisible by the tp mesh "
                        f"axis size {size}; tensor parallelism shards whole "
                        "heads / hidden units across lanes"
                    )
        if (
            window is not None
            and cfg.sp_impl == "ring"
            and cfg.sp_axis is not None
            and cfg.sp_axis in mesh.axis_names
        ):
            # Same statically-knowable class as the ulysses head check
            # below: fail at engine init with the clean error, not inside
            # shard_map tracing.
            raise ValueError(
                "attn_window does not compose with sp_impl='ring' (the "
                "ring would need per-step band skipping); use "
                "sp_impl='ulysses' — its local full-sequence attention "
                "windows exactly — or drop the sp axis"
            )
        if (
            cfg.sp_impl == "ulysses"
            and cfg.sp_axis is not None
            and cfg.sp_axis in mesh.axis_names
        ):
            # Ulysses shards HEADS during the attention compute; under tp
            # the lanes already hold nh/tp heads, so the requirement is on
            # the LOCAL head counts.
            sp_size = mesh.shape[cfg.sp_axis]
            tp_size = (
                mesh.shape[tp] if tp is not None and tp in mesh.axis_names
                else 1
            )
            for what, count in (("n_heads", nh), ("kv_heads", nkv)):
                if (count // tp_size) % sp_size != 0:
                    raise ValueError(
                        f"sp_impl='ulysses' shards attention heads: local "
                        f"{what} ({count}//tp={count // tp_size}) must be "
                        f"divisible by the {cfg.sp_axis!r} axis size "
                        f"({sp_size}); use sp_impl='ring' for this head "
                        "count"
                    )
        if "validate_mesh" in mlp_meta:
            mlp_meta["validate_mesh"](mesh)

    # Per-stage param specs (pre-stacking): column-parallel projections shard
    # their output dim over tp, row-parallel their input dim; a custom mlp
    # contributes its own declared subtree (or stays replicated).  The dict
    # must name every param key, so it is built only when something in the
    # block is actually sharded.
    mlp_specs = mlp_meta.get("param_specs")
    if tp is not None or mlp_specs is not None:
        param_specs: Optional[dict] = {
            "ln1": P(),
            "wq": P() if tp is None else P(None, tp),
            "wk": P() if tp is None else P(None, tp),
            "wv": P() if tp is None else P(None, tp),
            "wo": P() if tp is None else P(tp, None),
            "ln2": P(),
        }
        if cfg.norm == "layernorm":
            param_specs.update(ln1b=P(), ln2b=P())
        if cfg.attn_out_bias:
            param_specs["bo"] = P()  # per-dim, added post-psum: replicated
        if cfg.attn_bias:
            # Biases shard with their projection's output (head) dim.
            bias_spec = P() if tp is None else P(tp)
            param_specs.update(bq=bias_spec, bk=bias_spec, bv=bias_spec)
        if cfg.qk_norm:
            # Per-head-dim vectors shared by every head: replicated.
            param_specs.update(qn=P(), kn=P())
        if cfg.lora_rank:
            # A factors replicate (or row-shard with wo); B factors shard
            # like their projection's output dim.
            param_specs["lora"] = {
                "qa": P(), "qb": P(None, tp),
                "ka": P(), "kb": P(None, tp),
                "va": P(), "vb": P(None, tp),
                "oa": P(tp, None) if tp is not None else P(), "ob": P(),
            }
        if mlp is None and cfg.mlp_impl == "classic":
            param_specs.update(
                w_fc=P(None, tp),
                b_fc=P() if tp is None else P(tp),  # shards with hidden
                w_proj=P(tp, None),
                b_proj=P(),                         # added post-psum
            )
        elif mlp is None:
            param_specs.update(
                w_gate=P(None, tp),
                w_up=P(None, tp),
                w_down=P(tp, None),
            )
        else:
            param_specs["mlp"] = mlp_specs if mlp_specs is not None else P()
    else:
        param_specs = None

    meta = {
        # Declares which sp/tp (and the mlp's ep) axes the block collects
        # over, so the SPMD engine can reject a cfg/engine mismatch instead
        # of silently computing shard-local attention / partial sums.
        "kind": "transformer_block",
        "sp_axis": cfg.sp_axis,
        "tp_axis": tp,
        "validate_mesh": validate_mesh,
        "param_specs": param_specs,
    }
    if "ep_axis" in mlp_meta:
        meta["ep_axis"] = mlp_meta["ep_axis"]
    if "moe" in mlp_meta:
        # The mlp's static MoE hyperparameter record rides up so the
        # analysis stack (planner / sharding / capacity-overflow lint)
        # can read the sparse dispatch through the block wrapper.
        meta["moe"] = mlp_meta["moe"]
    if "balance_weight" in mlp_meta:
        # Surfaced so the engine's ragged-batch warning can see a MoE
        # balance penalty through the block wrapper (spmd._row_coupled).
        meta["balance_weight"] = mlp_meta["balance_weight"]
    if counted:
        meta["apply_counts"] = apply_counts
    return Layer(name=name, init=init, apply=apply, meta=meta)


def _local_vocab_ids(
    ids: jnp.ndarray,
    axis: str,
    v_loc: int,
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Map global token ids onto this lane's vocab shard: ``(idx, in_range)``
    with ``idx`` clipped into ``[0, v_loc)`` and ``in_range`` marking ids the
    lane actually owns.  Shared by the vocab-parallel embedding lookup and
    cross-entropy target-logit gather so the masked arithmetic cannot drift."""
    local = ids - jax.lax.axis_index(axis) * v_loc
    in_range = (local >= 0) & (local < v_loc)
    return jnp.clip(local, 0, v_loc - 1), in_range


def _vocab_meta(cfg: TransformerConfig, table_spec: Any) -> dict:
    """Shared meta for the vocab-parallel embedding/head: param sharding +
    vocab divisibility validation."""
    tp = cfg.tp_axis

    def validate_mesh(mesh):
        if tp is None or tp not in mesh.axis_names:
            return
        size = mesh.shape[tp]
        if cfg.vocab % size != 0:
            raise ValueError(
                f"vocab={cfg.vocab} is not divisible by the tp mesh axis "
                f"size {size}; the vocab-parallel embedding/head shard the "
                "vocabulary dimension across tp lanes"
            )

    meta = {"tp_axis": tp, "validate_mesh": validate_mesh}
    if tp is not None:
        meta["param_specs"] = table_spec
    return meta


def token_embedding(cfg: TransformerConfig, *, name: str = "embed") -> Layer:
    """Token embedding; vocab-parallel over ``cfg.tp_axis`` when set (each
    lane holds ``vocab/tp`` rows; out-of-shard tokens contribute zero and a
    psum assembles the full embedding — Megatron's parallel embedding).

    ``cfg.pos_emb='learned'`` adds an absolute position table ``pos``
    (``[max_pos, dim]``, replicated — GPT-2 class); under a bound sp
    axis each shard reads its GLOBAL position rows, mirroring the rope
    offset."""
    cfg.validate_arch()

    def init(rng, in_spec):
        del in_spec
        p = {"table": _normal(rng, (cfg.vocab, cfg.dim), 0.02, cfg.dtype)}
        if cfg.pos_emb == "learned":
            k2 = jax.random.fold_in(rng, 1)
            p["pos"] = _normal(k2, (cfg.max_pos, cfg.dim), 0.02, cfg.dtype)
        if cfg.embed_layernorm:
            p["eln"] = jnp.ones((cfg.dim,))
            p["elnb"] = jnp.zeros((cfg.dim,))
        return p, ()

    def apply(params, state, x, *, rng=None, train=True):
        del rng, train
        # Sequence packing: a packed batch dict carries the tokens plus
        # the segment/position planes; the embedding emits the packed
        # activation TUPLE the blocks thread through (packed documents
        # restart their positions at 0, so the learned-position gather
        # below reads each token's WITHIN-DOCUMENT row).
        seg = pk_pos = None
        if _is_packed_batch(x):
            seg = x["segment_ids"]
            pk_pos = x.get("positions")
            if pk_pos is None:
                raise ValueError(
                    "packed batch is missing 'positions' (per-token "
                    "within-document positions); build batches with "
                    "utils.data.pack_documents/packed_batches"
                )
            if axis_bound(cfg.sp_axis):
                raise ValueError(
                    "packed batches do not compose with a bound "
                    "sequence-parallel axis; drop cfg.sp_axis for "
                    "packed training"
                )
            x = x["tokens"]
        table = params["table"]
        if axis_bound(cfg.tp_axis):
            idx, in_range = _local_vocab_ids(x, cfg.tp_axis, table.shape[0])
            rows = jnp.where(
                in_range[..., None], jnp.take(table, idx, axis=0), 0
            )
            out = psum_value(rows, cfg.tp_axis)
        else:
            out = jnp.take(table, x, axis=0)
        if cfg.embed_scale is not None:
            # Gemma-style sqrt(dim) scaling; a TIED head still reads the
            # UNSCALED table (matching that family).
            out = out * jnp.asarray(cfg.embed_scale, out.dtype)
        if "pos" in params and seg is not None:
            # Packed positions are per-token and reset per document, so
            # the deepest reachable row is block_len - 1 (a document
            # filling its whole block).  Same hazard as the unpacked
            # branch below: jnp.take CLAMPS out-of-range rows under
            # jit, so guard statically on the block length instead of
            # silently training the tail of a long document on the
            # table's last row.
            s = x.shape[-1]
            if s + cfg.pos_emb_offset > cfg.max_pos:
                raise ValueError(
                    f"packed block length {s} + pos_emb_offset "
                    f"{cfg.pos_emb_offset} exceeds the learned position "
                    f"table (max_pos={cfg.max_pos} rows): a document "
                    "filling its block would read clamped rows — pack "
                    "with block_len <= max_pos - pos_emb_offset"
                )
            out = out + jnp.take(
                params["pos"], cfg.pos_emb_offset + pk_pos, axis=0
            ).astype(out.dtype)
        elif "pos" in params:
            s = x.shape[-1]
            sp_active = axis_bound(cfg.sp_axis)
            if not sp_active and s + cfg.pos_emb_offset > cfg.max_pos:
                # jnp.take CLAMPS out-of-range rows under jit — the last
                # tokens would silently reuse row max_pos-1.  Decode has
                # its own guard (generation._check_max_pos); this covers
                # the encoder/training path.  Under a bound sp axis the
                # global offset is traced, so shards rely on the caller
                # sizing seq*sp against the table.
                raise ValueError(
                    f"sequence length {s} + pos_emb_offset "
                    f"{cfg.pos_emb_offset} exceeds the learned position "
                    f"table (max_pos={cfg.max_pos} rows)"
                )
            off = (
                jax.lax.axis_index(cfg.sp_axis) * s if sp_active else 0
            )
            out = out + jnp.take(
                params["pos"],
                cfg.pos_emb_offset + off + jnp.arange(s),
                axis=0,
            ).astype(out.dtype)
        if "eln" in params:  # BERT-style post-embedding LayerNorm
            out = _norm(
                out, params["eln"], cfg.norm_eps,
                bias=params["elnb"], centered=True,
            )
        if seg is not None:
            return (out, seg, pk_pos), state
        return out, state

    tp = cfg.tp_axis
    table_spec = {"table": P(tp)}
    if cfg.pos_emb == "learned":
        table_spec["pos"] = P()
    if cfg.embed_layernorm:
        table_spec.update(eln=P(), elnb=P())
    meta = _vocab_meta(cfg, table_spec)
    return Layer(name=name, init=init, apply=apply, meta=meta)


def _head_init(cfg: TransformerConfig) -> Callable:
    """Final-norm scale + vocab projection params — the ONE schema shared
    by :func:`lm_head` and :func:`chunked_lm_loss`, so the two head
    configurations stay checkpoint-interchangeable."""

    def init(rng, in_spec):
        del in_spec
        p = {"scale": jnp.ones((cfg.dim,))}
        if cfg.norm == "layernorm":
            p["bias"] = jnp.zeros((cfg.dim,))
        if not cfg.tie_embeddings:
            p["w"] = _normal(
                rng, (cfg.dim, cfg.vocab), cfg.dim ** -0.5, cfg.dtype
            )
        return p, ()

    return init


def _head_w(cfg: TransformerConfig, params: Any) -> jnp.ndarray:
    """The head projection ``[dim, vocab]``: the layer's own ``w``, or —
    under ``cfg.tie_embeddings`` — the embedding table (spliced into the
    param dict by the engine / the generation extractor), transposed.
    A weight-only-int8 ``w`` (``models.quant``) dequantizes at the
    read."""
    if "w" in params:
        from torchgpipe_tpu.models.quant import dequantize_weight

        return dequantize_weight(params["w"], cfg.dtype)
    if cfg.tie_embeddings and "table" in params:
        return params["table"].T
    if cfg.tie_embeddings:
        raise ValueError(
            "tie_embeddings=True but the head received neither 'w' nor "
            "the spliced embedding 'table' — pair the tied head with "
            "SpmdGPipe (which splices pre params per meta['tie_pre']) or "
            "models.generation.spmd_params_for_generation"
        )
    raise ValueError(
        f"head params are missing 'w' (got keys {sorted(params)}) — was "
        "the checkpoint built for a different head configuration?"
    )


def lm_head(
    cfg: TransformerConfig, *, name: str = "head", gather_logits: bool = True
) -> Layer:
    """Final RMSNorm + vocabulary projection; vocab-parallel over
    ``cfg.tp_axis`` when set (Megatron column-parallel output layer).

    With ``gather_logits=True`` (default) the per-lane logit shards are
    re-assembled into full ``[.., vocab]`` logits, so any loss works.  Pass
    ``False`` to keep lane-local ``[.., vocab/tp]`` logits — 1/tp of the
    logits memory — and pair with :func:`vocab_parallel_cross_entropy`.
    """

    init = _head_init(cfg)

    def apply(params, state, x, *, rng=None, train=True):
        del rng, train
        if _is_packed_act(x):
            x = x[0]  # packed tuple: logits come from the hidden plane
        h = _block_norm(cfg, params, "scale", x)
        w = _head_w(cfg, params)
        if axis_bound(cfg.tp_axis):
            h = psum_grad(h, cfg.tp_axis)  # region entry: full grad upstream
            logits = h @ w  # local [.., vocab/tp]
            if gather_logits:
                logits = all_gather_value(logits, cfg.tp_axis, axis=-1)
            return checkpoint_name(logits, "ce_logits"), state
        # Named save point: under remat, dropping "ce_logits" from the
        # save set recomputes the [tokens, vocab] matrix instead of
        # holding it across the backward.
        return checkpoint_name(h @ w, "ce_logits"), state

    tp = cfg.tp_axis
    norm_spec = (
        {"scale": P(), "bias": P()}
        if cfg.norm == "layernorm"
        else {"scale": P()}
    )
    if cfg.tie_embeddings:
        meta = _vocab_meta(cfg, dict(norm_spec))
        meta["tie_pre"] = ("table",)
    else:
        meta = _vocab_meta(cfg, {**norm_spec, "w": P(None, tp)})
    if tp is not None and not gather_logits:
        # Declares that this layer's output stays sharded over (axis, dim) —
        # consumed by SpmdGPipe.apply, which gathers it so inference returns
        # full logits instead of silently handing back one lane's shard.
        meta["out_gather"] = (tp, -1)
    return Layer(name=name, init=init, apply=apply, meta=meta)


def vocab_parallel_cross_entropy(axis: Optional[str]) -> Callable:
    """Cross-entropy over vocab-sharded logits (``lm_head(...,
    gather_logits=False)``): full-vocabulary softmax without ever
    materializing full logits — the log-sum-exp and target-logit terms are
    assembled with tp collectives (Megatron's parallel cross-entropy).

    Returns a ``loss_fn(local_logits, labels)`` for the engines.  Outside a
    bound axis it degrades to the plain :func:`cross_entropy`.
    """

    def loss(logits, labels):
        if not axis_bound(axis):
            return cross_entropy(logits, labels)
        logits = logits.astype(jnp.float32)
        # Stable global log-sum-exp: lane max -> pmax (constant wrt grads —
        # the max's gradient contribution cancels analytically).
        m = pmax_stop(jnp.max(logits, axis=-1), axis)
        se = psum_value(
            jnp.sum(jnp.exp(logits - m[..., None]), axis=-1), axis
        )
        z = jnp.log(se) + m
        # Target logit lives on exactly one lane; zeros elsewhere, psum.
        idx, in_range = _local_vocab_ids(labels, axis, logits.shape[-1])
        tl = jnp.take_along_axis(logits, idx[..., None], axis=-1)[..., 0]
        tl = psum_value(jnp.where(in_range, tl, 0.0), axis)
        return jnp.mean(z - tl)

    return loss


def chunked_lm_loss(
    cfg: TransformerConfig, *, chunk: int = 8192, name: str = "chunked_ce"
) -> Layer:
    """Fused final-norm + vocab projection + cross-entropy as a parametric
    LOSS LAYER for ``SpmdGPipe(loss_fn=...)`` or
    ``GPipe.value_and_grad_with_loss_params`` — the big-vocabulary memory
    fix: the ``[tokens, vocab]`` logit matrix (2 GiB at 128k vocab x 4k
    tokens in f32, the recorded single-chip OOM blocker for the 1B preset)
    is never materialized.  The head matmul and the softmax-cross-entropy
    run as one online log-sum-exp scan over vocabulary chunks
    (:func:`torchgpipe_tpu.ops.losses.chunked_softmax_xent`); peak extra
    memory is one ``[tokens, chunk]`` tile.

    Use with ``post=None`` — this layer owns the final RMSNorm and the
    head weights (params ``scale``/``w``, trained via the engine's
    ``grads["loss"]``).  Decomposes over tokens (mean), so it composes
    with every schedule and the pp-sharded loss phase
    (``loss_reduction='mean'``).  Local head weights only (no
    ``tp_axis`` vocab sharding — pair tp models with
    ``vocab_parallel_cross_entropy`` instead)."""
    from torchgpipe_tpu.ops.losses import chunked_softmax_xent

    if cfg.tie_embeddings and cfg.tp_axis is not None:
        raise ValueError(
            "chunked_lm_loss cannot tie to a vocab-parallel embedding: "
            "the tp-sharded table would hand this loss a [vocab/tp, dim] "
            "local shard while the labels index the GLOBAL vocabulary — "
            "the loss would silently normalize over 1/tp of the "
            "vocabulary.  Use vocab_parallel_cross_entropy with "
            "lm_head(gather_logits=False) for tp models, or untie"
        )
    init = _head_init(cfg)

    def row_loss(params, state, y_and_labels):
        # Engine fast path for ragged batches (SpmdGPipe._masked_loss_sum):
        # per-row losses in ONE batched call, each row the token mean of
        # that batch-1 slice.  ``apply`` is its mean (rows share one
        # sequence length), so the two paths cannot drift.
        del state
        y, labels = y_and_labels
        if _is_packed_act(y):
            y = y[0]  # packed tuple: the hidden plane carries the logits
        weights = None
        if isinstance(labels, dict):  # packed targets: weight real tokens
            labels, weights = labels["labels"], labels["weights"]
        h = _block_norm(cfg, params, "scale", y)
        losses = chunked_softmax_xent(
            h.reshape(-1, cfg.dim), _head_w(cfg, params),
            labels.reshape(-1), chunk,
        )
        losses = losses.reshape(labels.shape[0], -1)
        if weights is not None:
            w = weights.astype(losses.dtype)
            return jnp.sum(losses * w, axis=1) / jnp.maximum(
                jnp.sum(w, axis=1), 1.0
            )
        return jnp.mean(losses, axis=1)

    def apply(params, state, y_and_labels, *, rng=None, train=True):
        del rng, train
        return jnp.mean(row_loss(params, state, y_and_labels)), state

    meta: dict = {
        "row_loss": row_loss,
        # Declared so the static autotuner (torchgpipe_tpu.tune) can sweep
        # the vocab-chunk size: the live softmax tile is [tokens, chunk],
        # so the chunk trades loss-phase memory against launch overhead.
        "ce_chunk": chunk,
        "with_ce_chunk": lambda c: chunked_lm_loss(cfg, chunk=c, name=name),
    }
    if cfg.tie_embeddings:
        meta["tie_pre"] = ("table",)
    return Layer(name=name, init=init, apply=apply, meta=meta)


def llama(cfg: TransformerConfig, *, head: bool = True) -> List[Layer]:
    """Flat sequential layer list for the MPMD GPipe engine: embed, blocks,
    head — the "nn.Sequential of transformer blocks" shape (BASELINE.json).

    ``head=False`` omits the lm_head: pair with
    :func:`chunked_lm_loss` via
    ``GPipe.value_and_grad_with_loss_params`` so the ``[tokens, vocab]``
    logits never materialize (the big-vocab memory fix)."""
    if cfg.tie_embeddings:
        raise ValueError(
            "tie_embeddings is an SPMD-engine feature: the MPMD layer "
            "list places the embedding and the head on different stage "
            "devices with independent param trees, so the tied gradient "
            "would need a manual cross-stage reduction.  Use "
            "llama_spmd(cfg, n) + SpmdGPipe (pre params are replicated "
            "across pp lanes; the tie is spliced and gradients sum "
            "automatically), or set tie_embeddings=False here"
        )
    layers: List[Layer] = [token_embedding(cfg)]
    for i in range(cfg.n_layers):
        layers.append(transformer_block(cfg, name=f"block{i}", layer=i))
    if head:
        layers.append(lm_head(cfg))
    return layers


def layers_per_stage(cfg: TransformerConfig, n_stages: int) -> int:
    """Blocks one stage of the SPMD engine runs.  Stages are STACKED (one
    program, the stage a leading axis of every leaf), so every stage runs
    the same layer types in the same order: a stage holds whole periods
    of ``cfg.attn_period``, and block ``i`` of any stage is a layer of
    type ``i % len(period)``."""
    if cfg.n_layers % n_stages != 0:
        raise ValueError(
            f"n_layers={cfg.n_layers} must divide evenly into {n_stages} stages"
        )
    per = cfg.n_layers // n_stages
    period = len(cfg.attn_period)
    if per % period != 0:
        raise ValueError(
            f"{per} layers a stage do not hold whole periods of the "
            f"{period} attention layer types (cfg.attn_layers): the SPMD "
            "engine's stages are stacked, so each must run the same types "
            "in the same order — use a stage count that leaves a multiple "
            f"of {period} layers a stage"
        )
    return per


def llama_spmd(
    cfg: TransformerConfig, n_stages: int, *, gather_logits: bool = True
) -> Tuple[Layer, Layer, Layer]:
    """(block, pre, post) for the SPMD engine: each stage runs
    ``n_layers // n_stages`` blocks.

    Under ``cfg.tp_axis`` the embedding and head are vocab-parallel; pass
    ``gather_logits=False`` (with
    ``loss_fn=vocab_parallel_cross_entropy(cfg.tp_axis)``) to keep logits
    vocab-sharded through the loss — 1/tp of the logits memory."""
    per = layers_per_stage(cfg, n_stages)
    block = chain(
        [transformer_block(cfg, name=f"b{i}", layer=i) for i in range(per)],
        name="stage",
    )
    return (
        block,
        token_embedding(cfg),
        lm_head(cfg, gather_logits=gather_logits),
    )


def cross_entropy(logits: jnp.ndarray, labels: jnp.ndarray) -> jnp.ndarray:
    """Mean token cross-entropy at aligned positions; logits [b, s, v], int
    labels [b, s].  For a causal-LM objective pass *pre-shifted* arrays
    (``logits`` from ``tokens[:, :-1]``, ``labels = tokens[:, 1:]``) — this
    function does not shift."""
    logp = jax.nn.log_softmax(logits.astype(jnp.float32), axis=-1)
    ll = jnp.take_along_axis(logp, labels[..., None], axis=-1)[..., 0]
    return -jnp.mean(ll)


def _packed_token_nll(
    logits: Any, target: Any
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Per-position negative log-likelihood and its real-token weights
    for the packed/padded dict target contract ``{"labels", "weights"}``
    (``utils.data``): the ONE definition the weighted losses and the
    per-document extractor share."""
    if _is_packed_act(logits):
        logits = logits[0]
    labels, weights = target["labels"], target["weights"]
    logp = jax.nn.log_softmax(logits.astype(jnp.float32), axis=-1)
    ll = jnp.take_along_axis(logp, labels[..., None], axis=-1)[..., 0]
    return -ll, weights.astype(jnp.float32)


def packed_cross_entropy(logits: Any, target: Any) -> jnp.ndarray:
    """Cross-entropy weighted by REAL tokens, not block size: the loss
    for packed (and padded-with-mask) batches whose target is the
    ``{"labels", "weights"}`` dict from ``utils.data`` — pad positions
    and document-final tokens carry weight 0, so a 50%-padding batch is
    not silently diluted to half the gradient signal per step.  Returns
    ``Σ w·nll / Σ w`` (the token-weighted mean over THIS call).

    For micro-batched/pipelined training where the engine sums or
    averages per-micro-batch losses, prefer
    :func:`packed_cross_entropy_sum` with ``loss_reduction='sum'``: the
    raw weighted SUM decomposes exactly over any batch split, while this
    mean's denominator is per-call."""
    nll, w = _packed_token_nll(logits, target)
    return jnp.sum(nll * w) / jnp.maximum(jnp.sum(w), 1.0)


def packed_cross_entropy_sum(logits: Any, target: Any) -> jnp.ndarray:
    """``Σ w·nll`` over the call — decomposes EXACTLY over micro-batches
    and megastep slices (the packed-vs-padded equivalence gates compare
    this figure).  Pair with the engines' ``loss_reduction='sum'`` and
    normalize by the corpus' real-token count outside the step (or fold
    ``1/N_real`` into the packer's weights)."""
    nll, w = _packed_token_nll(logits, target)
    return jnp.sum(nll * w)


def per_document_losses(
    logits: Any,
    target: Any,
    segment_ids: jnp.ndarray,
    n_docs: int,
) -> jnp.ndarray:
    """Token-mean loss PER PACKED DOCUMENT.

    ``segment_ids`` is the batch's ``[b, s]`` segment plane and
    ``n_docs`` the (static) maximum segments per row; entry
    ``r * n_docs + (d - 1)`` of the returned ``[b * n_docs]`` vector is
    row ``r`` segment ``d``'s mean nll over its REAL supervised
    positions (0 where the segment is absent).  Map a corpus document to
    its entry via :class:`~torchgpipe_tpu.utils.data.Packing.doc_locs`
    (its row, plus its arrival order within that row).  The
    packed-vs-unpacked equivalence gates compare these against each
    document run alone with pad masking."""
    nll, w = _packed_token_nll(logits, target)
    b = nll.shape[0]
    out = []
    for d in range(1, n_docs + 1):
        m = (segment_ids == d).astype(jnp.float32) * w
        out.append(jnp.sum(nll * m, axis=1) / jnp.maximum(jnp.sum(m, axis=1), 1.0))
    return jnp.stack(out, axis=1).reshape(b * n_docs)
