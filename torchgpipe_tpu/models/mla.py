"""Multi-head latent attention (MLA; DeepSeek-V2, arXiv:2405.04434) for
the serving path: projections, YaRN rotary frequencies, and the attend
over a LATENT cache in its two equal forms.

Per token with block input ``h`` (after ``ln1``), ``H`` heads:

* ``c_q = rms(h W_qa)``; ``[q_nope | q_pe] = c_q W_qb`` as ``H`` heads
  of ``qk_nope_head_dim + qk_rope_head_dim``;
* ``[c_kv | k_pe] = h W_kva``; ``c_kv = rms(c_kv)``; ``k_pe`` is ONE
  rotary head shared by all ``H``; ``q_pe`` and ``k_pe`` rotate by
  position under YaRN's frequencies;
* ``[k_nope | v] = c_kv W_kvb`` as ``H`` heads of
  ``qk_nope_head_dim + v_head_dim``;
* ``score = (q_nope . k_nope + q_pe . k_pe) * qk_head_dim**-0.5 *
  yarn_mscale(factor, mscale_all_dim)**2``, causal, softmax in f32.

What a cache row holds is ``c_kv`` after its norm and ``k_pe`` after
rotation (``kv_lora_rank + qk_rope_head_dim`` values a token a layer).
:func:`attend` reads such rows in one of two forms with the same result:

* **absorbed** — ``W_kvb``'s key half is folded into the query
  (``q~ = q_nope W_kvb^K^T`` in the latent space) and its value half
  into the output (``o = (p c_kv) W_kvb^V``): no K or V is ever
  materialised, and the work per query is ``2 H L (2 c + r)``;
* **expanded** — the rows go through ``W_kvb`` first (``2 L c H (n +
  v)`` whatever the number of queries), then plain attention.

:func:`absorbs` picks by those two counts: a single-token step and a
prefill chunk of tens of queries against thousands of rows are absorbed,
a whole prompt against its own rows (``g = L``) is expanded.  Block params (all bias-free): ``ln1``,
``wq_a [dim, q_lora]``, ``q_norm``, ``wq_b [q_lora, H*(n+r)]``,
``wkv_a [dim, c+r]``, ``kv_norm``, ``wkv_b [c, H*(n+v)]`` (per head: key
columns, then value columns), ``wo [H*v, dim]``, ``ln2`` and a
feed-forward (``w_gate/w_up/w_down``, or ``mlp`` for an expert layer).

Rotary layout: the half-split convention of ``transformer._rope``
(dims ``i`` and ``i + r/2`` rotate together).  The published weights of
this family pair dims ``2i`` and ``2i + 1``; the two differ by a fixed
permutation of ``W_qb``'s and ``W_kva``'s rotary columns, which an
importer applies once and seeded weights do not notice.
"""

from __future__ import annotations

import math
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from torchgpipe_tpu.models.transformer import (
    MLAConfig,
    TransformerConfig,
    YarnRope,
    _normal,
    _rms,
    _rope,
)

Pytree = Any


def yarn_mscale(scale: float, m: float) -> float:
    """YaRN's attention temperature term: ``0.1 m ln(scale) + 1``."""
    return 1.0 if scale <= 1.0 else 0.1 * m * math.log(scale) + 1.0


def _correction_dim(rotations: float, dim: int, base: float,
                    max_pos: int) -> float:
    return dim * math.log(max_pos / (rotations * 2.0 * math.pi)) / (
        2.0 * math.log(base))


def yarn_inv_freq(dim: int, theta: float,
                  yarn: Optional[YarnRope]) -> np.ndarray:
    """Rotary inverse frequencies ``[dim // 2]``: ``theta**(-2i/dim)``,
    and under YaRN those divided by ``factor`` where the ramp between
    the ``beta_fast`` and ``beta_slow`` correction dims says so."""
    f = theta ** (-np.arange(0, dim, 2, dtype=np.float64) / dim)
    if yarn is None:
        return f.astype(np.float32)
    low = math.floor(_correction_dim(
        yarn.beta_fast, dim, theta, yarn.original_max_pos))
    high = math.ceil(_correction_dim(
        yarn.beta_slow, dim, theta, yarn.original_max_pos))
    low, high = max(low, 0), min(high, dim - 1)
    if low == high:
        high += 0.001
    ramp = np.clip((np.arange(dim // 2) - low) / (high - low), 0.0, 1.0)
    return (f / yarn.factor * ramp + f * (1.0 - ramp)).astype(np.float32)


def score_scale(m: MLAConfig) -> float:
    """``qk_head_dim**-0.5`` times YaRN's ``mscale_all_dim`` term squared."""
    scale = m.qk_head_dim ** -0.5
    if m.rope_scaling is not None and m.rope_scaling.mscale_all_dim:
        scale *= yarn_mscale(
            m.rope_scaling.factor, m.rope_scaling.mscale_all_dim) ** 2
    return scale


def yarn_amplitude(y: Optional[YarnRope]) -> float:
    """The factor YaRN puts on cos and sin: the ratio of its two mscale
    terms (``0.1 ln(factor) + 1`` with the defaults)."""
    if y is None:
        return 1.0
    return yarn_mscale(y.factor, y.mscale) / yarn_mscale(
        y.factor, y.mscale_all_dim)


def rope_amplitude(m: MLAConfig) -> float:
    """The factor YaRN puts on cos and sin."""
    return yarn_amplitude(m.rope_scaling)


def rope(cfg: TransformerConfig, x: jnp.ndarray, pos: Any) -> jnp.ndarray:
    """Rotate ``x [b, g, heads, r]`` at positions ``pos + 0..g-1``
    (``pos``: a scalar, or ``[b]`` for a base position a row) under the
    config's YaRN frequencies and amplitude."""
    m = cfg.mla
    inv = jnp.asarray(yarn_inv_freq(x.shape[-1], cfg.rope_theta,
                                    m.rope_scaling))
    return _rope(x, cfg.rope_theta, pos, freqs=inv,
                 amplitude=rope_amplitude(m))


def attn_shapes(cfg: TransformerConfig) -> Dict[str, Tuple[int, ...]]:
    """Shapes of one block's attention matrices."""
    m, d, H = cfg.mla, cfg.dim, cfg.n_heads
    return {
        "wq_a": (d, m.q_lora_rank),
        "wq_b": (m.q_lora_rank, H * m.qk_head_dim),
        "wkv_a": (d, m.cache_row),
        "wkv_b": (m.kv_lora_rank, H * (m.qk_nope_head_dim + m.v_head_dim)),
        "wo": (H * m.v_head_dim, d),
    }


def init_block(cfg: TransformerConfig, rng: jax.Array,
               mlp: Optional[Pytree] = None,
               mlp_hidden: Optional[int] = None) -> Pytree:
    """Seeded params of one MLA block: the attention above with either a
    dense SwiGLU of width ``mlp_hidden`` (default ``cfg.mlp_hidden``) or
    the given ``mlp`` params (an expert layer's, from its own ``init``)."""
    m, d, dt = cfg.mla, cfg.dim, cfg.dtype
    shapes = attn_shapes(cfg)
    ks = jax.random.split(rng, len(shapes) + 3)
    p: Dict[str, Any] = {
        "ln1": jnp.ones((d,), jnp.float32), "ln2": jnp.ones((d,), jnp.float32),
        "q_norm": jnp.ones((m.q_lora_rank,), jnp.float32),
        "kv_norm": jnp.ones((m.kv_lora_rank,), jnp.float32),
    }
    for k, (name, shape) in zip(ks, sorted(shapes.items())):
        p[name] = _normal(k, shape, shape[0] ** -0.5, dt)
    if mlp is not None:
        p["mlp"] = mlp
        return p
    h = mlp_hidden or cfg.mlp_hidden
    p["w_gate"] = _normal(ks[-3], (d, h), d ** -0.5, dt)
    p["w_up"] = _normal(ks[-2], (d, h), d ** -0.5, dt)
    p["w_down"] = _normal(ks[-1], (h, d), h ** -0.5, dt)
    return p


def project(
    cfg: TransformerConfig, p: Pytree, h: jnp.ndarray, pos: Any,
) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """The block's attention prologue on normed states ``h [b, g, dim]``
    at positions ``pos + 0..g-1``: ``(q_nope [b, g, H, n], q_pe
    [b, g, H, r] rotated, c_kv [b, g, c] normed, k_pe [b, g, r]
    rotated)`` — the last two are the new cache rows."""
    m = cfg.mla
    b, g, _ = h.shape
    with jax.named_scope("mla.q"):
        cq = _rms(h @ p["wq_a"], p["q_norm"], cfg.norm_eps)
        q = (cq @ p["wq_b"]).reshape(b, g, -1, m.qk_head_dim)
        q_nope = q[..., :m.qk_nope_head_dim]
        q_pe = rope(cfg, q[..., m.qk_nope_head_dim:], pos)
    with jax.named_scope("mla.kv"):
        kv = h @ p["wkv_a"]
        ckv = _rms(kv[..., :m.kv_lora_rank], p["kv_norm"], cfg.norm_eps)
        kpe = rope(cfg, kv[..., None, m.kv_lora_rank:], pos)[:, :, 0]
    return q_nope, q_pe, ckv, kpe


def absorbs(m: MLAConfig, g: int, L: int) -> bool:
    """Whether ``g`` queries against ``L`` cache rows need fewer FLOPs
    absorbed (a head: ``2 g c (2 L + n + v)``, scores and output in the
    latent space and ``W_kvb``'s two halves on the queries) than
    expanded (``2 L (n + v) (c + g)``, the rows through ``W_kvb`` and
    plain attention); the rotary term is common.  ``g`` = 1 always."""
    c, nv = m.kv_lora_rank, m.qk_nope_head_dim + m.v_head_dim
    return g == 1 or g * c * (2 * L + nv) <= L * nv * (c + g)


def _causal(scores: jnp.ndarray, pos0: Any) -> jnp.ndarray:
    """Mask ``scores [b, H, g, L]``: query ``i`` (position ``pos0 + i``,
    ``pos0`` a scalar or ``[b]``) sees rows ``<= pos0 + i``."""
    g, L = scores.shape[2], scores.shape[3]
    qpos = jnp.asarray(pos0).reshape(-1, 1, 1) + jnp.arange(g)[None, :, None]
    seen = jnp.arange(L)[None, None, :] <= qpos            # [B', g, L]
    return jnp.where(seen[:, None], scores, -jnp.inf)


def _wkv_b(cfg: TransformerConfig, p: Pytree) -> jnp.ndarray:
    """``W_kvb`` as ``[c, H, n + v]``: a head's key half ``[..., :n]``,
    then its value half."""
    m = cfg.mla
    return p["wkv_b"].reshape(
        m.kv_lora_rank, cfg.n_heads, m.qk_nope_head_dim + m.v_head_dim
    )


def absorbed_halves(
    cfg: TransformerConfig, p: Pytree,
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """``(W_kvb^K [c, H, n], W_kvb^V [c, H, v])``: what the absorbed
    form folds into the queries and the output."""
    w, n = _wkv_b(cfg, p), cfg.mla.qk_nope_head_dim
    return w[..., :n], w[..., n:]


def absorb_queries(
    q_nope: jnp.ndarray, wk: jnp.ndarray, dtype: Any,
) -> jnp.ndarray:
    """The absorbed form's queries: ``q_nope [b, g, H, n]`` through
    ``W_kvb``'s key half into the latent space, ``[b, g, H, c]`` rounded
    to ``dtype`` (the cache's)."""
    return jnp.einsum("bghn,chn->bghc", q_nope, wk,
                      preferred_element_type=jnp.float32).astype(dtype)


def expand_output(o_lat: jnp.ndarray, wv: jnp.ndarray) -> jnp.ndarray:
    """The absorbed form's epilogue: ``o_lat [b, g, H, c]`` (attention
    over the latent rows) through ``W_kvb``'s value half, ``[b, g, H *
    v]`` float32."""
    out = jnp.einsum("bghc,chv->bghv", o_lat, wv,
                     preferred_element_type=jnp.float32)
    b, g, H, v = out.shape
    return out.reshape(b, g, H * v)


def attend(
    cfg: TransformerConfig,
    p: Pytree,
    q_nope: jnp.ndarray,      # [b, g, H, n]
    q_pe: jnp.ndarray,        # [b, g, H, r] rotated
    ckv: jnp.ndarray,         # [b, L, c] cache rows (this call's written)
    kpe: jnp.ndarray,         # [b, L, r]
    pos0: Any,                # [] or [b]: position of the first query
    absorbed: Optional[bool] = None,
) -> jnp.ndarray:
    """Causal latent attention of ``g`` queries a row against that row's
    ``L`` cache rows: ``[b, g, H * v]``.  Products take the cache's type
    with float32 accumulation; the softmax is float32.  ``absorbed``
    defaults to :func:`absorbs` of the shapes."""
    m = cfg.mla
    b, g, H, n = q_nope.shape
    if absorbed is None:
        absorbed = absorbs(m, g, ckv.shape[1])
    dt, f32 = ckv.dtype, jnp.float32
    w = _wkv_b(cfg, p)
    wk, wv = w[..., :n], w[..., n:]
    scale = score_scale(m)
    pe = jnp.einsum("bghr,blr->bhgl", q_pe.astype(dt), kpe,
                    preferred_element_type=f32)
    if absorbed:
        with jax.named_scope("mla.scores"):
            q_lat = absorb_queries(q_nope, wk, dt)
            scores = jnp.einsum("bghc,blc->bhgl", q_lat, ckv,
                                preferred_element_type=f32)
            prob = jax.nn.softmax(_causal((scores + pe) * scale, pos0), -1)
        with jax.named_scope("mla.out"):
            o_lat = jnp.einsum("bhgl,blc->bghc", prob.astype(dt), ckv,
                               preferred_element_type=f32).astype(dt)
            return expand_output(o_lat, wv)
    with jax.named_scope("mla.expand"):
        kv = jnp.einsum("blc,chx->blhx", ckv, w,
                        preferred_element_type=f32).astype(dt)
    with jax.named_scope("mla.scores"):
        scores = jnp.einsum("bghn,blhn->bhgl", q_nope.astype(dt),
                            kv[..., :n], preferred_element_type=f32)
        prob = jax.nn.softmax(_causal((scores + pe) * scale, pos0), -1)
    with jax.named_scope("mla.out"):
        out = jnp.einsum("bhgl,blhv->bghv", prob.astype(dt), kv[..., n:],
                         preferred_element_type=f32)
    return out.reshape(b, g, H * m.v_head_dim)


__all__ = [
    "absorb_queries", "absorbed_halves", "absorbs", "attend", "attn_shapes",
    "expand_output", "init_block", "project", "rope",
    "rope_amplitude", "score_scale", "yarn_amplitude", "yarn_inv_freq",
    "yarn_mscale",
]
