"""The gated delta-rule mixer with per-channel decay (Kimi Delta
Attention: Kimi Linear, arXiv:2510.26692; flash-linear-attention's
``fla/layers/kda.py``) of a hybrid model's ``K`` layers
(``TransformerConfig.layer_pattern``, ``cfg.linear_attn``), on the
serving path: a chunk of ``g`` tokens a row, continued from the row's
conv tail and recurrent state and handing both on.

For one row, with ``u`` the layer's normed input and ``cfg.linear_attn``'s
sizes (``H`` heads of ``d``, a conv of ``K`` taps; the low-rank
projections' width is that of their weights):

* ``[q | k | v] = silu(causal depthwise conv_K(u W_in))``, no bias, the
  conv reading the ``K - 1`` inputs before the chunk from the row's
  TAIL; each ``[H, d]``;
* per head ``q <- q / |q| * d ** -0.5`` and ``k <- k / |k|`` (the L2
  norm over the head's ``d`` channels);
* the decay is per CHANNEL: ``log a_t = -exp(A_log[h]) softplus(u W_fa
  W_fb + dt_bias)``, ``[H, d]``;
* ``beta_t = 2 sigmoid(u W_b)`` (``[H]``; the factor 2 is
  ``neg_eigval``: the transition's eigenvalue along ``k``, ``1 - beta``,
  then lies in (-1, 1));
* ``S_t = (I - beta_t k_t k_t^T) Diag(a_t) S_{t-1} + beta_t k_t v_t^T``,
  ``S`` of ``[d, d]`` a head, and ``o_t = S_t^T q_t``;
* ``y = RMSNorm_d(o) * w * sigmoid(u W_ga W_gb)`` (the norm over each
  head's ``d`` channels, ``w [d]``) and ``out = y W_out``.

The state is kept and updated in float32 (it sums over the whole
context), the tail in the served type; every sum of the chunk and the
step is float32 at ``highest`` precision.  A chunk of one token is the
recurrence itself (:func:`_step`); a longer chunk takes the chunked (WY)
form of Gated DeltaNet (arXiv:2412.06464) with the per-channel decay of
Kimi Linear (:func:`_chunked`): blocks of :data:`CHUNK` positions in
which the state passes from block to block.  A chunk
boundary changes the order of the sums and nothing else.

The per-channel decay makes the factored ``k * exp(G)`` / ``k *
exp(-G)`` form of a block overflow (``G``, the decay's running sum, is
down to ``-20`` a position).  So a block's pairwise decays are taken
only as ``exp(G_i - G_j)`` with ``i`` at or after ``j`` (never above 1):
pair by pair within a sub-block of :data:`SUB` positions, and across
sub-blocks factored through the position just before the later
sub-block, where both factors are such differences.

The two rules that make a pooled slot's state safe are
``models.recurrent``'s (a new tenant reads zero state and tail, a masked
position is a no-op); here a masked position's ``log a`` and ``beta``
are 0, so its transition is the identity and nothing enters ``S``.
"""

from __future__ import annotations

import math
from typing import Any, Tuple

import jax
import jax.numpy as jnp
from jax import lax

from torchgpipe_tpu.models import recurrent
from torchgpipe_tpu.models.recurrent import entering as _entering
from torchgpipe_tpu.models.transformer import TransformerConfig

Pytree = Any
HIGHEST = lax.Precision.HIGHEST
# Positions of a block of the chunked form (fla's KDA block): the state
# passes from block to block.  It changes the order of the sums, not the
# mathematics.
CHUNK = 64
# Positions of a sub-block of the chunked form: the pairs within one
# take their decays pair by pair, the pairs across sub-blocks as
# products (fla's sub-chunk of KDA).
SUB = 16
# The L2 norm's epsilon (fla's ``l2norm``).
L2_EPS = 1e-6


def init_state(cfg: TransformerConfig, rows: int,
               dtype: Any) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Zeroed ``(tail [rows, K - 1, 3 H d], state f32 [rows, H, d, d])``
    of one delta-rule layer."""
    la = cfg.linear_attn
    return (jnp.zeros((rows, la.conv_kernel - 1, la.conv_dim), dtype),
            jnp.zeros((rows, la.n_heads, la.head_dim, la.head_dim),
                      jnp.float32))


def _mm(a: jnp.ndarray, spec: str, b: jnp.ndarray) -> jnp.ndarray:
    """``einsum(spec, a, b)`` at ``highest`` precision."""
    return jnp.einsum(spec, a, b, precision=HIGHEST)


def _l2(x: jnp.ndarray) -> jnp.ndarray:
    return x * lax.rsqrt(jnp.sum(x * x, -1, keepdims=True) + L2_EPS)


def _qk_norm(q: jnp.ndarray, k: jnp.ndarray) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Per head: ``q / |q| * d ** -0.5`` and ``k / |k|``."""
    return _l2(q) * q.shape[-1] ** -0.5, _l2(k)


def _decay(p: Pytree, f: jnp.ndarray) -> jnp.ndarray:
    """``log a [b, g, H, d] = -exp(A_log[h]) softplus(f + dt_bias)``, one
    decay a channel (``f = u W_fa W_fb``, ``[b, g, H, d]``)."""
    dt = jax.nn.softplus(f + p["dt_bias"].reshape(f.shape[-2:]))
    return -jnp.exp(p["A_log"])[:, None] * dt


def _gates(log_a: jnp.ndarray, beta: jnp.ndarray,
           valid: jnp.ndarray) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """A masked position's ``log a`` and ``beta`` are 0: its transition
    is the identity and nothing enters the state."""
    return (jnp.where(valid[..., None, None], log_a, 0.0),
            jnp.where(valid[..., None], beta, 0.0))


def _step(q, k, v, log_a, beta, state):
    """The recurrence at one position: ``q``/``k``/``v``/``log_a`` ``[b,
    H, d]``, ``beta [b, H]``, ``state [b, H, d, d]`` -> ``(o [b, H, d],
    new state)``.  With ``S' = Diag(a) S``: ``u = beta (v - S'^T k)``,
    ``S_t = S' + k u^T`` and ``o = S_t^T q = S'^T q + (q . k) u`` (the
    last form reads the state once for both products)."""
    a = jnp.exp(log_a)
    read = _mm(jnp.stack([a * k, a * q], axis=2), "bhik,bhkv->bhiv", state)
    u = beta[..., None] * (v - read[:, :, 0])
    o = read[:, :, 1] + jnp.sum(q * k, -1, keepdims=True) * u
    return o, a[..., None] * state + k[..., None] * u[..., None, :]


def _pairs(x: jnp.ndarray, k: jnp.ndarray, G: jnp.ndarray,
           strict: bool) -> jnp.ndarray:
    """``P[.., i, j] = sum_c x_i[c] k_j[c] exp(G_i[c] - G_j[c])`` for
    ``j < i`` (``j <= i`` unless ``strict``), 0 above: ``x``, ``k``,
    ``G`` ``[.., L, d]`` with ``G`` non-increasing along ``L``.  Within
    a sub-block pair by pair; from an earlier sub-block through the
    position ``r`` just before the later one: ``exp(G_i - G_r)
    exp(G_r - G_j)``, both factors at most 1."""
    L = x.shape[-2]
    s = math.gcd(L, SUB)
    i, j = jnp.arange(s)[:, None], jnp.arange(s)[None, :]
    seen = (i > j) if strict else (i >= j)
    rows = []
    for a in range(L // s):
        blk = slice(a * s, (a + 1) * s)
        xa, ka, Ga = x[..., blk, :], k[..., blk, :], G[..., blk, :]
        diff = Ga[..., :, None, :] - Ga[..., None, :, :]
        diff = jnp.where(seen[..., None], diff, -jnp.inf)
        diag = jnp.sum(xa[..., :, None, :] * ka[..., None, :, :]
                       * jnp.exp(diff), -1)
        parts = [diag, jnp.zeros(diag.shape[:-1] + (L - (a + 1) * s,),
                                 diag.dtype)]
        if a:
            Gr = G[..., a * s - 1:a * s, :]
            left = xa * jnp.exp(Ga - Gr)
            right = k[..., :a * s, :] * jnp.exp(Gr - G[..., :a * s, :])
            parts.insert(0, _mm(left, "...ic,...jc->...ij", right))
        rows.append(jnp.concatenate(parts, -1))
    return jnp.concatenate(rows, -2)


def _unit_lower_inverse(a: jnp.ndarray) -> jnp.ndarray:
    """``(I + a)^-1`` of a strictly lower ``a [.., L, L]``: forward
    substitution within sub-blocks of :data:`SUB`, then the blocks below
    the diagonal from those (``T_ab = -T_aa sum_{b <= c < a} a_ac
    T_cb``), every product at ``highest``."""
    L = a.shape[-1]
    s = math.gcd(L, SUB)
    n = L // s
    eye = jnp.eye(s, dtype=a.dtype)
    diag = jnp.stack([a[..., b * s:(b + 1) * s, b * s:(b + 1) * s]
                      for b in range(n)], -3)            # [.., n, s, s]
    t = jnp.broadcast_to(eye, diag.shape)
    for i in range(1, s):
        row = eye[i] - _mm(diag[..., i, :], "...j,...jk->...k", t)
        t = t.at[..., i, :].set(row)
    blocks = {(b, b): t[..., b, :, :] for b in range(n)}
    for gap in range(1, n):
        for b in range(n - gap):
            c = b + gap
            acc = sum(_mm(a[..., c * s:(c + 1) * s, m * s:(m + 1) * s],
                          "...ij,...jk->...ik", blocks[(m, b)])
                      for m in range(b, c))
            blocks[(c, b)] = -_mm(blocks[(c, c)], "...ij,...jk->...ik", acc)
    zero = jnp.zeros_like(t[..., 0, :, :])
    return jnp.concatenate([
        jnp.concatenate([blocks.get((r, b), zero) for b in range(n)], -1)
        for r in range(n)], -2)


def _block(q, k, v, log_a, beta, state):
    """One block of the chunked form over ``L`` positions: ``q``/``k``/
    ``v``/``log_a`` ``[b, H, L, d]``, ``beta [b, H, L]``, ``state [b, H,
    d, d]`` at the block's start.  With ``G`` the running sum of ``log
    a``, the values each position adds, ``U``, solve ``(I + A) U = beta
    (V - (k * e^G) S_0)``, ``A_ij = beta_i sum_c k_i k_j e^(G_i - G_j)``
    below the diagonal; then ``o = (q * e^G) S_0 + M U`` (``M_ij = sum_c
    q_i k_j e^(G_i - G_j)``, ``j <= i``) and the state leaving is
    ``e^(G_L) S_0 + (k * e^(G_L - G))^T U``."""
    G = jnp.cumsum(log_a, axis=-2)
    eg = jnp.exp(G)
    A = beta[..., None] * _pairs(k, k, G, strict=True)
    w = beta[..., None] * (v - _mm(k * eg, "bhlk,bhkv->bhlv", state))
    U = _mm(_unit_lower_inverse(A), "bhij,bhjv->bhiv", w)
    o = (_mm(q * eg, "bhlk,bhkv->bhlv", state)
         + _mm(_pairs(q, k, G, strict=False), "bhij,bhjv->bhiv", U))
    last = G[..., -1:, :]
    new = (jnp.exp(last)[..., 0, :, None] * state
           + _mm(k * jnp.exp(last - G), "bhlk,bhlv->bhkv", U))
    return o, new


def _chunked(q, k, v, log_a, beta, state):
    """The chunked form over ``g`` positions (``[b, H, g, ..]``) in
    blocks of :data:`CHUNK` (the last one padded with no-op positions:
    ``log a = 0``, ``beta = 0``)."""
    g, chunk = q.shape[2], CHUNK
    if g <= chunk:
        return _block(q, k, v, log_a, beta, state)
    n = -(-g // chunk)
    pad = n * chunk - g

    def blocks(x):
        x = jnp.pad(x, [(0, 0), (0, 0), (0, pad)] + [(0, 0)] * (x.ndim - 3))
        x = x.reshape(x.shape[:2] + (n, chunk) + x.shape[3:])
        return jnp.moveaxis(x, 2, 0)

    def body(st, args):
        o, st = _block(*args, st)
        return st, o

    state, os = lax.scan(body, state, tuple(map(blocks, (q, k, v, log_a, beta))))
    os = jnp.moveaxis(os, 0, 2)
    return os.reshape(os.shape[:2] + (n * chunk,) + os.shape[4:])[:, :, :g], state


def mixer(
    cfg: TransformerConfig,
    p: Pytree,
    u: jnp.ndarray,              # [b, g, dim] — the layer's normed input
    tail: jnp.ndarray,           # [b, K - 1, 3 H d] — the rows' conv tails
    state: jnp.ndarray,          # f32 [b, H, d, d] — the rows' states
    n_valid: jnp.ndarray,        # [b] int32 — valid tokens a row (0: no-op)
    fresh: jnp.ndarray,          # [b] bool — a new tenant: zero state and tail
) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """``(out [b, g, dim], new tail, new state)`` of one delta-rule layer
    over a chunk of ``g`` tokens a row (see the module docstring).  A row
    with ``n_valid = 0`` hands back its tail and state as they came."""
    la = cfg.linear_attn
    b, g, _ = u.shape
    H, d = la.n_heads, la.head_dim
    valid = jnp.arange(g)[None, :] < n_valid[:, None]        # [b, g]
    keep = n_valid > 0
    with jax.named_scope("kda.proj"):
        qkv = u @ p["in_proj"]
    tail_in, state_in = _entering(fresh, tail, state)
    with jax.named_scope("kda.conv"):
        qkv, new_tail = recurrent.conv(qkv, tail_in, p["conv_w"], None, n_valid)
        new_tail = recurrent.kept(keep, new_tail, tail)
        q, k, v = (x.reshape(b, g, H, d) for x in jnp.split(qkv, 3, axis=-1))
        q, k = _qk_norm(q, k)
    with jax.named_scope("kda.gate"):
        f = ((u @ p["f_a"]) @ p["f_b"]).astype(jnp.float32)
        log_a = _decay(p, f.reshape(b, g, H, d))
        beta = jax.nn.sigmoid((u @ p["b_proj"]).astype(jnp.float32))
        if la.neg_eigval:
            beta = 2.0 * beta
        log_a, beta = _gates(log_a, beta, valid)
        gate = jax.nn.sigmoid(((u @ p["g_a"]) @ p["g_b"]).astype(jnp.float32))
    if g == 1:
        with jax.named_scope("kda.step"):
            o, st = _step(q[:, 0], k[:, 0], v[:, 0], log_a[:, 0], beta[:, 0],
                          state_in)
            o = o[:, None]
    else:
        with jax.named_scope("kda.chunk"):
            o, st = _chunked(*(jnp.moveaxis(x, 1, 2)
                               for x in (q, k, v, log_a, beta)), state_in)
            o = jnp.moveaxis(o, 2, 1)
    new_state = recurrent.kept(keep, st, state)
    with jax.named_scope("kda.out"):
        var = jnp.mean(jnp.square(o), -1, keepdims=True)
        y = o * lax.rsqrt(var + cfg.norm_eps) * p["norm"].astype(jnp.float32)
        y = y.reshape(b, g, H * d) * gate
        out = y.astype(u.dtype) @ p["out_proj"]
    return out, new_tail, new_state


__all__ = ["init_state", "mixer"]
