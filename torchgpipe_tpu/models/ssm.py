"""The Mamba-2 mixer (Dao & Gu, arXiv:2405.21060) of a hybrid model's
``M`` layers (``TransformerConfig.layer_pattern``, ``cfg.ssm``), on the
serving path: a chunk of ``g`` tokens a row, continued from the row's
conv tail and recurrent state and handing both on.

For one row, with ``u`` the layer's normed input and ``cfg.ssm``'s sizes
(``H`` heads of ``P``, ``G`` groups of B and C of ``N``, a conv of ``K``
taps):

* ``[z | xBC | dt] = u W_in`` (``d_inner``, ``conv_dim``, ``H`` wide);
* ``xBC <- silu(causal depthwise conv_K(xBC) + b)``, the conv reading the
  ``K - 1`` inputs before the chunk from the row's TAIL; split into ``x``
  ``[H, P]``, ``B`` and ``C`` ``[G, N]``; head ``h`` reads group ``h //
  (H / G)``;
* ``dt = softplus(dt + dt_bias)``, ``a = exp(-dt exp(A_log))``;
* ``S_t[h] = a_t[h] S_{t-1}[h] + dt_t[h] x_t[h] (x) B_t[g]``, ``S`` of
  ``[P, N]``, and ``y_t[h] = S_t[h] C_t[g] + D[h] x_t[h]``;
* ``y <- RMSNorm over groups of d_inner / G (y * silu(z)) * w`` (the
  norm AFTER the gate, ``RMSNormGated(group_size=d_inner / n_groups)``)
  and ``out = y W_out``.

The state is kept and updated in float32 (it sums over the whole
context), the tail in the served type; every sum of the scan is float32
at ``highest`` precision.  A chunk of one token is the recurrence itself
(:func:`_step`); a longer chunk takes the chunked form (:func:`_ssd`),
blocks of ``cfg.ssm.chunk`` positions in which the sums run as products
and the state passes from block to block: a chunk boundary changes the
order of the sums and nothing else.

Two rules make a pooled slot's state safe (``models.kv_cache.
HybridCache``): a row whose frontier is 0 (a new tenant: admission gave
it the slot) reads a zero state and tail, whatever its slot's last
tenant left; and a masked position is a no-op, its ``dt`` 0 (so ``a = 1``
and nothing enters ``S``) and its input never in the tail, so a row that
does nothing this call hands back its state and tail bit-untouched.
"""

from __future__ import annotations

from typing import Any, Tuple

import jax
import jax.numpy as jnp
from jax import lax

from torchgpipe_tpu.models.transformer import SSMConfig, TransformerConfig

Pytree = Any
HIGHEST = lax.Precision.HIGHEST


def init_state(cfg: TransformerConfig, rows: int,
               dtype: Any) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Zeroed ``(tail [rows, K - 1, conv_dim], state f32 [rows, H, P, N])``
    of one mixer layer."""
    s = cfg.ssm
    return (jnp.zeros((rows, s.conv_kernel - 1, s.conv_dim), dtype),
            jnp.zeros((rows, s.n_heads, s.head_dim, s.state), jnp.float32))


def _conv(s: SSMConfig, p: Pytree, xbc: jnp.ndarray, tail: jnp.ndarray,
          n_valid: jnp.ndarray) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """``(silu(conv(xbc) + b) f32 [b, g, conv_dim], the new tail)``: the
    conv over ``[tail ; xbc]``; the new tail is the ``K - 1`` inputs
    that end at the row's last VALID position (the old tail itself for a
    row with ``n_valid = 0``)."""
    g, k = xbc.shape[1], s.conv_kernel
    ext = jnp.concatenate([tail, xbc.astype(tail.dtype)], axis=1)
    w = p["conv_w"].astype(jnp.float32)
    acc = sum(ext[:, j:j + g].astype(jnp.float32) * w[j] for j in range(k))
    acc = acc + p["conv_b"].astype(jnp.float32)
    new_tail = jnp.take_along_axis(ext, _tail_at(n_valid, k)[:, :, None], axis=1)
    return jax.nn.silu(acc), new_tail


def _tail_at(n_valid: jnp.ndarray, k: int) -> jnp.ndarray:
    """Where in ``[tail ; chunk]`` a row's new tail lies: the ``k - 1``
    inputs that end at its last VALID position, so a padded position's
    input never enters it."""
    return n_valid[:, None] + jnp.arange(k - 1)[None, :]


def _entering(fresh: jnp.ndarray, tail: jnp.ndarray,
              state: jnp.ndarray) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """The tail and state a row continues from: zero for a new tenant
    (``fresh``), whatever its slot's last tenant left."""
    return (jnp.where(fresh[:, None, None], 0, tail).astype(tail.dtype),
            jnp.where(fresh[:, None, None, None], 0.0, state))


def _steps(dt: jnp.ndarray, valid: jnp.ndarray) -> jnp.ndarray:
    """A masked position's step is 0: ``a = 1`` and nothing enters the
    state, which it hands on untouched."""
    return jnp.where(valid[..., None], dt, 0.0)


def _step(x, dt, a_log, B, C, state):
    """The recurrence at one position: ``x [b, G, R, P]``, ``dt [b, G,
    R]``, ``a_log = dt * A``, ``B``/``C`` ``[b, G, N]``, ``state [b, G,
    R, P, N]`` -> ``(y [b, G, R, P], new state)``."""
    decay = jnp.exp(a_log)[..., None, None]
    inflow = (dt[..., None] * x)[..., None] * B[:, :, None, None, :]
    new = decay * state + inflow
    y = jnp.einsum("bgrpn,bgn->bgrp", new, C, precision=HIGHEST)
    return y, new


def _ssd_block(x, dt, a_log, B, C, state):
    """One block of the chunked form over ``L`` positions: ``x [b, L, G,
    R, P]``, ``dt`` / ``a_log`` ``[b, L, G, R]``, ``B``/``C`` ``[b, L,
    G, N]``, ``state [b, G, R, P, N]`` at the block's start.  Position
    ``t`` takes ``x_s`` (``s <= t``) with weight ``exp(cum_t - cum_s)
    (C_t . B_s) dt_s`` and the entering state decayed by
    ``exp(cum_t)``; the state leaving is the entering one decayed over
    the block plus every position's inflow decayed to the block's end."""
    L = x.shape[1]
    cum = jnp.cumsum(a_log, axis=1)                         # [b, L, G, R]
    causal = (jnp.arange(L)[:, None] >= jnp.arange(L)[None, :])
    causal = causal[None, :, :, None, None]                 # [1, t, s, 1, 1]
    seg = cum[:, :, None] - cum[:, None, :]                 # [b, t, s, G, R]
    decay = jnp.exp(jnp.where(causal, seg, -jnp.inf))
    cb = jnp.einsum("btgn,bsgn->btsg", C, B, precision=HIGHEST)
    w = decay * cb[..., None] * dt[:, None]
    y = jnp.einsum("btsgr,bsgrp->btgrp", w, x, precision=HIGHEST)
    y = y + jnp.einsum("btgn,bgrpn->btgrp", C, state,
                       precision=HIGHEST) * jnp.exp(cum)[..., None]
    to_end = jnp.exp(cum[:, -1:] - cum) * dt                # [b, L, G, R]
    inflow = jnp.einsum("bsgrp,bsgn->bgrpn", to_end[..., None] * x, B,
                        precision=HIGHEST)
    return y, jnp.exp(cum[:, -1])[..., None, None] * state + inflow


def _ssd(chunk: int, x, dt, a_log, B, C, state):
    """The chunked form over ``g`` positions in blocks of ``chunk``
    (the last one padded with no-op positions: ``dt = 0``)."""
    g = x.shape[1]
    if g <= chunk:
        return _ssd_block(x, dt, a_log, B, C, state)
    n = -(-g // chunk)
    pad = n * chunk - g

    def blocks(v):
        v = jnp.pad(v, [(0, 0), (0, pad)] + [(0, 0)] * (v.ndim - 2))
        return jnp.moveaxis(v.reshape(v.shape[0], n, chunk, *v.shape[2:]),
                            1, 0)

    def body(st, args):
        y, st = _ssd_block(*args, st)
        return st, y

    state, ys = lax.scan(body, state, tuple(map(blocks, (x, dt, a_log, B, C))))
    ys = jnp.moveaxis(ys, 0, 1)
    return ys.reshape(x.shape[0], n * chunk, *ys.shape[3:])[:, :g], state


def _gated_norm(s: SSMConfig, y: jnp.ndarray, z: jnp.ndarray,
                w: jnp.ndarray, eps: float) -> jnp.ndarray:
    """``RMSNorm over groups of d_inner / G (y * silu(z)) * w``, f32."""
    y = y * jax.nn.silu(z.astype(jnp.float32))
    grouped = y.reshape(*y.shape[:-1], s.n_groups, -1)
    var = jnp.mean(jnp.square(grouped), -1, keepdims=True)
    y = (grouped * lax.rsqrt(var + eps)).reshape(y.shape)
    return y * w.astype(jnp.float32)


def mixer(
    cfg: TransformerConfig,
    p: Pytree,
    u: jnp.ndarray,              # [b, g, dim] — the layer's normed input
    tail: jnp.ndarray,           # [b, K - 1, conv_dim] — the rows' conv tails
    state: jnp.ndarray,          # f32 [b, H, P, N] — the rows' states
    n_valid: jnp.ndarray,        # [b] int32 — valid tokens a row (0: no-op)
    fresh: jnp.ndarray,          # [b] bool — a new tenant: zero state and tail
) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """``(out [b, g, dim], new tail, new state)`` of one mixer layer
    over a chunk of ``g`` tokens a row (see the module docstring).  A row
    with ``n_valid = 0`` hands back its tail and state as they came."""
    s = cfg.ssm
    b, g, _ = u.shape
    G, R = s.n_groups, s.n_heads // s.n_groups
    valid = jnp.arange(g)[None, :] < n_valid[:, None]        # [b, g]
    keep = n_valid > 0
    with jax.named_scope("ssm.in_proj"):
        zxbcdt = u @ p["in_proj"]
        z = zxbcdt[..., :s.d_inner]
        xbc = zxbcdt[..., s.d_inner:s.d_inner + s.conv_dim]
        dt = zxbcdt[..., s.d_inner + s.conv_dim:]
    tail_in, state_in = _entering(fresh, tail, state)
    with jax.named_scope("ssm.conv"):
        xbc, new_tail = _conv(s, p, xbc, tail_in, n_valid)
        new_tail = jnp.where(keep[:, None, None], new_tail, tail)
    with jax.named_scope("ssm.scan"):
        x = xbc[..., :s.d_inner].reshape(b, g, G, R, s.head_dim)
        B = xbc[..., s.d_inner:s.d_inner + G * s.state].reshape(b, g, G, s.state)
        C = xbc[..., s.d_inner + G * s.state:].reshape(b, g, G, s.state)
        dt = jax.nn.softplus(dt.astype(jnp.float32) + p["dt_bias"])
        dt = _steps(dt, valid).reshape(b, g, G, R)
        a_log = dt * -jnp.exp(p["A_log"]).reshape(G, R)
        st = state_in.reshape(b, G, R, s.head_dim, s.state)
        if g == 1:
            y, st = _step(x[:, 0], dt[:, 0], a_log[:, 0], B[:, 0], C[:, 0], st)
            y = y[:, None]
        else:
            y, st = _ssd(s.chunk, x, dt, a_log, B, C, st)
        y = y + p["D"].reshape(G, R)[:, :, None] * x
        new_state = jnp.where(keep[:, None, None, None],
                              st.reshape(state.shape), state)
    with jax.named_scope("ssm.out"):
        y = _gated_norm(s, y.reshape(b, g, s.d_inner), z, p["norm"],
                        cfg.norm_eps)
        out = y.astype(u.dtype) @ p["out_proj"]
    return out, new_tail, new_state


__all__ = ["init_state", "mixer"]
