"""The plain reference: Mistral's forward, loss, gradients and AdamW in
straightforward ``jax.numpy``, float32 arithmetic at ``highest`` precision.

It imports nothing of the program.  It follows the published architecture
(pre-norm blocks, RMSNorm, rotary embedding in the half-split convention of
the published code, grouped-query causal attention with a sliding window,
gated SiLU feed-forward, untied head) and the AdamW of Loshchilov & Hutter
with bias correction.  Departures, each noted where it is made: state is
STORED in the types the configuration states (bfloat16 weights and moments,
float32 norm scales) and every product, sum and update is computed in
float32; the backward pass goes layer by layer and row by row so that it
fits beside its own state on one chip.

``low=True`` is the control: the same mathematics with the operands of every
weight product rounded to four significant bits (an fp8 e4m3 mantissa), the
precision step below bfloat16 that a later change might be tempted by.
"""

from __future__ import annotations

from typing import Any, Dict, List, Mapping, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from chipbench.peaks import head_dim

F32 = jnp.float32
HIGHEST = lax.Precision.HIGHEST


def _fp8_round(x: jax.Array) -> jax.Array:
    """Round to 1 + 3 mantissa bits (e4m3's precision, range unbounded);
    straight-through for the gradient."""
    mant, exp = jnp.frexp(lax.stop_gradient(x))
    return x + lax.stop_gradient(jnp.ldexp(jnp.round(mant * 16.0) / 16.0, exp) - x)


def _mm(a: jax.Array, w: jax.Array, low: bool) -> jax.Array:
    w = w.astype(F32)
    if low:
        a, w = _fp8_round(a), _fp8_round(w)
    return jnp.matmul(a, w, precision=HIGHEST)


def _rms(x: jax.Array, scale: jax.Array, eps: float) -> jax.Array:
    return x * lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * scale


def _rope(x: jax.Array, theta: float) -> jax.Array:
    """x [S, heads, head_dim]; positions 0..S-1; halves rotate together."""
    s, _, hd = x.shape
    half = hd // 2
    freqs = theta ** (-jnp.arange(half, dtype=F32) / half)
    ang = jnp.arange(s, dtype=F32)[:, None] * freqs
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _attend(q: jax.Array, k: jax.Array, v: jax.Array,
            window: Optional[int]) -> jax.Array:
    """Causal grouped-query attention of one row, one KV group at a time
    (recomputed in the backward, so one group's scores are alive at once)."""
    s, h, hd = q.shape
    kv = k.shape[1]
    qg = q.reshape(s, kv, h // kv, hd).transpose(1, 0, 2, 3)
    i, j = jnp.arange(s)[:, None], jnp.arange(s)[None, :]
    seen = j <= i
    if window:
        seen = seen & (i - j < window)

    @jax.checkpoint
    def group(args):
        qq, kk, vv = args
        sc = jnp.einsum("sgd,td->gst", qq, kk, precision=HIGHEST) * hd ** -0.5
        p = jax.nn.softmax(jnp.where(seen[None], sc, -1e30), -1)
        return jnp.einsum("gst,td->sgd", p, vv, precision=HIGHEST)

    out = lax.map(group, (qg, k.transpose(1, 0, 2), v.transpose(1, 0, 2)))
    return out.transpose(1, 0, 2, 3).reshape(s, h * hd)


def block(m: Mapping[str, Any], p: Mapping[str, jax.Array], x: jax.Array,
          low: bool) -> jax.Array:
    """One pre-norm block on one row, x [S, hidden] float32."""
    s = x.shape[0]
    hd, eps, theta = head_dim(m), m["rms_norm_eps"], m["rope_theta"]
    h = _rms(x, p["ln1"], eps)
    q = _rope(_mm(h, p["wq"], low).reshape(s, -1, hd), theta)
    k = _rope(_mm(h, p["wk"], low).reshape(s, -1, hd), theta)
    v = _mm(h, p["wv"], low).reshape(s, -1, hd)
    x = x + _mm(_attend(q, k, v, m.get("sliding_window")), p["wo"], low)
    h = _rms(x, p["ln2"], eps)
    gated = jax.nn.silu(_mm(h, p["w_gate"], low)) * _mm(h, p["w_up"], low)
    return x + _mm(gated, p["w_down"], low)


def head_logits(m: Mapping[str, Any], p: Mapping[str, jax.Array],
                x: jax.Array, low: bool) -> jax.Array:
    return _mm(_rms(x, p["scale"], m["rms_norm_eps"]), p["w"], low)


def _row_loss_sum(m, p, x, y, low):
    logp = jax.nn.log_softmax(head_logits(m, p, x, low), -1)
    return -jnp.sum(jnp.take_along_axis(logp, y[:, None], -1))


def _f32(tree: Any) -> Any:
    return jax.tree_util.tree_map(lambda a: a.astype(F32), tree)


def _norms(tree: Mapping[str, jax.Array]) -> Dict[str, jax.Array]:
    return {k: jnp.sqrt(jnp.sum(jnp.square(g.astype(F32)))) for k, g in tree.items()}


def train_programs(m: Mapping[str, Any], opt: Mapping[str, float], low: bool) -> Dict[str, Any]:
    """The reference's jitted pieces: forward of a layer, and for each unit
    (head, layer, embedding) its backward with the AdamW update of its own
    leaves, so that no whole-model gradient is ever held."""
    lr, b1, b2 = opt["learning_rate"], opt["b1"], opt["b2"]
    eps, wd = opt["eps"], opt["weight_decay"]

    def adamw(p, mu, nu, g, t):
        # Decoupled weight decay; moments and weights go back to the
        # stored type after a float32 update.
        out = {}
        for k in p:
            mu32 = b1 * mu[k].astype(F32) + (1.0 - b1) * g[k]
            nu32 = b2 * nu[k].astype(F32) + (1.0 - b2) * g[k] * g[k]
            upd = (mu32 / (1.0 - b1 ** t)) / (
                jnp.sqrt(nu32 / (1.0 - b2 ** t)) + eps)
            p32 = p[k].astype(F32)
            out[k] = ((p32 - lr * (upd + wd * p32)).astype(p[k].dtype),
                      mu32.astype(mu[k].dtype), nu32.astype(nu[k].dtype))
        return ({k: v[0] for k, v in out.items()},
                {k: v[1] for k, v in out.items()},
                {k: v[2] for k, v in out.items()})

    def layer_fwd(p, xs):
        p32 = _f32(p)
        return lax.map(lambda x: block(m, p32, x, low), xs)

    def layer_step(p, mu, nu, xs, dys, t):
        def body(acc, xd):
            _, vjp = jax.vjp(lambda pp, xx: block(m, pp, xx, low), _f32(p), xd[0])
            g, dx = vjp(xd[1])
            return jax.tree_util.tree_map(jnp.add, acc, g), dx
        g, dxs = lax.scan(body, jax.tree_util.tree_map(jnp.zeros_like, _f32(p)), (xs, dys))
        return adamw(p, mu, nu, g, t) + (dxs, _norms(g))

    def head_step(p, mu, nu, xs, ys, t):
        scale = 1.0 / (ys.shape[0] * ys.shape[1])

        def body(carry, xy):
            loss, (g, dx) = jax.value_and_grad(
                lambda pp, xx: _row_loss_sum(m, pp, xx, xy[1], low) * scale,
                argnums=(0, 1))(_f32(p), xy[0])
            return (carry[0] + loss, jax.tree_util.tree_map(jnp.add, carry[1], g)), dx
        zero = (jnp.zeros((), F32), jax.tree_util.tree_map(jnp.zeros_like, _f32(p)))
        (loss, g), dxs = lax.scan(body, zero, (xs, ys))
        return adamw(p, mu, nu, g, t) + (dxs, _norms(g), loss)

    def embed_step(p, mu, nu, tokens, dxs, t):
        table = p["table"]
        g = {"table": jnp.zeros(table.shape, F32).at[tokens.reshape(-1)].add(
            dxs.reshape(-1, table.shape[1]))}
        return adamw(p, mu, nu, g, t) + (_norms(g),)

    return {
        "embed_fwd": jax.jit(lambda p, tok: p["table"][tok].astype(F32)),
        "layer_fwd": jax.jit(layer_fwd),
        "layer_step": jax.jit(layer_step, donate_argnums=(0, 1, 2, 4)),
        "head_step": jax.jit(head_step, donate_argnums=(0, 1, 2, 3)),
        "embed_step": jax.jit(embed_step, donate_argnums=(0, 1, 2)),
    }


class TrainReference:
    """Follows the first steps of a training run from the same weights and
    batches.  The units of the flat list (embedding, blocks, head) are
    dealt round the ``devices``, so a model that needs several chips' memory
    is held across them; the arithmetic is the same."""

    def __init__(self, m: Mapping[str, Any], flat: Sequence[Dict[str, jax.Array]],
                 opt: Mapping[str, float], low: bool = False,
                 devices: Optional[Sequence[Any]] = None) -> None:
        devices = list(devices or [jax.devices()[0]])
        self._dev = [devices[i % len(devices)] for i in range(len(flat))]
        self.p = [jax.device_put(dict(u), d) for u, d in zip(flat, self._dev)]
        zeros = jax.jit(lambda t: jax.tree_util.tree_map(jnp.zeros_like, t))
        self.mu = [zeros(u) for u in self.p]
        self.nu = [zeros(u) for u in self.p]
        self.count = 0
        f = train_programs(m, opt, low)
        self._embed_fwd, self._layer_fwd = f["embed_fwd"], f["layer_fwd"]
        self._layer_step, self._head_step = f["layer_step"], f["head_step"]
        self._embed_step = f["embed_step"]

    def step(self, tokens: np.ndarray) -> Tuple[float, List[float]]:
        """One optimizer step on ``tokens`` [rows, seq + 1].  Returns the
        loss and the norm of each leaf's gradient (unit by unit of the flat
        list, each unit's keys sorted)."""
        self.count += 1
        t = np.float32(self.count)
        tokens = np.asarray(tokens, np.int32)
        x, y = tokens[:, :-1], tokens[:, 1:]
        last = len(self.p) - 1
        acts = [self._embed_fwd(self.p[0], jax.device_put(x, self._dev[0]))]
        for i in range(1, last):
            # A layer's input is kept where the layer lives, for its backward.
            acts[-1] = jax.device_put(acts[-1], self._dev[i])
            acts.append(self._layer_fwd(self.p[i], acts[-1]))
        norms: List[Any] = [None] * len(self.p)
        top = jax.device_put(acts.pop(), self._dev[last])
        self.p[last], self.mu[last], self.nu[last], dxs, norms[last], loss = (
            self._head_step(self.p[last], self.mu[last], self.nu[last], top,
                            jax.device_put(y, self._dev[last]), t))
        for i in range(last - 1, 0, -1):
            self.p[i], self.mu[i], self.nu[i], dxs, norms[i] = self._layer_step(
                self.p[i], self.mu[i], self.nu[i], acts.pop(),
                jax.device_put(dxs, self._dev[i]), t)
        self.p[0], self.mu[0], self.nu[0], norms[0] = self._embed_step(
            self.p[0], self.mu[0], self.nu[0], jax.device_put(x, self._dev[0]),
            jax.device_put(dxs, self._dev[0]), t)
        norms = jax.device_get(norms)
        return float(loss), [float(u[k]) for u in norms for k in sorted(u)]

    def change_norms(self, start: Sequence[Dict[str, jax.Array]]) -> List[float]:
        """Norm of each leaf's change from ``start`` (the weights as made)."""
        out = [_change_norms(now, jax.device_put(dict(was), d))
               for now, was, d in zip(self.p, start, self._dev)]
        return [float(u[k]) for u in jax.device_get(out) for k in sorted(u)]


@jax.jit
def _change_norms(now: Mapping[str, jax.Array], was: Mapping[str, jax.Array]) -> Dict[str, jax.Array]:
    return _norms({k: now[k].astype(F32) - was[k].astype(F32) for k in now})


class ServeReference:
    """One full forward over a prompt with its served tokens, all positions
    at once, no cache: the logits of the positions that chose a token."""

    def __init__(self, m: Mapping[str, Any], flat: Sequence[Dict[str, jax.Array]],
                 length: int, rows: int, low: bool = False) -> None:
        self.flat, self.length, self.rows = list(flat), length, rows

        def logits(flat, tokens, start):
            x = flat[0]["table"][tokens].astype(F32)
            for p in flat[1:-1]:
                x = block(m, _f32(p), x, low)
            return head_logits(m, _f32(flat[-1]), lax.dynamic_slice_in_dim(x, start, rows), low)

        self._logits = jax.jit(logits)

    def chosen_logits(self, prompt: np.ndarray, served: np.ndarray) -> np.ndarray:
        """Logits [len(served), vocab] at the positions that chose each
        served token (position len(prompt)-1+i chose served[i])."""
        n, p = len(served), len(prompt)
        if not (0 < n <= self.rows and p + n <= self.length):
            raise ValueError(f"request of {p}+{n} tokens does not fit the reference")
        tokens = np.zeros((self.length,), np.int32)
        tokens[:p + n] = np.concatenate([prompt, served])
        start = min(p - 1, self.length - self.rows)
        out = np.asarray(self._logits(self.flat, tokens, np.int32(start)))
        return out[p - 1 - start:p - 1 - start + n]


def widest_gap(logits: np.ndarray, chosen: np.ndarray) -> float:
    """The most by which a chosen token's logit lies below the row's best."""
    return float(np.max(logits.max(-1) - logits[np.arange(len(chosen)), chosen]))
