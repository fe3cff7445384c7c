"""The plain reference of a ``mellum2`` configuration: forward, loss, gradients
and AdamW in straightforward ``jax.numpy``, float32 arithmetic at ``highest``
precision.  It imports nothing of the program.

Every layer: ``u = RMS(x)``, ``h = x + Attn_l(u)``, ``y = h + MoE(RMS(h))``;
a final RMS norm and an untied head; mean next-token cross-entropy.

* ``Attn_l``: 32 query and 4 key/value heads of 128, no biases; q and k
  RMS-normed over the 128 with a learned scale; rotary embedding in the
  half-split layout; scores ``q.k / sqrt(128)``, softmax in float32.  A
  ``sliding_attention`` layer rotates by plain frequencies and attends iff
  ``0 <= i - j < sliding_window``; a ``full_attention`` layer is causal and
  rotates under YaRN (Peng et al., arXiv:2309.00071): the frequencies below
  the ``beta_slow`` correction dim divided by ``factor``, those above the
  ``beta_fast`` one kept, a linear ramp between; cos and sin scaled by the
  record's ``attention_factor``.
* ``MoE``: ``p = softmax(W_r u)`` over ALL the published experts; the
  ``num_experts_per_tok`` largest; their ``p`` divided by their sum
  (``norm_topk_prob``); the sum over the selected experts that are HELD
  (``[held_first, held_first + num_experts)``) of ``g_i W_down,i
  (silu(W_gate,i u) * W_up,i u)``.  What absent experts would add is left
  out, as in the program.

Departures from the published model, each where it is made: the share (held
experts, vocabulary slice, depth); the half-split rotary layout; state STORED
in the configuration's types (bfloat16 weights and moments, float32 norm
scales and router) with every product, sum and update in float32; the
backward layer by layer and row by row, attention a head at a time and the
experts one at a time (over the tokens an expert was given where they are
few, else over ALL of a row's tokens with weight 0 where it was not chosen),
so that it fits beside its own state on one chip.

``low=True`` is the control: every weight product's operands rounded to four
significant bits (``reference._fp8_round``), the router's among them.
"""

from __future__ import annotations

import math
from typing import Any, Dict, List, Mapping, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from chipbench.reference import F32, HIGHEST, _f32, _mm, _rms
from chipbench.reference import train_programs as dense_programs

SLIDING, FULL = "sliding_attention", "full_attention"
# An expert whose tokens number at most a row's length over this is computed
# on those tokens alone, any other on every token with weight 0 where it was
# not chosen: the same sum either way, a quarter of the products at the
# uniform load of an eighth.
GATHER_SHARE = 4


def yarn_inv_freq(dim: int, rope: Mapping[str, Any]) -> np.ndarray:
    """Inverse frequencies ``[dim // 2]`` under a ``rope_type: yarn`` record."""
    base, factor = float(rope["rope_theta"]), float(rope["factor"])
    original = rope["original_max_position_embeddings"]
    plain = base ** (-np.arange(0, dim, 2, dtype=np.float64) / dim)

    def correction_dim(rotations: float) -> float:
        return dim * math.log(original / (rotations * 2.0 * math.pi)) / (2.0 * math.log(base))

    low = max(math.floor(correction_dim(rope["beta_fast"])), 0)
    high = min(math.ceil(correction_dim(rope["beta_slow"])), dim - 1)
    ramp = np.clip((np.arange(dim // 2) - low) / max(high - low, 1e-3), 0.0, 1.0)
    return (plain / factor * ramp + plain * (1.0 - ramp)).astype(np.float32)


def rope(m: Mapping[str, Any], kind: str, x: jax.Array) -> jax.Array:
    """x [S, heads, head_dim] at positions 0..S-1; halves rotate together."""
    s, _, hd = x.shape
    half = hd // 2
    record = m["rope_parameters"][kind]
    if record["rope_type"] == "yarn":
        freqs, factor = jnp.asarray(yarn_inv_freq(hd, record)), record["attention_factor"]
    else:
        freqs = float(record["rope_theta"]) ** (-jnp.arange(half, dtype=F32) / half)
        factor = 1.0
    ang = jnp.arange(s, dtype=F32)[:, None] * freqs
    cos, sin = factor * jnp.cos(ang)[:, None, :], factor * jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def attend(q: jax.Array, k: jax.Array, v: jax.Array, window: Optional[int]) -> jax.Array:
    """Causal grouped-query attention of one row, a query head at a time
    (recomputed in the backward, so one head's scores are alive at once)."""
    s, h, hd = q.shape
    group = h // k.shape[1]
    i, j = jnp.arange(s)[:, None], jnp.arange(s)[None, :]
    seen = j <= i
    if window:
        seen = seen & (i - j < window)
    kt, vt = k.transpose(1, 0, 2), v.transpose(1, 0, 2)

    @jax.checkpoint
    def head(args):
        qq, n = args
        sc = jnp.matmul(qq, kt[n // group].T, precision=HIGHEST) * hd ** -0.5
        p = jax.nn.softmax(jnp.where(seen, sc, -1e30), -1)
        return jnp.matmul(p, vt[n // group], precision=HIGHEST)

    out = lax.map(head, (q.transpose(1, 0, 2), jnp.arange(h)))
    return out.transpose(1, 0, 2).reshape(s, h * hd)


def experts(m: Mapping[str, Any], p: Mapping[str, jax.Array], u: jax.Array,
            low: bool) -> jax.Array:
    """The held experts' part of the routed sum for one row, u [S, hidden]."""
    probs = jax.nn.softmax(_mm(u, p["router"], low), -1)        # over all published experts
    top, chosen = lax.top_k(probs, m["num_experts_per_tok"])
    gates = top / jnp.sum(top, -1, keepdims=True) if m["norm_topk_prob"] else top
    held = m["held_first"] + jnp.arange(p["w_gate"].shape[0])
    # [S, held]: a token's weight on each held expert, 0 where it was not chosen.
    weight = jnp.sum(gates[:, :, None] * (chosen[:, :, None] == held), axis=1)

    cap = max(u.shape[0] // GATHER_SHARE, 1)

    @jax.checkpoint
    def one(y, expert):
        w_gate, w_up, w_down, g = expert

        def ffn(rows):
            return _mm(jax.nn.silu(_mm(rows, w_gate, low)) * _mm(rows, w_up, low), w_down, low)

        def its_tokens():
            # The tokens this expert was given, gathered (at most ``cap``;
            # the slots past their number repeat token 0 with weight 0).
            at = jnp.nonzero(g > 0, size=cap, fill_value=0)[0]
            live = jnp.arange(cap) < jnp.sum(g > 0)
            return y.at[at].add(jnp.where(live, g[at], 0.0)[:, None] * ffn(u[at]))

        def every_token():
            return y + g[:, None] * ffn(u)

        return lax.cond(jnp.sum(g > 0) <= cap, its_tokens, every_token), None

    y, _ = lax.scan(one, jnp.zeros_like(u), (p["w_gate"], p["w_up"], p["w_down"], weight.T))
    return y


def block(m: Mapping[str, Any], kind: str, p: Mapping[str, Any], x: jax.Array,
          low: bool) -> jax.Array:
    """One pre-norm block of layer type ``kind`` on one row, x [S, hidden] float32."""
    s, hd, eps = x.shape[0], m["head_dim"], m["rms_norm_eps"]
    u = _rms(x, p["ln1"], eps)
    q = _rms(_mm(u, p["wq"], low).reshape(s, -1, hd), p["qn"], eps)
    k = _rms(_mm(u, p["wk"], low).reshape(s, -1, hd), p["kn"], eps)
    v = _mm(u, p["wv"], low).reshape(s, -1, hd)
    window = m["sliding_window"] if kind == SLIDING else None
    x = x + _mm(attend(rope(m, kind, q), rope(m, kind, k), v, window), p["wo"], low)
    return x + experts(m, p["mlp"], _rms(x, p["ln2"], eps), low)


def train_programs(m: Mapping[str, Any], opt: Mapping[str, float], low: bool) -> Dict[str, Any]:
    """The reference's jitted pieces.  Embedding and head, with the AdamW of
    their own leaves, are the dense reference's (the same mathematics); a
    layer's forward and its backward with its AdamW update are this model's,
    compiled once a layer type."""
    lr, b1, b2 = opt["learning_rate"], opt["b1"], opt["b2"]
    eps, wd = opt["eps"], opt["weight_decay"]

    def adamw(p, mu, nu, g, t):
        # Decoupled weight decay; moments and weights go back to the stored
        # type after a float32 update.
        mu32 = b1 * mu.astype(F32) + (1.0 - b1) * g
        nu32 = b2 * nu.astype(F32) + (1.0 - b2) * g * g
        upd = (mu32 / (1.0 - b1 ** t)) / (jnp.sqrt(nu32 / (1.0 - b2 ** t)) + eps)
        p32 = p.astype(F32)
        return ((p32 - lr * (upd + wd * p32)).astype(p.dtype),
                mu32.astype(mu.dtype), nu32.astype(nu.dtype))

    def layer_fwd(p, xs, kind):
        p32 = _f32(p)
        return lax.map(lambda x: block(m, kind, p32, x, low), xs)

    def layer_step(p, mu, nu, xs, dys, t, kind):
        def body(acc, xd):
            _, vjp = jax.vjp(lambda pp, xx: block(m, kind, pp, xx, low), _f32(p), xd[0])
            g, dx = vjp(xd[1])
            return jax.tree_util.tree_map(jnp.add, acc, g), dx
        g, dxs = lax.scan(body, jax.tree_util.tree_map(jnp.zeros_like, _f32(p)), (xs, dys))
        new = jax.tree_util.tree_map(lambda *a: adamw(*a, t), p, mu, nu, g)
        pick = lambda n: jax.tree_util.tree_map(   # noqa: E731
            lambda _, triple: triple[n], p, new)
        return pick(0), pick(1), pick(2), dxs, _leaf_norms(g)

    dense = dense_programs(m, opt, low)
    return {
        "embed_fwd": dense["embed_fwd"], "head_step": dense["head_step"],
        "embed_step": dense["embed_step"],
        "layer_fwd": jax.jit(layer_fwd, static_argnums=(2,)),
        "layer_step": jax.jit(layer_step, static_argnums=(6,), donate_argnums=(0, 1, 2, 4)),
    }


def _leaf_norms(tree: Any) -> List[jax.Array]:
    """Norm of every leaf, in ``jax.tree_util``'s order (nested groups too)."""
    return [jnp.sqrt(jnp.sum(jnp.square(g.astype(F32))))
            for g in jax.tree_util.tree_leaves(tree)]


@jax.jit
def _tree_change_norms(now: Any, was: Any) -> List[jax.Array]:
    return _leaf_norms(jax.tree_util.tree_map(
        lambda a, b: a.astype(F32) - b.astype(F32), now, was))


class TrainReference:
    """Follows the first steps of a training run from the same weights and
    batches, on one chip."""

    def __init__(self, m: Mapping[str, Any], flat: Sequence[Dict[str, Any]],
                 opt: Mapping[str, float], low: bool = False) -> None:
        self.kinds = list(m["layer_types"])
        if len(self.kinds) != len(flat) - 2:
            raise ValueError(f"{len(flat) - 2} blocks for {len(self.kinds)} layer_types")
        self.p = [dict(u) for u in flat]
        zeros = jax.jit(lambda t: jax.tree_util.tree_map(jnp.zeros_like, t))
        self.mu = [zeros(u) for u in self.p]
        self.nu = [zeros(u) for u in self.p]
        self.count = 0
        self._f = train_programs(m, opt, low)

    def step(self, tokens: np.ndarray) -> Tuple[float, List[float]]:
        """One optimizer step on ``tokens`` [rows, seq + 1].  Returns the loss
        and the norm of each leaf's gradient (unit by unit of the flat list,
        each unit's leaves in ``jax.tree_util``'s order)."""
        self.count += 1
        t, f = np.float32(self.count), self._f
        tokens = np.asarray(tokens, np.int32)
        x, y = tokens[:, :-1], tokens[:, 1:]
        last = len(self.p) - 1
        acts = [f["embed_fwd"](self.p[0], x)]
        for i in range(1, last):
            acts.append(f["layer_fwd"](self.p[i], acts[-1], self.kinds[i - 1]))
        norms: List[Any] = [None] * len(self.p)
        self.p[last], self.mu[last], self.nu[last], dxs, head_norms, loss = f["head_step"](
            self.p[last], self.mu[last], self.nu[last], acts.pop(), y, t)
        for i in range(last - 1, 0, -1):
            self.p[i], self.mu[i], self.nu[i], dxs, norms[i] = f["layer_step"](
                self.p[i], self.mu[i], self.nu[i], acts.pop(), dxs, t, self.kinds[i - 1])
        self.p[0], self.mu[0], self.nu[0], embed_norms = f["embed_step"](
            self.p[0], self.mu[0], self.nu[0], x, dxs, t)
        # The dense reference's units give {leaf: norm}; sorted keys are
        # jax.tree_util's order.
        norms[0] = [embed_norms[k] for k in sorted(embed_norms)]
        norms[last] = [head_norms[k] for k in sorted(head_norms)]
        return float(loss), [float(n) for unit in jax.device_get(norms) for n in unit]

    def change_norms(self, start: Sequence[Dict[str, Any]]) -> List[float]:
        """Norm of each leaf's change from ``start`` (the weights as made)."""
        out = [_tree_change_norms(now, dict(was)) for now, was in zip(self.p, start)]
        return [float(n) for unit in jax.device_get(out) for n in unit]
