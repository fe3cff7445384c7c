"""The plain reference of Trinity-Large-Preview's forward (``model_type:
afmoe``; source
https://huggingface.co/arcee-ai/Trinity-Large-Preview/blob/main/config.json):
straightforward ``jax.numpy``, float32 arithmetic at ``highest`` precision,
all positions of a sequence at once, no cache, no ring, no kernel.  It imports
nothing of the program; the weights are ``weights_trinity.make_flat``'s.

The equations (``x`` is ``[t, hidden]``; N1..N4 RMS norms with a learned
scale, eps ``rms_norm_eps``):

* embedding: ``x = E[ids] * sqrt(hidden_size)`` (``mup_enabled``).
* a layer: ``x = x + N2(attn(N1(x)))``, then ``x = x + N4(ff(N3(x)))``
  (``ln1``, ``ln1p``, ``ln2``, ``ln2p``).  ``ff`` is the dense SwiGLU of
  ``intermediate_size`` in the leading ``num_dense_layers`` layers, the expert
  layer after them.
* attention: ``q = h Wq``, ``k = h Wk``, ``v = h Wv``, ``g = h Wg``; q and k
  RMS-normed over the ``head_dim`` of a head with a learned scale (``qn``,
  ``kn``); in a ``sliding_attention`` layer q and k are rotated (theta
  ``rope_theta``, no scaling) and query ``i`` attends keys ``j`` with ``0 <= i
  - j < sliding_window``; in a ``full_attention`` layer nothing is rotated and
  the attention is causal over the whole context; ``out = (softmax(q k^T /
  sqrt(head_dim)) v * sigmoid(g)) Wo``.
* expert layer: ``s = sigmoid(h Wr)`` over all the published experts in
  float32; the ``num_experts_per_tok`` chosen are the largest of ``s + b``
  (``router_bias``); weights are ``s`` (not ``s + b``) at the chosen, divided
  by their sum + 1e-20 (``route_norm``), times ``route_scale``; ``y =
  shared(h) + sum over the chosen experts THAT ARE HELD of w_e expert_e(h)``,
  every expert a SwiGLU of ``moe_intermediate_size``.
* final RMS norm (``scale``), untied head over the slice of the vocabulary.

Departures from the published model, each noted where it is made:

1. **The chip's share.**  The configuration holds experts ``[held_first,
   held_first + num_experts)`` of the published count; the router scores all
   of them and normalises over all the chosen; the routed sum runs over the
   chosen experts that are HELD (``_experts``).  With every expert held this
   is the model's layer.
2. **Rotary layout**: the two halves of a head's dims rotate together, as in
   ``reference.py`` (a fixed permutation of ``Wq``'s and ``Wk``'s columns away
   from interleaved pairs; seeded weights do not notice).
3. State is STORED as the configuration states (bfloat16 weights, float32
   norm scales, router and bias); every product and sum is float32.
4. Attention runs a block of queries at a time against the keys its band can
   reach (a window layer: the ``sliding_window + block`` keys before the
   block's last query; a full layer: all of them), and the routed sum an
   expert at a time, so that a 16,384-position sequence fits beside the
   weights; the arithmetic is the same.

``low=True`` is the control of ``reference.py``: the operands of every weight
product rounded to four significant bits.  ``leave_out`` names mechanisms of
the block left out or altered (``FAULTS``): what ``limits_trinity.py`` plants
in the reference's place to read what each limit refuses.
"""

from __future__ import annotations

from typing import Any, Dict, FrozenSet, Mapping, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from chipbench.reference import F32, HIGHEST, _mm, _rms, _rope
from chipbench.weights_axk1 import published

# Mechanisms ``leave_out`` may name.
FAULTS = ("no_window", "full_rotated", "no_gate", "no_post_norms", "no_bias",
          "no_route_scale", "held_shifted")
QUERY_BLOCK = 512


def _attend(q: jax.Array, k: jax.Array, v: jax.Array, window: Optional[int]) -> jax.Array:
    """Causal grouped-query attention of one sequence (departure 4): q ``[S,
    H, hd]``, k and v ``[S, G, hd]``; a block of queries at a time over the
    ``span`` keys that end with the block's last query."""
    s, h, hd = q.shape
    g = k.shape[1]
    qb = QUERY_BLOCK if s % QUERY_BLOCK == 0 else s
    span = s if window is None else min(s, -(-(window - 1 + qb) // qb) * qb)
    pad = span - qb
    kp = jnp.pad(k, ((pad, 0), (0, 0), (0, 0)))
    vp = jnp.pad(v, ((pad, 0), (0, 0), (0, 0)))

    def block(i):
        qq = lax.dynamic_slice_in_dim(q, i * qb, qb).reshape(qb, g, h // g, hd)
        kk = lax.dynamic_slice_in_dim(kp, i * qb, span)
        vv = lax.dynamic_slice_in_dim(vp, i * qb, span)
        qpos = i * qb + jnp.arange(qb)[:, None]
        kpos = i * qb - pad + jnp.arange(span)[None, :]
        seen = (kpos <= qpos) & (kpos >= 0)
        if window is not None:
            seen = seen & (qpos - kpos < window)
        sc = jnp.einsum("qgrd,kgd->grqk", qq, kk, precision=HIGHEST) * hd ** -0.5
        prob = jax.nn.softmax(jnp.where(seen[None, None], sc, -1e30), -1)
        return jnp.einsum("grqk,kgd->qgrd", prob, vv, precision=HIGHEST).reshape(qb, h * hd)

    return lax.map(block, jnp.arange(s // qb)).reshape(s, h * hd)


def _attention(m: Mapping[str, Any], p: Mapping[str, jax.Array], h: jax.Array, layer: int,
               low: bool, leave_out: FrozenSet[str]) -> jax.Array:
    s, hd, eps, theta = h.shape[0], m["head_dim"], m["rms_norm_eps"], m["rope_theta"]
    q = _rms(_mm(h, p["wq"], low).reshape(s, -1, hd), p["qn"], eps)
    k = _rms(_mm(h, p["wk"], low).reshape(s, -1, hd), p["kn"], eps)
    v = _mm(h, p["wv"], low).reshape(s, -1, hd)
    sliding = m["layer_types"][layer] == "sliding_attention"
    if sliding or "full_rotated" in leave_out:
        q, k = _rope(q, theta), _rope(k, theta)          # departure 2
    window = m["sliding_window"] if sliding and "no_window" not in leave_out else None
    out = _attend(q, k, v, window)
    if "no_gate" not in leave_out:
        out = out * jax.nn.sigmoid(_mm(h, p["wg"], low))
    return _mm(out, p["wo"], low)


def _swiglu(p: Mapping[str, jax.Array], u: jax.Array, low: bool) -> jax.Array:
    return _mm(jax.nn.silu(_mm(u, p["w_gate"], low)) * _mm(u, p["w_up"], low), p["w_down"], low)


def route(m: Mapping[str, Any], p: Mapping[str, Any], u: jax.Array, low: bool = False,
          leave_out: FrozenSet[str] = frozenset()) -> jax.Array:
    """``w [t, all experts]``: token ``t``'s weight on expert ``e`` (0 where
    ``e`` was not chosen)."""
    if m["score_func"] != "sigmoid":
        raise ValueError("the reference computes sigmoid scores")
    scores = jax.nn.sigmoid(_mm(u, p["router"], low))
    chosen_by = scores if "no_bias" in leave_out else scores + p["router_bias"]
    idx = lax.top_k(chosen_by, m["num_experts_per_tok"])[1]
    top = jnp.take_along_axis(scores, idx, -1)
    if m["route_norm"]:
        top = top / (jnp.sum(top, -1, keepdims=True) + 1e-20)
    if "no_route_scale" not in leave_out:
        top = m["route_scale"] * top
    return jnp.zeros_like(scores).at[jnp.arange(u.shape[0])[:, None], idx].set(top)


def _experts(m: Mapping[str, Any], p: Mapping[str, Any], u: jax.Array, low: bool,
             leave_out: FrozenSet[str]) -> jax.Array:
    """The routed experts' and the shared expert's sum (departure 1: the
    routed sum over the held experts)."""
    first = m.get("held_first", 0) + ("held_shifted" in leave_out)
    held = p["w_gate"].shape[0]
    w = route(m, p, u, low, leave_out)

    def one(acc, args):                     # departure 4: an expert at a time
        pe, we = args
        return acc + we[:, None] * _swiglu(pe, u, low), None

    stacked = {name: p[name] for name in ("w_gate", "w_up", "w_down")}
    routed, _ = lax.scan(one, jnp.zeros_like(u), (stacked, w[:, first:first + held].T))
    return routed + _swiglu(p["shared"], u, low)


def block(m: Mapping[str, Any], p: Mapping[str, Any], x: jax.Array, layer: int,
          low: bool = False, leave_out: FrozenSet[str] = frozenset()) -> jax.Array:
    """One layer on one sequence, x [S, hidden] float32."""
    eps = m["rms_norm_eps"]
    posted = "no_post_norms" not in leave_out
    a = _attention(m, p, _rms(x, p["ln1"], eps), layer, low, leave_out)
    x = x + (_rms(a, p["ln1p"], eps) if posted else a)
    u = _rms(x, p["ln2"], eps)
    f = _experts(m, p["mlp"], u, low, leave_out) if "mlp" in p else _swiglu(p, u, low)
    return x + (_rms(f, p["ln2p"], eps) if posted else f)


def expert_layer(m: Mapping[str, Any], p: Mapping[str, Any], u: jax.Array) -> jax.Array:
    """``ff(u)`` of an expert block's ``mlp`` params (the share test's oracle)."""
    with jax.default_matmul_precision("highest"):
        return _experts(m, p, u.astype(F32), False, frozenset())


class ServeReference:
    """One full forward over a prompt with its served tokens: the logits of
    the positions that chose a token.  ``reference.ServeReference``'s
    interface; a block at a time (one jitted call a block, so one block's
    float32 copies are alive at once).  A sequence is padded to the next
    multiple of ``bucket`` positions, not to the longest the pool holds: one
    program a bucket in use, and a short request costs what it is."""

    def __init__(self, m: Mapping[str, Any], flat: Sequence[Dict[str, Any]], length: int,
                 rows: int, low: bool = False, leave_out: Sequence[str] = (),
                 bucket: int = 4096) -> None:
        unknown = sorted(set(leave_out) - set(FAULTS))
        if unknown:
            raise ValueError(f"leave_out {unknown}: {FAULTS} are computed")
        self.flat, self.length, self.rows, self.bucket = list(flat), length, rows, bucket
        routers = [p["mlp"]["router"].shape[1] for p in self.flat[1:-1] if "mlp" in p]
        if any(n != published(m, "num_experts") for n in routers):
            raise ValueError("the routers do not score the published number of experts")
        out = frozenset(leave_out)
        scale = float(m["hidden_size"]) ** 0.5 if m["mup_enabled"] else 1.0
        self._embed = jax.jit(lambda p, tokens: p["table"][tokens].astype(F32) * scale)
        self._block = jax.jit(
            lambda p, x, layer: block(m, p, x, layer, low, out), static_argnums=2)

        def head(p, x, start, rows):
            x = lax.dynamic_slice_in_dim(x, start, rows)
            return _mm(_rms(x, p["scale"], m["rms_norm_eps"]), p["w"], low)

        self._head = jax.jit(head, static_argnums=3)

    def _hidden(self, tokens: np.ndarray) -> jax.Array:
        x = self._embed(self.flat[0], np.asarray(tokens, np.int32))
        for layer, params in enumerate(self.flat[1:-1]):
            x = self._block(params, x, layer)
        return x

    def _padded(self, n: int) -> int:
        return min(-(-n // self.bucket) * self.bucket, max(self.length, n))

    def chosen_logits(self, prompt: np.ndarray, served: np.ndarray) -> np.ndarray:
        """Logits [len(served), vocab] at the positions that chose each
        served token (position len(prompt)-1+i chose served[i])."""
        n, p = len(served), len(prompt)
        if not (0 < n <= self.rows and p + n <= self.length):
            raise ValueError(f"request of {p}+{n} tokens does not fit the reference")
        length = self._padded(p + n)
        rows = min(self.rows, length)
        tokens = np.zeros((length,), np.int32)
        tokens[:p + n] = np.concatenate([prompt, served])
        start = min(p - 1, length - rows)
        with jax.default_matmul_precision("highest"):
            out = np.asarray(self._head(self.flat[-1], self._hidden(tokens), np.int32(start), rows))
        return out[p - 1 - start:p - 1 - start + n]

    def all_logits(self, tokens: np.ndarray) -> np.ndarray:
        """Logits [len(tokens), vocab] of every position (the tests' oracle)."""
        with jax.default_matmul_precision("highest"):
            return np.asarray(self._head(self.flat[-1], self._hidden(tokens), np.int32(0),
                                         len(tokens)))
