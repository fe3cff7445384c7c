"""The plain reference of Solar-Open2's forward (``model_type: solar_open2``;
source https://huggingface.co/upstage/Solar-Open2-250B/blob/main/config.json):
straightforward ``jax.numpy``, float32 arithmetic at ``highest`` precision,
all positions of a sequence at once, no cache, no pool, no kernel.  It imports
nothing of the program; the weights are ``weights_solar.make_flat``'s.

The equations (``x`` is ``[t, hidden]``; RMS norms with a learned scale, eps
``rms_norm_eps``):

* embedding: ``x = E[ids]``.
* a published layer: ``x = x + Mix(N(x))`` then ``x = x + MoE(N(x))``, two
  blocks of ``weights_solar.pattern`` (``*E`` for a layer of ``gqa_layers``,
  ``KE`` for the others), each with its own norm (``ln1``).
* delta-rule mixer (Kimi Delta Attention; ``configs/solar-open2.json``
  ``assumed``): ``q, k, v = silu(causal depthwise conv of
  short_conv_kernel_size taps, no bias (zero before the first position), of u
  W_in)``, each ``[H, d]``; ``q <- q / |q| d^-1/2``, ``k <- k / |k|`` a head;
  ``log a = -exp(A_log[h]) softplus(u W_fa W_fb + dt_bias)`` a channel;
  ``beta = 2 sigmoid(u W_b)`` a head; then, POSITION BY POSITION from ``S =
  0``: ``S' = Diag(a_t) S``, ``S = S' + beta_t k_t (v_t - S'^T k_t)^T``,
  ``o_t = S^T q_t`` (a plain scan over the positions: the recurrence itself,
  not a chunked form); ``y = RMSNorm over each head's d (o) * norm *
  sigmoid(u W_ga W_gb)``; ``out = y W_out``.
* attention: ``q = u Wq``, ``k = u Wk``, ``v = u Wv``, nothing rotated
  (``use_rope: false``), causal over the whole context; ``out = (softmax(q k^T
  / sqrt(head_dim)) v * sigmoid(u Wg)) Wo`` (``use_gqa_gate``).
* expert layer: ``s = sigmoid(u Wr)`` over all the published experts in
  float32; the ``num_experts_per_tok`` chosen are the largest of ``s + b``
  (``router_bias``); weights are ``s`` at the chosen, divided by their sum
  (``norm_topk_prob``), times ``routed_scaling_factor``; ``y = shared(u) +
  sum over the chosen experts THAT ARE HELD of w_e expert_e(u)``, every expert
  ``down(silu(gate u) * up u)``.
* final RMS norm (``scale``), untied head over the slice of the vocabulary.

Departures from the published model, each noted where it is made:

1. **The chip's share.**  The configuration holds experts ``[held_first,
   held_first + n_routed_experts)`` of the published count; the router scores
   all of them and normalises over all the chosen; the routed sum runs over
   the chosen experts that are HELD (``reference_nemotron.route`` and the sum
   in ``_experts``).  With every expert held this is the model's layer.
2. State is STORED as the configuration states (bfloat16 weights; float32
   norm scales, router, bias and the mixer's ``A_log`` and ``dt_bias``); every
   product and sum, and the recurrent state, is float32, a layer's weights
   cast to float32 as its block runs (one jitted call a block).
3. Attention runs a block of queries at a time (``reference_trinity._attend``)
   and the routed sum an expert at a time, so that a 4096-position sequence
   fits beside the program's weights; the arithmetic is the same.

``low=True`` is the control of ``reference.py``: the operands of every weight
product rounded to four significant bits.  ``leave_out`` names mechanisms left
out or altered (``FAULTS``): what ``limits_solar.py`` plants in the reference's
place to read what each limit refuses.
"""

from __future__ import annotations

from typing import Any, Dict, FrozenSet, Mapping, Sequence

import jax
import jax.numpy as jnp
from jax import lax

from chipbench import reference_nemotron
from chipbench.reference import F32, HIGHEST, _mm, _rms
from chipbench.reference_trinity import _attend, _swiglu
from chipbench.weights_axk1 import published
from chipbench.weights_solar import kda_sizes, pattern

# Mechanisms ``leave_out`` may name: beta without the factor 2 (no negative
# eigenvalues), the decay averaged over a head's channels (one decay a head),
# k not L2-normed, the full layer's output gate left out.
FAULTS = ("beta_no_neg", "decay_per_head", "no_k_l2norm", "no_attn_gate")
L2_EPS = 1e-6


def _l2(x: jax.Array) -> jax.Array:
    return x * lax.rsqrt(jnp.sum(x * x, -1, keepdims=True) + L2_EPS)


def _kda(m: Mapping[str, Any], p: Mapping[str, jax.Array], u: jax.Array, low: bool,
         leave_out: FrozenSet[str]) -> jax.Array:
    t, s = u.shape[0], kda_sizes(m)
    H, d, k = s["heads"], s["head_dim"], s["conv_kernel"]
    padded = jnp.pad(_mm(u, p["in_proj"], low), ((k - 1, 0), (0, 0)))
    w = p["conv_w"].astype(F32)
    qkv = jax.nn.silu(sum(padded[j:j + t] * w[j] for j in range(k)))
    q, kk, v = (x.reshape(t, H, d) for x in jnp.split(qkv, 3, axis=-1))
    q = _l2(q) * d ** -0.5
    if "no_k_l2norm" not in leave_out:
        kk = _l2(kk)
    f = _mm(_mm(u, p["f_a"], low), p["f_b"], low).reshape(t, H, d)
    log_a = -jnp.exp(p["A_log"])[:, None] * jax.nn.softplus(f + p["dt_bias"].reshape(H, d))
    if "decay_per_head" in leave_out:
        log_a = jnp.broadcast_to(jnp.mean(log_a, -1, keepdims=True), log_a.shape)
    beta = jax.nn.sigmoid(_mm(u, p["b_proj"], low))
    if m["kda_allow_neg_eigval"] and "beta_no_neg" not in leave_out:
        beta = 2.0 * beta

    def step(S, inp):
        q_t, k_t, v_t, la_t, b_t = inp
        S = jnp.exp(la_t)[:, :, None] * S
        old = jnp.einsum("hkv,hk->hv", S, k_t, precision=HIGHEST)
        S = S + b_t[:, None, None] * k_t[:, :, None] * (v_t - old)[:, None, :]
        return S, jnp.einsum("hkv,hk->hv", S, q_t, precision=HIGHEST)

    _, o = lax.scan(step, jnp.zeros((H, d, d), F32), (q, kk, v, log_a, beta))
    y = o * lax.rsqrt(jnp.mean(o * o, -1, keepdims=True) + m["rms_norm_eps"]) * p["norm"]
    gate = jax.nn.sigmoid(_mm(_mm(u, p["g_a"], low), p["g_b"], low))
    return _mm(y.reshape(t, H * d) * gate, p["out_proj"], low)


def _attention(m: Mapping[str, Any], p: Mapping[str, jax.Array], u: jax.Array, low: bool,
               leave_out: FrozenSet[str]) -> jax.Array:
    s, hd = u.shape[0], m["head_dim"]
    q = _mm(u, p["wq"], low).reshape(s, -1, hd)
    k = _mm(u, p["wk"], low).reshape(s, -1, hd)
    v = _mm(u, p["wv"], low).reshape(s, -1, hd)
    out = _attend(q, k, v, None)
    if m["use_gqa_gate"] and "no_attn_gate" not in leave_out:
        out = out * jax.nn.sigmoid(_mm(u, p["wg"], low))
    return _mm(out, p["wo"], low)


def _experts(m: Mapping[str, Any], p: Mapping[str, Any], u: jax.Array, low: bool) -> jax.Array:
    """The routed experts' and the shared expert's sum (departure 1: the
    routed sum over the held experts)."""
    first, held = m.get("held_first", 0), p["w_up"].shape[0]
    w = reference_nemotron.route(m, p, u, low)

    def one(acc, args):                     # departure 3: an expert at a time
        pe, we = args
        return acc + we[:, None] * _swiglu(pe, u, low), None

    stacked = {name: p[name] for name in ("w_gate", "w_up", "w_down")}
    routed, _ = lax.scan(one, jnp.zeros_like(u), (stacked, w[:, first:first + held].T))
    return routed + _swiglu(p["shared"], u, low)


def block(m: Mapping[str, Any], p: Mapping[str, Any], x: jax.Array, kind: str,
          low: bool = False, leave_out: FrozenSet[str] = frozenset()) -> jax.Array:
    """One block of ``kind`` (its letter of the pattern) on one sequence,
    x [S, hidden] float32."""
    u = _rms(x, p["ln1"], m["rms_norm_eps"])
    if kind == "K":
        return x + _kda(m, p, u, low, leave_out)
    if kind == "E":
        return x + _experts(m, p["mlp"], u, low)
    return x + _attention(m, p, u, low, leave_out)


def expert_layer(m: Mapping[str, Any], p: Mapping[str, Any], u: jax.Array) -> jax.Array:
    """``F(u)`` of an expert block's ``mlp`` params (the share test's oracle)."""
    with jax.default_matmul_precision("highest"):
        return _experts(m, p, u.astype(F32), False)


class ServeReference(reference_nemotron.ServeReference):
    """One full forward over a prompt with its served tokens: the logits of
    the positions that chose a token (``reference_nemotron.ServeReference``'s
    reading of them, a block at a time)."""

    def __init__(self, m: Mapping[str, Any], flat: Sequence[Dict[str, Any]], length: int,
                 rows: int, low: bool = False, leave_out: Sequence[str] = (),
                 bucket: int = 2048) -> None:
        unknown = sorted(set(leave_out) - set(FAULTS))
        if unknown:
            raise ValueError(f"leave_out {unknown}: {FAULTS} are computed")
        self.flat, self.length, self.rows, self.bucket = list(flat), length, rows, bucket
        routers = [p["mlp"]["router"].shape[1] for p in self.flat[1:-1] if "mlp" in p]
        if any(n != published(m, "n_routed_experts") for n in routers):
            raise ValueError("the routers do not score the published number of experts")
        out = frozenset(leave_out)
        self._embed = jax.jit(lambda p, tokens: p["table"][tokens].astype(F32))
        self._kinds = pattern(m)
        self._block = jax.jit(
            lambda p, x, kind: block(m, p, x, kind, low, out), static_argnums=2)

        def head(p, x, start, rows):
            x = lax.dynamic_slice_in_dim(x, start, rows)
            return _mm(_rms(x, p["scale"], m["rms_norm_eps"]), p["w"], low)

        self._head = jax.jit(head, static_argnums=3)


__all__ = ["FAULTS", "ServeReference", "block", "expert_layer"]
