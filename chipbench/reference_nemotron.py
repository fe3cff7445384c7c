"""The plain reference of Nemotron-3-Nano's forward (``model_type:
nemotron_h``; source
https://huggingface.co/nvidia/NVIDIA-Nemotron-3-Nano-30B-A3B-BF16/blob/main/config.json):
straightforward ``jax.numpy``, float32 arithmetic at ``highest`` precision,
all positions of a sequence at once, no cache, no pool, no kernel.  It imports
nothing of the program; the weights are ``weights_nemotron.make_flat``'s.

The equations (``x`` is ``[t, hidden]``; RMS norms with a learned scale, eps
``layer_norm_epsilon``):

* embedding: ``x = E[ids]``.
* a layer: ``x = x + F(N(x))`` (``ln1``), ``F`` what its letter of
  ``hybrid_override_pattern`` says: ``M`` the Mamba-2 mixer, ``E`` the expert
  layer, ``*`` attention.  Nothing pairs attention with a feed-forward.
* mixer: ``[z | xBC | dt] = u W_in`` (widths ``d_inner = mamba_num_heads x
  mamba_head_dim``, ``d_inner + 2 n_groups ssm_state_size``, ``mamba_num_heads``);
  ``xBC = silu(causal depthwise conv of conv_kernel taps (zero before the
  first position) + b)``, split into ``x [heads, head_dim]``, ``B`` and ``C``
  ``[n_groups, state]``, head ``h`` reading group ``h // (heads / n_groups)``;
  ``dt = softplus(dt + dt_bias)``; then, POSITION BY POSITION, ``S_t[h] =
  exp(-dt_t[h] exp(A_log[h])) S_{t-1}[h] + dt_t[h] x_t[h] (x) B_t[g]`` from
  ``S_{-1} = 0`` and ``y_t[h] = S_t[h] C_t[g] + D[h] x_t[h]`` (a plain scan
  over the positions: the recurrence itself, not a chunked form); ``y = RMSNorm
  over groups of d_inner / n_groups (y * silu(z)) * norm``; ``out = y W_out``.
* attention: ``q = u Wq``, ``k = u Wk``, ``v = u Wv``, nothing rotated, causal
  over the whole context; ``out = softmax(q k^T / sqrt(head_dim)) v Wo``.
* expert layer: ``s = sigmoid(u Wr)`` over all the published experts in
  float32; the ``num_experts_per_tok`` chosen are the largest of ``s + b``
  (``router_bias``); weights are ``s`` at the chosen, divided by their sum +
  1e-20 (``norm_topk_prob``), times ``routed_scaling_factor``; ``y = shared(u) +
  sum over the chosen experts THAT ARE HELD of w_e expert_e(u)``, every expert
  ``down(relu(up u) ** 2)``.
* final RMS norm (``scale``), untied head over the slice of the vocabulary.

Departures from the published model, each noted where it is made:

1. **The chip's share.**  The configuration holds experts ``[held_first,
   held_first + n_routed_experts)`` of the published count; the router scores
   all of them and normalises over all the chosen; the routed sum runs over
   the chosen experts that are HELD (``_experts``).  With every expert held
   this is the model's layer.
2. State is STORED as the configuration states (bfloat16 weights, float32
   norm scales, router, bias and the mixer's ``A_log``, ``dt_bias``, ``D``);
   every product and sum, and the recurrent state, is float32.
3. Attention runs a block of queries at a time (``reference_trinity._attend``)
   and the routed sum an expert at a time, so that a 4096-position sequence
   fits beside the weights; the arithmetic is the same.

``low=True`` is the control of ``reference.py``: the operands of every weight
product rounded to four significant bits.  ``leave_out`` names mechanisms left
out or altered (``FAULTS``): what ``limits_nemotron.py`` plants in the
reference's place to read what each limit refuses.
"""

from __future__ import annotations

from typing import Any, Dict, FrozenSet, Mapping, Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from chipbench.reference import F32, HIGHEST, _mm, _rms
from chipbench.reference_trinity import _attend
from chipbench.weights_axk1 import published

# Mechanisms ``leave_out`` may name: the mixer's D skip, its norm over groups
# (``full_norm``: over all of d_inner), the state kept in bfloat16 between
# positions, relu in place of relu**2, the routing's scale and bias, and the
# held experts shifted by one.
FAULTS = ("no_D", "full_norm", "state_bf16", "relu", "no_route_scale", "no_bias",
          "held_shifted")


def _mixer(m: Mapping[str, Any], p: Mapping[str, jax.Array], u: jax.Array, low: bool,
           leave_out: FrozenSet[str]) -> jax.Array:
    t = u.shape[0]
    heads, hd, groups, n = (m["mamba_num_heads"], m["mamba_head_dim"], m["n_groups"],
                            m["ssm_state_size"])
    inner, k = heads * hd, m["conv_kernel"]
    zxbcdt = _mm(u, p["in_proj"], low)
    z, xbc, dt = (zxbcdt[:, :inner], zxbcdt[:, inner:-heads], zxbcdt[:, -heads:])
    padded = jnp.pad(xbc, ((k - 1, 0), (0, 0)))
    w = p["conv_w"].astype(F32)
    xbc = jax.nn.silu(sum(padded[j:j + t] * w[j] for j in range(k))
                      + p["conv_b"].astype(F32))
    x = xbc[:, :inner].reshape(t, heads, hd)
    B = jnp.repeat(xbc[:, inner:inner + groups * n].reshape(t, groups, n), heads // groups, 1)
    C = jnp.repeat(xbc[:, inner + groups * n:].reshape(t, groups, n), heads // groups, 1)
    dt = jax.nn.softplus(dt + p["dt_bias"])
    a = -jnp.exp(p["A_log"])
    d = jnp.zeros_like(p["D"]) if "no_D" in leave_out else p["D"]

    def step(state, inp):
        x_t, b_t, c_t, dt_t = inp
        state = (jnp.exp(dt_t * a)[:, None, None] * state
                 + (dt_t[:, None] * x_t)[:, :, None] * b_t[:, None, :])
        if "state_bf16" in leave_out:
            state = state.astype(jnp.bfloat16).astype(F32)
        return state, jnp.einsum("hpn,hn->hp", state, c_t, precision=HIGHEST) + d[:, None] * x_t

    _, y = lax.scan(step, jnp.zeros((heads, hd, n), F32), (x, B, C, dt))
    y = y.reshape(t, inner) * jax.nn.silu(z)
    size = inner if "full_norm" in leave_out else inner // groups
    y = y.reshape(t, -1, size)
    y = y * lax.rsqrt(jnp.mean(y * y, -1, keepdims=True) + m["layer_norm_epsilon"])
    return _mm(y.reshape(t, inner) * p["norm"], p["out_proj"], low)


def _attention(m: Mapping[str, Any], p: Mapping[str, jax.Array], u: jax.Array,
               low: bool) -> jax.Array:
    s, hd = u.shape[0], m["head_dim"]
    q = _mm(u, p["wq"], low).reshape(s, -1, hd)
    k = _mm(u, p["wk"], low).reshape(s, -1, hd)
    v = _mm(u, p["wv"], low).reshape(s, -1, hd)
    return _mm(_attend(q, k, v, None), p["wo"], low)


def _relu2(p: Mapping[str, jax.Array], u: jax.Array, low: bool, relu_only: bool) -> jax.Array:
    h = jax.nn.relu(_mm(u, p["w_up"], low))
    return _mm(h if relu_only else h * h, p["w_down"], low)


def route(m: Mapping[str, Any], p: Mapping[str, Any], u: jax.Array, low: bool = False,
          leave_out: FrozenSet[str] = frozenset()) -> jax.Array:
    """``w [t, all experts]``: token ``t``'s weight on expert ``e`` (0 where
    ``e`` was not chosen)."""
    scores = jax.nn.sigmoid(_mm(u, p["router"], low))
    chosen_by = scores if "no_bias" in leave_out else scores + p["router_bias"]
    idx = lax.top_k(chosen_by, m["num_experts_per_tok"])[1]
    top = jnp.take_along_axis(scores, idx, -1)
    if m["norm_topk_prob"]:
        top = top / (jnp.sum(top, -1, keepdims=True) + 1e-20)
    if "no_route_scale" not in leave_out:
        top = m["routed_scaling_factor"] * top
    return jnp.zeros_like(scores).at[jnp.arange(u.shape[0])[:, None], idx].set(top)


def _experts(m: Mapping[str, Any], p: Mapping[str, Any], u: jax.Array, low: bool,
             leave_out: FrozenSet[str]) -> jax.Array:
    """The routed experts' and the shared expert's sum (departure 1: the
    routed sum over the held experts)."""
    first = m.get("held_first", 0) + ("held_shifted" in leave_out)
    held = p["w_up"].shape[0]
    relu_only = "relu" in leave_out
    w = route(m, p, u, low, leave_out)

    def one(acc, args):                     # departure 3: an expert at a time
        pe, we = args
        return acc + we[:, None] * _relu2(pe, u, low, relu_only), None

    stacked = {name: p[name] for name in ("w_up", "w_down")}
    routed, _ = lax.scan(one, jnp.zeros_like(u), (stacked, w[:, first:first + held].T))
    return routed + _relu2(p["shared"], u, low, relu_only)


def block(m: Mapping[str, Any], p: Mapping[str, Any], x: jax.Array, kind: str,
          low: bool = False, leave_out: FrozenSet[str] = frozenset()) -> jax.Array:
    """One layer of ``kind`` (its letter of the pattern) on one sequence,
    x [S, hidden] float32."""
    u = _rms(x, p["ln1"], m["layer_norm_epsilon"])
    if kind == "M":
        return x + _mixer(m, p, u, low, leave_out)
    if kind == "E":
        return x + _experts(m, p["mlp"], u, low, leave_out)
    return x + _attention(m, p, u, low)


def expert_layer(m: Mapping[str, Any], p: Mapping[str, Any], u: jax.Array) -> jax.Array:
    """``F(u)`` of an expert block's ``mlp`` params (the share test's oracle)."""
    with jax.default_matmul_precision("highest"):
        return _experts(m, p, u.astype(F32), False, frozenset())


class ServeReference:
    """One full forward over a prompt with its served tokens: the logits of
    the positions that chose a token.  ``reference_trinity.ServeReference``'s
    interface; a block at a time (one jitted call a block, one program a kind
    of block and a length), a sequence padded to the next multiple of
    ``bucket`` positions."""

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
        self._kinds = m["hybrid_override_pattern"]
        self._block = jax.jit(
            lambda p, x, kind: block(m, p, x, kind, low, out), static_argnums=2)

        def head(p, x, start, rows):
            x = lax.dynamic_slice_in_dim(x, start, rows)
            return _mm(_rms(x, p["scale"], m["layer_norm_epsilon"]), p["w"], low)

        self._head = jax.jit(head, static_argnums=3)

    def _hidden(self, tokens: np.ndarray) -> jax.Array:
        x = self._embed(self.flat[0], np.asarray(tokens, np.int32))
        for kind, params in zip(self._kinds, self.flat[1:-1]):
            x = self._block(params, x, kind)
        return x

    def _padded(self, n: int) -> int:
        return min(-(-n // self.bucket) * self.bucket, max(self.length, n))

    def chosen_logits(self, prompt: np.ndarray, served: np.ndarray) -> np.ndarray:
        """Logits [len(served), vocab] at the positions that chose each
        served token (position len(prompt)-1+i chose served[i]).  Padding
        lies AFTER the sequence: causal, it changes nothing before it."""
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
