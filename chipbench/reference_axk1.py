"""The plain reference of A.X-K1's forward (``model_type: axk1``; source
https://huggingface.co/skt/A.X-K1/blob/main/config.json): straightforward
``jax.numpy``, float32 arithmetic at ``highest`` precision, all positions of a
sequence at once, no cache, attention NOT absorbed.  It imports nothing of the
program; the weights are ``weights_axk1.make_flat``'s.

The equations (``d`` hidden, ``H`` heads, every norm RMS with a learned scale;
block ``x' = x + Attn(norm1(x))``, ``y = x' + FF(norm2(x'))``):

* attention, per token with normed hidden ``h``: ``c_q = norm_q(h W_qa)``;
  ``[q_nope | q_pe] = c_q W_qb`` as ``H`` heads; ``[c_kv | k_pe] = h W_kva``,
  ``c_kv = norm_kv(c_kv)``, ``k_pe`` ONE head shared by all ``H``; ``q_pe`` and
  ``k_pe`` rotated by position under YaRN's frequencies (``inv_freq`` below);
  ``[k_nope | v] = c_kv W_kvb`` as ``H`` heads; ``score = (q_nope . k_nope +
  q_pe . k_pe) * (n + r) ** -0.5 * yarn_mscale(factor, mscale_all_dim) ** 2``,
  causal, softmax; output ``concat_h(p v_h) W_o``.  No bias anywhere.
* feed-forward of the first ``first_k_dense_replace`` blocks: a SwiGLU of
  ``intermediate_size``.  Of the others, with ``u = norm2(x')``: ``s =
  sigmoid(u W_g)`` over all the published experts; the ``num_experts_per_tok``
  largest are selected (``topk_method: none``: over all experts, no groups, no
  bias); ``w_e = routed_scaling_factor * s_e / sum of the selected s``;
  ``FF(u) = sum over selected e of w_e E_e(u) + S(u)``, ``E_e`` and ``S`` SwiGLUs
  of ``moe_intermediate_size``.

Departures from the published model, each noted where it is made:

1. **The chip's share.**  The configuration holds experts ``[held_first,
   held_first + n_routed_experts)`` of the published count.  The router scores
   all of them and normalises over all the selected; the routed sum runs over
   the selected experts that are HELD, and what the absent ones would add is
   left out (``_experts``).  With every expert held this is the model's layer.
2. **Rotary layout.**  The two halves of the rotary dims rotate together
   (``x_i`` with ``x_{i + r/2}``), as in ``reference.py``; the published code
   pairs ``x_{2i}`` with ``x_{2i+1}`` after the same projection.  The two differ
   by one fixed permutation of ``W_qb``'s and ``W_kva``'s rotary columns, and
   the scores, which only see ``q_pe . k_pe``, are the same for weights drawn
   from a seed (``_rope``).
3. State is STORED as the configuration states (bfloat16 weights, float32 norm
   scales and router) and every product and sum is computed in float32.
4. Attention runs a block of heads at a time and the routed sum an expert at a
   time, so that a 4096-position sequence fits beside the weights; the
   arithmetic is the same.

``low=True`` is the control of ``reference.py``: the operands of every weight
product rounded to four significant bits.
"""

from __future__ import annotations

import math
from typing import Any, Dict, Mapping, Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from chipbench.reference import F32, HIGHEST, _mm, _rms, widest_gap  # noqa: F401
from chipbench.weights_axk1 import published


def yarn_mscale(scale: float, m: float) -> float:
    return 1.0 if scale <= 1.0 else 0.1 * m * math.log(scale) + 1.0


def inv_freq(m: Mapping[str, Any]) -> np.ndarray:
    """``f_i = theta^(-2i/r)``; under YaRN ``f_i / factor`` where the ramp
    between the correction dims of ``beta_fast`` and ``beta_slow`` is 1,
    ``f_i`` where it is 0, and the mix between."""
    r, theta, y = m["qk_rope_head_dim"], float(m["rope_theta"]), m.get("rope_scaling")
    f = theta ** (-np.arange(0, r, 2, dtype=np.float64) / r)
    if not y:
        return f.astype(np.float32)

    def dim_of(rotations: float) -> float:
        return r * math.log(y["original_max_position_embeddings"]
                            / (rotations * 2.0 * math.pi)) / (2.0 * math.log(theta))

    low = max(math.floor(dim_of(y["beta_fast"])), 0)
    high = min(math.ceil(dim_of(y["beta_slow"])), r - 1)
    ramp = np.clip((np.arange(r // 2) - low) / max(high - low, 0.001), 0.0, 1.0)
    return (f / y["factor"] * ramp + f * (1.0 - ramp)).astype(np.float32)


def score_scale(m: Mapping[str, Any]) -> float:
    scale = (m["qk_nope_head_dim"] + m["qk_rope_head_dim"]) ** -0.5
    y = m.get("rope_scaling")
    if y and y.get("mscale_all_dim"):
        scale *= yarn_mscale(y["factor"], y["mscale_all_dim"]) ** 2
    return scale


def _rope(m: Mapping[str, Any], x: jax.Array) -> jax.Array:
    """x [S, heads, r] at positions 0..S-1 (departure 2: halves together)."""
    half = x.shape[-1] // 2
    ang = jnp.arange(x.shape[0], dtype=F32)[:, None] * jnp.asarray(inv_freq(m))
    y = m.get("rope_scaling")
    amp = yarn_mscale(y["factor"], y["mscale"]) / yarn_mscale(
        y["factor"], y["mscale_all_dim"]) if y else 1.0
    cos, sin = (amp * jnp.cos(ang))[:, None, :], (amp * jnp.sin(ang))[:, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _attention(m: Mapping[str, Any], p: Mapping[str, jax.Array], h: jax.Array,
               low: bool, head_block: int) -> jax.Array:
    s = h.shape[0]
    H, n, r, v = (m["num_attention_heads"], m["qk_nope_head_dim"], m["qk_rope_head_dim"],
                  m["v_head_dim"])
    eps, c = m["rms_norm_eps"], m["kv_lora_rank"]
    q = _mm(_rms(_mm(h, p["wq_a"], low), p["q_norm"], eps), p["wq_b"], low).reshape(s, H, n + r)
    kv_a = _mm(h, p["wkv_a"], low)
    k_pe = _rope(m, kv_a[:, None, c:])[:, 0]                            # [S, r], one head
    kv = _mm(_rms(kv_a[:, :c], p["kv_norm"], eps), p["wkv_b"], low).reshape(s, H, n + v)
    q_pe = _rope(m, q[..., n:])
    seen = jnp.arange(s)[None, :] <= jnp.arange(s)[:, None]
    scale = score_scale(m)

    def heads(args):                        # departure 4: a block of heads at a time
        qn, qp, kn, vv = args               # [S, hb, .]
        sc = (jnp.einsum("shd,thd->hst", qn, kn, precision=HIGHEST)
              + jnp.einsum("shr,tr->hst", qp, k_pe, precision=HIGHEST)) * scale
        prob = jax.nn.softmax(jnp.where(seen[None], sc, -1e30), -1)
        return jnp.einsum("hst,thd->shd", prob, vv, precision=HIGHEST)

    hb = math.gcd(H, head_block)

    def split(x):                           # [S, H, .] -> [H/hb, S, hb, .]
        return x.reshape(s, H // hb, hb, x.shape[-1]).transpose(1, 0, 2, 3)

    out = lax.map(heads, (split(q[..., :n]), split(q_pe), split(kv[..., :n]), split(kv[..., n:])))
    return _mm(out.transpose(1, 0, 2, 3).reshape(s, H * v), p["wo"], low)


def _swiglu(p: Mapping[str, jax.Array], u: jax.Array, low: bool) -> jax.Array:
    return _mm(jax.nn.silu(_mm(u, p["w_gate"], low)) * _mm(u, p["w_up"], low), p["w_down"], low)


def _experts(m: Mapping[str, Any], p: Mapping[str, Any], u: jax.Array, low: bool) -> jax.Array:
    """The routed experts' and the shared expert's sum (departure 1: the
    routed sum over the held experts)."""
    k, first = m["num_experts_per_tok"], m.get("held_first", 0)
    held = p["w_gate"].shape[0]
    if m["scoring_func"] != "sigmoid" or m["topk_method"] != "none":
        raise ValueError("the reference computes sigmoid scores and topk_method 'none'")
    scores = jax.nn.sigmoid(_mm(u, p["router"], low))                   # [S, all experts]
    top, idx = lax.top_k(scores, k)
    weight = top / jnp.sum(top, -1, keepdims=True) if m["norm_topk_prob"] else top
    weight = m["routed_scaling_factor"] * weight
    # w[s, e]: token s's weight on expert e (0 where e was not selected).
    w = jnp.zeros_like(scores).at[jnp.arange(u.shape[0])[:, None], idx].set(weight)

    def one(acc, args):                     # departure 4: an expert at a time
        pe, we = args
        return acc + we[:, None] * _swiglu(pe, u, low), None

    stacked = {name: p[name] for name in ("w_gate", "w_up", "w_down")}
    routed, _ = lax.scan(one, jnp.zeros_like(u), (stacked, w[:, first:first + held].T))
    return routed + _swiglu(p["shared"], u, low)


def block(m: Mapping[str, Any], p: Mapping[str, Any], x: jax.Array, low: bool,
          head_block: int = 4) -> jax.Array:
    """One pre-norm block on one sequence, x [S, hidden] float32."""
    eps = m["rms_norm_eps"]
    x = x + _attention(m, p, _rms(x, p["ln1"], eps), low, head_block)
    u = _rms(x, p["ln2"], eps)
    return x + (_experts(m, p["mlp"], u, low) if "mlp" in p else _swiglu(p, u, low))


def expert_layer(m: Mapping[str, Any], p: Mapping[str, Any], u: jax.Array) -> jax.Array:
    """``FF(u)`` of an expert block's ``mlp`` params (the share test's oracle)."""
    with jax.default_matmul_precision("highest"):
        return _experts(m, p, u.astype(F32), False)


class ServeReference:
    """One full forward over a prompt with its served tokens: the logits of
    the positions that chose a token.  ``reference.ServeReference``'s
    interface; a block at a time (one jitted call a block, so one block's
    float32 copies are alive at once)."""

    def __init__(self, m: Mapping[str, Any], flat: Sequence[Dict[str, Any]],
                 length: int, rows: int, low: bool = False) -> None:
        self.flat, self.length, self.rows = list(flat), length, rows
        routers = [p["mlp"]["router"].shape[1] for p in self.flat[1:-1] if "mlp" in p]
        if any(n != published(m, "n_routed_experts") for n in routers):
            raise ValueError("the routers do not score the published number of experts")
        self._embed = jax.jit(lambda p, tokens: p["table"][tokens].astype(F32))
        self._block = jax.jit(lambda p, x: block(m, p, x, low))

        def head(p, x, start):
            x = lax.dynamic_slice_in_dim(x, start, rows)
            return _mm(_rms(x, p["scale"], m["rms_norm_eps"]), p["w"], low)

        self._head = jax.jit(head)

    def _hidden(self, tokens: np.ndarray) -> jax.Array:
        x = self._embed(self.flat[0], np.asarray(tokens, np.int32))
        for params in self.flat[1:-1]:
            x = self._block(params, x)
        return x

    def chosen_logits(self, prompt: np.ndarray, served: np.ndarray) -> np.ndarray:
        """Logits [len(served), vocab] at the positions that chose each
        served token (position len(prompt)-1+i chose served[i])."""
        n, p = len(served), len(prompt)
        if not (0 < n <= self.rows and p + n <= self.length):
            raise ValueError(f"request of {p}+{n} tokens does not fit the reference")
        tokens = np.zeros((self.length,), np.int32)
        tokens[:p + n] = np.concatenate([prompt, served])
        start = min(p - 1, self.length - self.rows)
        with jax.default_matmul_precision("highest"):
            out = np.asarray(self._head(self.flat[-1], self._hidden(tokens), np.int32(start)))
        return out[p - 1 - start:p - 1 - start + n]

    def all_logits(self, tokens: np.ndarray) -> np.ndarray:
        """Logits [length, vocab] of every position of ``tokens`` (a
        reference built with ``rows == length``; the tests' oracle)."""
        if len(tokens) != self.length or self.rows != self.length:
            raise ValueError("all_logits needs length == rows == len(tokens)")
        with jax.default_matmul_precision("highest"):
            return np.asarray(self._head(self.flat[-1], self._hidden(tokens), np.int32(0)))
