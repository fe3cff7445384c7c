"""Weights of a Solar-Open2 (``model_type: solar_open2``) configuration, from
the configuration file's draw, made on the device in one jitted call and
handed to the program and to the plain reference alike.

A published layer is two blocks, a mixer and then experts (``pattern``: ``*E``
for a layer of ``gqa_layers``, ``KE`` for the others).  The flat list is
``[embedding, block 0 .. block 2 depth - 1, head]``; block ``i`` is what
letter ``i`` of the pattern says, each with its one pre-norm ``ln1``:

* ``K``, the gated delta-rule mixer (KDA): ``in_proj [hidden, 3 H d]`` (q, k
  and v), ``conv_w [conv_kernel, 3 H d]`` (tap ``conv_kernel - 1`` on the
  current position, no bias), the decay's ``f_a [hidden, rank]`` and ``f_b
  [rank, H d]`` with ``dt_bias [H d]`` and ``A_log [H]`` (float32),
  ``b_proj [hidden, H]``, the output gate's ``g_a [hidden, rank]`` and ``g_b
  [rank, H d]``, ``norm [d]`` (float32, the output norm's scale, shared by
  the heads) and ``out_proj [H d, hidden]``;
* ``*``, gated attention: ``wq``, ``wk``, ``wv``, ``wg`` (the output gate,
  ``[hidden, heads x head_dim]``) and ``wo``;
* ``E``, the expert layer, under ``mlp``: a float32 ``router`` over ALL the
  published experts and its float32 ``router_bias`` (the record's
  ``e_score_correction_bias``), the HELD experts' SwiGLU ``w_gate`` / ``w_up``
  / ``w_down`` (``n_routed_experts`` of them in the file, as cut) and the
  ``shared`` expert's.

Each matrix is drawn in the served type with standard deviation ``fan_in **
-0.5``, the embedding's rows with unit variance; norm scales are ones.  The
mixer's own parameters follow Kimi Linear's initialisation
(``configs/solar-open2.json`` ``assumed``): ``exp(A_log)`` uniform in [1, 16]
a head, ``dt_bias`` the inverse softplus of a step drawn log-uniform in
[``draw.dt_min``, ``draw.dt_max``] a channel (floored at ``draw.dt_floor``),
the conv's taps uniform in ``+-conv_kernel ** -0.5``.  The selection's bias is
drawn with standard deviation ``draw.router_bias_std``.

As ``weights_nemotron.py`` does, ``draw.seed`` names ONE draw of the weights
and of the token ids, and a run's ``--seed`` RELABELS it: a permutation of the
vocabulary and one of the hidden units (every matrix's axis over them), both
symmetries of the model, so every seed routes the same tokens to the same
experts, up to the order in which sums round.
"""

from __future__ import annotations

import functools
from typing import Any, Dict, List, Mapping

import jax
import jax.numpy as jnp
import numpy as np

from chipbench.weights import DTYPES, seed_key
from chipbench.weights_axk1 import published, swiglu_shapes
from chipbench.weights_mellum2 import _relabelling
from chipbench.weights_nemotron import _freeze

# The axis of each drawn matrix that runs over the hidden units (the others,
# f_b and g_b, run over none).
HIDDEN = {"wq": -2, "wk": -2, "wv": -2, "wg": -2, "wo": -1, "in_proj": -2, "f_a": -2,
          "b_proj": -2, "g_a": -2, "out_proj": -1, "router": -2, "w_gate": -2, "w_up": -2,
          "w_down": -1}


def pattern(m: Mapping[str, Any]) -> str:
    """Two letters a published layer: ``*E`` in ``gqa_layers``, ``KE`` else."""
    full = set(m["gqa_layers"])
    return "".join("*E" if i in full else "KE" for i in range(m["num_hidden_layers"]))


def kda_sizes(m: Mapping[str, Any]) -> Dict[str, int]:
    """The delta-rule mixer's widths: heads, head size, ``width = H d`` (of q,
    of k, of v), the conv's taps and the low-rank projections' rank (the head
    size, as Kimi Linear's)."""
    la = m["linear_attn_config"]
    heads, d = la["num_heads"], la["head_dim"]
    return {"heads": heads, "head_dim": d, "width": heads * d,
            "conv_kernel": la["short_conv_kernel_size"], "rank": d}


def block_shapes(m: Mapping[str, Any], letter: str) -> Dict[str, Any]:
    """Matrix shapes of a block of ``letter`` (nested as the block's params are)."""
    d = m["hidden_size"]
    if letter == "K":
        s = kda_sizes(m)
        w, r = s["width"], s["rank"]
        return {"in_proj": (d, 3 * w), "f_a": (d, r), "f_b": (r, w), "b_proj": (d, s["heads"]),
                "g_a": (d, r), "g_b": (r, w), "out_proj": (w, d)}
    if letter == "*":
        hd = m["head_dim"]
        q, kv = m["num_attention_heads"] * hd, m["num_key_value_heads"] * hd
        return {"wq": (d, q), "wk": (d, kv), "wv": (d, kv), "wg": (d, q), "wo": (q, d)}
    if letter != "E":
        raise ValueError(f"pattern letter {letter!r}: K, E and * are drawn")
    width = m["moe_intermediate_size"]
    return {"mlp": dict(swiglu_shapes(d, width, (m["n_routed_experts"],)),
                        router=(d, published(m, "n_routed_experts")),
                        shared=swiglu_shapes(d, m["n_shared_experts"] * width))}


def _kda_own(k: jax.Array, spec: tuple, dt: Any) -> Dict[str, jax.Array]:
    heads, d, kernel, t_min, t_max, t_floor = spec
    ks = jax.random.split(k, 3)
    bound = kernel ** -0.5
    step = jnp.exp(jax.random.uniform(ks[0], (heads * d,), jnp.float32,
                                      np.log(t_min), np.log(t_max)))
    step = jnp.maximum(step, t_floor)
    return {
        "conv_w": jax.random.uniform(ks[1], (kernel, 3 * heads * d), jnp.float32,
                                     -bound, bound).astype(dt),
        "dt_bias": step + jnp.log(-jnp.expm1(-step)),      # softplus^-1(step)
        "A_log": jnp.log(jax.random.uniform(ks[2], (heads,), jnp.float32, 1.0, 16.0)),
        "norm": jnp.ones((d,), jnp.float32),
    }


@functools.partial(jax.jit, static_argnames=("spec",))
def _make(key: jax.Array, relabel: jax.Array, spec: tuple) -> List[Dict[str, Any]]:
    d, vocab, dtype, bias_std, kda_spec, letters, blocks = spec
    dt = DTYPES[dtype]
    rows, units = _relabelling(relabel, vocab, d)

    def draw(k, shapes):
        out = {}
        for sub, (name, shape) in zip(jax.random.split(k, len(shapes)), shapes):
            if isinstance(shape[0], tuple):                 # a nested group
                out[name] = draw(sub, shape)
                continue
            kind = jnp.float32 if name == "router" else dt
            w = (shape[-2] ** -0.5 * jax.random.normal(sub, shape, kind)).astype(kind)
            out[name] = jnp.take(w, units, axis=HIDDEN[name]) if name in HIDDEN else w
        if "router" in out:
            out["router_bias"] = bias_std * jax.random.normal(
                jax.random.fold_in(k, 1), (out["router"].shape[1],), jnp.float32)
        return out

    keys = jax.random.split(key, len(letters) + 2)
    ones = jnp.ones((d,), jnp.float32)
    flat: List[Dict[str, Any]] = [
        {"table": jax.random.normal(keys[0], (vocab, d), dt)[rows][:, units]}]
    for k, letter, shapes in zip(keys[1:-1], letters, blocks):
        block = dict(draw(k, shapes), ln1=ones)
        if letter == "K":
            block.update(_kda_own(jax.random.fold_in(k, 2), kda_spec, dt))
        flat.append(block)
    flat.append({"scale": ones,
                 "w": (d ** -0.5 * jax.random.normal(keys[-1], (d, vocab), dt)
                       ).astype(dt)[units][:, rows]})
    return flat


def make_flat(m: Mapping[str, Any], seed: int) -> List[Dict[str, Any]]:
    """[embedding, block 0 .. block 2 depth - 1, head] on the default device:
    the file's draw (``m['draw']['seed']``) under the seed's relabelling."""
    letters = pattern(m)
    blocks = tuple(_freeze(block_shapes(m, c)) for c in letters)
    s, dr = kda_sizes(m), m["draw"]
    kda = (s["heads"], s["head_dim"], s["conv_kernel"], dr["dt_min"], dr["dt_max"],
           dr["dt_floor"])
    spec = (m["hidden_size"], m["vocab_size"], m["torch_dtype"], dr["router_bias_std"],
            kda, letters, blocks)
    return _make(seed_key(dr["seed"]), seed_key(seed), spec)

