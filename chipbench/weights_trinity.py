"""Weights of a Trinity (``model_type: afmoe``) configuration from the seed,
made on the device in one jitted call and handed to the program and to the
plain reference alike.

The flat list is ``[embedding, block 0 .. block depth-1, head]``.  Every block
has gated grouped-query attention with a per-head norm on q and k and a norm
on each branch's input AND output: ``ln1``, ``wq``, ``wk``, ``wv``, ``wg``
(the output gate), ``wo``, ``qn``, ``kn``, ``ln1p``, ``ln2``, ``ln2p``.  The
first ``num_dense_layers`` blocks have the dense SwiGLU (``w_gate``, ``w_up``,
``w_down`` of ``intermediate_size``); the others carry ``mlp``: a float32
``router`` over ALL the published experts, the selection's float32
``router_bias`` over the same, the HELD experts' stacked SwiGLUs
(``num_experts`` of them in the file, as cut) and the ``shared`` expert.

Drawn as ``weights_mellum2.py`` draws: each matrix in its own type with
standard deviation ``fan_in ** -0.5``, norm scales and the router in float32.
Three choices are this file's (``configs/trinity-large.json`` ``assumed``):

* The embedding's rows have standard deviation ``hidden_size ** -0.5``, so
  that AFTER the published scale (``mup_enabled``: times ``sqrt(hidden_size)``)
  a token's vector has unit variance, as ``weights_mellum2``'s unit rows have
  without a scale.  With unit rows BEFORE the scale the stream would stand at
  55 and every branch (normed to its gain) at a fifth of a bfloat16 unit in
  the last place of it.
* The post-norms' gains (``ln1p``, ``ln2p``) are the constant ``published
  depth ** -0.5`` (0.129 at 60 layers): the record's family is described as
  "depth-scaled sandwich norm", an initialisation (Pangu Ultra,
  arXiv:2504.07866, scales the output norms' gains by the inverse root of the
  depth) and not an equation; the paper's constants are not re-read here, so
  the constant is 1.  ``wo`` and ``w_down`` keep ``fan_in ** -0.5``: the
  output norm takes their scale out.
* The selection's bias ``b`` is drawn with standard deviation
  ``draw.router_bias_std`` (0.02): the four largest sigmoid scores of 256 lie
  close under 1, so that small a bias changes three quarters of the top-4
  sets (``PERF.md``); a bias of zeros would make leaving it out invisible.

The cell's work is the FILE's, as ``weights_mellum2.py``'s is:
``configs/trinity-large.json`` ``draw.seed`` names ONE draw of the weights and
of the token ids, and a run's ``--seed`` RELABELS it: a permutation of the
vocabulary (embedding rows, head columns and the token ids with them:
``relabel_ids``) and one of the hidden units (every matrix's axis over them).
Both are symmetries of the model (its norms' scales are constants), so every
seed gives other arrays and other ids, the same seed the same, and every seed
routes the same tokens to the same experts, up to the order in which sums
round.  Read beside it (my chip runs, PR 34): with a fresh draw a seed the
held experts' share of the assignments read 10.98 to 13.68 % over twelve seeds
and ``serve_tokens_per_s`` followed it, 998.9 down to 961.4: one set of six
spread 2.46 % against half of a 3 % bound.  What a seed no longer does is draw
another sample of weights: the gaps of ``correct`` over seeds are one draw's
(the limits were set on fresh draws before this, ``PERF.md`` section 2;
``limits_trinity.py --patch '{"draw": {"seed": n}}'`` reads another draw).
"""

from __future__ import annotations

import functools
from typing import Any, Dict, List, Mapping

import jax
import jax.numpy as jnp
import numpy as np

from chipbench.weights import DTYPES, seed_key
from chipbench.weights_axk1 import published, swiglu_shapes
from chipbench.weights_mellum2 import HIDDEN_AXIS, _relabelling

# The axis of each drawn matrix that runs over the hidden units:
# ``weights_mellum2``'s, and the gate's.
HIDDEN = dict(HIDDEN_AXIS, wg=-2)


def attention_shapes(m: Mapping[str, Any]) -> Dict[str, Any]:
    d, hd = m["hidden_size"], m["head_dim"]
    q, kv = m["num_attention_heads"] * hd, m["num_key_value_heads"] * hd
    return {"wq": (d, q), "wk": (d, kv), "wv": (d, kv), "wg": (d, q), "wo": (q, d)}


def block_shapes(m: Mapping[str, Any], layer: int) -> Dict[str, Any]:
    """Matrix shapes of block ``layer`` (nested as the block's params are)."""
    d = m["hidden_size"]
    shapes: Dict[str, Any] = dict(attention_shapes(m))
    if layer < m["num_dense_layers"]:
        shapes.update(swiglu_shapes(d, m["intermediate_size"]))
        return shapes
    width = m["moe_intermediate_size"]
    shapes["mlp"] = dict(swiglu_shapes(d, width, (m["num_experts"],)),
                         router=(d, published(m, "num_experts")),
                         shared=swiglu_shapes(d, m["num_shared_experts"] * width))
    return shapes


def _freeze(tree: Any) -> Any:
    return tuple(sorted((k, _freeze(v)) for k, v in tree.items())) if isinstance(tree, dict) else tree


@functools.partial(jax.jit, static_argnames=("spec",))
def _make(key: jax.Array, relabel: jax.Array, spec: tuple) -> List[Dict[str, Any]]:
    d, hd, vocab, dtype, post_gain, bias_std, blocks = spec
    dt = DTYPES[dtype]
    rows, units = _relabelling(relabel, vocab, d)

    def draw(k, shapes):
        out = {}
        for sub, (name, shape) in zip(jax.random.split(k, len(shapes)), shapes):
            if isinstance(shape[0], tuple):                 # a nested group
                out[name] = draw(sub, shape)
            else:
                kind = jnp.float32 if name == "router" else dt
                out[name] = jnp.take(
                    (shape[-2] ** -0.5 * jax.random.normal(sub, shape, kind)).astype(kind),
                    units, axis=HIDDEN[name])
        if "router" in out:
            experts = out["router"].shape[1]
            out["router_bias"] = bias_std * jax.random.normal(
                jax.random.fold_in(k, 1), (experts,), jnp.float32)
        return out

    keys = jax.random.split(key, len(blocks) + 2)
    ones = jnp.ones((d,), jnp.float32)
    flat: List[Dict[str, Any]] = [
        {"table": (d ** -0.5 * jax.random.normal(keys[0], (vocab, d), dt)
                   ).astype(dt)[rows][:, units]}]
    for k, shapes in zip(keys[1:-1], blocks):
        flat.append(dict(draw(k, shapes), ln1=ones, ln2=ones,
                         ln1p=post_gain * ones, ln2p=post_gain * ones,
                         qn=jnp.ones((hd,), jnp.float32), kn=jnp.ones((hd,), jnp.float32)))
    flat.append({"scale": ones,
                 "w": (d ** -0.5 * jax.random.normal(keys[-1], (d, vocab), dt)
                       ).astype(dt)[units][:, rows]})
    return flat


def make_flat(m: Mapping[str, Any], seed: int) -> List[Dict[str, Any]]:
    """[embedding, block 0 .. block depth-1, head] on the default device:
    the file's draw (``m['draw']['seed']``) under the seed's relabelling."""
    blocks = tuple(_freeze(block_shapes(m, i)) for i in range(m["num_hidden_layers"]))
    spec = (m["hidden_size"], m["head_dim"], m["vocab_size"], m["torch_dtype"],
            published(m, "num_hidden_layers") ** -0.5, m["draw"]["router_bias_std"], blocks)
    return _make(seed_key(m["draw"]["seed"]), seed_key(seed), spec)


def relabel_ids(m: Mapping[str, Any], seed: int, ids: np.ndarray) -> np.ndarray:
    """Token ids of the file's draw under the seed's relabelling of the
    vocabulary: id ``t`` of the draw is the row that holds its embedding in
    ``make_flat(m, seed)``."""
    perm, _ = jax.device_get(_relabelling(seed_key(seed), m["vocab_size"], m["hidden_size"]))
    return np.argsort(perm).astype(ids.dtype)[ids]
