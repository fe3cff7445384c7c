"""Weights of a Nemotron-H (``model_type: nemotron_h``) configuration with
experts, from the configuration file's draw, made on the device in one jitted
call and handed to the program and to the plain reference alike.

The flat list is ``[embedding, block 0 .. block depth-1, head]``; block ``i``
is what letter ``i`` of ``hybrid_override_pattern`` says, each with its one
pre-norm ``ln1``:

* ``M``, the Mamba-2 mixer: ``in_proj [hidden, d_inner + conv_dim + heads]``
  (``z``, then ``x``, ``B`` and ``C``, then one step a head), ``conv_w
  [conv_kernel, conv_dim]`` (tap ``conv_kernel - 1`` on the current position)
  and ``conv_b``, ``dt_bias``, ``A_log`` and ``D`` (``[heads]``, float32),
  ``norm [d_inner]`` (the gated norm's scale) and ``out_proj [d_inner,
  hidden]``;
* ``E``, the expert layer, under ``mlp``: a float32 ``router`` over ALL the
  published experts and its float32 ``router_bias`` (the record's
  ``e_score_correction_bias``), the HELD experts' ungated ``w_up`` / ``w_down``
  (``n_routed_experts`` of them in the file, as cut) and the ``shared``
  expert's, of ``moe_shared_expert_intermediate_size``;
* ``*``, attention: ``wq``, ``wk``, ``wv``, ``wo``.

Each matrix is drawn in the served type with standard deviation ``fan_in **
-0.5``, the embedding's rows with unit variance (no embedding scale in this
family); norm scales are ones.  The mixer's own parameters follow the
published initialisation (mamba_ssm's ``Mamba2``; ``configs/nemotron3-nano.json``
``assumed``): ``A = -exp(A_log)`` with ``exp(A_log)`` uniform in [1, 16], the
step's bias the inverse softplus of a step drawn log-uniform in
[``time_step_min``, ``time_step_max``] (floored at ``time_step_floor``), ``D``
ones, the conv's taps and bias uniform in ``+-conv_kernel ** -0.5`` (the
depthwise conv's default).  The selection's bias is drawn with standard
deviation ``draw.router_bias_std``: a bias of zeros would make leaving it out
invisible.

The cell's work is the FILE's: ``draw.seed`` names ONE draw of the weights and
of the token ids, and a run's ``--seed`` RELABELS it, as ``weights_trinity.py``
does: a permutation of the vocabulary (embedding rows, head columns, the
token ids) and one of the hidden units (every matrix's axis over them).  Both
are symmetries of the model (its norms' scales are constants), so every seed
routes the same tokens to the same experts, up to the order in which sums
round.
"""

from __future__ import annotations

import functools
from typing import Any, Dict, List, Mapping, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from chipbench.weights import DTYPES, seed_key
from chipbench.weights_axk1 import published
from chipbench.weights_mellum2 import _relabelling

# The axis of each drawn matrix that runs over the hidden units.
HIDDEN = {"wq": -2, "wk": -2, "wv": -2, "wo": -1, "in_proj": -2, "out_proj": -1,
          "router": -2, "w_up": -2, "w_down": -1}
# Mixer parameters that are not matrices over the hidden units.
MIXER_OWN = ("conv_w", "conv_b", "dt_bias", "A_log", "D", "norm")


def ssm_sizes(m: Mapping[str, Any]) -> Dict[str, int]:
    """The mixer's widths: ``d_inner`` (heads x head_dim; ``expand`` is not
    read), the conv's channels and the input projection's columns."""
    d_inner = m["mamba_num_heads"] * m["mamba_head_dim"]
    conv = d_inner + 2 * m["n_groups"] * m["ssm_state_size"]
    return {"d_inner": d_inner, "conv_dim": conv,
            "in_width": d_inner + conv + m["mamba_num_heads"]}


def block_shapes(m: Mapping[str, Any], layer: int) -> Dict[str, Any]:
    """Matrix shapes of block ``layer`` (nested as the block's params are)."""
    d, kind = m["hidden_size"], m["hybrid_override_pattern"][layer]
    if kind == "M":
        s = ssm_sizes(m)
        return {"in_proj": (d, s["in_width"]), "out_proj": (s["d_inner"], d)}
    if kind == "*":
        hd = m["head_dim"]
        q, kv = m["num_attention_heads"] * hd, m["num_key_value_heads"] * hd
        return {"wq": (d, q), "wk": (d, kv), "wv": (d, kv), "wo": (q, d)}
    if kind != "E":
        raise ValueError(f"pattern letter {kind!r}: M, E and * are drawn")
    width, shared = m["moe_intermediate_size"], m["moe_shared_expert_intermediate_size"]
    held = (m["n_routed_experts"],)
    return {"mlp": {"w_up": held + (d, width), "w_down": held + (width, d),
                    "router": (d, published(m, "n_routed_experts")),
                    "shared": {"w_up": (d, shared), "w_down": (shared, d)}}}


def _freeze(tree: Any) -> Any:
    return tuple(sorted((k, _freeze(v)) for k, v in tree.items())) if isinstance(tree, dict) else tree


def _mixer_own(k: jax.Array, spec: tuple, dt: Any) -> Dict[str, jax.Array]:
    heads, kernel, conv_dim, d_inner, t_min, t_max, t_floor = spec
    ks = jax.random.split(k, 4)
    bound = kernel ** -0.5
    step = jnp.exp(jax.random.uniform(ks[0], (heads,), jnp.float32,
                                      np.log(t_min), np.log(t_max)))
    step = jnp.maximum(step, t_floor)
    return {
        "conv_w": jax.random.uniform(ks[1], (kernel, conv_dim), jnp.float32,
                                     -bound, bound).astype(dt),
        "conv_b": jax.random.uniform(ks[2], (conv_dim,), jnp.float32,
                                     -bound, bound).astype(dt),
        "dt_bias": step + jnp.log(-jnp.expm1(-step)),      # softplus^-1(step)
        "A_log": jnp.log(jax.random.uniform(ks[3], (heads,), jnp.float32, 1.0, 16.0)),
        "D": jnp.ones((heads,), jnp.float32),
        "norm": jnp.ones((d_inner,), jnp.float32),
    }


@functools.partial(jax.jit, static_argnames=("spec",))
def _make(key: jax.Array, relabel: jax.Array, spec: tuple) -> List[Dict[str, Any]]:
    d, vocab, dtype, bias_std, mixer_spec, pattern, blocks = spec
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
        {"table": jax.random.normal(keys[0], (vocab, d), dt)[rows][:, units]}]
    for k, letter, shapes in zip(keys[1:-1], pattern, blocks):
        block = dict(draw(k, shapes), ln1=ones)
        if letter == "M":
            block.update(_mixer_own(jax.random.fold_in(k, 2), mixer_spec, dt))
        flat.append(block)
    flat.append({"scale": ones,
                 "w": (d ** -0.5 * jax.random.normal(keys[-1], (d, vocab), dt)
                       ).astype(dt)[units][:, rows]})
    return flat


def make_flat(m: Mapping[str, Any], seed: int) -> List[Dict[str, Any]]:
    """[embedding, block 0 .. block depth-1, head] on the default device:
    the file's draw (``m['draw']['seed']``) under the seed's relabelling."""
    pattern = m["hybrid_override_pattern"]
    blocks = tuple(_freeze(block_shapes(m, i)) for i in range(m["num_hidden_layers"]))
    s = ssm_sizes(m)
    mixer = (m["mamba_num_heads"], m["conv_kernel"], s["conv_dim"], s["d_inner"],
             m["time_step_min"], m["time_step_max"], m["time_step_floor"])
    spec = (m["hidden_size"], m["vocab_size"], m["torch_dtype"],
            m["draw"]["router_bias_std"], mixer, pattern, blocks)
    return _make(seed_key(m["draw"]["seed"]), seed_key(seed), spec)


def relabel_ids(m: Mapping[str, Any], seed: int, ids: np.ndarray) -> np.ndarray:
    """Token ids of the file's draw under the seed's relabelling of the
    vocabulary: id ``t`` of the draw is the row that holds its embedding in
    ``make_flat(m, seed)``."""
    perm, _ = jax.device_get(_relabelling(seed_key(seed), m["vocab_size"], m["hidden_size"]))
    return np.argsort(perm).astype(ids.dtype)[ids]


def param_count(m: Mapping[str, Any]) -> Tuple[int, Dict[str, int]]:
    """(all parameters this chip holds, by kind of block), from the shapes."""
    out: Dict[str, int] = {}
    for i, letter in enumerate(m["hybrid_override_pattern"]):
        n = sum(int(np.prod(s)) for s in jax.tree_util.tree_leaves(
            block_shapes(m, i), is_leaf=lambda x: isinstance(x, tuple)))
        out[letter] = out.get(letter, 0) + n
    out["vocab"] = 2 * m["hidden_size"] * m["vocab_size"]
    return sum(out.values()), out
