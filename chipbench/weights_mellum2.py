"""Weights of a ``mellum2`` configuration from the seed, made on the device in
one jitted call and handed to the program and to the plain reference alike.

The flat list is ``[embedding, block 0 .. block depth-1, head]``.  Every block
has grouped-query attention with a per-head norm on q and k (``ln1``, ``wq``,
``wk``, ``wv``, ``wo``, ``qn``, ``kn``, ``ln2``) and an expert feed-forward
``mlp``: a float32 ``router`` over ALL the published experts and the HELD
experts' stacked SwiGLUs (``num_experts`` of them in the file, as cut).
Weights in the stated type, norm scales and the router in float32.  Each
matrix is drawn in its own type with standard deviation ``fan_in ** -0.5``;
the two that write into the residual stream (``wo``, ``w_down``) with that
over ``sqrt(2 x published depth)``, the residual-scaled initialisation of
GPT-2 (Radford et al. 2019, section 2.3) and Megatron-LM
(``scaled_init_method_normal``, arXiv:1909.08053); the embedding's rows with
standard deviation 1 (``torch.nn.Embedding``'s default; the unit-variance rows
of Vaswani et al. 2017, section 3.4).  The record states no
``initializer_range``.  Seeded weights stand for a trained model's, whose
routers tell tokens apart: with unit rows a token's own vector, and not what
attention averages over its context, decides its route in every layer, and the
held experts' load is near the uniform share at step 0 on every seed (25.0 %,
the fullest expert 1.07 x the mean).  Read beside it (chip runs, PR 32): with
embedding rows of 0.02, as ``weights.py`` draws the dense cells', every deeper
router sees nearly one vector, some held experts got no token and the fullest
6.3 times the mean (share 23.2 %, by the seed's luck); with unscaled branches
three seeds' ``train_tokens_per_s`` spread over 2.5 %.  The load does not stay
there: at the cell's learning rate it drifts from the fifth step on
(``configs/mellum2.json``, ``train.load_drift``), and HOW FAST is the draw's:
ten fresh draws read steps of 3.398 to 3.443 s over a window (chip runs, PR
32), 0.75 % of spread in ``train_tokens_per_s`` against half of a 1 % bound.

So the cell's work is the FILE's, as a backlog's order is its traffic file's:
``configs/mellum2.json`` ``draw.seed`` names ONE draw of the weights and of
the token ids (the mix's generator), and a run's ``--seed``
RELABELS it: a permutation of the vocabulary (embedding rows, head columns and
the token ids with them) and one of the hidden units (every matrix's axis over
them).  Both are symmetries of the model, of the loss and of AdamW, which is
elementwise: every seed gives other arrays and other ids, the same seed the
same, and every seed's training run routes the same tokens to the same
experts, up to the order in which sums round.  What a seed no longer does is
draw another sample of weights: the gaps of ``correct`` over seeds are one
draw's (the limits were set on 18 fresh draws before this, ``PERF.md``
section 2, and ``limits_mellum2.py --draw`` reads another draw).
"""

from __future__ import annotations

import functools
from typing import Any, Dict, List, Mapping, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from chipbench.common import resolve
from chipbench.weights import DTYPES, seed_key
from chipbench.weights_axk1 import published, swiglu_shapes


def block_shapes(m: Mapping[str, Any]) -> Dict[str, Any]:
    """Matrix shapes of a block (nested as the block's params are)."""
    d, hd = m["hidden_size"], m["head_dim"]
    q, kv = m["num_attention_heads"] * hd, m["num_key_value_heads"] * hd
    return {"wq": (d, q), "wk": (d, kv), "wv": (d, kv), "wo": (q, d),
            "mlp": dict(swiglu_shapes(d, m["moe_intermediate_size"], (m["num_experts"],)),
                        router=(d, published(m, "num_experts")))}


def _freeze(tree: Any) -> Any:
    return tuple(sorted((k, _freeze(v)) for k, v in tree.items())) if isinstance(tree, dict) else tree


@functools.partial(jax.jit, static_argnames=("spec",))
def _make(key: jax.Array, relabel: jax.Array, spec: tuple) -> List[Dict[str, Any]]:
    d, hd, vocab, depth, dtype, branch_scale, shapes = spec
    dt = DTYPES[dtype]
    rows, units = _relabelling(relabel, vocab, d)

    def draw(k, shapes):
        out = {}
        for sub, (name, shape) in zip(jax.random.split(k, len(shapes)), shapes):
            if isinstance(shape[0], tuple):                 # a nested group
                out[name] = draw(sub, shape)
            else:
                kind = jnp.float32 if name == "router" else dt
                std = shape[-2] ** -0.5 * (branch_scale if name in ("wo", "w_down") else 1.0)
                out[name] = jnp.take((std * jax.random.normal(sub, shape, kind)).astype(kind),
                                     units, axis=HIDDEN_AXIS[name])
        return out

    keys = jax.random.split(key, depth + 2)
    flat: List[Dict[str, Any]] = [
        {"table": jax.random.normal(keys[0], (vocab, d), dt)[rows][:, units]}]
    for k in keys[1:-1]:
        flat.append(dict(draw(k, shapes),
                         ln1=jnp.ones((d,), jnp.float32), ln2=jnp.ones((d,), jnp.float32),
                         qn=jnp.ones((hd,), jnp.float32), kn=jnp.ones((hd,), jnp.float32)))
    flat.append({"scale": jnp.ones((d,), jnp.float32),
                 "w": (d ** -0.5 * jax.random.normal(keys[-1], (d, vocab), dt)
                       ).astype(dt)[units][:, rows]})
    return flat


# The axis of each drawn matrix that runs over the hidden units (the norm
# scales are drawn as ones, which no relabelling moves).
HIDDEN_AXIS = {"wq": -2, "wk": -2, "wv": -2, "wo": -1, "router": -2,
               "w_gate": -2, "w_up": -2, "w_down": -1}


def _relabelling(key: jax.Array, vocab: int, d: int) -> Tuple[jax.Array, jax.Array]:
    """A seed's two permutations: row ``r`` of the relabelled embedding is
    row ``rows[r]`` of the draw's, hidden unit ``j`` is unit ``units[j]``."""
    k_rows, k_units = jax.random.split(key)
    return jax.random.permutation(k_rows, vocab), jax.random.permutation(k_units, d)


def _spec(m: Mapping[str, Any]) -> tuple:
    return (m["hidden_size"], m["head_dim"], m["vocab_size"], m["num_hidden_layers"],
            m["torch_dtype"], (2.0 * published(m, "num_hidden_layers")) ** -0.5,
            _freeze(block_shapes(m)))


def make_flat(m: Mapping[str, Any], seed: int) -> List[Dict[str, Any]]:
    """[embedding, block 0 .. block depth-1, head] on the default device:
    the file's draw (``m['draw']['seed']``) under the seed's relabelling."""
    return _make(seed_key(m["draw"]["seed"]), seed_key(seed), _spec(m))


def token_batches(m: Mapping[str, Any], traffic: Mapping[str, Any], seed: int,
                  rows: int, seq: int) -> np.ndarray:
    """The mix's batches of token ids for the file's draw, under the seed's
    relabelling of the vocabulary: id ``t`` of the draw is the row that holds
    its embedding in ``make_flat(m, seed)``."""
    ids = resolve(traffic["generator"])(traffic, m["draw"]["seed"], rows, seq, m["vocab_size"])
    perm, _ = jax.device_get(_relabelling(seed_key(seed), m["vocab_size"], m["hidden_size"]))
    return np.argsort(perm).astype(ids.dtype)[ids]


def stack_for_stages(flat: List[Dict[str, Any]], n_stages: int) -> Dict[str, Any]:
    """The SPMD pipeline's layout: stage j runs blocks j*per .. j*per+per-1,
    each leaf (nested groups too) stacked over a leading stage axis."""
    blocks = flat[1:-1]
    per, rest = divmod(len(blocks), n_stages)
    if rest:
        raise ValueError(f"{len(blocks)} blocks do not divide into {n_stages} stages")
    stacked = tuple(
        jax.tree_util.tree_map(lambda *leaves: jnp.stack(leaves),
                               *[blocks[j * per + k] for j in range(n_stages)])
        for k in range(per))
    return {"pre": flat[0], "blocks": stacked, "post": flat[-1]}


def leaf_names(flat: List[Dict[str, Any]]) -> List[str]:
    """``<unit>.<path>`` of every leaf, unit by unit of the flat list, in
    ``jax.tree_util``'s order (the order of every per-leaf list here)."""
    names = []
    for i, unit in enumerate(flat):
        unit_name = "embed" if i == 0 else "head" if i == len(flat) - 1 else f"block{i - 1}"
        paths = jax.tree_util.tree_flatten_with_path(unit)[0]
        names += [unit_name + "." + ".".join(str(k.key) for k in path) for path, _ in paths]
    return names
