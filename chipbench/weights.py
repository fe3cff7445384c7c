"""Weights from the seed, made on the device in one jitted call.

The benchmark makes the weights and hands them to the program and to the
plain reference alike; neither side makes its own.  The layout is the flat
per-layer list the program's own extractors produce (embedding, blocks,
head), weights in the served type and norm scales in float32.
"""

from __future__ import annotations

import functools
from typing import Any, Dict, List, Mapping

import jax
import jax.numpy as jnp

from chipbench.peaks import head_dim

DTYPES = {"bfloat16": jnp.bfloat16, "float32": jnp.float32}


def seed_key(seed: int) -> jax.Array:
    """A key from any whole number (the driver's seeds pass 2**31)."""
    seed = int(seed)
    return jax.random.fold_in(
        jax.random.PRNGKey(seed & 0x7FFFFFFF), seed >> 31
    )


def layer_shapes(m: Mapping[str, Any]) -> Dict[str, tuple]:
    d, hd, i = m["hidden_size"], head_dim(m), m["intermediate_size"]
    q, kv = m["num_attention_heads"] * hd, m["num_key_value_heads"] * hd
    return {"wq": (d, q), "wk": (d, kv), "wv": (d, kv), "wo": (q, d),
            "w_gate": (d, i), "w_up": (d, i), "w_down": (i, d)}


@functools.partial(jax.jit, static_argnames=("spec",))
def _make(key: jax.Array, spec: tuple) -> List[Dict[str, jax.Array]]:
    d, vocab, depth, dtype, shapes = spec
    dt = DTYPES[dtype]

    def normal(k, shape, std):
        return (std * jax.random.normal(k, shape, jnp.float32)).astype(dt)

    keys = jax.random.split(key, depth + 2)
    flat: List[Dict[str, jax.Array]] = [
        {"table": normal(keys[0], (vocab, d), 0.02)}
    ]
    for layer in range(depth):
        ks = jax.random.split(keys[1 + layer], len(shapes))
        block = {"ln1": jnp.ones((d,), jnp.float32),
                 "ln2": jnp.ones((d,), jnp.float32)}
        for k, (name, shape) in zip(ks, shapes):
            block[name] = normal(k, shape, shape[0] ** -0.5)
        flat.append(block)
    flat.append({"scale": jnp.ones((d,), jnp.float32),
                 "w": normal(keys[-1], (d, vocab), d ** -0.5)})
    return flat


def make_flat(m: Mapping[str, Any], seed: int) -> List[Dict[str, jax.Array]]:
    """[embedding, block 0 .. block depth-1, head] on the default device."""
    spec = (m["hidden_size"], m["vocab_size"], m["num_hidden_layers"],
            m["torch_dtype"], tuple(sorted(layer_shapes(m).items())))
    return _make(seed_key(seed), spec)


def stack_for_stages(flat: List[Dict[str, jax.Array]], n_stages: int) -> Dict[str, Any]:
    """The SPMD pipeline's layout: stage j runs blocks j*per .. j*per+per-1,
    each leaf stacked over a leading stage axis."""
    blocks = flat[1:-1]
    per = len(blocks) // n_stages
    if per * n_stages != len(blocks):
        raise ValueError(f"{len(blocks)} blocks do not divide into {n_stages} stages")
    stacked = tuple(
        {name: jnp.stack([blocks[j * per + k][name] for j in range(n_stages)])
         for name in blocks[0]}
        for k in range(per)
    )
    return {"pre": flat[0], "blocks": stacked, "post": flat[-1]}
