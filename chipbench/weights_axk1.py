"""Weights of an A.X-K1 configuration from the seed, made on the device in
one jitted call and handed to the program and to the plain reference alike.

The flat list is ``[embedding, block 0 .. block depth-1, head]``.  Every block
has latent attention (``ln1``, ``wq_a``, ``q_norm``, ``wq_b``, ``wkv_a``,
``kv_norm``, ``wkv_b`` with each head's key columns before its value columns,
``wo``, ``ln2``).  The first ``first_k_dense_replace`` blocks have the dense
SwiGLU (``w_gate``, ``w_up``, ``w_down`` of ``intermediate_size``); the others
carry ``mlp``: a float32 ``router`` over ALL the published experts, the HELD
experts' stacked SwiGLUs (``n_routed_experts`` of them in the file, as cut)
and the ``shared`` expert.  Weights in the served type, norm scales and the
router in float32.  Each matrix is drawn in its own type with standard
deviation ``fan_in ** -0.5``, so no float32 copy of a 0.35 GiB leaf is made.
"""

from __future__ import annotations

import functools
from typing import Any, Dict, List, Mapping, Tuple

import jax
import jax.numpy as jnp

from chipbench.weights import DTYPES, seed_key


def published(m: Mapping[str, Any], key: str) -> Any:
    """The source's value of a key that the file cut (``reduced``), else the file's."""
    cut = m.get("reduced", {}).get(key)
    return cut["published"] if cut else m[key]


def attention_shapes(m: Mapping[str, Any]) -> Dict[str, Tuple[int, ...]]:
    d, h = m["hidden_size"], m["num_attention_heads"]
    n, r, v = m["qk_nope_head_dim"], m["qk_rope_head_dim"], m["v_head_dim"]
    return {"wq_a": (d, m["q_lora_rank"]), "wq_b": (m["q_lora_rank"], h * (n + r)),
            "wkv_a": (d, m["kv_lora_rank"] + r), "wkv_b": (m["kv_lora_rank"], h * (n + v)),
            "wo": (h * v, d)}


def swiglu_shapes(d: int, width: int, lead: Tuple[int, ...] = ()) -> Dict[str, Tuple[int, ...]]:
    return {"w_gate": lead + (d, width), "w_up": lead + (d, width), "w_down": lead + (width, d)}


def block_shapes(m: Mapping[str, Any], layer: int) -> Dict[str, Any]:
    """Matrix shapes of block ``layer`` (nested as the block's params are)."""
    d = m["hidden_size"]
    shapes: Dict[str, Any] = dict(attention_shapes(m))
    if layer < m["first_k_dense_replace"]:
        shapes.update(swiglu_shapes(d, m["intermediate_size"]))
        return shapes
    width = m["moe_intermediate_size"]
    shapes["mlp"] = dict(swiglu_shapes(d, width, (m["n_routed_experts"],)),
                         router=(d, published(m, "n_routed_experts")),
                         shared=swiglu_shapes(d, m["n_shared_experts"] * width))
    return shapes


def _freeze(tree: Any) -> Any:
    return tuple(sorted((k, _freeze(v)) for k, v in tree.items())) if isinstance(tree, dict) else tree


@functools.partial(jax.jit, static_argnames=("spec",))
def _make(key: jax.Array, spec: tuple) -> List[Dict[str, Any]]:
    d, vocab, dtype, q_rank, kv_rank, blocks = spec
    dt = DTYPES[dtype]

    def draw(k, shapes):
        out = {}
        for sub, (name, shape) in zip(jax.random.split(k, len(shapes)), shapes):
            if isinstance(shape[0], tuple):                 # a nested group
                out[name] = draw(sub, shape)
            else:
                fan_in, kind = shape[-2], jnp.float32 if name == "router" else dt
                out[name] = (fan_in ** -0.5 * jax.random.normal(sub, shape, kind)).astype(kind)
        return out

    keys = jax.random.split(key, len(blocks) + 2)
    flat: List[Dict[str, Any]] = [
        {"table": (0.02 * jax.random.normal(keys[0], (vocab, d), dt)).astype(dt)}]
    for k, shapes in zip(keys[1:-1], blocks):
        flat.append(dict(draw(k, shapes),
                         ln1=jnp.ones((d,), jnp.float32), ln2=jnp.ones((d,), jnp.float32),
                         q_norm=jnp.ones((q_rank,), jnp.float32),
                         kv_norm=jnp.ones((kv_rank,), jnp.float32)))
    flat.append({"scale": jnp.ones((d,), jnp.float32),
                 "w": (d ** -0.5 * jax.random.normal(keys[-1], (d, vocab), dt)).astype(dt)})
    return flat


def make_flat(m: Mapping[str, Any], seed: int) -> List[Dict[str, Any]]:
    """[embedding, block 0 .. block depth-1, head] on the default device."""
    blocks = tuple(_freeze(block_shapes(m, i)) for i in range(m["num_hidden_layers"]))
    spec = (m["hidden_size"], m["vocab_size"], m["torch_dtype"], m["q_lora_rank"],
            m["kv_lora_rank"], blocks)
    return _make(seed_key(seed), spec)
