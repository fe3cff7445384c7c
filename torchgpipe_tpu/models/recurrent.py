"""What a recurrent mixer's slot keeps, and the two rules that make a
pooled slot's state safe, for every mixer whose layer keeps a state a
slot (:data:`MIXERS`: the Mamba-2 mixer of ``models.ssm`` and the gated
delta-rule mixer of ``models.kda``).

A slot keeps, a mixer layer each, the TAIL of its causal depthwise conv
(the ``K - 1`` inputs before the next chunk, in the served type) and a
float32 STATE that sums the whole context (``models.kv_cache.
HybridCache``).  Two rules make a pooled slot's state safe:

* a row whose frontier is 0 (a new tenant: admission gave it the slot)
  reads a zero state and tail, whatever its slot's last tenant left
  (:func:`entering`);
* a masked position is a no-op: the mixer makes its step the identity
  and its input never enters the tail (:func:`conv`), and a row that
  does nothing this call (``n_valid = 0``) hands back its state and
  tail bit-untouched (:func:`kept`).
"""

from __future__ import annotations

import importlib
from typing import Any, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp


class Mixer(NamedTuple):
    """A letter of a layer pattern whose layers keep a state a slot: the
    ``TransformerConfig`` field that describes them, the module under
    ``torchgpipe_tpu.models`` that computes them (its ``init_state`` and
    ``mixer``), and the two ``kv_cache.HybridCache`` fields that hold
    their conv tails and their states, each in layer order among the
    letter's own layers."""

    config: str
    module: str
    tails: str
    states: str

    def impl(self) -> Any:
        """The module that computes the letter's layers."""
        return importlib.import_module(f"torchgpipe_tpu.models.{self.module}")


# Every state-keeping letter: the one place that says which mixer a letter
# means and where its state lives.
MIXERS = {
    "M": Mixer(config="ssm", module="ssm", tails="conv", states="ssm"),
    "K": Mixer(config="linear_attn", module="kda", tails="kda_conv",
               states="kda"),
}


def conv(x: jnp.ndarray, tail: jnp.ndarray, w: jnp.ndarray,
         b: Optional[jnp.ndarray],
         n_valid: jnp.ndarray) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """``(silu(conv(x) + b) f32 [rows, g, C], the new tail)``: the causal
    depthwise conv of ``w [K, C]`` (tap ``K - 1`` on the current
    position) over ``[tail ; x]``, ``b`` None for a conv with no bias.
    The new tail is the ``K - 1`` inputs that end at the row's last VALID
    position (the old tail itself for a row with ``n_valid = 0``)."""
    g, k = x.shape[1], w.shape[0]
    ext = jnp.concatenate([tail, x.astype(tail.dtype)], axis=1)
    w = w.astype(jnp.float32)
    acc = sum(ext[:, j:j + g].astype(jnp.float32) * w[j] for j in range(k))
    if b is not None:
        acc = acc + b.astype(jnp.float32)
    new_tail = jnp.take_along_axis(ext, _tail_at(n_valid, k)[:, :, None],
                                   axis=1)
    return jax.nn.silu(acc), new_tail


def _tail_at(n_valid: jnp.ndarray, k: int) -> jnp.ndarray:
    """Where in ``[tail ; chunk]`` a row's new tail lies: the ``k - 1``
    inputs that end at its last VALID position, so a padded position's
    input never enters it."""
    return n_valid[:, None] + jnp.arange(k - 1)[None, :]


def _rows(flag: jnp.ndarray, like: jnp.ndarray) -> jnp.ndarray:
    """``flag [rows]`` against an array whose leading axis is the row."""
    return flag[(slice(None),) + (None,) * (like.ndim - 1)]


def entering(fresh: jnp.ndarray, tail: jnp.ndarray,
             state: jnp.ndarray) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """The tail and state a row continues from: zero for a new tenant
    (``fresh``), whatever its slot's last tenant left."""
    return (jnp.where(_rows(fresh, tail), 0, tail).astype(tail.dtype),
            jnp.where(_rows(fresh, state), 0.0, state))


def kept(keep: jnp.ndarray, new: jnp.ndarray,
         old: jnp.ndarray) -> jnp.ndarray:
    """``new`` where the row ran (``keep``), else ``old`` as it came."""
    return jnp.where(_rows(keep, new), new, old)


__all__ = ["MIXERS", "Mixer", "conv", "entering", "kept"]
