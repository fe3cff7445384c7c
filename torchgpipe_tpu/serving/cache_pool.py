"""Slot-pooled KV caches: fixed banks, free-list allocation, recycling.

The TPU serving problem in one sentence: request churn must never change
an array shape (XLA recompiles per shape — the ``recompilation-hazard``
lint rule), yet requests arrive, finish and cancel at arbitrary times.
The pool squares that circle the PagedAttention/Orca way, specialised to
one page per request: fixed banks of ``num_slots x max_len`` rows per
layer — a cache of :mod:`torchgpipe_tpu.models.kv_cache` (``KVCache``,
int8 ``QuantKVCache`` or, for a latent-attention model, ``LatentCache``:
``init_cache`` decides by the attention kind) whose batch dim IS the
slot dim; what a row holds and how its banks are laid out — a ring of
``window + chunk - 1`` rows in the window layers of a model that mixes
layer types, ``max_len`` rows everywhere else — is that module's
business, not the pool's — a host-side free list handing slots to
requests and taking them back, and a per-slot ``lengths`` vector (host
mirror, passed into every compiled step) giving each slot its own
sequence frontier.

Recycling needs NO device work: a freed slot's stale rows are dead by
masking — every attention read masks cache rows ``> length``, and decode
writes land exactly at ``length``, so a recycled slot can never see its
previous tenant's K/V, scales included (the bitwise slot-reuse test in
``tests/test_serving.py`` pins this for the int8 cache, where a stale
*scale* would corrupt every row it spans).  A hybrid model's recurrent
state (``kv_cache.HybridCache``) is not masked by position: it sums the
whole context.  Its recycling needs no device work either: admission
sets the slot's frontier to 0, and a row at frontier 0 reads a zero
state and conv tail in the step program (``models.ssm.mixer``), so the
last tenant's state is never read (``ServingMetrics.state_zeroed_slots``
counts those admissions).

Sizing: :func:`torchgpipe_tpu.tune.serving_cache_bytes` accounts the
pool via ``eval_shape`` (no allocation);
:func:`torchgpipe_tpu.tune.serving_max_slots` inverts it against an HBM
budget — the scheduler's admission control reads that number.
"""

from __future__ import annotations

from typing import Any, Dict, List, Optional

import jax.numpy as jnp
import numpy as np

from torchgpipe_tpu.models import kv_cache
from torchgpipe_tpu.models.kv_cache import init_cache, init_quant_cache
from torchgpipe_tpu.models.transformer import TransformerConfig


class CachePool:
    """A fixed-shape KV bank + free-list slot allocator.

    The device state (``cache``) is intentionally PUBLIC and replaced
    wholesale by the engine after every compiled step — the pool object
    owns allocation bookkeeping (host-side, O(1) per event), not the
    arrays' life cycle.  ``lengths`` is the host mirror of per-slot
    frontiers: the engine advances it deterministically (it knows
    exactly how many tokens each step absorbed), so steady-state serving
    never fetches it back from the device.
    """

    def __init__(
        self,
        cfg: TransformerConfig,
        num_slots: int,
        max_len: int,
        *,
        kv_quant: bool = False,
        dtype: Optional[Any] = None,
        chunk: int = 1,
    ) -> None:
        if num_slots < 1:
            raise ValueError(f"num_slots must be >= 1, got {num_slots}")
        if max_len < 2:
            raise ValueError(
                f"max_len must hold a prompt plus one generated token, "
                f"got {max_len}"
            )
        self.cfg = cfg
        self.num_slots = num_slots
        self.max_len = max_len
        self.kv_quant = kv_quant
        self.dtype = dtype
        # The most tokens a step writes into a slot at once: it sizes
        # the rings of a model that mixes layer types, nothing else.
        self.chunk = chunk
        self.cache: Any = (
            init_quant_cache(cfg, num_slots, max_len)
            if kv_quant
            else init_cache(cfg, num_slots, max_len, dtype=dtype,
                            chunk=chunk)
        )
        self.lengths = np.zeros((num_slots,), np.int32)
        # LIFO free list: the most-recently-freed slot is reused first,
        # maximising the chance its rows are still warm in cache AND
        # exercising the stale-row masking continuously.
        self._free: List[int] = list(range(num_slots - 1, -1, -1))
        self._owner: Dict[int, str] = {}
        # Per-slot refcounts: alloc() hands the owner one reference;
        # retain() adds more (the fleet prefix cache pins donor slots
        # this way).  A slot re-enters the LIFO free list only when the
        # LAST reference releases — a pinned slot outlives its request
        # and can never be recycled while something still reads its
        # rows (the refcount invariant tools/fleet_verify.py churns).
        self._refs: Dict[int, int] = {}

    # ------------------------------------------------------------------ #
    # allocation                                                         #
    # ------------------------------------------------------------------ #

    @property
    def num_free(self) -> int:
        return len(self._free)

    @property
    def num_active(self) -> int:
        """Slots owned by a live request (pinned-only slots excluded)."""
        return len(self._owner)

    @property
    def num_pinned(self) -> int:
        """Slots kept out of the free list ONLY by extra references
        (``retain``) — typically prefix-cache donors whose request has
        finished."""
        return self.num_slots - len(self._free) - len(self._owner)

    def alloc(self, owner: str) -> Optional[int]:
        """Hand a free slot to ``owner`` (its frontier reset to 0, one
        reference), or ``None`` when the pool is exhausted."""
        if not self._free:
            return None
        slot = self._free.pop()
        self._owner[slot] = owner
        self._refs[slot] = 1
        self.lengths[slot] = 0
        return slot

    def retain(self, slot: int) -> int:
        """Add a reference to an allocated/pinned slot (the prefix
        cache's donor pin); returns the new refcount."""
        if slot not in self._refs:
            raise KeyError(f"slot {slot} is not allocated")
        self._refs[slot] += 1
        return self._refs[slot]

    def refcount(self, slot: int) -> int:
        return self._refs.get(slot, 0)

    def free(self, slot: int) -> None:
        """The OWNER's release: the slot loses its request but recycles
        only when no extra references pin it (refcount 0).  No device
        work either way: stale rows are dead by masking (see the module
        docstring)."""
        if slot not in self._owner:
            raise KeyError(f"slot {slot} is not allocated")
        del self._owner[slot]
        self.release(slot)

    def release(self, slot: int) -> None:
        """Drop one (non-owner) reference; at refcount 0 the slot
        re-enters the LIFO free list with its frontier zeroed."""
        refs = self._refs.get(slot)
        if refs is None:
            raise KeyError(f"slot {slot} is not allocated")
        if refs > 1:
            self._refs[slot] = refs - 1
            return
        del self._refs[slot]
        self.lengths[slot] = 0
        self._free.append(slot)

    def owner_of(self, slot: int) -> Optional[str]:
        return self._owner.get(slot)

    def active_slots(self) -> List[int]:
        return sorted(self._owner)

    def check_refcounts(self) -> None:
        """Structural invariants, for tests and the fleet-verify churn
        grid: free/referenced partition the slots, every owned slot is
        referenced, refcounts are positive.  Raises (never ``assert``
        — the gate must stay live under ``python -O``)."""
        free = set(self._free)
        reffed = set(self._refs)
        if free & reffed:
            raise RuntimeError(
                f"slots both free and referenced: {sorted(free & reffed)}"
            )
        if free | reffed != set(range(self.num_slots)):
            raise RuntimeError(
                f"free {sorted(free)} + referenced {sorted(reffed)} do "
                f"not partition the {self.num_slots} slots"
            )
        if not set(self._owner) <= reffed:
            raise RuntimeError(
                f"owned slots {sorted(set(self._owner) - reffed)} carry "
                "no reference"
            )
        if any(n < 1 for n in self._refs.values()):
            raise RuntimeError(f"non-positive refcount: {self._refs}")

    # ------------------------------------------------------------------ #
    # accounting                                                         #
    # ------------------------------------------------------------------ #

    def bytes(self) -> int:
        """Bytes this pool's device arrays pin (eval_shape accounting —
        equals the allocated size)."""
        from torchgpipe_tpu.tune import serving_cache_bytes

        return serving_cache_bytes(
            self.cfg, self.num_slots, self.max_len,
            kv_quant=self.kv_quant, dtype=self.dtype, chunk=self.chunk,
        )

    def bytes_by_kind(self) -> Dict[str, int]:
        """:meth:`bytes` by the kind of layer that holds them:
        ``window`` (a layer that attends in a window: a ring's rows in
        a model that mixes layer types), ``full``, and a hybrid model's
        recurrent ``state`` (``kv_cache.bytes_by_kind``)."""
        return kv_cache.bytes_by_kind(self.cfg, self.cache)

    def lengths_device(self) -> jnp.ndarray:
        """The per-slot frontier vector as an int32 array for a step.

        SNAPSHOT semantics, deliberately: ``jnp.asarray`` on CPU may
        alias the numpy buffer zero-copy, and the engine mutates
        ``self.lengths`` in place right after dispatching the
        (asynchronously executing) step that reads it — without the copy
        the program races the host update (observed as nondeterministic
        outputs on the CPU backend)."""
        return jnp.asarray(self.lengths.copy())


__all__ = ["CachePool"]
