"""What a cache row is: the kinds of cache, their banks and layout, and
every way a row is written, merged, copied or exported.

A cache holds, for each block, *banks*: fixed ``[b, ...]`` buffers whose
leading axis is the batch (a serving pool's SLOT axis) and which have
one *length* axis, the position in the sequence.  Three kinds:

* :class:`KVCache` — ``k`` and ``v``, each ``[b, L, n_kv, hd]``;
* :class:`QuantKVCache` — the same two banks in int8, plus ``k_scale``
  and ``v_scale``, f32 ``[b, n_kv, L]``: per-(position, kv-head)
  symmetric scales with the length LAST (the flash decode kernel tiles
  scales along ``L``, so storing ``L`` last avoids a per-step transpose
  of the whole buffer);
* :class:`LatentCache` — what latent attention (``cfg.mla``) caches:
  ``ckv [b, L, kv_lora_rank]`` and ``kpe [b, L, qk_rope_head_dim]``.

A model whose layers mix window and full attention (a period of more
than one ``cfg.attn_layers`` entry) gets banks that differ in LENGTH by
layer (:func:`layer_rows`): a window layer holds a RING of ``window +
chunk - 1`` rows (rounded up to the decode kernel's block; position
``p`` lives in row ``p % rows``, and a chunk's ``chunk`` queries still
find their whole band beside the rows they write), a full layer
``max_len`` rows.  Every function here goes by the bank's own length.
A one-entry period (a model-global window, or none) keeps ``max_len``
rows in every layer.

A hybrid model whose mixer layers keep a recurrent state (``M``
layers of ``cfg.ssm``, ``models.ssm``; ``K`` layers of
``cfg.linear_attn``, ``models.kda``) gets a :class:`HybridCache`: its
attention layers' K/V rows as a :class:`KVCache` (``kv``), and beside
them, a mixer layer each, a state that does NOT grow with the context:
the conv's last inputs and the float32 state, in the fields of the
mixer that keeps them (``recurrent.MIXERS``: ``conv`` / ``ssm`` for the
Mamba-2 layers, ``kda_conv`` / ``kda`` for the delta-rule layers), each
of the shape its own mixer's ``init_state`` gives.  Nothing of it lies at a position, so
what takes rows at their position (the merges and copies below, int8
rows, a prefix's rows, migration, rollback) refuses it by name
(:func:`refuse_state`).

Every kind has two CONTENT banks a layer (K and V, or the latent and
the rotated key head) and, where its rows are quantised, one scale bank
beside each.  :func:`layers` hands a layer's banks out as ``(a, b,
a_scale, b_scale)`` with ``None`` for the scales a kind does not have;
the writes take and return that tuple and :func:`rebuild` makes a cache
of the same kind from a list of them.  Which attention reads the banks
is the caller's decision (``generation._attend_chunk``, ``mla.attend``);
an int8 layer is quantised HERE at the write and dequantised by the
attention at the read.

The callers — ``models.generation``, ``serving.engine``,
``serving.cache_pool`` and ``tune`` — know none of the above: a new kind
of row is a new class here, its entry in ``_LENGTH_AXIS`` and, in
``generation``, the lines that choose its attention.
"""

from __future__ import annotations

from typing import Any, Dict, Iterator, List, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax

from torchgpipe_tpu.models.recurrent import MIXERS
from torchgpipe_tpu.models.transformer import TransformerConfig

# One layer's banks: the two content banks, then their scale banks
# (``None`` for a kind that has none).
Layer = Tuple[jnp.ndarray, jnp.ndarray,
              Optional[jnp.ndarray], Optional[jnp.ndarray]]


class KVCache(NamedTuple):
    """Per-layer K/V buffers plus the current fill length."""

    k: List[jnp.ndarray]  # each [b, max_len, n_kv, hd]
    v: List[jnp.ndarray]
    length: jnp.ndarray   # [] int32 — tokens already cached


class LatentCache(NamedTuple):
    """The cache of a latent-attention model (``cfg.mla``): a layer's
    row is the normed KV latent and the rotated shared key head, not K
    and V.  Two banks a layer, because the two are read apart (the
    latent feeds scores AND output, the key head scores only) and a
    slice of one wider bank's minor dim would be a copy of the bank."""

    ckv: List[jnp.ndarray]  # each [b, max_len, kv_lora_rank]
    kpe: List[jnp.ndarray]  # each [b, max_len, qk_rope_head_dim]
    length: jnp.ndarray     # [] int32 — tokens already cached


class HybridCache(NamedTuple):
    """The cache of a hybrid model (``cfg.layer_pattern`` with mixer
    layers that keep a state, ``cfg.keeps_state``): the attention
    layers' rows, and a mixer layer each a slot's conv tail and
    recurrent state, in the fields of the mixer that keeps them
    (``recurrent.MIXERS``: the Mamba-2 ``M`` layers' of ``models.ssm``,
    the delta-rule ``K`` layers' of ``models.kda``; each field in layer
    order among its own letter).  A row of the state is a SLOT, not a position: the state
    sums the whole context, so a new tenant starts it from zero (the
    mixer reads zero where a row's frontier is 0) and a no-op row leaves
    it untouched."""

    kv: KVCache              # the attention ('*') layers' K/V, layer order
    conv: List[jnp.ndarray]  # 'M': each [b, conv_kernel - 1, conv_dim]
    ssm: List[jnp.ndarray]   # 'M': each f32 [b, n_heads, head_dim, state]
    kda_conv: List[jnp.ndarray]  # 'K': each [b, conv_kernel - 1, 3 H d]
    kda: List[jnp.ndarray]   # 'K': each f32 [b, H, d, d]
    length: jnp.ndarray      # [] int32 — tokens already cached


class QuantKVCache(NamedTuple):
    """int8 K/V buffers with per-(position, kv-head) scales — half the
    cache HBM footprint/traffic of bf16 and a quarter of f32; see
    ``generate(kv_quant=True)``."""

    k: List[jnp.ndarray]        # int8 [b, L, n_kv, hd]
    v: List[jnp.ndarray]
    k_scale: List[jnp.ndarray]  # f32 [b, n_kv, L]
    v_scale: List[jnp.ndarray]
    length: jnp.ndarray


# The axis of each bank on which its length lies (axis 0 is the batch /
# slot axis of every bank).  THE statement of the layout: the writes,
# the merge and the copy below index by it and nothing else does.
_LENGTH_AXIS = {"k": 1, "v": 1, "ckv": 1, "kpe": 1, "k_scale": 2, "v_scale": 2}


def _bank_fields(cache: Any) -> Tuple[str, ...]:
    """The bank fields of ``cache``'s kind: content banks, then scales."""
    return tuple(f for f in cache._fields if f != "length")


def _refuse_mla(cfg: TransformerConfig, what: str) -> None:
    if cfg.mla is not None:
        raise NotImplementedError(
            f"{what} holds K and V rows; a latent-attention model "
            "(cfg.mla) caches the KV latent instead (LatentCache) and "
            "is served by prefill / generate / decode_slots and "
            "serving.Engine's plain pool"
        )


# A ring's length is a multiple of the decode kernel's largest block
# (``ops.flash_attention._decode_tiling``: 512 rows), so that the kernel
# reads a ring at the block it reads a full layer at.
RING_GRANULE = 512


def refuse_state(cfg: TransformerConfig, what: str) -> None:
    """Refuse, by name, what is written for cache rows that lie at their
    position where the model's mixer layers keep a recurrent state
    (``cfg.keeps_state``): a slot's state sums its whole context, so it
    cannot be cut at a position, copied in part, rolled back or
    quantized a row at a time."""
    if cfg.keeps_state:
        letters = sorted(set(cfg.layer_pattern) & set(MIXERS))
        raise NotImplementedError(
            f"{what} takes cache rows that lie at their position; this "
            f"model's mixer layers ({letters} of cfg.layer_pattern) keep "
            "a recurrent state a slot (kv_cache.HybridCache) that sums "
            "the whole context and has no row at a position: prefill / "
            "decode_slots and serving.Engine's plain pool serve it"
        )


def attention_view(cfg: TransformerConfig, cache: Any,
                   layer: int) -> Tuple[Any, int]:
    """``(cache, index)``: the cache whose banks layer ``layer``'s
    attention reads and the index of its banks there (a hybrid model's
    attention layers are counted among themselves)."""
    if not isinstance(cache, HybridCache):
        return cache, layer
    return cache.kv, cfg.layer_pattern[:layer].count("*")


def bytes_by_kind(cfg: TransformerConfig, cache: Any) -> Dict[str, int]:
    """The bytes ``cache``'s banks pin by the kind of layer that holds
    them: ``window`` and ``full`` (:func:`layer_kind`), and ``state``,
    a hybrid model's mixer layers' tails and states, every mixer's by
    its own shape."""
    out = {"window": 0, "full": 0}
    kv = cache
    if isinstance(cache, HybridCache):
        kv = cache.kv
        out["state"] = sum(
            b.size * b.dtype.itemsize
            for mixer in MIXERS.values()
            for b in (*getattr(cache, mixer.tails),
                      *getattr(cache, mixer.states)))
    attn = [i for i in range(cfg.n_layers)
            if cfg.layer_type(i) in ("block", "attention")]
    for i, banks in zip(attn, layers(kv)):
        out[layer_kind(cfg, i)] += sum(
            b.size * b.dtype.itemsize for b in banks if b is not None)
    return out


def ring_layer(cfg: TransformerConfig, layer: int) -> bool:
    """Whether layer ``layer``'s banks are a ring: a window layer of a
    model that mixes layer types (a one-entry period keeps plain rows;
    its ring is ``generate(cache_mode='ring')``'s)."""
    return (len(cfg.attn_period) > 1
            and cfg.attn_layer(layer).window is not None)


def layer_kind(cfg: TransformerConfig, layer: int) -> str:
    """``'window'`` for a layer that attends in a window (a ring's rows
    where the model mixes layer types), ``'full'`` for one that attends
    over its whole context (a latent layer among them): the two kinds
    the pool's bytes and the attention's rows are counted by."""
    full = cfg.mla is not None or cfg.attn_layer(layer).window is None
    return "full" if full else "window"


def ring_rows(window: int, chunk: int) -> int:
    """Rows of a ring for ``window`` under writes of ``chunk`` tokens a
    call: the band of the chunk's first query and the chunk itself,
    rounded up to :data:`RING_GRANULE`."""
    return -(-(window + chunk - 1) // RING_GRANULE) * RING_GRANULE


def layer_rows(cfg: TransformerConfig, max_len: int, chunk: int = 1
               ) -> List[int]:
    """The length of each layer's banks: ``max_len``, or for a ring
    layer (:func:`ring_layer`) :func:`ring_rows` where that is less."""
    return [
        min(ring_rows(cfg.attn_layer(i).window, chunk), max_len)
        if ring_layer(cfg, i) else max_len
        for i in range(cfg.n_layers)
    ]


def refuse_rings(cfg: TransformerConfig, what: str) -> None:
    """Refuse, by name, what is written for rows that lie at their
    position: a model that mixes layer types holds its window layers'
    rows in rings (position ``p`` in row ``p % rows``, older rows
    overwritten)."""
    if len(cfg.attn_period) > 1:
        raise NotImplementedError(
            f"{what} takes cache rows that lie at their position; this "
            f"model mixes {len(cfg.attn_period)} attention layer types "
            "(cfg.attn_layers) and its window layers' rows live in rings "
            "(kv_cache.layer_rows), which plain prefill / generate / "
            "decode_slots and serving.Engine's plain pool serve"
        )


def init_cache(
    cfg: TransformerConfig, batch: int, max_len: int,
    dtype: Optional[jnp.dtype] = None, chunk: int = 1,
) -> Any:
    """Zeroed cache for ``cfg.n_layers`` blocks, as the attention kind
    says: :class:`KVCache`, or :class:`LatentCache` under ``cfg.mla``.
    ``chunk`` is the most tokens a call will write into a row at once
    (a serving pool's largest prefill chunk): it sizes the rings of a
    model that mixes layer types (:func:`layer_rows`) and nothing else."""
    dt = dtype or cfg.dtype
    if cfg.keeps_state:
        n_attn = cfg.layer_pattern.count("*")
        rows = (batch, max_len, cfg.kv_heads, cfg.head_dim)
        banks = {}
        for letter, mixer in MIXERS.items():
            states = [mixer.impl().init_state(cfg, batch, dt)
                      for _ in range(cfg.layer_pattern.count(letter))]
            banks[mixer.tails] = [c for c, _ in states]
            banks[mixer.states] = [st for _, st in states]
        return HybridCache(
            kv=KVCache(k=[jnp.zeros(rows, dt) for _ in range(n_attn)],
                       v=[jnp.zeros(rows, dt) for _ in range(n_attn)],
                       length=jnp.zeros((), jnp.int32)),
            length=jnp.zeros((), jnp.int32), **banks,
        )
    if cfg.mla is not None:
        m = cfg.mla
        return LatentCache(
            ckv=[jnp.zeros((batch, max_len, m.kv_lora_rank), dt)
                 for _ in range(cfg.n_layers)],
            kpe=[jnp.zeros((batch, max_len, m.qk_rope_head_dim), dt)
                 for _ in range(cfg.n_layers)],
            length=jnp.zeros((), jnp.int32),
        )
    shapes = [(batch, rows, cfg.kv_heads, cfg.head_dim)
              for rows in layer_rows(cfg, max_len, chunk)]
    return KVCache(
        k=[jnp.zeros(shape, dt) for shape in shapes],
        v=[jnp.zeros(shape, dt) for shape in shapes],
        length=jnp.zeros((), jnp.int32),
    )


def init_quant_cache(
    cfg: TransformerConfig, batch: int, max_len: int
) -> QuantKVCache:
    """Zeroed int8 KV cache for ``cfg.n_layers`` blocks."""
    shape = (batch, max_len, cfg.kv_heads, cfg.head_dim)
    _refuse_mla(cfg, "the int8 QuantKVCache")
    refuse_rings(cfg, "the int8 QuantKVCache")
    refuse_state(cfg, "the int8 QuantKVCache")
    sshape = (batch, cfg.kv_heads, max_len)
    return QuantKVCache(
        k=[jnp.zeros(shape, jnp.int8) for _ in range(cfg.n_layers)],
        v=[jnp.zeros(shape, jnp.int8) for _ in range(cfg.n_layers)],
        k_scale=[jnp.zeros(sshape, jnp.float32) for _ in range(cfg.n_layers)],
        v_scale=[jnp.zeros(sshape, jnp.float32) for _ in range(cfg.n_layers)],
        length=jnp.zeros((), jnp.int32),
    )


def _quant_rows(rows: jnp.ndarray) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Symmetric per-(position, head) int8 quantization over head_dim."""
    amax = jnp.max(jnp.abs(rows.astype(jnp.float32)), axis=-1)
    scale = jnp.maximum(amax, 1e-8) / 127.0
    q = jnp.clip(
        jnp.round(rows.astype(jnp.float32) / scale[..., None]), -127, 127
    ).astype(jnp.int8)
    return q, scale


def _dequant_rows(q: jnp.ndarray, scale: jnp.ndarray) -> jnp.ndarray:
    # scale is [b, n_kv, L] (see QuantKVCache); rows are [b, L, n_kv, hd].
    return q.astype(jnp.float32) * jnp.transpose(scale, (0, 2, 1))[..., None]


def bank_rows(cache: Any) -> List[int]:
    """The length of each layer's banks, in block order (a hybrid
    cache's: of its attention layers)."""
    if isinstance(cache, HybridCache):
        cache = cache.kv
    field = _bank_fields(cache)[0]
    return [bank.shape[_LENGTH_AXIS[field]] for bank in getattr(cache, field)]


def _cache_rows(cache: Any) -> int:
    """``max_len`` of a cache of any kind: its longest layer's length
    (every layer's, unless window layers hold rings)."""
    return max(bank_rows(cache))


def layers(cache: Any) -> Iterator[Layer]:
    """Each layer's banks in block order, as ``(a, b, a_scale,
    b_scale)``: the two content banks and their scale banks, ``None``
    where the kind has none."""
    for banks in zip(*(getattr(cache, f) for f in _bank_fields(cache))):
        yield banks + (None,) * (4 - len(banks))


def rebuild(cache: Any, new_layers: List[Layer], length: jnp.ndarray) -> Any:
    """A cache of ``cache``'s kind from per-layer banks (as the writes
    return them) and a new ``length``."""
    fields = _bank_fields(cache)
    return type(cache)(
        *(list(bank) for bank in list(zip(*new_layers))[:len(fields)]),
        length=length,
    )


def _write(layer: Layer, rows: Tuple, put: Any, put_scales: Any) -> Layer:
    """``layer`` with the content rows ``rows = (a rows, b rows)`` put
    into its banks: cast to the banks' dtype, or — a layer with scale
    banks — quantised to int8 (:func:`_quant_rows`) with the ``[b, g,
    n_kv]`` scales put into the scale banks."""
    a, b, a_scale, b_scale = layer
    if a_scale is None:
        return (put(a, rows[0].astype(a.dtype)),
                put(b, rows[1].astype(b.dtype)), None, None)
    (qa, sa), (qb, sb) = _quant_rows(rows[0]), _quant_rows(rows[1])
    return (put(a, qa), put(b, qb)) + put_scales(a_scale, sa, b_scale, sb)


def write_columns(layer: Layer, rows: Tuple, at: Any) -> Layer:
    """``layer`` with the new content rows ``rows = (a [b, g, ...],
    b [b, g, ...])`` written at columns ``at .. at + g - 1`` of every
    row (one offset for the whole batch: the chunk at ``pos0``, the
    ring at ``pos % W``, a prompt at 0)."""

    def put(bank, new, axis=1):
        return lax.dynamic_update_slice_in_dim(bank, new, at, axis)

    def put_scales(a_scale, sa, b_scale, sb):
        return (put(a_scale, jnp.transpose(sa, (0, 2, 1)), 2),
                put(b_scale, jnp.transpose(sb, (0, 2, 1)), 2))

    return _write(layer, rows, put, put_scales)


class ScatterIndex(NamedTuple):
    """Where :func:`write_scattered` puts a call's rows: batch row ``i``
    is slot ``slots[i]`` and its token ``j`` lands at column
    ``wpos[i, j]`` — ``max_len`` (out of range) drops it."""

    rows: jnp.ndarray   # [S, 1] — slot of each row, against wpos
    rows3: jnp.ndarray  # [S, 1, 1] — the same, against a scale bank
    wpos: jnp.ndarray   # [S, g]


def scatter_index(slots: jnp.ndarray, wpos: jnp.ndarray) -> ScatterIndex:
    """The index of one call's writes, built once for all its layers."""
    return ScatterIndex(slots[:, None], slots[:, None, None], wpos)


def write_scattered(layer: Layer, rows: Tuple, at: ScatterIndex) -> Layer:
    """``layer`` with the new content rows ``rows = (a [S, g, ...],
    b [S, g, ...])`` scattered to ``(slot, column)`` per token, masked
    tokens dropped: every other slot, and every column not named, stays
    bit-untouched (and a donated bank is updated in place)."""

    def put(bank, new):
        return bank.at[at.rows, at.wpos].set(new, mode="drop")

    def put_scales(a_scale, sa, b_scale, sb):
        heads = jnp.arange(a_scale.shape[1])[None, None, :]
        cols = at.wpos[:, :, None]
        return (a_scale.at[at.rows3, heads, cols].set(sa, mode="drop"),
                b_scale.at[at.rows3, heads, cols].set(sb, mode="drop"))

    return _write(layer, rows, put, put_scales)


def _refuse_hybrid(cache: Any) -> None:
    if isinstance(cache, HybridCache):
        raise NotImplementedError(
            "a HybridCache's mixer layers keep a state a slot, not rows "
            "at positions: rows cannot be merged, copied or shipped "
            "apart from it (kv_cache.refuse_state)"
        )


def _map_banks(cache: Any, fn: Any) -> Any:
    """A cache of ``cache``'s kind and length whose every bank is
    ``fn(field, layer, bank, the bank's length axis)``, field by field
    in layout order."""
    _refuse_hybrid(cache)
    return type(cache)(
        *(
            [fn(f, i, bank, _LENGTH_AXIS[f])
             for i, bank in enumerate(getattr(cache, f))]
            for f in _bank_fields(cache)
        ),
        length=cache.length,
    )


def keep_finished_rows(
    new: Any, old: Any, alive: jnp.ndarray, pos: jnp.ndarray
) -> Any:
    """Per-row masked no-op: rows finished (``alive[i]=False``) keep their
    OLD cache content — eos padding never enters a finished row's banks,
    so its cache stays bit-exact at the row's true frontier (the property
    batched serving and multi-turn continuation rely on).  The decode
    step wrote exactly ONE position (``pos``; ring buffers wrap it to
    their window), so only that column is merged back — O(b·heads·dim)
    per layer, not a full-cache copy.  ``new``'s ``length`` is kept: the
    shared scalar still advances (static shapes)."""
    masks: Dict[int, jnp.ndarray] = {}  # ``alive`` against a bank, by rank

    def merge(f: str, i: int, n: jnp.ndarray, axis: int) -> jnp.ndarray:
        if n.ndim not in masks:
            masks[n.ndim] = alive[(slice(None),) + (None,) * (n.ndim - 1)]
        at = jnp.mod(pos, n.shape[axis])
        col = jnp.where(
            masks[n.ndim],
            lax.dynamic_slice_in_dim(n, at, 1, axis),
            lax.dynamic_slice_in_dim(getattr(old, f)[i], at, 1, axis),
        )
        return lax.dynamic_update_slice_in_dim(n, col, at, axis)

    return _map_banks(new, merge)


def copy_rows(cache: Any, src: Any, dst: jnp.ndarray, n: jnp.ndarray) -> Any:
    """``cache`` with rows ``[0, n)`` of slot ``dst`` of every bank
    taken from ``src``: a slot index of the same cache, or one slot's
    shipped rows (:func:`slot_rows`, of this or of another pool with the
    same :func:`slot_row_specs`).  Rows ``>= n`` of ``dst`` and every
    other slot are untouched.  ``src`` (an index), ``dst`` and ``n`` may
    be traced values: one fixed-shape program serves every copy.  Each
    bank goes by its own length."""
    shipped = isinstance(src, dict)

    def put(f: str, i: int, bank: jnp.ndarray, axis: int) -> jnp.ndarray:
        # A slot's rows have lost the slot axis, so their length axis
        # (and the mask's) sits at ``axis - 1``.
        shape = [1] * (bank.ndim - 1)
        shape[axis - 1] = bank.shape[axis]
        m = (jnp.arange(bank.shape[axis]) < n).reshape(shape)
        row = src[f][i] if shipped else bank[src]
        return bank.at[dst].set(jnp.where(m, row, bank[dst]))

    return _map_banks(cache, put)


def slot_rows(cache: Any, slot: Any) -> Dict[str, List[jnp.ndarray]]:
    """One slot's rows of every bank, slot axis sliced away, by field."""
    _refuse_hybrid(cache)
    return {
        f: [bank[slot] for bank in getattr(cache, f)]
        for f in _bank_fields(cache)
    }


def slot_row_specs(cache: Any) -> Dict[str, List[jax.ShapeDtypeStruct]]:
    """The shapes and dtypes of :func:`slot_rows`."""
    return jax.eval_shape(lambda c: slot_rows(c, 0), cache)
