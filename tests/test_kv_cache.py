"""``models.kv_cache``: what a cache row is, pinned for every kind of
cache (plain K/V, int8 K/V with scales, latent) through the module's own
surface — the writes, the column merge, the row copy and the export.

The engine, generation, fleet, disagg and ``test_mla_moe`` tests cover
the same kinds end to end (``generate``, ``decode_slots``, prefix reuse,
migration); these hold the module to its contract bank by bank.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from torchgpipe_tpu.models import kv_cache
from torchgpipe_tpu.models.transformer import MLAConfig, TransformerConfig

KINDS = ["plain", "int8", "latent"]
CFG = TransformerConfig(vocab=64, dim=32, n_layers=2, n_heads=4, n_kv_heads=2)
LATENT = TransformerConfig(
    vocab=64, dim=32, n_layers=2, n_heads=4,
    mla=MLAConfig(q_lora_rank=12, kv_lora_rank=16, qk_nope_head_dim=8,
                  qk_rope_head_dim=4, v_head_dim=8),
)
S, L = 3, 12  # slots (batch rows), max_len


def make_cache(kind):
    if kind == "int8":
        return kv_cache.init_quant_cache(CFG, S, L)
    return kv_cache.init_cache(LATENT if kind == "latent" else CFG, S, L)


def content_rows(kind, b, g, seed):
    """New rows ``(a [b, g, ...], b [b, g, ...])`` as a block computes
    them: K and V, or the latent and the key head."""
    ka, kb = jax.random.split(jax.random.PRNGKey(seed))
    if kind == "latent":
        m = LATENT.mla
        return (jax.random.normal(ka, (b, g, m.kv_lora_rank)),
                jax.random.normal(kb, (b, g, m.qk_rope_head_dim)))
    shape = (b, g, CFG.kv_heads, CFG.head_dim)
    return jax.random.normal(ka, shape), jax.random.normal(kb, shape)


def filled(kind, seed):
    """A cache whose every position of every layer holds random rows."""
    cache = make_cache(kind)
    new = [
        kv_cache.write_columns(layer, content_rows(kind, S, L, seed + i), 0)
        for i, layer in enumerate(kv_cache.layers(cache))
    ]
    return kv_cache.rebuild(cache, new, jnp.asarray(L, jnp.int32))


def banks_of(cache):
    """``{(field, layer): numpy bank}`` for every bank of ``cache``."""
    return {
        (f, i): np.asarray(bank)
        for f in kv_cache._bank_fields(cache)
        for i, bank in enumerate(getattr(cache, f))
    }


def read_columns(layer, at, g):
    """Float content rows of columns ``at .. at + g - 1`` as attention
    would read them (an int8 layer dequantised by its scales)."""
    a, b, a_scale, b_scale = layer
    if a_scale is not None:
        a = kv_cache._dequant_rows(a, a_scale)
        b = kv_cache._dequant_rows(b, b_scale)
    return np.asarray(a[:, at:at + g]), np.asarray(b[:, at:at + g])


@pytest.mark.parametrize("kind", KINDS)
def test_column_write_reads_back(kind):
    """``write_columns`` at one offset: the rows read back (int8: within
    half a scale step, its scales at ``[b, n_kv, L]``), every other
    column stays zero, and ``rebuild`` keeps the kind."""
    cache = make_cache(kind)
    at, g = 5, 3
    rows = content_rows(kind, S, g, seed=0)
    new = [kv_cache.write_columns(layer, rows, jnp.asarray(at))
           for layer in kv_cache.layers(cache)]
    out = kv_cache.rebuild(cache, new, jnp.asarray(at + g, jnp.int32))
    assert type(out) is type(cache) and int(out.length) == at + g
    assert kv_cache._cache_rows(out) == L
    for layer in kv_cache.layers(out):
        got = read_columns(layer, at, g)
        for want, have in zip(rows, got):
            if kind == "int8":
                step = np.abs(np.asarray(want)).max(-1, keepdims=True) / 127.0
                assert (np.abs(have - np.asarray(want)) <= step / 2 + 1e-7).all()
            else:
                np.testing.assert_array_equal(have, np.asarray(want))
        for other in (read_columns(layer, 0, at), read_columns(layer, at + g, L)):
            assert all(not o.any() for o in other)
    if kind == "int8":
        assert out.k[0].dtype == jnp.int8
        assert out.k_scale[0].shape == (S, CFG.kv_heads, L)
        scale = np.asarray(out.k_scale[0])
        assert scale[:, :, at:at + g].all() and not scale[:, :, :at].any()


@pytest.mark.parametrize("kind", KINDS)
def test_scatter_write_drops_masked_positions(kind):
    """``write_scattered`` with two rows landing in slots 2 and 0 and
    some tokens masked (``wpos == max_len``): the named positions hold
    the rows; slot 1 and every position not named are bit-untouched."""
    before = filled(kind, seed=10)
    slots = jnp.asarray([2, 0])
    # row 0 (slot 2): tokens at columns 4, 5, third masked;
    # row 1 (slot 0): all three masked.
    wpos = jnp.asarray([[4, 5, L], [L, L, L]])
    rows = content_rows(kind, 2, 3, seed=1)
    at = kv_cache.scatter_index(slots, wpos)
    new = [kv_cache.write_scattered(layer, rows, at)
           for layer in kv_cache.layers(before)]
    after = kv_cache.rebuild(before, new, before.length)
    reference = kv_cache.rebuild(before, [
        kv_cache.write_columns(
            tuple(None if b is None else b[2:3] for b in layer),
            tuple(r[:1, :2] for r in rows), 4)
        for layer in kv_cache.layers(before)
    ], before.length)
    old, got, ref = banks_of(before), banks_of(after), banks_of(reference)
    for key, bank in got.items():
        axis = kv_cache._LENGTH_AXIS[key[0]]
        np.testing.assert_array_equal(bank[:2], old[key][:2])  # slots 0, 1
        cols = np.moveaxis(bank[2], axis - 1, 0)
        np.testing.assert_array_equal(
            cols, np.moveaxis(ref[key][0], axis - 1, 0))
        keep = [c for c in range(L) if c not in (4, 5)]
        np.testing.assert_array_equal(
            cols[keep], np.moveaxis(old[key][2], axis - 1, 0)[keep])
        assert not np.array_equal(cols[4:6],
                                  np.moveaxis(old[key][2], axis - 1, 0)[4:6])


@pytest.mark.parametrize("source", ["slot", "shipped"])
@pytest.mark.parametrize("kind", KINDS)
def test_row_copy_moves_rows_below_n_only(kind, source):
    """``copy_rows``: rows ``[0, n)`` of the source — a slot of the same
    cache, or another pool's shipped rows — land in slot ``dst`` of every
    bank, scales included; rows ``>= n`` of ``dst`` and every other slot
    stay as they were."""
    cache = filled(kind, seed=20)
    src, dst, n = 0, 2, 7
    if source == "slot":
        donor = cache
        out = kv_cache.copy_rows(cache, jnp.asarray(src), jnp.asarray(dst),
                                 jnp.asarray(n))
    else:
        donor = filled(kind, seed=30)
        out = kv_cache.copy_rows(cache, kv_cache.slot_rows(donor, src),
                                 jnp.asarray(dst), jnp.asarray(n))
    assert type(out) is type(cache)
    old, got, give = banks_of(cache), banks_of(out), banks_of(donor)
    for key, bank in got.items():
        axis = kv_cache._LENGTH_AXIS[key[0]] - 1    # of one slot's rows
        np.testing.assert_array_equal(bank[:dst], old[key][:dst])
        moved = np.moveaxis(bank[dst], axis, 0)
        np.testing.assert_array_equal(
            moved[:n], np.moveaxis(give[key][src], axis, 0)[:n])
        np.testing.assert_array_equal(
            moved[n:], np.moveaxis(old[key][dst], axis, 0)[n:])
        assert not np.array_equal(moved[:n],
                                  np.moveaxis(old[key][dst], axis, 0)[:n])


@pytest.mark.parametrize("kind", KINDS)
def test_column_merge_keeps_finished_rows(kind):
    """``keep_finished_rows``: at column ``pos`` a finished row keeps its
    OLD content, a live row its new one; nothing else moves and the new
    ``length`` stands."""
    old, new = filled(kind, seed=40), filled(kind, seed=50)
    new = new._replace(length=jnp.asarray(9, jnp.int32))
    pos = 8
    alive = jnp.asarray([True, False, True])
    out = kv_cache.keep_finished_rows(new, old, alive, jnp.asarray(pos))
    assert type(out) is type(new) and int(out.length) == 9
    was, now, got = banks_of(old), banks_of(new), banks_of(out)
    for key, bank in got.items():
        axis = kv_cache._LENGTH_AXIS[key[0]]
        want = np.moveaxis(now[key].copy(), axis, 0)
        want[pos, 1] = np.moveaxis(was[key], axis, 0)[pos, 1]
        np.testing.assert_array_equal(np.moveaxis(bank, axis, 0), want)
        assert not np.array_equal(was[key], now[key])


@pytest.mark.parametrize("kind", KINDS)
def test_exported_rows_match_their_specs(kind):
    """``slot_rows`` of one slot are that slot's rows of every bank, and
    their shapes and dtypes are ``slot_row_specs``'."""
    cache = filled(kind, seed=60)
    rows, specs = kv_cache.slot_rows(cache, 1), kv_cache.slot_row_specs(cache)
    fields = list(kv_cache._bank_fields(cache))
    assert sorted(rows) == sorted(specs) == sorted(fields)
    for f in fields:
        assert len(rows[f]) == len(specs[f]) == CFG.n_layers
        for row, spec, bank in zip(rows[f], specs[f], getattr(cache, f)):
            assert (row.shape, row.dtype) == (spec.shape, spec.dtype)
            np.testing.assert_array_equal(np.asarray(row), np.asarray(bank)[1])
