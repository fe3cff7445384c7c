"""The compact prefill step, pinned.

The engine's chunked-prefill program runs over ``R`` rows (the prompts
that are prefilling, by slot index), not over the pool's ``num_slots``:

1. **Same work** — ``decode_slots(..., slots=...)`` gives, row for row,
   the logits and the K/V rows the pool-wide form gives, and leaves
   every slot it was not handed bitwise untouched — for fewer pending
   rows than ``R`` (padded rows), as many, and over a chunk ladder.
2. **FIFO deferral** — more pending prompts than ``R`` are served
   oldest first, the rest wait one prefill step, and every request
   finishes with the tokens ``generate`` gives, for each kind of engine
   that prefills (plain, ladder, int8 KV, prefix cache, ``prefill``
   role, speculative).
3. **One trace a program** under admission / eviction churn with
   deferral, and specs that name the new inputs.
4. **The counters** — ``serving_prefill_rows`` over
   ``serving_prefill_row_capacity`` is the fill share, and
   ``serving_prefill_deferred_rows`` the rows left waiting, on a
   hand-built schedule; the ``engine.prefill`` span carries ``cap`` and
   ``deferred``.
5. **The per-row decode kernel under ``decode_slots``** (PR 29) —
   forced in interpret mode it gives the dense path's logits, cache and
   lengths, pool-wide and compact, with rows that do nothing; off TPU
   the engine never reaches it; ``serving_attend_rows_read`` over
   ``serving_attend_rows_capacity`` counts what it fetches.
"""

import dataclasses

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from torchgpipe_tpu import fleet
from torchgpipe_tpu.layers import sequential_init
from torchgpipe_tpu.models.generation import (
    decode_slots,
    generate,
    init_cache,
    init_quant_cache,
)
from torchgpipe_tpu.models.transformer import TransformerConfig, llama
from torchgpipe_tpu.serving import Engine
from torchgpipe_tpu.serving.engine import prefill_rows_for

CFG = TransformerConfig(
    vocab=64, dim=32, n_layers=2, n_heads=4, n_kv_heads=2
)
DRAFT_CFG = TransformerConfig(
    vocab=64, dim=16, n_layers=1, n_heads=2, n_kv_heads=2
)
SLOTS, MAX_LEN = 16, 32     # prefill_rows_for(16) == 8: R < num_slots


def _params(cfg, seed):
    params, _, _ = sequential_init(
        llama(cfg), jax.random.PRNGKey(seed),
        jax.ShapeDtypeStruct((2, 8), jnp.int32),
    )
    return params


@pytest.fixture(scope="module")
def flat_params():
    return _params(CFG, 0)


@pytest.fixture(scope="module")
def draft_params():
    return _params(DRAFT_CFG, 1)


def _ref(params, prompt, new, **kw):
    return np.asarray(
        generate(CFG, params, jnp.asarray(prompt)[None, :], new,
                 max_len=MAX_LEN, **kw)
    )[0]


def _prompts(seed, n, lo=3, hi=14):
    rng = np.random.RandomState(seed)
    return [
        rng.randint(0, 64, (int(rng.randint(lo, hi)),)).astype(np.int32)
        for _ in range(n)
    ]


def test_rule_for_rows():
    """A fifth of the pool, at least 8 rows, never over the pool."""
    assert [prefill_rows_for(s) for s in (1, 4, 8, 16, 32, 64, 128)] == [
        1, 4, 8, 8, 8, 12, 25
    ]


# --------------------------------------------------------------------- #
# 1. the program: compact == pool-wide on its rows, nothing elsewhere   #
# --------------------------------------------------------------------- #


def _filled_cache(quant, seed):
    """A pool whose every row holds something, so an untouched slot is
    told apart from a rewritten one."""
    make = init_quant_cache if quant else init_cache
    cache = make(CFG, SLOTS, MAX_LEN)
    rng = np.random.RandomState(seed)

    def fill(a):
        if a.ndim == 0:
            return a
        if a.dtype == jnp.int8:
            return jnp.asarray(rng.randint(-100, 100, a.shape), a.dtype)
        return jnp.asarray(rng.standard_normal(a.shape), a.dtype)

    return jax.tree_util.tree_map(fill, cache)


@pytest.mark.parametrize("quant", [False, True], ids=["bf", "int8"])
@pytest.mark.parametrize("g", [1, 4, 8])
@pytest.mark.parametrize(
    "rows,pending", [(4, 2), (4, 4), (8, 5), (16, 16)],
    ids=["R>pending", "R=pending", "R>pending-of-8", "R=pool"],
)
def test_compact_rows_match_pool_wide(flat_params, quant, g, rows, pending):
    """Row ``i`` of the compact call is slot ``slots[i]`` of the
    pool-wide call: same logits, same K/V rows written; a slot that was
    not handed in (or was handed in as padding) keeps its bytes."""
    rng = np.random.RandomState(100 * rows + 10 * pending + g)
    cache = _filled_cache(quant, seed=g)
    lengths = rng.randint(0, MAX_LEN - g, (SLOTS,)).astype(np.int32)
    picked = rng.permutation(SLOTS)[:pending].astype(np.int32)
    slots = np.zeros((rows,), np.int32)         # padding: slot 0, n_valid 0
    slots[:pending] = picked
    n_valid = np.zeros((rows,), np.int32)
    n_valid[:pending] = rng.randint(1, g + 1, (pending,))
    tokens = rng.randint(0, 64, (rows, g)).astype(np.int32)

    wide_tokens = np.zeros((SLOTS, g), np.int32)
    wide_valid = np.zeros((SLOTS,), np.int32)
    wide_tokens[picked] = tokens[:pending]
    wide_valid[picked] = n_valid[:pending]

    got_logits, got_cache, got_len = decode_slots(
        CFG, flat_params, jnp.asarray(tokens), cache, jnp.asarray(lengths),
        jnp.asarray(n_valid), slots=jnp.asarray(slots),
    )
    ref_logits, ref_cache, ref_len = decode_slots(
        CFG, flat_params, jnp.asarray(wide_tokens), cache,
        jnp.asarray(lengths), jnp.asarray(wide_valid),
    )
    assert got_logits.shape == (rows, g, CFG.vocab)
    assert np.array_equal(np.asarray(got_len), np.asarray(ref_len))
    for i in range(pending):
        n = int(n_valid[i])
        np.testing.assert_allclose(
            np.asarray(got_logits[i, :n]),
            np.asarray(ref_logits[picked[i], :n]), rtol=2e-5, atol=2e-5,
        )
        assert np.array_equal(
            np.asarray(got_logits[i, :n]).argmax(-1),
            np.asarray(ref_logits[picked[i], :n]).argmax(-1),
        )
    others = np.setdiff1d(np.arange(SLOTS), picked)
    for name in got_cache._fields:
        if name == "length":
            continue
        for got, ref, old in zip(
            getattr(got_cache, name), getattr(ref_cache, name),
            getattr(cache, name),
        ):
            got, ref, old = np.asarray(got), np.asarray(ref), np.asarray(old)
            # every slot outside the batch: the bytes it had
            assert np.array_equal(got[others], old[others]), name
            # the prefilled slots: what the pool-wide program wrote
            # (to a float's last bits: another batch size, another
            # matmul blocking; an int8 row may round one step apart)
            np.testing.assert_allclose(
                got[picked].astype(np.float32),
                ref[picked].astype(np.float32), rtol=2e-5,
                atol=1 if got.dtype == np.int8 else 2e-5,
            )


# --------------------------------------------------------------------- #
# 2. the engine: oldest first, all finish, every engine kind            #
# --------------------------------------------------------------------- #


def _engine(kind, flat_params, draft_params, **kw):
    common = dict(num_slots=SLOTS, max_len=MAX_LEN, prefill_chunk=4)
    common.update(kw)
    if kind == "ladder":
        common["prefill_chunk"] = (1, 2, 4, 8)
    if kind == "int8":
        common["kv_quant"] = True
    if kind == "prefix-cache":
        common["prefix_cache"] = fleet.RadixPrefixCache(min_prefix_len=2)
    if kind == "prefill-role":
        common["role"] = "prefill"
    if kind == "speculative":
        common["prefill_chunk"] = 8
        return fleet.SpeculativeEngine(
            CFG, flat_params, DRAFT_CFG, draft_params, gamma=2, **common
        )
    return Engine(CFG, flat_params, **common)


@pytest.mark.parametrize(
    "kind",
    ["plain", "ladder", "int8", "prefix-cache", "prefill-role",
     "speculative"],
)
def test_more_pending_than_rows_all_finish_exact(
    kind, flat_params, draft_params
):
    """12 prompts into a 16-slot pool whose prefill program has 8 rows:
    the step takes the 8 oldest, the other 4 wait, and every stream is
    the one ``generate`` gives."""
    eng = _engine(kind, flat_params, draft_params)
    assert eng.prefill_rows == 8
    prompts = _prompts(seed=3, n=12)
    if kind == "prefix-cache":      # a shared head, so copies happen too
        prompts = [np.concatenate([prompts[0][:3], p]) for p in prompts]
    new = 5
    rids = [eng.submit(p, new) for p in prompts]
    eng.step()
    first = [e for e in eng.timeline.events if e.name == "engine.prefill"][-1]
    assert first.fields["cap"] == 8
    assert first.fields["rows"] == 8 and first.fields["deferred"] == 4
    # the eight oldest absorbed a chunk, the four youngest nothing
    absorbed = [eng._requests[r].prefilled > 0 for r in rids]
    if kind != "prefix-cache":      # (a copied prefix counts as absorbed)
        assert absorbed == [True] * 8 + [False] * 4
    eng.run()
    assert eng.metrics.prefill_deferred_rows >= 4
    alone = None
    if kind == "int8":
        # Chunked prefill reads its earlier chunks back from int8 rows,
        # which ``generate``'s one-pass prefill does not: the reference
        # is the same engine serving each request with nobody beside it.
        alone = _engine(kind, flat_params, draft_params)
    for rid, p in zip(rids, prompts):
        if alone is not None:
            alone_rid = alone.submit(p, new)
            alone.run()
            ref = alone.result(alone_rid)
        else:
            ref = _ref(flat_params, p, new)
        if kind == "prefill-role":
            # streams leave at the first token: parked for migration
            assert eng.status(rid) == "migrating"
            assert eng.result(rid).tolist() == ref[:1].tolist(), rid
        else:
            assert eng.status(rid) == "finished"
            assert eng.result(rid).tolist() == ref.tolist(), rid


def test_first_tokens_come_in_admission_order(flat_params):
    """Equal prompts, more of them than rows: the first tokens are
    emitted in the order the requests were admitted."""
    eng = Engine(CFG, flat_params, num_slots=SLOTS, max_len=MAX_LEN,
                 prefill_chunk=4)
    order = []
    prompts = _prompts(seed=9, n=14, lo=6, hi=7)      # all 6 tokens long
    rids = [
        eng.submit(p, 2, on_token=lambda rid, tok: order.append(rid))
        for p in prompts
    ]
    eng.run()
    firsts = list(dict.fromkeys(order))               # first emission each
    assert firsts == rids


# --------------------------------------------------------------------- #
# 3. one trace a program under churn; specs                             #
# --------------------------------------------------------------------- #


@pytest.mark.parametrize("chunk", [4, (1, 2, 4, 8)], ids=["single", "ladder"])
def test_one_trace_a_program_under_churn_with_deferral(flat_params, chunk):
    """Bursts larger than ``R``, staggered arrivals, cancellations and
    slot recycling: each program's body is traced once."""
    eng = Engine(CFG, flat_params, num_slots=SLOTS, max_len=MAX_LEN,
                 prefill_chunk=chunk)
    rng = np.random.RandomState(4)
    served = []
    for burst in (12, 3, 16, 1, 9):
        prompts = _prompts(seed=int(rng.randint(1 << 30)), n=burst, lo=1)
        rids = [eng.submit(p, 3) for p in prompts]
        if burst > 8:
            assert eng.cancel(rids[burst // 2])
            rids.pop(burst // 2), prompts.pop(burst // 2)
        eng.step()
        eng.step()
        served += list(zip(rids, prompts))
    eng.run()
    stats = eng.compile_stats
    assert stats["decode"] == 1
    assert all(v <= 1 for v in stats.values()), stats
    if chunk == 4:
        assert stats == {"prefill": 1, "decode": 1}
    assert eng.metrics.prefill_deferred_rows > 0
    for rid, p in served:
        assert eng.status(rid) == "finished"
        assert eng.result(rid).tolist() == _ref(flat_params, p, 3).tolist()


def test_specs_name_the_compact_inputs_and_lint_clean(
    flat_params, draft_params
):
    """``step_input_specs`` are true statements about the programs: the
    prefill programs take ``slots [R]``, ``tokens [R, g]``, ``n_valid
    [R]``, ``finish [R]`` beside the pool-wide ``lengths`` and device
    token vector ``cur_tok``; decode stays pool-wide and its ``tokens``
    IS that vector; the speculative verify program is pool-wide at its
    bucket and takes neither.  The lint's
    churn grid and ladder walk hold at ``R < num_slots``."""
    from torchgpipe_tpu.analysis import (
        Severity, certify_speculative, lint_serving,
    )

    eng = _engine("ladder", flat_params, draft_params)
    specs = eng.step_input_specs()
    for g in (1, 2, 4, 8):
        spec = specs[f"prefill@{g}"]
        assert spec["slots"].shape == (8,)
        assert spec["tokens"].shape == (8, g)
        assert spec["n_valid"].shape == (8,)
        assert spec["lengths"].shape == (SLOTS,)
        assert spec["finish"].shape == (8,)
        assert spec["cur_tok"].shape == (SLOTS,)
    assert specs["decode"]["tokens"].shape == (SLOTS,)
    assert specs["decode"]["n_valid"].shape == (SLOTS,)
    assert "slots" not in specs["decode"]
    assert eng.program_count == 5 == len(specs)
    findings = lint_serving(eng)
    assert all(f.severity < Severity.WARNING for f in findings), [
        f.format() for f in findings
    ]

    se = _engine("speculative", flat_params, draft_params)
    specs = se.step_input_specs()
    assert specs["verify"]["tokens"].shape == (SLOTS, 8)
    assert not {"slots", "cur_tok", "finish"} & set(specs["verify"])
    assert specs["prefill"]["tokens"].shape == (8, 8)
    # prefill + decode + verify + the draft set (1 and 8)
    assert se.program_count == 5 == len(specs)
    fs = certify_speculative(se)
    assert [f.severity for f in fs] == [Severity.INFO]
    assert "verify@8" in fs[0].message
    fs = lint_serving(se)
    assert all(f.severity < Severity.WARNING for f in fs), [
        f.format() for f in fs
    ]


# --------------------------------------------------------------------- #
# 4. the counters and the span's fields                                 #
# --------------------------------------------------------------------- #


def test_fill_share_and_deferred_rows_on_a_hand_built_schedule(flat_params):
    """12 prompts of two chunks each, 8 rows a step.  Prefill steps:
    8 rows (4 wait), 8 rows (4 wait) — the first eight now decode, and
    the scheduler alternates — then 4 rows, 4 rows.  Rows 24 of capacity
    32: fill share 0.75; 8 rows were left waiting."""
    eng = Engine(CFG, flat_params, num_slots=SLOTS, max_len=MAX_LEN,
                 prefill_chunk=4)
    for p in _prompts(seed=2, n=12, lo=8, hi=9):      # all 8 tokens long
        eng.submit(p, 3)
    eng.run()
    m = eng.metrics
    assert m.prefill_steps == 4
    assert m.prefill_rows == 24
    assert m.prefill_row_capacity == 32
    assert m.prefill_deferred_rows == 8
    assert m.prefill_fill_share == 0.75
    snap = m.snapshot()
    assert snap["prefill_rows"] / snap["prefill_row_capacity"] == 0.75
    assert snap["prefill_deferred_rows"] == 8
    reg = m.registry
    assert reg.counter("serving_prefill_rows").value() == 24
    assert reg.counter("serving_prefill_row_capacity").value() == 32
    assert reg.counter("serving_prefill_deferred_rows").value() == 8
    spans = [e.fields for e in eng.timeline.events
             if e.name == "engine.prefill"][-4:]
    assert [(f["rows"], f["cap"], f["deferred"]) for f in spans] == [
        (8, 8, 4), (8, 8, 4), (4, 8, 0), (4, 8, 0)
    ]
    # Decode steps are pool-wide: occupancy's denominator is the rows
    # each step's program had.
    assert m.total_slot_steps == 32 + SLOTS * m.decode_steps


# --------------------------------------------------------------------- #
# 5. the per-row decode kernel under decode_slots                       #
# --------------------------------------------------------------------- #

# Heads of 128 (what the kernel tiles) over three 128-blocks of cache.
KCFG = TransformerConfig(
    vocab=64, dim=512, n_layers=2, n_heads=4, n_kv_heads=2,
    dtype=jnp.bfloat16,
)
KCFG32 = dataclasses.replace(KCFG, dtype=jnp.float32)
KSLOTS, KLEN = 6, 384


@pytest.fixture(scope="module")
def kernel_params():
    return _params(KCFG, 3)


@pytest.mark.parametrize("g", [1, 8])
@pytest.mark.parametrize("compact", [False, True], ids=["pool", "compact"])
@pytest.mark.parametrize("cfg,tol", [(KCFG, 0.1), (KCFG32, 2e-4)],
                         ids=["bf16", "f32"])
def test_decode_slots_through_the_kernel(monkeypatch, cfg, tol, g, compact):
    """``decode_slots`` with the Pallas kernel forced (interpret mode)
    against its dense path, on a bf16 pool (to a few of bf16's last
    bits of the logits: the attention agrees to f32's, and a hidden
    state then rounds one step apart here and there) and on an f32 pool
    (tight): the logits of the rows that did something, the cache (a
    masked row's slot bit for bit) and the lengths, with frontiers on
    both sides of a block edge, rows with ``n_valid = 0`` and, compact,
    a padded row that repeats a slot."""
    from torchgpipe_tpu.models import generation

    KCFG, kernel_params = cfg, _params(cfg, 3)
    rng = np.random.RandomState(g + 7 * compact)
    cache = init_cache(KCFG, KSLOTS, KLEN)
    assert cache.k[0].dtype == cfg.dtype
    cache = jax.tree_util.tree_map(
        lambda a: a if a.ndim == 0 else jnp.asarray(
            rng.standard_normal(a.shape), a.dtype), cache)
    lengths = np.array([0, 127, 128, 129, KLEN - g, 300], np.int32)
    if compact:
        slots = np.array([4, 1, 3, 1], np.int32)    # last row: padding
        n_valid = np.array([g, 1, g, 0], np.int32)
    else:
        slots = None
        n_valid = np.array([g, 1, 0, g, g, 0], np.int32)
    tokens = rng.randint(0, 64, (len(n_valid), g)).astype(np.int32)

    def run():
        return decode_slots(
            KCFG, kernel_params, jnp.asarray(tokens), cache,
            jnp.asarray(lengths), jnp.asarray(n_valid),
            slots=None if slots is None else jnp.asarray(slots),
        )

    ref_logits, ref_cache, ref_len = run()
    calls = []
    orig = generation._attend_chunk

    def forced(*a, **kw):
        calls.append(kw.get("slots") is not None)
        return orig(*a, **{**kw, "use_flash": True})

    monkeypatch.setattr(generation, "_attend_chunk", forced)
    got_logits, got_cache, got_len = run()
    assert calls == [compact] * KCFG.n_layers
    assert np.array_equal(np.asarray(got_len), np.asarray(ref_len))
    for i, n in enumerate(n_valid):
        np.testing.assert_allclose(
            np.asarray(got_logits[i, :n]), np.asarray(ref_logits[i, :n]),
            rtol=tol, atol=tol,
        )
    # Layer 0 writes K/V before any attention: bit-equal.  Layer 1's
    # rows follow layer 0's attention; a slot whose row did nothing
    # keeps its bytes in both.
    idle = np.setdiff1d(
        np.arange(KSLOTS),
        (np.arange(KSLOTS) if slots is None else slots)[n_valid > 0],
    )
    for name in ("k", "v"):
        got, ref, old = (getattr(c, name) for c in (got_cache, ref_cache,
                                                    cache))
        assert np.array_equal(np.asarray(got[0]), np.asarray(ref[0]))
        np.testing.assert_allclose(
            np.asarray(got[1], np.float32), np.asarray(ref[1], np.float32),
            rtol=tol, atol=tol,
        )
        for layer in range(KCFG.n_layers):
            assert np.array_equal(
                np.asarray(got[layer])[idle], np.asarray(old[layer])[idle]
            )


def test_engine_off_tpu_never_reaches_the_kernel(flat_params, monkeypatch):
    """Off TPU the dense path serves every step: with the kernel made
    unreachable the engine still gives ``generate``'s tokens, request
    for request."""
    from torchgpipe_tpu.ops import flash_attention

    def unreachable(*a, **kw):
        raise AssertionError("the decode kernel ran off TPU")

    monkeypatch.setattr(
        flash_attention, "flash_decode_attention", unreachable
    )
    eng = Engine(CFG, flat_params, num_slots=SLOTS, max_len=MAX_LEN,
                 prefill_chunk=4)
    prompts = _prompts(seed=5, n=3)
    rids = [eng.submit(p, 4) for p in prompts]
    eng.run()
    for rid, p in zip(rids, prompts):
        assert np.array_equal(eng.result(rid), _ref(flat_params, p, 4))


def test_attend_rows_counters_on_a_three_request_script(
    kernel_params, monkeypatch
):
    """Three prompts of 130, 20 and 260 tokens, chunks of 128, a pool of
    8 slots x 384 rows.  Off TPU every step reads its capacity: the
    counters are equal, and each action span carries ``rows_read`` =
    ``rows_cap``.  Where the platform is a TPU (answered for it here:
    the counter runs no program) the same frontiers count the
    128-blocks inside each row's length."""
    from torchgpipe_tpu.models import generation
    from torchgpipe_tpu.models.generation import attend_rows_counter

    eng = Engine(KCFG, kernel_params, num_slots=8, max_len=KLEN,
                 prefill_chunk=128)
    rng = np.random.RandomState(0)
    for n in (130, 20, 260):
        eng.submit(rng.randint(0, 64, (n,)).astype(np.int32), 2)
    eng.run()
    m = eng.metrics
    # prefill: 3 steps of R = 8 rows; decode: pool-wide steps of 8 rows
    assert m.prefill_steps == 3
    assert m.attend_rows_capacity == 8 * KLEN * (3 + m.decode_steps)
    assert m.attend_rows_read == m.attend_rows_capacity
    reg = m.registry
    assert reg.counter("serving_attend_rows_read").value() == (
        m.attend_rows_read)
    assert reg.counter("serving_attend_rows_capacity").value() == (
        m.attend_rows_capacity)
    snap = m.snapshot()
    assert snap["attend_rows_read"] == snap["attend_rows_capacity"]
    spans = [e.fields for e in eng.timeline.events
             if e.name in ("engine.prefill", "engine.decode")]
    for f in spans[-(3 + m.decode_steps):]:       # the ring is shared
        assert f["rows_read"] == f["rows_cap"] == 8 * KLEN

    class _Tpu:
        platform = "tpu"

    monkeypatch.setattr(generation.jax, "devices", lambda *a: [_Tpu()])
    cache = eng.pool.cache
    # The first prefill step: rows 0..2 at frontier 0 take 128, 20 and
    # 128 tokens (one block each: the chunk's last position bounds the
    # read), five padded rows take nothing.
    pos0 = np.zeros((8,), np.int32)
    n_valid = np.array([128, 20, 128, 0, 0, 0, 0, 0], np.int32)
    assert attend_rows_counter(KCFG, cache, 8, 128)(pos0, n_valid) == (
        3 * 128, 8 * KLEN)
    # The second: rows at frontier 128 read two blocks.
    pos0 = np.array([128, 128, 0, 0, 0, 0, 0, 0], np.int32)
    n_valid = np.array([2, 128, 0, 0, 0, 0, 0, 0], np.int32)
    assert attend_rows_counter(KCFG, cache, 8, 128)(pos0, n_valid) == (
        2 * 256, 8 * KLEN)
    # A decode step: slots at 131, 21 and 261 tokens, five idle.
    pos0 = np.array([131, 21, 261, 0, 0, 0, 0, 0], np.int32)
    n_valid = np.array([1, 1, 1, 0, 0, 0, 0, 0], np.int32)
    assert attend_rows_counter(KCFG, cache, 8, 1)(pos0, n_valid) == (
        256 + 128 + 384, 8 * KLEN)
    # An int8 pool attends dense: read is the capacity.
    quant = init_quant_cache(KCFG, 8, KLEN)
    assert attend_rows_counter(KCFG, quant, 8, 1)(pos0, n_valid) == (
        8 * KLEN, 8 * KLEN)
