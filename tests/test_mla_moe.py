"""Latent attention (MLA) with a latent cache, and sigmoid-routed experts
with a shared expert on one chip's share, pinned against the plain
reference (``chipbench/reference_axk1.py``: float32 at ``highest``, not
absorbed, no cache, all positions at once).  Toy widths, seeded weights,
CPU, float32.

(a) ``prefill``'s logits against the reference's full forward.
(b) Chunked prefill, then token-by-token decode, through ``decode_slots``
    in both its forms with a slot recycled, and through ``Engine`` with
    more requests than slots: every served position's logits (the
    engine's: every served token) against the reference, for two
    ``prefill_chunk``s; bf16 weights in the program's place fail the
    same tolerance.
(c) Absorbed against expanded attention on the same cache.
(d) YaRN's frequencies and the score scale against numbers worked by
    hand for the published A.X-K1 record.
(e) The share ties to the model: the routed parts of all the shares plus
    the shared expert once are the reference's uncut layer; the combine
    weights of the selected sum to ``route_scale``.
(f) No token is dropped under a router biased onto one held expert.
(g) Sigmoid, scale and shared expert each change the result.

Tolerances: the program and the reference compute the same float32
mathematics in different orders (absorbed against expanded products, a
sort-and-segment expert sum against an expert at a time), so logits of
size ~3 agree to a few 1e-6; ``TOL`` leaves a decade of room, and is two
decades under what rounding the weights to bfloat16 moves them (b).
"""

import dataclasses
import pathlib
import sys
import types

import numpy as np
import pytest

import jax
import jax.numpy as jnp

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parents[1]))

from chipbench import reference_axk1 as ref  # noqa: E402
from chipbench import weights_axk1  # noqa: E402
from torchgpipe_tpu import tune  # noqa: E402
from torchgpipe_tpu.fleet import SpeculativeEngine  # noqa: E402
from torchgpipe_tpu.models import mla  # noqa: E402
from torchgpipe_tpu.models.generation import (  # noqa: E402
    LatentCache,
    beam_search,
    decode_slots,
    generate,
    init_cache,
    init_quant_cache,
    prefill,
)
from torchgpipe_tpu.models.hf_interop import config_from_hf_latent_moe  # noqa: E402
from torchgpipe_tpu.models.moe import MoEConfig, _route, _scores, moe_mlp  # noqa: E402
from torchgpipe_tpu.models.transformer import (  # noqa: E402
    MLAConfig,
    TransformerConfig,
    YarnRope,
    transformer_block,
)
from torchgpipe_tpu.serving import Engine  # noqa: E402
from torchgpipe_tpu.utils.tracing import default_timeline  # noqa: E402

TOL = 5e-5
YARN = {"beta_fast": 32, "beta_slow": 1, "factor": 32, "mscale": 1, "mscale_all_dim": 1,
        "original_max_position_embeddings": 4096, "type": "yarn"}
# The configuration file's keys at toy widths: 16 experts, 4 a token, this
# share holds experts 4..7; one dense block, then two expert blocks.
TOY = {
    "hidden_size": 64, "intermediate_size": 128, "num_attention_heads": 4,
    "num_hidden_layers": 3, "vocab_size": 97, "q_lora_rank": 24, "kv_lora_rank": 16,
    "qk_nope_head_dim": 8, "qk_rope_head_dim": 4, "v_head_dim": 8,
    "first_k_dense_replace": 1, "n_routed_experts": 4, "held_first": 4,
    "moe_intermediate_size": 32, "n_shared_experts": 1, "num_experts_per_tok": 4,
    "norm_topk_prob": True, "routed_scaling_factor": 2.5, "scoring_func": "sigmoid",
    "topk_method": "none", "rms_norm_eps": 1e-6, "rope_theta": 10000,
    "rope_scaling": YARN, "tie_word_embeddings": False, "attention_bias": False,
    "moe_layer_freq": 1, "torch_dtype": "float32",
    "reduced": {"n_routed_experts": {"published": 16}},
}
E, K, EXPERT_LAYERS = 16, 4, 2


def configs(m=TOY):
    hf = dict(m, n_routed_experts=weights_axk1.published(m, "n_routed_experts"))
    return config_from_hf_latent_moe(
        types.SimpleNamespace(**hf), held=(m["held_first"], m["n_routed_experts"]))


@pytest.fixture(scope="module")
def model():
    cfg, moe = configs()
    return cfg, moe, weights_axk1.make_flat(TOY, 11)


def tokens_of(seed, n):
    return np.asarray(jax.random.randint(jax.random.PRNGKey(seed), (n,), 0, TOY["vocab_size"]))


def reference_logits(flat, tokens, m=TOY):
    return ref.ServeReference(m, flat, len(tokens), len(tokens)).all_logits(tokens)


# --------------------------------------------------------------------- #
# (a) prefill                                                            #
# --------------------------------------------------------------------- #


@pytest.mark.parametrize("length", [1, 7, 20])
def test_prefill_logits_equal_the_reference(model, length):
    cfg, moe, flat = model
    tok = tokens_of(1, 20)
    want = reference_logits(flat, tok[:length])[-1]
    got, cache = prefill(cfg, flat, jnp.asarray(tok[None, :length]), 32, moe=moe)
    assert isinstance(cache, LatentCache) and int(cache.length) == length
    assert cache.ckv[0].shape == (1, 32, 16) and cache.kpe[0].shape == (1, 32, 4)
    np.testing.assert_allclose(np.asarray(got[0]), want, atol=TOL)


def test_generate_continues_from_the_latent_cache(model):
    """``generate`` (prefill, then the single-token decode through the
    cache) picks the reference's best token at every step."""
    cfg, moe, flat = model
    prompt = tokens_of(2, 9)
    out = np.asarray(generate(cfg, flat, jnp.asarray(prompt[None]), 6, moe=moe))[0]
    logits = reference_logits(flat, np.concatenate([prompt, out]))[8:-1]
    assert ref.widest_gap(logits, out) <= TOL


# --------------------------------------------------------------------- #
# (b) chunked prefill + decode through the slot pool                     #
# --------------------------------------------------------------------- #


def serve_by_hand(cfg, moe, flat, tok, n_prompt, chunk, compact, cache, lengths, slot):
    """Teacher-force ``tok`` through slot ``slot`` of a 3-slot pool: the
    prompt in chunks of ``chunk`` (compact or pool-wide ``decode_slots``),
    the rest a token at a time pool-wide.  Returns every position's logits."""
    S, rows = lengths.shape[0], []
    for a in range(0, n_prompt, chunk):
        n = min(chunk, n_prompt - a)
        if compact:
            buf = np.zeros((2, chunk), np.int32)
            buf[0, :n] = tok[a:a + n]
            # Row 1 is padding: a valid slot index with nothing to write.
            lg, cache, lengths = decode_slots(
                cfg, flat, jnp.asarray(buf), cache, lengths, jnp.asarray([n, 0]),
                moe=moe, slots=jnp.asarray([slot, 0]))
            rows.append(np.asarray(lg[0, :n]))
        else:
            buf, nv = np.zeros((S, chunk), np.int32), np.zeros((S,), np.int32)
            buf[slot, :n], nv[slot] = tok[a:a + n], n
            lg, cache, lengths = decode_slots(
                cfg, flat, jnp.asarray(buf), cache, lengths, jnp.asarray(nv), moe=moe)
            rows.append(np.asarray(lg[slot, :n]))
    for t in tok[n_prompt:]:
        buf, nv = np.zeros((S, 1), np.int32), np.zeros((S,), np.int32)
        buf[slot, 0], nv[slot] = t, 1
        lg, cache, lengths = decode_slots(
            cfg, flat, jnp.asarray(buf), cache, lengths, jnp.asarray(nv), moe=moe)
        rows.append(np.asarray(lg[slot]))
    return np.concatenate(rows), cache, lengths


@pytest.mark.parametrize("chunk", [4, 8])
@pytest.mark.parametrize("compact", [True, False], ids=["compact", "pool-wide"])
def test_chunked_prefill_then_decode_equals_the_reference(model, chunk, compact):
    cfg, moe, flat = model
    cache, lengths = init_cache(cfg, 3, 32), jnp.zeros((3,), jnp.int32)
    first, second = tokens_of(3, 26), tokens_of(4, 19)
    got, cache, lengths = serve_by_hand(
        cfg, moe, flat, first, 17, chunk, compact, cache, lengths, slot=1)
    np.testing.assert_allclose(got, reference_logits(flat, first), atol=TOL)
    # The slot is recycled: its frontier goes back to 0, its stale rows stay.
    lengths = lengths.at[1].set(0)
    got, cache, lengths = serve_by_hand(
        cfg, moe, flat, second, 11, chunk, compact, cache, lengths, slot=1)
    np.testing.assert_allclose(got, reference_logits(flat, second), atol=TOL)
    assert np.asarray(lengths).tolist() == [0, 19, 0]
    assert not np.asarray(cache.ckv[0][0]).any() and not np.asarray(cache.ckv[0][2]).any()


def test_bf16_weights_fail_the_same_tolerance(model):
    """(b) is tight: the program on weights rounded to bfloat16 is outside
    ``TOL`` of the float32 reference by two decades."""
    cfg, moe, flat = model
    rounded = jax.tree_util.tree_map(
        lambda a: a.astype(jnp.bfloat16).astype(a.dtype) if a.ndim >= 2 else a, flat)
    tok = tokens_of(3, 26)
    got, _, _ = serve_by_hand(cfg, moe, rounded, tok, 17, 8, True,
                              init_cache(cfg, 3, 32), jnp.zeros((3,), jnp.int32), slot=1)
    assert np.abs(got - reference_logits(flat, tok)).max() > 100 * TOL


@pytest.mark.parametrize("chunk", [4, 8])
def test_engine_serves_the_references_tokens_with_slots_recycled(model, chunk):
    """Seven requests through a three-slot engine: each served token is the
    reference's best at its position (within ``TOL``), the expert counters
    add up, and every step's held-expert load is read where the host
    waits for that step (one step later, under the next step's action),
    onto that ``engine.fetch`` span and into the counters."""
    cfg, moe, flat = model
    eng = Engine(cfg, flat, moe=moe, num_slots=3, max_len=48, prefill_chunk=chunk,
                 donate=True)      # as the cell runs it: one step in flight
    rng = np.random.default_rng(chunk)
    reqs = {f"r{i}": (tokens_of(20 + i, int(rng.integers(3, 22))), int(rng.integers(2, 9)))
            for i in range(7)}
    mark = len(default_timeline().events)
    for rid, (prompt, new) in reqs.items():
        eng.submit(prompt, new, rid=rid)
    assert eng.run() == "idle"
    positions = 0
    for rid, (prompt, new) in reqs.items():
        out = eng.result(rid)
        assert len(out) == new
        logits = reference_logits(flat, np.concatenate([prompt, out]))
        assert ref.widest_gap(logits[len(prompt) - 1:-1], out) <= TOL
        positions += len(prompt) + new - 1      # the last token is never fed back
    snap = eng.metrics.snapshot()
    assert snap["moe_routed_assignments"] == positions * K * EXPERT_LAYERS
    assert 0 < snap["moe_held_assignments"] < snap["moe_routed_assignments"]
    assert not eng.read_expert_counts()     # the run ended on a fetch
    for kind in ("prefill", "decode"):
        assert snap["moe_expert_tokens_max"][kind] >= snap["moe_expert_tokens_mean"][kind] > 0
    assert eng.compile_stats == {"prefill": 1, "decode": 1}
    actions = [e for e in list(default_timeline().events)[mark:]
               if e.name in ("engine.prefill", "engine.decode")]
    fetches = [e for e in list(default_timeline().events)[mark:] if e.name == "engine.fetch"]
    # One wait a launched program: the first step waits for none, the last for two.
    assert len(fetches) == len(actions) and {e.parent for e in fetches} < {e.seq for e in actions}
    assert all(e.fields["held"] >= e.fields["max_expert"] >= 0 for e in fetches)
    kinds = {k: sum(e.name == "engine." + k for e in actions) for k in ("prefill", "decode")}
    assert {k: eng.metrics.moe_expert_tokens(k)["steps"] for k in kinds} == kinds
    assert sum(e.fields["held"] for e in fetches) == snap["moe_held_assignments"]


# --------------------------------------------------------------------- #
# (b') the latent decode kernel under ``decode_slots``                   #
# --------------------------------------------------------------------- #

# The toy model at widths the kernel's gate admits: a latent of one lane
# tile, eight heads (a sublane tile of query rows at one token a row),
# and a pool of 256 rows a slot (one block).
KTOY = dict(TOY, kv_lora_rank=128, num_attention_heads=8)
KLEN = 256


@pytest.fixture(scope="module")
def kernel_model():
    cfg, moe = configs(KTOY)
    return cfg, moe, weights_axk1.make_flat(KTOY, 11)


@pytest.fixture
def on_tpu(monkeypatch):
    """The gate's platform check answered as on a TPU: the kernel runs
    (in interpret mode, the platform being the CPU).  Counts the calls
    that reach it."""
    from torchgpipe_tpu.models import generation
    from torchgpipe_tpu.ops import flash_attention

    calls, inner = [], flash_attention.latent_decode_attention

    def counted(q_lat, *args, **kwargs):
        assert kwargs["interpret"]
        calls.append(q_lat.shape)
        return inner(q_lat, *args, **kwargs)

    monkeypatch.setattr(generation, "_on_tpu", lambda: True)
    monkeypatch.setattr(flash_attention, "latent_decode_attention", counted)
    return calls


def random_pool(cfg, slots, rows, seed=9):
    """A pool whose every row holds noise: a row the attention must not
    read, or the write must not touch, shows."""
    cache = init_cache(cfg, slots, rows)
    keys = iter(jax.random.split(jax.random.PRNGKey(seed), 2 * cfg.n_layers))
    noisy = lambda banks: [jax.random.normal(next(keys), a.shape, a.dtype) for a in banks]
    return cache._replace(ckv=noisy(cache.ckv), kpe=noisy(cache.kpe))


@pytest.mark.parametrize("chunk", [4, 8])
@pytest.mark.parametrize("compact", [True, False], ids=["compact", "pool-wide"])
def test_decode_slots_through_the_latent_kernel_equals_the_dense_path(
        kernel_model, chunk, compact, on_tpu, monkeypatch):
    """``decode_slots`` over a ``LatentCache`` with the kernel under its
    attention (both forms: the compact prefill rows and the pool-wide
    decode rows) against the dense path on the same noisy pool and
    against the plain reference; the slots the script does not write
    stay bit-identical."""
    from torchgpipe_tpu.models import generation

    cfg, moe, flat = kernel_model
    tok = tokens_of(3, 26)
    pool, lengths = random_pool(cfg, 3, KLEN), jnp.zeros((3,), jnp.int32)
    got, cache, _ = serve_by_hand(cfg, moe, flat, tok, 17, chunk, compact, pool, lengths, slot=1)
    steps = -(-17 // chunk) + 9
    assert len(on_tpu) == steps * cfg.n_layers
    assert set(on_tpu) == {(2 if compact else 3, chunk, 8, 128), (3, 1, 8, 128)}
    monkeypatch.setattr(generation, "_on_tpu", lambda: False)
    dense, dense_cache, _ = serve_by_hand(
        cfg, moe, flat, tok, 17, chunk, compact, pool, lengths, slot=1)
    assert len(on_tpu) == steps * cfg.n_layers
    np.testing.assert_allclose(got, dense, atol=TOL)
    np.testing.assert_allclose(got, reference_logits(flat, tok, KTOY), atol=TOL)
    for kernel_bank, dense_bank, before in zip(
            cache.ckv + cache.kpe, dense_cache.ckv + dense_cache.kpe, pool.ckv + pool.kpe):
        for slot in (0, 2):
            assert np.array_equal(np.asarray(kernel_bank[slot]), np.asarray(before[slot]))
        np.testing.assert_allclose(np.asarray(kernel_bank), np.asarray(dense_bank), atol=TOL)


def test_attend_rows_counter_counts_a_latent_pools_blocks(model, kernel_model, monkeypatch):
    """For a latent pool ``attend_rows_counter`` answers as for a K/V
    one: the block-rounded rows inside each live row's frontier where
    the gate admits the shapes on this platform, the capacity where
    the dense path runs (off a TPU; widths the kernel does not tile)."""
    from torchgpipe_tpu.models import generation
    from torchgpipe_tpu.models.generation import attend_rows_counter

    cfg, _, _ = kernel_model
    cache = init_cache(cfg, 4, 1024)                 # two 512-blocks a slot
    pos0 = np.array([0, 511, 512, 1000], np.int32)
    n_valid = np.array([1, 1, 0, 1], np.int32)
    cap = 4 * 1024
    assert attend_rows_counter(cfg, cache, 4, 1)(pos0, n_valid) == (cap, cap)
    monkeypatch.setattr(generation, "_on_tpu", lambda: True)
    assert attend_rows_counter(cfg, cache, 4, 1)(pos0, n_valid) == (512 + 512 + 0 + 1024, cap)
    # A chunk of 8: the chunk's last position bounds the read.
    pos0 = np.array([0, 505, 512, 1016], np.int32)
    n_valid = np.array([8, 3, 0, 8], np.int32)
    assert attend_rows_counter(cfg, cache, 4, 8)(pos0, n_valid) == (512 + 1024 + 0 + 1024, cap)
    # 384 rows go by 128-blocks; 200 rows by none (the dense path).
    assert attend_rows_counter(cfg, init_cache(cfg, 4, 384), 4, 1)(
        np.array([0, 127, 128, 383]), n_valid) == (128 + 128 + 0 + 384, 4 * 384)
    assert attend_rows_counter(cfg, init_cache(cfg, 4, 200), 4, 1)(pos0, n_valid) == (800, 800)
    # The toy model's latent of 16 is no lane tile: dense on any platform.
    toy_cfg, _, _ = model
    assert attend_rows_counter(toy_cfg, init_cache(toy_cfg, 4, 1024), 4, 1)(pos0, n_valid) == (
        cap, cap)


@pytest.mark.parametrize("q_shape,bank_shape,rope_dim,admits", [
    ((128, 1, 64, 512), (128, 4096, 512), 64, True),      # the cell's decode program
    ((25, 32, 64, 512), (128, 4096, 512), 64, True),      # and its prefill program
    ((25, 32, 64, 512), (128, 4000, 512), 64, False),     # a length no block divides
    ((25, 128, 64, 512), (128, 4096, 512), 64, False),    # an accumulator too large to stay
    ((128, 1, 64, 512), (128, 128, 512), 64, False),      # a cache too short for a dispatch
    ((128, 1, 64, 96), (128, 4096, 96), 64, False),       # a latent that is no lane tile
    ((128, 1, 4, 512), (128, 4096, 512), 64, False),      # query rows under a sublane tile
    ((128, 1, 64, 512), (128, 4096, 256), 64, False),     # a bank of another width
], ids=["decode", "prefill", "length", "accumulator", "short", "lanes", "sublanes", "width"])
def test_the_latent_kernels_gate_by_shape(q_shape, bank_shape, rope_dim, admits):
    from torchgpipe_tpu.ops.flash_attention import supports_latent_decode

    assert supports_latent_decode(q_shape, bank_shape, rope_dim) is admits


@pytest.mark.parametrize("chunk", [4, 8])
def test_engine_serves_the_references_tokens_through_the_latent_kernel(
        kernel_model, chunk, on_tpu):
    """Seven requests through a three-slot engine whose two programs
    attend through the latent decode kernel (interpret mode): each
    served token is the reference's best at its position, slots are
    recycled, and the counters say the attention read the blocks inside
    the frontiers and not the pool."""
    cfg, moe, flat = kernel_model
    eng = Engine(cfg, flat, moe=moe, num_slots=3, max_len=KLEN, prefill_chunk=chunk,
                 donate=True)
    rng = np.random.default_rng(chunk)
    reqs = {f"r{i}": (tokens_of(20 + i, int(rng.integers(3, 22))), int(rng.integers(2, 9)))
            for i in range(7)}
    for rid, (prompt, new) in reqs.items():
        eng.submit(prompt, new, rid=rid)
    assert eng.run() == "idle"
    for rid, (prompt, new) in reqs.items():
        out = eng.result(rid)
        assert len(out) == new
        logits = reference_logits(flat, np.concatenate([prompt, out]), KTOY)
        assert ref.widest_gap(logits[len(prompt) - 1:-1], out) <= TOL
    assert eng.compile_stats == {"prefill": 1, "decode": 1}
    # Each program lowered the kernel for its shapes, every layer through one call site.
    assert len(on_tpu) == 2 * cfg.n_layers
    assert set(on_tpu) == {(eng.prefill_rows, chunk, 8, 128), (3, 1, 8, 128)}
    m = eng.metrics
    steps = m.prefill_steps + m.decode_steps
    assert 0 < m.attend_rows_read < m.attend_rows_capacity
    # One block a live row, none for an idle one: every context fits a block.
    assert m.attend_rows_read % KLEN == 0 and m.attend_rows_read <= 3 * KLEN * steps


# --------------------------------------------------------------------- #
# (c) the two forms of the attend                                        #
# --------------------------------------------------------------------- #


@pytest.mark.parametrize("g", [1, 5])
def test_absorbed_equals_expanded_on_the_same_cache(model, g):
    cfg, _, flat = model
    p, ks = flat[2], jax.random.split(jax.random.PRNGKey(5), 4)
    q_nope = jax.random.normal(ks[0], (2, g, 4, 8))
    q_pe = jax.random.normal(ks[1], (2, g, 4, 4))
    ckv = jax.random.normal(ks[2], (2, 24, 16))
    kpe = jax.random.normal(ks[3], (2, 24, 4))
    pos0 = jnp.asarray([3, 17])
    a = mla.attend(cfg, p, q_nope, q_pe, ckv, kpe, pos0, absorbed=True)
    b = mla.attend(cfg, p, q_nope, q_pe, ckv, kpe, pos0, absorbed=False)
    assert a.shape == (2, g, 32)
    np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-5)


def test_the_form_follows_the_shapes():
    """``mla.absorbs``: the form with fewer FLOPs for ``g`` queries against
    ``L`` rows.  At the published sizes a decode step and a prefill chunk of
    32 over a slot's 4096 rows are absorbed (18.3 GFLOP a row a layer against
    74), a whole prompt against its own rows is expanded; the toy model's
    chunks and prompts fall on both sides, so (a) and (b) pin both forms
    against the reference."""
    m = MLAConfig(q_lora_rank=1536, kv_lora_rank=512, qk_nope_head_dim=128,
                  qk_rope_head_dim=64, v_head_dim=128)
    assert mla.absorbs(m, 1, 4096) and mla.absorbs(m, 32, 4096) and mla.absorbs(m, 1, 1)
    assert not mla.absorbs(m, 4096, 4096) and not mla.absorbs(m, 256, 4096)
    toy = configs()[0].mla
    assert mla.absorbs(toy, 8, 32) and not mla.absorbs(toy, 20, 20)


# --------------------------------------------------------------------- #
# (d) YaRN by hand                                                       #
# --------------------------------------------------------------------- #


def test_yarn_frequencies_and_score_scale_by_hand():
    yarn = YarnRope(factor=32.0, original_max_pos=4096, beta_fast=32.0, beta_slow=1.0,
                    mscale=1.0, mscale_all_dim=1.0)
    m = MLAConfig(q_lora_rank=1536, kv_lora_rank=512, qk_nope_head_dim=128,
                  qk_rope_head_dim=64, v_head_dim=128, rope_scaling=yarn)
    inv = mla.yarn_inv_freq(64, 10000.0, yarn)
    f = 10000.0 ** (-np.arange(0, 64, 2) / 64.0)
    assert inv.shape == (32,) and inv[0] == 1.0
    np.testing.assert_allclose(inv[31], 10000.0 ** (-62 / 64) / 32, rtol=1e-6)
    # The correction range is dims 10..23 (floor of 10.47, ceiling of 22.51):
    # untouched up to 10, divided by 32 from 23 on, a linear mix between.
    np.testing.assert_allclose(inv[:11], f[:11], rtol=1e-6)
    np.testing.assert_allclose(inv[23:], f[23:] / 32, rtol=1e-6)
    np.testing.assert_allclose(inv[15], f[15] * (1 - 5 / 13) + f[15] / 32 * (5 / 13), rtol=1e-6)
    assert mla.score_scale(m) == pytest.approx(0.130861, rel=1e-5)
    assert mla.rope_amplitude(m) == 1.0
    assert m.cache_row == 576
    # The reference's own arithmetic gives the same numbers.
    published = {"qk_rope_head_dim": 64, "qk_nope_head_dim": 128, "rope_theta": 10000,
                 "rope_scaling": YARN}
    np.testing.assert_allclose(ref.inv_freq(published), inv, rtol=1e-6)
    assert ref.score_scale(published) == pytest.approx(0.130861, rel=1e-5)


# --------------------------------------------------------------------- #
# (e)-(g) the expert layer                                               #
# --------------------------------------------------------------------- #

UNCUT = dict(TOY, n_routed_experts=E, held_first=0, reduced={})


@pytest.fixture(scope="module")
def expert_layer():
    """An uncut layer's params (all 16 experts, the shared expert) and
    normed states to put through it."""
    p = weights_axk1.make_flat(UNCUT, 5)[2]["mlp"]
    u = jax.random.normal(jax.random.PRNGKey(6), (2, 9, 64))
    return p, u


def share_of(p, first, count):
    return dict(p, **{k: p[k][first:first + count] for k in ("w_gate", "w_up", "w_down")})


def layer_out(cfg, moe, p, u):
    return np.asarray(moe_mlp(cfg, moe).apply(p, (), u, train=False)[0])


def test_all_shares_and_the_shared_expert_once_are_the_uncut_layer(model, expert_layer):
    cfg, moe, _ = model
    p, u = expert_layer
    routed = sum(
        layer_out(cfg, dataclasses.replace(moe, held=(first, 4), n_shared=0),
                  share_of(p, first, 4), u)
        for first in range(0, E, 4))
    one = dataclasses.replace(moe, held=(0, 4))
    shared = (layer_out(cfg, one, share_of(p, 0, 4), u)
              - layer_out(cfg, dataclasses.replace(one, n_shared=0), share_of(p, 0, 4), u))
    want = np.asarray(ref.expert_layer(UNCUT, p, u.reshape(-1, 64))).reshape(u.shape)
    np.testing.assert_allclose(routed + shared, want, atol=1e-5)
    # One share alone is the reference given the same share.
    cut = dict(UNCUT, n_routed_experts=4, held_first=8)
    np.testing.assert_allclose(
        layer_out(cfg, dataclasses.replace(moe, held=(8, 4)), share_of(p, 8, 4), u),
        np.asarray(ref.expert_layer(cut, share_of(p, 8, 4), u.reshape(-1, 64))).reshape(u.shape),
        atol=1e-5)


def test_the_selected_weights_sum_to_the_route_scale(model, expert_layer):
    _, moe, _ = model
    p, u = expert_layer
    scores = _scores(moe, u.reshape(-1, 64) @ p["router"])
    idxs, _, gates = _route(scores, K, moe)
    np.testing.assert_allclose(np.asarray(gates.sum(0)), 2.5, rtol=1e-6)
    assert all(len(set(col)) == K for col in np.asarray(idxs).T)


def test_no_token_is_dropped_when_every_token_picks_one_held_expert(model, expert_layer):
    """A router biased onto held expert 5: all 18 tokens land on it (a
    capacity of ``1.25 * K * t / E`` would keep 6 of them), and the
    result is the reference's."""
    cfg, moe, _ = model
    p, u = expert_layer
    # Every token shares a large component along dim 0, and expert 5's
    # router column reads only that: its sigmoid score is 1.0 everywhere.
    u = u.at[..., 0].set(3.0)
    mine = share_of(dict(p, router=p["router"].at[:, 5].set(0.0).at[0, 5].set(50.0)), 4, 4)
    layer = moe_mlp(cfg, moe)                                     # holds experts 4..7
    y, counts = layer.meta["forward_counts"](mine, u)
    assert np.asarray(counts).tolist()[1] == 18                   # expert 5 is local 1
    want = ref.expert_layer(TOY, mine, u.reshape(-1, 64))
    np.testing.assert_allclose(np.asarray(y).reshape(-1, 64), np.asarray(want), atol=1e-5)
    # Masked positions are left out of the count, not of the mathematics.
    valid = jnp.ones((2, 9), bool).at[1, 4:].set(False)
    y2, counts2 = layer.meta["forward_counts"](mine, u, valid)
    assert np.asarray(counts2).tolist()[1] == 13
    np.testing.assert_allclose(np.asarray(y2)[0], np.asarray(y)[0], atol=1e-6)


@pytest.mark.parametrize("change", [{"scoring": "softmax"}, {"route_scale": 1.0},
                                    {"n_shared": 0}, {"norm_topk": False}],
                         ids=lambda c: next(iter(c)))
def test_each_switch_changes_the_result(model, expert_layer, change):
    """No silent default: sigmoid, the scale, the shared expert and the
    normalisation each move the layer's output."""
    cfg, moe, _ = model
    p, u = expert_layer
    mine = share_of(p, 4, 4)
    base = layer_out(cfg, moe, mine, u)
    moved = layer_out(cfg, dataclasses.replace(moe, **change), mine, u)
    assert np.abs(moved - base).max() > 1e-2


def test_held_needs_the_dropless_path_and_a_real_range(model):
    cfg, _, _ = model
    for bad in (dict(held=(14, 4)), dict(held=(0, 4), dispatch="dense"),
                dict(held=(0, 4), ep_axis="ep"), dict(select="group_limited_greedy"),
                dict(scoring="tanh")):
        with pytest.raises(ValueError):
            moe_mlp(cfg, MoEConfig(n_experts=E, top_k=K, **bad))


# --------------------------------------------------------------------- #
# the pool, the sizing, and what refuses a latent model                  #
# --------------------------------------------------------------------- #


def test_serving_cache_bytes_counts_the_latent_row(model):
    cfg, _, _ = model
    slots, rows, row = 5, 48, (16 + 4) * 4 * TOY["num_hidden_layers"]   # float32 here
    assert tune.serving_cache_bytes(cfg, slots, rows) == slots * rows * row + 4
    half = tune.serving_cache_bytes(cfg, slots, rows, dtype=jnp.bfloat16)
    assert half == slots * rows * row // 2 + 4
    pool = Engine(cfg, model[2], moe=model[1], num_slots=slots, max_len=rows).pool
    assert pool.bytes() == slots * rows * row + 4
    assert sum(a.nbytes for a in jax.tree_util.tree_leaves(pool.cache)) == pool.bytes()
    budget = 3 * rows * row + 4 + 1000
    assert tune.serving_max_slots(cfg, rows, budget, donated=True) == 3
    # The published sizes: 1152 bytes a token a layer in bfloat16.
    big = TransformerConfig(dim=7168, n_layers=6, n_heads=64, dtype=jnp.bfloat16, mla=MLAConfig(
        q_lora_rank=1536, kv_lora_rank=512, qk_nope_head_dim=128, qk_rope_head_dim=64,
        v_head_dim=128))
    assert tune.serving_cache_bytes(big, 128, 4096) == 128 * 4096 * 1152 * 6 + 4


@pytest.mark.parametrize("kwargs", [{"kv_quant": True}, {"prefix_cache": object()},
                                    {"role": "prefill"}, {"role": "decode"}],
                         ids=lambda k: "-".join(f"{a}={b}" for a, b in k.items())[:24])
def test_engine_features_written_for_kv_rows_refuse_a_latent_model(model, kwargs):
    cfg, moe, flat = model
    with pytest.raises(NotImplementedError, match="latent"):
        Engine(cfg, flat, moe=moe, num_slots=2, max_len=16, **kwargs)


def test_other_kv_row_paths_refuse_a_latent_model(model):
    cfg, moe, flat = model
    prompt = jnp.asarray(tokens_of(1, 4)[None])
    with pytest.raises(NotImplementedError, match="latent"):
        init_quant_cache(cfg, 1, 8)
    with pytest.raises(NotImplementedError, match="latent"):
        prefill(cfg, flat, prompt, 8, moe=moe, kv_quant=True)
    with pytest.raises(NotImplementedError, match="latent"):
        beam_search(cfg, flat, prompt, 2, num_beams=2, moe=moe)
    with pytest.raises(NotImplementedError, match="latent"):
        SpeculativeEngine(cfg, flat, cfg, flat, moe=moe, num_slots=2, max_len=16)
    with pytest.raises(NotImplementedError, match="serving path"):
        transformer_block(cfg)
    eng = Engine(cfg, flat, moe=moe, num_slots=2, max_len=16)
    with pytest.raises(NotImplementedError, match="latent"):
        eng.kv_row_specs()


@pytest.mark.parametrize("key,value", [("topk_method", "noaux_tc"), ("scoring_func", "tanh"),
                                       ("attention_bias", True), ("moe_layer_freq", 2)])
def test_config_mapper_raises_on_what_it_does_not_compute(key, value):
    hf = dict(TOY, n_routed_experts=E, **{key: value})
    with pytest.raises(ValueError, match=key):
        config_from_hf_latent_moe(types.SimpleNamespace(**hf))


def test_config_mapper_maps_the_published_record():
    cfg, moe = configs()
    assert cfg.attn_kind == "mla" and cfg.mla.cache_row == 20
    assert cfg.mla.rope_scaling == YarnRope(32.0, 4096, 32.0, 1.0, 1.0, 1.0)
    assert (moe.n_experts, moe.top_k, moe.held, moe.expert_hidden) == (16, 4, (4, 4), 32)
    assert (moe.scoring, moe.norm_topk, moe.route_scale, moe.n_shared, moe.select) == (
        "sigmoid", True, 2.5, 1, "none")
    assert TransformerConfig().attn_kind == "gqa"
