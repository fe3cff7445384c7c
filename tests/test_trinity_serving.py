"""Window and full attention mixed by layer on the SERVING path: a cache
whose window layers hold a ring and whose full layers hold ``max_len`` rows,
the ``afmoe`` block (output gate, per-head q/k norm, unrotated full layers,
sandwich norms, embedding scale) and bias-corrected sigmoid routing on one
chip's share, pinned against the plain reference
(``chipbench/reference_trinity.py``: float32 at ``highest``, all positions at
once, no cache, no ring).  Toy widths, seeded weights, CPU, float32.

(a) ``prefill``, and chunked prefill then token-by-token decode through
    ``decode_slots`` in both its forms at chunk sizes 1 and ``g``, with a
    context past ``2 x (window + g)`` so that every ring wraps twice: every
    position's LOGITS against the reference's full forward; a slot recycled
    after a wrapped request leaves the next request's logits untouched.
(b) ``Engine`` with more requests than slots: every served token against the
    reference, the pool's lengths and bytes by kind, the counters by kind.
(c) The decode kernel (interpret mode) reads a ring as the dense path does.
(d) A one-entry period (Mistral's shape) builds the cache it built before,
    and the engine's two programs lower to the text they lowered to on the
    parent commit (Mistral's and A.X-K1's shapes at toy widths).
(e) The share ties to the model: the routed parts of all 8 shares plus the
    shared expert once are the reference's uncut layer; the bias moves the
    selection and not the weights.
(f) ``config_from_hf_afmoe`` round-trips the record's keys and refuses what
    it does not compute, by name.
(g) What is written for rows that lie at their position refuses a pool with
    rings by name: int8 rows, the prefix cache, KV-row migration, the
    speculative engine, ``cache_mode='ring'``; the training block refuses the
    gate and the sandwich norms.

Tolerance: program and reference compute the same float32 mathematics in
different orders (a ring read under a position mask against a band of one
long sequence, a sort-and-segment expert sum against an expert at a time), so
logits of size ~3 agree to about 1e-6; ``TOL`` leaves a decade of room and is
two decades under what rounding the weights to bfloat16 moves them.
"""

import dataclasses
import hashlib
import pathlib
import sys
import types

import numpy as np
import pytest

import jax
import jax.numpy as jnp

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parents[1]))

from chipbench import reference_trinity as ref  # noqa: E402
from chipbench import weights, weights_axk1, weights_trinity  # noqa: E402
from chipbench.builders.spmd_train import program_config  # noqa: E402
from torchgpipe_tpu.fleet import SpeculativeEngine  # noqa: E402
from torchgpipe_tpu.fleet.prefix_cache import RadixPrefixCache  # noqa: E402
from torchgpipe_tpu.models import generation, kv_cache  # noqa: E402
from torchgpipe_tpu.models.generation import (  # noqa: E402
    decode_slots,
    generate,
    init_cache,
    init_quant_cache,
    prefill,
)
from torchgpipe_tpu.models.hf_interop import (  # noqa: E402
    config_from_hf_afmoe,
    config_from_hf_latent_moe,
)
from torchgpipe_tpu.models.moe import MoEConfig, _route, _scores, moe_mlp  # noqa: E402
from torchgpipe_tpu.models.transformer import (  # noqa: E402
    AttnLayer,
    TransformerConfig,
    transformer_block,
)
from torchgpipe_tpu.ops.flash_attention import (  # noqa: E402
    decode_rows_read,
    flash_decode_attention,
)
from torchgpipe_tpu.serving import Engine  # noqa: E402
from torchgpipe_tpu.serving.cache_pool import CachePool  # noqa: E402

TOL = 2e-5
WINDOW, E, K = 8, 16, 4
# The configuration file's keys at toy widths: a dense layer, then one whole
# period (s s f s after the leading s: "s s s f s"); 16 experts, 4 a token,
# this share holds experts 4..7; heads wider than hidden / heads, as published.
TOY = {
    "hidden_size": 64, "intermediate_size": 128, "num_attention_heads": 4,
    "num_key_value_heads": 2, "head_dim": 32, "vocab_size": 97, "num_hidden_layers": 5,
    "num_dense_layers": 1,
    "layer_types": ["sliding_attention"] * 3 + ["full_attention", "sliding_attention"],
    "sliding_window": WINDOW, "num_experts": 4, "held_first": 4, "num_experts_per_tok": K,
    "moe_intermediate_size": 32, "num_shared_experts": 1, "route_norm": True,
    "route_scale": 2.448, "score_func": "sigmoid", "rms_norm_eps": 1e-5, "rope_theta": 10000,
    "rope_scaling": None, "mup_enabled": True, "n_group": 1, "topk_group": 1,
    "num_expert_groups": 1, "num_limited_groups": 1, "tie_word_embeddings": False,
    "hidden_act": "silu", "load_balance_coeff": 5e-5, "torch_dtype": "float32",
    "reduced": {"num_experts": {"published": E}, "num_hidden_layers": {"published": 60}},
    "draw": {"seed": 11, "router_bias_std": 0.1},
}
MAX_LEN, T = 64, 45                 # 45 > 2 x (8 + 4): every ring wraps twice


def program(m, **patch):
    """(TransformerConfig, MoEConfig) from the file's keys, the router at its
    published width."""
    hf = dict(m, num_experts=weights_axk1.published(m, "num_experts"), **patch)
    cfg, moe = config_from_hf_afmoe(
        types.SimpleNamespace(**hf), held=(m["held_first"], m["num_experts"]))
    return dataclasses.replace(cfg, dtype=jnp.float32), moe


@pytest.fixture(autouse=True)
def small_rings(monkeypatch):
    """Rings of the toy window: rounded to 4 rows, not to the kernel's 512."""
    monkeypatch.setattr(kv_cache, "RING_GRANULE", 4)


@pytest.fixture(scope="module")
def model():
    cfg, moe = program(TOY)
    return cfg, moe, weights_trinity.make_flat(TOY, 3)


@pytest.fixture(scope="module")
def sequence(model):
    tokens = np.random.default_rng(0).integers(0, TOY["vocab_size"], size=T).astype(np.int32)
    return tokens, ref.ServeReference(TOY, model[2], T, T).all_logits(tokens)


def served_logits(model, tokens, g, compact, cache, lengths, slot, chunked=30):
    """``tokens`` through ``decode_slots`` into ``slot``: chunks of ``g`` up
    to position ``chunked``, then a token at a time.  Returns every
    position's logits and the cache and frontiers left behind."""
    cfg, moe, flat = model
    S = lengths.shape[0]

    @jax.jit
    def step(cache, lengths, toks, n_valid, slots):
        return decode_slots(cfg, flat, toks, cache, lengths, n_valid, moe=moe, slots=slots)[:3]

    got = np.zeros((len(tokens), TOY["vocab_size"]), np.float32)
    pos = 0
    while pos < len(tokens):
        width = g if pos < chunked else 1
        take = min(width, len(tokens) - pos)
        rows = 2 if compact else S
        row = 0 if compact else slot
        toks, n_valid = np.zeros((rows, width), np.int32), np.zeros((rows,), np.int32)
        toks[row, :take], n_valid[row] = tokens[pos:pos + take], take
        # Compact: row 0 is ``slot``, row 1 a padded row naming the same slot.
        slots = jnp.asarray([slot, slot], jnp.int32) if compact else None
        logits, cache, lengths = step(cache, lengths, jnp.asarray(toks),
                                      jnp.asarray(n_valid), slots)
        got[pos:pos + take] = np.asarray(logits[row, :take])
        pos += take
    return got, cache, lengths


# --- (a) ------------------------------------------------------------------- #


def test_the_pool_holds_a_ring_in_window_layers_and_max_len_rows_in_full(model):
    cfg = model[0]
    assert kv_cache.layer_rows(cfg, MAX_LEN, chunk=4) == [12, 12, 12, MAX_LEN, 12]
    assert kv_cache.layer_rows(cfg, MAX_LEN, chunk=1) == [8, 8, 8, MAX_LEN, 8]
    assert kv_cache.layer_rows(cfg, 10, chunk=4) == [10] * 5     # never past max_len
    cache = init_cache(cfg, 3, MAX_LEN, chunk=4)
    assert kv_cache.bank_rows(cache) == [12, 12, 12, MAX_LEN, 12]
    assert kv_cache._cache_rows(cache) == MAX_LEN
    assert [kv_cache.ring_layer(cfg, i) for i in range(5)] == [True] * 3 + [False, True]


@pytest.mark.parametrize("compact", [False, True], ids=["pool-wide", "compact"])
@pytest.mark.parametrize("g", [1, 4])
def test_served_logits_equal_the_plain_forward_after_the_ring_wrapped_twice(
        model, sequence, g, compact):
    tokens, want = sequence
    cache = init_cache(model[0], 3, MAX_LEN, chunk=g)
    assert T > 2 * max(kv_cache.bank_rows(cache)[0], WINDOW + g)
    got, cache, lengths = served_logits(
        model, tokens, g, compact, cache, jnp.zeros((3,), jnp.int32), slot=1)
    np.testing.assert_allclose(got, want, atol=TOL)
    assert list(np.asarray(lengths)) == [0, T, 0]
    # The slot is recycled: its stale rows, ring rows among them, are dead
    # under the mask of the position a row holds.
    again = np.random.default_rng(1).integers(0, TOY["vocab_size"], size=19).astype(np.int32)
    got, _, _ = served_logits(model, again, g, compact, cache,
                              lengths.at[1].set(0), slot=1, chunked=8)
    np.testing.assert_allclose(
        got, ref.ServeReference(TOY, model[2], 19, 19).all_logits(again), atol=TOL)


def test_bf16_weights_fail_the_tolerance_the_program_keeps(model, sequence):
    tokens, want = sequence
    cfg, moe, flat = model
    rounded = jax.tree_util.tree_map(
        lambda a: a.astype(jnp.bfloat16).astype(a.dtype) if a.ndim >= 2 else a, flat)
    cache = init_cache(cfg, 3, MAX_LEN, chunk=4)
    got, _, _ = served_logits((cfg, moe, rounded), tokens, 4, False, cache,
                              jnp.zeros((3,), jnp.int32), slot=0)
    assert np.abs(got - want).max() > 100 * TOL


def test_one_shot_prefill_then_decode_through_the_same_cache(model, sequence):
    """``prefill`` banks a prompt past the ring's length into each window
    layer's ring; ``decode_slots`` and ``generate`` go on from there."""
    tokens, want = sequence
    cfg, moe, flat = model
    logits, cache = prefill(cfg, flat, jnp.asarray(tokens[None, :30]), MAX_LEN, moe=moe)
    np.testing.assert_allclose(np.asarray(logits[0]), want[29], atol=TOL)
    assert kv_cache.bank_rows(cache) == [8, 8, 8, MAX_LEN, 8]
    lengths = jnp.asarray([30], jnp.int32)
    for pos in range(30, T):
        logits, cache, lengths = decode_slots(
            cfg, flat, jnp.asarray(tokens[None, pos:pos + 1]), cache, lengths,
            jnp.ones((1,), jnp.int32), moe=moe)[:3]
        np.testing.assert_allclose(np.asarray(logits[0, 0]), want[pos], atol=TOL)
    # Greedy generation is the argmax chain of the reference's own logits.
    out = np.asarray(generate(cfg, flat, jnp.asarray(tokens[None, :30]), 6, moe=moe))[0]
    full = np.concatenate([tokens[:30], out])
    again = ref.ServeReference(TOY, flat, len(full), len(full)).all_logits(full)
    gaps = again[29:-1].max(-1) - again[29:-1][np.arange(6), out]
    assert gaps.max() < TOL


def test_a_ring_too_short_for_the_chunk_is_refused_by_name(model):
    cfg, moe, flat = model
    cache = init_cache(cfg, 2, MAX_LEN, chunk=1)
    with pytest.raises(ValueError, match="ring of 8 rows.*chunk of 4"):
        decode_slots(cfg, flat, jnp.zeros((2, 4), jnp.int32), cache,
                     jnp.zeros((2,), jnp.int32), jnp.ones((2,), jnp.int32), moe=moe)


# --- (b) ------------------------------------------------------------------- #


def test_the_engine_serves_the_record_through_the_mixed_pool(model):
    cfg, moe, flat = model
    eng = Engine(cfg, flat, moe=moe, num_slots=2, max_len=MAX_LEN, prefill_chunk=4)
    assert kv_cache.bank_rows(eng.pool.cache) == [12, 12, 12, MAX_LEN, 12]
    row = 2 * cfg.kv_heads * cfg.head_dim * 4                    # K and V, float32
    by_kind = eng.pool.bytes_by_kind()
    assert by_kind == {"window": 4 * 2 * 12 * row, "full": 2 * MAX_LEN * row}
    assert eng.pool.bytes() == sum(by_kind.values()) + 4         # + the length scalar
    assert eng.metrics.snapshot()["kv_pool_bytes"] == by_kind
    rng = np.random.default_rng(5)
    prompts = [rng.integers(0, TOY["vocab_size"], size=n).astype(np.int32) for n in (37, 9, 26)]
    rids = [eng.submit(p, 12) for p in prompts]
    assert eng.run() == "idle"
    for rid, prompt in zip(rids, prompts):
        out = eng.result(rid)
        full = np.concatenate([prompt, out])
        logits = ref.ServeReference(TOY, flat, len(full), len(full)).all_logits(full)
        chose = logits[len(prompt) - 1:-1]
        assert (chose.max(-1) - chose[np.arange(len(out)), out]).max() < TOL
    snap = eng.metrics.snapshot()
    kinds = snap["attend_rows_by_kind"]
    # Off TPU the dense path reads a layer's whole length: rows x its length.
    assert kinds["window"]["read"] == kinds["window"]["capacity"] > 0
    assert kinds["full"]["capacity"] * 12 == kinds["window"]["capacity"] * MAX_LEN
    assert snap["attend_rows_capacity"] == sum(k["capacity"] for k in kinds.values())
    fields = [e.fields for e in eng.timeline.events if e.name == "engine.decode"]
    assert fields and all(
        f["rows_cap"] == f["rows_cap_window"] + f["rows_cap_full"] for f in fields[-3:])


def test_ring_rows_are_counted_by_the_blocks_of_positions_a_band_touches():
    """``decode_rows_read`` on a ring: frontiers past the ring's length, the
    band's blocks of positions, one more than the ring has where the band
    starts inside a block."""
    pos0 = np.array([0, 100, 511, 5000, 5119, 9000])
    read = decode_rows_read(pos0, pos0 + 1, 4096, 512)
    blocks = [1, 1, 1, (5000 // 512) - ((5000 - 4095) // 512) + 1,
              (5119 // 512) - ((5119 - 4095) // 512) + 1,
              (9000 // 512) - ((9000 - 4095) // 512) + 1]
    assert read == 512 * sum(blocks) and 9 in blocks and 10 not in blocks
    # A chunk of 32 whose last query opens a block of positions.
    assert decode_rows_read(np.array([4090 + 4096]), np.array([4090 + 4096 + 32]), 4096, 512) == (
        512 * ((8217 // 512) - ((8186 - 4095) // 512) + 1))


# --- (c) ------------------------------------------------------------------- #


@pytest.mark.parametrize("g", [1, 8])
def test_the_decode_kernel_reads_a_ring_as_the_dense_path_does(g):
    """Interpret mode, 128-row blocks: a ring of 256 rows under a window of
    200, rows at frontiers before, at and far past the ring's length."""
    window, ring, nkv, nh, hd = 200, 256, 2, 4, 128
    rng = np.random.default_rng(g)
    pos0 = jnp.asarray([0, 57, 250, 255, 300, 777, 1500 - g], jnp.int32)
    b = pos0.shape[0]
    # A long K/V history a row; the ring holds the newest row of each residue.
    hist_k = rng.standard_normal((b, 1500, nkv, hd)).astype(np.float32)
    hist_v = rng.standard_normal((b, 1500, nkv, hd)).astype(np.float32)
    ck, cv = np.zeros((b, ring, nkv, hd), np.float32), np.zeros((b, ring, nkv, hd), np.float32)
    for i, p0 in enumerate(np.asarray(pos0)):
        for p in range(max(0, p0 + g - ring), p0 + g):
            ck[i, p % ring], cv[i, p % ring] = hist_k[i, p], hist_v[i, p]
    q = jnp.asarray(rng.standard_normal((b, g, nh, hd)).astype(np.float32))
    # The oracle: plain banded attention over the history itself.
    want = generation._attend_chunk(
        q, jnp.asarray(hist_k), jnp.asarray(hist_v), pos0, window, use_flash=False)
    dense = generation._attend_chunk(
        q, jnp.asarray(ck), jnp.asarray(cv), pos0, window, use_flash=False, ring=True)
    np.testing.assert_allclose(np.asarray(dense), np.asarray(want), atol=2e-6)
    slots = jnp.arange(b)[::-1]
    kernel = flash_decode_attention(
        q, jnp.asarray(ck)[::-1], jnp.asarray(cv)[::-1], pos0, window=window, slots=slots,
        lengths=pos0 + g, ring=True, interpret=True)
    np.testing.assert_allclose(np.asarray(kernel), np.asarray(want), atol=2e-5)
    with pytest.raises(ValueError, match="ring of 256 rows"):
        flash_decode_attention(q, jnp.asarray(ck), jnp.asarray(cv), pos0, window=300,
                               ring=True, interpret=True)


# --- (d) ------------------------------------------------------------------- #

MISTRAL = {"hidden_size": 64, "intermediate_size": 128, "num_attention_heads": 4,
           "num_key_value_heads": 2, "vocab_size": 256, "num_hidden_layers": 2,
           "sliding_window": 64, "rope_theta": 10000.0, "rms_norm_eps": 1e-5,
           "torch_dtype": "bfloat16", "tie_word_embeddings": False}
AXK1 = {"hidden_size": 64, "intermediate_size": 128, "num_attention_heads": 4,
        "num_hidden_layers": 3, "vocab_size": 97, "q_lora_rank": 24, "kv_lora_rank": 16,
        "qk_nope_head_dim": 8, "qk_rope_head_dim": 4, "v_head_dim": 8,
        "first_k_dense_replace": 1, "n_routed_experts": 4, "held_first": 4,
        "moe_intermediate_size": 32, "n_shared_experts": 1, "num_experts_per_tok": 4,
        "norm_topk_prob": True, "routed_scaling_factor": 2.5, "scoring_func": "sigmoid",
        "topk_method": "none", "rms_norm_eps": 1e-6, "rope_theta": 10000,
        "rope_scaling": {"beta_fast": 32, "beta_slow": 1, "factor": 32, "mscale": 1,
                         "mscale_all_dim": 1, "original_max_position_embeddings": 4096,
                         "type": "yarn"},
        "torch_dtype": "bfloat16", "reduced": {"n_routed_experts": {"published": 16}}}
# sha256 of ``jit(...).lower(...).as_text()`` of the two step programs of an
# engine of 4 slots x 64 rows, chunk 8, donating, on the PARENT commit
# (b4f9e18, PR 33; the same script run in a clone of it, jax 0.9.0, CPU).
# The prefill programs re-pinned where the chunked prefill came to run the
# head at each row's sampled position alone, with no per-position grid
# (``decode_slots(logits_at=)``): the decode programs are as they were.
PARENT_PROGRAMS = {
    "mistral-7b": ("2ef42b7a580b7787410b630aa47410ab65e364387eccf81f3ea8260dee9ec4be",
                   "196be28c92b082268abba9b6136f3a7ae8e74c51bc0eb78a44ccf0f160c13d7c"),
    # Re-pinned by PR 37: the served expert sum's combine gathers back by
    # the inverse sort and sums the k choices in float32 where it
    # scatter-added, so both programs lower anew.
    "axk1": ("2079a677a63773c81bc046c551168b0a78cebe1f85367dd6ecba63f85ec5b91f",
             "c60b8f8734c87ca39534b5078db09cc6e0d1d2cc832f8f9bb7fa3cd32a9fe966"),
}


def toy_engine(name, dtype="bfloat16"):
    sizes = dict(num_slots=4, max_len=64, prefill_chunk=8, donate=True)
    if name == "mistral-7b":
        m = dict(MISTRAL, torch_dtype=dtype)
        return Engine(program_config(m), weights.make_flat(m, 1), **sizes)
    m = dict(AXK1, torch_dtype=dtype)
    cfg, moe = config_from_hf_latent_moe(
        types.SimpleNamespace(**dict(m, n_routed_experts=16)), held=(4, 4))
    return Engine(dataclasses.replace(cfg, dtype=weights.DTYPES[dtype]),
                  weights_axk1.make_flat(m, 1), moe=moe, **sizes)


@pytest.mark.parametrize("name", sorted(PARENT_PROGRAMS))
def test_a_one_entry_period_keeps_its_cache_and_its_programs(name):
    eng = toy_engine(name)
    assert kv_cache.bank_rows(eng.pool.cache) == [64] * eng.cfg.n_layers
    assert not any(kv_cache.ring_layer(eng.cfg, i) for i in range(eng.cfg.n_layers))
    S, R, g = eng.pool.num_slots, eng.prefill_rows, eng.prefill_chunk

    def i32(*shape):
        return jnp.zeros(shape, jnp.int32)

    texts = (
        eng._prefill_fns["prefill"].lower(
            eng.params, eng.pool.cache, i32(S), i32(S), i32(R), i32(R, g), i32(R),
            jnp.zeros((R,), bool), eng._key).as_text(),
        eng._decode_fn.lower(
            eng.params, eng.pool.cache, i32(S), i32(S), i32(S), eng._key).as_text(),
    )
    assert tuple(hashlib.sha256(t.encode()).hexdigest() for t in texts) == PARENT_PROGRAMS[name]
    # Its counters are one kind's, and the unlabelled pair is that kind's
    # (served in float32: this CPU has no bfloat16 product of MLA's form).
    eng = toy_engine(name, "float32")
    kind = "full" if eng.cfg.mla is not None else "window"
    eng.submit(np.arange(11, dtype=np.int32), 3)
    assert eng.run() == "idle"
    kinds = eng.metrics.snapshot()["attend_rows_by_kind"]
    other = "window" if kind == "full" else "full"
    assert kinds[other] == {"read": 0, "capacity": 0}
    assert kinds[kind]["capacity"] == eng.metrics.attend_rows_capacity > 0


# --- (e) ------------------------------------------------------------------- #


@pytest.fixture(scope="module")
def expert_layer(model):
    """An expert layer's params with ALL 16 experts, and normed states."""
    uncut = dict(TOY, num_experts=E, held_first=0, reduced={})
    p = weights_trinity.make_flat(uncut, 11)[2]["mlp"]
    u = jax.random.normal(jax.random.PRNGKey(2), (3, 6, 64), jnp.float32)
    return uncut, p, u


def share_of(p, first, count):
    cut = {k: p[k][first:first + count] for k in ("w_gate", "w_up", "w_down")}
    return dict(p, **cut)


def layer_out(cfg, moe, p, u):
    return np.asarray(moe_mlp(cfg, moe).apply(p, (), u, rng=None, train=False)[0])


def test_all_eight_shares_and_the_shared_expert_once_are_the_uncut_layer(model, expert_layer):
    cfg, moe, _ = model
    uncut, p, u = expert_layer
    routed = sum(
        layer_out(cfg, dataclasses.replace(moe, held=(first, 2), n_shared=0),
                  share_of(p, first, 2), u)
        for first in range(0, E, 2))                              # 8 shares of 2 experts
    one = dataclasses.replace(moe, held=(0, 2))
    shared = (layer_out(cfg, one, share_of(p, 0, 2), u)
              - layer_out(cfg, dataclasses.replace(one, n_shared=0), share_of(p, 0, 2), u))
    want = np.asarray(ref.expert_layer(uncut, p, u.reshape(-1, 64))).reshape(u.shape)
    np.testing.assert_allclose(routed + shared, want, atol=1e-5)
    # One share alone is the reference given the same share.
    cut = dict(uncut, num_experts=4, held_first=8)
    np.testing.assert_allclose(
        layer_out(cfg, dataclasses.replace(moe, held=(8, 4)), share_of(p, 8, 4), u),
        np.asarray(ref.expert_layer(cut, share_of(p, 8, 4), u.reshape(-1, 64))).reshape(u.shape),
        atol=1e-5)


def test_the_bias_moves_the_selection_and_not_the_weights(model, expert_layer):
    _, moe, _ = model
    uncut, p, u = expert_layer
    scores = _scores(moe, u.reshape(-1, 64) @ p["router"])
    idxs, _, gates = _route(scores, K, moe, p["router_bias"])
    plain, _, _ = _route(scores, K, dataclasses.replace(moe, select="none"))
    chosen, unbiased = np.asarray(idxs).T, np.asarray(plain).T
    assert any(set(a) != set(b) for a, b in zip(chosen, unbiased))    # the bias is felt
    want = np.sort(np.argsort(-np.asarray(scores + p["router_bias"]), -1)[:, :K], -1)
    np.testing.assert_array_equal(np.sort(chosen, -1), want)
    # Weights: the scores WITHOUT the bias at the chosen, over their sum, scaled.
    at = np.take_along_axis(np.asarray(scores), chosen, -1)
    np.testing.assert_allclose(np.asarray(gates).T, 2.448 * at / at.sum(-1, keepdims=True),
                               rtol=1e-6)
    w = np.asarray(ref.route(uncut, p, u.reshape(-1, 64)))
    np.testing.assert_allclose(np.take_along_axis(w, chosen, -1), np.asarray(gates).T, rtol=1e-5)
    assert ((w > 0).sum(-1) == K).all()


# --- (f) ------------------------------------------------------------------- #


def test_config_from_hf_afmoe_round_trips_the_records_keys():
    cfg, moe = program(TOY)
    assert (cfg.vocab, cfg.dim, cfg.n_layers, cfg.n_heads, cfg.kv_heads, cfg.head_dim) == (
        97, 64, 5, 4, 2, 32)
    assert cfg.mlp_hidden == TOY["intermediate_size"] and cfg.norm_eps == 1e-5
    assert cfg.qk_norm and cfg.attn_gate and cfg.sandwich_norm and not cfg.tie_embeddings
    assert cfg.embed_scale == 8.0                                # sqrt(64): mup_enabled
    assert cfg.attn_layers == (AttnLayer(WINDOW, 10000.0),) * 3 + (
        AttnLayer(None, 10000.0, rope=False),)
    assert [cfg.attn_layer(i).window for i in range(5)] == [8, 8, 8, None, 8]
    assert moe == MoEConfig(
        n_experts=E, top_k=K, dispatch="dropless", scoring="sigmoid", norm_topk=True,
        route_scale=2.448, n_shared=1, expert_hidden=32, held=(4, 4), select="bias")
    assert program(TOY, mup_enabled=False)[0].embed_scale is None
    assert program(TOY, score_func="softmax")[1].scoring == "softmax"


@pytest.mark.parametrize("patch, named", [
    ({"score_func": "tanh"}, "score_func='tanh'"),
    ({"num_limited_groups": 2}, "num_limited_groups=2"),
    ({"n_group": 4}, "n_group=4"),
    ({"rope_scaling": {"type": "yarn", "factor": 4}}, "rope_scaling"),
    ({"layer_types": ["sliding_attention", "linear_attention"]}, "linear_attention"),
])
def test_config_from_hf_afmoe_refuses_what_it_does_not_compute(patch, named):
    with pytest.raises(ValueError, match=named):
        program(TOY, **patch)


# --- (g) ------------------------------------------------------------------- #


def _engine(model, **kwargs):
    cfg, moe, flat = model
    return Engine(cfg, flat, moe=moe, num_slots=2, max_len=MAX_LEN, prefill_chunk=4, **kwargs)


def test_int8_rows_refuse_a_pool_with_rings(model):
    with pytest.raises(NotImplementedError, match="int8 QuantKVCache.*rings"):
        _engine(model, kv_quant=True)
    with pytest.raises(NotImplementedError, match="int8 QuantKVCache.*rings"):
        init_quant_cache(model[0], 2, MAX_LEN)
    with pytest.raises(NotImplementedError, match="int8 QuantKVCache.*rings"):
        CachePool(model[0], 2, MAX_LEN, kv_quant=True)


def test_the_prefix_cache_refuses_a_pool_with_rings(model):
    with pytest.raises(NotImplementedError, match="prefix cache.*rings"):
        _engine(model, prefix_cache=RadixPrefixCache())


def test_kv_row_migration_refuses_a_pool_with_rings(model):
    for role in ("prefill", "decode"):
        with pytest.raises(NotImplementedError, match="KV-row migration.*rings"):
            _engine(model, role=role)
    eng = _engine(model)
    rid = eng.submit(np.arange(6, dtype=np.int32), 2)
    eng.step()
    with pytest.raises(NotImplementedError, match="export_kv_rows.*rings"):
        eng.export_kv_rows(eng._requests[rid])
    with pytest.raises(NotImplementedError, match="ingest_migration.*rings"):
        eng.ingest_migration(rid="m", prompt=np.arange(4), max_new_tokens=2, rows={},
                             last_token=1)
    with pytest.raises(NotImplementedError, match="kv_row_specs.*rings"):
        eng.kv_row_specs()


def test_the_speculative_engine_refuses_a_pool_with_rings(model):
    cfg, moe, flat = model
    draft = TransformerConfig(vocab=97, dim=32, n_layers=1, n_heads=2)
    with pytest.raises(NotImplementedError, match="speculative.*rings"):
        SpeculativeEngine(cfg, flat, draft, [], moe=moe, num_slots=2, max_len=MAX_LEN,
                          prefill_chunk=4)
    with pytest.raises(NotImplementedError, match="speculative.*rings"):
        generation.speculative_generate(cfg, flat, cfg, flat, jnp.zeros((1, 4), jnp.int32), 2,
                                        moe=moe, draft_moe=moe)


def test_cache_mode_ring_refuses_a_mixed_period(model):
    cfg, moe, flat = model
    with pytest.raises(NotImplementedError, match="cache_mode='ring'.*rings"):
        generate(cfg, flat, jnp.zeros((1, 4), jnp.int32), 2, moe=moe, cache_mode="ring")
    with pytest.raises(NotImplementedError, match="ring=True.*rings"):
        prefill(cfg, flat, jnp.zeros((1, 4), jnp.int32), MAX_LEN, moe=moe, ring=True)


@pytest.mark.parametrize("flag, named", [("attn_gate", "attn_gate"),
                                         ("sandwich_norm", "sandwich_norm")])
def test_the_training_block_refuses_what_it_does_not_compute(flag, named):
    cfg = TransformerConfig(vocab=97, dim=32, n_layers=1, n_heads=2, **{flag: True})
    with pytest.raises(NotImplementedError, match=named + ".*serving path only"):
        transformer_block(cfg)


def test_the_training_block_leaves_a_full_layer_unrotated():
    """``AttnLayer(rope=False)`` is read by the training block too: shifting
    every position of an unrotated full layer's input changes nothing but
    what causality changes, so its last row depends on the rows, not on
    where they stand."""
    from torchgpipe_tpu.models.transformer import _maybe_rope

    cfg = TransformerConfig(vocab=97, dim=32, n_layers=2, n_heads=2, attn_layers=(
        AttnLayer(4, 10000.0), AttnLayer(None, 10000.0, rope=False)))
    x = jax.random.normal(jax.random.PRNGKey(0), (1, 6, 2, 16))
    assert not np.allclose(np.asarray(_maybe_rope(cfg, x, 3, 0)), np.asarray(x))
    np.testing.assert_array_equal(np.asarray(_maybe_rope(cfg, x, 3, 1)), np.asarray(x))
