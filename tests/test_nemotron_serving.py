"""A hybrid model on the SERVING path: Mamba-2 mixer layers whose per-slot
recurrent state (conv tail and float32 state) sits in the pool beside one
attention layer's rows, ungated relu**2 experts with a shared expert of its
own width, on one chip's share (``model_type: nemotron_h``), pinned against
the plain reference (``chipbench/reference_nemotron.py``: float32 at
``highest``, all positions at once, the mixer's recurrence position by
position, no cache).  Toy widths, seeded weights, CPU, float32.

(a) ``prefill`` then ``decode_slots`` through the pool: every position's
    LOGITS against the reference's full forward.
(b) A prompt absorbed in chunks of 1, 7 and the SSD block's size gives the
    logits and the state a single pass gives.
(c) A masked or padded row leaves the state and the conv tail bit-untouched,
    a padded row on a slot that a real row of the same call writes too.
(d) A recycled slot's old state changes nothing for its new tenant.
(e) ``Engine`` with more requests than slots: every served token against the
    reference, and the state's counters and bytes.
(f) The share ties to the model: the two held halves plus the shared expert
    once are the uncut layer.
(g) Planted faults fail the comparison: state kept in bfloat16, the step not
    masked on pads, the conv tail taken from pads, the state not reset at
    admission, the D skip dropped, the norm over all of d_inner, relu for
    relu**2, the 2.5 scale left out.
(h) What takes rows at their position refuses the state by name; the
    ``nemotron_h`` record maps and an unknown pattern letter is refused.

Tolerance: program and reference compute the same float32 mathematics in
different orders (the chunked form's products and a one-step recurrence
against a scan position by position; a sort-and-segment expert sum against
an expert at a time), so logits of size ~4 agree to about 2e-6; ``TOL``
leaves a decade of room.  The planted faults move them by 3e-4 (the state
rounded to bfloat16 between calls, the weakest: 15 x ``TOL``) to 3.9, and
each is held to five times ``TOL``.
"""

import dataclasses
import json
import pathlib
import sys
import types

import numpy as np
import pytest

import jax
import jax.numpy as jnp

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parents[1]))

from chipbench import reference_nemotron as ref  # noqa: E402
from chipbench import weights_nemotron  # noqa: E402
from torchgpipe_tpu.fleet import SpeculativeEngine  # noqa: E402
from torchgpipe_tpu.fleet.prefix_cache import RadixPrefixCache  # noqa: E402
from torchgpipe_tpu.models import generation, moe as moe_mod, ssm  # noqa: E402
from torchgpipe_tpu.models.generation import (  # noqa: E402
    beam_search,
    decode_slots,
    init_cache,
    init_quant_cache,
    prefill,
    speculative_generate,
)
from torchgpipe_tpu.models.hf_interop import config_from_hf_nemotron_h  # noqa: E402
from torchgpipe_tpu.models.kv_cache import HybridCache  # noqa: E402
from torchgpipe_tpu.models.transformer import transformer_block  # noqa: E402
from torchgpipe_tpu.serving import Engine  # noqa: E402
from torchgpipe_tpu.serving.qos import QosConfig, QosPolicy  # noqa: E402

TOL = 2e-5
CONFIG = pathlib.Path(__file__).resolve().parents[1] / "chipbench" / "configs" / "nemotron3-nano.json"
E = 16
# The configuration file's keys at toy widths: the file's pattern (one whole
# period, EMEMEMEM*), 8 heads of 16 in 2 groups of state 16, a conv of 4;
# 16 experts, 6 a token, this share holds experts 4..11; SSD blocks of 8.
TOY = dict(
    json.loads(CONFIG.read_text()), hidden_size=64, vocab_size=97, mamba_num_heads=8,
    mamba_head_dim=16, n_groups=2, ssm_state_size=16, num_attention_heads=4,
    num_key_value_heads=2, head_dim=32, moe_intermediate_size=32,
    moe_shared_expert_intermediate_size=64, n_routed_experts=8, held_first=4,
    torch_dtype="float32", chunk_size=8,
    draw={"seed": 11, "router_bias_std": 0.1},
)
TOY["reduced"] = dict(TOY["reduced"], n_routed_experts={"published": E})
MAX_LEN = 48


def program(m, **patch):
    """(TransformerConfig, MoEConfig) from the file's keys, the router at its
    published width."""
    hf = dict(m, n_routed_experts=E, **patch)
    cfg, moe = config_from_hf_nemotron_h(
        types.SimpleNamespace(**hf), held=(m["held_first"], m["n_routed_experts"]))
    return dataclasses.replace(cfg, dtype=jnp.float32), moe


@pytest.fixture(scope="module")
def model():
    cfg, moe = program(TOY)
    flat = weights_nemotron.make_flat(TOY, 5)
    return cfg, moe, flat, ref.ServeReference(TOY, flat, MAX_LEN, MAX_LEN)


def _seq(seed, n):
    return np.random.default_rng(seed).integers(0, TOY["vocab_size"], n).astype(np.int32)


def _chunked(cfg, moe, flat, cache, lengths, slot, tokens, g, pad_slot=None):
    """``tokens`` absorbed into ``slot`` in compact calls of ``g`` (the
    last one padded), with a padding row on ``pad_slot`` in every call;
    returns (every position's logits, cache, lengths)."""
    out = []
    for a in range(0, len(tokens), g):
        chunk = tokens[a:a + g]
        t = np.zeros((2, g), np.int32)
        t[0, :len(chunk)] = chunk
        slots = np.array([slot, slot if pad_slot is None else pad_slot], np.int32)
        lg, cache, lengths = decode_slots(
            cfg, flat, jnp.asarray(t), cache, lengths, jnp.array([len(chunk), 0]),
            moe=moe, slots=jnp.asarray(slots))
        out.append(np.asarray(lg[0, :len(chunk)]))
    return np.concatenate(out), cache, lengths


def _decode(cfg, moe, flat, cache, lengths, slot, tokens):
    """Teacher-forced decode of ``tokens`` in ``slot``, one pool-wide step a
    token (the other slots no-op rows)."""
    out = []
    S = lengths.shape[0]
    for tok in tokens:
        t = np.zeros((S, 1), np.int32)
        t[slot] = tok
        n = np.zeros((S,), np.int32)
        n[slot] = 1
        lg, cache, lengths = decode_slots(cfg, flat, jnp.asarray(t), cache, lengths,
                                          jnp.asarray(n), moe=moe)
        out.append(np.asarray(lg[slot, 0]))
    return np.stack(out), cache, lengths


def _scenario(cfg, moe, flat, g=8):
    """Two requests through slot 0 of a 2-slot pool: A (prefill in chunks of
    ``g`` with pads, then decode), then B in the RECYCLED slot; a padding
    row sits on slot 0 in every compact call.  Returns [(tokens, logits)]."""
    cache, lengths = init_cache(cfg, 2, MAX_LEN), jnp.zeros((2,), jnp.int32)
    got = []
    for seed, (p, d) in ((1, (13, 4)), (2, (11, 5))):
        seq = _seq(seed, p + d)
        lengths = lengths.at[0].set(0)          # admission: frontier 0
        a, cache, lengths = _chunked(cfg, moe, flat, cache, lengths, 0, seq[:p], g, 0)
        b, cache, lengths = _decode(cfg, moe, flat, cache, lengths, 0, seq[p:])
        got.append((seq, np.concatenate([a, b])))
    return got


def _gap(model_ref, got):
    return max(float(np.abs(logits - model_ref.all_logits(seq)).max()) for seq, logits in got)


def test_prefill_then_decode_slots_matches_reference(model):
    cfg, moe, flat, r = model
    seq = _seq(3, 20)
    last, cache = prefill(cfg, flat, jnp.asarray(seq[None, :12]), MAX_LEN, moe=moe)
    assert isinstance(cache, HybridCache) and int(cache.length) == 12
    want = r.all_logits(seq)
    np.testing.assert_allclose(np.asarray(last[0]), want[11], atol=TOL)
    logits, _, lengths = _decode(cfg, moe, flat, cache, jnp.array([12], jnp.int32), 0, seq[12:])
    np.testing.assert_allclose(logits, want[12:], atol=TOL)
    assert int(lengths[0]) == 20


@pytest.mark.parametrize("g", [1, 7, 8])
def test_chunked_prefill_agrees_with_one_pass(model, g):
    """Chunks of 1 (the recurrence, one step a call), 7 (the chunked form,
    pads in the last) and 8 (the SSD block, ``chunk_size``): the logits and
    the state of a single pass."""
    cfg, moe, flat, r = model
    seq = _seq(4, 19)
    _, one = prefill(cfg, flat, jnp.asarray(seq[None]), MAX_LEN, moe=moe)
    cache, lengths = init_cache(cfg, 2, MAX_LEN), jnp.zeros((2,), jnp.int32)
    logits, cache, _ = _chunked(cfg, moe, flat, cache, lengths, 1, seq, g, pad_slot=0)
    np.testing.assert_allclose(logits, r.all_logits(seq), atol=TOL)
    # The state and the tail (the input projection's last columns) up to
    # the rounding of products of other shapes.
    for a, b in zip(cache.ssm + cache.conv, one.ssm + one.conv):
        np.testing.assert_allclose(np.asarray(a[1]), np.asarray(b[0]), atol=1e-5)


def test_noop_and_padded_rows_leave_the_state_bit_untouched(model):
    cfg, moe, flat, _ = model
    cache, lengths = init_cache(cfg, 3, MAX_LEN), jnp.zeros((3,), jnp.int32)
    for slot, seed in ((0, 5), (1, 6)):
        _, cache, lengths = _chunked(cfg, moe, flat, cache, lengths, slot, _seq(seed, 9), 4)
    before = jax.tree_util.tree_map(np.asarray, (cache.conv, cache.ssm))
    # Pool-wide decode: slot 0 runs, slots 1 and 2 are no-op rows.
    _, cache, _ = _decode(cfg, moe, flat, cache, lengths, 0, _seq(7, 1))
    # Compact: slot 2 runs, a padding row sits on slot 1 (n_valid 0).
    t = jnp.asarray(_seq(8, 8).reshape(2, 4))
    _, cache, _ = decode_slots(cfg, flat, t, cache, lengths, jnp.array([4, 0]), moe=moe,
                               slots=jnp.array([2, 1]))
    for old, new in zip(before, (cache.conv, cache.ssm)):
        for o, n in zip(old, new):
            np.testing.assert_array_equal(o[1], np.asarray(n[1]))   # never ran
            assert not np.array_equal(o[0], np.asarray(n[0]))       # decoded
            assert not np.array_equal(o[2], np.asarray(n[2]))       # absorbed


def test_a_recycled_slot_starts_from_zero_state(model):
    """B in slot 0 after A gives what B in a fresh pool gives, bit for bit,
    and the reference's logits."""
    cfg, moe, flat, r = model
    (_, _), (seq, recycled) = _scenario(cfg, moe, flat)
    cache, lengths = init_cache(cfg, 2, MAX_LEN), jnp.zeros((2,), jnp.int32)
    a, cache, lengths = _chunked(cfg, moe, flat, cache, lengths, 0, seq[:11], 8, 0)
    b, _, _ = _decode(cfg, moe, flat, cache, lengths, 0, seq[11:])
    np.testing.assert_array_equal(recycled, np.concatenate([a, b]))
    np.testing.assert_allclose(recycled, r.all_logits(seq), atol=TOL)


def test_engine_serves_a_backlog_with_slot_churn(model):
    """8 requests through 3 slots: every served token is the reference's best
    (a gap under ``TOL`` where two logits nearly tie), the state counters count
    what ran, and the pool's bytes are K/V rows and states by kind."""
    cfg, moe, flat, r = model
    eng = Engine(cfg, flat, moe=moe, num_slots=3, max_len=MAX_LEN, prefill_chunk=8)
    reqs = {}
    for i in range(8):
        prompt = _seq(10 + i, 5 + 3 * i)
        reqs[eng.submit(prompt, 4 + i % 3)] = prompt
    assert eng.run() == "idle"
    for rid, prompt in reqs.items():
        served = np.asarray(eng.result(rid))
        logits = r.all_logits(np.concatenate([prompt, served]))[len(prompt) - 1:-1]
        gaps = logits.max(-1) - logits[np.arange(len(served)), served]
        assert gaps.max() <= TOL, (rid, gaps)
    snap = eng.metrics.snapshot()
    assert snap["state_zeroed_slots"] == 8
    slot = eng._slot_state_bytes
    assert slot == 4 * (3 * (128 + 2 * 2 * 16) * 4 + 8 * 16 * 16 * 4)
    assert snap["kv_pool_bytes"] == {"window": 0, "full": 3 * MAX_LEN * 2 * 2 * 32 * 4,
                                     "state": 3 * slot}
    # A decoding row reads and writes its slot's state; a prompt's first
    # chunk writes it alone.
    assert snap["state_bytes"]["decode"] > 0 and snap["state_bytes"]["prefill"] > 0
    assert snap["state_bytes"]["decode"] % (2 * slot) == 0


def test_the_two_held_halves_and_the_shared_expert_once_are_the_uncut_layer(model):
    cfg, _, _, _ = model
    m = dict(TOY, n_routed_experts=E, held_first=0)
    full = weights_nemotron.make_flat(dict(m, reduced={}), 3)[1]["mlp"]
    u = jax.random.normal(jax.random.PRNGKey(1), (1, 10, TOY["hidden_size"]), jnp.float32)
    want = ref.expert_layer(dict(m, reduced={}), full, u[0])
    shared = ref._relu2(full["shared"], u[0], False, False)
    total = 0.0
    for first in (0, E // 2):
        _, moe = program(dict(TOY, n_routed_experts=E // 2, held_first=first))
        half = dict(full, w_up=full["w_up"][first:first + E // 2],
                    w_down=full["w_down"][first:first + E // 2])
        out = moe_mod.moe_mlp(cfg, moe).apply(half, (), u, rng=None, train=False)[0]
        total = total + np.asarray(out[0]) - np.asarray(shared)
    np.testing.assert_allclose(total + np.asarray(shared), np.asarray(want), atol=TOL)


FAULTS = {
    "state_bf16": lambda mp: [
        mp.setattr(ssm, f, (lambda real: lambda *a: real(
            *a[:-1], a[-1].astype(jnp.bfloat16).astype(jnp.float32)))(getattr(ssm, f)))
        for f in ("_step", "_ssd")],
    "dt_unmasked": lambda mp: mp.setattr(ssm, "_steps", lambda dt, valid: dt),
    "tail_from_pads": (lambda real: lambda mp: mp.setattr(
        ssm, "_conv", lambda s, p, xbc, tail, n_valid: real(
            s, p, xbc, tail, jnp.where(n_valid > 0, xbc.shape[1], 0)))
    )(ssm._conv),
    "no_reset": lambda mp: mp.setattr(
        ssm, "_entering", lambda fresh, tail, state: (tail, state)),
    "full_norm": (lambda real: lambda mp: mp.setattr(
        ssm, "_gated_norm", lambda s, *a: real(dataclasses.replace(s, n_groups=1), *a))
    )(ssm._gated_norm),
    "relu": lambda mp: mp.setattr(
        moe_mod, "_ffn", lambda x, g, up, down, product=jnp.matmul:
        product(jax.nn.relu(product(x, up)), down)),
}


@pytest.mark.parametrize("fault", sorted(FAULTS) + ["no_D", "no_route_scale"])
def test_a_planted_fault_fails_the_comparison(model, fault, monkeypatch):
    cfg, moe, flat, r = model
    assert _gap(r, _scenario(cfg, moe, flat)) <= TOL
    if fault == "no_D":
        flat = [dict(p, D=jnp.zeros_like(p["D"])) if "D" in p else p for p in flat]
    elif fault == "no_route_scale":
        moe = dataclasses.replace(moe, route_scale=1.0)
    else:
        FAULTS[fault](monkeypatch)
    assert _gap(r, _scenario(cfg, moe, flat)) > 5 * TOL, fault


REFUSALS = {
    "prefix_cache": lambda cfg, moe, flat: Engine(
        cfg, flat, moe=moe, num_slots=2, max_len=MAX_LEN, prefix_cache=RadixPrefixCache()),
    "kv_row_migration": lambda cfg, moe, flat: Engine(
        cfg, flat, moe=moe, num_slots=2, max_len=MAX_LEN, role="prefill"),
    "int8_rows": lambda cfg, moe, flat: init_quant_cache(cfg, 2, MAX_LEN),
    "qos_preemption": lambda cfg, moe, flat: Engine(
        cfg, flat, moe=moe, num_slots=2, max_len=MAX_LEN, qos=QosPolicy(QosConfig())),
    "preempt_request": lambda cfg, moe, flat: Engine(
        cfg, flat, moe=moe, num_slots=2, max_len=MAX_LEN).preempt_request("r"),
    "resume_serving": lambda cfg, moe, flat: Engine(
        cfg, flat, moe=moe, num_slots=2, max_len=MAX_LEN).resume_serving(),
    "speculative_engine": lambda cfg, moe, flat: SpeculativeEngine(
        cfg, flat, cfg, flat, moe=moe, num_slots=2, max_len=MAX_LEN),
    "speculative_generate": lambda cfg, moe, flat: speculative_generate(
        cfg, flat, cfg, flat, jnp.zeros((1, 4), jnp.int32), 2, moe=moe, draft_moe=moe),
    "beam_search": lambda cfg, moe, flat: beam_search(
        cfg, flat, jnp.zeros((1, 4), jnp.int32), 2, moe=moe),
    "generate": lambda cfg, moe, flat: generation.generate(
        cfg, flat, jnp.zeros((1, 4), jnp.int32), 2, moe=moe),
    "training_block": lambda cfg, moe, flat: transformer_block(cfg),
}


@pytest.mark.parametrize("what", sorted(REFUSALS))
def test_what_takes_rows_at_positions_refuses_the_state_by_name(model, what):
    cfg, moe, flat, _ = model
    with pytest.raises(NotImplementedError, match="layer_pattern|mixer layers"):
        REFUSALS[what](cfg, moe, flat)


def test_the_nemotron_h_record_maps_and_an_unknown_letter_is_refused():
    cfg, moe = program(TOY)
    assert cfg.layer_pattern == "EMEMEMEM*" and cfg.n_layers == 9
    assert [cfg.layer_type(i) for i in (0, 1, 8)] == ["experts", "mixer", "attention"]
    s = cfg.ssm
    assert (s.n_heads, s.head_dim, s.n_groups, s.state, s.conv_kernel, s.chunk) == (
        8, 16, 2, 16, 4, 8)
    assert s.d_inner == 128 and s.conv_dim == 128 + 64
    assert not cfg.attn_layer(8).rope and cfg.attn_layer(8).window is None
    assert (moe.n_experts, moe.top_k, moe.act, moe.select, moe.scoring) == (
        E, 6, "relu2", "bias", "sigmoid")
    assert (moe.route_scale, moe.norm_topk, moe.expert_hidden, moe.shared_hidden,
            moe.held) == (2.5, True, 32, 64, (4, 8))
    for pattern in ("EM-*", "EMX*"):
        with pytest.raises(ValueError, match="not computed here"):
            program(dict(TOY, hybrid_override_pattern=pattern, num_hidden_layers=4))
    with pytest.raises(ValueError, match="mlp_hidden_act"):
        program(TOY, mlp_hidden_act="silu")
    with pytest.raises(ValueError, match="time_step_limit"):
        program(TOY, time_step_limit=[0.0, 0.1])
    program(TOY, time_step_limit=[0.0, None])          # the family's "no clip"
