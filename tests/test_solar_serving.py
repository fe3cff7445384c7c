"""A hybrid model of gated delta-rule layers on the SERVING path: Kimi Delta
Attention mixers (per-channel decay, beta up to 2) whose per-slot float32 state
and conv tail sit in the pool beside one gated, unrotated full layer's rows,
SwiGLU experts chosen by biased sigmoid scores beside a shared expert, on one
chip's share (``model_type: solar_open2``), pinned against the plain reference
(``chipbench/reference_solar.py``: float32 at ``highest``, all positions at
once, the delta rule's recurrence position by position, no cache).  Toy
widths, seeded weights, CPU, float32.

(a) ``prefill`` then ``decode_slots`` through the pool: every position's
    LOGITS against the reference's full forward.
(b) A prompt absorbed in chunks of 1, 7, the WY block's size and two blocks
    gives the logits and the state a single pass gives; the chunked form is
    the recurrence where beta passes 1 and a channel's decay reaches -20 a
    position (a factored ``exp(-G)`` overflows there).
(c) A masked or padded row leaves the state and the conv tail bit-untouched.
(d) A recycled slot's old state changes nothing for its new tenant.
(e) ``Engine`` with more requests than slots: every served token against the
    reference, and the state's counters and bytes.
(f) The share ties to the model: the eight EP shares plus the shared expert
    once are the uncut layer.
(g) Planted faults fail the comparison: beta without its factor 2, one decay
    a head, k not L2-normed, the full layer's gate left out, the state not
    reset at admission, the gates not masked on pads.
(h) What takes rows at their position refuses the state by name; the
    ``solar_open2`` record maps, and what it cannot honour is refused.
(i) A pattern with both mixers keeps each one's state in its own shape.

Tolerance: program and reference compute the same float32 mathematics in
different orders (the WY block's products and a one-step recurrence against a
scan position by position; a sort-and-segment expert sum against an expert at
a time), so logits of size ~4 agree to about 3e-6; ``TOL`` leaves a decade of
room, and each planted fault is held to five times ``TOL``.
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

from chipbench import reference_solar as ref  # noqa: E402
from chipbench import weights_solar  # noqa: E402
from torchgpipe_tpu.fleet import SpeculativeEngine  # noqa: E402
from torchgpipe_tpu.fleet.prefix_cache import RadixPrefixCache  # noqa: E402
from torchgpipe_tpu.models import generation, kda, kv_cache, moe as moe_mod  # noqa: E402
from torchgpipe_tpu.models.generation import (  # noqa: E402
    beam_search,
    decode_slots,
    init_cache,
    init_quant_cache,
    prefill,
    speculative_generate,
)
from torchgpipe_tpu.models.hf_interop import config_from_hf_solar_open2  # noqa: E402
from torchgpipe_tpu.models.kv_cache import HybridCache  # noqa: E402
from torchgpipe_tpu.models.transformer import (  # noqa: E402
    LinearAttnConfig,
    SSMConfig,
    TransformerConfig,
    transformer_block,
)
from torchgpipe_tpu.serving import Engine  # noqa: E402
from torchgpipe_tpu.serving.qos import QosConfig, QosPolicy  # noqa: E402

TOL = 2e-5
CONFIG = pathlib.Path(__file__).resolve().parents[1] / "chipbench" / "configs" / "solar-open2.json"
E = 16
# The configuration file's keys at toy widths: the file's cut (*E KE KE KE),
# 4 delta-rule heads of 16, 4 query heads of 32 on 2 KV heads; 16 experts, 8
# a token, this share holds experts 4..11.
TOY = dict(
    json.loads(CONFIG.read_text()), hidden_size=64, vocab_size=97, num_attention_heads=4,
    num_key_value_heads=2, head_dim=32, moe_intermediate_size=32, n_routed_experts=8,
    held_first=4, torch_dtype="float32",
)
TOY["linear_attn_config"] = dict(TOY["linear_attn_config"], num_heads=4, head_dim=16)
TOY["draw"] = dict(TOY["draw"], seed=11, router_bias_std=0.1)
TOY["reduced"] = dict(TOY["reduced"], n_routed_experts={"published": E})
MAX_LEN = 48
BLOCK = 8               # the WY block of the toy: a prompt of 19 spans three


@pytest.fixture(autouse=True, scope="module")
def _toy_block():
    """The chunked form in blocks of ``BLOCK`` (``kda.CHUNK``, 64 as
    published), so that the toy's prompts span several."""
    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(kda, "CHUNK", BLOCK)
        yield


def program(m, **patch):
    """(TransformerConfig, MoEConfig) from the file's keys, the router at its
    published width."""
    hf = dict(m, n_routed_experts=E, **patch)
    cfg, moe = config_from_hf_solar_open2(
        types.SimpleNamespace(**hf), held=(m["held_first"], m["n_routed_experts"]))
    return dataclasses.replace(cfg, dtype=jnp.float32), moe


@pytest.fixture(scope="module")
def model():
    cfg, moe = program(TOY)
    flat = weights_solar.make_flat(TOY, 5)
    return cfg, moe, flat, ref.ServeReference(TOY, flat, MAX_LEN, MAX_LEN)


def _fresh_step():
    """``decode_slots`` jitted, traced anew: a fault planted by monkeypatch
    after this call reaches it, where a wrapper traced before would not."""
    def step(cfg, moe, flat, tokens, cache, lengths, n_valid, slots=None):
        return decode_slots(cfg, flat, tokens, cache, lengths, n_valid, moe=moe, slots=slots)

    return jax.jit(step, static_argnums=(0, 1))


STEP = _fresh_step()


def _seq(seed, n):
    return np.random.default_rng(seed).integers(0, TOY["vocab_size"], n).astype(np.int32)


def _chunked(cfg, moe, flat, cache, lengths, slot, tokens, g, pad_slot=None, step=STEP):
    """``tokens`` absorbed into ``slot`` in compact calls of ``g`` (the
    last one padded), with a padding row on ``pad_slot`` in every call;
    returns (every position's logits, cache, lengths)."""
    out = []
    for a in range(0, len(tokens), g):
        chunk = tokens[a:a + g]
        t = np.zeros((2, g), np.int32)
        t[0, :len(chunk)] = chunk
        slots = np.array([slot, slot if pad_slot is None else pad_slot], np.int32)
        lg, cache, lengths = step(cfg, moe, flat, jnp.asarray(t), cache, lengths,
                                  jnp.array([len(chunk), 0]), jnp.asarray(slots))
        out.append(np.asarray(lg[0, :len(chunk)]))
    return np.concatenate(out), cache, lengths


def _decode(cfg, moe, flat, cache, lengths, slot, tokens, step=STEP):
    """Teacher-forced decode of ``tokens`` in ``slot``, one pool-wide step a
    token (the other slots no-op rows)."""
    out = []
    S = lengths.shape[0]
    for tok in tokens:
        t = np.zeros((S, 1), np.int32)
        t[slot] = tok
        n = np.zeros((S,), np.int32)
        n[slot] = 1
        lg, cache, lengths = step(cfg, moe, flat, jnp.asarray(t), cache, lengths,
                                  jnp.asarray(n))
        out.append(np.asarray(lg[slot, 0]))
    return np.stack(out), cache, lengths


def _scenario(cfg, moe, flat, g=BLOCK, step=STEP):
    """Two requests through slot 0 of a 2-slot pool: A (prefill in chunks of
    ``g`` with pads, then decode), then B in the RECYCLED slot; a padding
    row sits on slot 0 in every compact call.  Returns [(tokens, logits)]."""
    cache, lengths = init_cache(cfg, 2, MAX_LEN), jnp.zeros((2,), jnp.int32)
    got = []
    for seed, (p, d) in ((1, (13, 4)), (2, (11, 5))):
        seq = _seq(seed, p + d)
        lengths = lengths.at[0].set(0)          # admission: frontier 0
        a, cache, lengths = _chunked(cfg, moe, flat, cache, lengths, 0, seq[:p], g, 0, step)
        b, cache, lengths = _decode(cfg, moe, flat, cache, lengths, 0, seq[p:], step)
        got.append((seq, np.concatenate([a, b])))
    return got


def _gap(model_ref, got):
    return max(float(np.abs(logits - model_ref.all_logits(seq)).max()) for seq, logits in got)


def test_prefill_then_decode_slots_matches_reference(model):
    cfg, moe, flat, r = model
    seq = _seq(3, 20)
    last, cache = prefill(cfg, flat, jnp.asarray(seq[None, :12]), MAX_LEN, moe=moe)
    assert isinstance(cache, HybridCache) and int(cache.length) == 12
    assert not cache.conv and not cache.ssm and len(cache.kda) == 3
    want = r.all_logits(seq)
    np.testing.assert_allclose(np.asarray(last[0]), want[11], atol=TOL)
    logits, _, lengths = _decode(cfg, moe, flat, cache, jnp.array([12], jnp.int32), 0, seq[12:])
    np.testing.assert_allclose(logits, want[12:], atol=TOL)
    assert int(lengths[0]) == 20


@pytest.mark.parametrize("g", [1, 7, BLOCK, 2 * BLOCK])
def test_chunked_prefill_agrees_with_one_pass(model, g):
    """Chunks of 1 (the recurrence, one step a call), 7 (the chunked form,
    pads in the last), the WY block and two blocks a call: the logits and the
    state of a single pass (whose 19 positions span three blocks)."""
    cfg, moe, flat, r = model
    seq = _seq(4, 19)
    _, one = prefill(cfg, flat, jnp.asarray(seq[None]), MAX_LEN, moe=moe)
    cache, lengths = init_cache(cfg, 2, MAX_LEN), jnp.zeros((2,), jnp.int32)
    logits, cache, _ = _chunked(cfg, moe, flat, cache, lengths, 1, seq, g, pad_slot=0)
    np.testing.assert_allclose(logits, r.all_logits(seq), atol=TOL)
    for a, b in zip(cache.kda + cache.kda_conv, one.kda + one.kda_conv):
        np.testing.assert_allclose(np.asarray(a[1]), np.asarray(b[0]), atol=1e-5)


def _kda_params(key, dim, la, a_log=(1.0, 4.0, 16.0, 40.0)):
    """A delta-rule layer's params whose decay reaches -20 a position in some
    channels (``A_log`` to log 40, ``dt_bias`` to 3) and whose beta passes 1."""
    k = jax.random.split(key, 9)
    w, r = la.width, la.head_dim
    n = lambda key, shape: jax.random.normal(key, shape) * shape[0] ** -0.5  # noqa: E731
    return {"ln1": jnp.ones(dim), "in_proj": n(k[0], (dim, 3 * w)), "f_a": n(k[1], (dim, r)),
            "f_b": n(k[2], (r, w)), "b_proj": 3.0 * n(k[3], (dim, la.n_heads)),
            "g_a": n(k[4], (dim, r)), "g_b": n(k[5], (r, w)), "out_proj": n(k[6], (w, dim)),
            "conv_w": jax.random.uniform(k[7], (la.conv_kernel, 3 * w), minval=-0.5,
                                         maxval=0.5),
            "dt_bias": jax.random.uniform(k[8], (w,), minval=-6.0, maxval=3.0),
            "A_log": jnp.log(jnp.array(a_log[:la.n_heads])),
            "norm": jnp.ones((la.head_dim,))}


def _mixer_inputs(seed, g, rows=2):
    """:func:`_kda_params` at toy widths, and a chunk of inputs from a state
    and tail the slot already holds."""
    la = LinearAttnConfig(n_heads=4, head_dim=16)
    cfg = TransformerConfig(dim=32, n_layers=1, layer_pattern="K", linear_attn=la)
    k = jax.random.split(jax.random.PRNGKey(seed), 4)
    u = jax.random.normal(k[1], (rows, g, cfg.dim))
    tail = 0.3 * jax.random.normal(k[2], (rows, la.conv_kernel - 1, la.conv_dim))
    state = 0.3 * jax.random.normal(k[3], (rows, la.n_heads, la.head_dim, la.head_dim))
    return cfg, _kda_params(k[0], cfg.dim, la), u, tail, state


@pytest.mark.parametrize("n_valid", [21, 16, 5], ids=["three-blocks", "two-blocks", "padded"])
def test_the_chunked_form_is_the_recurrence_under_strong_decay(n_valid):
    """The chunked form over 21 positions (blocks of 8, the last padded; or
    fewer valid, the rest masked) against one recurrence step a position,
    both from a state and tail the slot already holds: where beta passes 1
    and a channel's decay reaches -20 a position, its output and the state
    it hands on agree, and a masked position changes nothing."""
    cfg, p, u, tail, state = _mixer_inputs(1, 21)
    f = ((u @ p["f_a"]) @ p["f_b"]).reshape(2, 21, 4, 16)
    assert float(kda._decay(p, f).min()) < -20.0
    assert float(jnp.max(2 * jax.nn.sigmoid(u @ p["b_proj"]))) > 1.5
    nv = jnp.array([n_valid, 21])
    no = jnp.zeros((2,), bool)
    mixer = jax.jit(kda.mixer, static_argnums=0)
    with jax.default_matmul_precision("highest"):
        out, new_tail, new_state = mixer(cfg, p, u, tail, state, nv, no)
        t, s, steps = tail, state, []
        for i in range(21):
            o, t, s = mixer(cfg, p, u[:, i:i + 1], t, s, (nv > i).astype(jnp.int32), no)
            steps.append(o)
    want = jnp.concatenate(steps, axis=1)
    assert np.isfinite(np.asarray(out)).all()
    np.testing.assert_allclose(np.asarray(out[:, :n_valid]), np.asarray(want[:, :n_valid]),
                               atol=1e-4, rtol=1e-4)
    np.testing.assert_allclose(np.asarray(new_state), np.asarray(s), atol=1e-4, rtol=1e-4)
    np.testing.assert_array_equal(np.asarray(new_tail), np.asarray(t))


def test_noop_and_padded_rows_leave_the_state_bit_untouched(model):
    cfg, moe, flat, _ = model
    cache, lengths = init_cache(cfg, 3, MAX_LEN), jnp.zeros((3,), jnp.int32)
    for slot, seed in ((0, 5), (1, 6)):
        _, cache, lengths = _chunked(cfg, moe, flat, cache, lengths, slot, _seq(seed, 9), 4)
    before = jax.tree_util.tree_map(np.asarray, (cache.kda_conv, cache.kda))
    # Pool-wide decode: slot 0 runs, slots 1 and 2 are no-op rows.
    _, cache, _ = _decode(cfg, moe, flat, cache, lengths, 0, _seq(7, 1))
    # Compact: slot 2 runs, a padding row sits on slot 1 (n_valid 0).
    t = jnp.asarray(_seq(8, 8).reshape(2, 4))
    _, cache, _ = STEP(cfg, moe, flat, t, cache, lengths, jnp.array([4, 0]),
                       jnp.array([2, 1]))
    for old, new in zip(before, (cache.kda_conv, cache.kda)):
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
    a, cache, lengths = _chunked(cfg, moe, flat, cache, lengths, 0, seq[:11], BLOCK, 0)
    b, _, _ = _decode(cfg, moe, flat, cache, lengths, 0, seq[11:])
    np.testing.assert_array_equal(recycled, np.concatenate([a, b]))
    np.testing.assert_allclose(recycled, r.all_logits(seq), atol=TOL)


def test_engine_serves_a_backlog_with_slot_churn(model):
    """8 requests through 3 slots: every served token is the reference's best
    (a gap under ``TOL`` where two logits nearly tie), the state counters count
    what ran, and the pool's bytes are K/V rows and delta-rule states."""
    cfg, moe, flat, r = model
    eng = Engine(cfg, flat, moe=moe, num_slots=3, max_len=MAX_LEN, prefill_chunk=BLOCK)
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
    # Three delta-rule layers: a tail of 3 x (3 x 64) and a state of 4 x 16 x 16.
    assert slot == 3 * (3 * 3 * 64 * 4 + 4 * 16 * 16 * 4)
    assert snap["kv_pool_bytes"] == {"window": 0, "full": 3 * MAX_LEN * 2 * 2 * 32 * 4,
                                     "state": 3 * slot}
    assert snap["state_bytes"]["decode"] > 0 and snap["state_bytes"]["prefill"] > 0
    assert snap["state_bytes"]["decode"] % (2 * slot) == 0


def test_the_eight_shares_and_the_shared_expert_once_are_the_uncut_layer(model):
    """Eight EP shares of two experts each of the 16, each computed by the
    program's held expert layer, minus the shared expert seven times: the
    reference's uncut layer."""
    cfg, _, _, _ = model
    m = dict(TOY, n_routed_experts=E, held_first=0, reduced={})
    full = weights_solar.make_flat(m, 3)[2]["mlp"]
    u = jax.random.normal(jax.random.PRNGKey(1), (1, 10, TOY["hidden_size"]), jnp.float32)
    want = ref.expert_layer(m, full, u[0])
    share = E // 8
    outs = []
    for first in range(0, E, share):
        _, moe = program(dict(TOY, n_routed_experts=share, held_first=first))
        part = dict(full, **{k: full[k][first:first + share] for k in ("w_gate", "w_up", "w_down")})
        outs.append(np.asarray(moe_mod.moe_mlp(cfg, moe).apply(
            part, (), u, rng=None, train=False)[0][0]))
    shared = np.asarray(ref._swiglu(full["shared"], u[0], False))
    np.testing.assert_allclose(sum(outs) - 7 * shared, np.asarray(want), atol=TOL)


FAULTS = {
    "decay_per_head": (lambda real: lambda mp: mp.setattr(
        kda, "_decay", lambda p, f: (lambda la: jnp.broadcast_to(
            la.mean(-1, keepdims=True), la.shape))(real(p, f))))(kda._decay),
    "no_k_l2norm": (lambda real: lambda mp: mp.setattr(
        kda, "_qk_norm", lambda q, k: (real(q, k)[0], k)))(kda._qk_norm),
    "no_reset": lambda mp: mp.setattr(
        kda, "_entering", lambda fresh, tail, state: (tail, state)),
    "gates_unmasked": lambda mp: mp.setattr(
        kda, "_gates", lambda log_a, beta, valid: (log_a, beta)),
}


@pytest.mark.parametrize("fault", sorted(FAULTS) + ["beta_no_neg", "no_attn_gate"])
def test_a_planted_fault_fails_the_comparison(model, fault, monkeypatch):
    cfg, moe, flat, r = model
    assert _gap(r, _scenario(cfg, moe, flat)) <= TOL
    if fault == "beta_no_neg":
        cfg = dataclasses.replace(
            cfg, linear_attn=dataclasses.replace(cfg.linear_attn, neg_eigval=False))
    elif fault == "no_attn_gate":
        cfg = dataclasses.replace(cfg, attn_gate=False)
    else:
        FAULTS[fault](monkeypatch)
    assert _gap(r, _scenario(cfg, moe, flat, step=_fresh_step())) > 5 * TOL, fault


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
    with pytest.raises(NotImplementedError, match=r"\['K'\] of cfg.layer_pattern|delta-rule"):
        REFUSALS[what](cfg, moe, flat)


def test_the_solar_open2_record_maps():
    cfg, moe = program(TOY)
    assert cfg.layer_pattern == "*EKEKEKE" and cfg.n_layers == 8
    assert [cfg.layer_type(i) for i in (0, 1, 2)] == ["attention", "experts", "mixer"]
    la = cfg.linear_attn
    assert (la.n_heads, la.head_dim, la.conv_kernel, la.neg_eigval) == (4, 16, 4, True)
    assert la.width == 64 and la.conv_dim == 192
    assert cfg.attn_gate and not cfg.attn_layer(0).rope and cfg.attn_layer(0).window is None
    assert (cfg.n_heads, cfg.kv_heads, cfg.head_dim, cfg.norm_eps) == (4, 2, 32, 1e-5)
    assert (moe.n_experts, moe.top_k, moe.act, moe.select, moe.scoring) == (
        E, 8, "swiglu", "bias", "sigmoid")
    assert (moe.route_scale, moe.norm_topk, moe.expert_hidden, moe.n_shared, moe.held) == (
        1.0, True, 32, 1, (4, 8))
    full = dict(TOY, num_hidden_layers=8, gqa_layers=[0, 4])
    assert program(full)[0].layer_pattern == "*EKEKEKE*EKEKEKE"


@pytest.mark.parametrize("patch,match", [
    ({"kda_use_full_proj": True}, "kda_use_full_proj"),
    ({"use_rope": True}, "use_rope"),
    ({"first_k_dense_replace": 1}, "first_k_dense_replace"),
    ({"gqa_layers": [1]}, "gqa_layers"),
    ({"num_hidden_layers": 8, "gqa_layers": [0, 3]}, "gqa_layers"),
    ({"linear_attn_config": {"num_heads": 4, "head_dim": 16, "num_kv_heads": 2,
                             "short_conv_kernel_size": 4}}, "num_kv_heads"),
], ids=["full_proj", "rope", "dense_layer", "gqa_off_pattern", "gqa_not_one_in_four",
        "kv_heads"])
def test_what_the_record_asks_and_the_program_cannot_honour_is_refused(patch, match):
    with pytest.raises(ValueError, match=match):
        program(dict(TOY, **patch))


def test_both_mixers_keep_their_own_state_shape_in_one_pool():
    """A pattern with a Mamba-2 layer and a delta-rule layer: each keeps its
    tail and state in its own fields and shape, the pool's bytes count both,
    and a chunk then a step through the pool move both."""
    la = LinearAttnConfig(n_heads=2, head_dim=8)
    ssm = SSMConfig(n_heads=4, head_dim=8, n_groups=2, state=8, chunk=4)
    cfg = TransformerConfig(vocab=31, dim=32, n_layers=3, n_heads=2, n_kv_heads=1,
                            layer_pattern="MK*", ssm=ssm, linear_attn=la)
    cache = init_cache(cfg, 2, 16)
    assert [c.shape for c in cache.conv + cache.ssm] == [(2, 3, 64), (2, 4, 8, 8)]
    assert [c.shape for c in cache.kda_conv + cache.kda] == [(2, 3, 48), (2, 2, 8, 8)]
    state = 2 * (3 * 64 * 4 + 4 * 8 * 8 * 4 + 3 * 48 * 4 + 2 * 8 * 8 * 4)
    assert kv_cache.bytes_by_kind(cfg, cache) == {"window": 0, "full": 2 * 16 * 2 * 16 * 4,
                                                  "state": state}
    ks = jax.random.split(jax.random.PRNGKey(0), 4)
    n = lambda k, shape: 0.2 * jax.random.normal(k, shape)  # noqa: E731
    mamba = {"ln1": jnp.ones(32), "in_proj": n(ks[0], (32, 32 + 64 + 4)),
             "conv_w": n(ks[1], (4, 64)), "conv_b": jnp.zeros(64), "dt_bias": jnp.zeros(4),
             "A_log": jnp.zeros(4), "D": jnp.ones(4), "norm": jnp.ones(32),
             "out_proj": n(ks[2], (32, 32))}
    la_p = _kda_params(ks[1], 32, la)
    attn = {"ln1": jnp.ones(32), "wq": n(ks[3], (32, 32)), "wk": n(ks[3], (32, 16)),
            "wv": n(ks[3], (32, 16)), "wo": n(ks[3], (32, 32))}
    flat = [{"table": n(ks[0], (31, 32))}, mamba, la_p, attn,
            {"scale": jnp.ones(32), "w": n(ks[1], (32, 31))}]
    lengths = jnp.zeros((2,), jnp.int32)
    _, cache, lengths = STEP(cfg, None, flat, jnp.ones((2, 5), jnp.int32), cache, lengths,
                             jnp.array([5, 3]))
    out, moved, _ = STEP(cfg, None, flat, jnp.ones((2, 1), jnp.int32), cache, lengths,
                         jnp.array([1, 0]))
    assert np.isfinite(np.asarray(out)).all()
    for before, after in zip(cache.ssm + cache.kda, moved.ssm + moved.kda):
        assert not np.array_equal(np.asarray(before[0]), np.asarray(after[0]))
        np.testing.assert_array_equal(np.asarray(before[1]), np.asarray(after[1]))
