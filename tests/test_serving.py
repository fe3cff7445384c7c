"""The serving engine's contracts, pinned.

1. **Steady-state compile contract** — >= 16 ragged, staggered,
   partially-cancelled requests through the engine compile EXACTLY two
   programs (prefill, decode): zero retraces, on both MPMD- and
   SPMD-derived params.
2. **Exactness** — greedy tokens streamed through the pooled engine
   equal :func:`generation.generate` run per-request on the same
   params, including requests that were queued, drained to a resilience
   checkpoint, and resumed in a fresh engine.
3. **Continuous batching wins** — on a ragged workload the
   iteration-level scheduler beats the static run-to-longest baseline
   (same compiled programs, ``wave_admission=True``) in tokens/step and
   occupancy, and the metrics snapshot is consistent with the request
   log.
4. **Slot recycling is clean** — int8 (QuantKVCache) pools: alloc ->
   decode -> free -> realloc the same slot produces BITWISE the output
   a fresh pool produces (stale rows/scales are dead by masking).
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from torchgpipe_tpu.layers import sequential_init
from torchgpipe_tpu.models.generation import generate
from torchgpipe_tpu.models.transformer import TransformerConfig, llama
from torchgpipe_tpu.serving import Engine

CFG = TransformerConfig(
    vocab=64, dim=32, n_layers=2, n_heads=4, n_kv_heads=2
)


@pytest.fixture(scope="module")
def flat_params():
    params, _, _ = sequential_init(
        llama(CFG), jax.random.PRNGKey(0),
        jax.ShapeDtypeStruct((2, 8), jnp.int32),
    )
    return params


def _ref(params, prompt, new, max_len=32, **kw):
    return np.asarray(
        generate(CFG, params, jnp.asarray(prompt)[None, :], new,
                 max_len=max_len, **kw)
    )[0]


def _workload(seed, n, vocab=64, plen_hi=10, new_hi=8):
    rng = np.random.RandomState(seed)
    return [
        (rng.randint(0, vocab, (int(rng.randint(2, plen_hi)),))
         .astype(np.int32),
         int(rng.randint(2, new_hi)))
        for _ in range(n)
    ]


class FakeClock:
    def __init__(self):
        self.t = 0.0

    def __call__(self):
        self.t += 1.0
        return self.t


# --------------------------------------------------------------------- #
# 1. steady-state compile contract                                      #
# --------------------------------------------------------------------- #


def _mpmd_flat():
    from torchgpipe_tpu import GPipe
    from torchgpipe_tpu.models.generation import mpmd_params_for_generation

    model = GPipe(llama(CFG), balance=[2, 2], chunks=2)
    params, _ = model.init(
        jax.random.PRNGKey(0), jax.ShapeDtypeStruct((2, 8), jnp.int32)
    )
    return mpmd_params_for_generation(model, params)


def _spmd_flat():
    from torchgpipe_tpu.models.generation import spmd_params_for_generation
    from torchgpipe_tpu.models.transformer import cross_entropy, llama_spmd
    from torchgpipe_tpu.spmd import SpmdGPipe, make_mesh

    block, pre, post = llama_spmd(CFG, 2)
    mesh = make_mesh(2, 1, devices=jax.devices()[:2])
    pipe = SpmdGPipe(
        block, 2, mesh, chunks=2, loss_fn=cross_entropy,
        pre=pre, post=post,
    )
    params = pipe.place(
        pipe.init(jax.random.PRNGKey(0),
                  jax.ShapeDtypeStruct((4, 8), jnp.int32))
    )
    return spmd_params_for_generation(pipe, params)


@pytest.mark.slow  # tier-1 870s budget: top offender, covered by the CI full job
@pytest.mark.parametrize("derive", ["mpmd", "spmd"])
def test_two_compiled_programs_zero_retraces(derive):
    """16+ ragged, staggered requests with mid-flight cancellations:
    exactly one trace per program, outputs exact vs generate — the SAME
    trained pipeline params serve both engines."""
    params = _mpmd_flat() if derive == "mpmd" else _spmd_flat()
    reqs = _workload(seed=0, n=16)
    eng = Engine(CFG, params, num_slots=4, max_len=32, prefill_chunk=4)
    rids = []
    cancelled = set()
    for i, (prompt, new) in enumerate(reqs):
        rid = eng.submit(prompt, new)
        rids.append(rid)
        if i in (5, 11):  # cancel while queued/just admitted
            assert eng.cancel(rid)
            cancelled.add(rid)
            continue
        eng.step()        # staggered arrivals: serve between submits
        eng.step()
    eng.run()

    assert eng.compile_stats == {"prefill": 1, "decode": 1}, (
        eng.compile_stats
    )
    for rid, (prompt, new) in zip(rids, reqs):
        if rid in cancelled:
            assert eng.status(rid) == "cancelled"
            continue
        got = eng.result(rid)
        assert len(got) == new
        assert got.tolist() == _ref(params, prompt, new).tolist()[:new], rid


# --------------------------------------------------------------------- #
# 2. continuous vs static + metrics consistency                         #
# --------------------------------------------------------------------- #


def test_continuous_beats_static_and_metrics_consistent(flat_params):
    """Ragged/staggered mix: iteration-level recycling finishes the same
    workload in fewer engine steps at higher occupancy than the static
    run-to-longest baseline; the snapshot agrees with the request log."""
    rng = np.random.RandomState(3)
    reqs = [
        (rng.randint(0, 64, (int(rng.randint(3, 7)),)).astype(np.int32),
         [24, 2, 3, 20, 2, 4, 18, 3, 2, 16, 3, 2][i])
        for i in range(12)
    ]

    def run(wave):
        clock = FakeClock()
        eng = Engine(
            CFG, flat_params, num_slots=4, max_len=32, prefill_chunk=4,
            wave_admission=wave, clock=clock,
        )
        rids = [eng.submit(p, n) for p, n in reqs]
        eng.run()
        return eng, rids

    cont, rids = run(False)
    stat, _ = run(True)
    cs, ss = cont.metrics.snapshot(), stat.metrics.snapshot()
    assert cs["tokens_out"] == ss["tokens_out"] == sum(n for _, n in reqs)
    assert cs["engine_steps"] < ss["engine_steps"], (cs, ss)
    assert cs["tokens_per_step"] > ss["tokens_per_step"], (cs, ss)
    assert cs["occupancy"] > ss["occupancy"], (cs, ss)

    # snapshot <-> request log consistency
    by_rid = {r["rid"]: r for r in cs["requests"]}
    for rid, (prompt, new) in zip(rids, reqs):
        row = by_rid[rid]
        assert row["status"] == "finished"
        assert row["tokens"] == len(cont.result(rid)) == new
        assert row["queue_wait"] is not None and row["queue_wait"] >= 0
        assert row["ttft"] is not None and row["ttft"] >= row["queue_wait"]
        if new > 1:
            assert row["tpot"] is not None and row["tpot"] > 0
    assert cs["engine_steps"] == cs["prefill_steps"] + cs["decode_steps"]
    assert 0.0 < cs["occupancy"] <= 1.0


# --------------------------------------------------------------------- #
# 3. drain / resume through a resilience checkpoint                     #
# --------------------------------------------------------------------- #


def test_drain_resume_exact(flat_params, tmp_path):
    """Preemption mid-burst: the engine drains through the resilience
    hook, unfinished requests checkpoint, and a fresh engine resumes
    each stream to EXACTLY the never-preempted output."""
    from torchgpipe_tpu.resilience.checkpoint import CheckpointManager
    from torchgpipe_tpu.resilience.preemption import PreemptionHandler

    mgr = CheckpointManager(str(tmp_path))
    handler = PreemptionHandler()         # not installed: simulate() only
    reqs = _workload(seed=1, n=6, new_hi=9)
    eng = Engine(
        CFG, flat_params, num_slots=2, max_len=48, prefill_chunk=4,
        preemption=handler, checkpoint_manager=mgr,
    )
    rids = [eng.submit(p, n) for p, n in reqs]
    for _ in range(7):
        eng.step()
    handler.simulate()        # SIGTERM stand-in -> add_callback drain hook
    assert eng.run() == "preempted"
    snap = eng.metrics.snapshot()
    assert snap["drains"] == 1 and snap["preempted_requests"] > 0

    eng2 = Engine(CFG, flat_params, num_slots=2, max_len=48,
                  prefill_chunk=4)
    restored = Engine.restore_requests(mgr)
    assert restored, "drain checkpointed nothing"
    for kw in restored:
        eng2.submit(kw.pop("prompt"), kw.pop("max_new_tokens"), **kw)
    eng2.run()
    for rid, (prompt, new) in zip(rids, reqs):
        got = (
            eng2.result(rid) if rid in eng2._requests else eng.result(rid)
        )
        assert got.tolist() == _ref(
            flat_params, prompt, new, max_len=48
        ).tolist(), rid


# --------------------------------------------------------------------- #
# 4. slot recycling: int8 pools stay bitwise clean                      #
# --------------------------------------------------------------------- #


@pytest.mark.parametrize("kv_quant", [False, True])
def test_slot_reuse_bitwise_clean(flat_params, kv_quant):
    """alloc -> decode -> free -> realloc THE SAME slots: outputs equal a
    fresh pool bitwise (stale int8 rows AND stale scales are dead by
    masking), with ragged prompts prefilled into non-contiguous slots."""
    first = _workload(seed=2, n=4)
    second = _workload(seed=7, n=4)

    def serve(eng, reqs):
        rids = [eng.submit(p, n) for p, n in reqs]
        eng.run()
        return [eng.result(r).tolist() for r in rids]

    # dirty pool: serve a first burst (every slot written), then reuse
    dirty = Engine(CFG, flat_params, num_slots=4, max_len=32,
                   prefill_chunk=4, kv_quant=kv_quant)
    serve(dirty, first)
    assert dirty.pool.num_free == 4          # all slots recycled
    # non-contiguous occupancy: park a long request in one slot so the
    # second burst prefills around it
    hold_prompt = first[0][0][:3]
    hold = dirty.submit(hold_prompt, 20)
    for _ in range(4):
        dirty.step()                          # it grabs one slot
    got_dirty = serve(dirty, second)
    dirty.cancel(hold)

    fresh = Engine(CFG, flat_params, num_slots=4, max_len=32,
                   prefill_chunk=4, kv_quant=kv_quant)
    fresh.submit(hold_prompt, 20)
    for _ in range(4):
        fresh.step()
    got_fresh = serve(fresh, second)

    assert got_dirty == got_fresh            # bitwise: same ints out
    for (p, n), toks in zip(second, got_dirty):
        assert toks == _ref(
            flat_params, p, n, kv_quant=kv_quant
        ).tolist()[:len(toks)]


# --------------------------------------------------------------------- #
# admission control / accounting                                        #
# --------------------------------------------------------------------- #


def test_admission_budget_caps_active_slots(flat_params):
    """The eval_shape pool accounting caps slots under an HBM budget:
    bytes are linear in slots, non-donated steps account the pool TWICE
    (input + output buffers live across a step), and the engine clamps
    the ALLOCATED pool — not just active requests — to the cap."""
    from torchgpipe_tpu.tune import (
        serving_cache_bytes, serving_max_slots, tree_bytes,
    )

    one = serving_cache_bytes(CFG, 1, 32)
    per_slot = serving_cache_bytes(CFG, 2, 32) - one
    # strictly linear in slots (the shared length scalar aside)
    assert serving_cache_bytes(CFG, 4, 32) - serving_cache_bytes(
        CFG, 3, 32
    ) == per_slot
    pbytes = tree_bytes(flat_params)
    # exactly 2 slots double-buffered: 2*(fixed + 2*per_slot) + change
    budget = pbytes + 2 * (one + per_slot) + per_slot  # 2.5 slots' worth
    assert serving_max_slots(
        CFG, 32, budget, param_bytes=pbytes
    ) == 2
    # donated steps alias in place: the same budget fits ~2x the slots
    assert serving_max_slots(
        CFG, 32, budget, param_bytes=pbytes, donated=True
    ) >= 4

    eng = Engine(CFG, flat_params, num_slots=4, max_len=32,
                 prefill_chunk=4, hbm_budget_bytes=budget)
    assert eng.scheduler.max_active == 2
    assert eng.pool.num_slots == 2    # allocation clamped, not just use
    for p, n in _workload(seed=4, n=6):
        eng.submit(p, n)
    peak = 0
    while not eng.scheduler.idle:
        if not eng.step():
            break
        peak = max(peak, eng.pool.num_active)
    assert peak == 2                  # capped below requested num_slots=4

    with pytest.raises(ValueError, match="admission cap is 0"):
        Engine(CFG, flat_params, num_slots=4, max_len=32,
               hbm_budget_bytes=1)


def test_steady_decode_reuses_device_lengths(flat_params, monkeypatch):
    """The decode hot path must NOT re-upload the slot frontiers every
    step: the compiled step returns the advanced lengths vector and the
    engine re-feeds it; ``pool.lengths_device()`` (the host→device
    snapshot copy) runs only when something OTHER than a step mutated
    the host mirror — admission and eviction — and the outputs stay
    exactly the per-request ``generate`` reference."""
    from torchgpipe_tpu.serving import cache_pool

    uploads = {"n": 0}
    real = cache_pool.CachePool.lengths_device

    def counting(self):
        uploads["n"] += 1
        return real(self)

    monkeypatch.setattr(cache_pool.CachePool, "lengths_device", counting)
    eng = Engine(CFG, flat_params, num_slots=2, max_len=64,
                 prefill_chunk=4)
    p = np.arange(4, dtype=np.int32) % CFG.vocab
    rid = eng.submit(p, 24)   # long generation: many steady decode steps
    eng.run()
    snap = eng.metrics.snapshot()
    steps = snap["engine_steps"]
    assert steps > 10
    # One upload at admission (the alloc zeroed the slot's frontier) and
    # one when the finished request released it mid-"idle"; every steady
    # decode step reused the device-resident vector.
    assert uploads["n"] <= 2, (uploads, steps)
    assert eng.result(rid).tolist() == _ref(
        flat_params, p, 24, max_len=64
    ).tolist()


def test_dispatch_retries_transient_errors(flat_params):
    """A transient failure in a compiled step is retried INSIDE the
    engine (bounded backoff, counted in metrics) and the request still
    decodes exactly; the step's results are materialized under the
    retry guard, so an async execution failure cannot escape to the
    host fetch after the cache was committed."""
    sleeps = []
    eng = Engine(CFG, flat_params, num_slots=2, max_len=32,
                 prefill_chunk=4, sleep=sleeps.append)
    real = eng._decode_fn
    state = {"raised": False}

    def flaky(*args):
        if not state["raised"]:
            state["raised"] = True
            raise ConnectionError("transient blip")
        return real(*args)

    eng._decode_fn = flaky
    p, n = _workload(seed=9, n=1)[0]
    rid = eng.submit(p, n)
    eng.run()
    assert state["raised"] and sleeps
    assert eng.metrics.snapshot()["retries"] == 1
    assert eng.result(rid).tolist() == _ref(flat_params, p, n).tolist()


def test_submit_rejects_oversized_request(flat_params):
    eng = Engine(CFG, flat_params, num_slots=2, max_len=16)
    with pytest.raises(ValueError, match="max_len"):
        eng.submit(np.arange(10, dtype=np.int32), 10)
    with pytest.raises(ValueError, match="empty prompt"):
        eng.submit(np.zeros((0,), np.int32), 4)


# --------------------------------------------------------------------- #
# static lint                                                           #
# --------------------------------------------------------------------- #


def test_lint_serving_clean(flat_params):
    """The serve-verify gate's API: both step programs trace, no host
    callbacks, one signature each over the churn grid; an inadmissible
    request is an INFO rejection, not a hazard."""
    from torchgpipe_tpu.analysis import lint_serving
    from torchgpipe_tpu.analysis.diagnostics import Severity

    eng = Engine(CFG, flat_params, num_slots=3, max_len=24,
                 prefill_chunk=4)
    findings = lint_serving(eng, grid=[(2, 4), (9, 8), (1, 1), (30, 30)])
    worst = [f for f in findings if f.severity >= Severity.WARNING]
    assert not worst, [f.format() for f in findings]
    infos = [f for f in findings if f.rule == "serving-admission"]
    assert len(infos) == 1                    # (30, 30) > max_len=24


def test_lint_serving_catches_request_sized_buffer(flat_params):
    """Non-vacuity: the churn check drives the REAL buffer-construction
    path, so an engine that sizes its prefill buffer from the request
    (the recompile-per-request bug class) is an ERROR, and a busy engine
    refuses to lint."""
    import numpy as np

    from torchgpipe_tpu.analysis import lint_serving
    from torchgpipe_tpu.analysis.diagnostics import Severity

    eng = Engine(CFG, flat_params, num_slots=3, max_len=24,
                 prefill_chunk=4)
    orig = eng._token_buffer

    def request_sized(kind):
        if kind == "prefill":   # the bug: width = this batch's max take
            take = max(
                min(eng.prefill_chunk, r.prompt_len - r.prefilled)
                for r in eng.scheduler.prefill_pending()
            )
            return np.zeros((eng.pool.num_slots, take), np.int32)
        return orig(kind)

    eng._token_buffer = request_sized
    findings = lint_serving(eng, grid=[(2, 4), (9, 8)])
    errors = [f for f in findings if f.rule == "recompilation-hazard"]
    assert errors and all(f.severity == Severity.ERROR for f in errors)

    busy = Engine(CFG, flat_params, num_slots=2, max_len=24)
    busy.submit(np.arange(4, dtype=np.int32), 4)
    with pytest.raises(ValueError, match="idle"):
        lint_serving(busy)


# --------------------------------------------------------------------- #
# prefill bucket ladder                                                 #
# --------------------------------------------------------------------- #


def test_scheduler_bucket_selection():
    """bucket_for / prefill_bucket: smallest covering bucket; oversized
    work caps at the ladder max; a bare int stays the classic single
    chunk."""
    from torchgpipe_tpu.serving.cache_pool import CachePool
    from torchgpipe_tpu.serving.scheduler import (
        Request,
        Scheduler,
        normalize_buckets,
    )

    assert normalize_buckets(8) == (8,)
    assert normalize_buckets([8, 2, 4, 2, 1]) == (1, 2, 4, 8)
    with pytest.raises(ValueError, match=">= 1"):
        normalize_buckets([0, 4])

    pool = CachePool(CFG, 4, 32)
    sched = Scheduler(pool, prefill_chunk=(2, 4, 16))
    assert sched.prefill_chunk == 16          # classic attr = ladder max
    assert [sched.bucket_for(n) for n in (1, 2, 3, 4, 5, 16, 99)] == [
        2, 2, 4, 4, 16, 16, 16
    ]
    # Step bucket covers the LARGEST pending chunk across slots.
    for rid, plen in (("a", 2), ("b", 7)):
        r = Request(rid=rid, prompt=np.zeros(plen, np.int32),
                    max_new_tokens=2)
        sched.submit(r)
    sched.admit()
    assert sched.prefill_bucket() == 16


def test_ladder_compile_counter_zero_retrace(flat_params):
    """The ladder's dynamic proof: a request mix exercising EVERY
    bucket compiles each bucket's program EXACTLY once (plus decode) —
    zero retraces across churn — and outputs stay exact vs generate."""
    eng = Engine(CFG, flat_params, num_slots=3, max_len=32,
                 prefill_chunk=(1, 2, 4, 8))
    assert eng.program_count == 5
    # Served one at a time so each prompt length picks its own bucket:
    # 1 -> 1, 2 -> 2, 3 -> 4, 7 -> 8, 12 -> 8 then remainder buckets.
    mix = [(1, 2), (2, 2), (3, 2), (7, 2), (12, 3)]
    rng = np.random.RandomState(5)
    results = []
    for plen, new in mix:
        prompt = rng.randint(0, 64, (plen,)).astype(np.int32)
        rid = eng.submit(prompt, new)
        eng.run()
        results.append((rid, prompt, new))
    first = dict(eng.compile_stats)
    assert set(first) == {
        "prefill@1", "prefill@2", "prefill@4", "prefill@8", "decode"
    }
    assert all(v == 1 for v in first.values()), first
    # Second pass over the same mix (staggered this time): ZERO new
    # traces.
    for plen, new in mix:
        prompt = rng.randint(0, 64, (plen,)).astype(np.int32)
        results.append((eng.submit(prompt, new), prompt, new))
    eng.run()
    assert eng.compile_stats == first
    for rid, prompt, new in results:
        ref = _ref(flat_params, prompt, new)
        assert eng.result(rid).tolist() == ref.tolist(), rid


def test_certify_ladder_clean_and_bound(flat_params):
    """certify_ladder: the exhaustive pending-chunk walk certifies the
    declared bound (INFO), and a scheduler whose bucket choice escapes
    the ladder is an ERROR."""
    from torchgpipe_tpu.analysis.diagnostics import Severity
    from torchgpipe_tpu.analysis.serving import certify_ladder

    eng = Engine(CFG, flat_params, num_slots=3, max_len=24,
                 prefill_chunk=(1, 4))
    fs = certify_ladder(eng)
    assert [f.severity for f in fs] == [Severity.INFO]
    assert "3" in fs[0].message  # len(ladder)+1 programs

    eng.scheduler.bucket_for = lambda n: n  # the bug: request-sized
    fs = certify_ladder(eng)
    errors = [f for f in fs if f.severity == Severity.ERROR]
    assert errors and errors[0].rule == "ladder-bound"


def test_lint_serving_clean_with_ladder(flat_params):
    """The full serve-verify lint over a ladder engine: zero WARNING+
    findings (every bucket's program traces, no host callbacks, churn
    stays inside the declared signatures)."""
    from torchgpipe_tpu.analysis import lint_serving
    from torchgpipe_tpu.analysis.diagnostics import Severity

    eng = Engine(CFG, flat_params, num_slots=3, max_len=24,
                 prefill_chunk=(2, 4))
    findings = lint_serving(eng, grid=[(2, 4), (9, 8), (1, 1)])
    worst = [f for f in findings if f.severity >= Severity.WARNING]
    assert not worst, [f.format() for f in findings]


# --------------------------------------------------------------------- #
# one step in flight                                                    #
# --------------------------------------------------------------------- #


def _latent_moe_toy():
    """The latent-attention, routed-expert toy of ``tests/test_mla_moe.py``,
    told what it holds: its step programs return ``(tokens, counts)``."""
    import test_mla_moe as toy
    from chipbench import weights_axk1

    cfg, moe = toy.configs()
    return cfg, moe, weights_axk1.make_flat(toy.TOY, 11), toy.TOY["vocab_size"]


def _stream(eng, reqs, serial, **submit_kw):
    """Every request's tokens as ``on_token`` hands them over.  Half of
    ``reqs`` is queued at once, the rest arrives one every third
    iteration, so slots are freed and taken mid-run.  ``serial``: the
    reference loop, which settles after every step (nothing is ever in
    flight when a step is built; an engine that does not donate its
    cache is that loop already)."""
    got = {}

    def on_token(rid, tok):
        got.setdefault(rid, []).append(int(tok))

    pending = [(f"q{i}", p, n) for i, (p, n) in enumerate(reqs)]
    for rid, p, n in pending[:len(pending) // 2]:
        eng.submit(p, n, rid=rid, on_token=on_token, **submit_kw)
    late = pending[len(pending) // 2:]
    it = 0
    while late or not eng.scheduler.idle:
        if late and it % 3 == 0:
            rid, p, n = late.pop(0)
            eng.submit(p, n, rid=rid, on_token=on_token, **submit_kw)
        eng.step()
        if serial:
            eng._settle()
        it += 1
    assert eng._inflight is None        # idle implies settled
    return got


@pytest.mark.parametrize("model", ["gqa", "latent_moe"])
@pytest.mark.parametrize("sampling", ["greedy", "seeded"])
def test_overlapped_streams_equal_the_serial_loops(flat_params, model,
                                                   sampling):
    """Step k+1 is launched before step k's tokens are fetched; the
    streams are token for token those of a loop that settles after
    every step — greedy and seeded sampling (the key travels on the
    device), mixed prompt lengths, admissions and evictions mid-run, on
    the GQA toy and on the latent / expert toy (``(tok, counts)``)."""
    if model == "gqa":
        cfg, params, vocab, kw = CFG, flat_params, 64, {}
    else:
        cfg, moe, params, vocab = _latent_moe_toy()
        kw = {"moe": moe}
    if sampling == "seeded":
        kw.update(temperature=0.8, top_k=12, rng=jax.random.PRNGKey(5))
    reqs = _workload(seed=21, n=9, vocab=vocab, plen_hi=14, new_hi=9)

    def engine(donate):
        return Engine(cfg, params, num_slots=3, max_len=32,
                      prefill_chunk=4, donate=donate, **kw)

    fast, slow = engine(True), engine(False)
    got = _stream(fast, reqs, serial=False)
    want = _stream(slow, reqs, serial=True)
    assert got == want
    assert sorted(got) == sorted(f"q{i}" for i in range(len(reqs)))
    assert all(len(got[f"q{i}"]) == n for i, (_, n) in enumerate(reqs))
    a, b = fast.metrics.snapshot(), slow.metrics.snapshot()
    for key in ("prefill_steps", "decode_steps", "tokens_out",
                "occupancy", "moe_held_assignments"):
        assert a[key] == b[key], key
    assert b["steps_launched_ahead"] == 0
    # Every step but the first of a busy stretch is launched ahead.
    assert a["steps_launched_ahead"] >= a["engine_steps"] - 3
    assert fast.compile_stats == slow.compile_stats == {
        "prefill": 1, "decode": 1}
    if model == "gqa" and sampling == "greedy":
        for i, (p, n) in enumerate(reqs):
            assert got[f"q{i}"] == _ref(flat_params, p, n).tolist()


def test_eos_row_launched_behind_is_discarded(flat_params):
    """A request that ends by EOS at step k has a row in step k+1, which
    was launched before k's tokens were fetched: that row emits nothing,
    no token is counted for it, and the slot's next tenant matches its
    cold run bit for bit."""
    from torchgpipe_tpu.utils.tracing import Timeline

    (p1, _), (p2, n2) = _workload(seed=4, n=2, new_hi=9)
    full = _ref(flat_params, p1, 12).tolist()
    cut = next(j for j in range(2, 12) if full[j] not in full[:j])
    eos = full[cut]

    def serve(serial):
        eng = Engine(CFG, flat_params, num_slots=1, max_len=32,
                     prefill_chunk=4, donate=not serial)
        eng.timeline = Timeline()
        seen = []
        eng.submit(p1, 12, rid="a", eos_id=eos,
                   on_token=lambda rid, t: seen.append(int(t)))
        eng.submit(p2, n2, rid="b")
        while not eng.scheduler.idle:
            eng.step()
            if serial:
                eng._settle()
        return eng, seen

    fast, seen = serve(serial=False)
    slow, _ = serve(serial=True)
    assert seen == full[:cut + 1] == fast.result("a").tolist()
    assert fast.status("a") == "finished"
    assert fast.metrics.requests["a"].tokens == cut + 1
    assert fast.metrics.tokens_out == slow.metrics.tokens_out == cut + 1 + n2
    assert sum(e.fields["tokens"] for e in fast.timeline.events
               if e.name == "engine.emit") == cut + 1 + n2
    # The one wasted row is a decode step the serial loop never runs.
    assert fast.metrics.decode_steps == slow.metrics.decode_steps + 1
    # The next tenant of the ONE slot, whose frontier was reset on
    # release, never attends the row written past the end of "a".
    assert fast.result("b").tolist() == _ref(flat_params, p2, n2).tolist()
    assert fast.result("b").tolist() == slow.result("b").tolist()
    fast.pool.check_refcounts()
    assert fast.pool.num_free == 1


@pytest.mark.parametrize("kind", ["fatal", "exhausted"])
def test_a_failed_step_that_cannot_be_retried_leaves_the_pre_step_state(
        flat_params, kind):
    """``donate=False``: the engine waits for a step where it launches
    it, under the retry, and commits only what is known good.  A failure
    that cannot be retried (not transient, or the retries used up)
    re-raises with the POOL HOLDING THE PRE-STEP ARRAYS and the host's
    books where they were — no step is ever in flight behind it — so
    the same engine goes on, exactly, once the fault is gone."""
    sleeps = []
    eng = Engine(CFG, flat_params, num_slots=2, max_len=32,
                 prefill_chunk=4, sleep=sleeps.append)
    reqs = [(p, max(n, 6)) for p, n in _workload(seed=9, n=3, new_hi=9)]
    rids = [eng.submit(p, n) for p, n in reqs]
    while eng.metrics.decode_steps < 3:
        assert eng.step() and eng._inflight is None
    real = eng._decode_fn
    error = {"fatal": ValueError("a wrong program"),
             "exhausted": ConnectionError("the link stays down")}[kind]

    def broken(*args):
        raise error

    eng._decode_fn = broken
    while eng.scheduler.next_action() != "decode":
        assert eng.step()
    cache, key = eng.pool.cache, eng._key
    books = (eng.pool.lengths.copy(), eng._lengths_shadow.copy(),
             eng._cur_tok.copy(), eng.metrics.snapshot()["engine_steps"],
             {r: (q.prefilled, list(q.generated), q.in_flight)
              for r, q in eng._requests.items()})
    with pytest.raises(type(error)):
        eng.step()
    retries = 0 if kind == "fatal" else eng.guard_policy.max_retries
    assert len(sleeps) == eng.metrics.snapshot()["retries"] == retries
    assert eng.pool.cache is cache and eng._key is key
    assert eng._inflight is None
    now = (eng.pool.lengths, eng._lengths_shadow, eng._cur_tok,
           eng.metrics.snapshot()["engine_steps"],
           {r: (q.prefilled, list(q.generated), q.in_flight)
            for r, q in eng._requests.items()})
    for was, got in zip(books[:3], now[:3]):
        assert was.tolist() == got.tolist()
    assert books[3:] == now[3:]
    eng._decode_fn = real
    assert eng.run() == "idle"
    for rid, (p, n) in zip(rids, reqs):
        assert eng.result(rid).tolist() == _ref(flat_params, p, n).tolist()


@pytest.mark.parametrize("via", ["step", "unfinished"])
def test_a_failure_met_at_the_wait_abandons_the_steps_in_flight(
        flat_params, monkeypatch, via):
    """``donate=True``: a step's inputs are consumed by its launch, so
    its failure — met where the host waits for it, one step later, with
    another step launched behind it — cannot be retried: it re-raises,
    nothing stays in flight, and ``drain()`` (the router's failover)
    snapshots every unfinished request with the tokens it DID deliver,
    those that had ended by length in the lost steps included.  The
    router's listing (``unfinished``) meets the failure once and is
    whole on the next call."""
    from torchgpipe_tpu.serving import engine as engine_mod

    eng = Engine(CFG, flat_params, num_slots=2, max_len=32,
                 prefill_chunk=4, donate=True)
    reqs = [(p, 6) for p, _ in _workload(seed=9, n=3)]
    rids = [eng.submit(p, n) for p, n in reqs]
    real_wait = jax.block_until_ready
    state = {"armed": False, "raised": 0}

    def wait(x):
        if state["armed"]:
            state["raised"] += 1
            raise ConnectionError("transient blip on the device")
        return real_wait(x)

    monkeypatch.setattr(engine_mod.jax, "block_until_ready", wait)
    # Run until a step that ends a request BY LENGTH is in flight.
    while not (eng._inflight is not None and eng._inflight.released):
        assert eng.step()
    lost = [r.rid for r in eng._inflight.released]
    delivered = {r: list(eng._requests[r].generated) for r in rids}
    state["armed"] = True
    with pytest.raises(ConnectionError):
        eng.step() if via == "step" else eng.unfinished()
    state["armed"] = False
    assert state["raised"] == 1 and eng.metrics.snapshot()["retries"] == 0
    assert set(lost) <= set(eng.unfinished())
    assert eng._inflight is None
    assert all(q.in_flight == 0 for q in eng._requests.values())
    snap = eng.drain()
    unfinished = snap["requests"]
    assert unfinished and set(lost) <= set(unfinished)
    for rid in unfinished:
        assert snap["tree"][rid]["generated"].tolist() == delivered[rid]
    # The snapshot resumes exactly on a fresh engine.
    fresh = Engine(CFG, flat_params, num_slots=2, max_len=32,
                   prefill_chunk=4, donate=True)
    for kw in Engine.restore_requests(snap):
        kw = dict(kw)
        fresh.submit(kw.pop("prompt"), kw.pop("max_new_tokens"), **kw)
    assert fresh.run() == "idle"
    for rid, (p, n) in zip(rids, reqs):
        got = (fresh if rid in unfinished else eng).result(rid).tolist()
        assert got == _ref(flat_params, p, n).tolist()


def test_router_failover_records_a_lost_step_and_resumes_exactly(
        flat_params, monkeypatch):
    """A failover asked for while the replica's step in flight is
    doomed: the router lists the unfinished requests through the
    engine's public ``unfinished()``, RECORDS the lost step in a
    ``failover`` event, and every stream finishes on the survivor
    token for token."""
    from torchgpipe_tpu import fleet
    from torchgpipe_tpu.serving import engine as engine_mod

    class Recorder:
        events = []

        def record(self, kind, detail="", rid=None):
            self.events.append((kind, detail))

    router = fleet.Router(
        {n: Engine(CFG, flat_params, num_slots=2, max_len=32,
                   prefill_chunk=4, donate=True) for n in ("r0", "r1")},
        recorder=Recorder(), seed=0,
    )
    reqs = [(p, 6) for p, _ in _workload(seed=9, n=3)]
    rids = [router.submit(p, n, session="s") for p, n in reqs]
    dying = router._records[rids[0]].replica
    eng = router.replicas[dying].engine
    while not (eng._inflight is not None and eng._inflight.released):
        assert router.step()
    doomed = eng._inflight.tok
    real_wait = jax.block_until_ready

    def wait(x):
        if x is doomed:
            raise RuntimeError("the chip is gone")
        return real_wait(x)

    monkeypatch.setattr(engine_mod.jax, "block_until_ready", wait)
    moved = router.failover(dying)
    assert moved and not router.replicas[dying].alive
    lost = [d for k, d in Recorder.events
            if k == "failover" and "step in flight was lost" in d]
    assert len(lost) == 1 and "the chip is gone" in lost[0]
    assert router.run() == "idle"
    for rid, (p, n) in zip(rids, reqs):
        assert router.result(rid).tolist() == _ref(flat_params, p, n).tolist()


def _stepped(flat_params, serial, steps=9, **kw):
    """An engine ``steps`` iterations into a workload: the overlapped
    one with a step in flight, the serial reference with none."""
    eng = Engine(CFG, flat_params, num_slots=2, max_len=48,
                 prefill_chunk=4, donate=not serial, **kw)
    reqs = _workload(seed=31, n=4, new_hi=9)
    rids = [eng.submit(p, n + 4) for p, n in reqs]
    for _ in range(steps):
        eng.step()
        if serial:
            eng._settle()
    assert (eng._inflight is None) == serial
    return eng, rids, [(p, n + 4) for p, n in reqs]


@pytest.mark.parametrize("what", ["cancel", "preempt", "drain", "swap",
                                  "status"])
def test_callers_see_the_serial_state_with_a_step_in_flight(flat_params,
                                                            what):
    """``cancel`` / ``preempt_request`` / ``drain`` + ``restore_requests``
    / ``swap_params`` / ``status`` + ``result`` first settle the step in
    flight: what they observe and return is what the serial loop
    shows after the same steps."""
    fast, rids, reqs = _stepped(flat_params, serial=False)
    slow, _, _ = _stepped(flat_params, serial=True)
    active = list(slow.scheduler.active)
    assert active
    rid = active[0]

    def finish(eng, kwargs=()):
        for kw in kwargs:
            kw = dict(kw)
            eng.submit(kw.pop("prompt"), kw.pop("max_new_tokens"), **kw)
        eng.run()
        return {r: eng.result(r).tolist() for r in rids
                if r in eng._requests}

    if what == "cancel":
        assert fast.cancel(rid) and slow.cancel(rid)
        assert fast._inflight is None
        assert fast.result(rid).tolist() == slow.result(rid).tolist()
        assert finish(fast) == finish(slow)
    elif what == "preempt":
        a, b = fast.preempt_request(rid), slow.preempt_request(rid)
        assert a["prompt"].tolist() == b["prompt"].tolist()
        assert a["emitted_prefix"] == b["emitted_prefix"]
        assert a["max_new_tokens"] == b["max_new_tokens"]
        got = finish(fast, [a])
        assert got == finish(slow, [b])
        want = dict(zip(rids, reqs))[rid]
        assert got[rid] == _ref(flat_params, *want, max_len=48).tolist()
    elif what == "drain":
        a, b = fast.drain(), slow.drain()
        assert a["requests"] == b["requests"]
        assert {r: {k: v.tolist() for k, v in t.items()}
                for r, t in a["tree"].items()} == {
                    r: {k: v.tolist() for k, v in t.items()}
                    for r, t in b["tree"].items()}
        fresh = Engine(CFG, flat_params, num_slots=2, max_len=48,
                       prefill_chunk=4)
        done = finish(fresh, Engine.restore_requests(a))
        for r, (p, n) in zip(rids, reqs):
            got = done[r] if r in done else fast.result(r).tolist()
            assert got == _ref(flat_params, p, n, max_len=48).tolist()
    elif what == "swap":
        fast.swap_params(flat_params, 1)
        slow.swap_params(flat_params, 1)
        assert fast._inflight is None and fast.version == 1
        assert {r: fast.result(r).tolist() for r in rids} == {
            r: slow.result(r).tolist() for r in rids}
        assert finish(fast) == finish(slow)
        assert fast.compile_stats == {"prefill": 1, "decode": 1}
    else:
        assert [fast.status(r) for r in rids] == [
            slow.status(r) for r in rids]
        assert fast._inflight is None
        assert {r: fast.result(r).tolist() for r in rids} == {
            r: slow.result(r).tolist() for r in rids}
        assert fast.metrics.tokens_out == slow.metrics.tokens_out


@pytest.mark.parametrize("donate", [True, False])
def test_steps_launched_ahead_and_the_spans_that_say_so(flat_params, donate):
    """On an uninterrupted run of an engine that donates its cache every
    step but the first is launched while the one before it is in flight:
    the counter reads steps - 1 and each ``engine.step`` span carries
    ``ahead``.  An engine that can retry a failed step (``donate=False``)
    waits for every step where it launches it: nothing is ever launched
    ahead, and the pool is held twice, not three times.  The program set
    is what it was either way."""
    from torchgpipe_tpu.utils.tracing import Timeline

    eng = Engine(CFG, flat_params, num_slots=3, max_len=32,
                 prefill_chunk=4, donate=donate)
    eng.timeline = Timeline()
    for p, n in _workload(seed=2, n=5):
        eng.submit(p, n)
    assert eng.run() == "idle"
    snap = eng.metrics.snapshot()
    steps = [e for e in eng.timeline.events if e.name == "engine.step"]
    assert len(steps) == snap["engine_steps"] > 8
    ahead = snap["engine_steps"] - 1 if donate else 0
    assert snap["steps_launched_ahead"] == ahead
    assert [e.fields["ahead"] for e in steps] == (
        [0] + [1] * ahead if donate else [0] * len(steps))
    assert eng.compile_stats == {"prefill": 1, "decode": 1}
    assert eng.program_count == 2
    # A call that finds no action and nothing in flight is idle and
    # leaves no span; the engine never reports idle with a step in
    # flight.
    before = len(eng.timeline.events)
    assert eng.step() is False and eng._inflight is None
    assert len(eng.timeline.events) == before


# --------------------------------------------------------------------- #
# soak (slow tier)                                                      #
# --------------------------------------------------------------------- #


@pytest.mark.slow
def test_serving_soak_churn(flat_params):
    """Long random churn — submits, cancels, staggered steps — stays at
    two programs and exact outputs throughout."""
    rng = np.random.RandomState(11)
    eng = Engine(CFG, flat_params, num_slots=4, max_len=32,
                 prefill_chunk=4)
    live, done = {}, {}
    for i in range(40):
        prompt = rng.randint(0, 64, (int(rng.randint(2, 12)),)).astype(
            np.int32
        )
        new = int(rng.randint(1, 9))
        rid = eng.submit(prompt, new)
        live[rid] = (prompt, new)
        if rng.rand() < 0.15 and live:
            victim = list(live)[int(rng.randint(len(live)))]
            if eng.cancel(victim):
                live.pop(victim)
        for _ in range(int(rng.randint(0, 4))):
            eng.step()
    eng.run()
    assert eng.compile_stats == {"prefill": 1, "decode": 1}
    for rid, (prompt, new) in live.items():
        got = eng.result(rid)
        assert got.tolist() == _ref(flat_params, prompt, new).tolist(), rid


@pytest.mark.slow
def test_serving_soak_ragged_ladder(flat_params):
    """Ragged bursty churn through a LADDER engine: the program count
    stays at the certified bound (each bucket traced at most once) and
    every output stays exact."""
    rng = np.random.RandomState(23)
    eng = Engine(CFG, flat_params, num_slots=4, max_len=32,
                 prefill_chunk=(1, 2, 4, 8))
    live = {}
    for i in range(30):
        prompt = rng.randint(0, 64, (int(rng.randint(1, 17)),)).astype(
            np.int32
        )
        new = int(rng.randint(1, 9))
        live[eng.submit(prompt, new)] = (prompt, new)
        for _ in range(int(rng.randint(0, 4))):
            eng.step()
    eng.run()
    stats = eng.compile_stats
    assert sum(stats.values()) <= eng.program_count, stats
    assert all(v <= 1 for v in stats.values()), stats
    for rid, (prompt, new) in live.items():
        got = eng.result(rid)
        assert got.tolist() == _ref(flat_params, prompt, new).tolist(), rid
