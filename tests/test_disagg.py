"""Phase-disaggregated serving, pinned (docs/serving.md, disaggregation
section).

1. **The handoff is bitwise** — a 1-prefill + 1-decode fleet (KV rows
   shipped through the fixed-shape ``migrate_ingest`` program at each
   prompt completion) serves greedy streams bitwise equal to the
   single-engine reference, for fp and int8 (QuantKVCache) pools alike,
   with exactly one handoff per request and no retracing.
2. **Roles are statically certified and validated** — prefill engines
   compile the prefill ladder ONLY, decode engines exactly 2 programs;
   ``certify_disagg`` proves it; mixed/partial fleets and wrong-role
   calls are ValueErrors at construction, not runtime surprises.
3. **Pool state stays where it belongs** — radix-prefix hits pin donor
   slots on the PREFILL pool only (a migrated request never re-pins on
   its decode replica), and session pins bind decode placement only.
4. **Death in either pool resumes bitwise** — covered end-to-end in
   ``tools/disagg_verify.py`` (ci_lint step 14); here the policy halves:
   per-role autoscaler pools (decode priced by migration rate, never
   robbed below its floor), phase-filtered SLO blame, and the
   prefill-heavy trace preset's honesty counters.

Tier-1 budget: ONE module-scoped trained-params fixture; every test
that steps a compiled engine is slow-marked (the fast core keeps the
host-side policy/validation tests only).
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from torchgpipe_tpu import fleet
from torchgpipe_tpu.layers import sequential_init
from torchgpipe_tpu.models.generation import generate
from torchgpipe_tpu.models.transformer import TransformerConfig, llama
from torchgpipe_tpu.obs import MetricsRegistry, Objective, SloMonitor
from torchgpipe_tpu.serving import Engine

CFG = TransformerConfig(
    vocab=64, dim=32, n_layers=2, n_heads=4, n_kv_heads=2
)
MAX_LEN = 48


@pytest.fixture(scope="module")
def flat_params():
    params, _, _ = sequential_init(
        llama(CFG), jax.random.PRNGKey(0),
        jax.ShapeDtypeStruct((2, 8), jnp.int32),
    )
    return params


def _ref(params, prompt, new, **kw):
    return np.asarray(
        generate(CFG, params, jnp.asarray(prompt)[None, :], new,
                 max_len=MAX_LEN, **kw)
    )[0]


def _build(params, roles, seed=1, **engine_kw):
    reg = MetricsRegistry()
    router = fleet.Router(
        {
            name: Engine(
                CFG, params, num_slots=4, max_len=MAX_LEN,
                prefill_chunk=8, role=role,
                registry=reg.labeled(replica=name), **engine_kw,
            )
            for name, role in roles
        },
        registry=reg, seed=seed,
    )
    return router, reg


def _workload(seed, n, plen=(3, 9), new=(2, 7)):
    rng = np.random.RandomState(seed)
    return [
        (rng.randint(0, 64, (int(rng.randint(*plen)),)).astype(np.int32),
         int(rng.randint(*new)))
        for _ in range(n)
    ]


# --------------------------------------------------------------------- #
# 1. bitwise handoff (fp + int8), static certification                  #
# --------------------------------------------------------------------- #


@pytest.mark.slow  # fast-gate budget: compiled engines; CI full job
def test_split_fleet_bitwise_with_one_handoff_per_request(flat_params):
    router, reg = _build(
        flat_params, [("p0", "prefill"), ("d0", "decode")]
    )
    reqs = _workload(seed=0, n=6)
    rids = [router.submit(p, n, session=f"s{i % 2}")
            for i, (p, n) in enumerate(reqs)]
    assert router.run() == "idle"
    for rid, (p, n) in zip(rids, reqs):
        assert np.array_equal(
            router.result(rid), _ref(flat_params, p, n)
        ), rid
    assert reg.counter("fleet_migrations").value() == len(reqs)
    # the split SHRANK each replica's program set, and nothing retraced
    peng = router.replicas["p0"].engine
    deng = router.replicas["d0"].engine
    assert peng.program_count == len(peng.prefill_buckets)
    assert deng.program_count == 2            # decode + migrate_ingest
    for eng in (peng, deng):
        assert all(v <= 1 for v in eng.trace_counts.values())
    # every stream FINISHED on the decode pool, only MIGRATED through
    # the prefill pool
    assert all(
        r.status == "migrated"
        for r in peng.metrics.requests.values()
    )
    assert all(
        deng.metrics.requests[rid].status == "finished" for rid in rids
    )


@pytest.mark.slow  # fast-gate budget: compiled engines; CI full job
def test_int8_quantkv_rows_migrate_bitwise(flat_params):
    """Quantized pools ship rows AND scales: streams equal the int8
    single-engine reference exactly."""
    router, reg = _build(
        flat_params, [("p0", "prefill"), ("d0", "decode")],
        kv_quant=True,
    )
    reqs = _workload(seed=3, n=5)
    rids = [router.submit(p, n) for p, n in reqs]
    assert router.run() == "idle"
    for rid, (p, n) in zip(rids, reqs):
        assert np.array_equal(
            router.result(rid),
            _ref(flat_params, p, n, kv_quant=True),
        ), rid
    assert reg.counter("fleet_migrations").value() == len(reqs)


@pytest.mark.slow  # fast-gate budget: compiled engines; CI full job
def test_certify_disagg_certifies_the_pair(flat_params):
    from torchgpipe_tpu.analysis import Severity
    from torchgpipe_tpu.analysis.serving import certify_disagg

    router, _ = _build(
        flat_params, [("p0", "prefill"), ("d0", "decode")]
    )
    peng = router.replicas["p0"].engine
    deng = router.replicas["d0"].engine
    certs = certify_disagg(peng, deng)
    assert certs, "certification must report, not stay silent"
    assert all(f.severity < Severity.WARNING for f in certs), [
        f.message for f in certs if f.severity >= Severity.WARNING
    ]
    # swapped roles is a hard ERROR, not a shrug
    bad = certify_disagg(deng, peng)
    assert any(f.severity >= Severity.ERROR for f in bad)


# --------------------------------------------------------------------- #
# 2. construction-time validation                                       #
# --------------------------------------------------------------------- #


def test_role_and_fleet_validation(flat_params):
    with pytest.raises(ValueError, match="role"):
        Engine(CFG, flat_params, num_slots=2, max_len=MAX_LEN,
               role="draft")
    # a decode-role engine never prefills: a prefix cache is dead config
    with pytest.raises(ValueError, match="prefix cache"):
        Engine(CFG, flat_params, num_slots=2, max_len=MAX_LEN,
               role="decode",
               prefix_cache=fleet.RadixPrefixCache())
    # the fleet is all-unified or a full prefill+decode split — nothing
    # between
    with pytest.raises(ValueError):
        fleet.Router({
            "u0": Engine(CFG, flat_params, num_slots=2,
                         max_len=MAX_LEN, role="unified"),
            "p0": Engine(CFG, flat_params, num_slots=2,
                         max_len=MAX_LEN, role="prefill"),
        })
    with pytest.raises(ValueError, match="decode"):
        fleet.Router({
            "p0": Engine(CFG, flat_params, num_slots=2,
                         max_len=MAX_LEN, role="prefill"),
        })
    # speculation lives on unified replicas only — both phase roles
    # compile a REDUCED program set the speculative round can't run on
    with pytest.raises(ValueError, match="unified-only"):
        fleet.SpeculativeEngine(
            CFG, flat_params, CFG, flat_params, gamma=2,
            num_slots=2, max_len=MAX_LEN, role="prefill",
        )


def test_wrong_role_calls_are_refused(flat_params):
    deng = Engine(CFG, flat_params, num_slots=2, max_len=MAX_LEN,
                  role="decode")
    with pytest.raises(ValueError, match="ingest_migration"):
        deng.submit(np.zeros(3, np.int32), 4)
    ueng = Engine(CFG, flat_params, num_slots=2, max_len=MAX_LEN)
    with pytest.raises(ValueError, match="role"):
        ueng.ingest_migration(
            rid="q0", prompt=np.zeros(3, np.int32), max_new_tokens=4,
            rows={}, last_token=1,
        )


# --------------------------------------------------------------------- #
# 3. pool state stays where it belongs                                  #
# --------------------------------------------------------------------- #


@pytest.mark.slow  # fast-gate budget: compiled engines; CI full job
def test_prefix_hits_never_repin_on_the_decode_pool(flat_params):
    """Shared-prefix requests reuse donor KV on the PREFILL replica;
    after migration the decode replica holds plain slots — zero pins —
    and frees every one of them at stream end."""
    # built by hand: only the prefill engine may carry the cache
    pc = fleet.RadixPrefixCache(min_prefix_len=4)
    reg = MetricsRegistry()
    peng = Engine(CFG, flat_params, num_slots=4, max_len=MAX_LEN,
                  prefill_chunk=8, role="prefill", prefix_cache=pc,
                  registry=reg.labeled(replica="p0"))
    deng = Engine(CFG, flat_params, num_slots=4, max_len=MAX_LEN,
                  prefill_chunk=8, role="decode",
                  registry=reg.labeled(replica="d0"))
    router = fleet.Router({"p0": peng, "d0": deng}, registry=reg,
                          seed=1)
    rng = np.random.RandomState(5)
    prefix = rng.randint(0, 64, (8,)).astype(np.int32)
    reqs = [
        (np.concatenate([
            prefix,
            rng.randint(0, 64, (int(rng.randint(1, 5)),))
            .astype(np.int32),
        ]), int(rng.randint(2, 6)))
        for _ in range(6)
    ]
    rids = [router.submit(p, n) for p, n in reqs]
    assert router.run() == "idle"
    for rid, (p, n) in zip(rids, reqs):
        assert np.array_equal(
            router.result(rid), _ref(flat_params, p, n)
        ), rid
    assert peng._prefix_cache.hits > 0        # reuse actually happened
    assert deng.pool.num_pinned == 0          # pins never crossed over
    assert deng.pool.num_free == deng.pool.num_slots
    peng.pool.check_refcounts()


@pytest.mark.slow  # fast-gate budget: compiled engines; CI full job
def test_session_pins_bind_decode_placement_only(flat_params):
    router, _ = _build(
        flat_params,
        [("p0", "prefill"), ("d0", "decode"), ("d1", "decode")],
        seed=2,
    )
    reqs = _workload(seed=9, n=8)
    rids = [router.submit(p, n, session=f"s{i % 2}")
            for i, (p, n) in enumerate(reqs)]
    assert router.run() == "idle"
    for rid, (p, n) in zip(rids, reqs):
        assert np.array_equal(
            router.result(rid), _ref(flat_params, p, n)
        ), rid
    # each session's streams all finished on ONE decode replica, and
    # the pin names a decode-pool member
    for s in ("s0", "s1"):
        assert router._sessions[s] in router.pools["decode"]
        homes = {
            name
            for name in ("d0", "d1")
            for rid, r in
            router.replicas[name].engine.metrics.requests.items()
            if r.status == "finished"
            and rid in rids[int(s[1]) :: 2]
        }
        assert len(homes) == 1, (s, homes)


# --------------------------------------------------------------------- #
# 4. policy halves: trace preset, SLO phase blame, per-role autoscaler  #
# --------------------------------------------------------------------- #


def test_prefill_heavy_preset_is_deterministic_and_honest():
    cfg = fleet.prefill_heavy_config(60, seed=4, max_len=48)
    s1, s2 = fleet.TraceStats(), fleet.TraceStats()
    a = list(fleet.synthetic_trace(cfg, s1))
    b = list(fleet.synthetic_trace(cfg, s2))
    assert [r.prompt.tolist() for r in a] == [
        r.prompt.tolist() for r in b
    ]
    assert s1.skipped_too_long == 0           # every request fits
    assert s1.burst_arrivals > 0
    # the burst state is the prefill storm: long prompts, tiny budgets
    assert s1.burst_prompt_tokens > 0
    bursty = [r for r in a if len(r.prompt) >= 24]
    assert bursty and all(r.max_new_tokens <= 4 for r in bursty)
    for r in a:
        assert len(r.prompt) + r.max_new_tokens <= 48


def test_slo_objective_phase_validation_and_filtered_blame():
    with pytest.raises(ValueError, match="phase"):
        Objective(name="x", series="s", threshold=0.1, phase="draft")

    class Clock:
        t = 0.0

        def __call__(self):
            return self.t

    clock = Clock()
    reg = MetricsRegistry(clock=clock)
    h = reg.histogram("serving_tpot_seconds", labels=("replica",))
    mon = SloMonitor(
        reg,
        [
            Objective(name="ttft-p95", series="serving_ttft_seconds",
                      threshold=0.1, phase="prefill"),
            Objective(name="tpot-p95", series="serving_tpot_seconds",
                      threshold=0.1, phase="decode"),
        ],
        short_window=10.0, long_window=40.0, min_count=2,
        min_interval=0.0,
    )
    for _ in range(50):
        clock.t += 1.0
        h.observe(9.0, replica="d0")
        mon.tick()
    # decode burn blames the decode pool's replica — and ONLY when the
    # caller asks about the decode phase (or doesn't filter at all)
    assert mon.breaching() == {"d0"}
    assert mon.breaching(phase="decode") == {"d0"}
    assert mon.breaching(phase="prefill") == set()


class _FakePool:
    def __init__(self, n):
        self.num_slots = n
        self.max_len = 32
        self.num_free = n


class _FakeScheduler:
    def __init__(self):
        self.queue = []
        self.active = {}


class _FakeEngine:
    """Engine facade for policy tests: enough surface for the router's
    construction-time checks (role, pool compatibility) and the
    autoscaler's drain/resume actuation — no compiled programs."""

    def __init__(self, role):
        self.role = role
        self.drain_hooks = []
        self.pool = _FakePool(1)
        self.scheduler = _FakeScheduler()
        self.admitting = True

    def kv_row_specs(self):
        return {}

    def take_migration_ready(self):
        return []

    def unfinished(self):
        return []

    def drain(self):
        self.admitting = False
        return {"tree": {}, "requests": {}}

    def resume_serving(self):
        self.admitting = True


def test_autoscaler_prices_pools_separately_and_guards_the_floor():
    """The decode pool is priced by the migration counter, scaled
    within its own pool only, and never drained below its floor to
    feed a burning prefill window."""

    class Clock:
        t = 0.0

        def __call__(self):
            return self.t

    clock = Clock()
    reg = MetricsRegistry(clock=clock)
    router = fleet.Router(
        {
            "p0": _FakeEngine("prefill"), "p1": _FakeEngine("prefill"),
            "d0": _FakeEngine("decode"), "d1": _FakeEngine("decode"),
        },
        registry=reg,
    )
    scaler = fleet.Autoscaler(
        router, service_time_s=0.05, headroom=1.0, hold_ticks=1,
    )
    # Idle: both pools collapse to their own floor of 1, prefill pool
    # visited first, ONE action per tick.
    acts = []
    for _ in range(3):
        clock.t += 0.1
        acts.append(scaler.tick())
    assert acts == ["down:p1", "down:d1", None]
    assert scaler.parked == ["p1", "d1"]
    for _ in range(3):                        # per-pool floors hold
        clock.t += 0.1
        assert scaler.tick() is None
    # A prefill storm prices ONLY the prefill pool: d1 stays parked
    # (its pool's verdict is still 1) while p1 returns.
    scaler.observe_arrival(60)
    assert scaler.desired_replicas(role="prefill") == 2   # pool cap
    assert scaler.desired_replicas(role="decode") == 1
    clock.t += 0.01
    scaler.observe_arrival(1)
    assert scaler.tick() == "up:p1"
    assert scaler.parked == ["d1"]
    # Handoffs start flowing: the migration counter is the decode
    # pool's own arrival window, and it un-parks d1.
    clock.t += 60.0                           # drain the prefill window
    for _ in range(3):
        clock.t += 0.5
        router._c_migrations.inc(30)
        if scaler.tick() == "up:d1":
            break
    assert "d1" not in scaler.parked
    assert scaler.desired_replicas(role="decode") == 2


# --------------------------------------------------------------------- #
# 5. observability: the stitched story of one migrated request          #
# --------------------------------------------------------------------- #


@pytest.mark.slow  # fast-gate budget: compiled engines; CI full job
def test_stitched_trace_tells_the_handoff_story(flat_params):
    """One rid's flight events across prefill replica, decode replica,
    and router stitch into a single complete tree: a prefill-phase
    attempt, an explicit kv-handoff migration span, a decode-phase
    attempt — no orphans."""
    from torchgpipe_tpu import obs
    from torchgpipe_tpu.obs.flightrec import (
        FlightRecorder,
        dump_from_dict,
    )

    recs = {n: FlightRecorder(worker=n) for n in ("p0", "d0")}
    router_rec = FlightRecorder(worker="router")
    reg = MetricsRegistry()
    router = fleet.Router(
        {
            n: Engine(CFG, flat_params, num_slots=4, max_len=MAX_LEN,
                      prefill_chunk=8, role=role, recorder=recs[n],
                      registry=reg.labeled(replica=n))
            for n, role in (("p0", "prefill"), ("d0", "decode"))
        },
        registry=reg, seed=1, recorder=router_rec,
    )
    reqs = _workload(seed=11, n=3)
    rids = [router.submit(p, n) for p, n in reqs]
    assert router.run() == "idle"
    dumps = [dump_from_dict(r.to_dict())
             for r in (*recs.values(), router_rec)]
    trace = obs.stitch_request(dumps, rids[0])
    assert trace.replicas == ["p0", "d0"]
    assert trace.migrations == 1
    assert trace.orphans == [] and trace.complete
    names = [s.name for s in trace.root.children]
    assert "attempt@p0:prefill" in names      # phase-labeled attempts
    assert "attempt@d0:decode" in names
    assert "migration p0->d0" in names
    mig = next(s for s in trace.root.children
               if s.name == "migration p0->d0")
    assert "kv handoff" in mig.detail         # not a failover move
    p_attempt = next(s for s in trace.root.children
                     if s.name == "attempt@p0:prefill")
    assert [c.name for c in p_attempt.children][-1] == "handoff"
    d_attempt = next(s for s in trace.root.children
                     if s.name == "attempt@d0:decode")
    kinds = [c.name for c in d_attempt.children]
    assert "decode" in kinds and kinds[-1] == "finish"
    tree = obs.format_request_tree(trace)
    assert "attempt@d0:decode" in tree
