"""Elastic world-size resize, pinned (docs/robustness.md + serving.md).

1. **Kill-and-resume is exact** — 4 stages lose a rank at a megastep
   boundary (``faults.inject(die_at_megastep=...)``), the supervisor
   resumes CERTIFIED on 2 stages with the loss trajectory bitwise equal
   to a hand-resized oracle, then re-absorbs the returned capacity back
   to 4.
2. **The restore path rewinds honestly** — a mid-step ``PeerDiedError``
   means unsaved state died with the rank: the supervisor restores the
   newest snapshot (taken under the OLD cut, routed through
   ``repartition``) and replays from its step.
3. **Optimizer state is carried when the cut survives, re-initialized
   when it doesn't** — both paths asserted, the carried one bitwise
   against an undisturbed run.
4. **Scale-up waits for the megastep boundary** — capacity returned
   mid-megastep is absorbed at the NEXT boundary, never inside the
   compiled K-step program.
5. **World-size-aware manifests** — the corrupt-manifest +
   wrong-world-size pair on :class:`CheckpointManager`.
6. **Transport backoff is jittered and capped**, and retries land on
   the ``retries_total{rank}`` counter.
7. **The autoscaler is a damped control loop** — Little's-law pricing,
   hysteresis, cooldown, the ``slo_min_in_rotation`` floor, the SLO
   burn override — and its scale-down never drops an in-flight request
   (real engines, streams bitwise).

The real-process rank-death path (LocalTransport fixture in a bounded
subprocess) is the ``elastic-verify`` gate, slow-marked here.
"""

import os
import random
import socket
import subprocess
import sys

import numpy as np
import pytest

import jax
import jax.numpy as jnp
import optax

from torchgpipe_tpu import GPipe, fleet
from torchgpipe_tpu.analysis import planner
from torchgpipe_tpu.distributed.context import (
    RETRY_BACKOFF_BASE_S,
    RETRY_BACKOFF_CAP_S,
    PeerDiedError,
    TcpTransport,
    _retry_sleep_s,
)
from torchgpipe_tpu.layers import named, sequential_init
from torchgpipe_tpu.models.generation import generate
from torchgpipe_tpu.models.transformer import TransformerConfig, llama
from torchgpipe_tpu.obs import MetricsRegistry
from torchgpipe_tpu.obs.flightrec import FlightRecorder
from torchgpipe_tpu.ops import dense, gelu
from torchgpipe_tpu.resilience import faults
from torchgpipe_tpu.resilience.checkpoint import (
    CheckpointError,
    CheckpointManager,
)
from torchgpipe_tpu.resilience.supervisor import (
    Supervisor,
    SupervisorError,
    _even_balance,
)
from torchgpipe_tpu.serving import Engine


def mse(out, tgt):
    return jnp.mean((out.astype(jnp.float32) - tgt) ** 2)


def _layers():
    return named([
        dense(16, name="fc1"), gelu("a1"),
        dense(16, name="fc2"), dense(8, name="head"),
    ])


_X = jax.random.normal(jax.random.PRNGKey(0), (8, 16))
_Y = jax.random.normal(jax.random.PRNGKey(1), (8, 8))


def _batch(step):
    # Distinct deterministic batch per step: a restore-and-rewind must
    # replay the SAME data stream or continuity claims are vacuous.
    k = jax.random.fold_in(jax.random.PRNGKey(7), step)
    return _X + 0.01 * jax.random.normal(k, _X.shape), _Y


def _pipe4(**kw):
    return GPipe(_layers(), balance=[1, 1, 1, 1], chunks=2,
                 hbm_budget_bytes=64 << 30, **kw)


def _sup(pipe, tmp_path, **kw):
    kw.setdefault("world", list(range(len(pipe.balance))))
    kw.setdefault("stage_counts", (4, 2, 1))
    return Supervisor(
        pipe, optax.sgd(1e-2), mse, _batch,
        checkpoint=CheckpointManager(os.path.join(str(tmp_path), "ck")),
        **kw,
    )


def _init(pipe):
    spec = jax.ShapeDtypeStruct(_X.shape, _X.dtype)
    return pipe.init(jax.random.PRNGKey(0), spec)


# --------------------------------------------------------------------- #
# 1. the demo: 4 -> 2 -> 4 kill-and-resume, bitwise vs oracle           #
# --------------------------------------------------------------------- #


def test_kill_and_resume_4_2_4_bitwise(tmp_path):
    pipe = _pipe4()
    params, state = _init(pipe)
    reg = MetricsRegistry()
    rec = FlightRecorder(
        rank=0, dump_path=os.path.join(str(tmp_path), "flight.json")
    )
    sup = _sup(pipe, tmp_path, registry=reg, recorder=rec)
    # Oracle plan FIRST (same public search the supervisor runs), while
    # the supervisor's pipe is still the pristine 4-stage one.
    plan2 = sup.plan_for(2)
    assert plan2 is not None and plan2.feasible and plan2.certified

    with faults.inject(die_at_megastep=(3, 2)):
        res = sup.run(4, params, state)
    assert [e.reason for e in res.events] == ["rank-death:3"]
    assert res.events[0].action == "checkpoint"
    assert res.events[0].certified
    assert res.pipe.balance == [2, 2]
    assert len(res.losses) == 4

    # Oracle: 2 undisturbed steps on 4 stages, hand-resize through the
    # SAME certified plan via the public apply_plan + repartition, 2
    # more steps.  Same programs, same reduction order -> bitwise.
    opipe = _pipe4()
    oparams, ostate = _init(opipe)
    opt = optax.sgd(1e-2)
    oopt = opipe.init_opt_state(opt, oparams)
    ostep = opipe.make_train_step(opt, mse)
    olosses = []
    for i in range(2):
        x, y = _batch(i)
        loss, oparams, oopt, ostate, _ = ostep(oparams, oopt, ostate, x, y)
        olosses.append(float(loss))
    opipe2 = planner.apply_plan(opipe, plan2)
    oparams = opipe2.place(opipe2.repartition(oparams))
    ostate = opipe2.place(opipe2.repartition(ostate))
    oopt = opipe2.init_opt_state(opt, oparams)
    ostep2 = opipe2.make_train_step(opt, mse)
    for i in range(2, 4):
        x, y = _batch(i)
        loss, oparams, oopt, ostate, _ = ostep2(oparams, oopt, ostate, x, y)
        olosses.append(float(loss))
    np.testing.assert_array_equal(
        np.asarray(res.losses), np.asarray(olosses)
    )

    # Scale back up: returned capacity re-absorbed, training continues.
    sup.return_capacity([3])
    res2 = sup.run(2, res.params, res.state, res.opt_state)
    assert res2.pipe.balance == [1, 1, 1, 1]
    up = res2.events[-1]
    assert up.reason == "capacity-returned" and up.to_stages == 4
    # Every decision is a recorded incident: registry + flight dump.
    c = reg.counter("supervisor_resizes_total", labels=("direction",))
    assert c.value(direction="down") == 1
    assert c.value(direction="up") == 1
    assert reg.gauge("supervisor_world_size").value() == 4.0
    kinds = [e.kind for e in rec.events()]
    assert kinds.count("supervisor_resize") == 2
    assert os.path.exists(os.path.join(str(tmp_path), "flight.json"))


# --------------------------------------------------------------------- #
# 2. mid-step death: restore + rewind                                   #
# --------------------------------------------------------------------- #


def test_mid_step_death_restores_and_rewinds(tmp_path):
    pipe = _pipe4()
    params, state = _init(pipe)
    died = []

    def batch_fn(step):
        if step == 3 and not died:
            died.append(step)
            raise PeerDiedError(3, "w3", "listener gone")
        return _batch(step)

    sup = Supervisor(
        pipe, optax.sgd(1e-2), mse, batch_fn,
        checkpoint=CheckpointManager(os.path.join(str(tmp_path), "ck")),
        world=[0, 1, 2, 3], stage_counts=(4, 2, 1), checkpoint_every=2,
    )
    res = sup.run(6, params, state)
    ev = res.events[0]
    assert ev.action == "restore"
    assert ev.reason == "peer-died:3"
    # cadence 2: the newest snapshot before the step-3 death is step 2,
    # so the run rewound there and replayed.
    assert ev.step == 2
    assert res.pipe.balance == [2, 2]
    assert res.steps == 6 and len(res.losses) == 6


def test_unattributed_timeout_reraises(tmp_path):
    pipe = _pipe4()
    params, state = _init(pipe)

    def batch_fn(step):
        if step == 1:
            raise TimeoutError("recv timed out")  # no rank, no verdict
        return _batch(step)

    sup = Supervisor(
        pipe, optax.sgd(1e-2), mse, batch_fn,
        checkpoint=CheckpointManager(os.path.join(str(tmp_path), "ck")),
        world=[0, 1, 2, 3],
    )
    with pytest.raises(TimeoutError):
        sup.run(2, params, state)


# --------------------------------------------------------------------- #
# 3. optimizer state across a resize: carried vs re-initialized         #
# --------------------------------------------------------------------- #


def test_opt_state_carried_when_cut_survives(tmp_path):
    # 5 ranks hold a 4-stage pipe; losing the spare keeps the stage
    # count, keeps the cut, and must keep momentum BITWISE: the whole
    # trajectory equals an undisturbed run's.
    pipe = _pipe4()
    params, state = _init(pipe)
    opt = optax.sgd(1e-2, momentum=0.9)
    sup = Supervisor(
        pipe, opt, mse, _batch,
        checkpoint=CheckpointManager(os.path.join(str(tmp_path), "ck")),
        world=[0, 1, 2, 3, 4], stage_counts=(4, 2),
    )
    with faults.inject(die_at_megastep=(4, 1)):
        res = sup.run(4, params, state)
    assert [e.opt_state for e in res.events] == ["carried"]
    assert res.events[0].from_stages == res.events[0].to_stages == 4

    opipe = _pipe4()
    oparams, ostate = _init(opipe)
    oopt = opipe.init_opt_state(opt, oparams)
    ostep = opipe.make_train_step(opt, mse)
    olosses = []
    for i in range(4):
        x, y = _batch(i)
        loss, oparams, oopt, ostate, _ = ostep(oparams, oopt, ostate, x, y)
        olosses.append(float(loss))
    np.testing.assert_array_equal(
        np.asarray(res.losses), np.asarray(olosses)
    )


def test_opt_state_reinit_when_cut_changes(tmp_path):
    pipe = _pipe4()
    params, state = _init(pipe)
    sup = Supervisor(
        pipe, optax.sgd(1e-2, momentum=0.9), mse, _batch,
        checkpoint=CheckpointManager(os.path.join(str(tmp_path), "ck")),
        world=[0, 1, 2, 3], stage_counts=(4, 2),
    )
    with faults.inject(die_at_megastep=(1, 1)):
        res = sup.run(2, params, state)
    assert [e.opt_state for e in res.events] == ["reinit"]
    assert res.events[0].to_stages == 2
    # Honestly re-initialized: fresh momentum is all zeros.
    fresh = res.pipe.init_opt_state(optax.sgd(1e-2, momentum=0.9),
                                    res.params)
    chex_like = jax.tree_util.tree_structure(res.opt_state)
    assert jax.tree_util.tree_structure(fresh) == chex_like


# --------------------------------------------------------------------- #
# 4. scale-up waits for the megastep boundary                           #
# --------------------------------------------------------------------- #


def test_scale_up_absorbed_at_megastep_boundary(tmp_path):
    pipe = GPipe(_layers(), balance=[2, 2], chunks=2, fused=True,
                 megastep=2, devices=[jax.devices()[0]],
                 hbm_budget_bytes=64 << 30)
    params, state = _init(pipe)
    holder = {}

    def batch_fn(step):
        # Capacity comes back MID-megastep (while round [0, 1] runs):
        # absorption must wait for the next boundary.
        if step == 1:
            holder["sup"].return_capacity([2, 3])
        return _batch(step)

    sup = Supervisor(
        pipe, optax.sgd(1e-2), mse, batch_fn,
        checkpoint=CheckpointManager(os.path.join(str(tmp_path), "ck")),
        world=[0, 1], stage_counts=(4, 2),
    )
    holder["sup"] = sup
    res = sup.run(4, params, state)
    assert [e.reason for e in res.events] == ["capacity-returned"]
    ev = res.events[0]
    assert ev.step == 2 and ev.step % 2 == 0  # the boundary, not step 1
    assert ev.to_stages == 4
    assert res.pipe.balance == [1, 1, 1, 1]
    assert len(res.losses) == 4


def test_no_certified_plan_refuses_resume(tmp_path):
    pipe = _pipe4()
    params, state = _init(pipe)
    sup = _sup(pipe, tmp_path, stage_counts=(4,))  # 4 is the ONLY count
    with faults.inject(die_at_megastep=(3, 1)):
        with pytest.raises(SupervisorError):
            sup.run(2, params, state)


# --------------------------------------------------------------------- #
# 5. world-size-aware manifests                                         #
# --------------------------------------------------------------------- #


def _stage_params(tmp_path, balance):
    pipe = GPipe(_layers(), balance=list(balance), chunks=2)
    params, state = _init(pipe)
    return pipe, params, state


def test_restore_wrong_world_size_routes_through_repartition(tmp_path):
    pipe4, params4, _ = _stage_params(tmp_path, [1, 1, 1, 1])
    mgr = CheckpointManager(os.path.join(str(tmp_path), "ck"))
    mgr.save(5, params4, world_size=4, balance=[1, 1, 1, 1])

    pipe2, params2_t, _ = _stage_params(tmp_path, [2, 2])
    # Legacy behavior (no world_size declared): the strict template
    # unflatten fails on the structure mismatch.
    with pytest.raises(CheckpointError):
        mgr.restore_latest(params2_t)
    # Declared: the snapshot comes back FLAT with its recorded cut, and
    # the caller routes through repartition explicitly.
    snap = mgr.restore_latest(params2_t, world_size=2)
    assert snap is not None
    assert isinstance(snap.tree, dict)
    assert snap.metadata["world_size"] == 4
    assert snap.metadata["balance"] == [1, 1, 1, 1]
    strict = mgr.restore_step(snap.step, params4)
    carried = pipe2.place(pipe2.repartition(strict.tree))
    flat_a = jax.tree_util.tree_leaves(carried)
    flat_b = jax.tree_util.tree_leaves(params4)
    assert len(flat_a) == len(flat_b)
    for a, b in zip(flat_a, flat_b):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    # Matching world size restores through the template as before.
    snap4 = mgr.restore_latest(params4, world_size=4)
    assert not isinstance(snap4.tree, dict)


def test_restore_corrupt_manifest_skipped(tmp_path):
    _, params4, _ = _stage_params(tmp_path, [1, 1, 1, 1])
    mgr = CheckpointManager(os.path.join(str(tmp_path), "ck"))
    good = mgr.save(1, params4, world_size=4, balance=[1, 1, 1, 1])
    bad = mgr.save(2, params4, world_size=4, balance=[1, 1, 1, 1])
    with open(os.path.join(bad, "manifest.json"), "w") as f:
        f.write("{not json")
    snap = mgr.restore_latest(world_size=2)
    assert snap is not None and snap.step == 1  # corrupt step 2 skipped
    assert mgr._recorded_world_size(2) is None
    assert mgr._recorded_world_size(1) == 4
    assert good != bad


# --------------------------------------------------------------------- #
# 6. fault hook + transport backoff satellites                          #
# --------------------------------------------------------------------- #


def test_die_at_megastep_is_trace_inert():
    assert not faults.should_die_at_megastep(0, 99)  # no active plan
    with faults.inject(die_at_megastep=(1, 2)):
        # Host-side only: never tokens the compiled-program caches.
        assert faults.plan_token() is None
        assert not faults.should_die_at_megastep(1, 0)
        assert not faults.should_die_at_megastep(1, 1)
        assert faults.should_die_at_megastep(1, 2)
        assert faults.should_die_at_megastep(1, 7)   # at-or-after k
        assert not faults.should_die_at_megastep(0, 7)
    assert not faults.should_die_at_megastep(1, 2)   # plan left


def test_retry_backoff_jitter_and_cap():
    rng = random.Random(0)
    first = [_retry_sleep_s(1, rng) for _ in range(64)]
    # Equal-jitter around the base: [base/2, base], genuinely spread.
    assert all(
        RETRY_BACKOFF_BASE_S / 2 <= s <= RETRY_BACKOFF_BASE_S
        for s in first
    )
    assert max(first) - min(first) > 0.05
    # Exponential until the cap, then pinned to [cap/2, cap] forever.
    for attempt in (5, 8, 20, 100):
        s = _retry_sleep_s(attempt, rng)
        assert RETRY_BACKOFF_CAP_S / 2 <= s <= RETRY_BACKOFF_CAP_S
    # Deterministic per seed (reproducible traces).
    a = [_retry_sleep_s(i, random.Random(3)) for i in range(1, 6)]
    b = [_retry_sleep_s(i, random.Random(3)) for i in range(1, 6)]
    assert a == b


def test_tcp_retries_land_on_registry_counter():
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        dead_port = s.getsockname()[1]  # bound-then-closed: refused
    reg = MetricsRegistry()
    transport = TcpTransport(
        "w0", {"w0": ("127.0.0.1", 0), "w1": ("127.0.0.1", dead_port)},
        connect_timeout=1.0, registry=reg,
    )
    try:
        with pytest.raises(TimeoutError):
            transport.send("w1", "forward", 0, np.zeros((2,)))
    finally:
        transport.close()
    retried = reg.counter(
        "retries_total", labels=("rank",)
    ).value(rank="w0")
    assert retried >= 1


# --------------------------------------------------------------------- #
# 7. the autoscaler policy                                              #
# --------------------------------------------------------------------- #


class _FakePool:
    def __init__(self, n):
        self.num_slots = n


class _FakeScheduler:
    def __init__(self):
        self.queue = []
        self.active = {}


class _FakeEngine:
    def __init__(self, slots=1):
        self.drain_hooks = []
        self.pool = _FakePool(slots)
        self.scheduler = _FakeScheduler()
        self.admitting = True

    def unfinished(self):
        return []

    def drain(self):
        self.admitting = False
        return {"tree": {}, "requests": {}}

    def resume_serving(self):
        self.admitting = True


class _Clock:
    def __init__(self):
        self.t = 0.0

    def __call__(self):
        return self.t


def _policy_fixture(n=3, **kw):
    clock = _Clock()
    reg = MetricsRegistry(clock=clock)
    router = fleet.Router(
        {f"r{i}": _FakeEngine() for i in range(n)}, registry=reg,
    )
    kw.setdefault("service_time_s", 0.05)
    kw.setdefault("headroom", 1.0)
    kw.setdefault("hold_ticks", 2)
    scaler = fleet.Autoscaler(router, **kw)
    return clock, router, scaler


def test_autoscaler_trajectory_hysteresis_and_bounds():
    clock, router, scaler = _policy_fixture()
    traj = []
    # Idle: desired collapses to min_replicas=1, but only after
    # hold_ticks consecutive agreeing ticks, one replica per action.
    for _ in range(5):
        clock.t += 0.1
        traj.append(scaler.tick())
    assert traj == [None, "down:r2", None, "down:r1", None]
    assert scaler.parked == ["r2", "r1"]
    assert sum(r.in_rotation for r in router.replicas.values()) == 1
    # The floor holds: further idle ticks never park the last replica.
    for _ in range(4):
        clock.t += 0.1
        assert scaler.tick() is None
    # Burst: 60 arrivals in-window at 0.05 s/req over 1 slot = demand 3.
    scaler.observe_arrival(60)
    assert scaler.desired_replicas() == 3
    up = []
    for _ in range(4):
        clock.t += 0.01  # stay inside the rate window
        scaler.observe_arrival(1)
        up.append(scaler.tick())
    assert up == [None, "up:r1", None, "up:r2"]  # LIFO: warm ones first
    assert scaler.parked == []
    assert sum(r.in_rotation for r in router.replicas.values()) == 3


def test_autoscaler_cooldown_and_slo_floor():
    clock, router, scaler = _policy_fixture(cooldown_s=10.0)
    for _ in range(6):
        clock.t += 0.1
        scaler.tick()
    # One action, then the cooldown gates the next despite the trend.
    parked = list(scaler.parked)
    assert len(parked) == 1
    clock.t += 10.0
    scaler.tick()
    clock.t += 0.1
    scaler.tick()
    assert len(scaler.parked) == 2

    # slo_min_in_rotation lifts the autoscaler's own floor.
    clock2 = _Clock()
    reg2 = MetricsRegistry(clock=clock2)
    router2 = fleet.Router(
        {f"r{i}": _FakeEngine() for i in range(3)}, registry=reg2,
        slo_min_in_rotation=2,
    )
    scaler2 = fleet.Autoscaler(
        router2, service_time_s=0.05, hold_ticks=1, min_replicas=1
    )
    assert scaler2.min_replicas == 2
    for _ in range(5):
        clock2.t += 0.1
        scaler2.tick()
    assert sum(r.in_rotation for r in router2.replicas.values()) == 2


def test_autoscaler_slo_burn_overrides_demand():
    class _BurningSlo:
        def active_alerts(self):
            return ["p95_ttft"]

    clock, router, scaler = _policy_fixture(slo=_BurningSlo())
    # Zero arrivals, but the alert is firing: desired = active + 1,
    # clamped to the fleet -> never a scale-down while burning.
    assert scaler.desired_replicas() == 3
    for _ in range(5):
        clock.t += 0.1
        assert scaler.tick() is None


def test_autoscaler_rejects_unpriced_and_bad_bounds():
    _, router, _ = _policy_fixture()
    with pytest.raises(ValueError):
        fleet.Autoscaler(router)  # no cost model, no declared time
    with pytest.raises(ValueError):
        fleet.Autoscaler(router, service_time_s=0.05, headroom=0.5)
    with pytest.raises(ValueError):
        fleet.Autoscaler(
            router, service_time_s=0.05, min_replicas=5, max_replicas=2
        )


# ----- real engines: a scale-down never drops an in-flight request --- #

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


def _ref(params, prompt, new, max_len=32):
    return np.asarray(
        generate(CFG, params, jnp.asarray(prompt)[None, :], new,
                 max_len=max_len)
    )[0]


def test_autoscaler_scale_down_streams_bitwise(flat_params):
    clock = _Clock()
    reg = MetricsRegistry(clock=clock)
    router = fleet.Router(
        {n: Engine(CFG, flat_params, num_slots=4, max_len=32,
                   prefill_chunk=8, registry=reg.labeled(replica=n))
         for n in ("r0", "r1")},
        registry=reg, seed=0,
    )
    scaler = fleet.Autoscaler(
        router, service_time_s=0.05, hold_ticks=1, min_replicas=1
    )
    rng = np.random.RandomState(0)
    reqs = [
        (rng.randint(0, 64, (6,)).astype(np.int32), 4) for _ in range(4)
    ]
    rids = [router.submit(p, n, session="s0") for p, n in reqs]
    for _ in range(2):
        router.step()
    clock.t += 5.0  # arrivals age out: desired collapses to 1
    action = scaler.tick()
    assert action is not None and action.startswith("down:")
    assert router.run() == "idle"
    for rid, (p, n) in zip(rids, reqs):
        assert np.array_equal(router.result(rid), _ref(flat_params, p, n))
    # And the resize is a recorded incident.
    assert reg.counter(
        "autoscaler_resizes_total", labels=("direction",)
    ).value(direction="down") == 1


# --------------------------------------------------------------------- #
# the real-process path: the elastic-verify gate, slow-marked           #
# --------------------------------------------------------------------- #


@pytest.mark.slow
def test_elastic_verify_gate_passes():
    proc = subprocess.run(
        [sys.executable, os.path.join("tools", "elastic_verify.py")],
        cwd=os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        capture_output=True, text=True, timeout=600,
    )
    assert proc.returncode == 0, proc.stdout + proc.stderr


def test_even_balance_helper():
    assert _even_balance(4, 2) == (2, 2)
    assert _even_balance(4, 4) == (1, 1, 1, 1)
    assert _even_balance(5, 2) == (3, 2)
    assert _even_balance(7, 3) == (3, 2, 2)
