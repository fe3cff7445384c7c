"""Resilience-stack tests: crash-safe checkpoints, kill-and-resume,
guarded steps, fault injection, peer-death detection.

The load-bearing property (ISSUE 2 acceptance): a run preempted at an
arbitrary step resumes from ``restore_latest()`` and reaches **bitwise
identical** params/opt-state to an uninterrupted run — on both engines.
Everything here is CPU-sized and tier-1 (no ``slow`` marker): resilience
code that is only exercised on hardware is resilience code that is never
exercised.
"""

import json
import os
import signal

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from torchgpipe_tpu import GPipe, SpmdGPipe, make_mesh
from torchgpipe_tpu.distributed import (
    DistributedGPipe,
    LocalTransport,
)
from torchgpipe_tpu.distributed.context import PeerDiedError
from torchgpipe_tpu.layers import chain, named
from torchgpipe_tpu.ops import dense, gelu
from torchgpipe_tpu.precision import DynamicLossScale
from torchgpipe_tpu.resilience import (
    CheckpointManager,
    FaultyTransport,
    PreemptionHandler,
    SendFault,
    StepGuard,
    classify_error,
    faults,
)
from torchgpipe_tpu.resilience.checkpoint import latest_step_or_none
from torchgpipe_tpu.resilience.guard import GuardPolicy


def _mse(out, tgt):
    return jnp.mean((out - tgt) ** 2)


def _leaves_equal(a, b):
    la, lb = jax.tree_util.tree_leaves(a), jax.tree_util.tree_leaves(b)
    assert len(la) == len(lb)
    for x, y in zip(la, lb):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))


# --------------------------------------------------------------------- #
# CheckpointManager                                                     #
# --------------------------------------------------------------------- #


def _tree(seed, extra=0.0):
    k = jax.random.PRNGKey(seed)
    return {
        "w": jax.random.normal(k, (4, 3)) + extra,
        "nested": {"b": jnp.arange(5, dtype=jnp.float32)},
        "step": jnp.asarray(seed, jnp.int32),
    }


def test_checkpoint_roundtrip_metadata_and_gc(tmp_path):
    mgr = CheckpointManager(tmp_path / "ck", keep_last_k=2)
    assert mgr.restore_latest() is None
    for s in (1, 2, 3):
        mgr.save(s, _tree(s), metadata={"loss_scale": 2.0 ** s})
    # keep-last-k GC dropped step 1
    assert mgr.steps() == [2, 3]
    snap = mgr.restore_latest(template=_tree(0))
    assert snap.step == 3
    assert snap.metadata == {"loss_scale": 8.0}
    _leaves_equal(snap.tree, _tree(3))
    # without a template: the flat keystr dict
    flat = mgr.restore_latest().tree
    assert "['w']" in flat and "['nested']['b']" in flat


def test_checkpoint_skips_truncated_npz(tmp_path):
    mgr = CheckpointManager(tmp_path / "ck", keep_last_k=3)
    mgr.save(1, _tree(1))
    p2 = mgr.save(2, _tree(2))
    with open(os.path.join(p2, "state.npz"), "r+b") as f:
        f.truncate(64)  # torn write / disk corruption after the save
    snap = mgr.restore_latest(template=_tree(0))
    assert snap.step == 1
    _leaves_equal(snap.tree, _tree(1))


def test_checkpoint_skips_corrupt_manifest_and_checksum(tmp_path):
    mgr = CheckpointManager(tmp_path / "ck", keep_last_k=3)
    mgr.save(1, _tree(1))
    p2 = mgr.save(2, _tree(2))
    p3 = mgr.save(3, _tree(3))
    # step 3: unparseable manifest (partial write)
    with open(os.path.join(p3, "manifest.json"), "w") as f:
        f.write('{"format": 1, "step": 3, "arr')
    # step 2: checksum mismatch (bit rot) — flip the npz payload wholesale
    man = json.load(open(os.path.join(p2, "manifest.json")))
    first_key = sorted(man["arrays"])[0]
    man["arrays"][first_key]["crc32"] ^= 0xDEADBEEF
    with open(os.path.join(p2, "manifest.json"), "w") as f:
        json.dump(man, f)
    snap = mgr.restore_latest(template=_tree(0))
    assert snap.step == 1


def test_checkpoint_sharded_backend_roundtrip_and_corruption(tmp_path):
    """The orbax-sharded backend under the same manifest/GC/skip protocol
    (single-process here; multi-host writes shards per process)."""
    mgr = CheckpointManager(tmp_path / "ck", keep_last_k=3)
    mgr.save(1, _tree(1), sharded=True)
    p2 = mgr.save(2, _tree(2), sharded=True, metadata={"epoch": 7})
    snap = mgr.restore_latest(template=_tree(0))
    assert snap.step == 2 and snap.metadata == {"epoch": 7}
    _leaves_equal(snap.tree, _tree(2))
    # sharded restores need the template (structure + shardings)
    with pytest.raises(Exception, match="template"):
        mgr.restore_latest()
    # corrupt one orbax payload file -> file-level CRC mismatch -> skip
    victims = [
        os.path.join(dp, f)
        for dp, _, fs in os.walk(os.path.join(p2, "sharded"))
        for f in fs
        if os.path.getsize(os.path.join(dp, f)) > 0
    ]
    with open(sorted(victims)[0], "r+b") as f:
        b = bytearray(f.read())
        b[len(b) // 2] ^= 0xFF
        f.seek(0)
        f.write(b)
    snap = mgr.restore_latest(template=_tree(0))
    assert snap.step == 1
    _leaves_equal(snap.tree, _tree(1))


def test_resave_crash_window_falls_back_to_old(tmp_path):
    """Re-saving an existing step swaps via ``step_<n>.old``; a crash
    between the two renames leaves only the .old copy — which steps()
    must still list and restore must still load."""
    mgr = CheckpointManager(tmp_path / "ck", keep_last_k=3)
    p3 = mgr.save(3, _tree(3))
    os.rename(p3, p3 + ".old")  # the mid-swap crash state
    assert mgr.steps() == [3]
    snap = mgr.restore_latest(template=_tree(0))
    assert snap.step == 3
    _leaves_equal(snap.tree, _tree(3))
    # A completed re-save sweeps the now-redundant fallback copy.
    mgr.save(3, _tree(4))
    assert not os.path.exists(p3 + ".old")
    _leaves_equal(mgr.restore_latest(template=_tree(0)).tree, _tree(4))
    assert latest_step_or_none(tmp_path / "ck") == 3


def test_orphaned_old_snapshot_retired_past_keep_window(tmp_path):
    """An .old copy whose primary never completed (mid-swap crash, run
    moved on) survives while inside the keep-last-k window, but is
    retired once k newer complete snapshots exist — no unbounded leak."""
    mgr = CheckpointManager(tmp_path / "ck", keep_last_k=2)
    p1 = mgr.save(1, _tree(1))
    os.rename(p1, p1 + ".old")  # crash state: .old is step 1's only copy
    mgr.save(2, _tree(2))
    assert os.path.exists(p1 + ".old")  # inside the window: still a fallback
    assert mgr.restore_step(1, template=_tree(0)).step == 1
    mgr.save(3, _tree(3))  # two newer complete snapshots -> retire it
    assert not os.path.exists(p1 + ".old")
    assert mgr.steps() == [2, 3]


def test_checkpoint_missing_key_is_strict(tmp_path):
    from torchgpipe_tpu.resilience.checkpoint import CheckpointError

    mgr = CheckpointManager(tmp_path / "ck")
    mgr.save(1, {"a": jnp.ones(3)})
    with pytest.raises(CheckpointError, match="missing"):
        mgr.restore_step(1, template={"a": jnp.ones(3), "b": jnp.ones(2)})


@pytest.mark.filterwarnings(
    # The simulated mid-write crash abandons numpy's internal ZipFile; its
    # __del__ then complains about the (deliberately) closed handle.
    "ignore::pytest.PytestUnraisableExceptionWarning"
)
def test_serialization_save_is_atomic(tmp_path, monkeypatch):
    """An interrupted utils.serialization.save never truncates the
    previously-good .npz (write-to-temp + rename)."""
    from torchgpipe_tpu.utils import serialization

    path = str(tmp_path / "model.npz")
    good = {"w": np.arange(6, dtype=np.float32)}
    serialization.save(path, good)

    class Bomb:
        """Array-like that explodes mid-serialization."""

        def __array__(self, *a, **k):
            raise RuntimeError("simulated crash mid-save")

    with pytest.raises(RuntimeError, match="mid-save"):
        serialization.save(path, {"w": Bomb()})
    # The old bytes survive, and no temp litter remains.
    assert list(serialization.load(path)) == ["w"]
    np.testing.assert_array_equal(serialization.load(path)["w"], good["w"])
    assert [p for p in os.listdir(tmp_path) if ".tmp-" in p] == []


# --------------------------------------------------------------------- #
# kill-and-resume: bitwise-identical recovery on both engines           #
# --------------------------------------------------------------------- #

TOTAL_STEPS = 6
PREEMPT_AT = 3


def _data(step, din, dout):
    kx = jax.random.fold_in(jax.random.PRNGKey(100), step)
    ky = jax.random.fold_in(jax.random.PRNGKey(200), step)
    return (
        jax.random.normal(kx, (8, din)),
        jax.random.normal(ky, (8, dout)),
    )


def _gpipe_setup():
    layers = named([dense(12, name="fc1"), gelu("a1"), dense(6, name="head")])
    model = GPipe(layers, balance=[2, 1], chunks=2)
    opt = optax.adam(1e-2)
    params, state = model.init(
        jax.random.PRNGKey(0), jax.ShapeDtypeStruct((8, 12), jnp.float32)
    )
    opt_state = model.init_opt_state(opt, params)
    step_fn = model.make_train_step(opt, _mse, donate=False)

    def run_one(carry, s):
        params, opt_state, state = carry
        x, y = _data(s, 12, 6)
        _, params, opt_state, state, _ = step_fn(
            params, opt_state, state, x, y
        )
        return (params, opt_state, state)

    return (params, opt_state, state), run_one


def _spmd_setup():
    block = chain([dense(12, name="fc"), gelu("act")], name="blk")
    mesh = make_mesh(2, 2)
    pipe = SpmdGPipe(block, 2, mesh, chunks=2, loss_fn=_mse, dp_axis="dp")
    opt = optax.adam(1e-2)
    params = pipe.init(
        jax.random.PRNGKey(0), jax.ShapeDtypeStruct((8, 12), jnp.float32)
    )
    opt_state = pipe.place_tree(opt.init(params))
    step_fn = pipe.make_train_step(opt, donate=False)

    def run_one(carry, s):
        params, opt_state = carry
        x, y = _data(s, 12, 12)
        _, params, opt_state = step_fn(params, opt_state, x, y)
        return (params, opt_state)

    return (params, opt_state), run_one


def _resumable_loop(setup, tmp_path, pack, unpack):
    """Train with save-every-step + simulated preemption, then 'restart the
    process' (fresh engine, fresh compiled step) and finish from
    restore_latest(); also run uninterrupted for the oracle."""
    # Uninterrupted oracle.
    carry, run_one = setup()
    for s in range(TOTAL_STEPS):
        carry = run_one(carry, s)
    oracle = carry

    # Incarnation 1: preempted (simulated SIGTERM via the fault plan).
    mgr = CheckpointManager(tmp_path / "ck", keep_last_k=2)
    carry, run_one = setup()
    stopped_at = None
    with PreemptionHandler() as stop:
        with faults.inject(preempt_at_step=PREEMPT_AT):
            for s in range(TOTAL_STEPS):
                carry = run_one(carry, s)
                mgr.save(s, pack(carry, s))
                if stop.check(s):
                    stopped_at = s
                    break
    assert stopped_at == PREEMPT_AT
    assert stop.preempted

    # Incarnation 2: fresh engine/step (a new process would rebuild both).
    carry, run_one = setup()
    snap = mgr.restore_latest(template=pack(carry, 0))
    assert snap.step == PREEMPT_AT
    carry, start = unpack(snap)
    for s in range(start + 1, TOTAL_STEPS):
        carry = run_one(carry, s)
    return oracle, carry


@pytest.mark.slow  # fast-gate budget (VERDICT r5 #6): covered by the CI full job
def test_kill_and_resume_bitwise_gpipe(tmp_path):
    def pack(carry, s):
        params, opt_state, state = carry
        return {"params": params, "opt": opt_state,
                "step": jnp.asarray(s, jnp.int32)}

    def unpack(snap):
        _, _, state0 = _gpipe_setup()[0]
        return (
            (snap.tree["params"], snap.tree["opt"], state0),
            int(snap.tree["step"]),
        )

    oracle, resumed = _resumable_loop(_gpipe_setup, tmp_path, pack, unpack)
    _leaves_equal(oracle[0], resumed[0])  # params bitwise
    _leaves_equal(oracle[1], resumed[1])  # opt-state bitwise


def test_kill_and_resume_bitwise_spmd(tmp_path):
    def pack(carry, s):
        params, opt_state = carry
        return {"params": params, "opt": opt_state,
                "step": jnp.asarray(s, jnp.int32)}

    def unpack(snap):
        return (
            (snap.tree["params"], snap.tree["opt"]),
            int(snap.tree["step"]),
        )

    oracle, resumed = _resumable_loop(_spmd_setup, tmp_path, pack, unpack)
    _leaves_equal(oracle[0], resumed[0])
    _leaves_equal(oracle[1], resumed[1])


# --------------------------------------------------------------------- #
# StepGuard: NaN skip + loss-scale backoff, transient retry             #
# --------------------------------------------------------------------- #


def test_nan_step_skipped_and_loss_scale_backs_off():
    layers = named([dense(12, name="fc1"), gelu("a1"), dense(6, name="head")])
    model = GPipe(layers, balance=[2, 1], chunks=2)
    opt = optax.adam(1e-2)
    params, state = model.init(
        jax.random.PRNGKey(0), jax.ShapeDtypeStruct((8, 12), jnp.float32)
    )
    opt_state = model.init_opt_state(opt, params)
    step_fn = model.make_train_step(opt, _mse, donate=False)
    # extra_state_argnums: input position 2 (the threaded model state)
    # replaces outputs[3] on a skipped step, so a stateful model never
    # threads statistics computed from the poisoned batch.
    guard = StepGuard(
        step_fn,
        loss_scale=DynamicLossScale(scale=1024.0),
        extra_state_argnums=(2,),
    )
    x, y = _data(0, 12, 6)

    loss, p1, o1, state1, _ = guard(params, opt_state, state, x, y)
    assert np.isfinite(float(loss))
    assert guard.stats.steps == 1

    with faults.inject(nan_at=(1, 0)):
        loss, p2, o2, state2, _ = guard(p1, o1, state1, x, y)
    assert not np.isfinite(float(loss))
    assert guard.stats.skipped == 1
    assert guard.loss_scale.scale == 512.0  # backoff_factor=0.5
    _leaves_equal(p1, p2)  # skip-step: params unchanged
    _leaves_equal(o1, o2)  # ... and optimizer state unchanged
    assert state2 is state1  # ... and threaded state restored, not poisoned
    state = state2

    # Clean step afterwards: the good-step counter restarts growth.
    loss, p3, _, state, _ = guard(p2, o2, state, x, y)
    assert np.isfinite(float(loss))
    assert guard.stats.steps == 2
    assert guard.loss_scale.good_steps == 1


def test_loss_scale_wiring_scales_and_unscales_exactly():
    """The scaling half of the protocol is the caller's wiring
    (precision.DynamicLossScale docstring): scale the loss fed to
    value_and_grad, unscale the returned grads — recovering the
    unscaled gradients exactly (power-of-two scale, float32 math)."""
    from torchgpipe_tpu.precision import DynamicLossScale as LS

    layers = named([dense(12, name="fc1"), dense(6, name="head")])
    model = GPipe(layers, balance=[1, 1], chunks=2)
    params, state = model.init(
        jax.random.PRNGKey(0), jax.ShapeDtypeStruct((8, 12), jnp.float32)
    )
    x, y = _data(0, 12, 6)
    _, grads_ref, _, _ = model.value_and_grad(params, state, x, y, _mse)

    ls = LS(scale=2.0 ** 6)
    scaled_loss = lambda o, t: ls.scale_loss(_mse(o, t))
    loss_s, grads_s, _, _ = model.value_and_grad(
        params, state, x, y, scaled_loss
    )
    assert float(loss_s) == pytest.approx(
        (2.0 ** 6) * float(jnp.mean((model.apply(params, state, x)[0] - y) ** 2)),
        rel=1e-6,
    )
    _leaves_equal(ls.unscale(grads_s), grads_ref)


def test_spmd_nan_injection_poisons_only_while_active():
    (params, opt_state), _ = _spmd_setup()
    block = chain([dense(12, name="fc"), gelu("act")], name="blk")
    mesh = make_mesh(2, 2)
    pipe = SpmdGPipe(block, 2, mesh, chunks=2, loss_fn=_mse, dp_axis="dp")
    params = pipe.init(
        jax.random.PRNGKey(0), jax.ShapeDtypeStruct((8, 12), jnp.float32)
    )
    x, y = _data(0, 12, 12)
    clean, _ = pipe.train_step(params, x, y)
    with faults.inject(nan_at=(1, 1)):
        bad, _ = pipe.train_step(params, x, y)
    again, _ = pipe.train_step(params, x, y)
    assert np.isfinite(float(clean))
    assert not np.isfinite(float(bad))
    # Program cache keyed on the plan token: the poisoned trace is gone.
    np.testing.assert_array_equal(np.asarray(clean), np.asarray(again))


def test_inert_plan_does_not_invalidate_program_cache():
    """A preempt-only plan never reaches a traced program: it must not
    token the program caches (each miss is a full pipeline recompile),
    while an expired nan plan's poisoned program must be evicted."""
    block = chain([dense(12, name="fc"), gelu("act")], name="blk")
    mesh = make_mesh(2, 2)
    pipe = SpmdGPipe(block, 2, mesh, chunks=2, loss_fn=_mse, dp_axis="dp")
    params = pipe.init(
        jax.random.PRNGKey(0), jax.ShapeDtypeStruct((8, 12), jnp.float32)
    )
    x, y = _data(0, 12, 12)
    pipe.train_step(params, x, y)
    assert len(pipe._train_step_fns) == 1
    with faults.inject(preempt_at_step=5):
        pipe.train_step(params, x, y)
    assert len(pipe._train_step_fns) == 1  # inert plan: same program
    with faults.inject(nan_at=(0, 0)):
        pipe.train_step(params, x, y)
        assert len(pipe._train_step_fns) == 2
    pipe.train_step(params, x, y)
    assert len(pipe._train_step_fns) == 1  # poisoned program evicted


def test_spmd_nan_injection_rejected_off_fill_drain():
    block = chain([dense(12, name="fc"), gelu("act")], name="blk")
    mesh = make_mesh(2, 2)
    pipe = SpmdGPipe(
        block, 2, mesh, chunks=2, loss_fn=_mse, dp_axis="dp",
        schedule="1f1b", loss_reduction="mean",
    )
    params = pipe.init(
        jax.random.PRNGKey(0), jax.ShapeDtypeStruct((8, 12), jnp.float32)
    )
    x, y = _data(0, 12, 12)
    with faults.inject(nan_at=(0, 0)):
        with pytest.raises(NotImplementedError, match="fill_drain"):
            pipe.train_step(params, x, y)


def test_classify_error():
    assert classify_error(ConnectionError("x")) == "transient"
    assert classify_error(ConnectionRefusedError("x")) == "transient"
    assert classify_error(TimeoutError("x")) == "transient"
    assert classify_error(ValueError("x")) == "fatal"
    assert classify_error(PeerDiedError(2, "w2")) == "fatal"
    from jax.errors import JaxRuntimeError as XlaRuntimeError

    assert classify_error(
        XlaRuntimeError("RESOURCE_EXHAUSTED: out of memory")
    ) == "transient"
    assert classify_error(
        XlaRuntimeError("DATA_LOSS: torn transfer")
    ) == "transient"
    assert classify_error(
        XlaRuntimeError("INVALID_ARGUMENT: shape mismatch")
    ) == "fatal"


def test_guard_retries_transient_then_succeeds():
    calls = {"n": 0}
    sleeps = []

    def flaky_step(params, opt_state, x):
        calls["n"] += 1
        if calls["n"] <= 2:
            raise ConnectionError("flaky fabric")
        return (jnp.asarray(0.5), params, opt_state)

    guard = StepGuard(
        flaky_step,
        policy=GuardPolicy(max_retries=3, backoff_base=0.01),
        sleep=sleeps.append,
    )
    loss, p, o = guard({"w": jnp.ones(2)}, {"m": jnp.zeros(2)}, None)
    assert float(loss) == 0.5
    assert guard.stats.retries == 2
    assert sleeps == [0.01, 0.02]  # bounded exponential backoff


def test_guard_reraises_model_bugs_immediately():
    def buggy_step(params, opt_state):
        raise ValueError("a real bug")

    guard = StepGuard(buggy_step, sleep=lambda s: None)
    with pytest.raises(ValueError, match="a real bug"):
        guard(None, None)
    assert guard.stats.retries == 0


def test_guard_gives_up_after_max_retries():
    def always_down(params, opt_state):
        raise ConnectionError("still down")

    guard = StepGuard(
        always_down,
        policy=GuardPolicy(max_retries=2, backoff_base=0.0),
        sleep=lambda s: None,
    )
    with pytest.raises(ConnectionError, match="still down"):
        guard(None, None)
    assert guard.stats.retries == 2


# --------------------------------------------------------------------- #
# transport faults + peer death (MPMD distributed mode)                 #
# --------------------------------------------------------------------- #

WORKERS = ["w0", "w1"]


def _make_distributed_ranks(transport, recv_timeout=None):
    layers = [dense(8, name="fc1"), dense(4, name="fc2")]
    ranks = []
    for r in range(2):
        box = transport.register(WORKERS[r])
        ranks.append(
            DistributedGPipe(
                layers, r, WORKERS, [1, 1], chunks=2,
                transport=transport, mailbox=box,
                recv_timeout=recv_timeout,
            )
        )
    rng = jax.random.PRNGKey(0)
    in_spec = jax.ShapeDtypeStruct((4, 8), jnp.float32)
    for rank in ranks:
        rank._params, rank._state = rank.init(rng, in_spec)
    return ranks


def _distributed_step(ranks, x, y):
    outs = None
    for r, rank in enumerate(ranks):
        res = rank.forward(
            rank._params, rank._state, x if r == 0 else None,
            rng=jax.random.PRNGKey(1),
        )
        if rank.is_last:
            outs = res
    loss, gys, _ = ranks[-1].loss_grads(outs, y, _mse)
    for rank in reversed(ranks):
        rank.backward(gys if rank.is_last else None)
    return loss


def test_transport_drop_is_transient_and_guard_retries():
    inner = LocalTransport()
    transport = FaultyTransport(
        inner, [SendFault("drop", dst="w1", kind="forward", times=1)]
    )
    ranks = _make_distributed_ranks(transport)
    x = jax.random.normal(jax.random.PRNGKey(2), (4, 8))
    y = jax.random.normal(jax.random.PRNGKey(3), (4, 4))

    def step(params, opt_state):
        loss = _distributed_step(ranks, x, y)
        return (loss, params, opt_state)

    guard = StepGuard(
        step, policy=GuardPolicy(backoff_base=0.0), sleep=lambda s: None
    )
    loss, _, _ = guard(None, None)
    assert np.isfinite(float(loss))
    assert guard.stats.retries == 1
    assert transport.log == [("drop", "w1", "forward", 0)]


def test_faulty_transport_lose_delay_duplicate():
    inner = LocalTransport()
    box = inner.register("dst")
    t = FaultyTransport(inner)
    t.add(SendFault("lose", kind="a", times=1))
    t.add(SendFault("duplicate", kind="b", times=1))
    t.add(SendFault("delay", kind="c", times=1, delay_s=0.0))
    t.send("dst", "a", 0, "gone")       # lost
    t.send("dst", "a", 1, "arrives")    # rule exhausted
    t.send("dst", "b", 0, "twice")
    t.send("dst", "c", 0, "late")
    assert box.get("a", 1, timeout=1) == "arrives"
    assert box.get("b", 0, timeout=1) == "twice"
    assert box.get("b", 0, timeout=1) == "twice"
    assert box.get("c", 0, timeout=1) == "late"
    with pytest.raises(TimeoutError):
        box.get("a", 0, timeout=0.05)


def test_peer_died_error_names_the_rank():
    transport = LocalTransport()
    ranks = _make_distributed_ranks(transport, recv_timeout=0.2)
    # Rank 0 dies: its worker unregisters (the `worker` contextmanager's
    # finally path); rank 1 then waits on a channel no one will fill.
    transport.unregister("w0")
    with pytest.raises(PeerDiedError, match=r"rank 0 \('w0'\)") as excinfo:
        ranks[1].forward(ranks[1]._params, ranks[1]._state, None)
    assert excinfo.value.rank == 0
    assert excinfo.value.worker == "w0"
    # Fatal for the guard: restart-and-restore, not retry.
    assert classify_error(excinfo.value) == "fatal"


def test_slow_peer_still_times_out_as_timeout():
    transport = LocalTransport()
    ranks = _make_distributed_ranks(transport, recv_timeout=0.1)
    # Both ranks alive; rank 1 simply never receives (rank 0 not driven).
    with pytest.raises(TimeoutError) as excinfo:
        ranks[1].forward(ranks[1]._params, ranks[1]._state, None)
    assert not isinstance(excinfo.value, PeerDiedError)


# --------------------------------------------------------------------- #
# preemption                                                            #
# --------------------------------------------------------------------- #


def test_preemption_handler_latches_sigterm():
    with PreemptionHandler(signals=(signal.SIGTERM,)) as h:
        assert not h.preempted
        os.kill(os.getpid(), signal.SIGTERM)
        assert h.preempted
        assert h.signum == signal.SIGTERM
    # Handlers restored on exit.
    assert signal.getsignal(signal.SIGTERM) is signal.SIG_DFL


def test_preemption_callbacks_fire_once_even_late():
    """Drain hooks fire exactly once each — including hooks registered
    AFTER preemption latched (the serving engine may be built mid-grace-
    window), and a failing hook never blocks the others."""
    h = PreemptionHandler()
    early, late = [], []
    h.add_callback(lambda: early.append(1))
    h.add_callback(lambda: (_ for _ in ()).throw(RuntimeError("boom")))
    h.simulate()
    assert early == [1]
    h.simulate()                      # re-latch: no double delivery
    assert early == [1]
    h.add_callback(lambda: late.append(1))   # registered after the latch
    assert late == [1]


def test_preemption_callbacks_do_not_pin_bound_engines():
    """A bound-method hook is held weakly: discarding the object that
    registered it (a dead serving engine and its KV pool) leaves it
    collectable, and the latch skips the dead hook."""
    import gc
    import weakref

    calls = []

    class Owner:
        def hook(self):
            calls.append(id(self))

    h = PreemptionHandler()
    dead, kept = Owner(), Owner()
    h.add_callback(dead.hook)
    h.add_callback(kept.hook)
    wr = weakref.ref(dead)
    del dead
    gc.collect()
    assert wr() is None               # the handler does not pin it
    h.simulate()
    assert calls == [id(kept)]        # dead hook skipped, live one fired


def test_preemption_callbacks_accept_c_bound_methods():
    """Bound methods WeakMethod cannot hold (C-implemented methods like
    Lock.release) fall back to a strong reference instead of raising at
    registration."""
    import threading

    h = PreemptionHandler()
    lock = threading.Lock()
    lock.acquire()
    h.add_callback(lock.release)      # builtin bound method
    h.simulate()
    assert not lock.locked()          # it fired


def test_preemption_check_honors_fault_plan():
    with PreemptionHandler() as h:
        with faults.inject(preempt_at_step=2):
            assert [s for s in range(4) if h.check(s)] == [2, 3]
    with PreemptionHandler() as h:
        assert not h.check(0)


def test_fault_plans_do_not_nest():
    with faults.inject(nan_at=(0, 0)):
        with pytest.raises(RuntimeError, match="do not nest"):
            with faults.inject(preempt_at_step=1):
                pass
    assert faults.active_plan() is None
