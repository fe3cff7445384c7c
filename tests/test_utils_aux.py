"""Aux subsystem tests: timeline tracing and model persistence
(SURVEY.md §5 parity: tracing/profiling and checkpoint/resume)."""

import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from torchgpipe_tpu import GPipe
from torchgpipe_tpu.ops import batch_norm, dense, relu
from torchgpipe_tpu.utils.serialization import (
    load,
    load_state_dict,
    save,
    state_dict,
)
from torchgpipe_tpu.utils.tracing import Timeline, simulate_pipeline


def _layers():
    return [
        dense(8, name="d0"), batch_norm(name="bn0"), relu("r0"),
        dense(4, name="d1"),
    ]


def _mse(out, tgt):
    return jnp.mean((out - tgt) ** 2)


def test_timeline_records_all_cells():
    tracer = Timeline()
    model = GPipe(_layers(), balance=[2, 2], chunks=3, tracer=tracer)
    in_spec = jax.ShapeDtypeStruct((6, 8), jnp.float32)
    params, state = model.init(jax.random.PRNGKey(0), in_spec)
    x = jax.random.normal(jax.random.PRNGKey(1), (6, 8))
    y = jax.random.normal(jax.random.PRNGKey(2), (6, 4))
    model.value_and_grad(params, state, x, y, _mse)
    fwd = [e for e in tracer.events if e.name == "fwd"]
    bwd = [e for e in tracer.events if e.name == "bwd"]
    # m*n cells each direction.
    assert len(fwd) == 3 * 2 and len(bwd) == 3 * 2
    assert {(e.stage, e.mbatch) for e in fwd} == {
        (j, i) for j in range(2) for i in range(3)
    }
    assert "stage 0" in tracer.summary()

    tracer.reset()
    model.apply(params, state, x)
    assert all(e.name == "fwd" for e in tracer.events)
    assert len(tracer.events) == 6


def test_timeline_sync_ablation_and_schedule_simulation():
    tracer = Timeline(sync=True)
    model = GPipe(_layers(), balance=[2, 2], chunks=4, tracer=tracer)
    in_spec = jax.ShapeDtypeStruct((8, 8), jnp.float32)
    params, state = model.init(jax.random.PRNGKey(0), in_spec)
    x = jax.random.normal(jax.random.PRNGKey(1), (8, 8))
    model.apply(params, state, x)
    res = simulate_pipeline(tracer.events, n_stages=2)
    assert res is not None
    makespan, busy, bubble = res
    assert makespan > 0
    assert 0.0 < busy <= 1.0 and abs(busy + bubble - 1.0) < 1e-9
    # Uniform-cell sanity: projected makespan never exceeds the serialized
    # sum, never undercuts the critical path (longest stage's total).
    total = sum(ev.duration for ev in tracer.events)
    assert makespan <= total + 1e-9
    per_stage = {}
    for ev in tracer.events:
        per_stage[ev.stage] = per_stage.get(ev.stage, 0.0) + ev.duration
    assert makespan >= max(per_stage.values()) - 1e-9


def test_simulate_pipeline_analytic_uniform_cells():
    # Hand-built uniform timeline: bubble must equal (n-1)/(m+n-1) exactly.
    from torchgpipe_tpu.utils.tracing import TimelineEvent

    m, n, t = 4, 2, 0.01
    events = [
        TimelineEvent("fwd", j, i, 0.0, t) for i in range(m) for j in range(n)
    ]
    makespan, busy, bubble = simulate_pipeline(events, n)
    assert abs(makespan - (m + n - 1) * t) < 1e-12
    assert abs(bubble - (n - 1) / (m + n - 1)) < 1e-9


def test_state_dict_roundtrip(tmp_path):
    model = GPipe(_layers(), balance=[2, 2], chunks=2)
    in_spec = jax.ShapeDtypeStruct((4, 8), jnp.float32)
    params, state = model.init(jax.random.PRNGKey(0), in_spec)

    d = state_dict(model, params, state)
    # Method spelling delegates to the same function (reference API shape).
    d2 = model.state_dict(params, state)
    assert sorted(d) == sorted(d2)
    # Reference-style keys: partitions.<stage>.<layer_name>...
    assert any(k.startswith("partitions.0.d0.params") for k in d)
    assert any(k.startswith("partitions.1.d1.params") for k in d)
    assert any(k.startswith("partitions.0.bn0.state") for k in d)

    path = os.path.join(tmp_path, "ckpt.npz")
    save(path, d)
    loaded = load(path)
    assert set(loaded) == set(d)

    # Fresh model instance (same topology), different init -> load restores.
    model2 = GPipe(_layers(), balance=[2, 2], chunks=2)
    params2, state2 = model2.init(jax.random.PRNGKey(99), in_spec)
    params3, state3 = model2.load_state_dict(params2, state2, loaded)
    x = jax.random.normal(jax.random.PRNGKey(1), (4, 8))
    out_orig, _ = model.apply(params, state, x)
    out_loaded, _ = model2.apply(params3, state3, x)
    np.testing.assert_allclose(np.asarray(out_orig), np.asarray(out_loaded), rtol=1e-6)


def test_load_state_dict_strictness():
    import pytest

    model = GPipe(_layers(), balance=[2, 2], chunks=2)
    in_spec = jax.ShapeDtypeStruct((4, 8), jnp.float32)
    params, state = model.init(jax.random.PRNGKey(0), in_spec)
    d = state_dict(model, params, state)

    missing = dict(d)
    missing.pop(sorted(missing)[0])
    with pytest.raises(KeyError, match="missing"):
        load_state_dict(model, params, state, missing)

    extra = dict(d)
    extra["partitions.9.zzz.params.w"] = np.zeros((1,))
    with pytest.raises(KeyError, match="unexpected"):
        load_state_dict(model, params, state, extra)

    bad = dict(d)
    k = next(iter(bad))
    bad[k] = np.zeros((1, 1, 1))
    with pytest.raises(ValueError, match="shape mismatch"):
        load_state_dict(model, params, state, bad)


def test_simulate_pipeline_multistep_averaging():
    # Repeated observations of the same cell (multi-step timeline) must
    # average into one representative step — busy stays <= 1.
    from torchgpipe_tpu.utils.tracing import TimelineEvent

    m, n, t = 4, 2, 0.01
    events = [
        TimelineEvent("fwd", j, i, 0.0, t)
        for _ in range(3)  # three identical steps
        for i in range(m)
        for j in range(n)
    ]
    makespan, busy, bubble = simulate_pipeline(events, n)
    assert abs(makespan - (m + n - 1) * t) < 1e-12
    assert 0.0 < busy <= 1.0
    assert abs(bubble - (n - 1) / (m + n - 1)) < 1e-9


@pytest.mark.slow
def test_sharded_checkpoint_roundtrip(cpu_devices, tmp_path):
    """SPMD training state (sharded params + optax state) survives an orbax
    save/restore with shardings intact — the resume story for the compiled
    engine."""
    import optax

    from torchgpipe_tpu.models.transformer import (
        TransformerConfig, cross_entropy, llama_spmd,
    )
    from torchgpipe_tpu.spmd import SpmdGPipe, make_mesh
    from torchgpipe_tpu.utils.serialization import (
        restore_sharded, save_sharded,
    )

    pp = 2
    cfg = TransformerConfig(
        vocab=32, dim=16, n_layers=pp, n_heads=2, n_kv_heads=2, tp_axis="tp"
    )
    block, pre, post = llama_spmd(cfg, pp)
    mesh = make_mesh(pp, 1, tp=2, devices=cpu_devices[:4])
    pipe = SpmdGPipe(
        block, pp, mesh, chunks=2, loss_fn=cross_entropy,
        pre=pre, post=post, tp_axis="tp",
    )
    tokens = jax.random.randint(jax.random.PRNGKey(1), (4, 8), 0, cfg.vocab)
    params = pipe.init(
        jax.random.PRNGKey(0), jax.ShapeDtypeStruct(tokens.shape, tokens.dtype)
    )
    opt = optax.adam(1e-3)
    opt_state = pipe.place_tree(opt.init(params))
    loss0, grads = pipe.train_step(params, tokens, tokens)
    updates, opt_state = opt.update(grads, opt_state)
    params = optax.apply_updates(params, updates)

    ckpt = {"params": params, "opt_state": opt_state, "step": jnp.asarray(1)}
    save_sharded(str(tmp_path / "ckpt"), ckpt)
    restored = restore_sharded(str(tmp_path / "ckpt"), ckpt)

    jax.tree_util.tree_map(
        lambda a, b: np.testing.assert_array_equal(np.asarray(a), np.asarray(b)),
        ckpt,
        restored,
    )
    # Shardings preserved (tp-sharded weight keeps its spec)...
    wq = params["blocks"][0]["wq"]
    assert restored["params"]["blocks"][0]["wq"].sharding == wq.sharding
    # ...and training continues from the restored state.
    loss1, _ = pipe.train_step(restored["params"], tokens, tokens)
    assert float(loss1) < float(loss0) + 1e-3


def test_interleaved_virtual_stages():
    """More stages than devices wrap around (stage j -> device j % n): an
    interleaved 'virtual stage' pipeline — transparency must hold with the
    schedule looping placement."""
    from torchgpipe_tpu.layers import sequential_apply
    from torchgpipe_tpu.ops import gelu

    layers = [
        dense(8, name="d0"), gelu("g0"), dense(8, name="d1"), gelu("g1"),
        dense(8, name="d2"), gelu("g2"), dense(4, name="d3"),
    ]
    devices = jax.devices()[:2]
    # 4 virtual stages on 2 devices: placement d0,d1,d0,d1.
    model = GPipe(layers, balance=[2, 2, 2, 1], devices=devices, chunks=2)
    assert [d.id for d in model.devices] == [0, 1, 0, 1]
    in_spec = jax.ShapeDtypeStruct((4, 8), jnp.float32)
    params, state = model.init(jax.random.PRNGKey(0), in_spec)
    x = jax.random.normal(jax.random.PRNGKey(1), (4, 8))
    out, _ = model.apply(params, state, x)

    dev0 = jax.devices()[0]
    flat_p = jax.device_put([l for st in params for l in st], dev0)
    flat_s = jax.device_put([l for st in state for l in st], dev0)
    ref, _ = sequential_apply(
        layers, flat_p, flat_s, jax.device_put(x, dev0), train=False
    )
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=1e-5)


def test_prefetch_to_device_order_and_placement():
    """prefetch_to_device yields every batch, in order, already committed
    to the requested device, advancing the source at most `size` ahead."""
    from torchgpipe_tpu.utils.data import prefetch_to_device

    pulled = []

    def source():
        for i in range(6):
            pulled.append(i)
            yield {"x": jnp.full((2,), i), "y": jnp.full((1,), -i)}

    dev = jax.devices()[-1]
    out = []
    it = prefetch_to_device(source(), size=2, device=dev)
    first = next(it)
    # After one yield the pipeline holds at most size items beyond it.
    assert len(pulled) <= 3, pulled
    out.append(first)
    out.extend(it)
    assert len(out) == 6
    for i, batch in enumerate(out):
        assert int(batch["x"][0]) == i
        assert batch["x"].devices() == {dev}

    with pytest.raises(ValueError):
        list(prefetch_to_device(source(), size=0))


def test_prefetch_to_pipe_spmd_sharding_and_gpipe_device(cpu_devices):
    """pipe_data_sharding resolves SPMD batches to the mesh's data
    sharding (megastep's stacked form keeps the K axis whole) and GPipe
    batches to stage 0's device; prefetch_to_pipe commits (x, y) tuples
    to that placement before the consumer asks."""
    from jax.sharding import NamedSharding
    from torchgpipe_tpu import SpmdGPipe, make_mesh
    from torchgpipe_tpu.layers import chain, named
    from torchgpipe_tpu.utils.data import (
        pipe_data_sharding,
        prefetch_to_pipe,
    )

    block = chain([dense(8, name="fc")], name="blk")
    mesh = make_mesh(2, 2, devices=cpu_devices[:4])
    pipe = SpmdGPipe(block, 2, mesh, chunks=2,
                     loss_fn=lambda o, t: jnp.mean((o - t) ** 2),
                     dp_axis="dp")
    sh = pipe_data_sharding(pipe)
    assert isinstance(sh, NamedSharding) and sh.spec == (("dp",),)
    assert pipe_data_sharding(pipe, stacked=True).spec == (None, ("dp",))

    def source():
        for i in range(3):
            yield (jnp.full((4, 8), i), jnp.full((4, 8), -i))

    got = list(prefetch_to_pipe(source(), pipe, size=2))
    assert len(got) == 3
    for i, (x, y) in enumerate(got):
        assert int(x[0, 0]) == i and int(y[0, 0]) == -i
        assert x.sharding == sh  # committed, not pending

    model = GPipe(named([dense(8, name="fc1"), dense(4, name="fc2")]),
                  balance=[1, 1], chunks=2)
    assert pipe_data_sharding(model) is model.devices[0]


def test_prefetch_feeds_train_steps_without_retrace(cpu_devices):
    """The ordering/compile-count contract of the wired input pipeline:
    K steps over prefetched batches trace the SPMD train program ONCE
    (no per-batch retrace — shapes are stable and placement happens in
    the prefetcher), and the iterator runs ahead of consumption (batch
    k+1 already committed while step k is consumed) — so no step waits
    on a host→device copy it could have overlapped."""
    from torchgpipe_tpu import SpmdGPipe, make_mesh
    from torchgpipe_tpu.layers import chain
    from torchgpipe_tpu.utils.data import prefetch_to_pipe

    block = chain([dense(12, name="fc")], name="blk")
    mesh = make_mesh(2, devices=cpu_devices[:2])
    pipe = SpmdGPipe(block, 2, mesh, chunks=2,
                     loss_fn=lambda o, t: jnp.mean((o - t) ** 2))
    params = pipe.init(
        jax.random.PRNGKey(0), jax.ShapeDtypeStruct((8, 12), jnp.float32)
    )
    pulled = []

    def source():
        for i in range(4):
            pulled.append(i)
            yield (jax.random.normal(jax.random.PRNGKey(i), (8, 12)),
                   jax.random.normal(jax.random.PRNGKey(100 + i), (8, 12)))

    consumed = 0
    for x, y in prefetch_to_pipe(source(), pipe, size=2):
        # Run-ahead ordering: while consuming batch k, the source has
        # already produced (at least) batch k+1.
        assert len(pulled) >= min(consumed + 2, 4)
        pipe.train_step(params, x, y)
        consumed += 1
    assert consumed == 4
    # ONE compiled program for all prefetched batches: the cache keyed
    # on (rng?, ragged?, fault-token) holds exactly one entry.
    assert len(pipe._train_step_fns) == 1


def test_save_sharded_swap_is_process0_gated(tmp_path, monkeypatch):
    """Multi-host overwrite protocol (unit test with a fake checkpointer):
    every rank calls save between global barriers, but ONLY process 0
    performs the tmp->final directory swap — a non-zero rank must neither
    delete nor rename anything, and the branch must not depend on a
    per-host filesystem probe."""
    import torchgpipe_tpu.utils.serialization as ser

    events = []

    class _FakeCkptr:
        def __enter__(self):
            return self

        def __exit__(self, *a):
            return False

        def save(self, path, tree):
            events.append(("save", path))

        def wait_until_finished(self):
            events.append(("wait",))

    class _FakeMH:
        @staticmethod
        def sync_global_devices(tag):
            events.append(("barrier", tag))

    import jax.experimental as jexp

    ocp = pytest.importorskip("orbax.checkpoint")

    monkeypatch.setattr(ocp, "StandardCheckpointer", lambda: _FakeCkptr())
    monkeypatch.setattr(jexp, "multihost_utils", _FakeMH, raising=False)
    monkeypatch.setattr(ser.jax, "process_count", lambda: 2)
    monkeypatch.setattr(
        ser.os, "rename", lambda *a: events.append(("rename", a))
    )

    path = str(tmp_path / "ckpt")

    # Rank 1: saves + barriers, zero filesystem surgery.
    monkeypatch.setattr(ser.jax, "process_index", lambda: 1)
    events.clear()
    ser.save_sharded(path, {"w": jnp.arange(4.0)})
    kinds = [e[0] for e in events]
    assert "save" in kinds and kinds.count("barrier") == 3, events
    assert "rename" not in kinds, events

    # Rank 0: the swap happens, after the post-save barrier.
    monkeypatch.setattr(ser.jax, "process_index", lambda: 0)
    events.clear()
    ser.save_sharded(path, {"w": jnp.arange(4.0)})
    kinds = [e[0] for e in events]
    assert "rename" in kinds, events
    # The swap must come strictly AFTER the post-save barrier (every host's
    # shards durable) — not merely after this rank's own wait.
    post_save_barrier = events.index(("barrier", "save_sharded:post-save"))
    assert kinds.index("rename") > post_save_barrier, events


def test_timeline_chrome_trace_export(tmp_path):
    """to_chrome_trace writes a valid trace-event JSON: one thread-name
    metadata row per stage and one complete-event slice per recorded cell,
    with microsecond timestamps."""
    import json

    tracer = Timeline()
    model = GPipe(_layers(), balance=[2, 2], chunks=2, tracer=tracer,
                  fused=False)
    in_spec = jax.ShapeDtypeStruct((4, 8), jnp.float32)
    params, state = model.init(jax.random.PRNGKey(0), in_spec)
    x = jax.random.normal(jax.random.PRNGKey(1), (4, 8))
    y = jax.random.normal(jax.random.PRNGKey(2), (4, 4))
    model.value_and_grad(params, state, x, y, _mse)

    path = os.path.join(str(tmp_path), "trace.json")
    tracer.to_chrome_trace(path)
    with open(path) as f:
        doc = json.load(f)
    events = doc["traceEvents"]
    meta = [e for e in events if e["ph"] == "M"]
    slices = [e for e in events if e["ph"] == "X"]
    assert {m["args"]["name"] for m in meta} == {"stage 0", "stage 1"}
    # 2 chunks x 2 stages, fwd + bwd — plus the gathered-loss barrier's
    # own span on the last stage (mb -1; see obs.reconcile, which needs
    # the loss kept out of the first backward cell's measured time).
    assert len(slices) == 2 * 2 * 2 + 1, slices
    cells = [s for s in slices if s["args"]["kind"] != "loss"]
    assert len(cells) == 2 * 2 * 2
    (loss_slice,) = [s for s in slices if s["args"]["kind"] == "loss"]
    assert loss_slice["args"]["stage"] == 1
    assert loss_slice["args"]["micro_batch"] == -1
    assert all(s["ts"] >= 0 for s in slices)
    # Durations must faithfully reflect the recorded events (the 0.01us
    # render floor only applies to genuinely sub-resolution intervals).
    want = {
        (e.name, e.stage, e.mbatch): max(e.duration * 1e6, 0.01)
        for e in tracer.events
    }
    for s in slices:
        a = s["args"]
        key = (a["kind"], a["stage"], a["micro_batch"])
        assert abs(s["dur"] - want[key]) < 1e-6, (s, want[key])
    kinds = {s["args"]["kind"] for s in slices}
    assert kinds == {"fwd", "bwd", "loss"}


def test_global_batch_from_local_single_process(cpu_devices):
    """Single-process (all devices addressable): degrades to device_put
    with the requested sharding — same API everywhere."""
    import numpy as np
    from jax.sharding import Mesh, PartitionSpec as P

    from torchgpipe_tpu.utils.data import global_batch_from_local

    mesh = Mesh(np.array(cpu_devices[:4]).reshape(4), ("dp",))
    batch = {"x": np.arange(8, dtype=np.float32).reshape(8, 1)}
    out = global_batch_from_local(mesh, P("dp"), batch)
    assert out["x"].shape == (8, 1)
    np.testing.assert_array_equal(
        np.asarray(out["x"]), batch["x"]
    )
    assert out["x"].sharding.spec == P("dp")


def test_simulate_pipeline_1f1b_uniform_cells():
    """Uniform cells: the 1F1B projection must reproduce the closed-form
    makespan (2m + 2(n-1)) * t — the same tick count the SPMD 1F1B
    schedule realizes — and beat neither phase-barriered fill-drain nor
    the per-device work floor 2m*t."""
    from torchgpipe_tpu.utils.tracing import TimelineEvent

    n, m, t = 4, 8, 1.0
    events = []
    for j in range(n):
        for i in range(m):
            # TimelineEvent(name, stage, mbatch, t_start, t_end)
            events.append(TimelineEvent("fwd", j, i, 0.0, t))
            events.append(TimelineEvent("bwd", j, i, 0.0, t))
    makespan, busy, bubble = simulate_pipeline(events, n, schedule="1f1b")
    assert abs(makespan - (2 * m + 2 * (n - 1)) * t) < 1e-9, makespan
    fd_makespan, _, _ = simulate_pipeline(events, n)
    assert makespan <= fd_makespan
    assert makespan >= 2 * m * t
    assert 0.0 < busy <= 1.0 and abs(busy + bubble - 1.0) < 1e-9


def test_recommend_schedule_ranks_uniform_cells():
    """Uniform cells: same-device rows come first sorted by makespan with
    1f1b/zb beating the phase-barriered fill-drain; interleaved rows are
    ranked apart and labeled with their reduced device count."""
    from torchgpipe_tpu.utils.tracing import TimelineEvent, recommend_schedule

    n, m, t = 4, 8, 1.0
    events = []
    for j in range(n):
        for i in range(m):
            events.append(TimelineEvent("fwd", j, i, 0.0, t))
            events.append(TimelineEvent("bwd", j, i, 0.0, t))
    rows = recommend_schedule(events, n, virtual_stages=(2, 3))
    same = [r for r in rows if r.devices == n]
    assert [r.schedule for r in same[:1]][0] in ("1f1b", "zb")
    assert {r.schedule for r in same} == {"fill_drain", "1f1b", "zb"}
    # Ranked: monotone makespans within the same-device block, and the
    # block precedes every interleaved row.
    assert all(
        a.makespan <= b.makespan for a, b in zip(same, same[1:])
    )
    fd = next(r for r in same if r.schedule == "fill_drain")
    assert same[0].makespan <= fd.makespan
    inter = [r for r in rows if r.schedule == "interleaved"]
    # v=3 does not divide n=4 — only the v=2 projection appears.
    assert [r.virtual_stages for r in inter] == [2]
    assert inter[0].devices == n // 2
    assert rows.index(inter[0]) > rows.index(same[-1])
    assert "devices" in inter[0].note
    for r in rows:
        assert 0.0 < r.busy <= 1.0 and abs(r.busy + r.bubble - 1.0) < 1e-9


def test_recommend_schedule_forward_only_timeline():
    """Without bwd events the 1f1b/zb/interleaved projections are
    undefined and must be omitted rather than ranked at a fake
    zero-backward makespan.  n=4 so the v=2 interleaved config would
    otherwise be applicable — the omission is the phase check, not a
    divisibility accident."""
    from torchgpipe_tpu.utils.tracing import TimelineEvent, recommend_schedule

    n, m = 4, 8
    events = [
        TimelineEvent("fwd", j, i, 0.0, 0.5)
        for j in range(n)
        for i in range(m)
    ]
    rows = recommend_schedule(events, n, virtual_stages=(2,))
    assert [r.schedule for r in rows] == ["fill_drain"]


def test_recommend_schedule_skips_inapplicable_interleaved():
    """An interleaved projection whose micro-batch count the measurement
    cannot support (m=7 not divisible by n//v=2 devices) is skipped, not
    allowed to abort the same-device ranking."""
    from torchgpipe_tpu.utils.tracing import TimelineEvent, recommend_schedule

    n, m = 4, 7
    events = []
    for j in range(n):
        for i in range(m):
            events.append(TimelineEvent("fwd", j, i, 0.0, 1.0))
            events.append(TimelineEvent("bwd", j, i, 0.0, 1.0))
    rows = recommend_schedule(events, n, virtual_stages=(2,))
    assert {r.schedule for r in rows} == {"fill_drain", "1f1b", "zb"}


def test_recommend_schedule_ignores_non_cell_phases():
    """'loss' events (recorded by the engine on the last stage) must not
    skew the ranking: only fill-drain's simulate_pipeline path counts
    them, so a fair comparison drops them — makespans match the
    loss-free timeline and busy stays a valid fraction."""
    from torchgpipe_tpu.utils.tracing import TimelineEvent, recommend_schedule

    n, m, t = 4, 8, 1.0
    cells = []
    for j in range(n):
        for i in range(m):
            cells.append(TimelineEvent("fwd", j, i, 0.0, t))
            cells.append(TimelineEvent("bwd", j, i, 0.0, t))
    noisy = cells + [
        TimelineEvent("loss", n - 1, i, 0.0, 10 * t) for i in range(m)
    ]
    clean_rows = recommend_schedule(cells, n)
    noisy_rows = recommend_schedule(noisy, n)
    assert [(r.schedule, r.makespan) for r in noisy_rows] == [
        (r.schedule, r.makespan) for r in clean_rows
    ]
    for r in noisy_rows:
        assert 0.0 < r.busy <= 1.0 and abs(r.busy + r.bubble - 1.0) < 1e-9


def test_simulate_pipeline_rejects_unknown_schedule():
    from torchgpipe_tpu.utils.tracing import TimelineEvent

    ev = [TimelineEvent("fwd", 0, 0, 0.0, 1.0)]
    import pytest as _pytest

    with _pytest.raises(ValueError, match="fill_drain"):
        simulate_pipeline(ev, 1, schedule="zigzag")


@pytest.mark.slow
def test_sharded_checkpoint_roundtrip_interleaved_and_loss(
    cpu_devices, tmp_path
):
    """save_sharded/restore_sharded round-trip the round-2 param layouts:
    interleaved [n, v, ...] stage-sharded blocks AND parametric loss-layer
    params — restored arrays keep their mesh shardings and training
    continues bit-identically."""
    pytest.importorskip("orbax.checkpoint")
    from torchgpipe_tpu.models.transformer import (
        TransformerConfig,
        chunked_lm_loss,
        llama_spmd,
    )
    from torchgpipe_tpu.spmd import SpmdGPipe, make_mesh
    from torchgpipe_tpu.utils.serialization import (
        restore_sharded,
        save_sharded,
    )

    n, v, m = 2, 2, 4
    cfg = TransformerConfig(
        vocab=64, dim=32, n_layers=n * v, n_heads=4, n_kv_heads=2
    )
    block, pre, post = llama_spmd(cfg, n * v)
    mesh = make_mesh(n, 1, devices=cpu_devices[:n])
    pipe = SpmdGPipe(
        block, n, mesh, chunks=m, loss_fn=chunked_lm_loss(cfg, chunk=16),
        pre=pre, post=None, checkpoint="always",
        schedule="interleaved", virtual_stages=v,
    )
    tokens = jnp.mod(jnp.arange(2 * m * 16).reshape(2 * m, 16), 64).astype(
        jnp.int32
    )
    labels = jnp.mod(tokens + 1, 64)
    params = pipe.init(
        jax.random.PRNGKey(0), jax.ShapeDtypeStruct(tokens.shape, tokens.dtype)
    )
    loss0, grads = pipe.train_step(params, tokens, labels)
    params = jax.tree_util.tree_map(lambda p, g: p - 0.1 * g, params, grads)

    save_sharded(str(tmp_path / "ckpt"), params)
    zeros = jax.tree_util.tree_map(jnp.zeros_like, params)
    zeros = pipe.place(zeros)  # template carries the mesh shardings
    restored = restore_sharded(str(tmp_path / "ckpt"), zeros)

    # Shardings preserved (stage-sharded blocks stay stage-sharded).
    leaf = jax.tree_util.tree_leaves(restored["blocks"])[0]
    leaf0 = jax.tree_util.tree_leaves(params["blocks"])[0]
    assert leaf.sharding == leaf0.sharding
    # Training continues identically from the restored state.
    l1, _ = pipe.train_step(params, tokens, labels)
    l2, _ = pipe.train_step(restored, tokens, labels)
    assert float(l1) == float(l2)
    assert float(l1) != float(loss0)


def test_simulate_pipeline_interleaved_uniform_cells():
    """Uniform cells, 8 measured global blocks projected onto 4 devices
    with v=2 virtual stages: the interleaved projection must (a) beat the
    plain-1F1B projection of the SAME work on 4 devices with v=1-style
    2-block stages — the bubble shrinks by ~v — and (b) never beat the
    per-device work floor 2·m·v·t."""
    from torchgpipe_tpu.utils.tracing import TimelineEvent

    n_blocks, v, m, t = 8, 2, 8, 1.0
    n_dev = n_blocks // v
    events = []
    for g in range(n_blocks):
        for i in range(m):
            events.append(TimelineEvent("fwd", g, i, 0.0, t))
            events.append(TimelineEvent("bwd", g, i, 0.0, t))
    res = simulate_pipeline(
        events, n_blocks, schedule="interleaved", virtual_stages=v
    )
    assert res is not None
    makespan, busy, bubble = res
    # Work floor: each device runs 2 ops per (chunk, micro-batch).
    floor = 2 * m * v * t
    assert makespan >= floor - 1e-9
    assert 0.0 < busy <= 1.0 and 0.0 <= bubble < 1.0

    # Same total work on n_dev devices WITHOUT interleaving: fuse each
    # device's v blocks into one 2t-per-op stage and 1F1B it.
    fused = []
    for j in range(n_dev):
        for i in range(m):
            fused.append(TimelineEvent("fwd", j, i, 0.0, 2 * t))
            fused.append(TimelineEvent("bwd", j, i, 0.0, 2 * t))
    plain, _, _ = simulate_pipeline(fused, n_dev, schedule="1f1b")
    assert makespan < plain, (makespan, plain)
    # The bubble advantage is ~v: interleaved idle ticks = plain/v.
    idle_inter = makespan - floor
    idle_plain = plain - floor
    assert idle_inter <= idle_plain / v + 2 * t, (idle_inter, idle_plain)


def test_simulate_pipeline_interleaved_validation():
    from torchgpipe_tpu.utils.tracing import TimelineEvent

    events = [TimelineEvent("fwd", 0, 0, 0.0, 1.0)]
    with pytest.raises(ValueError, match="virtual_stages >= 2"):
        simulate_pipeline(events, 4, schedule="interleaved")
    with pytest.raises(ValueError, match="must divide"):
        simulate_pipeline(events, 6, schedule="interleaved", virtual_stages=4)
    with pytest.raises(ValueError, match="only applies"):
        simulate_pipeline(events, 4, schedule="1f1b", virtual_stages=2)


def test_simulate_pipeline_interleaved_rejects_partial_groups():
    from torchgpipe_tpu.utils.tracing import TimelineEvent

    events = [
        TimelineEvent("fwd", g, i, 0.0, 1.0)
        for g in range(8) for i in range(6)  # m=6 not divisible by n=4
    ]
    with pytest.raises(ValueError, match="divisible by the device count"):
        simulate_pipeline(events, 8, schedule="interleaved", virtual_stages=2)


def test_simulate_pipeline_zb_uniform_cells():
    """Uniform cells, zb projection (fused bwd split into two halves):
    must beat the fused-backward 1F1B projection of the same timeline and
    respect the per-stage work floor (m fwd + m bwd per stage)."""
    from torchgpipe_tpu.utils.tracing import TimelineEvent

    n, m, t = 4, 8, 1.0
    events = []
    for j in range(n):
        for i in range(m):
            events.append(TimelineEvent("fwd", j, i, 0.0, t))
            events.append(TimelineEvent("bwd", j, i, 0.0, t))
    zb_mk, zb_busy, _ = simulate_pipeline(events, n, schedule="zb")
    f1_mk, _, _ = simulate_pipeline(events, n, schedule="1f1b")
    assert zb_mk < f1_mk, (zb_mk, f1_mk)
    assert zb_mk >= 2 * m * t - 1e-9  # work floor per stage
    assert 0.0 < zb_busy <= 1.0


def test_recommend_schedule_on_real_engine_timeline():
    """End-to-end: a sync Timeline traced from a real pipelined training
    step feeds recommend_schedule — all three same-device schedules rank
    with finite makespans and valid busy fractions."""
    from torchgpipe_tpu.ops.nn import dense, relu
    from torchgpipe_tpu.layers import named
    from torchgpipe_tpu.utils.tracing import recommend_schedule

    layers = named([dense(16), relu(), dense(16), relu()])
    tracer = Timeline(sync=True)
    model = GPipe(layers, balance=[2, 2], chunks=4, tracer=tracer)
    spec = jax.ShapeDtypeStruct((8, 16), jnp.float32)
    params, state = model.init(jax.random.PRNGKey(0), spec)
    x = jax.random.normal(jax.random.PRNGKey(1), (8, 16))
    y = jax.random.normal(jax.random.PRNGKey(2), (8, 16))
    model.value_and_grad(
        params, state, x, y, lambda o, t: jnp.mean((o - t) ** 2)
    )
    rows = recommend_schedule(tracer.events, n_stages=2)
    assert {r.schedule for r in rows if r.devices == 2} == {
        "fill_drain", "1f1b", "zb"
    }
    for r in rows:
        assert np.isfinite(r.makespan) and r.makespan > 0
        assert 0.0 < r.busy <= 1.0


def test_simulate_pipeline_survives_train_trace_with_barrier_spans():
    """The engine's gathered-loss barrier records at mb -1 (and SPMD
    step spans at stage -1); simulate_pipeline must project the CELLS
    and ignore aggregate spans — a traced training run is the function's
    documented input."""
    tracer = Timeline(sync=True)
    model = GPipe(_layers(), balance=[2, 2], chunks=4, tracer=tracer)
    in_spec = jax.ShapeDtypeStruct((8, 8), jnp.float32)
    params, state = model.init(jax.random.PRNGKey(0), in_spec)
    x = jax.random.normal(jax.random.PRNGKey(1), (8, 8))
    y = jax.random.normal(jax.random.PRNGKey(2), (8, 4))
    model.value_and_grad(params, state, x, y, _mse)
    assert any(e.mbatch < 0 for e in tracer.events)  # the loss barrier
    res = simulate_pipeline(tracer.events, n_stages=2)
    assert res is not None
    makespan, busy, bubble = res
    assert makespan > 0 and 0.0 < busy <= 1.0
    # Identical to projecting the cell spans alone.
    cells = [e for e in tracer.events if e.mbatch >= 0 and e.stage >= 0]
    assert simulate_pipeline(cells, n_stages=2) == res
