"""The span spine (``utils.tracing.Timeline.span``) and what records into it:
the spans inside ``Engine.step``, the ``step`` span of the SPMD train step
with the schedule it was built with, the named scopes of the compiled step
and the names of the flash kernels."""

import gc
import glob
import os

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from torchgpipe_tpu.layers import chain, sequential_init
from torchgpipe_tpu.models.transformer import TransformerConfig, llama
from torchgpipe_tpu.ops import dense, layer_norm
from torchgpipe_tpu.ops.flash_attention import (
    flash_attention,
    flash_decode_attention,
)
from torchgpipe_tpu.parallel import interleaved, zerobubble
from torchgpipe_tpu.serving import Engine
from torchgpipe_tpu.spmd import SpmdGPipe, make_mesh, schedule_shape
from torchgpipe_tpu.utils import tracing
from torchgpipe_tpu.utils.tracing import Timeline, default_timeline

CFG = TransformerConfig(vocab=64, dim=32, n_layers=2, n_heads=4, n_kv_heads=2)
ACTIONS = ("engine.prefill", "engine.decode")


def mse(y, t):
    return jnp.mean((y - t) ** 2)


@pytest.fixture(scope="module")
def flat_params():
    params, _, _ = sequential_init(
        llama(CFG), jax.random.PRNGKey(0),
        jax.ShapeDtypeStruct((2, 8), jnp.int32),
    )
    return params


def _engine(flat_params, donate=True):
    """A toy engine recording into a timeline of its own; it donates
    its cache, as the benchmark's engines do, so it keeps a step in
    flight."""
    eng = Engine(CFG, flat_params, num_slots=4, max_len=32, prefill_chunk=4,
                 donate=donate)
    eng.timeline = Timeline()
    return eng


def _serve(eng, n=6, seed=0):
    rng = np.random.RandomState(seed)
    for _ in range(n):
        prompt = rng.randint(0, 64, (int(rng.randint(2, 10)),))
        eng.submit(prompt.astype(np.int32), int(rng.randint(2, 6)))
    assert eng.run() == "idle"
    return list(eng.timeline.events)


# --------------------------------------------------------------------- #
# the spine                                                             #
# --------------------------------------------------------------------- #


def test_span_records_parent_sequence_and_fields():
    tl = Timeline()
    with tl.span("outer", stage=2, rows=3) as outer:
        with tl.span("inner"):
            tl.annotate(tokens=5)
        tl.annotate(g=4)
    inner, got = tl.events            # children close, and land, first
    assert (got.name, got.stage, got.seq, got.parent) == ("outer", 2, 0, -1)
    assert got.fields == {"rows": 3, "g": 4} and outer.seq == 0
    assert (inner.seq, inner.parent, inner.fields) == (1, 0, {"tokens": 5})
    assert got.t_start <= inner.t_start <= inner.t_end <= got.t_end
    tl.annotate(lost=1)               # outside any span: a no-op
    with tl.span("gone") as gone:
        gone.drop()
    assert [e.name for e in tl.events] == ["inner", "outer"]


def test_bounded_timeline_reports_a_wrap():
    tl = Timeline(capacity=4)
    for i in range(3):
        with tl.span(f"s{i}"):
            pass
    assert [e.seq for e in tl.since(0)] == [0, 1, 2]      # not wrapped yet
    for i in range(3, 7):
        with tl.span(f"s{i}"):
            pass
    assert len(tl.events) == 4 and tl.events[0].seq == 3
    assert tl.since(2) is None        # seq 2 was pushed out: say so
    assert [e.seq for e in tl.since(3)] == [3, 4, 5, 6]
    tl.reset()
    assert tl.since(0) == []


def test_span_lands_in_the_profilers_trace(tmp_path):
    """Under ``jax.profiler.start_trace`` the span is written into the
    profile under its own name, on the profiler's clock."""
    tl = Timeline()
    jax.profiler.start_trace(str(tmp_path))
    try:
        with tl.span("spine.probe", rows=3):
            jnp.ones((8, 8)).sum().block_until_ready()
    finally:
        jax.profiler.stop_trace()
    (path,) = glob.glob(
        os.path.join(str(tmp_path), "**", "*.xplane.pb"), recursive=True
    )
    data = jax.profiler.ProfileData.from_file(path)
    found = [
        e for plane in data.planes for line in plane.lines
        for e in line.events if e.name == "spine.probe"
    ]
    assert len(found) == 1 and found[0].duration_ns > 0
    assert dict(found[0].stats).get("rows") in (3, "3")


def test_default_timeline_is_one_bounded_ring():
    assert default_timeline() is default_timeline()
    # A 44 s window of 2.4 ms steps at 7 spans a step.
    assert default_timeline().capacity >= 7 * 44 / 2.4e-3


def test_mark_takes_the_next_seq_and_the_open_span_as_parent():
    tl = Timeline()
    t = tracing.time.perf_counter()
    tl.mark("alone", t, t + 0.5, why="x")
    with tl.span("outer") as outer:
        with tl.span("inner"):
            pass
        tl.mark("late", t + 1.0, t + 1.25)
    alone, inner, late, got = tl.events
    assert (alone.name, alone.seq, alone.parent) == ("alone", 0, -1)
    assert alone.fields == {"why": "x"} and alone.duration == pytest.approx(0.5)
    assert alone.t_start == pytest.approx(t - tl._t0)
    assert (outer.seq, inner.seq, late.seq) == (1, 2, 3)      # the order they came
    assert (late.parent, late.fields, got.name) == (1, None, "outer")
    assert late.duration == pytest.approx(0.25)


def test_wrapped_ring_with_marks_answers_since_truthfully():
    """A mark lands at once and the span open around it later, with the
    smaller ``seq``: what was pushed out is told by ``seq``, not by place."""
    tl = Timeline(capacity=3)
    t = tracing.time.perf_counter()
    with tl.span("s0"):                 # seq 0: lands after marks 1 and 2
        tl.mark("m1", t, t)
        tl.mark("m2", t, t)
    assert [e.seq for e in tl.since(0)] == [1, 2, 0]
    tl.mark("m3", t, t)                 # pushes m1 (seq 1) out, not s0
    assert tl.since(0) is None and tl.since(1) is None
    assert [e.seq for e in tl.since(2)] == [2, 3]
    assert [e.name for e in tl.events] == ["m2", "s0", "m3"]


# --------------------------------------------------------------------- #
# time the program did not choose to spend                              #
# --------------------------------------------------------------------- #


def _children(span, name, **fields):
    """The ``name`` events under ``span`` that carry ``fields`` (a test
    process makes collections and compiles of its own now and then)."""
    return [e for e in default_timeline().events
            if e.name == name and e.parent == span.seq
            and all(e.fields[k] == v for k, v in fields.items())]


def test_forced_collection_is_a_child_of_the_span_it_fell_into():
    with default_timeline().span("probe.gc") as probe:
        gc.collect()
    (event,) = _children(probe, "gc.collect", generation=2)
    assert event.fields["collected"] >= 0
    mine = next(e for e in default_timeline().events if e.seq == probe.seq)
    assert mine.t_start <= event.t_start < event.t_end <= mine.t_end


def test_full_collection_lands_in_the_profilers_trace(tmp_path):
    """A generation-2 collection holds a ``TraceAnnotation`` open from its
    start to its stop: a profile shows it on the device trace's clock."""
    jax.profiler.start_trace(str(tmp_path))
    try:
        gc.collect()
        gc.collect(0)
    finally:
        jax.profiler.stop_trace()
    (path,) = glob.glob(
        os.path.join(str(tmp_path), "**", "*.xplane.pb"), recursive=True
    )
    data = jax.profiler.ProfileData.from_file(path)
    found = [
        e for plane in data.planes for line in plane.lines
        for e in line.events if e.name == "gc.collect"
    ]
    assert len(found) == 1 and found[0].duration_ns > 0


def test_short_young_collection_leaves_nothing():
    gc.collect()                        # so that the young one has no work
    with default_timeline().span("probe.gc0") as probe:
        gc.collect(0)
    assert _children(probe, "gc.collect", generation=0) == []


def test_long_young_collection_is_marked(monkeypatch):
    monkeypatch.setattr(tracing._ProcessMarks, "GC_FLOOR_S", 0.0)
    with default_timeline().span("probe.gc0") as probe:
        gc.collect(0)
    assert len(_children(probe, "gc.collect", generation=0)) == 1


def test_installing_twice_records_once():
    callbacks = list(gc.callbacks)
    tracing._PROCESS_MARKS.install()
    assert gc.callbacks == callbacks
    with default_timeline().span("probe.gc") as probe:
        gc.collect()
        jax.jit(lambda x: x - 3.0)(jnp.ones((5,)))
    assert len(_children(probe, "gc.collect", generation=2)) == 1
    assert len(_children(probe, "xla.compile", fun="jit(<lambda>)",
                         phase="backend")) == 1


def test_timeline_of_ones_own_records_neither():
    tl = Timeline()
    with tl.span("probe"):
        gc.collect()
        jax.jit(lambda x: x - 4.0)(jnp.ones((5,)))
    assert [e.name for e in tl.events] == ["probe"]


def test_fresh_jit_leaves_its_phases_under_the_span_and_a_second_call_none():
    def fresh_probe(x):
        return x * 2.0 + 1.0

    fn = jax.jit(fresh_probe)
    x = jnp.ones((3, 7))
    with default_timeline().span("probe.jit") as first:
        fn(x)
    with default_timeline().span("probe.jit") as second:
        fn(x)
    by_phase = {}
    for e in _children(first, "xla.compile"):
        by_phase.setdefault(e.fields["phase"], []).append(e)
    assert set(by_phase) == {"trace", "lower", "backend"}
    assert "fresh_probe" in [e.fields["fun"] for e in by_phase["trace"]]
    (lower,), (backend,) = by_phase["lower"], by_phase["backend"]
    assert lower.fields["fun"] == backend.fields["fun"] == "jit(fresh_probe)"
    assert backend.fields["cache_hit"] in (0, 1) and "cache_hit" not in lower.fields
    assert all(e.duration > 0 and e.t_end <= backend.t_end for e in by_phase["trace"])
    assert _children(second, "xla.compile") == []


def test_backend_phase_says_whether_it_was_a_load_from_the_cache():
    tl = Timeline()
    marks = tracing._ProcessMarks(tl)           # not installed: driven by hand
    backend = "/jax/core/compile/backend_compile_duration"
    marks._on_event("/jax/compilation_cache/compile_requests_use_cache")
    marks._on_duration(backend, 2.0, fun_name="jit(cold)")
    marks._on_event(marks.CACHE_HIT)
    marks._on_duration("/jax/compilation_cache/cache_retrieval_time_sec", 0.1)
    marks._on_duration(backend, 0.25, fun_name="jit(warm)")
    marks._on_duration(backend, 1.0, fun_name="jit(cold_again)")
    assert [(e.fields["fun"], e.fields["cache_hit"], round(e.duration, 6))
            for e in tl.events] == [
        ("jit(cold)", 0, 2.0), ("jit(warm)", 1, 0.25), ("jit(cold_again)", 0, 1.0)]


# --------------------------------------------------------------------- #
# the serving engine                                                    #
# --------------------------------------------------------------------- #


@pytest.mark.parametrize("donate", [True, False])
def test_engine_step_spans_cover_the_step(flat_params, donate):
    eng = _engine(flat_params, donate)
    events = _serve(eng)
    eng_tokens = eng.metrics.tokens_out
    by_seq = {e.seq: e for e in events}
    children = {}
    for e in events:
        children.setdefault(e.parent, []).append(e)
    steps = [e for e in events if e.name == "engine.step"]
    assert len(steps) > 6 and all(e.parent == -1 for e in steps)
    seen = set()
    for step in steps:
        admit, action = sorted(children[step.seq], key=lambda e: e.seq)
        assert admit.name == "engine.admit" and "admitted" in admit.fields
        assert action.name in ACTIONS and action.fields["rows"] >= 1
        assert ("g" in action.fields) == (action.name == "engine.prefill")
        leaves = [e.name for e in sorted(children[action.seq],
                                         key=lambda e: e.seq)]
        # Build and launch this step's program; then, where a step was
        # in flight (``ahead``), wait for THAT step and deliver its
        # tokens under the one ``engine.emit`` that also advances the
        # books by this step's counts.
        ahead = step.fields["ahead"]
        assert ahead == (donate and step is not steps[0])
        want = ["engine.build", "engine.dispatch"]
        want += ["engine.fetch"] if ahead else []
        want += ["engine.emit"]
        # The run's last step leaves nothing to launch behind it, so it
        # is waited for and delivered in its own iteration; so is every
        # step of an engine that can retry (``donate=False``), which has
        # waited for it inside ``engine.dispatch`` already.
        own = step is steps[-1] or not donate
        want += ["engine.fetch", "engine.emit"] if own else []
        assert leaves == want
        seen.add(action.name)
    assert seen == set(ACTIONS)
    # Every token was delivered under some ``engine.emit``.
    assert sum(e.fields["tokens"] for e in events
               if e.name == "engine.emit") == eng_tokens
    assert not any(e.name == "engine.settle" for e in events)
    for e in events:                  # every child lies inside its parent
        if e.parent != -1:
            p = by_seq[e.parent]
            assert p.t_start <= e.t_start <= e.t_end <= p.t_end
            assert p.seq < e.seq
    assert sum(e.fields["admitted"] for e in events
               if e.name == "engine.admit") == 6


@pytest.mark.parametrize("action", ACTIONS)
def test_action_span_is_open_before_its_program_is_called(flat_params, action):
    eng = _engine(flat_params)
    fns = eng._prefill_fns if action == "engine.prefill" else None
    inner = (next(iter(fns.values())) if fns else eng._decode_fn)

    def probed(*args):
        with eng.timeline.span("probe"):
            return inner(*args)

    if fns:
        fns[next(iter(fns))] = probed
    else:
        eng._decode_fn = probed
    events = _serve(eng, n=2)
    by_seq = {e.seq: e for e in events}
    probes = [e for e in events if e.name == "probe"]
    assert probes
    for probe in probes:
        dispatch = by_seq[probe.parent]
        assert dispatch.name == "engine.dispatch"
        assert by_seq[dispatch.parent].name == action


def test_idle_iteration_records_nothing(flat_params):
    eng = _engine(flat_params)
    assert eng.step() is False
    assert list(eng.timeline.events) == []


def test_admit_span_carries_the_state_admission_left_behind(flat_params):
    """``queued`` / ``free`` / ``slots`` are the scheduler's and the pool's
    own numbers where admission ends, step by step; a backlog that empties
    shows room and nobody waiting."""
    eng = _engine(flat_params)
    inner, seen = eng.scheduler.next_action, []

    def probed():
        action = inner()
        if action is not None:
            seen.append({"queued": len(eng.scheduler.queue),
                         "free": eng.pool.num_free,
                         "slots": eng.pool.num_slots})
        return action

    eng.scheduler.next_action = probed
    events = _serve(eng, n=9)           # nine requests through four slots
    admits = [e.fields for e in events if e.name == "engine.admit"]
    assert len(admits) == len(seen) > 9
    for fields, want in zip(admits, seen):
        assert {k: fields[k] for k in want} == want and "admitted" in fields
    assert admits[0]["queued"] == 5 and admits[0]["free"] == 0
    assert all(a["slots"] == 4 for a in admits)
    assert max(a["queued"] for a in admits) == 5
    last = admits[-1]
    assert last["queued"] == 0 and last["free"] > 0


def test_engine_records_into_the_default_timeline(flat_params):
    eng = Engine(CFG, flat_params, num_slots=4, max_len=32, prefill_chunk=4)
    assert eng.timeline is default_timeline()


# --------------------------------------------------------------------- #
# the SPMD train step                                                   #
# --------------------------------------------------------------------- #


@pytest.mark.parametrize("schedule,v,ticks,busy", [
    ("fill_drain", 1, 11, 32),
    ("1f1b", 1, 22, 64),
    ("zb", 1, zerobubble.zero_bubble_tables(4, 8).ticks, 3 * 32),
    ("interleaved", 2, interleaved.interleaved_tables(4, 8, 2).ticks,
     2 * 2 * 32),
])
def test_schedule_shape_counts_4_stages_8_chunks(schedule, v, ticks, busy):
    """Busy slots are the schedule's cells (forward, backward and zb's W,
    per virtual stage), the rest of stages x ticks is the bubble."""
    assert schedule_shape(schedule, 4, 8, v) == {
        "schedule": schedule, "ticks": ticks, "stage_ticks": 4 * ticks,
        "busy_stage_ticks": busy,
    }


def test_schedule_shape_agrees_with_the_tables_own_bubble():
    zb = zerobubble.zero_bubble_tables(4, 8)
    shape = schedule_shape("zb", 4, 8)
    assert shape["stage_ticks"] - shape["busy_stage_ticks"] == zb.bubble_ticks
    il = interleaved.interleaved_tables(4, 8, 2)
    shape = schedule_shape("interleaved", 4, 8, 2)
    assert (shape["stage_ticks"] - shape["busy_stage_ticks"]
            == 4 * il.bubble_ticks)


def _pipe(cpu_devices, schedule, n, m, **kw):
    block = chain([layer_norm(name="ln"), dense(16, name="fc")], name="blk")
    mesh = make_mesh(n, 1, devices=cpu_devices[:n])
    return SpmdGPipe(block, n, mesh, chunks=m, loss_fn=mse,
                     checkpoint="always", schedule=schedule, **kw)


@pytest.mark.parametrize("schedule,n,m", [("fill_drain", 4, 8), ("1f1b", 2, 4)])
def test_step_span_carries_the_schedule_it_was_built_with(
    cpu_devices, schedule, n, m
):
    pipe = _pipe(cpu_devices, schedule, n, m)
    x = jax.random.normal(jax.random.PRNGKey(0), (2 * m, 16))
    params = pipe.init(jax.random.PRNGKey(1), x)
    opt = optax.sgd(1e-2)
    step = pipe.make_train_step(opt, donate=False)
    def spans():
        return [e for e in default_timeline().events if e.name == "step"]

    before = len(spans())
    step(params, pipe.place_tree(opt.init(params)), x, x)
    span = default_timeline().events[-1]      # no tracer: the default ring
    assert len(spans()) == before + 1
    # The first call compiled inside the span: its phases are its children.
    assert {"trace", "lower", "backend"} <= {
        e.fields["phase"] for e in default_timeline().events
        if e.name == "xla.compile" and e.parent == span.seq}
    assert span.name == "step" and span.stage == -1 and span.duration > 0
    assert span.fields == schedule_shape(schedule, n, m)
    if schedule == "fill_drain":
        assert (span.fields["stage_ticks"],
                span.fields["busy_stage_ticks"]) == (44, 32)


@pytest.mark.parametrize("schedule", ["fill_drain", "1f1b"])
def test_compiled_step_names_its_parts(cpu_devices, schedule):
    pipe = _pipe(cpu_devices, schedule, 2, 2, tracer=Timeline())
    x = jax.random.normal(jax.random.PRNGKey(0), (4, 16))
    params = pipe.init(jax.random.PRNGKey(1), x)
    opt = optax.sgd(1e-2)
    step = pipe.make_train_step(opt, donate=False)
    jaxpr = jax.make_jaxpr(step)(params, pipe.place_tree(opt.init(params)), x, x)
    text = jaxpr.pretty_print(name_stack=True)
    for scope in ("forward", "backward", "optimizer", "tick"):
        assert scope in text, scope


# --------------------------------------------------------------------- #
# the kernels                                                           #
# --------------------------------------------------------------------- #


@pytest.mark.parametrize("streaming,names", [
    (False, ("flash_fwd", "flash_bwd_dq", "flash_bwd_dkv")),
    (True, ("flash_fwd_stream", "flash_bwd_dq_stream", "flash_bwd_dkv_stream")),
])
def test_flash_kernels_carry_their_names(streaming, names):
    q = jnp.ones((1, 64, 2, 16), jnp.float32)

    def loss(q, k, v):
        return jnp.sum(flash_attention(q, k, v, causal=True, interpret=True,
                                       block_q=32, block_k=32,
                                       streaming=streaming))

    text = str(jax.make_jaxpr(jax.grad(loss, argnums=(0, 1, 2)))(q, q, q))
    for name in names:
        assert f"name={name}\n" in text or f"name={name} " in text, name


def test_decode_kernel_carries_its_name():
    q = jnp.ones((1, 1, 2, 128), jnp.float32)
    cache = jnp.ones((1, 256, 1, 128), jnp.float32)
    text = str(jax.make_jaxpr(
        lambda p: flash_decode_attention(q, cache, cache, p, interpret=True)
    )(jnp.int32(3)))
    assert "name=flash_decode" in text
