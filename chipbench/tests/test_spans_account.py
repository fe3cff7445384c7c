"""The readers of ``chipbench/spans_account.py``: on the toy runs every metric's
file resolves to a reader that finds what the program recorded; on a timeline
written here by hand each does the arithmetic its name says; and each reads as
nothing on the parent commit's shape of span (no field of this PR's, no mark),
on a wrapped ring and on a program without spans."""

import time
import types

import pytest

import toy
from chipbench import spans
from chipbench.common import HERE, load_json, resolve
from test_spans import facts_of
from torchgpipe_tpu.utils.tracing import Timeline

# Metric -> what the hand-written timeline below reads as.
SERVING = {
    "tokens_per_step.backlog": 13 / 3,
    "decode_rows_per_step.backlog": 4.0,
    "slots_in_use_pct.backlog": None,       # weighted by the steps' own durations
    "queue_dry_step_share_pct.backlog": None,
    "prefill_fill_share_pct.backlog": 25.0,
    "prefill_deferred_rows_per_step.backlog": 5.0,
    "steps_launched_ahead_share_pct.backlog": 100.0 * 2 / 3,
    "attend_rows_read_share_pct.backlog": 100.0 * 60 / 300,
    "longest_engine_step_ms.backlog": None,
    "gc_pause_ms.backlog": 2.0,
    "setup_compile_s": 7.0,
    "setup_cache_miss_s": 2.0,
}
TRAIN = {"gc_pause_ms.train": 2.0, "setup_compile_s": 7.0, "setup_cache_miss_s": 2.0}
# What reads a field or a mark this PR adds: nothing to read on the parent commit.
NEW = {"slots_in_use_pct.backlog", "queue_dry_step_share_pct.backlog", "gc_pause_ms.backlog",
       "gc_pause_ms.train", "setup_compile_s", "setup_cache_miss_s"}
SERVE_FACTS = {"prefill_steps": 1, "decode_steps": 2}
TRAIN_FACTS = {"steps": 2, "cell": types.SimpleNamespace(trace=False)}


def read(metric, facts):
    spec = load_json(HERE / "layer_metrics" / f"{metric}.json")
    assert spec["optional"] is True      # the parent commit runs with these files
    return resolve(spec["reader"])(facts, **spec.get("args", {}))


def written(capacity=None, shape="change"):
    """A warm-up step, then a window of one prefill and two decode steps and
    two train steps, with set-up's compiles before them and a collection of
    2 ms inside the second step of each kind.  ``shape``: as this ``change``
    records them, as the ``parent`` commit does (none of this PR's fields, no
    mark), or ``bare`` spans without a field."""
    tl = Timeline(capacity=capacity)
    now = time.perf_counter()
    if shape == "change":   # a nested trace, a compile, a load from the cache
        tl.mark("xla.compile", now - 10, now - 6, phase="trace", fun="outer")
        tl.mark("xla.compile", now - 9, now - 8, phase="trace", fun="inner")
        tl.mark("xla.compile", now - 5, now - 3, phase="backend", fun="jit(outer)", cache_hit=0)
        tl.mark("xla.compile", now - 2, now - 1, phase="backend", fun="jit(warm)", cache_hit=1)

    def collect():
        if shape == "change":
            at = time.perf_counter()
            tl.mark("gc.collect", at, at + 0.002, generation=2, collected=0)
            time.sleep(0.003)

    def step(action, ahead, admit, tokens, pause=False, **fields):
        keep = shape != "bare"
        with tl.span("engine.step"):
            with tl.span("engine.admit"):
                if keep:
                    tl.annotate(admitted=1, **(admit if shape == "change" else {}))
            with tl.span(action, **(fields if keep else {})):
                with tl.span("engine.dispatch"):
                    if pause:
                        collect()
                with tl.span("engine.emit"):
                    if keep:
                        tl.annotate(tokens=tokens)
            if keep:
                tl.annotate(ahead=ahead)

    step("engine.decode", 0, dict(queued=9, free=0, slots=4), 99, rows=1, rows_read=1, rows_cap=1)
    step("engine.prefill", 0, dict(queued=3, free=0, slots=4), 0,
         rows=2, g=8, cap=8, deferred=5, rows_read=10, rows_cap=100)
    step("engine.decode", 1, dict(queued=0, free=1, slots=4), 5, pause=True,
         rows=3, rows_read=20, rows_cap=100)
    step("engine.decode", 1, dict(queued=0, free=0, slots=4), 8, rows=5, rows_read=30, rows_cap=100)
    for pause in (False, True):
        with tl.span("step"):
            if pause:
                collect()
    return tl


def test_every_reader_on_the_toy_serving_run():
    facts = facts_of(toy.BACKLOG, toy.TOY_CONFIG)
    values = {metric: read(metric, facts) for metric in SERVING}
    assert all(isinstance(v, float) and v >= 0.0 for v in values.values()), values
    steps = facts["prefill_steps"] + facts["decode_steps"]
    assert values["tokens_per_step.backlog"] * steps == pytest.approx(facts["output_tokens"])
    assert 0.0 < values["slots_in_use_pct.backlog"] <= 100.0
    assert values["decode_rows_per_step.backlog"] <= toy.TOY_CONFIG["serve"]["num_slots"]
    assert values["longest_engine_step_ms.backlog"] >= facts["step_wall_ms"]["all"] * 0.9
    assert values["setup_compile_s"] > values["setup_cache_miss_s"]


def test_every_reader_on_the_toy_pp4_run():
    facts = facts_of(toy.PP4, toy.PP4_CONFIG)
    values = {metric: read(metric, facts) for metric in TRAIN}
    assert all(isinstance(v, float) and v >= 0.0 for v in values.values()), values
    assert values["setup_compile_s"] > 0.0


@pytest.mark.parametrize("metric,want", list(SERVING.items()) + list(TRAIN.items())[:1])
def test_reader_does_its_arithmetic(monkeypatch, metric, want):
    tl = written()
    monkeypatch.setattr(spans, "_timeline", lambda: tl)
    got = read(metric, TRAIN_FACTS if metric.endswith(".train") else SERVE_FACTS)
    steps = [e for e in tl.events if e.name == "engine.step"][1:]       # the window's
    total = sum(e.duration for e in steps)
    if metric == "slots_in_use_pct.backlog":
        want = 100.0 * (steps[0].duration + 0.75 * steps[1].duration + steps[2].duration) / total
    elif metric == "queue_dry_step_share_pct.backlog":
        want = 100.0 * steps[1].duration / total
    elif metric == "longest_engine_step_ms.backlog":
        want = 1e3 * steps[1].duration      # the one that held the collection
    assert got == pytest.approx(want)


def test_setup_reads_the_same_before_a_train_window(monkeypatch):
    tl = written()
    monkeypatch.setattr(spans, "_timeline", lambda: tl)
    for metric, want in TRAIN.items():
        assert read(metric, TRAIN_FACTS) == pytest.approx(want)


@pytest.mark.parametrize("metric", sorted(set(SERVING) | set(TRAIN)))
def test_reader_finds_nothing_where_nothing_is(monkeypatch, metric):
    facts = TRAIN_FACTS if metric.endswith(".train") else SERVE_FACTS
    shapes = {"parent": written(shape="parent"), "bare": written(shape="bare"),
              "wrapped": written(capacity=12), "none": None}
    for shape, tl in shapes.items():
        monkeypatch.setattr(spans, "_timeline", lambda tl=tl: tl)
        value = read(metric, facts)
        if shape == "parent" and metric not in NEW:
            assert value is not None, shape
        elif shape == "bare" and metric == "longest_engine_step_ms.backlog":
            assert value > 0.0      # it reads the span alone
        else:
            assert value is None, shape
