"""The readers of ``chipbench/spans.py`` on toy runs: every new metric's file
resolves to a reader that finds the program's spans, the phases add up to the
step the builder timed from outside, and a wrapped ring reads as nothing."""

import importlib
import types

import pytest

import toy
from chipbench import spans
from chipbench.common import HERE, load_json, resolve
from chipbench.run import make_cell
from torchgpipe_tpu.utils.tracing import Timeline

ENGINE = ["engine_admit_ms.backlog", "engine_build_ms.backlog", "engine_dispatch_ms.backlog",
          "engine_fetch_ms.backlog", "engine_emit_ms.backlog", "engine_self_ms.backlog"]
# Device time a call, as trace.py would have reduced it (a CPU run has none).
MODULES = {"jit_prefill_body(1)": [0.0], "jit_decode_body(2)": [0.0]}


def read(metric, facts):
    spec = load_json(HERE / "layer_metrics" / f"{metric}.json")
    assert spec["optional"] is True      # the parent commit has no spans to read
    return resolve(spec["reader"])(facts, **spec.get("args", {}))


def facts_of(workload, config, seconds=1.0):
    """The facts ``run.py`` hands a reader, from a toy run of the builder."""
    cell = make_cell(toy.cell_of(workload), 7, seconds, config_patch=config,
                     traffic_patch=toy.traffic_patch(workload))
    system = cell.config[cell.traffic["system"]]
    builder = importlib.import_module("chipbench.builders." + system["builder"].replace("-", "_"))
    out = builder.run(cell)
    assert all(c.ok for c in out.checks)
    return dict(out.facts, cell=cell, trace={"modules": MODULES})


@pytest.fixture(scope="module")
def serve_facts():
    return facts_of(toy.BACKLOG, toy.TOY_CONFIG)


@pytest.fixture(scope="module")
def pp4_facts():
    return facts_of(toy.PP4, toy.PP4_CONFIG)


@pytest.mark.parametrize("metric", ENGINE + ["engine_launch_gap_ms.backlog"])
def test_engine_readers_find_their_spans(serve_facts, metric):
    value = read(metric, serve_facts)
    assert value is not None and value >= 0.0


def test_phases_and_self_time_add_up_to_the_step(serve_facts):
    """The builder's clock around ``Engine.step()`` against the spans inside
    it; the launch gap is a part of the dispatch, not a seventh addend."""
    total = sum(read(metric, serve_facts) for metric in ENGINE)
    assert total == pytest.approx(serve_facts["step_wall_ms"]["all"], rel=0.05)
    dispatch = read("engine_dispatch_ms.backlog", serve_facts)
    assert read("engine_launch_gap_ms.backlog", serve_facts) == pytest.approx(dispatch)
    assert read("engine_self_ms.backlog", serve_facts) < 0.2 * total


def test_train_readers_on_the_toy_pp4_run(pp4_facts):
    assert read("train_dispatch_ms.train", pp4_facts) > 0.0
    # 4 stages x 8 chunks, fill-drain: 3 of 11 ticks are fill and drain.
    assert read("bubble_tick_share_pct.pp4", pp4_facts) == pytest.approx(100.0 * 12 / 44)


def test_train_window_leaves_out_the_steps_behind_it(pp4_facts, monkeypatch):
    """A traced run takes ``trace_steps`` steps behind the window."""
    tl = Timeline()
    for i in range(6):
        with tl.span("step", mark=i):
            pass
    monkeypatch.setattr(spans, "_timeline", lambda: tl)
    cell = types.SimpleNamespace(trace=True, config={"train": {"trace_steps": 2}})
    window = spans._train_window({"steps": 3, "cell": cell})
    assert [e.fields["mark"] for e in window] == [1, 2, 3]
    cell.trace = False
    assert [e.fields["mark"] for e in spans._train_window({"steps": 3, "cell": cell})] == [3, 4, 5]


@pytest.mark.parametrize("capacity,wrapped", [(64, False), (8, True)])
def test_wrapped_ring_reads_as_nothing(monkeypatch, capacity, wrapped):
    tl = Timeline(capacity=capacity)
    for _ in range(5):
        with tl.span("engine.step"):
            with tl.span("engine.admit"):
                pass
            with tl.span("engine.decode"):
                for leaf in ("build", "dispatch", "fetch", "emit"):
                    with tl.span("engine." + leaf):
                        pass
    monkeypatch.setattr(spans, "_timeline", lambda: tl)
    facts = {"prefill_steps": 0, "decode_steps": 2, "trace": {"modules": MODULES}}
    for metric in ENGINE + ["engine_launch_gap_ms.backlog"]:
        assert (read(metric, facts) is None) == wrapped, metric


def test_program_without_spans_reads_as_nothing(monkeypatch, serve_facts, pp4_facts):
    """What the parent commit gives these readers: no default timeline."""
    monkeypatch.setattr(spans, "_timeline", lambda: None)
    for metric in ENGINE + ["engine_launch_gap_ms.backlog"]:
        assert read(metric, serve_facts) is None
    assert read("train_dispatch_ms.train", pp4_facts) is None
    assert read("bubble_tick_share_pct.pp4", pp4_facts) is None
