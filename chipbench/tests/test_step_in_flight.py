"""The backlog cell at toy width on the CPU, through ``run_cell`` as the
command line drives it, under a decode-heavy mix (short prompts, long answers:
nearly every step is a decode step over a full pool, where the host's share of
a step is largest).  The run is ``correct``, and every reader of the engine's
spans returns a number on an engine that keeps one step in flight:
``engine.dispatch`` is then the launch alone and ``engine.fetch`` the wait for
the step before, so the launch gap (dispatch less the program's device time)
may read below zero; that is a number."""

import pytest

import toy
from chipbench.common import HERE, load_json, resolve
from chipbench.run import run_cell
from torchgpipe_tpu.utils.tracing import default_timeline

CELL = toy.BACKLOG
# Prompts of one or two chunks, answers four to eight times as long, more
# requests than a second serves.
TOY_LONG_DECODE = {
    "requests": 12, "trace_seconds": 1.0, "max_total": 116,
    "prompt_len": {"median": 8, "sigma": 0.4, "min": 4, "max": 16},
    "new_tokens": {"median": 48, "sigma": 0.2, "min": 32, "max": 64},
}
ENGINE = sorted(p.stem for p in (HERE / "layer_metrics").glob("engine_*_ms.backlog.json"))
# Device time a call, as trace.py would have reduced it on a chip.
MODULES = {"jit_prefill_body(1)": [0.004], "jit_decode_body(2)": [0.004]}


@pytest.fixture(scope="module")
def run():
    mark = len(default_timeline().events)
    result = run_cell(CELL, 2 ** 31 + 17, 1.5, False, require_tpu=False,
                      config_patch=toy.TOY_CONFIG, traffic_patch=TOY_LONG_DECODE)
    return result, list(default_timeline().events)[mark:]


def test_toy_run_is_correct_and_decode_heavy(run):
    result, _ = run
    assert result["correct"] and result["failed"] == 0 and result["attempted"] > 0
    assert result["compared"]["compiled_in_window"]["value"] == 0
    notes = result["notes"]
    assert notes["decode_steps"] > 4 * notes["prefill_steps"] > 0
    assert result["metrics"]["serve_tokens_per_s"]["value"] > 0


@pytest.mark.parametrize("metric", ENGINE)
def test_every_engine_span_reader_returns_a_number(run, metric):
    result, _ = run
    assert len(ENGINE) == 7
    facts = dict(result["notes"], trace={"modules": MODULES})
    spec = load_json(HERE / "layer_metrics" / f"{metric}.json")
    value = resolve(spec["reader"])(facts, **spec.get("args", {}))
    assert isinstance(value, float) and value == value
    if metric != "engine_launch_gap_ms.backlog":
        assert value >= 0.0


def test_the_window_ran_with_a_step_in_flight(run):
    """One ``engine.step`` span a launched program, nearly all of them
    launched while the step before was in flight; the one wait is the
    ``engine.fetch`` under the next step's action."""
    result, events = run
    notes = result["notes"]
    steps = [e for e in events if e.name == "engine.step"]
    window = steps[-(notes["prefill_steps"] + notes["decode_steps"]):]
    assert len(window) == notes["prefill_steps"] + notes["decode_steps"]
    ahead = sum(e.fields["ahead"] for e in window)
    assert ahead >= len(window) - 2
    by_seq = {e.seq: e for e in events}
    fetches = [e for e in events if e.name == "engine.fetch" and e.seq > window[0].seq]
    assert len(fetches) >= ahead
    assert all(by_seq[e.parent].name in ("engine.prefill", "engine.decode", "engine.settle")
               for e in fetches if e.parent in by_seq)
