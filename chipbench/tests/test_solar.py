"""The ``engine-solar`` builder at toy widths on the CPU: a run of the cell is
correct, checks requests served from recycled slots and counts the state and
the experts; every planted fault, and the fp8 control in the program's place at
the configuration's own limits, comes out ``correct: false``; a seed relabels
the draw and leaves its work alone; ``peaks_solar``'s sizes against a count by
hand; the new per-layer metrics' files resolve to readers that read the
builder's facts; the reference's faults read over the limits."""

import functools
import json

import numpy as np
import pytest

from chipbench import limits_solar, peaks_solar as pk, reference_solar
from chipbench.common import HERE, ROOT, load_json, resolve
from chipbench.run import make_cell, run_cell

CELL = "solar-open2.serve-deep-backlog"
# The file's cut (*E KE KE KE) at toy widths: 4 delta-rule heads of 16, 4
# attention heads of 32 on 2 KV heads; 16 experts, 8 a token, this share holds
# experts 4..11.  float32 on the CPU: the program's gaps read 0 (every served
# token is the reference's best); the limits stand decades under the faults.
TOY = {
    "hidden_size": 64, "num_attention_heads": 4, "num_key_value_heads": 2, "head_dim": 32,
    "vocab_size": 256, "linear_attn_config": {"num_heads": 4, "head_dim": 16},
    "moe_intermediate_size": 32, "n_routed_experts": 8, "held_first": 4,
    "torch_dtype": "float32", "draw": {"router_bias_std": 0.1},
    "reduced": {"n_routed_experts": {"published": 16}},
    "serve": {"num_slots": 4, "max_len": 128, "prefill_chunk": 8,
              "limits": {"served_logit_gap_p99": 1e-3, "served_logit_gap_mean": 1e-4}},
}
TOY_SIZES = {"requests": 20, "cycles": 2, "trace_seconds": 1.0, "max_total": 128,
             "prompt_len": {"median": 24, "sigma": 0.9, "min": 4, "max": 100},
             "new_tokens": {"median": 8, "sigma": 0.7, "min": 2, "max": 16}}


def toy_run(seed, fault=None, seconds=6.0):
    return run_cell(CELL, seed, seconds, False, require_tpu=False, config_patch=TOY,
                    traffic_patch=TOY_SIZES, fault=fault)


@pytest.mark.parametrize("seed", [1, 2 ** 31 + 5])
def test_toy_run_is_correct_and_counts_the_state(seed):
    got = toy_run(seed)
    assert got["correct"] and got["failed"] == 0 and got["attempted"] > 0, got["compared"]
    assert set(got["compared"]) == {"served_logit_gap_p99", "served_logit_gap_mean",
                                    "recycled_missing", "compiled_in_window"}
    notes = got["notes"]
    assert notes["checked_recycled"] >= 2
    # A slot: 3 delta-rule layers x (a tail of 3 x 192 and a float32 state of
    # 4 x 16 x 16), the full layer's K and V at 128 rows, in the closed form's
    # served type (bfloat16) whatever the toy computes in.
    slot = 3 * (3 * 192 * 2 + 4 * 16 * 16 * 4)
    assert notes["kv_pool_bytes_by_kind"] == {"full": 4 * 128 * 2 * 2 * 32 * 2, "state": 4 * slot}
    live = notes["kv_live_bytes_by_kind"]
    assert 0 < live["state"] <= 4 * slot and notes["kv_live_bytes"] == pytest.approx(
        live["full"] + live["state"])
    assert notes["decode_state_bytes"] > 0 and notes["state_zeroed_slots"] == notes["admitted"]
    assert 0 < notes["decode_experts_touched"] <= 4 * 8
    assert 0 < notes["moe_held_assignments"] < notes["moe_routed_assignments"]


def test_a_seed_relabels_the_draw_and_leaves_its_work_alone():
    from chipbench import weights_solar
    from chipbench.builders import engine_solar as b

    cells = [make_cell(CELL, seed, 6.0, False, TOY, TOY_SIZES) for seed in (6, 2 ** 31 + 7)]
    flats = [weights_solar.make_flat(c.config, c.seed) for c in cells]
    assert not np.array_equal(flats[0][0]["table"], flats[1][0]["table"])
    first = [b.draw_requests(c)[0].prompt for c in cells]
    assert len(first[0]) == len(first[1]) and not np.array_equal(first[0], first[1])
    for flat, ids in zip(flats, first):      # the same vectors go in, unit by unit relabelled
        rows = np.sort(np.asarray(flat[0]["table"][ids], np.float32), axis=1)
        np.testing.assert_array_equal(
            rows, np.sort(np.asarray(flats[0][0]["table"][first[0]], np.float32), axis=1))


@pytest.mark.parametrize("name", limits_solar.PROGRAM_FAULTS)
def test_a_planted_fault_is_refused(name):
    got = toy_run(3, fault=limits_solar.program_fault(name))
    assert not got["correct"], (name, got["compared"])
    failed = [k for k, c in got["compared"].items() if not c["value"] <= c["limit"]]
    assert set(failed) & {"served_logit_gap_p99", "served_logit_gap_mean"}, (name, failed)


def test_the_control_in_the_programs_place_is_refused_at_the_files_limits(monkeypatch):
    """The fp8 control judged in the program's place (its own first choices on
    the served requests' prompts and tokens) comes out ``correct: false``
    through the run's checks at the configuration file's limits, both of the
    compared gaps over their limit."""
    from chipbench.builders import engine_solar as b

    limits = load_json(HERE / "configs" / "solar-open2.json")["serve"]["limits"]
    monkeypatch.setattr(b, "served_logit_gaps",
                        functools.partial(b.served_logit_gaps, low=True))
    got = run_cell(CELL, 3, 6.0, False, require_tpu=False,
                   config_patch=dict(TOY, serve=dict(TOY["serve"], limits=limits)),
                   traffic_patch=TOY_SIZES)
    assert not got["correct"], got["compared"]
    failed = {k for k, c in got["compared"].items() if not c["value"] <= c["limit"]}
    assert failed == {"served_logit_gap_p99", "served_logit_gap_mean"}, got["compared"]


def test_the_cells_sizes_by_hand():
    m = load_json(HERE / "configs" / "solar-open2.json")
    sv = m["serve"]
    assert pk.letters(m) == {"K": 3, "E": 4, "*": 1}
    assert pk.kda_params(m) == (4096 * 3 * 8192 + 2 * (4096 * 128 + 128 * 8192) + 4096 * 64
                                + 8192 * 4096 + 4 * 3 * 8192 + 8192 + 64 + 128)
    assert pk.expert_params(m) == 3 * 4096 * 1280 == pk.shared_params(m)
    assert pk.attention_params(m) == 3 * 4096 * 8192 + 2 * 4096 * 1024
    # 3 x 154.8 M + 126.1 M + 4 x 629.1 M + 201.3 M: 3.308 B, 6.16 GiB in bf16.
    assert pk.weight_params(m) == pytest.approx(3.308e9, rel=1e-3)
    assert pk.slot_state_bytes(m) == 3 * (3 * 3 * 8192 * 2 + 64 * 128 * 128 * 4)
    pool = pk.pool_bytes(m, sv)
    assert pool["full"] == 256 * 4096 * 2 * 8 * 128 * 2
    assert sum(pool.values()) / 2 ** 30 == pytest.approx(7.11, abs=0.01)
    # A decode step of 256 rows moves about 14.4 GB: the state and tails read
    # and written 6.67 GB, nearly every held expert (5.03), the other weights
    # and rows of contexts near 1,250.
    touched = 4 * 40 * (1 - (1 - 8 / 320) ** 256)
    state = 2 * 256 * pk.slot_state_bytes(m)
    assert state / 1e9 == pytest.approx(6.67, abs=0.01)
    step = pk.decode_step_bytes(m, touched, state, 256 * 1250)
    assert step / 1e9 == pytest.approx(14.4, abs=0.05)
    assert 1e3 * step / 819e9 == pytest.approx(17.6, abs=0.1)


def test_the_new_metrics_resolve_to_readers_of_the_facts():
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    names = [x["name"] for x in bench["per_layer"] if x.get("workloads") == [CELL]]
    assert sorted(names) == sorted([
        "serve_mfu_pct.solar", "decode_hbm_roofline_pct.solar",
        "expert_tokens_max_over_mean.solar", "held_assignment_share_pct.solar"])
    got = toy_run(4)
    cell = make_cell(CELL, 4, 6.0, False, TOY, TOY_SIZES)
    facts = dict(got["notes"], cell=cell, key_sum=1.0e4, elapsed_s=6.0,
                 peaks={"flops_bf16": 1e12, "hbm_bytes_per_s": 1e11},
                 trace={"modules": {"jit_decode_body": [1e-3]}})
    for name in names:
        reader = load_json(HERE / "layer_metrics" / f"{name}.json")
        value = resolve(reader["reader"])(facts, **reader.get("args", {}))
        assert value is not None and value > 0, name
    for name in ("serve_mfu_pct.solar", "decode_hbm_roofline_pct.solar"):
        reader = load_json(HERE / "layer_metrics" / f"{name}.json")
        assert resolve(reader["reader"])(dict(facts, peaks=None), **reader.get("args", {})) is None


def test_the_faults_in_the_references_place_read_over_the_limits():
    """``limits_solar.py --faults all`` plants in the REFERENCE each mechanism
    left out: every one reads over a limit where the program reads under
    both."""
    cell = make_cell(CELL, 4, 6.0, False, TOY, TOY_SIZES)
    out = limits_solar.readings(cell, faults=reference_solar.FAULTS)
    limits = TOY["serve"]["limits"]
    assert out["program"]["served_logit_gap_p99"] <= limits["served_logit_gap_p99"]
    assert out["program"]["served_logit_gap_mean"] <= limits["served_logit_gap_mean"]
    assert out["checked_recycled"] >= 2
    for name in reference_solar.FAULTS:
        got = out[f"fault_{name}"]
        assert (got["served_logit_gap_p99"] > limits["served_logit_gap_p99"]
                or got["served_logit_gap_mean"] > limits["served_logit_gap_mean"]), (name, got)
