"""The ``engine-latent-moe`` builder at toy widths on the CPU: a run of the
cell is correct; a run with the shared expert left out, the route scale left
out, or a softmax router in the sigmoid's place, comes out ``correct: false``
(the reference is given the configuration as published), as does the fp8
control; and the four new per-layer metrics' files resolve to readers that
read the builder's facts."""

import dataclasses

import pytest

from chipbench import limits_latent_moe
from chipbench.common import HERE, load_json, resolve
from chipbench.run import make_cell, run_cell

CELL = "axk1.serve-backlog"
# 16 experts, 4 a token, this share holds experts 4..7; one dense block and
# two expert blocks.  Limit from toy readings on the CPU (float32): the
# program 0.0 on six seeds, the faults >= 0.26, the control >= 0.12.
TOY = {
    "hidden_size": 64, "intermediate_size": 128, "num_attention_heads": 4,
    "num_key_value_heads": 4, "q_lora_rank": 24, "kv_lora_rank": 16, "qk_nope_head_dim": 8,
    "qk_rope_head_dim": 4, "v_head_dim": 8, "moe_intermediate_size": 32, "vocab_size": 256,
    "num_hidden_layers": 3, "n_routed_experts": 4, "held_first": 4, "num_experts_per_tok": 4,
    "torch_dtype": "float32", "reduced": {"n_routed_experts": {"published": 16}},
    "serve": {"num_slots": 4, "max_len": 256, "prefill_chunk": 8,
              "limits": {"served_logit_gap_p99": 0.05, "served_logit_gap_mean": 0.005}},
}
SIZES = {
    "requests": 20, "trace_seconds": 1.0, "max_total": 116,
    "prompt_len": {"median": 24, "sigma": 0.9, "min": 4, "max": 100},
    "new_tokens": {"median": 8, "sigma": 0.7, "min": 2, "max": 16},
}
FAULTS = {"no_shared_expert": {"n_shared": 0}, "no_route_scale": {"route_scale": 1.0},
          "softmax_router": {"scoring": "softmax"}}


def toy_run(seed, fault=None, **kwargs):
    return run_cell(CELL, seed, 3.0, False, require_tpu=False, config_patch=TOY,
                    traffic_patch=SIZES, fault=fault, **kwargs)


@pytest.mark.parametrize("seed", [1, 2 ** 31 + 5])
def test_toy_run_is_correct_and_counts_its_experts(seed):
    got = toy_run(seed)
    assert got["correct"] and got["failed"] == 0 and got["attempted"] > 0
    assert set(got["compared"]) == {"served_logit_gap_p99", "served_logit_gap_mean", "compiled_in_window"}
    notes = got["notes"]
    share = notes["moe_held_assignments"] / notes["moe_routed_assignments"]
    assert 0.15 < share < 0.35              # 4 of 16 experts are held
    assert notes["kv_pool_bytes"] == 4 * 256 * (16 + 4) * 2 * 3
    assert notes["moe_prefill_steps"] == notes["prefill_steps"]
    assert notes["moe_decode_steps"] == notes["decode_steps"]


@pytest.mark.parametrize("name", sorted(FAULTS))
def test_a_left_out_mechanism_is_not_correct(name):
    def fault(point, value):
        return dataclasses.replace(value, **FAULTS[name]) if point == "moe_config" else value

    got = toy_run(3, fault=fault)
    gap = got["compared"]["served_logit_gap_p99"]
    assert not got["correct"] and gap["value"] > gap["limit"], (name, gap)


def test_control_fails_and_program_passes():
    cell = make_cell(CELL, 4, 3.0, config_patch=TOY, traffic_patch=SIZES)
    got = limits_latent_moe.readings(cell)
    assert got["checked_tokens"] > 0 and got["compiled_in_window"] == 0
    for name, limit in TOY["serve"]["limits"].items():
        assert got["control_fp8"][name] > limit >= got["program"][name], name


def test_new_metrics_read_the_builders_facts():
    from chipbench.builders import engine_latent_moe as b
    from chipbench.peaks import PEAKS

    cell = make_cell(CELL, 5, 2.0, config_patch=TOY, traffic_patch=SIZES)
    facts = dict(b.run(cell).facts, cell=cell, peaks=PEAKS["TPU v5e"],
                 trace={"modules": {"jit_decode_body(7)": [0.002, 0.004]}})
    values = {}
    for spec in load_json(HERE.parent / "BENCHMARK.json")["per_layer"]:
        if spec["name"].endswith(".axk1"):
            assert spec["workloads"] == [CELL]
            reader = load_json(HERE / "layer_metrics" / f"{spec['name']}.json")
            values[spec["name"]] = resolve(reader["reader"])(facts, **reader.get("args", {}))
    assert len(values) == 4 and all(v is not None and v > 0 for v in values.values()), values
    assert 15.0 < values["held_assignment_share_pct.axk1"] < 35.0
    assert values["expert_tokens_max_over_mean.axk1"] >= 1.0
    # A program without the counters, or a trace without the decode program,
    # reads as nothing.
    for name, without in (("serve_mfu_pct.axk1", "moe_held_assignments"),
                          ("held_assignment_share_pct.axk1", "moe_routed_assignments")):
        reader = load_json(HERE / "layer_metrics" / f"{name}.json")
        less = {k: v for k, v in facts.items() if k != without}
        assert resolve(reader["reader"])(less, **reader.get("args", {})) is None
    reader = load_json(HERE / "layer_metrics" / "decode_hbm_roofline_pct.axk1.json")
    assert resolve(reader["reader"])(dict(facts, trace={"modules": {}})) is None


def test_closed_forms_at_the_published_sizes():
    """ISSUE 28's arithmetic: parameters a layer, the cache row, the bytes a
    decode step cannot avoid."""
    from chipbench import peaks_latent_moe as pk

    m = load_json(HERE / "configs" / "axk1.json")
    assert pk.attention_params(m) == 101_122_048
    assert pk.expert_params(m) == 44_040_192
    assert pk.dense_ff_params(m) == 396_361_728
    assert pk.cache_row_bytes(m) == 1152 * 6
    weights = 2 * (6 * 101_122_048 + 396_361_728 + 5 * 13 * 44_040_192 + 7168 * 20480)
    assert pk.decode_weight_bytes(m) == weights + 4 * 5 * 7168 * 192
    assert pk.attention_flops_per_pair(m) == 2 * 64 * (192 + 128)
