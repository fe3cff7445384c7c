"""The ``engine-nemotron`` builder at toy widths on the CPU: a run of the cell
is correct, checks requests served from recycled slots and counts the state
and the experts; every planted fault comes out ``correct: false``; a seed
relabels the draw and leaves its work alone; ``peaks_nemotron``'s sizes
against a count by hand; the new per-layer metrics' files resolve to readers
that read the builder's facts; the traffic is ``conv-backlog``'s, cycled."""

import json

import numpy as np
import pytest

from chipbench import limits_nemotron, peaks_nemotron as pk, traffic
from chipbench.common import HERE, ROOT, load_json, resolve
from chipbench.run import make_cell, run_cell

CELL = "nemotron3-nano.serve-deep-backlog"
# The file's pattern (EMEMEMEM*) at toy widths: 8 mixer heads of 16 in 2 groups
# of state 16, 4 attention heads of 32 on 2 KV heads; 16 experts, 6 a token,
# this share holds experts 4..11.  float32 on the CPU: the program's gaps read
# 0 on three seeds (every served token is the reference's best); the four
# planted faults read 2.1 to 3.8 (p99) and 0.45 to 1.33 (mean) on seed 3, the
# weakest no_bias: the limits stand three decades under them.
TOY = {
    "hidden_size": 64, "num_attention_heads": 4, "num_key_value_heads": 2, "head_dim": 32,
    "vocab_size": 256, "mamba_num_heads": 8, "mamba_head_dim": 16, "n_groups": 2,
    "ssm_state_size": 16, "moe_intermediate_size": 32, "moe_shared_expert_intermediate_size": 64,
    "n_routed_experts": 8, "held_first": 4, "chunk_size": 8, "torch_dtype": "float32",
    "draw": {"router_bias_std": 0.1},
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
    # A slot: 4 mixer layers x (a tail of 3 x 192 and a float32 state of 8 x
    # 16 x 16), the attention layer's K and V at 128 rows, in the closed
    # form's served type (bfloat16) whatever the toy computes in.
    slot = 4 * (3 * 192 * 2 + 8 * 16 * 16 * 4)
    assert notes["kv_pool_bytes_by_kind"] == {"full": 4 * 128 * 2 * 2 * 32 * 2, "state": 4 * slot}
    live = notes["kv_live_bytes_by_kind"]
    assert 0 < live["state"] <= 4 * slot and notes["kv_live_bytes"] == pytest.approx(
        live["full"] + live["state"])
    # Every decoding row reads and writes its slot's state; the engine's own
    # counter says so in the served type's bytes (float32 here, as the pool).
    assert notes["decode_state_bytes"] > 0 and notes["state_zeroed_slots"] == notes["admitted"]
    assert 0 < notes["decode_experts_touched"] <= 4 * 8
    assert 0 < notes["moe_held_assignments"] < notes["moe_routed_assignments"]


def test_a_seed_relabels_the_draw_and_leaves_its_work_alone():
    from chipbench import weights_nemotron
    from chipbench.builders import engine_nemotron as b

    cells = [make_cell(CELL, seed, 6.0, False, TOY, TOY_SIZES) for seed in (6, 2 ** 31 + 7)]
    flats = [weights_nemotron.make_flat(c.config, c.seed) for c in cells]
    assert not np.array_equal(flats[0][0]["table"], flats[1][0]["table"])
    first = [b.draw_requests(c)[0].prompt for c in cells]
    assert len(first[0]) == len(first[1]) and not np.array_equal(first[0], first[1])
    for flat, ids in zip(flats, first):      # the same vectors go in, unit by unit relabelled
        rows = np.sort(np.asarray(flat[0]["table"][ids], np.float32), axis=1)
        np.testing.assert_array_equal(
            rows, np.sort(np.asarray(flats[0][0]["table"][first[0]], np.float32), axis=1))


@pytest.mark.parametrize("name", limits_nemotron.PROGRAM_FAULTS)
def test_a_planted_fault_is_refused(name):
    got = toy_run(3, fault=limits_nemotron.program_fault(name))
    assert not got["correct"], (name, got["compared"])
    failed = [k for k, c in got["compared"].items() if not c["value"] <= c["limit"]]
    assert set(failed) & {"served_logit_gap_p99", "served_logit_gap_mean"}, (name, failed)


def test_the_cells_sizes_by_hand():
    m = load_json(HERE / "configs" / "nemotron3-nano.json")
    sv = m["serve"]
    assert pk.letters(m) == {"M": 4, "E": 4, "*": 1}
    assert pk.mixer_params(m) == 2688 * 10304 + 4096 * 2688 + 5 * 6144 + 3 * 64 + 4096
    assert pk.expert_params(m) == 2 * 2688 * 1856 and pk.shared_params(m) == 2 * 2688 * 3712
    assert pk.attention_params(m) == 2 * 2688 * 4096 + 2 * 2688 * 256
    assert pk.weight_params(m) == pytest.approx(3.167e9, rel=2e-3)
    assert pk.slot_state_bytes(m) == 4 * (3 * 6144 * 2 + 64 * 64 * 128 * 4)
    pool = pk.pool_bytes(m, sv)
    assert pool["full"] == 512 * 4096 * 1024 and pool["state"] == 512 * pk.slot_state_bytes(m)
    assert sum(pool.values()) / 2 ** 30 == pytest.approx(6.07, abs=0.01)
    # A decode step of 512 rows moves about 15.5 GB: the state 8.74 GB (8.59
    # of float32 states, 0.15 of bfloat16 tails), nearly every held expert
    # (5.1), the other weights (0.87) and rows of contexts near 1200 (0.8).
    touched = 4 * 64 * (1 - (1 - 6 / 128) ** 512)        # every held expert, near enough
    state = 2 * 512 * pk.slot_state_bytes(m)
    rows = 512 * 1536                  # contexts near 1,200 in blocks of 512
    assert state / 1e9 == pytest.approx(8.74, abs=0.01)
    assert pk.decode_step_bytes(m, touched, state, rows) / 1e9 == pytest.approx(15.5, abs=0.1)


def test_the_new_metrics_resolve_to_readers_of_the_facts():
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    names = [x["name"] for x in bench["per_layer"] if x.get("workloads") == [CELL]]
    assert sorted(names) == sorted([
        "serve_mfu_pct.nemotron", "decode_hbm_roofline_pct.nemotron",
        "expert_tokens_max_over_mean.nemotron", "held_assignment_share_pct.nemotron"])
    got = toy_run(4)
    cell = make_cell(CELL, 4, 6.0, False, TOY, TOY_SIZES)
    facts = dict(got["notes"], cell=cell, processed_tokens=got["notes"]["processed_tokens"],
                 key_sum=1.0e4, elapsed_s=6.0, peaks={"flops_bf16": 1e12, "hbm_bytes_per_s": 1e11},
                 trace={"modules": {"jit_decode_body": [1e-3]}})
    for name in names:
        reader = load_json(HERE / "layer_metrics" / f"{name}.json")
        value = resolve(reader["reader"])(facts, **reader.get("args", {}))
        assert value is not None and value > 0, name
    # No decode program in the trace, no peaks: nothing to read, no error.
    for name in ("serve_mfu_pct.nemotron", "decode_hbm_roofline_pct.nemotron"):
        reader = load_json(HERE / "layer_metrics" / f"{name}.json")
        assert resolve(reader["reader"])(dict(facts, peaks=None), **reader.get("args", {})) is None


def test_the_deep_backlog_is_conv_backlog_cycled():
    deep = load_json(HERE / "traffic" / "conv-backlog-deep.json")
    base = load_json(HERE / "traffic" / "conv-backlog.json")
    assert deep["cycles"] == 32 and {k: v for k, v in deep.items()
                                     if k not in ("cycles", "why", "clips")} == {
        k: v for k, v in base.items() if k not in ("cycles", "why", "clips")}
    assert traffic.size_multiset(deep, 200) == traffic.size_multiset(base, 200)
    assert len(traffic.backlog(deep, 1, 44.0, 65536)) == 6400


def test_the_faults_in_the_references_place_read_over_the_limits():
    """``limits_nemotron.py --faults all`` plants in the REFERENCE each mechanism
    left out: every one reads over a limit where the program reads under
    both.  Not the state kept in bfloat16: at toy widths it moves a logit by
    3e-4, under the margin between any position's best two, so it changes no
    served token (``tests/test_nemotron_serving.py`` holds it by the
    logits themselves)."""
    from chipbench import reference_nemotron

    cell = make_cell(CELL, 4, 6.0, False, TOY, TOY_SIZES)
    out = limits_nemotron.readings(cell, faults=reference_nemotron.FAULTS)
    limits = TOY["serve"]["limits"]
    assert out["program"]["served_logit_gap_p99"] <= limits["served_logit_gap_p99"]
    assert out["program"]["served_logit_gap_mean"] <= limits["served_logit_gap_mean"]
    assert out["checked_recycled"] >= 2
    for name in set(reference_nemotron.FAULTS) - {"state_bf16"}:
        got = out[f"fault_{name}"]
        assert (got["served_logit_gap_p99"] > limits["served_logit_gap_p99"]
                or got["served_logit_gap_mean"] > limits["served_logit_gap_mean"]), (name, got)
