"""The ``engine-trinity`` builder at toy widths on the CPU: a run of the cell
is correct, wraps its rings under what it compares and counts rows and experts
by kind; every planted fault comes out ``correct: false``; the mixed traffic's
multiset is fixed by its file; ``peaks_trinity``'s FLOPs and bytes against a
count by hand; the new per-layer metrics' files resolve to readers that read
the builder's facts."""

import json

import numpy as np
import pytest

from chipbench import limits_trinity, peaks_trinity as pk, traffic_mixed
from chipbench.common import HERE, ROOT, load_json, resolve
from chipbench.run import run_cell
from torchgpipe_tpu.models import kv_cache

CELL = "trinity-large.serve-mixed-backlog"
# A dense layer and one period (s s s f s) of 32-wide heads, a window of 16 in
# contexts to 128; 16 experts, 4 a token, this share holds experts 4..7.
# float32 on the CPU (it has no bfloat16 product of every form): the program's
# gaps read 0 on four seeds (every served token is the reference's best), the
# weakest of the eight faults (no_bias) 0.038 (p99) and 0.0037 (mean), the
# others 0.075 to 2.9 and 0.006 to 0.78 (seed 3): the limits stand a decade
# under the weakest.
TOY = {
    "hidden_size": 64, "intermediate_size": 128, "num_attention_heads": 4,
    "num_key_value_heads": 2, "head_dim": 32, "vocab_size": 256, "num_hidden_layers": 5,
    "sliding_window": 16, "num_experts": 4, "held_first": 4, "moe_intermediate_size": 32,
    "torch_dtype": "float32", "draw": {"router_bias_std": 0.1},
    "reduced": {"num_experts": {"published": 16}},
    "serve": {"num_slots": 4, "max_len": 128, "prefill_chunk": 8,
              "limits": {"served_logit_gap_p99": 1e-3, "served_logit_gap_mean": 1e-4}},
}
TOY_SIZES = {
    "requests": 20, "trace_seconds": 1.0, "max_total": 128,
    "classes": [
        {"name": "short", "share": 0.7,
         "prompt_len": {"median": 12, "sigma": 0.5, "min": 4, "max": 40},
         "new_tokens": {"median": 5, "sigma": 0.7, "min": 2, "max": 12}},
        {"name": "long", "share": 0.3,
         "prompt_len": {"median": 48, "sigma": 0.6, "min": 8, "max": 128},
         "new_tokens": {"median": 6, "sigma": 0.7, "min": 2, "max": 12}},
    ],
}


@pytest.fixture(autouse=True)
def small_rings(monkeypatch):
    """Rings of the toy window: rounded to 8 rows, not to the kernel's 512,
    in the program and in the benchmark's own arithmetic alike."""
    monkeypatch.setattr(kv_cache, "RING_GRANULE", 8)
    monkeypatch.setattr(pk, "RING_GRANULE", 8)


def toy_run(seed, fault=None, trace=False, seconds=6.0):
    return run_cell(CELL, seed, seconds, trace, require_tpu=False, config_patch=TOY,
                    traffic_patch=TOY_SIZES, fault=fault)


@pytest.mark.parametrize("seed", [1, 2 ** 31 + 5])
def test_toy_run_is_correct_and_counts_by_kind(seed):
    got = toy_run(seed)
    assert got["correct"] and got["failed"] == 0 and got["attempted"] > 0, got["compared"]
    assert set(got["compared"]) == {"served_logit_gap_p99", "served_logit_gap_mean",
                                    "ring_wrapped_missing", "compiled_in_window"}
    notes = got["notes"]
    assert sum(c > 16 + 8 for c in notes["checked_contexts"]) >= 2      # rings wrapped
    # A slot: 4 window layers x a ring of 24 rows, one full layer x 128.
    row = 2 * 2 * 32 * 2
    assert notes["kv_pool_bytes_by_kind"] == {"window": 4 * 4 * 24 * row, "full": 4 * 128 * row}
    live = notes["kv_live_bytes_by_kind"]
    assert 0 < live["window"] <= notes["kv_pool_bytes_by_kind"]["window"]
    assert notes["kv_live_bytes"] == pytest.approx(live["window"] + live["full"])
    # Off TPU the dense path reads every row of a layer: capacity, by kind.
    read = notes["attend_rows_read"]
    assert read == notes["attend_rows_capacity"] and read["window"] * 128 == read["full"] * 24
    assert 0 < notes["moe_held_assignments"] < notes["moe_routed_assignments"]


def test_a_seed_relabels_the_draw_and_leaves_its_work_alone():
    """Two seeds give other arrays and other token ids, and the same run:
    the same steps, the same tokens out, the same experts' load."""
    from chipbench import weights_trinity
    from chipbench.builders import engine_trinity as b
    from chipbench.run import make_cell

    cells = [make_cell(CELL, seed, 6.0, False, TOY, TOY_SIZES) for seed in (6, 2 ** 31 + 7)]
    flats = [weights_trinity.make_flat(c.config, c.seed) for c in cells]
    assert not np.array_equal(flats[0][0]["table"], flats[1][0]["table"])
    first = [b.draw_requests(c)[0].prompt for c in cells]
    assert len(first[0]) == len(first[1]) and not np.array_equal(first[0], first[1])
    for flat, ids in zip(flats, first):      # the same vectors go in, unit by unit relabelled
        rows = np.sort(np.asarray(flat[0]["table"][ids], np.float32), axis=1)
        np.testing.assert_array_equal(
            rows, np.sort(np.asarray(flats[0][0]["table"][first[0]], np.float32), axis=1))
    # Each run times its own 6 s, so count the work of the requests both finished.
    notes = [toy_run(c.seed)["notes"] for c in cells]
    share = [n["moe_held_assignments"] / n["moe_routed_assignments"] for n in notes]
    assert share[0] == pytest.approx(share[1], rel=0.02)


@pytest.mark.parametrize("name", limits_trinity.PROGRAM_FAULTS)
def test_a_planted_fault_is_refused(name):
    got = toy_run(3, fault=limits_trinity.program_fault(name))
    assert not got["correct"], (name, got["compared"])
    failed = [k for k, c in got["compared"].items() if not c["value"] <= c["limit"]]
    assert set(failed) & {"served_logit_gap_p99", "served_logit_gap_mean"}, (name, failed)


def test_the_faults_in_the_references_place_read_as_the_programs_do():
    """``limits_trinity.py --faults 1`` plants in the REFERENCE what the test
    above plants in the program: every one of them reads over both limits."""
    from chipbench.run import make_cell

    cell = make_cell(CELL, 4, 6.0, False, TOY, TOY_SIZES)
    out = limits_trinity.readings(cell, faults=True)
    limits = TOY["serve"]["limits"]
    assert out["program"]["served_logit_gap_p99"] <= limits["served_logit_gap_p99"]
    for name in limits_trinity.reference_trinity.FAULTS:
        got = out[f"fault_{name}"]
        assert (got["served_logit_gap_p99"] > limits["served_logit_gap_p99"]
                or got["served_logit_gap_mean"] > limits["served_logit_gap_mean"]), (name, got)


def test_traced_run_reports_every_per_layer_metric_it_can_on_a_cpu():
    """A traced toy run: the CPU's trace holds no device operation, so the
    reduction is handed a recorded one; the facts are the builder's own."""
    bench = load_json(ROOT / "BENCHMARK.json")
    mine = [x for x in bench["per_layer"] if CELL in x.get("workloads", [CELL])]
    names = {x["name"] for x in mine}
    assert {"serve_mfu_pct.trinity", "flash_decode_roofline_pct.trinity",
            "attend_rows_read_share_pct.trinity", "expert_tokens_max_over_mean.trinity",
            "held_assignment_share_pct.trinity", "kv_live_gib.backlog",
            "hbm_peak_gib.backlog"} <= names
    assert "engine_launch_gap_ms.backlog" not in names
    from chipbench.builders import engine_trinity as b
    from chipbench.run import make_cell

    cell = make_cell(CELL, 5, 6.0, False, TOY, TOY_SIZES)
    w = b.window(cell)
    facts = dict(w["got"]["facts"], cell=cell, end_to_end=w["got"]["end_to_end"],
                 memory_peak_bytes=0, peaks={"flops_bf16": 197e12, "hbm_bytes_per_s": 819e9},
                 trace={"op_seconds": {"flash_decode.3 f32[4,1,12,128] tpu_custom_call/10": 1e-3},
                        "op_calls": {}, "modules": {}, "busy_s": 1.0, "window_s": 2.0})
    facts["traced_rows_read"] = {"window": 24 * 40, "full": 128 * 10}
    for name in ("serve_mfu_pct.trinity", "flash_decode_roofline_pct.trinity",
                 "attend_rows_read_share_pct.trinity", "expert_tokens_max_over_mean.trinity",
                 "held_assignment_share_pct.trinity"):
        reader = load_json(HERE / "layer_metrics" / f"{name}.json")
        value = resolve(reader["reader"])(facts, **reader.get("args", {}))
        assert value is not None and value > 0, name
    share = resolve("layers_trinity:rows_read_share")(facts)
    assert share == pytest.approx(100.0 * (4 * 24 + 128) / (5 * 128))     # dense path: capacity
    bytes_ = (4 * 24 * 40 + 128 * 10) * 2 * 2 * 32 * 2
    assert resolve("layers_trinity:flash_decode_roofline")(facts) == pytest.approx(
        100.0 * bytes_ / 819e9 / 1e-3)
    # A program without the counters by kind: the readers find nothing.
    bare = {k: v for k, v in facts.items()
            if k not in ("traced_rows_read", "attend_rows_read", "pairs_by_kind")}
    for reader in ("serve_mfu", "flash_decode_roofline", "rows_read_share"):
        assert resolve(f"layers_trinity:{reader}")(bare) is None


# --- the traffic ----------------------------------------------------------- #


def test_the_mixed_multiset_is_fixed_by_its_file():
    t = load_json(HERE / "traffic" / "mixed-backlog.json")
    short, long_ = traffic_mixed.class_multisets(t)
    assert (len(short), len(long_)) == (140, 60) == tuple(traffic_mixed.class_counts(t))
    # The short class is conv-backlog.json's law, letter for letter.
    conv = load_json(HERE / "traffic" / "conv-backlog.json")
    assert t["classes"][0]["prompt_len"] == conv["prompt_len"]
    assert t["classes"][0]["new_tokens"] == conv["new_tokens"]
    assert (t["pairing_seed"], t["order_seed"]) == (conv["pairing_seed"], conv["order_seed"])
    prompts, outputs = np.array(short).T
    assert (np.median(prompts), np.median(outputs)) == (1020, 129)
    assert (prompts.max(), outputs.max()) == (3915, 1901)
    prompts, outputs = np.array(long_).T
    assert (np.median(prompts), np.median(outputs)) == (4604, 110)
    assert int(prompts.mean()) == 6264 and (prompts + outputs).max() == 16384
    # The one clip, and the count it touches.
    assert traffic_mixed.clipped(t) == [0, 6] and "6 of the 60 long" in t["clips"]
    assert sum(p + o == t["max_total"] for p, o in long_) == 6
    assert sum(p + o > 4096 + 32 for p, o in short + long_) == 35
    reqs = traffic_mixed.backlog_mixed(t, 9, 44.0, 25024)
    assert len(reqs) == 800 and all(r.due_s == 0 for r in reqs)
    sizes = sorted((len(r.prompt), r.new_tokens) for r in reqs)
    assert sizes == sorted((short + long_) * 4)
    again = traffic_mixed.backlog_mixed(t, 10, 44.0, 25024)
    assert [(len(r.prompt), r.new_tokens) for r in reqs] == [
        (len(r.prompt), r.new_tokens) for r in again]               # the file's order
    assert not np.array_equal(reqs[0].prompt, again[0].prompt)      # the seed's ids
    with pytest.raises(ValueError, match="whole classes"):
        traffic_mixed.class_counts(dict(t, requests=7))


# --- the closed forms ------------------------------------------------------ #


def test_peaks_against_a_count_by_hand(monkeypatch):
    monkeypatch.setattr(pk, "RING_GRANULE", 512)
    m = load_json(HERE / "configs" / "trinity-large.json")
    sv = dict(m["serve"], num_slots=40, prefill_chunk=32)
    attn = 3072 * 6144 * 3 + 3072 * 1024 * 2                       # q, gate, o; k, v
    assert pk.attention_params(m) == attn == 62_914_560
    assert pk.expert_params(m) == 3 * 3072 * 3072 == 28_311_552
    assert pk.dense_ff_params(m) == 3 * 3072 * 12288
    assert pk.router_params(m) == 3072 * 256
    layer = attn + 33 * 28_311_552 + 3072 * 256                    # 32 held + the shared
    total = attn + 3 * 3072 * 12288 + 4 * layer + 2 * 3072 * 25024
    assert pk.weight_params(m) == total
    assert round(total * 2 / 2 ** 30, 2) == 8.05                   # GiB in bf16
    assert pk.layer_kinds(m) == {"window": 4, "full": 1}
    assert pk.cache_row_bytes(m) == 2 * 8 * 128 * 2 == 4096
    assert pk.ring_rows(m, 32) == 4608 and pk.ring_rows(m, 1) == 4096 and (
        pk.ring_rows(m, 128) == 4608)
    assert pk.slot_rows(m, sv) == {"window": 4608, "full": 16384}
    pool = pk.pool_bytes(m, sv)
    assert pool == {"window": 40 * 4 * 4608 * 4096, "full": 40 * 16384 * 4096}
    assert round(sum(pool.values()) / 2 ** 30, 2) == 5.31
    assert pk.one_length_slots(m, sv) == 17                        # 40 slots' bytes at one length
    assert pk.live_bytes(m, {"window": 10.0, "full": 3.0}) == {
        "window": 4 * 10 * 4096.0, "full": 3 * 4096.0}
    # Pairs: causal under the window, a band of 4096 past it.
    assert pk.pairs(3) == 6 and pk.pairs(3, 4096) == 6
    assert pk.pairs(5000, 4096) == 4096 * 4097 / 2 + 904 * 4096
    pairs = {"window": pk.pairs(5000, 4096), "full": pk.pairs(5000)}
    per_token = 5 * attn + 3 * 3072 * 12288 + 4 * (3072 * 256 + 28_311_552)
    want = (2.0 * per_token * 5000 + 2.0 * 28_311_552 * 700
            + 4.0 * 48 * 128 * (4 * pairs["window"] + pairs["full"])
            + 2.0 * 3072 * 25024 * 10)
    assert pk.serve_flops(m, 5000, 10, pairs, 700) == want
    assert pk.decode_kernel_bytes(m, {"window": 512.0, "full": 1024.0}) == (
        (4 * 512 + 1024) * 4096.0)


def test_the_configuration_file_states_its_cut():
    m = load_json(HERE / "configs" / "trinity-large.json")
    row = next(json.loads(line) for line in open(
        "/opt/skills/guides/model-configs/architectures.jsonl")
        if json.loads(line)["name"] == "Trinity-Large-Preview")
    assert m["source"] == row["source_url"]
    changed = {k for k, v in row["config"].items() if m[k] != v}
    assert changed == set(m["reduced"]) == {
        "num_hidden_layers", "num_dense_layers", "layer_types", "num_experts", "vocab_size"}
    for key, cut in m["reduced"].items():
        assert cut["here"] == m[key] and cut["why"]
        if key != "layer_types":
            assert cut["published"] == row["config"][key]
    assert m["layer_types"] == row["config"]["layer_types"][:5]
    for key in ("attention_gate", "qk_norm", "full_layers_unrotated", "sandwich_norm",
                "embedding_scale", "route_norm_epsilon", "rotary_layout", "post_norm_gains",
                "load_balance_coeff", "router_bias"):
        assert m["assumed"][key]
    assert "EP-8" in m["deployment"] and m["serve"]["builder"] == "engine-trinity"
    assert m["serve"]["num_slots"] % 8 == 0 and m["serve"]["max_len"] == 16384
