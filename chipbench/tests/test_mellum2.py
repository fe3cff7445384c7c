"""The ``spmd-train-moe`` builder at toy widths on the CPU: a run of the cell
is correct and counts its experts; a run with the window left out of the
sliding layers, YaRN's frequencies or its factor left out, the renormalisation
over the selected experts left out, or the held range shifted by one expert
comes out ``correct: false`` (the reference is given the configuration as
published), as do the fp8 control and half the batch; the six new per-layer
metrics' files resolve to readers that read the builder's facts."""

import dataclasses

import pytest

from chipbench import limits_mellum2
from chipbench.common import HERE, load_json, resolve
from chipbench.run import make_cell, run_cell

CELL = "mellum2.train-4x8192"
# One period (s s s f) of 16-wide heads, a window of 16 in sequences of 128,
# YaRN from an original length of 32; 16 experts, 4 a token, this share holds
# experts 4..7.  Limits from toy readings on the CPU in bfloat16 (loss / gradient
# / routers' gradient / change): the program on six seeds <= 6.9e-5 / 0.0037 /
# 0.0086 / 0.034; the fp8 control on three >= 2.2e-4 / 0.0140 / 0.0073 / 0.047
# (it fails by the loss and by the gradient on every seed); the five faults on two
# seeds each >= 1.3e-4 / 0.046 / 0.0030 / 0.035 (the gradient norms see every one
# of them).  Those were read at the first builder's learning rate of 1e-5, where
# most bfloat16 weights move by one unit in the last place or not at all; at the
# cell's 1e-4 the program reads <= 7.8e-5 / 0.0048 / 0.0082 / 0.0093 on six
# seeds.  The change's limit lies between the program's reading and 1, which is
# what a state left unchanged reads.
TOY = {
    "hidden_size": 64, "intermediate_size": 128, "num_attention_heads": 4,
    "num_key_value_heads": 2, "head_dim": 16, "vocab_size": 256, "num_hidden_layers": 4,
    "layer_types": ["sliding_attention"] * 3 + ["full_attention"],
    "mlp_layer_types": ["sparse"] * 4, "sliding_window": 16,
    "num_experts": 4, "held_first": 4, "num_experts_per_tok": 4, "moe_intermediate_size": 32,
    "rope_parameters": {"full_attention": {"original_max_position_embeddings": 32}},
    "reduced": {"num_experts": {"published": 16}},
    "train": {"batch": 4, "seq": 128, "chunks": 2, "trace_steps": 2,
              "limits": {"loss_rel_gap": 1.2e-4, "grad_norm_gap": 0.008, "router_grad_norm_gap": 0.02,
                         "change_norm_gap": 0.15}},
}


def toy_run(seed, fault=None, trace=False):
    return run_cell(CELL, seed, 1.0, trace, require_tpu=False, config_patch=TOY, fault=fault)


def _sliding(cfg, **patch):
    return dataclasses.replace(cfg, attn_layers=tuple(
        dataclasses.replace(e, **patch) if e.window is not None else e
        for e in cfg.attn_layers))


def _yarn(cfg, **patch):
    return dataclasses.replace(cfg, attn_layers=tuple(
        e if e.yarn is None else dataclasses.replace(e, yarn=dataclasses.replace(e.yarn, **patch))
        for e in cfg.attn_layers))


# The faults of ``limits_mellum2.FAULTS``, planted in the PROGRAM's config objects.
FAULTS = {
    "no_window": lambda cfg, moe: (_sliding(cfg, window=None), moe),
    "no_yarn_frequencies": lambda cfg, moe: (_yarn(cfg, factor=1.0), moe),
    "no_yarn_factor": lambda cfg, moe: (_yarn(cfg, mscale_all_dim=1.0), moe),   # the ratio reads 1
    "no_renormalisation": lambda cfg, moe: (cfg, dataclasses.replace(moe, norm_topk=False)),
    "held_shifted": lambda cfg, moe: (
        cfg, dataclasses.replace(moe, held=(moe.held[0] + 1, moe.held[1]))),
}


@pytest.mark.parametrize("seed", [1, 2 ** 31 + 5])
def test_toy_run_is_correct_and_counts_its_experts(seed):
    got = toy_run(seed)
    assert got["correct"] and got["failed"] == 0 and got["attempted"] > 0, got["compared"]
    assert set(got["compared"]) == {"loss_rel_gap", "grad_norm_gap", "router_grad_norm_gap",
                                    "change_norm_gap", "nonfinite_losses", "compiled_in_window"}


def test_a_seed_relabels_the_draw_and_leaves_its_work_alone():
    """Two seeds give other arrays and other token ids, and the same run:
    the same losses and the same held experts' load up to rounding, because
    the vocabulary's and the hidden units' permutations are symmetries of
    the model, of the loss and of AdamW.  Another draw gives another run."""
    import jax
    import numpy as np

    from chipbench import weights_mellum2 as w

    cell = make_cell(CELL, 0, 1.0, config_patch=TOY)
    m, tr = cell.config, cell.config["train"]
    seen = []
    for seed in (6, 2 ** 31 + 7):
        flat = jax.device_get(w.make_flat(m, seed))
        ids = w.token_batches(m, cell.traffic, seed, tr["batch"], tr["seq"])
        seen.append((flat, ids))
        assert w.token_batches(m, cell.traffic, seed, tr["batch"], tr["seq"]).tolist() == ids.tolist()
    (a, ids_a), (b, ids_b) = seen
    assert not np.array_equal(ids_a, ids_b)
    assert not np.array_equal(a[0]["table"], b[0]["table"])
    assert not np.array_equal(a[1]["mlp"]["router"], b[1]["mlp"]["router"])
    # A token's embedding holds the same numbers under both labellings ...
    f32 = lambda t: np.asarray(t, np.float32)      # noqa: E731
    np.testing.assert_array_equal(np.sort(f32(a[0]["table"])[ids_a], -1),
                                  np.sort(f32(b[0]["table"])[ids_b], -1))
    # ... and so does its route: router columns are experts, not hidden units.
    np.testing.assert_array_equal(np.sort(a[1]["mlp"]["router"], 0), np.sort(b[1]["mlp"]["router"], 0))

    runs = [toy_run(seed) for seed in (6, 2 ** 31 + 7)]
    # The window's first step (its last depends on how many a second holds).
    loads = [r["notes"]["held_share_pct_first_last"][0] for r in runs]
    assert abs(loads[0] - loads[1]) <= 2e-3 * loads[0], loads     # a near-tie or two may flip
    other = run_cell(CELL, 6, 1.0, False, require_tpu=False,
                     config_patch=dict(TOY, draw={"seed": 911}))
    assert abs(other["notes"]["held_share_pct_first_last"][0] - loads[0]) > 4e-3 * loads[0]


def test_faults_cover_the_same_mechanisms_as_the_limits_script():
    assert set(FAULTS) == set(limits_mellum2.FAULTS)


@pytest.mark.parametrize("name", sorted(FAULTS))
def test_a_left_out_mechanism_is_not_correct(name):
    def fault(point, value):
        return FAULTS[name](*value) if point == "program_config" else value

    got = toy_run(3, fault=fault)
    assert not got["correct"], (name, got["compared"])


def test_control_and_half_batch_fail_against_the_reference():
    cell = make_cell(CELL, 4, 1.0, config_patch=TOY)
    got = limits_mellum2.train_readings(cell, faults=False)
    limits = TOY["train"]["limits"]
    for name in ("control_fp8", "fault_half_batch"):
        assert any(got[name][k] > limits[k] for k in limits), (name, got[name])


def test_new_metrics_read_the_builders_facts():
    from chipbench.builders import spmd_train_moe as b
    from chipbench.peaks import PEAKS

    cell = make_cell(CELL, 5, 1.0, config_patch=TOY)
    out = b.run(cell)
    ops = {"flash_fwd.1 (bf16[4,128,16],f32[4,128,1]) tpu_custom_call/3": 0.5,
           "flash_bwd_dq.2 bf16[4,128,16] tpu_custom_call/6": 0.7,
           "flash_bwd_dkv.3 (f32[4,128,16],f32[4,128,16]) tpu_custom_call/6": 0.9,
           "ragged-dot-none.4 bf16[512,32] tpu_custom_call/7": 0.3}
    facts = dict(out.facts, cell=cell, peaks=PEAKS["TPU v5e"], end_to_end=out.end_to_end,
                 moe_traced_rows_per_product=100.0,
                 trace={"op_seconds": ops, "op_calls": {k: 4 for k in ops}})
    values = {}
    for spec in load_json(HERE.parent / "BENCHMARK.json")["per_layer"]:
        if spec["name"].endswith(".mellum2"):
            assert spec["workloads"] == [CELL]
            reader = load_json(HERE / "layer_metrics" / f"{spec['name']}.json")
            values[spec["name"]] = resolve(reader["reader"])(facts, **reader.get("args", {}))
    assert len(values) == 6 and all(v is not None and v > 0 for v in values.values()), values
    assert 15.0 < values["held_assignment_share_pct.mellum2"] < 35.0      # 4 of 16 are held
    assert values["expert_tokens_max_over_mean.mellum2"] >= 1.0
    # A program whose spans carry no counts reads as nothing.
    less = {k: v for k, v in facts.items() if not k.startswith("moe_")}
    for name in ("train_mfu_pct.mellum2", "held_assignment_share_pct.mellum2",
                 "expert_tokens_max_over_mean.mellum2", "expert_dot_roofline.mellum2"):
        reader = load_json(HERE / "layer_metrics" / f"{name}.json")
        assert resolve(reader["reader"])(less, **reader.get("args", {})) is None, name


def test_closed_forms_at_the_published_sizes():
    """ISSUE 32's arithmetic: parameters a layer, FLOPs a token, a window
    call's share of a full call's."""
    from chipbench import peaks as pk
    from chipbench import peaks_mellum2 as pm

    m = load_json(HERE / "configs" / "mellum2.json")
    assert pm.attention_params(m) == 21_233_664
    assert pm.expert_params(m) == 6_193_152
    assert pm.router_params(m) == 2304 * 64
    assert pm.kind_shares(m) == {"full_attention": 0.25, "sliding_attention": 0.75}
    # Two held assignments a token and layer is the uniform share (8 x 16 / 64).
    per_token = pm.train_flops(m, 8192, 1, 2 * 8)
    assert 2.60e9 < per_token < 2.70e9                 # the issue counts 2.646 GFLOP
    window = pk.flash_call(pm.as_kind(m, "sliding_attention"), 1, 8192, False)["flops"]
    full = pk.flash_call(pm.as_kind(m, "full_attention"), 1, 8192, False)["flops"]
    assert 0.22 < window / full < 0.24                 # mean keys 960 against 4096.5
