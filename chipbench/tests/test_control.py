"""The control comes out as not correct: the reference put in the program's
place at fp8 precision fails at least one of each cell's numbers, at a size
a test run can hold.  (On the chip, at the cells' own sizes:
``chipbench/limits.py``; readings in PERF.md section 2.)"""

import pytest

import toy
from chipbench import limits
from chipbench.run import make_cell


@pytest.mark.parametrize("seed", [1, 2, 3])
def test_train_control_and_half_batch_fail(seed):
    cell = make_cell(toy.TRAIN, seed, 1.0, config_patch=toy.TOY_CONFIG)
    got = limits.readings(cell)
    lim = toy.TOY_CONFIG["train"]["limits"]
    for name in ("control_fp8", "fault_half_batch"):
        assert any(got[name][k] > lim[k] for k in lim), (name, got[name])


@pytest.mark.parametrize("seed", [1, 2, 3])
def test_serve_control_fails_and_program_passes(seed):
    cell = make_cell(toy.cell_of(toy.STEADY), seed, 4.0, config_patch=toy.TOY_CONFIG,
                     traffic_patch=toy.traffic_patch(toy.STEADY))
    got = limits.readings(cell)
    lim = toy.TOY_CONFIG["serve"]["limits"]["served_logit_gap"]
    assert got["checked_tokens"] > 0
    assert got["control_fp8"]["served_logit_gap"] > lim
    assert got["program"]["served_logit_gap"] <= lim
