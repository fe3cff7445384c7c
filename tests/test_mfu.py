"""MFU reporting helpers (``benchmarks/common.py``): analytic model FLOPs
from XLA HLO cost analysis + the chip-gated ``MFU |`` line every speed
driver emits.  No reference counterpart (the reference publishes
wall-clock only, reference: docs/benchmarks.rst); this is the
measurement-honesty layer around the hardware numbers."""

import jax
import jax.numpy as jnp

import torchgpipe_tpu.utils.hw as hw
from benchmarks.common import (
    analytic_flops,
    print_mfu,
    sequential_step_flops,
)


def test_analytic_flops_counts_matmul():
    def step(a, b):
        return a @ b

    a = jnp.zeros((64, 64), jnp.float32)
    flops = analytic_flops(step, a, a)
    # One 64x64x64 matmul is 2*64^3 FLOPs; cost analysis may fold a bit
    # but must see at least the one matmul's order of magnitude.
    assert flops is not None
    assert flops >= 64 ** 3


def test_analytic_flops_accepts_shape_structs():
    def step(a):
        return jnp.sum(a * a)

    spec = jax.ShapeDtypeStruct((8, 8), jnp.float32)
    assert analytic_flops(step, spec) is not None


def test_print_mfu_line_on_known_chip(monkeypatch, capsys):
    monkeypatch.setattr(hw, "chip_peak_bf16_flops", lambda d: 1e12)
    print_mfu(1e9, tput=100.0, batch=10, label="lab")
    out = capsys.readouterr().out
    assert "MFU" in out and "lab" in out and "1.00%" in out


def test_print_mfu_silent_on_unknown_chip(monkeypatch, capsys):
    """Host-CPU runs print nothing AND never invoke the (potentially
    expensive) lazy FLOPs thunk."""
    monkeypatch.setattr(hw, "chip_peak_bf16_flops", lambda d: None)
    called = []

    def thunk():
        called.append(1)
        return 1e9

    print_mfu(thunk, tput=100.0, batch=10, label="lab")
    assert capsys.readouterr().out == ""
    assert not called


def test_print_mfu_lazy_thunk_invoked_on_chip(monkeypatch, capsys):
    monkeypatch.setattr(hw, "chip_peak_bf16_flops", lambda d: 2e12)
    print_mfu(lambda: 1e9, tput=200.0, batch=10, label="lazy")
    assert "lazy" in capsys.readouterr().out


def test_print_mfu_divides_by_chip_count(monkeypatch, capsys):
    """A pipeline spanning n chips is graded against n chips' worth of
    peak FLOP/s (bench.py's ``n_chips * peak`` convention) — without the
    divisor an 8-stage run would print MFU 8x too high."""
    monkeypatch.setattr(hw, "chip_peak_bf16_flops", lambda d: 1e12)
    print_mfu(1e9, tput=100.0, batch=10, label="one")
    print_mfu(1e9, tput=100.0, batch=10, label="eight", n_chips=8)
    out = capsys.readouterr().out
    assert "one: 1.00%" in out
    assert "eight: 0.12%" in out  # 1.00 / 8 = 0.125, printed 2dp


def test_print_mfu_refuses_impossible_numbers(monkeypatch, capsys):
    """mfu > 1 means the timed window cannot have held every step it
    counted; the line must say INVALID, not publish it."""
    monkeypatch.setattr(hw, "chip_peak_bf16_flops", lambda d: 1e9)
    print_mfu(1e9, tput=100.0, batch=10, label="hot")
    out = capsys.readouterr().out
    assert "INVALID" in out
    assert "do not publish" in out


def test_print_mfu_grades_against_the_models_device(monkeypatch, capsys):
    """The peak comes from the device the model ran on, not the global
    default — a CPU debug run on a TPU-attached host must stay silent."""
    seen = []

    def peak_of(d):
        seen.append(d)
        return None if d == "cpu-dev" else 1e12

    monkeypatch.setattr(hw, "chip_peak_bf16_flops", peak_of)
    print_mfu(1e9, tput=100.0, batch=10, label="dbg", device="cpu-dev")
    assert capsys.readouterr().out == ""
    assert seen == ["cpu-dev"]


def test_sequential_step_flops_on_gpipe_model():
    """The MFU numerator of a real GPipe model is positive and at least
    the forward matmul work."""
    from benchmarks.common import build_gpipe, softmax_xent
    from torchgpipe_tpu.ops.nn import dense

    layers = [dense(16, name=f"dense{i}") for i in range(4)]
    model = build_gpipe(layers, None, 2, 2, "except_last")
    x = jnp.zeros((4, 16), jnp.float32)
    y = jnp.zeros((4,), jnp.int32)
    params, state = model.init(
        jax.random.PRNGKey(0), jax.ShapeDtypeStruct(x.shape, x.dtype)
    )
    flops = sequential_step_flops(
        model, params, state, x, y, softmax_xent, jax.random.PRNGKey(1)
    )
    assert flops is not None
    # fwd alone: 4 layers x 2*4*16*16 = 8192 FLOPs of matmul.
    assert flops >= 8192
