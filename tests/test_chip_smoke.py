"""chip_smoke.py's phases at toy width on the CPU, and the repairs the
chip path needed.

The command itself has no small mode and refuses the CPU; its phases are
functions of the config and sizes, so the control flow, the checks and the
four-chip placement assertions are rehearsed here (virtual host devices,
dense attention or interpret-mode kernels).  What only the chip or its
compiler can say is in tests/test_chip_compile.py and in the chip run.
"""

import contextlib
import io
import json
import subprocess
import sys
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import chip_smoke as cs
from tests.subproc_env import REPO, cpu_subproc_env
from torchgpipe_tpu.layers import sequential_init
from torchgpipe_tpu.models import generation
from torchgpipe_tpu.models.transformer import (
    TransformerConfig,
    cross_entropy,
    llama,
)
from torchgpipe_tpu.ops.flash_attention import flash_attention
from torchgpipe_tpu.utils.compile_cache import CHECKOUT, enable_compile_cache


def toy_config(depth):
    # A window shorter than the training sequence and the prompts.
    return TransformerConfig(
        vocab=256, dim=64, n_layers=depth, n_heads=4, n_kv_heads=2,
        attn_window=16, rope_theta=10000.0,
    )


TOY = cs.Sizes(
    batch=4, seq=32, chunks=2, slots=4, max_len=64, prefill_chunk=8,
    requests=5, prompt_range=(3, 20), new_range=(3, 6), fixed_prompts=(5, 9),
    generate_prompts=2, generate_max_len=32, flash_block=8, flash_len=128,
)


def _lines(run):
    out = io.StringIO()
    with contextlib.redirect_stdout(out):
        run(cs.CompileMeter())
    return {
        line["phase"]: line
        for line in map(json.loads, out.getvalue().splitlines())
    }


@pytest.fixture(scope="module")
def one_chip_lines():
    return _lines(lambda meter: cs.one_chip(toy_config(2), 1, TOY, 0, meter))


@pytest.fixture(scope="module")
def four_chip_lines():
    devices = jax.devices()[:4]
    if len(devices) < 4:
        pytest.skip("needs 4 virtual host devices")
    return _lines(
        lambda meter: cs.four_chips(toy_config(4), TOY, 0, devices, meter)
    )


@pytest.mark.parametrize(
    "name", ["train_spmd", "train_mpmd", "serve", "generate"]
)
def test_one_chip_phase(one_chip_lines, name):
    line = one_chip_lines[name]
    assert line["ok"] is True
    assert {"compile_s", "steady_s", "device", "peak_bytes_in_use"} <= set(line)
    if name.startswith("train"):
        assert line["losses"][0] == pytest.approx(
            line["unpipelined_loss"], rel=1e-5
        )
        assert line["losses"][-1] < line["losses"][0]
    if name == "serve":
        assert line["programs"] == {"prefill": 1, "decode": 1}
        assert all(n % TOY.flash_block for n in line["prompt_lengths"])
        assert line["worst_logit_gap"] <= 1e-4
    if name == "generate":
        assert line["prompt_lengths"] == list(TOY.fixed_prompts)
        assert line["flash_vs_dense_max_abs"] <= 1e-4


@pytest.mark.parametrize("name", ["train_spmd_pp4", "train_mpmd_pp4"])
def test_four_chip_phase(four_chip_lines, name):
    line = four_chip_lines[name]
    assert line["ok"] is True
    assert line["losses"][0] == pytest.approx(
        line["unpipelined_loss"], rel=1e-5
    )
    ids = [d.id for d in jax.devices()[:4]]
    if name == "train_spmd_pp4":
        assert line["block_param_devices"] == ids
    else:
        assert line["stage_devices"] == [[i] for i in ids]


def test_four_chip_body_refuses_fewer_devices():
    with pytest.raises(cs.SmokeFailure, match="needs 4 devices"):
        cs.four_chips(
            toy_config(4), TOY, 0, jax.devices()[:2], cs.CompileMeter()
        )


@pytest.mark.parametrize("args", [[], ["--chips", "4"]])
def test_command_refuses_the_cpu(args):
    proc = subprocess.run(
        [sys.executable, "chip_smoke.py", *args], cwd=REPO,
        env=cpu_subproc_env(), capture_output=True, text=True, timeout=300,
    )
    assert proc.returncode != 0
    assert '"ok": true' not in proc.stdout
    assert "needs a TPU" in proc.stderr


@pytest.fixture
def cache_config():
    before = jax.config.jax_compilation_cache_dir
    min_secs = jax.config.jax_persistent_cache_min_compile_time_secs
    yield
    jax.config.update("jax_compilation_cache_dir", before)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", min_secs)


def test_compile_cache_follows_the_environment(monkeypatch, cache_config):
    before = jax.config.jax_compilation_cache_dir
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/some/dir")
    assert enable_compile_cache() == "/some/dir"
    assert jax.config.jax_compilation_cache_dir == before  # nothing set


@pytest.mark.parametrize("name", [".jax_cache", ".jax_cache_tests"])
def test_compile_cache_defaults_to_a_fixed_directory(
    monkeypatch, cache_config, name
):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    want = str(CHECKOUT / name)
    assert str(CHECKOUT) == REPO
    assert enable_compile_cache(name) == want
    assert jax.config.jax_compilation_cache_dir == want


# ------------------------------------------------------------------ #
# the generation.py gate                                             #
# ------------------------------------------------------------------ #


def _toy_prefill_inputs(s):
    cfg = toy_config(1)
    spec = jax.ShapeDtypeStruct((1, s), jnp.int32)
    params, _, _ = sequential_init(llama(cfg), jax.random.PRNGKey(0), spec)
    tokens = jnp.mod(jnp.arange(s)[None] * 7, cfg.vocab)
    return cfg, params, tokens


def test_flash_attention_raises_on_an_undivided_length():
    q = jnp.zeros((1, 200, 4, 16))
    k = jnp.zeros((1, 200, 2, 16))
    with pytest.raises(ValueError, match="divisible by the block sizes"):
        flash_attention(q, k, k, causal=True, interpret=True)


def test_prefill_forced_flash_raises_on_an_undivided_length():
    cfg, params, tokens = _toy_prefill_inputs(100)
    with pytest.raises(ValueError, match="divisible by the block sizes"):
        generation.prefill(cfg, params, tokens, 128, use_flash=True)


@pytest.mark.parametrize("s", [100, 130, 200])
def test_prefill_auto_dispatch_is_dense_where_blocks_do_not_divide(
    s, monkeypatch
):
    """On a TPU the auto-dispatch used to take the kernel at EVERY
    length (Mosaic refused 100; 130 and 200 ran a short grid and left
    NaN tail rows).  With the platform answered as "tpu" a kernel branch
    could not even lower here, so equality with the dense path also
    proves which branch ran."""
    cfg, params, tokens = _toy_prefill_inputs(s)
    dense, _ = generation.prefill(cfg, params, tokens, 256, use_flash=False)
    monkeypatch.setattr(
        jax, "devices", lambda *a: [types.SimpleNamespace(platform="tpu")]
    )
    auto, _ = generation.prefill(cfg, params, tokens, 256)
    assert np.isfinite(np.asarray(auto)).all()
    np.testing.assert_array_equal(np.asarray(auto), np.asarray(dense))


def test_mpmd_second_step_compiles_nothing():
    """GPipe.init handed each stage's state as a list and value_and_grad
    handed it back as a tuple, so feeding the returned state to step 2 —
    what every training loop does — recompiled every per-cell program."""
    cfg = toy_config(2)
    x = jnp.zeros((4, 16), jnp.int32)
    model = cs.GPipe(llama(cfg), [2, 2], chunks=2, checkpoint="except_last")
    params, state = model.init(
        jax.random.PRNGKey(0), jax.ShapeDtypeStruct(x.shape, x.dtype)
    )
    meter = cs.CompileMeter()
    for step in range(2):
        compiled = meter.programs
        _, _, new_state, _ = model.value_and_grad(
            params, state, x, x, cross_entropy
        )
        assert jax.tree_util.tree_structure(
            new_state
        ) == jax.tree_util.tree_structure(state)
        state = new_state
    assert meter.programs == compiled
