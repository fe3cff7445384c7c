"""The examples the README advertises run end to end on the CPU mesh."""

import pathlib
import subprocess
import sys

import pytest

from tests.subproc_env import REPO, cpu_subproc_env

# End-to-end subprocess runs - the slowest tests in the suite; the fast
# core target (pytest -m "not slow") skips them.
pytestmark = pytest.mark.slow


def test_examples_quickstart():
    """The README-advertised quickstart runs end to end on the CPU mesh."""
    repo = pathlib.Path(REPO)
    env = cpu_subproc_env(XLA_FLAGS="--xla_force_host_platform_device_count=8")
    r = subprocess.run(
        [sys.executable, str(repo / "examples" / "quickstart.py")],
        capture_output=True, text=True, timeout=600, env=env, cwd=str(repo),
    )
    assert r.returncode == 0, r.stderr[-800:]
    assert "quickstart done" in r.stdout
    assert "[mpmd] step 4" in r.stdout
    assert "[spmd] step 2" in r.stdout, r.stdout


def test_examples_spmd_skips():
    """The skips-on-SPMD workaround demo (promised by the engine's error
    message) runs end to end and its oracle assertion holds."""
    repo = pathlib.Path(REPO)
    env = cpu_subproc_env(XLA_FLAGS="--xla_force_host_platform_device_count=8")
    r = subprocess.run(
        [sys.executable, str(repo / "examples" / "spmd_skips.py")],
        capture_output=True, text=True, timeout=600, env=env, cwd=str(repo),
    )
    assert r.returncode == 0, r.stderr[-800:]
    assert "pipelined == sequential oracle" in r.stdout, r.stdout
    assert "spmd-skips demo complete" in r.stdout


def test_examples_generate():
    """The train-then-decode demo runs end to end and its learned-sequence
    assertion holds."""
    repo = pathlib.Path(REPO)
    env = cpu_subproc_env(XLA_FLAGS="--xla_force_host_platform_device_count=8")
    r = subprocess.run(
        [sys.executable, str(repo / "examples" / "generate.py")],
        capture_output=True, text=True, timeout=600, env=env, cwd=str(repo),
    )
    assert r.returncode == 0, r.stderr[-800:]
    assert "generate demo complete" in r.stdout, r.stdout


def test_examples_long_context():
    """The long-context tour (ring / ulysses / ulysses+window on a pp x sp
    mesh) runs end to end and its losses descend."""
    repo = pathlib.Path(REPO)
    env = cpu_subproc_env(XLA_FLAGS="--xla_force_host_platform_device_count=8")
    r = subprocess.run(
        [sys.executable, str(repo / "examples" / "long_context.py")],
        capture_output=True, text=True, timeout=600, env=env, cwd=str(repo),
    )
    assert r.returncode == 0, r.stderr[-800:]
    assert "long-context tour complete" in r.stdout, r.stdout


def test_examples_multihost():
    """The multi-host example (two real processes, one global mesh,
    per-process data feeding, sharded checkpoint) runs end to end."""
    import socket

    repo = pathlib.Path(REPO)
    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    port = s.getsockname()[1]
    s.close()
    env = cpu_subproc_env(MULTIHOST_EXAMPLE_PORT=str(port))
    env.pop("XLA_FLAGS", None)
    env.pop("JAX_PLATFORMS", None)
    r = subprocess.run(
        [sys.executable, str(repo / "examples" / "multihost_llama.py")],
        capture_output=True, text=True, timeout=800, env=env, cwd=str(repo),
    )
    assert r.returncode == 0, (r.stdout[-500:], r.stderr[-800:])
    assert "both ranks OK" in r.stdout
    assert "step 4: loss" in r.stdout


@pytest.mark.slow
def test_examples_hf_finetune():
    """The HF fine-tune example (import -> fused-optimizer pipeline
    training with donation -> decode -> export) runs end to end."""
    repo = pathlib.Path(REPO)
    env = cpu_subproc_env(XLA_FLAGS="--xla_force_host_platform_device_count=8")
    r = subprocess.run(
        [sys.executable, str(repo / "examples" / "hf_finetune.py")],
        capture_output=True, text=True, timeout=900, env=env, cwd=str(repo),
    )
    assert r.returncode == 0, r.stderr[-800:]
    assert "exported 20 tensors back into the HF model" in r.stdout, r.stdout
    assert "step 5" in r.stdout
