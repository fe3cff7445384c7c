"""Test harness configuration.

Mirrors the reference's CPU-first test strategy (see SURVEY.md §4): nearly all
engine tests run on multiple *host* devices so the entire scheduler/checkpoint/
skip machinery is exercised without TPU hardware (reference:
tests/test_gpipe.py:49 runs pipelines on devices=['cpu','cpu',...]).

The suite runs on the CPU backend with 8 virtual devices, through the plain
environment: the tier-1 command exports ``JAX_PLATFORMS=cpu``, and a bare
``pytest tests/`` gets the same from the defaults below.  XLA reads the device
count when the CPU backend first initializes — importing jax does not — so
setting it here is in time.  What only a chip can show is in chip_smoke.py.
"""

import os

# Silence XLA:CPU AOT cache-load feature-mismatch chatter (benign
# "prefer-no-scatter/gather" pseudo-feature messages logged at ERROR level on
# every cache hit, ~2KB each).  Level 3 filters all C++ ERROR logs; real XLA
# failures still surface as Python exceptions with full messages.
os.environ.setdefault("TF_CPP_MIN_LOG_LEVEL", "3")
os.environ.setdefault("JAX_PLATFORMS", "cpu")
if "--xla_force_host_platform_device_count" not in os.environ.get("XLA_FLAGS", ""):
    os.environ["XLA_FLAGS"] = (
        os.environ.get("XLA_FLAGS", "")
        + " --xla_force_host_platform_device_count=8"
    ).strip()

import jax  # noqa: E402

from torchgpipe_tpu.utils.compile_cache import enable_compile_cache  # noqa: E402

# Persistent compilation cache: the suite compiles hundreds of small XLA
# programs (stage variants x models); caching them makes warm runs several
# times faster while a cold run is unaffected.  JAX_COMPILATION_CACHE_DIR
# wins where the caller set it; else <checkout>/.jax_cache_tests.
enable_compile_cache(".jax_cache_tests", min_compile_secs=0.5)

import pytest  # noqa: E402

# Per-file time-budget lint (opt-in: TGPU_TEST_TIME_BUDGET=<seconds>):
# fails the session when a file's tests NOT marked 'slow' exceed the
# budget — how the tier-1 wall-clock target stays enforceable instead
# of rotting one slow test at a time.  Hooks re-exported so plain
# `pytest tests/` picks them up without -p.
from tools.pytest_file_budget import (  # noqa: E402,F401
    pytest_runtest_logreport,
    pytest_sessionfinish,
)


@pytest.fixture(autouse=True)
def _deterministic_seed():
    # Reference: tests/conftest.py:5-7 seeds torch; JAX keys are explicit, but
    # numpy-based data generation in tests still benefits from a fixed seed.
    import numpy as np

    np.random.seed(0)
    yield


@pytest.fixture(scope="session")
def cpu_devices():
    devs = jax.devices()
    if len(devs) < 8:
        pytest.skip("expected 8 virtual host devices")
    return devs


def counting_layer(calls):
    """A pass-through Layer whose apply fires a debug callback appending to
    ``calls`` — counts actual block executions (only the taken lax.cond
    branch fires at runtime).  Shared by the schedule checkpoint-mode
    forward-count tests (test_spmd_1f1b.py, test_spmd_interleaved.py)."""
    from torchgpipe_tpu.layers import Layer

    def init(rng, in_spec):
        del rng, in_spec
        return (), ()

    def apply(params, state, x, *, rng=None, train=True):
        del params, rng, train
        jax.debug.callback(lambda: calls.append(1))
        return x, state

    return Layer(name="count", init=init, apply=apply)
