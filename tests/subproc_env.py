"""Shared environment for subprocess tests.

A chip belongs to one process at a time, and the suite itself runs on the
CPU, so every child a test starts is pinned to the CPU backend too — a
child that reached for an accelerator its parent holds would fail or hang.
One helper, so no copy of the env dict can silently drop the pin (or the
repo root on PYTHONPATH, which children run from other directories need).
"""

import os
import pathlib

REPO = str(pathlib.Path(__file__).resolve().parents[1])


def cpu_subproc_env(**extra: str) -> dict:
    env = dict(
        os.environ,
        PYTHONPATH=REPO,
        JAX_PLATFORMS="cpu",
        TF_CPP_MIN_LOG_LEVEL="3",
    )
    env.update(extra)
    return env
