"""Where the persistent XLA compilation cache lives.

One rule for every entry point (``chip_smoke.py``, ``chipbench/run.py``,
the test suite): where the caller exported
``JAX_COMPILATION_CACHE_DIR``, jax reads the variable itself and nothing
is set in code; otherwise the cache is a FIXED directory inside the
checkout.  The path is part of the cache key, so a temporary, pid- or
time-derived directory would never hit.
"""

from __future__ import annotations

import os
import pathlib

import jax

CHECKOUT = pathlib.Path(__file__).resolve().parents[2]


def enable_compile_cache(
    name: str = ".jax_cache", min_compile_secs: float = 1.0
) -> str:
    """Turn the persistent compilation cache on; returns its directory.

    ``name`` is the directory under the checkout used when the
    environment names none (``.jax_cache`` for the programs,
    ``.jax_cache_tests`` for the suite — both in ``.gitignore``)."""
    jax.config.update(
        "jax_persistent_cache_min_compile_time_secs", min_compile_secs
    )
    from_env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if from_env:
        return from_env
    path = str(CHECKOUT / name)
    jax.config.update("jax_compilation_cache_dir", path)
    return path
