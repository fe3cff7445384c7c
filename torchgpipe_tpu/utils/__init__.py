"""Shared small utilities."""

from __future__ import annotations

from typing import Any

import jax
import jax.numpy as jnp

Pytree = Any


def tree_allclose(a: Pytree, b: Pytree, *, rtol: float = 1e-5, atol: float = 1e-6) -> bool:
    """Structural + numerical equality of two pytrees (test helper)."""
    la, ta = jax.tree_util.tree_flatten(a)
    lb, tb = jax.tree_util.tree_flatten(b)
    if ta != tb:
        return False
    return all(
        x.shape == y.shape and jnp.allclose(x, y, rtol=rtol, atol=atol)
        for x, y in zip(la, lb)
    )


def param_count(tree: Pytree) -> int:
    """Total number of scalar parameters in a pytree."""
    return sum(int(x.size) for x in jax.tree_util.tree_leaves(tree))


def tree_finite(tree: Pytree) -> jax.Array:
    """TRACEABLE all-finite reduction over every inexact leaf — the
    in-program twin of :func:`torchgpipe_tpu.resilience.guard._all_finite`
    (which host-syncs).  The megastep scan threads this through its carry
    so NaN skip-step semantics survive inside one compiled program: it
    must cover exactly what the StepGuard's host-side check covers (the
    whole step output) for megastep(K) to bitwise-match K guarded steps.
    """
    ok = jnp.bool_(True)
    for leaf in jax.tree_util.tree_leaves(tree):
        if jnp.issubdtype(jnp.result_type(leaf), jnp.inexact):
            ok = jnp.logical_and(ok, jnp.all(jnp.isfinite(leaf)))
    return ok


def host_device() -> Any:
    """Context placing computation on the host CPU backend (no-op fallback
    when unavailable).

    Used by the engines' ``init``: initialization is hundreds of tiny ops
    (one per weight), each a separate dispatch and most a separate tiny
    compile on the accelerator, so init on host, then transfer placed
    pytrees once.
    """
    import contextlib

    try:
        cpu = jax.local_devices(backend="cpu")[0]
    except Exception:
        return contextlib.nullcontext()
    return jax.default_device(cpu)
