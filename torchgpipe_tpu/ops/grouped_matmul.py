"""A grouped matrix product as a Pallas TPU kernel, tiled from its shape.

``grouped_matmul(x, w, group_sizes)`` computes what ``lax.ragged_dot(x,
w, group_sizes)`` computes: row ``i`` of ``x [m, k]`` times ``w[g] [k,
n]``, where the groups are consecutive runs of rows, ``group_sizes[g]``
long, from row 0.  Products accumulate in float32 and come out in the
inputs' type.  Rows past the last group belong to no group, and the
result holds anything there: the caller selects them out, as it must
for the compiler's own grouped product.

Two things set it apart from that product, which streams 128 x 128
weight tiles whatever the shape:

* Its tiles follow ``(m, k, n, groups)`` (:func:`tiles`): a width that is
  not a multiple of 128 is taken whole, every other the largest multiple
  of 128 that divides it and fits, so that an expert's weights come in a
  few large pieces per row tile.
* It reads each bank in the layout the chip stores it.  A bank ``[E, k,
  n]`` whose ``n`` is not a multiple of 128 is kept by the compiler with
  ``k`` minor, and a kernel that took it row-major would be handed a
  relaid copy of the whole bank on every call; it is read here through
  ``swapaxes(w, 1, 2)`` (a bitcast of that layout) as the transposed
  operand of each tile's product.

The grid and the row-tile metadata are those of the megablox grouped
matmul installed with jax (``jax.experimental.pallas.ops.tpu.megablox``):
the grid walks (n tiles, the row tiles each group touches, k tiles), so
a group that shares a row tile with its neighbours visits it once, and a
row tile of no group is never visited.  The kernel is named
``grouped_matmul`` in the compiled program and in a device trace.
"""

from __future__ import annotations

import functools
from typing import Any, List, Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu
from jax.experimental.pallas.ops.tpu.megablox.gmm import make_group_metadata

# What the double-buffered blocks and the float32 accumulator may take of
# the compiler's default scoped VMEM (16 MiB on a v5e), with room left for
# the kernel's own scratch.
_VMEM_BUDGET = 12 * 1024 * 1024


def _widths(d: int) -> List[int]:
    """Tile widths for a dimension ``d``, widest first: ``d`` itself where
    it is not a multiple of 128 (a block must then span it), else every
    multiple of 128 that divides it."""
    if d % 128:
        return [d]
    return [c for c in range(d, 0, -128) if d % c == 0]


def transposes(k: int, n: int) -> bool:
    """Whether a bank ``[E, k, n]`` is read through its transposed view:
    ``n`` is not a multiple of 128 and ``k`` is, so the chip stores the
    bank with ``k`` minor."""
    return n % 128 != 0 and k % 128 == 0


def tiles(m: int, k: int, n: int, groups: int,
          itemsize: int = 2) -> Optional[Tuple[int, int, int]]:
    """``(tm, tk, tn)`` for ``m`` rows of ``k`` against ``groups`` banks
    ``[k, n]``, or None where no tile fits.

    ``tm`` is 256 where a group averages 256 rows or more (prefill), else
    128 (decode), halved until something fits.  ``tk`` and ``tn`` are the
    pair of :func:`_widths` with the largest weight tile, the wider ``tn``
    among equals, whose two buffers of the row, weight and result tiles
    and float32 accumulator fit ``_VMEM_BUDGET``."""
    tm = 256 if m >= 256 * groups else 128
    while tm >= 16:
        fits = [
            (tk * tn, tn, tk) for tk in _widths(k) for tn in _widths(n)
            if 2 * (tm * tk + tk * tn + tm * tn) * itemsize + tm * tn * 4
            <= _VMEM_BUDGET
        ]
        if fits:
            _, tn, tk = max(fits)
            return tm, tk, tn
        tm //= 2
    return None


def _kernel(offsets_ref: Any, group_ref: Any, tile_ref: Any, x_ref: Any,
            w_ref: Any, o_ref: Any, acc_ref: Any, *, tm: int,
            transpose_rhs: bool) -> None:
    """One grid step: the row tile ``tile_ref[i]`` against its group's
    weight tile, accumulated over k; on the last k tile the rows of the
    group are stored and the tile's other rows left as they are (another
    group's, written by the visit before, or no group's)."""
    i, kk = pl.program_id(1), pl.program_id(2)

    @pl.when(kk == 0)
    def _zero() -> None:
        acc_ref[...] = jnp.zeros_like(acc_ref)

    dims = (((1,), (1 if transpose_rhs else 0,)), ((), ()))
    acc_ref[...] += lax.dot_general(
        x_ref[...], w_ref[...], dims, preferred_element_type=jnp.float32)

    @pl.when(kk == pl.num_programs(2) - 1)
    def _store() -> None:
        g = group_ref[i]
        row = tile_ref[i] * tm + lax.broadcasted_iota(
            jnp.int32, acc_ref.shape, 0)
        mine = (row >= offsets_ref[g]) & (row < offsets_ref[g + 1])
        o_ref[...] = jnp.where(
            mine, acc_ref[...], o_ref[...].astype(jnp.float32)
        ).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("interpret",))
def grouped_matmul(x: jnp.ndarray, w: jnp.ndarray, group_sizes: jnp.ndarray,
                   *, interpret: bool = False) -> jnp.ndarray:
    """``lax.ragged_dot(x, w, group_sizes)`` for ``x [m, k]``, ``w [E, k,
    n]`` and int32 ``group_sizes [E]``, through the Pallas kernel
    (``interpret=True`` runs it on any backend, for tests).  Rows past
    ``sum(group_sizes)`` hold anything.  Raises where :func:`tiles` finds
    no tile.  Jitted, so that a model's layers lower the kernel once a
    shape."""
    dtype = jnp.result_type(x, w)
    x, w = x.astype(dtype), w.astype(dtype)
    (m, k), (groups, _, n) = x.shape, w.shape
    tiling = tiles(m, k, n, groups, x.dtype.itemsize)
    if tiling is None:
        raise ValueError(
            f"no grouped_matmul tiles for {m} rows of {k} against "
            f"{groups} banks of {n}")
    tm, tk, tn = tiling
    rows = -(-m // tm) * tm
    if rows != m:  # the padded rows lie past the last group
        x = jnp.pad(x, ((0, rows - m), (0, 0)))
    transpose_rhs = transposes(k, n)
    if transpose_rhs:
        w = jnp.swapaxes(w, 1, 2)
    (offsets, group_ids, tile_ids), visits = make_group_metadata(
        group_sizes=group_sizes, m=rows, tm=tm,
        start_group=jnp.int32(0), num_nonzero_groups=groups,
        visit_empty_groups=False,
    )

    def x_map(j, i, kk, offsets, group_ids, tile_ids):
        return tile_ids[i], kk

    def w_map(j, i, kk, offsets, group_ids, tile_ids):
        if transpose_rhs:
            return group_ids[i], j, kk
        return group_ids[i], kk, j

    def o_map(j, i, kk, offsets, group_ids, tile_ids):
        return tile_ids[i], j

    w_block = (None, tn, tk) if transpose_rhs else (None, tk, tn)
    size = x.dtype.itemsize
    out = pl.pallas_call(
        functools.partial(_kernel, tm=tm, transpose_rhs=transpose_rhs),
        name="grouped_matmul",
        out_shape=jax.ShapeDtypeStruct((rows, n), x.dtype),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(n // tn, visits, k // tk),
            in_specs=[pl.BlockSpec((tm, tk), x_map),
                      pl.BlockSpec(w_block, w_map)],
            out_specs=pl.BlockSpec((tm, tn), o_map),
            scratch_shapes=[pltpu.VMEM((tm, tn), jnp.float32)],
        ),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary", "arbitrary")),
        cost_estimate=pl.CostEstimate(
            flops=2 * rows * k * n, transcendentals=0,
            bytes_accessed=(rows * k * (n // tn) + groups * k * n
                            + rows * n) * size),
        interpret=interpret,
    )(offsets, group_ids, tile_ids, x, w)
    return out[:m] if rows != m else out
