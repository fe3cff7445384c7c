"""Shared jaxpr-traversal core for static analysis and structural tests.

Grown out of ``tests/jaxpr_utils.py`` (which now re-exports from here): one
walker serves every structural assertion in the test suite (remat/collective
counts, residual-byte accounting, biggest-intermediate bounds) AND the lint
rule engine (:mod:`torchgpipe_tpu.analysis.rules`), so container handling —
ClosedJaxpr wrappers, raw Jaxpr bodies (e.g. shard_map), tuple/list params —
lives in exactly one place.

Two traversal styles:

* :func:`iter_jaxprs` — flat recursive iteration over every (sub-)jaxpr;
  the counting/byte helpers build on it.
* :func:`walk_eqns` — path-aware iteration yielding :class:`EqnSite`
  records that remember *where* an equation sits (the chain of enclosing
  primitives, e.g. ``shard_map/scan/remat2``) — what the lint rules need to
  distinguish "collective inside the pipelined loop body" from "collective
  in the epilogue".
"""

from __future__ import annotations

import dataclasses
from typing import Any, Iterator, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp


def avalify(tree: Any) -> Any:
    """Shaped leaves (arrays or anything with shape/dtype) ->
    ``ShapeDtypeStruct``; everything else passes through.  The ONE
    definition shared by the abstract tracer (analysis.trace) and the
    autotuner (torchgpipe_tpu.tune)."""
    return jax.tree_util.tree_map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype)
        if hasattr(a, "shape") and hasattr(a, "dtype")
        else a,
        tree,
    )

# Primitive names by role (jax spells some of these differently across
# versions — e.g. remat vs remat2 — so rules match against the set).
REMAT_PRIMS = ("remat", "remat2", "checkpoint")
LOOP_PRIMS = ("scan", "while")
COLLECTIVE_PRIMS = (
    "psum",
    "psum2",
    "psum_invariant",
    "pmean",
    "pmax",
    "pmin",
    "ppermute",
    "pgather",
    "all_gather",
    "all_to_all",
    "psum_scatter",
    "reduce_scatter",
)
# Collectives that REDUCE over an axis (the result mixes every lane's
# value) as opposed to permutations/layout changes (ppermute, all_to_all).
REDUCING_COLLECTIVE_PRIMS = tuple(
    p
    for p in COLLECTIVE_PRIMS
    if p not in ("ppermute", "pgather", "all_to_all")
)
# Host-synchronizing primitives: each runtime occurrence round-trips to the
# Python host, serializing the device stream.
HOST_CALLBACK_PRIMS = (
    "debug_callback",
    "debug_print",  # what jax.debug.print binds in jax 0.9
    "pure_callback",
    "io_callback",
    "host_callback",
    "outside_call",
    "infeed",
    "outfeed",
)
# Compute-heavy primitives (the ones worth flagging when dead and worth
# dtype-checking under a mixed-precision policy).
MATMUL_PRIMS = ("dot_general", "conv_general_dilated")


def iter_jaxprs(jaxpr: Any) -> Iterator[Any]:
    """Yield ``jaxpr`` and every sub-jaxpr reachable through eqn params."""
    yield jaxpr
    for eqn in jaxpr.eqns:
        for v in eqn.params.values():
            yield from _iter_param(v)


def _iter_param(v: Any) -> Iterator[Any]:
    if hasattr(v, "jaxpr"):  # ClosedJaxpr
        yield from iter_jaxprs(v.jaxpr)
    elif hasattr(v, "eqns"):  # raw Jaxpr (e.g. shard_map body)
        yield from iter_jaxprs(v)
    elif isinstance(v, (tuple, list)):
        for x in v:
            yield from _iter_param(x)


def subjaxprs(eqn: Any) -> List[Any]:
    """The immediate sub-jaxprs of one equation (not recursive)."""
    out: List[Any] = []

    def collect(v: Any) -> None:
        if hasattr(v, "jaxpr"):
            out.append(v.jaxpr)
        elif hasattr(v, "eqns"):
            out.append(v)
        elif isinstance(v, (tuple, list)):
            for x in v:
                collect(x)

    for v in eqn.params.values():
        collect(v)
    return out


@dataclasses.dataclass(frozen=True)
class EqnSite:
    """One equation plus where it sits in the traced program.

    ``path`` is the chain of enclosing primitive names from the program
    root (e.g. ``("shard_map", "scan", "remat2")``); ``index`` is the
    equation's position in its immediately-enclosing jaxpr — together with
    the program name they form the ``path/stage:eqn`` diagnostic anchor.
    """

    jaxpr: Any
    eqn: Any
    index: int
    path: Tuple[str, ...]

    def within(self, prim_name: str) -> bool:
        """True if any enclosing primitive is ``prim_name``."""
        return prim_name in self.path

    def within_any(self, prim_names: Sequence[str]) -> bool:
        """True if any enclosing primitive is one of ``prim_names``."""
        return any(p in self.path for p in prim_names)


def walk_eqns(jaxpr: Any, _path: Tuple[str, ...] = ()) -> Iterator[EqnSite]:
    """Yield an :class:`EqnSite` for every equation, depth-first, with the
    enclosing-primitive path tracked."""
    for i, eqn in enumerate(jaxpr.eqns):
        yield EqnSite(jaxpr=jaxpr, eqn=eqn, index=i, path=_path)
        sub_path = _path + (eqn.primitive.name,)
        for sub in subjaxprs(eqn):
            yield from walk_eqns(sub, sub_path)


def count_eqns(jaxpr: Any, names: Sequence[str]) -> int:
    """Number of equations (recursively) whose primitive name is in
    ``names``."""
    return sum(
        1
        for jx in iter_jaxprs(jaxpr)
        for eqn in jx.eqns
        if eqn.primitive.name in names
    )


def aval_bytes(v: Any) -> int:
    aval = getattr(v, "aval", None)
    if aval is None or not hasattr(aval, "shape"):
        return 0
    n = 1
    for d in aval.shape:
        n *= int(d)
    return n * jnp.dtype(aval.dtype).itemsize


def sum_eqn_output_bytes(jaxpr: Any, names: Sequence[str]) -> int:
    """Total output bytes of all equations whose primitive is in ``names``."""
    return sum(
        aval_bytes(v)
        for jx in iter_jaxprs(jaxpr)
        for eqn in jx.eqns
        if eqn.primitive.name in names
        for v in eqn.outvars
    )


def max_eqn_output_bytes(jaxpr: Any) -> int:
    """Largest single intermediate array (bytes) anywhere in the program."""
    return max(
        (
            aval_bytes(v)
            for jx in iter_jaxprs(jaxpr)
            for eqn in jx.eqns
            for v in eqn.outvars
        ),
        default=0,
    )


def _shape_prod(shape: Any, dims: Any) -> int:
    n = 1
    for i in dims:
        n *= int(shape[i])
    return n


def eqn_flops(eqn: Any) -> float:
    """Analytic FLOPs of one compute-heavy equation (matmul/conv MACs × 2);
    everything else counts 0 — elementwise work is noise next to the MXU
    ops this estimator exists to weigh."""
    name = eqn.primitive.name
    if name == "dot_general":
        lhs = eqn.invars[0].aval
        rhs = eqn.invars[1].aval
        (lc, rc), (lb, rb) = eqn.params["dimension_numbers"]
        batch = _shape_prod(lhs.shape, lb)
        k = _shape_prod(lhs.shape, lc)
        m = _shape_prod(
            lhs.shape,
            [i for i in range(len(lhs.shape)) if i not in lc and i not in lb],
        )
        n = _shape_prod(
            rhs.shape,
            [i for i in range(len(rhs.shape)) if i not in rc and i not in rb],
        )
        return 2.0 * batch * m * n * k
    if name == "conv_general_dilated":
        out = eqn.outvars[0].aval
        rhs = eqn.invars[1].aval
        dn = eqn.params["dimension_numbers"]
        out_ch = int(rhs.shape[dn.rhs_spec[0]])
        kernel_elems = 1
        for d in rhs.shape:
            kernel_elems *= int(d)
        out_elems = 1
        for d in out.shape:
            out_elems *= int(d)
        # MACs per output element = kernel elements feeding one output
        # channel; feature_group_count is already reflected in the
        # kernel's in-channel dim.
        return 2.0 * out_elems * (kernel_elems / max(out_ch, 1))
    return 0.0


def while_trip_bound(eqn: Any) -> Optional[int]:
    """Static trip-count bound of a ``while`` equation, or None.

    Bounded loops in this codebase follow one shape — a scalar integer
    counter compared against a STATIC bound in the cond (the
    bounded-decode loop of ``models.generation.generate(early_exit=True)``
    conds on ``(n < max_new_tokens) & any(alive)``) — so the bound is
    recoverable from the cond jaxpr: the largest integer Literal operand
    of a scalar comparison.  Loops whose bound is a traced value (no
    literal comparison) return None; callers fall back to counting the
    body once (XLA's convention).
    """
    cond = eqn.params.get("cond_jaxpr")
    if cond is None:
        return None
    body = cond.jaxpr if hasattr(cond, "jaxpr") else cond
    bounds: List[int] = []
    for ceqn in body.eqns:
        if ceqn.primitive.name not in ("lt", "le", "gt", "ge"):
            continue
        for v in ceqn.invars:
            val = getattr(v, "val", None)  # Literal operands carry .val
            aval = getattr(v, "aval", None)
            if (
                val is not None
                and aval is not None
                and not getattr(aval, "shape", (1,))
                and jnp.issubdtype(aval.dtype, jnp.integer)
            ):
                bounds.append(int(val))
    return max(bounds) if bounds else None


# The custom-call primitives whose params hold SEVERAL views of one
# computation (fun_jaxpr + fwd/bwd thunks): summing every sub-jaxpr would
# double-count the one body that actually executes.
CUSTOM_CALL_PRIMS = (
    "custom_vjp_call",
    "custom_vjp_call_jaxpr",
    "custom_jvp_call",
    "custom_jvp_call_jaxpr",
    "custom_lin",
)


def flops_estimate(jaxpr: Any) -> float:
    """Analytic matmul/conv FLOPs of a (possibly Closed) jaxpr with LOOP
    STRUCTURE respected: ``scan`` bodies multiply by their static
    ``length``, ``cond`` takes the max over branches (at runtime one
    branch executes), ``while`` bodies multiply by the static trip bound
    recovered from the cond's literal comparison
    (:func:`while_trip_bound` — the bounded-decode loop convention) and
    count once when no bound is recoverable, and ``custom_vjp``/
    ``custom_jvp`` call primitives count their ONE executed body (the
    max over the jaxpr views their params carry, never the sum).  XLA's
    own cost analysis counts EVERY loop body once and SUMS cond
    branches; that convention undercounts pipelined schedules and
    bounded decode loops and overcounts peeled tails, which is why the
    planner/autotuner use this walker for structured programs.
    """
    body = jaxpr.jaxpr if hasattr(jaxpr, "jaxpr") else jaxpr
    total = 0.0
    for eqn in body.eqns:
        name = eqn.primitive.name
        subs = subjaxprs(eqn)
        if name == "scan":
            length = eqn.params.get("length")
            if length is None:  # a length-0 scan really runs 0 bodies
                length = 1
            total += length * sum(flops_estimate(s) for s in subs)
        elif name == "cond":
            total += max((flops_estimate(s) for s in subs), default=0.0)
        elif name == "while":
            bound = while_trip_bound(eqn)
            total += (bound or 1) * sum(flops_estimate(s) for s in subs)
        elif name in CUSTOM_CALL_PRIMS:
            total += max((flops_estimate(s) for s in subs), default=0.0)
        elif name == "pallas_call":
            # Kernel body runs once per grid cell.
            grid = getattr(eqn.params.get("grid_mapping"), "grid", ()) or ()
            cells = 1
            for g in grid:
                if isinstance(g, int):
                    cells *= g
            total += cells * sum(flops_estimate(s) for s in subs)
        elif subs:
            total += sum(flops_estimate(s) for s in subs)
        else:
            total += eqn_flops(eqn)
    return total


def collective_comm_bytes(
    name: str, n: int, in_bytes: float, out_bytes: Optional[float] = None
) -> float:
    """The ONE per-primitive ring-model pricing table (per-device bytes
    on the wire), shared by :func:`eqn_comm_bytes` and the sharding
    propagation's :meth:`~torchgpipe_tpu.analysis.sharding.
    PropagationResult.comm_bytes` — so the planner's priced comm and
    the walker's can never desynchronize.  ``out_bytes=None`` derives a
    gather's output as ``n × in_bytes`` (exact for tiled gathers, the
    only form this codebase emits)."""
    if n <= 1:
        return 0.0
    frac = (n - 1) / n
    if name in ("all_gather", "pgather"):
        ob = out_bytes if out_bytes is not None else in_bytes * n
        return frac * ob
    if name in ("psum_scatter", "reduce_scatter"):
        return frac * in_bytes
    if name == "ppermute":
        return float(in_bytes)
    if name == "all_to_all":
        return frac * in_bytes
    # Reducing collectives (the psum family): ring all-reduce.
    return 2.0 * frac * in_bytes


def eqn_comm_bytes(eqn: Any, axis_sizes: "dict[str, int]") -> float:
    """Analytic communication volume (bytes moved per participating
    device) of ONE collective equation under a mesh whose axis sizes are
    ``axis_sizes``.  Non-collective equations cost 0.

    The model is the standard ring/bidirectional accounting (bytes on
    the wire per device, which is what bounds collective time on a
    bandwidth-limited interconnect):

    * all-reduce family (``psum``/``pmean``/``pmax``/``pmin``) —
      ``2·(N-1)/N`` × operand bytes (reduce-scatter + all-gather);
    * ``all_gather`` — ``(N-1)/N`` × *output* bytes (each device
      receives every other shard);
    * ``psum_scatter``/``reduce_scatter`` — ``(N-1)/N`` × input bytes;
    * ``ppermute`` — input bytes (each device forwards its operand one
      hop);
    * ``all_to_all`` — ``(N-1)/N`` × input bytes.

    An axis missing from ``axis_sizes`` counts as size 1 (zero volume)
    — axis *existence* is the ``collective-mismatch`` /
    ``implicit-reshard`` rules' job, not the cost model's.
    """
    name = eqn.primitive.name
    if name not in COLLECTIVE_PRIMS:
        return 0.0
    n = 1
    for a in collective_axes(eqn):
        n *= int(axis_sizes.get(a, 1))
    in_bytes = sum(aval_bytes(v) for v in eqn.invars)
    out_bytes = sum(aval_bytes(v) for v in eqn.outvars)
    return collective_comm_bytes(name, n, in_bytes, out_bytes)


def comm_bytes_estimate(jaxpr: Any, axis_sizes: "dict[str, int]") -> float:
    """Analytic per-device collective traffic (bytes) of a (possibly
    Closed) jaxpr — the communication companion to
    :func:`flops_estimate`, with the SAME loop-structure conventions:
    ``scan`` bodies multiply by their static ``length``, ``cond`` takes
    the max over branches, bounded ``while`` loops multiply by
    :func:`while_trip_bound`, and the ``custom_vjp``/``custom_jvp``
    call primitives count their one executed body.

    ``axis_sizes`` maps mesh-axis name → size (e.g. ``dict(mesh.shape)``
    or a *candidate* mesh the 3D planner is pricing) — the same traced
    program can be priced under different widths without retracing.
    Standalone uses: ``obs.reconcile``'s cost pricing and the planner's
    comm-volume charge against the makespan.
    """
    body = jaxpr.jaxpr if hasattr(jaxpr, "jaxpr") else jaxpr
    total = 0.0
    for eqn in body.eqns:
        name = eqn.primitive.name
        subs = subjaxprs(eqn)
        if name == "scan":
            length = eqn.params.get("length")
            if length is None:
                length = 1
            total += length * sum(
                comm_bytes_estimate(s, axis_sizes) for s in subs
            )
        elif name == "cond":
            total += max(
                (comm_bytes_estimate(s, axis_sizes) for s in subs),
                default=0.0,
            )
        elif name == "while":
            bound = while_trip_bound(eqn)
            total += (bound or 1) * sum(
                comm_bytes_estimate(s, axis_sizes) for s in subs
            )
        elif name in CUSTOM_CALL_PRIMS:
            total += max(
                (comm_bytes_estimate(s, axis_sizes) for s in subs),
                default=0.0,
            )
        elif subs:
            total += sum(comm_bytes_estimate(s, axis_sizes) for s in subs)
        else:
            total += eqn_comm_bytes(eqn, axis_sizes)
    return total


def scan_lengths(jaxpr: Any) -> List[Optional[int]]:
    """The trip counts (``length`` param) of every scan in the program, in
    encounter order — lets structural tests pin schedule depths exactly."""
    out: List[Optional[int]] = []
    for jx in iter_jaxprs(jaxpr):
        for eqn in jx.eqns:
            if eqn.primitive.name == "scan":
                out.append(eqn.params.get("length"))
    return out


def collective_axes(eqn: Any) -> Tuple[str, ...]:
    """The mesh-axis names a collective equation operates over.

    Normalizes the parameter spellings jax uses across collectives:
    ``axes`` (psum family), ``axis_name`` (ppermute/all_gather/all_to_all).
    Non-collective equations return ``()``.
    """
    raw = eqn.params.get("axes", eqn.params.get("axis_name", ()))
    if raw is None:
        return ()
    if isinstance(raw, (str, int)):
        raw = (raw,)
    return tuple(str(a) for a in raw if isinstance(a, str))


def prim_counts(jaxpr: Any, names: Sequence[str]) -> "dict[str, int]":
    """Per-primitive occurrence counts (recursive) for the given names."""
    out = {n: 0 for n in names}
    for jx in iter_jaxprs(jaxpr):
        for eqn in jx.eqns:
            if eqn.primitive.name in out:
                out[eqn.primitive.name] += 1
    return out
