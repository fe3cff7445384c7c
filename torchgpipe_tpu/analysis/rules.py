"""The lint rule engine: structural invariants checked over traced jaxprs.

Each rule is a pure function ``(PipelineTrace) -> List[Finding]`` registered
in :data:`RULES`.  The invariants are the ones the paper's correctness story
rests on (Kim et al., arXiv:2004.09910; Huang et al., arXiv:1811.06965):
checkpointing recomputes exactly the forward graph, micro-batches share one
compiled program, collectives run over axes that exist, and the pipelined
loop body never blocks on the host.  The test suite asserts these on its own
models (tests/test_structural.py etc.); the rule engine enforces them on
*any* user model before a long TPU compile.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Callable, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp

from torchgpipe_tpu.analysis import jaxpr as jx
from torchgpipe_tpu.analysis.diagnostics import Finding, Severity
from torchgpipe_tpu.analysis.trace import (
    FUSED_TRAIN,
    SPMD_TRAIN,
    STAGE_CKPT,
    STAGE_FORWARD,
    STAGE_RECOMPUTE,
    PipelineTrace,
    TracedProgram,
)
from torchgpipe_tpu.checkpoint import checkpoint_stop


@dataclasses.dataclass(frozen=True)
class Rule:
    """One named invariant check."""

    name: str
    description: str
    check: Callable[[PipelineTrace], List[Finding]]


# --------------------------------------------------------------------- #
# remat-coverage                                                        #
# --------------------------------------------------------------------- #


def _check_remat_coverage(trace: PipelineTrace) -> List[Finding]:
    out: List[Finding] = []
    if trace.engine == "spmd":
        for prog in trace.by_kind(SPMD_TRAIN):
            n_remat = jx.count_eqns(prog.jaxpr.jaxpr, jx.REMAT_PRIMS)
            if (
                trace.checkpoint in ("always", "except_last", "offload")
                and n_remat == 0
            ):
                out.append(Finding(
                    rule="remat-coverage",
                    severity=Severity.ERROR,
                    path=prog.path,
                    message=(
                        f"checkpoint={trace.checkpoint!r} is configured but "
                        "the compiled step contains no remat region — "
                        "activations will be saved for every cell (GPipe "
                        "memory profile lost; O(m) instead of O(1) "
                        "activation memory per stage)"
                    ),
                ))
        return out

    # MPMD: the fused whole-step program is the remat-count oracle —
    # checkpoint mode X over m micro-batches and n stages must produce
    # exactly stop(X, m) * n remat'd cells (reference gpipe.py:360-367).
    m = len(trace.mb_signatures) or trace.chunks
    stop = checkpoint_stop(trace.checkpoint, m, train=True)
    for prog in trace.by_kind(FUSED_TRAIN):
        n_remat = jx.count_eqns(prog.jaxpr.jaxpr, jx.REMAT_PRIMS)
        expected = stop * trace.n_stages
        if stop > 0 and n_remat != expected:
            out.append(Finding(
                rule="remat-coverage",
                severity=Severity.ERROR,
                path=prog.path,
                message=(
                    f"checkpoint={trace.checkpoint!r} over {m} micro-"
                    f"batches x {trace.n_stages} stages must remat exactly "
                    f"{expected} cells, found {n_remat} remat regions"
                ),
            ))
        if stop == 0 and n_remat != 0:
            out.append(Finding(
                rule="remat-coverage",
                severity=Severity.WARNING,
                path=prog.path,
                message=(
                    f"checkpoint='never' but {n_remat} remat region(s) "
                    "present — a layer applies jax.checkpoint on its own; "
                    "recompute will run even though the engine stores "
                    "residuals"
                ),
            ))

    # Divergence: the checkpointed forward and the recompute must contain
    # the forward's compute graph.  A layer branching on is_checkpointing /
    # is_recomputing that skips real compute breaks gradient correctness
    # (the reference's Checkpoint/Recompute pair recomputes the exact
    # forward, reference checkpoint.py:1-19).
    for j in range(trace.n_stages):
        fwd = trace.stage_program(STAGE_FORWARD, j)
        if fwd is None:
            continue
        fwd_counts = jx.prim_counts(fwd.jaxpr.jaxpr, jx.MATMUL_PRIMS)
        ck = trace.stage_program(STAGE_CKPT, j)
        if ck is not None:
            ck_counts = jx.prim_counts(ck.jaxpr.jaxpr, jx.MATMUL_PRIMS)
            if ck_counts != fwd_counts:
                out.append(Finding(
                    rule="remat-coverage",
                    severity=Severity.ERROR,
                    path=ck.path,
                    message=(
                        "checkpointed forward diverges from the plain "
                        f"forward (matmul/conv counts {ck_counts} vs "
                        f"{fwd_counts}) — a layer branches on "
                        "is_checkpointing(); the recompute will not "
                        "reproduce the forward graph"
                    ),
                ))
        rc = trace.stage_program(STAGE_RECOMPUTE, j)
        if rc is not None:
            rc_counts = jx.prim_counts(rc.jaxpr.jaxpr, jx.MATMUL_PRIMS)
            if any(rc_counts[k] < fwd_counts[k] for k in fwd_counts):
                out.append(Finding(
                    rule="remat-coverage",
                    severity=Severity.ERROR,
                    path=rc.path,
                    message=(
                        "recompute body is missing forward compute "
                        f"(matmul/conv counts {rc_counts} vs forward "
                        f"{fwd_counts}) — a layer branches on "
                        "is_recomputing() and skips real work; its "
                        "gradients will be wrong"
                    ),
                ))
    return out


# --------------------------------------------------------------------- #
# precision-drift                                                       #
# --------------------------------------------------------------------- #

_LOW_PRECISION = ("bfloat16", "float16")


def _check_precision_drift(trace: PipelineTrace) -> List[Finding]:
    dtype = trace.compute_dtype
    if dtype is None or jnp.dtype(dtype).name not in _LOW_PRECISION:
        return []
    dtype_name = jnp.dtype(dtype).name
    out: List[Finding] = []
    for prog in trace.by_kind(STAGE_FORWARD):
        for site in jx.walk_eqns(prog.jaxpr.jaxpr):
            name = site.eqn.primitive.name
            if name in jx.MATMUL_PRIMS:
                in_dtypes = {
                    str(getattr(v, "aval", None) and v.aval.dtype)
                    for v in site.eqn.invars
                    if getattr(v, "aval", None) is not None
                }
                if "float32" in in_dtypes:
                    out.append(Finding(
                        rule="precision-drift",
                        severity=Severity.WARNING,
                        path=prog.path,
                        eqn=site.index,
                        primitive=name,
                        message=(
                            f"float32 {name} inside a {dtype_name} compute "
                            "region — the precision policy (precision.py) "
                            "casts layer inputs/params down, so a float32 "
                            "matmul means a layer upcasts internally: 2x "
                            "MXU time and activation bytes for this op"
                        ),
                    ))
            elif name in ("rsqrt", "sqrt"):
                v = site.eqn.invars[0]
                aval = getattr(v, "aval", None)
                if aval is not None and str(aval.dtype) in _LOW_PRECISION:
                    out.append(Finding(
                        rule="precision-drift",
                        severity=Severity.WARNING,
                        path=prog.path,
                        eqn=site.index,
                        primitive=name,
                        message=(
                            f"normalization statistics computed in "
                            f"{aval.dtype} — the policy keeps norm "
                            "statistics float32 (variance of a "
                            f"{dtype_name} sum underflows); upcast before "
                            "the mean/variance like precision._wrap_norm"
                        ),
                    ))
    return out


# --------------------------------------------------------------------- #
# collective-mismatch                                                   #
# --------------------------------------------------------------------- #


def _check_collective_mismatch(trace: PipelineTrace) -> List[Finding]:
    out: List[Finding] = []
    if trace.engine != "spmd":
        # MPMD stage programs run on single devices; any collective traces
        # to an unbound axis name, which the tracer already converted into
        # a collective-mismatch finding in trace.errors.
        return out
    mesh_axes = set(trace.mesh_axes)
    for prog in trace.by_kind(SPMD_TRAIN):
        for site in jx.walk_eqns(prog.jaxpr.jaxpr):
            name = site.eqn.primitive.name
            if name not in jx.COLLECTIVE_PRIMS:
                continue
            axes = jx.collective_axes(site.eqn)
            unknown = [a for a in axes if a not in mesh_axes]
            if unknown:
                out.append(Finding(
                    rule="collective-mismatch",
                    severity=Severity.ERROR,
                    path=prog.path,
                    eqn=site.index,
                    primitive=name,
                    message=(
                        f"{name} over axis {unknown} but the SpmdGPipe "
                        f"mesh has axes {sorted(mesh_axes)}"
                    ),
                ))
            if (
                name in jx.REDUCING_COLLECTIVE_PRIMS
                and trace.pp_axis in axes
                and site.within("scan")
            ):
                out.append(Finding(
                    rule="collective-mismatch",
                    severity=Severity.ERROR,
                    path=prog.path,
                    eqn=site.index,
                    primitive=name,
                    message=(
                        f"{name} reduces over the pipeline axis "
                        f"{trace.pp_axis!r} inside the schedule loop — at "
                        "any tick the pp lanes hold DIFFERENT micro-"
                        "batches, so a mid-schedule reduction mixes "
                        "unrelated cells; reduce over dp/tp/ep instead, "
                        "or after the schedule drains"
                    ),
                ))
    return out


# --------------------------------------------------------------------- #
# recompilation-hazard                                                  #
# --------------------------------------------------------------------- #


def _check_recompilation(trace: PipelineTrace) -> List[Finding]:
    sigs = trace.mb_signatures
    distinct = sorted({s for s in sigs}, key=str)
    if len(distinct) <= 1:
        return []
    shapes = [
        " x ".join(f"{list(sh)}:{dt}" for _, sh, dt in sig)
        for sig in distinct
    ]
    return [Finding(
        rule="recompilation-hazard",
        severity=Severity.WARNING,
        path="scatter",
        message=(
            f"{len(sigs)} micro-batches carry {len(distinct)} distinct "
            f"shape signatures ({'; '.join(shapes)}): every stage compiles "
            f"{len(distinct)} programs instead of 1, and each new batch "
            "size recompiles again — pad the batch to a multiple of "
            f"chunks={trace.chunks} (the SPMD engine's masked path does "
            "this automatically)"
        ),
    )]


# --------------------------------------------------------------------- #
# pad-waste                                                             #
# --------------------------------------------------------------------- #

# Fraction of batch positions allowed to be trailing pad before the rule
# fires.  The threshold is deliberately generous: below it, packing's
# win rarely beats its (small) masking overhead.
PAD_WASTE_THRESHOLD = 0.25
# The default pad id probed first; the rule ALSO probes the batch's own
# most-common final-column token (tokenizers pad with eos or a dedicated
# nonzero id — hardcoding 0 would silently stand down on those corpora).
PAD_WASTE_PAD_ID = 0


def _walk_layer_kinds(obj: Any, out: set, depth: int = 0) -> None:
    """Collect ``meta['kind']`` strings from a Layer, following compound
    chains (``meta['children']``)."""
    if depth > 8 or obj is None:
        return
    meta = getattr(obj, "meta", None)
    if isinstance(meta, dict):
        kind = meta.get("kind")
        if isinstance(kind, str):
            out.add(kind)
        for child in meta.get("children", ()) or ():
            _walk_layer_kinds(child, out, depth + 1)


def _packing_capable(trace: PipelineTrace) -> bool:
    """True when the model can consume a packed batch: it is built from
    transformer blocks (segment-aware attention lives there), so the
    fix for a pad-heavy batch is ``utils.data.pack_documents``, not a
    model change."""
    kinds: set = set()
    pipe = trace.pipe
    for attr in ("block", "pre", "post"):
        _walk_layer_kinds(getattr(pipe, attr, None), kinds)
    for stage_layers in (getattr(pipe, "layers", None) or ()):
        _walk_layer_kinds(stage_layers, kinds)
    return "transformer_block" in kinds


def _check_pad_waste(trace: PipelineTrace) -> List[Finding]:
    """WARNING when the traced step's CONCRETE batch carries a trailing-
    pad fraction above :data:`PAD_WASTE_THRESHOLD` and the model is
    packing-capable — every pad position bills full attention/MLP FLOPs
    for zero gradient signal.  Stands down when ``segment_ids`` are
    present (the batch IS packed), when the sample is abstract (shapes
    carry no values), and on non-transformer models."""
    x = trace.x_sample
    if x is None:
        return []
    if isinstance(x, dict) and "segment_ids" in x:
        return []  # packed batch: the fix is already applied
    leaves = [
        leaf for leaf in jax.tree_util.tree_leaves(x)
        if (
            hasattr(leaf, "dtype") and hasattr(leaf, "shape")
            and not isinstance(leaf, jax.ShapeDtypeStruct)
            and not isinstance(leaf, jax.core.Tracer)
            and getattr(leaf, "ndim", 0) == 2
            and jnp.issubdtype(leaf.dtype, jnp.integer)
        )
    ]
    if not leaves or not _packing_capable(trace):
        return []
    import numpy as np

    from torchgpipe_tpu.utils.data import real_token_fraction

    out: List[Finding] = []
    for leaf in leaves:
        a = np.asarray(leaf)
        if a.size == 0:
            continue
        # Candidate pad ids: the declared default plus the batch's own
        # most-common final-column value (eos-padded corpora).  ONE
        # definition of "trailing pad" shared with the MFU scale.
        last = a[:, -1]
        vals, counts = np.unique(last, return_counts=True)
        candidates = {PAD_WASTE_PAD_ID, int(vals[np.argmax(counts)])}
        frac, pad_id = max(
            (1.0 - real_token_fraction(a, pad_id=c), c)
            for c in candidates
        )
        if frac > PAD_WASTE_THRESHOLD:
            out.append(Finding(
                rule="pad-waste",
                severity=Severity.WARNING,
                path="batch",
                message=(
                    f"{frac:.0%} of the sample batch's {a.shape} token "
                    f"positions are trailing pad (pad id {pad_id}) — "
                    "every one bills full attention/MLP FLOPs for zero "
                    "gradient signal, and this model is "
                    "packing-capable: pack the corpus with "
                    "utils.data.pack_documents (segment-aware "
                    "attention masks + per-document position resets; "
                    "docs/tuning.md, packing section)"
                ),
            ))
            break  # one finding per batch, not per token plane
    return out


# --------------------------------------------------------------------- #
# host-sync-in-loop                                                     #
# --------------------------------------------------------------------- #


def _check_host_sync(trace: PipelineTrace) -> List[Finding]:
    out: List[Finding] = []
    for prog in trace.programs:
        for site in jx.walk_eqns(prog.jaxpr.jaxpr):
            name = site.eqn.primitive.name
            if name not in jx.HOST_CALLBACK_PRIMS:
                continue
            if prog.kind in (SPMD_TRAIN, FUSED_TRAIN):
                in_loop = site.within_any(jx.LOOP_PRIMS)
                out.append(Finding(
                    rule="host-sync-in-loop",
                    severity=Severity.ERROR if in_loop else Severity.WARNING,
                    path=prog.path,
                    eqn=site.index,
                    primitive=name,
                    message=(
                        f"{name} inside the pipelined loop body — every "
                        "tick round-trips to the Python host, serializing "
                        "the device stream (the schedule's overlap is lost)"
                        if in_loop
                        else f"{name} in the compiled step — each call "
                        "synchronizes with the Python host once per step"
                    ),
                ))
            elif prog.kind == STAGE_FORWARD:
                out.append(Finding(
                    rule="host-sync-in-loop",
                    severity=Severity.WARNING,
                    path=prog.path,
                    eqn=site.index,
                    primitive=name,
                    message=(
                        f"{name} in a stage program — it fires once per "
                        "CELL (m micro-batches x this stage, every step), "
                        "and each firing blocks JAX's async dispatch, "
                        "which is what hides the MPMD schedule's latency"
                    ),
                ))
    return out


# --------------------------------------------------------------------- #
# dead-code (dead outputs / unused params)                              #
# --------------------------------------------------------------------- #


def _dce(closed: Any) -> Optional[Tuple[Any, List[bool]]]:
    """jax's own recursive DCE: (pruned jaxpr, per-invar used mask)."""
    from jax._src.interpreters import partial_eval as pe

    try:
        return pe.dce_jaxpr(
            closed.jaxpr, [True] * len(closed.jaxpr.outvars)
        )
    except Exception:  # pragma: no cover - DCE is best-effort
        return None


def _first_dead_matmul(jaxpr: Any) -> Optional[Tuple[int, str, Tuple[str, ...]]]:
    """Local liveness walk for an anchor: the first equation (any depth)
    whose outputs are never consumed and whose primitive is compute-heavy."""
    best: Optional[Tuple[int, str, Tuple[str, ...]]] = None
    for sub in jx.iter_jaxprs(jaxpr):
        live = {v for v in sub.outvars if type(v).__name__ != "Literal"}
        dead_sites: List[Tuple[int, Any]] = []
        for i in range(len(sub.eqns) - 1, -1, -1):
            eqn = sub.eqns[i]
            outs = [o for o in eqn.outvars if type(o).__name__ == "Var"]
            if getattr(eqn, "effects", None) or any(o in live for o in outs):
                for v in eqn.invars:
                    if type(v).__name__ == "Var":
                        live.add(v)
            else:
                dead_sites.append((i, eqn))
        for i, eqn in dead_sites:
            if eqn.primitive.name in jx.MATMUL_PRIMS:
                cand = (i, eqn.primitive.name, ())
                if best is None:
                    best = cand
    return best


def _check_dead_code(trace: PipelineTrace) -> List[Finding]:
    out: List[Finding] = []
    kinds = (STAGE_FORWARD, SPMD_TRAIN)
    for prog in trace.programs:
        if prog.kind not in kinds:
            continue
        res = _dce(prog.jaxpr)
        if res is None:
            continue
        pruned, used = res
        # Unused parameter leaves: the first len(param_leaf_names) invars
        # are the flattened params (trace.py keeps them first).
        names = prog.param_leaf_names or ()
        for i, name in enumerate(names):
            if i < len(used) and not used[i]:
                out.append(Finding(
                    rule="dead-code",
                    severity=Severity.WARNING,
                    path=prog.path,
                    message=(
                        f"parameter leaf {name} is never read by the "
                        "program — it still occupies device memory and "
                        "optimizer state (and under FSDP, gather "
                        "bandwidth) every step"
                    ),
                ))
        # Dead compute: compare compute-heavy primitive counts before and
        # after jax's recursive DCE.
        before = jx.prim_counts(prog.jaxpr.jaxpr, jx.MATMUL_PRIMS)
        after = jx.prim_counts(pruned, jx.MATMUL_PRIMS)
        for prim in jx.MATMUL_PRIMS:
            n_dead = before[prim] - after[prim]
            if n_dead > 0:
                anchor = _first_dead_matmul(prog.jaxpr.jaxpr)
                out.append(Finding(
                    rule="dead-code",
                    severity=Severity.WARNING,
                    path=prog.path,
                    eqn=anchor[0] if anchor else None,
                    primitive=prim,
                    message=(
                        f"{n_dead} {prim} equation(s) compute outputs "
                        "nothing consumes (dead-code elimination removes "
                        "them, but on the per-cell MPMD path each stage "
                        "still traces, compiles and schedules them; "
                        "drop the dead branch from the layer)"
                    ),
                ))
    return out


# --------------------------------------------------------------------- #
# remat-policy-names (silent no-op named-save policies)                 #
# --------------------------------------------------------------------- #


def _named_save_points(trace: PipelineTrace) -> set:
    """Every ``checkpoint_name`` tag occurring in any traced program."""
    names = set()
    for prog in trace.programs:
        for site in jx.walk_eqns(prog.jaxpr.jaxpr):
            if site.eqn.primitive.name == "name":
                names.add(site.eqn.params.get("name"))
    return names


def _check_remat_policy_names(trace: PipelineTrace) -> List[Finding]:
    """A named-save remat policy whose name set never occurs in the
    traced program saves NOTHING: the engine silently degrades to full
    recompute ('always' cost) — or, under ``checkpoint='offload'``,
    offloads nothing while claiming to.  Policies declare their names via
    :class:`torchgpipe_tpu.checkpoint.NamedSavePolicy` (the presets in
    ``checkpoint.policies``); opaque callables are not inspectable and
    are skipped."""
    policy = getattr(trace.pipe, "remat_policy", None)
    declared = getattr(policy, "names", None)
    if not declared or not trace.programs:
        return []
    present = _named_save_points(trace)
    missing = [n for n in declared if n not in present]
    if not missing:
        return []
    if len(missing) == len(declared):
        return [Finding(
            rule="remat-policy-names",
            severity=Severity.ERROR,
            path="remat_policy",
            message=(
                f"remat policy {getattr(policy, 'label', policy)!r} saves "
                f"only the checkpoint-named values {list(declared)}, but "
                "NONE of those names occur in the traced program — the "
                "policy is a silent no-op (every intermediate is "
                "recomputed; under 'offload', nothing reaches host "
                "memory).  Tag the model's intermediates with "
                "jax.ad_checkpoint.checkpoint_name (the framework "
                "transformer block tags attn_out/mlp_hidden/ce_logits), "
                "or pick a structural policy like "
                "checkpoint.policies.dots_no_batch"
            ),
        )]
    if getattr(policy, "default_preset", False):
        # Engine-installed catch-all (e.g. the 'offload' default covers
        # every canonical tag): absent individual names are expected.
        return []
    return [Finding(
        rule="remat-policy-names",
        severity=Severity.WARNING,
        path="remat_policy",
        message=(
            f"remat policy {getattr(policy, 'label', policy)!r} names "
            f"{missing} which never occur in the traced program (present "
            f"named save points: {sorted(present) or 'none'}); those "
            "entries save nothing"
        ),
    )]


# --------------------------------------------------------------------- #
# dispatch-per-step                                                     #
# --------------------------------------------------------------------- #


def _check_dispatch_per_step(trace: PipelineTrace) -> List[Finding]:
    """WARNING: a guarded train loop that re-enters Python once per
    optimizer step on a pipe where ``megastep`` is available and
    certified.

    Fires when the pipe declares ``megastep == 1`` AND a DONATED train
    step was built (``make_train_step(donate=True)`` — the engines
    record ``_train_step_donate``): donation already forfeits
    StepGuard's per-step retry/skip-restore (retry needs undonated
    inputs, and skip-restore needs the old params to survive), so
    nothing is lost by compiling K steps into one program — the
    per-step Python dispatch and host sync are pure overhead.

    Stand-downs (each deliberate):

    * ``donate=False`` — the user opted into StepGuard's per-step
      retry/skip-restore semantics, which NEED the Python boundary
      between steps; megastep would coarsen the retry granularity they
      asked for;
    * no train step built — nothing to judge;
    * MPMD per-cell scheduler (``fused=False``) — megastep requires the
      whole step to be one program;
    * the pipe's own schedule graph fails ``verify_ordering`` — do not
      recommend compiling K copies of a broken schedule.
    """
    pipe = trace.pipe
    if int(getattr(pipe, "megastep", 1) or 1) > 1:
        return []
    if getattr(pipe, "_train_step_donate", None) is not True:
        return []
    if trace.engine == "mpmd" and not getattr(pipe, "fused", False):
        return []
    try:
        from torchgpipe_tpu.analysis import events as ev
        from torchgpipe_tpu.analysis import schedule as sched

        if sched.verify_ordering(ev.events_for(pipe)):
            return []
    except Exception:  # noqa: BLE001 - can't certify, stand down
        return []
    return [Finding(
        rule="dispatch-per-step",
        severity=Severity.WARNING,
        path=f"{trace.engine}/train_step",
        message=(
            "the training loop re-enters Python once per optimizer step "
            "(megastep=1) on a pipe whose donated train step already "
            "forfeits per-step StepGuard retry — compile K steps into "
            "one program with make_train_step(megastep=K) (or declare "
            "megastep= on the pipe): per-step dispatch, host sync and "
            "guard bookkeeping drop K-fold, NaN skip-step moves inside "
            "the scan, and checkpoint/preemption hooks run at megastep "
            "boundaries (docs/tuning.md, megastep section).  Keep "
            "megastep=1 only when StepGuard's per-step transient-retry "
            "granularity is required — then build the step with "
            "donate=False, which stands this rule down"
        ),
    )]


# --------------------------------------------------------------------- #
# capacity-overflow                                                     #
# --------------------------------------------------------------------- #

# Expected-drop fraction above which a capacity-factor MoE dispatch is
# flagged: below it the truncation is routing noise the auxiliary
# balance loss absorbs; above it the layer silently zeroes a material
# share of its tokens every step (capacity overflow drops tokens, it
# does not error).
CAPACITY_OVERFLOW_THRESHOLD = 0.10

# Probe token count when the trace carries no concrete token plane: the
# capacity formula's ceil() rounds to the same drop fraction for any
# large t, so one asymptotic probe is representative.
_CAPACITY_PROBE_TOKENS = 4096


def _moe_lane_tokens(trace: PipelineTrace) -> Optional[int]:
    """Lane-local tokens at the MoE dispatch: per-micro-batch rows
    (batch over chunks x dp x ep) times sequence length, read off the
    traced input spec — the shape the engine computes capacity from.
    None when no 2-D token plane is visible."""
    leaves = [
        a for a in jax.tree_util.tree_leaves(trace.x_spec)
        if getattr(a, "ndim", 0) >= 2
    ]
    if not leaves:
        return None
    b, s = int(leaves[0].shape[0]), int(leaves[0].shape[1])
    width = max(int(trace.chunks or 1), 1)
    pipe = trace.pipe
    if trace.engine == "spmd":
        for ax in ("dp_axis", "ep_axis"):
            name = getattr(pipe, ax, None)
            if name:
                width *= int(pipe.mesh.shape[name])
    rows = max(b // width, 1)
    return rows * s


def _check_capacity_overflow(trace: PipelineTrace) -> List[Finding]:
    """The MoE dispatch-capacity rule, from the layer's static
    ``meta['moe']`` record (the same discovery path the planner and the
    sharding comm model use — :func:`analysis.events.find_moe_meta`):

    * ERROR — ``top_k > n_experts``: the router cannot pick k distinct
      experts from fewer than k; the top_k selection repeats experts and
      the combine double-counts them.
    * ERROR — an expert-parallel layer whose ``n_experts`` does not
      divide the pipe's ep width: ``validate_mesh`` refuses this mesh at
      run time; surface it statically.
    * WARNING — the expected drop fraction under balanced routing,
      ``1 - slots / demand`` with ``slots = n_experts * capacity`` and
      ``demand = top_k * tokens`` (token-choice) or ``tokens``
      (expert-choice), exceeds :data:`CAPACITY_OVERFLOW_THRESHOLD`:
      even a PERFECT router must drop that share every step.  Dropless
      dispatch has no capacity and stands down.
    """
    from torchgpipe_tpu.analysis import events as ev

    pipe = trace.pipe
    metas: List[Dict[str, Any]] = []
    for attr in ("block", "pre", "post"):
        metas.extend(ev.find_moe_meta(getattr(pipe, attr, None)))
    for lyr in (getattr(pipe, "layers", None) or ()):
        metas.extend(ev.find_moe_meta(lyr))
    if not metas:
        return []
    ep = 1
    if trace.engine == "spmd" and getattr(pipe, "ep_axis", None):
        ep = int(pipe.mesh.shape[pipe.ep_axis])
    lane_tokens = _moe_lane_tokens(trace)
    out: List[Finding] = []
    for i, m in enumerate(metas):
        E, K = int(m["n_experts"]), int(m["top_k"])
        path = f"{trace.engine}/moe[{i}]"
        if K > E:
            out.append(Finding(
                rule="capacity-overflow",
                severity=Severity.ERROR,
                path=path,
                message=(
                    f"top_k={K} exceeds n_experts={E} — the router "
                    "cannot select k distinct experts from fewer than "
                    "k; the top-k picks repeat experts and the combine "
                    "double-counts their outputs"
                ),
            ))
            continue
        if m.get("ep_axis") and ep > 1 and E % ep != 0:
            out.append(Finding(
                rule="capacity-overflow",
                severity=Severity.ERROR,
                path=path,
                message=(
                    f"n_experts={E} does not divide by the mesh's "
                    f"ep={ep} — validate_mesh refuses this mesh at run "
                    "time (each ep lane owns n_experts/ep experts); "
                    "choose n_experts divisible by ep or narrow the "
                    "expert axis"
                ),
            ))
            continue
        if m.get("dispatch") == "dropless":
            continue  # no capacity buffer, nothing to drop
        t = lane_tokens or _CAPACITY_PROBE_TOKENS
        cap = ev.moe_capacity(m, t)
        demand = t if m.get("router") == "expert_choice" else K * t
        drop = max(0.0, 1.0 - (E * cap) / max(demand, 1))
        if drop > CAPACITY_OVERFLOW_THRESHOLD:
            cf = float(m["capacity_factor"])
            out.append(Finding(
                rule="capacity-overflow",
                severity=Severity.WARNING,
                path=path,
                message=(
                    f"capacity_factor={cf:g} gives each of the {E} "
                    f"experts {cap} slots for {demand} routed "
                    f"assignments per lane ({t} tokens, top_k={K}) — "
                    f"even a perfectly balanced router must drop "
                    f"{drop:.0%} of them every step (capacity overflow "
                    "zeroes tokens silently, it does not error); raise "
                    "capacity_factor toward 1.0+, or switch to "
                    "dispatch='dropless' which has no capacity"
                ),
            ))
    return out


# --------------------------------------------------------------------- #
# registry + runner                                                     #
# --------------------------------------------------------------------- #

RULES: List[Rule] = [
    Rule(
        "remat-coverage",
        "checkpoint-configured stages must contain remat regions whose "
        "recompute body matches the forward body",
        _check_remat_coverage,
    ),
    Rule(
        "precision-drift",
        "under a low-precision compute policy, no float32 matmuls in "
        "compute regions and no low-precision norm statistics",
        _check_precision_drift,
    ),
    Rule(
        "collective-mismatch",
        "collective axis names must exist in the mesh; no reductions over "
        "the pipeline axis inside the schedule loop",
        _check_collective_mismatch,
    ),
    Rule(
        "recompilation-hazard",
        "micro-batches must share one shape signature (one compiled "
        "program per stage)",
        _check_recompilation,
    ),
    Rule(
        "pad-waste",
        "a packing-capable model's concrete sample batch should not "
        "carry a trailing-pad fraction above the threshold — pack the "
        "corpus (utils.data.pack_documents) instead of billing pad "
        "FLOPs; stands down when segment_ids are present or the sample "
        "is abstract",
        _check_pad_waste,
    ),
    Rule(
        "host-sync-in-loop",
        "no host callbacks inside the pipelined body",
        _check_host_sync,
    ),
    Rule(
        "dead-code",
        "no unused parameter leaves, no dead compute-heavy equations",
        _check_dead_code,
    ),
    Rule(
        "remat-policy-names",
        "a named-save remat policy must reference checkpoint names that "
        "occur in the traced program (no silent no-op policies)",
        _check_remat_policy_names,
    ),
    Rule(
        "dispatch-per-step",
        "a donated train step on a megastep-capable pipe should not "
        "re-enter Python per optimizer step (make_train_step(megastep=K) "
        "compiles K steps into one program); stands down when "
        "donate=False keeps StepGuard's per-step retry semantics",
        _check_dispatch_per_step,
    ),
    Rule(
        "capacity-overflow",
        "an MoE layer's static capacity must not force a material "
        "expected drop rate even under balanced routing, top_k must "
        "not exceed n_experts, and n_experts must divide the ep width "
        "(validate_mesh's run-time refusal, surfaced statically)",
        _check_capacity_overflow,
    ),
]


def _register_schedule_rules() -> None:
    """The schedule-level rule family (event-graph IR analyses) lives in
    :mod:`torchgpipe_tpu.analysis.schedule`; registering here keeps ONE
    rule registry for the API, the CLI and CI."""
    from torchgpipe_tpu.analysis import schedule as sched

    RULES.extend([
        Rule(
            "schedule-deadlock",
            "the configured scheduler's event graph must be cycle-free, "
            "every receive matched by its send (FIFO order, channel keys "
            "and collective permutations consistent)",
            sched.check_schedule_order,
        ),
        Rule(
            "donation-safety",
            "buffers donated through make_train_step(donate=) or freed by "
            "the schedule (vjp residuals, offload relocation) must have "
            "no read reachable after the consuming event",
            sched.check_donation,
        ),
        Rule(
            "memory-certification",
            "the event-graph certified per-stage high-water mark must "
            "agree with tune.py's eval_shape residual accounting and fit "
            "a declared HBM budget",
            sched.check_memory,
        ),
        Rule(
            "engine-equivalence",
            "MPMD and SPMD event graphs for the same model/chunks must be "
            "bisimilar up to schedule (same cells, same data dependencies)",
            sched.check_engine_equivalence,
        ),
    ])

_register_schedule_rules()


def _register_planner_rules() -> None:
    """The planner's drift rule (analysis.planner) — same single-registry
    treatment as the schedule family."""
    from torchgpipe_tpu.analysis import planner

    RULES.append(Rule(
        "plan-drift",
        "a pipe declaring hbm_budget_bytes must not run a configuration "
        "more than 10% below the planner's certified top plan "
        "(balance x schedule x chunks x remat)",
        planner.check_plan_drift,
    ))


_register_planner_rules()


def _register_sharding_rules() -> None:
    """The sharding-layout rule family (analysis.sharding) — same
    single-registry treatment as the schedule and planner families."""
    from torchgpipe_tpu.analysis import sharding as shd

    RULES.append(Rule(
        "implicit-reshard",
        "every param leaf must resolve through the partition-rule table "
        "(unmatched leaf = silent replication: ERROR), resolved specs "
        "must name existing mesh axes, and the propagated layout must "
        "induce no resharding collective inside the step (WARNING)",
        shd.check_implicit_reshard,
    ))
    RULES.append(Rule(
        "redundant-gather",
        "a gather-at-use (ZeRO-3/fsdp storage) leaf must not be "
        "re-gathered per use-site inside one block body when no write "
        "intervenes (WARNING under gather_schedule='use'), and the "
        "gathered window alone must fit the declared hbm_budget_bytes "
        "(ERROR — sharded storage cannot save a layout whose transient "
        "gathered copies don't fit)",
        shd.check_redundant_gather,
    ))


_register_sharding_rules()


def _check_dispatch_only_timeline(trace: PipelineTrace) -> List[Finding]:
    # Imported at CALL time: obs.reconciliation itself imports the analysis
    # package (for the event-graph cost model), so binding it at module
    # import would be a cycle.
    from torchgpipe_tpu.obs.reconciliation import check_dispatch_only_timeline

    return check_dispatch_only_timeline(trace)


def _check_stale_cost_model(trace: PipelineTrace) -> List[Finding]:
    # Call-time import for the same obs/analysis cycle reason as
    # _check_dispatch_only_timeline above.
    from torchgpipe_tpu.obs.costmodel import check_stale_cost_model

    return check_stale_cost_model(trace)


def _register_obs_rules() -> None:
    """The runtime-telemetry rules (obs.reconcile / obs.costmodel) —
    same single-registry treatment as the schedule and planner
    families."""
    RULES.append(Rule(
        "dispatch-only-timeline",
        "a sync=False Timeline records dispatch intervals, not device "
        "durations — simulate_pipeline/obs.reconcile projections over it "
        "assume true per-cell device times; stands down on sync=True",
        _check_dispatch_only_timeline,
    ))
    RULES.append(Rule(
        "stale-cost-model",
        "a measured CostModel attached for drift checks must match the "
        "pipe's current config fingerprint (schedule/chunks/remat/"
        "balance/mesh widths) — a stale model silently degrades "
        "planner.plan(cost_model=...) and drift checks to analytic "
        "pricing; stands down when no model is attached or it is fresh",
        _check_stale_cost_model,
    ))


_register_obs_rules()

RULES_BY_NAME: Dict[str, Rule] = {r.name: r for r in RULES}


def register_rule(rule: Rule) -> Rule:
    """Add a custom rule to the registry (it then runs by default and is
    selectable by name in ``lint(rules=...)`` and the CLI's ``--rules``)."""
    if rule.name in RULES_BY_NAME:
        raise ValueError(f"rule {rule.name!r} is already registered")
    RULES.append(rule)
    RULES_BY_NAME[rule.name] = rule
    return rule


def validate_rule_names(rules: Optional[Sequence[str]]) -> None:
    """Raise a didactic error for unknown rule names (shared by the API —
    BEFORE the expensive trace — and the CLI)."""
    if rules is None:
        return
    unknown = [r for r in rules if r not in RULES_BY_NAME]
    if unknown:
        raise ValueError(
            f"unknown lint rule(s) {unknown}; known rules: "
            f"{', '.join(sorted(RULES_BY_NAME))}"
        )


def run_rules(
    trace: PipelineTrace, rules: Optional[Sequence[str]] = None
) -> List[Finding]:
    """Run the selected rules (default: all) over a trace.

    Trace-time failures (``trace.errors``) are included — filtered to the
    selected rules, except ``trace-error`` findings which always surface
    (a program that cannot trace cannot be linted).
    """
    validate_rule_names(rules)
    selected = (
        list(RULES)
        if rules is None
        else [RULES_BY_NAME[name] for name in rules]
    )
    names = {r.name for r in selected}
    out = [
        f
        for f in trace.errors
        if f.rule == "trace-error" or f.rule in names
    ]
    for rule in selected:
        out.extend(rule.check(trace))
    return out
