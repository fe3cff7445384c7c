"""Static step autotuner: pick the training config without touching a device.

The optimization frontier named by the round-5 hardware verdict — remat
policy, flash in the training path, batch/chunk sweep — is a search over
discrete configs whose cost would otherwise be paid in full-size TPU
compiles (minutes per infeasible config).  Everything that search needs
is *statically knowable* on any host:

* **FLOPs** from XLA's HLO cost analysis (``lower()`` only traces) —
  including the
  per-policy RECOMPUTE cost, because the lowered per-cell vjp contains
  the remat region's replay;
* **residual/peak bytes** from ``jax.eval_shape`` over the cell's vjp
  closure and, where a compile is affordable, XLA's compiled memory analysis
  (``balance/profile.py``'s mechanism) — the two are cross-checked
  against each other in ``tests/test_tune.py``.

:func:`tune_step` sweeps (remat policy × micro-batch count × CE chunk
size) for a pipeline, rejects candidates whose predicted per-stage
residents exceed the HBM budget, and ranks the rest by predicted MFU.
``tools/tune_report.py`` prints the frontier table.

Prediction model (documented so the numbers are auditable):

* ``model_flops`` — the un-pipelined fwd+loss+bwd (the MFU numerator;
  recompute counts *against* utilization, never inflates it);
* per-lane work = ``m × cell_flops(policy) + epilogue/n`` where
  ``cell_flops`` is the HLO cost of one micro-batch cell's
  forward + policy-recompute + backward;
* schedule stretch = ``(m + n - 1) / m`` (the fill-drain bubble);
* ``predicted_mfu = model_flops / (chips × per_lane_work × stretch)`` —
  chip peak cancels, so the RANKING is hardware-independent (absolute
  step seconds additionally need a peak-FLOPs figure).
"""

from __future__ import annotations

import contextlib
import dataclasses
from typing import Any, Callable, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp

Pytree = Any

GiB = 2 ** 30

# HBM headroom a config needs beyond its modeled residents: program temp,
# reserved, transient transfers (~2.4 GiB measured on an AmoebaNet-D
# training step before PR 21; not re-measured).
DEFAULT_OVERHEAD_BYTES = int(2.4 * GiB)

# Params + gradients + two Adam moments, all at the param dtype — the
# multiplier applied to parameter bytes when modeling residents (same
# role as balance/profile.py's ``param_scale``).
DEFAULT_PARAM_SCALE = 4.0

# Host overhead of ONE compiled-program launch (Python dispatch, arg
# flattening, the guard's per-step host sync), expressed in the same
# walker-FLOP unit the planner's makespan uses: ~1 ms of wall clock at
# the v5e's 197 TFLOP/s bf16 peak — a convention, not a measurement
# (ROADMAP A6).  The megastep
# axis amortizes it as ``DISPATCH_OVERHEAD_FLOPS / K`` per optimizer
# step; like OFFLOAD_RANK_TAX this is a documented RANKING device, not
# a wall-clock promise.
DISPATCH_OVERHEAD_FLOPS = 2.0e11

# Lane-time discount the slot-buffer schedules (1f1b/zb/interleaved)
# earn from scan_unroll=True: static slot/ring indices let XLA fold the
# buffer machinery and fuse across ticks — measured -14%..-33% step
# time (BENCH_NOTES round 4), modeled as a flat 20% discount.
# fill_drain measured SLOWER fully unrolled, so its unroll axis is just
# {1} and the discount never applies there.
UNROLL_LANE_DISCOUNT = 0.8


# --------------------------------------------------------------------- #
# probes: flops, bytes, memory analysis                                 #
# --------------------------------------------------------------------- #


from torchgpipe_tpu.analysis.jaxpr import avalify as _avalify  # noqa: E402


def tree_bytes(tree: Pytree) -> int:
    """Total bytes of every shaped leaf (arrays or ShapeDtypeStructs)."""
    total = 0
    for leaf in jax.tree_util.tree_leaves(tree):
        if hasattr(leaf, "shape") and hasattr(leaf, "dtype"):
            n = 1
            for d in leaf.shape:
                n *= int(d)
            total += n * jnp.dtype(leaf.dtype).itemsize
    return total


def hlo_flops(fn: Callable, *args: Pytree) -> Optional[float]:
    """HLO-cost-analysis FLOPs of ``fn(*args)`` — abstract lowering only,
    no compile, no execution (host-CPU client fallback included)."""
    specs = _avalify(args)
    for kwargs in ({}, {"backend": "cpu"}):
        try:
            devs = jax.local_devices(**kwargs) if kwargs else None
            ctx = (
                jax.default_device(devs[0])
                if devs is not None
                else contextlib.nullcontext()
            )
            with ctx:
                cost = jax.jit(fn).lower(*specs).cost_analysis()
            if isinstance(cost, (list, tuple)):
                cost = cost[0] if cost else None
            if cost is None:
                continue
            flops = float(cost.get("flops", 0.0))
            if flops > 0:
                return flops
        except Exception:  # noqa: BLE001 - probe is best-effort
            continue
    return None


def xla_memory_analysis(fn: Callable, *args: Pytree) -> Optional[Any]:
    """``CompiledMemoryStats`` of ``fn(*args)`` compiled for the host CPU
    client — argument/output/temp byte totals straight from the compiler.
    Sizes are layout-true for the shapes/dtypes involved (CPU compiles in
    seconds where a full-size TPU compile takes minutes); returns None
    when the backend doesn't implement the analysis."""
    specs = _avalify(args)
    try:
        compiled = jax.jit(fn).lower(*specs).compile()
        return compiled.memory_analysis()
    except Exception:  # noqa: BLE001 - probe is best-effort
        return None


# --------------------------------------------------------------------- #
# MPMD (GPipe) per-stage residual probes                                #
# --------------------------------------------------------------------- #


def mpmd_stage_memory_profile(
    model: Any, x: Pytree
) -> Optional[Tuple[List[int], List[int], int]]:
    """Per-stage ``eval_shape`` byte accounting of ONE micro-batch:
    ``(residual_bytes[j], input_bytes[j], last_stage_output_bytes)``.

    ``residual_bytes[j]`` is stage ``j``'s vjp residual closure (what a
    non-checkpointed cell keeps alive between the forward and backward
    schedules); ``input_bytes[j]`` is its input activation (what a
    CHECKPOINTED cell saves for recompute-ahead).  The schedule verifier's
    memory certification weights the event graph's live intervals with
    these numbers; :func:`mpmd_stage_residual_bytes` is their
    max-residual reduction."""
    try:
        from torchgpipe_tpu.layers import sequential_init

        chunks = model.chunks
        mb = jax.tree_util.tree_map(
            lambda a: jax.ShapeDtypeStruct(
                (a.shape[0] // chunks,) + a.shape[1:], a.dtype
            ),
            _avalify(x),
        )
        flat_p, flat_s, _ = jax.eval_shape(
            lambda: sequential_init(model.layers, jax.random.PRNGKey(0), mb)
        )
        resid: List[int] = []
        inputs: List[int] = []
        i = 0
        for j, part in enumerate(model.partitions):
            stage = model._pipeline.stages[j]
            p_j = flat_p[i : i + len(part)]
            s_j = flat_s[i : i + len(part)]
            i += len(part)
            y, _, _, pull = jax.eval_shape(
                lambda xx, p=p_j, s=s_j, st=stage: st.fwd_vjp(
                    p, s, xx, {}, None, 1.0 / chunks
                ),
                mb,
            )
            resid.append(tree_bytes(pull))
            inputs.append(tree_bytes(mb))
            mb = y  # next stage's input spec
        return resid, inputs, tree_bytes(mb)
    except Exception:  # noqa: BLE001 - predictor stands down, rungs attempt
        return None


def mpmd_stage_residual_bytes(model: Any, x: Pytree) -> Optional[int]:
    """Max-over-stages device bytes of ONE micro-batch's vjp residuals.

    Under ``checkpoint='except_last'`` the last micro-batch's cells keep
    their full vjp residuals alive between the forward and backward
    programs; in the per-cell engine those residuals are *program
    arguments*, so a rung whose residuals exceed HBM fails at AOT compile
    time — after minutes of remote compilation.  ``eval_shape`` predicts
    the same number in milliseconds with no compile.  ``'never'`` holds
    this per micro-batch ×chunks; ``'offload'`` holds it in HOST memory
    (device residents ~0); ``'always'`` stores nothing between programs.
    """
    profile = mpmd_stage_memory_profile(model, x)
    if profile is None:
        return None
    # Stages sit on different chips: the binding number is the max.
    return max(profile[0])


def mpmd_stage_memory_analysis(
    model: Any, x: Pytree, stage_index: int
) -> Optional[Any]:
    """XLA memory analysis of ONE stage's fwd_vjp program at the
    micro-batch shape — the compiler's own accounting of the same
    residuals :func:`mpmd_stage_residual_bytes` predicts (the residual
    closure is part of ``output_size_in_bytes``).  Compiles for the host
    CPU client; use on the heaviest stage, not in a loop."""
    try:
        from torchgpipe_tpu.layers import sequential_init

        chunks = model.chunks
        mb = jax.tree_util.tree_map(
            lambda a: jax.ShapeDtypeStruct(
                (a.shape[0] // chunks,) + a.shape[1:], a.dtype
            ),
            _avalify(x),
        )
        flat_p, flat_s, _ = jax.eval_shape(
            lambda: sequential_init(model.layers, jax.random.PRNGKey(0), mb)
        )
        i = 0
        for j, part in enumerate(model.partitions):
            stage = model._pipeline.stages[j]
            p_j = flat_p[i : i + len(part)]
            s_j = flat_s[i : i + len(part)]
            i += len(part)
            if j == stage_index:
                return xla_memory_analysis(
                    lambda pp, ss, xx, st=stage: st.fwd_vjp(
                        pp, ss, xx, {}, None, 1.0 / chunks
                    ),
                    p_j,
                    s_j,
                    mb,
                )
            y, _, _, _ = jax.eval_shape(
                lambda xx, p=p_j, s=s_j, st=stage: st.fwd_vjp(
                    p, s, xx, {}, None, 1.0 / chunks
                ),
                mb,
            )
            mb = y
        return None
    except Exception:  # noqa: BLE001 - probe is best-effort
        return None


# --------------------------------------------------------------------- #
# candidate + report                                                    #
# --------------------------------------------------------------------- #


@dataclasses.dataclass(frozen=True)
class Candidate:
    """One scored point of the (policy × chunks × CE-chunk) sweep."""

    checkpoint: str
    policy: Optional[str]  # preset label, None = engine default
    chunks: int
    ce_chunk: Optional[int]
    predicted_mfu: Optional[float]
    model_flops: Optional[float]
    step_flops: Optional[float]  # per-chip executed work incl. recompute
    resident_bytes: int  # predicted per-stage device residents
    host_bytes: int  # residuals predicted to live in host memory
    feasible: bool
    reason: str = ""

    def describe(self) -> str:
        pol = self.policy or "-"
        mfu = (
            f"{self.predicted_mfu:.4f}"
            if self.predicted_mfu is not None
            else "n/a"
        )
        status = "ok" if self.feasible else f"REJECT ({self.reason})"
        host = (
            f" +{self.host_bytes / GiB:.2f} host"
            if self.host_bytes
            else ""
        )
        return (
            f"{self.checkpoint:<12} {pol:<28} m={self.chunks:<3} "
            f"ce={self.ce_chunk or '-':<6} mfu~{mfu:<8} "
            f"{self.resident_bytes / GiB:6.2f} GiB{host}  {status}"
        )


@dataclasses.dataclass
class TuneReport:
    """Ranked sweep result: feasible candidates best-first, then rejects."""

    candidates: List[Candidate]
    hbm_budget_bytes: int

    @property
    def best(self) -> Optional[Candidate]:
        for c in self.candidates:
            if c.feasible:
                return c
        return None

    def table(self) -> str:
        head = (
            f"{'checkpoint':<12} {'policy':<28} {'m':<5} {'ce':<9} "
            f"{'pred-mfu':<12} residents (budget "
            f"{self.hbm_budget_bytes / GiB:.2f} GiB)"
        )
        return "\n".join([head] + [c.describe() for c in self.candidates])


# --------------------------------------------------------------------- #
# SPMD scoring                                                          #
# --------------------------------------------------------------------- #


def _spmd_plain_step(pipe: Any, x_spec: Pytree, tgt_spec: Pytree) -> Tuple[
    Optional[Callable], Optional[Pytree]
]:
    """The un-pipelined fwd+loss+bwd with the block loop UNROLLED (one
    block apply per stage, no scan) — the MFU numerator, costable by
    XLA's HLO cost analysis, whose while-loop handling would otherwise
    count a scanned body once (recompute counts against utilization,
    never inflates it)."""
    try:
        params_spec = jax.eval_shape(
            lambda r: pipe._init_host(r, x_spec), jax.random.PRNGKey(0)
        )
    except Exception:  # noqa: BLE001
        return None, None
    n = pipe.n_stages

    def step(params: Pytree, x: Pytree, tgt: Pytree) -> Any:
        def loss_of(params: Pytree) -> jax.Array:
            h = x
            if pipe.pre is not None:
                h, _ = pipe.pre.apply(
                    params["pre"], (), h, rng=None, train=True
                )
            for j in range(n):
                bp = jax.tree_util.tree_map(lambda a: a[j], params["blocks"])
                h, _ = pipe.block.apply(bp, (), h, rng=None, train=True)
            if pipe.post is not None:
                h, _ = pipe.post.apply(
                    pipe._tied(
                        params["post"], params.get("pre", ()), pipe._tie_post
                    ),
                    (), h, rng=None, train=True,
                )
            p_loss = pipe._tied(
                params.get("loss", ()), params.get("pre", ()), pipe._tie_loss
            )
            return pipe._loss_call(p_loss, h, tgt)

        return jax.value_and_grad(loss_of)(params)

    return step, params_spec


def _model_flops(
    plain_step: Callable, params_spec: Pytree, x_spec: Pytree,
    tgt_spec: Pytree,
) -> Optional[float]:
    """The MFU numerator: analytic FLOPs of the un-pipelined step.

    Primary: the structure-aware jaxpr walker (the flash auto-picker's
    platform cond would be SUMMED over both branches by XLA's cost
    analysis — the walker takes the max, i.e. one executed branch).
    Falls back to HLO cost analysis when the trace fails; the two agree
    on cond-free programs (asserted in tests/test_tune.py)."""
    from torchgpipe_tpu.analysis import jaxpr as jx

    try:
        jaxpr = jax.make_jaxpr(plain_step)(params_spec, x_spec, tgt_spec)
        flops = jx.flops_estimate(jaxpr)
        if flops > 0:
            return flops
    except Exception:  # noqa: BLE001 - fall through to cost analysis
        pass
    return hlo_flops(plain_step, params_spec, x_spec, tgt_spec)


def _spmd_step_flops(
    pipe: Any, params_spec: Pytree, x_mb: Pytree, tgt_mb: Pytree
) -> Optional[float]:
    """Per-chip executed FLOPs of one REAL pipelined step — traced to a
    jaxpr and costed by the structure-aware walker
    (:func:`torchgpipe_tpu.analysis.jaxpr.flops_estimate`): the schedule
    scan multiplies by its tick count, ``cond`` tails count one branch,
    and the per-policy remat replay is present in the backward scan body
    — so recompute, bubble garbage-compute and the epilogue are all in
    the number.  XLA's own cost analysis counts loop bodies once, which
    is why the walker exists."""
    from torchgpipe_tpu.analysis import jaxpr as jx

    try:
        fn = pipe._build_train_step(use_rng=False)
        jaxpr = jax.make_jaxpr(lambda p, a, b: fn(p, a, b))(
            params_spec, x_mb, tgt_mb
        )
    except Exception:  # noqa: BLE001 - scoring stands down
        return None
    return jx.flops_estimate(jaxpr)


def spmd_param_layout_bytes(pipe: Any, params_spec: Pytree) -> int:
    """Per-device param bytes of an SPMD pipe under its RESOLVED layout
    (rule table → per-leaf spec → bytes ÷ shard widths): the one
    accounting shared by ``tune_step``'s fixed-resident model and the
    3D planner's memory certification.  Falls back to the plain
    stage-share sum if the layout cannot resolve (a user rule table
    with unmatched leaves fails loudly elsewhere)."""
    from torchgpipe_tpu.analysis import sharding as shd

    try:
        table = pipe.rule_table(params_spec)
        specs, unmatched = table.resolve(params_spec)
        if not unmatched:
            return shd.layout_bytes(
                params_spec, specs, shd.MeshSpec.from_mesh(pipe.mesh)
            )
    except Exception:  # noqa: BLE001 - accounting degrades, not tuning
        pass
    stage_params_spec = jax.tree_util.tree_map(
        lambda a: jax.ShapeDtypeStruct(a.shape[1:], a.dtype),
        params_spec["blocks"],
    )
    return tree_bytes(stage_params_spec) + sum(
        tree_bytes(params_spec[k])
        for k in ("pre", "post", "loss")
        if k in params_spec
    )


def _spmd_cell_residual_bytes(
    pipe: Any, stage_params_spec: Pytree, mb_spec: Pytree, plain: bool
) -> Optional[int]:
    """Per-cell stored residual bytes (identity-forwarded PARAM leaves
    excluded — weights exist once per stage, not once per in-flight
    cell; the same passthrough analysis the checkpoint='never' ring
    buffers use)."""
    from torchgpipe_tpu.spmd import _never_mode_spec

    fn = pipe._block_fn_plain if plain else pipe._block_fn

    def vjp_of(p: Pytree, x: Pytree) -> Any:
        _, pull = jax.vjp(lambda pp, xx: fn(pp, xx, None, 1.0, True), p, x)
        return pull

    try:
        _, leaf_specs, _, buffered = _never_mode_spec(
            vjp_of, (stage_params_spec,), mb_spec
        )
    except Exception:  # noqa: BLE001
        return None
    return sum(tree_bytes(leaf_specs[i]) for i in buffered)


def _spmd_variant(pipe: Any, checkpoint: str, policy: Any, chunks: int,
                  loss_fn: Any) -> Any:
    return dataclasses.replace(
        pipe,
        checkpoint=checkpoint,
        remat_policy=policy,
        chunks=chunks,
        loss_fn=loss_fn,
    )


def _default_spmd_space(pipe: Any) -> List[Tuple[str, Optional[str], Any]]:
    """(checkpoint, policy-label, policy) candidates — the CANONICAL
    enumeration lives in :mod:`torchgpipe_tpu.analysis.planner`
    (``spmd_remat_space``), which the joint planner and this sweep
    share so tune and plan never disagree on the searchable space."""
    from torchgpipe_tpu.analysis.planner import spmd_remat_space

    return spmd_remat_space(pipe)


def _chunk_options(pipe: Any, batch: int, requested: Optional[Sequence[int]]) -> List[int]:
    from torchgpipe_tpu.analysis.planner import spmd_chunk_options

    return spmd_chunk_options(pipe, batch, requested)


def megastep_options(
    requested: Optional[Sequence[int]] = None,
    steps: Optional[int] = None,
) -> List[int]:
    """Megastep K candidates — delegates to the planner's canonical
    space (:func:`torchgpipe_tpu.analysis.planner.megastep_options`),
    so the sweep and the lint rules share ONE definition."""
    from torchgpipe_tpu.analysis.planner import megastep_options as opts

    return opts(requested, steps)


def scan_unroll_options(schedule: str) -> List[Any]:
    """scan_unroll candidates per schedule (the planner's canonical
    space; see :data:`UNROLL_LANE_DISCOUNT` for the measured basis)."""
    from torchgpipe_tpu.analysis.planner import (
        scan_unroll_options as opts,
    )

    return opts(schedule)


def tune_step(
    pipe: Any,
    batch: Pytree,
    hbm_budget_bytes: int,
    *,
    target: Optional[Pytree] = None,
    chunks_options: Optional[Sequence[int]] = None,
    ce_chunk_options: Optional[Sequence[int]] = None,
    overhead_bytes: int = DEFAULT_OVERHEAD_BYTES,
    param_scale: float = DEFAULT_PARAM_SCALE,
) -> TuneReport:
    """Sweep (remat policy × micro-batch count × CE chunk size) for a
    pipeline and rank the HBM-feasible candidates by predicted MFU —
    entirely from HLO cost analysis and ``eval_shape``; no device is
    touched and nothing compiles for an accelerator.

    ``pipe`` is a :class:`~torchgpipe_tpu.spmd.SpmdGPipe` (fill-drain) or
    a :class:`~torchgpipe_tpu.gpipe.GPipe`; ``batch`` a representative
    input batch (arrays or ``ShapeDtypeStruct``).  CE chunk sizes are
    swept only when the pipe's loss layer declares ``meta['ce_chunk']``
    (:func:`~torchgpipe_tpu.models.transformer.chunked_lm_loss`).
    """
    from torchgpipe_tpu.gpipe import GPipe

    if isinstance(pipe, GPipe):
        return _tune_mpmd(
            pipe, batch, hbm_budget_bytes,
            chunks_options=chunks_options, overhead_bytes=overhead_bytes,
            param_scale=param_scale,
        )
    return _tune_spmd(
        pipe, batch, hbm_budget_bytes, target=target,
        chunks_options=chunks_options, ce_chunk_options=ce_chunk_options,
        overhead_bytes=overhead_bytes, param_scale=param_scale,
    )


def _tune_spmd(
    pipe: Any,
    batch: Pytree,
    hbm_budget_bytes: int,
    *,
    target: Optional[Pytree],
    chunks_options: Optional[Sequence[int]],
    ce_chunk_options: Optional[Sequence[int]],
    overhead_bytes: int,
    param_scale: float,
) -> TuneReport:
    if pipe.schedule != "fill_drain":
        raise ValueError(
            "tune_step models the fill_drain schedule (the explicit-"
            f"gradient schedules have their own memory laws); got "
            f"schedule={pipe.schedule!r}"
        )
    x_spec = _avalify(batch)
    tgt_spec = _avalify(target) if target is not None else x_spec
    n = pipe.n_stages
    dp = pipe.mesh.shape[pipe.dp_axis] if pipe.dp_axis else 1
    ep = pipe.mesh.shape[pipe.ep_axis] if pipe.ep_axis else 1
    n_chips = int(pipe.mesh.devices.size)
    B = jax.tree_util.tree_leaves(x_spec)[0].shape[0]

    if pipe.virtual_stages != 1:
        raise ValueError(
            "tune_step models one block chunk per device "
            "(virtual_stages=1); the interleaved layout has its own "
            "memory law"
        )
    plain_step, params_spec = _spmd_plain_step(pipe, x_spec, tgt_spec)
    model_flops = (
        _model_flops(plain_step, params_spec, x_spec, tgt_spec)
        if plain_step is not None
        else None
    )
    stage_params_spec = (
        jax.tree_util.tree_map(
            lambda a: jax.ShapeDtypeStruct(a.shape[1:], a.dtype),
            params_spec["blocks"],
        )
        if params_spec is not None
        else None
    )
    # Per-lane parameter/state residents (stage share + replicated
    # pre/post/loss), scaled for grads + optimizer moments — accounted
    # UNDER THE LAYOUT via the unified partition-rule layer, so tp/ep-
    # sharded leaves charge 1/width per chip (identical to the plain
    # stage-share sum when nothing beyond pp is sharded).  The planner's
    # 3D certification and ``zero_opt_state`` use the same accounting.
    param_bytes = 0
    if params_spec is not None:
        param_bytes = spmd_param_layout_bytes(pipe, params_spec)
    # The block consumes ACTIVATIONS (pre applied to the raw batch), not
    # the raw inputs — thread the full-batch spec through pre once.
    block_in_spec = x_spec
    if pipe.pre is not None and params_spec is not None:
        try:
            block_in_spec, _ = jax.eval_shape(
                lambda p, xx: pipe.pre.apply(p, (), xx, rng=None, train=True),
                params_spec["pre"], x_spec,
            )
        except Exception:  # noqa: BLE001 - probes below will stand down
            block_in_spec = None

    loss_meta = (
        pipe.loss_fn.meta
        if hasattr(pipe.loss_fn, "meta") and isinstance(
            getattr(pipe.loss_fn, "meta", None), dict
        )
        else {}
    )
    base_ce = loss_meta.get("ce_chunk")
    ce_opts: List[Optional[int]] = [base_ce]
    if base_ce is not None:
        requested = ce_chunk_options or (2048, 8192, 32768)
        ce_opts = sorted({int(c) for c in (*requested, base_ce)})

    seq_tokens = 1
    leaves = jax.tree_util.tree_leaves(x_spec)
    if leaves and len(leaves[0].shape) > 1:
        seq_tokens = int(leaves[0].shape[1])

    from torchgpipe_tpu import microbatch

    candidates: List[Candidate] = []
    for chunks in _chunk_options(pipe, B, chunks_options):
        # Per-lane micro-batch: the engine shards the batch over
        # chunks × dp × ep (spmd._check_batch's divisibility law).
        mb_spec = (
            jax.tree_util.tree_map(
                lambda a: jax.ShapeDtypeStruct(
                    (a.shape[0] // (chunks * dp * ep),) + a.shape[1:],
                    a.dtype,
                ),
                block_in_spec,
            )
            if block_in_spec is not None
            else None
        )
        mb_bytes = tree_bytes(mb_spec) if mb_spec is not None else 0
        try:
            x_mb = jax.eval_shape(
                lambda x, c=chunks: microbatch.scatter_stacked(x, c), x_spec
            )
            tgt_mb = jax.eval_shape(
                lambda x, c=chunks: microbatch.scatter_stacked(x, c), tgt_spec
            )
        except Exception:  # noqa: BLE001
            x_mb = tgt_mb = None
        T = chunks + n - 1  # schedule ticks = in-flight cell slots per lane
        step_flops_cache: dict = {}
        resid_cache: dict = {}

        def cell_resid(variant: Any, plain: bool, key: Any) -> Optional[int]:
            # The plain-block residual spec depends only on the chunks
            # (mb shape), and each remat'd spec only on its policy — one
            # eval_shape vjp trace per distinct key, not per sweep row.
            if key not in resid_cache:
                resid_cache[key] = _spmd_cell_residual_bytes(
                    variant, stage_params_spec, mb_spec, plain=plain
                )
            return resid_cache[key]
        for ckpt_mode, label, policy in _default_spmd_space(pipe):
            try:
                variant = _spmd_variant(
                    pipe, ckpt_mode, policy, chunks, pipe.loss_fn
                )
            except Exception as e:  # noqa: BLE001 - invalid combo
                candidates.append(Candidate(
                    checkpoint=ckpt_mode, policy=label, chunks=chunks,
                    ce_chunk=base_ce, predicted_mfu=None, model_flops=None,
                    step_flops=None, resident_bytes=0, host_bytes=0,
                    feasible=False, reason=f"build: {e}",
                ))
                continue
            remat = ckpt_mode in ("always", "offload", "except_last")
            # Executed work: the traced REAL step (schedule scan × ticks,
            # per-policy remat replay, epilogue).  'except_last' is scored
            # as its remat'd sibling — its peeled tail's cond would
            # otherwise hide (m-1)/m of the recompute behind a max().
            flops_key = (
                "always" if ckpt_mode == "except_last" else ckpt_mode, label
            )
            if flops_key not in step_flops_cache:
                scored_variant = (
                    _spmd_variant(pipe, "always", policy, chunks, pipe.loss_fn)
                    if ckpt_mode == "except_last"
                    else variant
                )
                step_flops_cache[flops_key] = (
                    _spmd_step_flops(scored_variant, params_spec, x_mb, tgt_mb)
                    if x_mb is not None
                    else None
                )
            step_flops = step_flops_cache[flops_key]
            # The remat'd residual spec depends only on the POLICY (the
            # wrapped block is identical across always/except_last), so
            # the cache keys on the policy label alone.
            resid_full = cell_resid(variant, True, "plain")
            resid_cell = (
                cell_resid(variant, False, ("remat", label))
                if remat
                else resid_full
            )
            if resid_cell is None or resid_full is None:
                candidates.append(Candidate(
                    checkpoint=ckpt_mode, policy=label, chunks=chunks,
                    ce_chunk=base_ce, predicted_mfu=None, model_flops=None,
                    step_flops=None, resident_bytes=0, host_bytes=0,
                    feasible=False, reason="residual probe failed",
                ))
                continue
            if ckpt_mode == "offload" and not getattr(
                variant.remat_policy, "offload", False
            ):
                # The installed jax lacks the offload save policy and the
                # preset degraded to device-resident saves
                # (checkpoint._offload_policy_or_fallback): NO host
                # credit — the residuals stay in HBM and the candidate
                # must be judged on that.
                host_cell = 0
            elif ckpt_mode == "offload":
                # Named points ride to host; the device keeps only what a
                # nothing-saveable remat would (inputs/carries).
                nothing = _spmd_variant(
                    pipe, "always", None, chunks, pipe.loss_fn
                )
                device_cell = cell_resid(nothing, False, ("remat", None))
                if device_cell is None:
                    # Probe failed: grant NO offload credit — the
                    # candidate is scored with its full residuals
                    # device-resident (conservative; a 0-byte result is
                    # legitimate and taken as-is).
                    host_cell = 0
                else:
                    host_cell = max(resid_cell - device_cell, 0)
                    resid_cell = device_cell
            else:
                host_cell = 0
            if ckpt_mode == "except_last":
                act_bytes = (T - 1) * resid_cell + resid_full
            elif ckpt_mode == "never":
                act_bytes = T * resid_full
            else:
                act_bytes = T * resid_cell
            for ce in ce_opts:
                tile = 0
                if base_ce is not None and ce is not None:
                    # Loss phase is pp-sharded: tokens/lane × chunk tile.
                    tile = (B * seq_tokens // max(n * dp * ep, 1)) * ce * 4
                resident = int(
                    param_bytes * param_scale
                    + act_bytes
                    + T * mb_bytes  # stacked per-tick outputs (scan ys)
                    + tile
                    + overhead_bytes
                )
                feasible = resident <= hbm_budget_bytes
                reason = "" if feasible else "over HBM budget"
                mfu = None
                if model_flops is not None and step_flops:
                    mfu = model_flops / (n_chips * step_flops)
                candidates.append(Candidate(
                    checkpoint=ckpt_mode, policy=label, chunks=chunks,
                    ce_chunk=ce if base_ce is not None else None,
                    predicted_mfu=mfu, model_flops=model_flops,
                    step_flops=step_flops, resident_bytes=resident,
                    host_bytes=T * host_cell, feasible=feasible,
                    reason=reason,
                ))
    return _ranked(candidates, hbm_budget_bytes)


# --------------------------------------------------------------------- #
# MPMD scoring                                                          #
# --------------------------------------------------------------------- #

_MODE_RECOMPUTE = {
    # Micro-batches whose cells replay their forward in the backward
    # schedule (recompute-ahead); the forward is ~1/3 of a fwd+bwd step,
    # so the work multiplier is 1 + stop/m/3.
    "always": lambda m: m,
    "except_last": lambda m: m - 1,
    "never": lambda m: 0,
    "offload": lambda m: 0,
}

# Conservative throughput tax charged to 'offload' when RANKING MPMD
# rungs: the host round-trip of every cell's residuals is asynchronous
# but not free, and is unvalidated on hardware — rank it below a
# measured-fast rung of comparable shape until a hardware number exists.
OFFLOAD_RANK_TAX = 0.3


def score_mpmd(
    model: Any,
    x: Pytree,
    capacity_bytes: Optional[int],
    *,
    overhead_bytes: int = DEFAULT_OVERHEAD_BYTES,
    fused: bool = False,
) -> Candidate:
    """Score ONE built GPipe config: an analytic throughput rank (work
    multiplier × fill-drain stretch) plus, when ``capacity_bytes`` is
    given, eval_shape residual feasibility.  ``capacity_bytes=None``
    skips the residual probe entirely — the probe eval_shape-traces every
    stage (~a minute for the full amoebanet)."""
    m = model.chunks
    n = len(model.partitions)
    B = jax.tree_util.tree_leaves(_avalify(x))[0].shape[0]
    mode = model.checkpoint
    resid = None
    host = 0
    if (
        capacity_bytes is not None
        and not fused
        and mode in ("except_last", "never", "offload")
    ):
        resid = mpmd_stage_residual_bytes(model, x)
    act_bytes = 0
    if resid is not None:
        if mode == "never":
            act_bytes = resid * m
        elif mode == "offload":
            host = resid * m
        else:
            act_bytes = resid
    resident = act_bytes + overhead_bytes
    feasible = capacity_bytes is None or resident <= capacity_bytes
    stop = _MODE_RECOMPUTE.get(mode, lambda m: m)(m)
    work_mult = 1.0 + (stop / m) / 3.0
    if mode == "offload" and not fused:
        work_mult *= 1.0 + OFFLOAD_RANK_TAX
    stretch = (m + n - 1) / m
    # Rank: recompute × bubble cost, batch-weighted SUB-linearly — the
    # measured amoebanet ladder shows per-chip samples/s growing with
    # batch well below linearly (360 -> 442 samples/s for 64 -> 128:
    # fixed overheads amortize and MXU tiles fill, but per-sample work
    # is batch-independent to first order), so sqrt(B) rewards the
    # bigger rung without letting batch size alone steamroll a cheaper
    # schedule.
    rank = float(B) ** 0.5 / (work_mult * stretch)
    return Candidate(
        checkpoint=mode, policy="fused" if fused else None, chunks=m,
        ce_chunk=None, predicted_mfu=rank, model_flops=None,
        step_flops=None, resident_bytes=int(resident), host_bytes=int(host),
        feasible=feasible,
        reason="" if feasible else "residuals over HBM capacity",
    )


def _tune_mpmd(
    pipe: Any,
    batch: Pytree,
    hbm_budget_bytes: int,
    *,
    chunks_options: Optional[Sequence[int]],
    overhead_bytes: int,
    param_scale: float,
) -> TuneReport:
    """GPipe sweep: checkpoint mode × chunks at a fixed batch."""
    from torchgpipe_tpu.gpipe import GPipe

    del param_scale  # per-stage params are not modeled on MPMD (multi-chip)
    from torchgpipe_tpu.analysis.planner import (
        MPMD_CHECKPOINT_SPACE, mpmd_chunk_options,
    )

    B = jax.tree_util.tree_leaves(_avalify(batch))[0].shape[0]
    opts = mpmd_chunk_options(B, chunks_options, pipe.chunks)
    candidates = []
    for chunks in opts:
        for mode in MPMD_CHECKPOINT_SPACE:
            try:
                model = GPipe(
                    pipe.layers, balance=pipe.balance, chunks=chunks,
                    checkpoint=mode, schedule=pipe.schedule,
                    loss_reduction=pipe.loss_reduction,
                )
            except Exception as e:  # noqa: BLE001
                candidates.append(Candidate(
                    checkpoint=mode, policy=None, chunks=chunks,
                    ce_chunk=None, predicted_mfu=None, model_flops=None,
                    step_flops=None, resident_bytes=0, host_bytes=0,
                    feasible=False, reason=f"build: {e}",
                ))
                continue
            candidates.append(score_mpmd(
                model, batch, hbm_budget_bytes,
                overhead_bytes=overhead_bytes,
            ))
    return _ranked(candidates, hbm_budget_bytes)


def resolve_policy(label: Optional[str]) -> Any:
    """A preset label from a :class:`Candidate` back to its policy object
    (None for engine defaults / the offload mode's built-in)."""
    from torchgpipe_tpu.checkpoint import policies

    if label in (None, "offload_default"):
        return None
    return getattr(policies, label)


def apply_candidate(pipe: Any, cand: Candidate) -> Any:
    """Rebuild an :class:`~torchgpipe_tpu.spmd.SpmdGPipe` with a swept
    candidate's (checkpoint, policy, chunks, CE chunk) applied — what
    a caller runs after the sweep."""
    loss_fn = pipe.loss_fn
    meta = getattr(loss_fn, "meta", None)
    if (
        cand.ce_chunk is not None
        and isinstance(meta, dict)
        and meta.get("ce_chunk") not in (None, cand.ce_chunk)
        and "with_ce_chunk" in meta
    ):
        loss_fn = meta["with_ce_chunk"](cand.ce_chunk)
    return dataclasses.replace(
        pipe,
        checkpoint=cand.checkpoint,
        remat_policy=resolve_policy(cand.policy),
        chunks=cand.chunks,
        loss_fn=loss_fn,
    )


def _ranked(candidates: List[Candidate], budget: int) -> TuneReport:
    # Ties (the CE-chunk axis changes memory, not FLOPs) break toward the
    # LARGEST feasible CE chunk: fewer vocab-scan steps at the same
    # predicted MFU — the knob's whole trade is tile memory vs launch
    # overhead, so among equal-MFU feasible rows the biggest tile that
    # fits wins.
    candidates.sort(
        key=lambda c: (
            not c.feasible,
            -(c.predicted_mfu or 0.0),
            -(c.ce_chunk or 0),
        )
    )
    return TuneReport(candidates=candidates, hbm_budget_bytes=budget)


# --------------------------------------------------------------------- #
# serving: KV-cache pool accounting                                     #
# --------------------------------------------------------------------- #


def serving_cache_bytes(
    cfg: Any,
    num_slots: int,
    max_len: int,
    *,
    kv_quant: bool = False,
    dtype: Optional[Any] = None,
    chunk: int = 1,
) -> int:
    """Bytes of a ``(num_slots, max_len)`` serving KV-cache pool — the
    same ``eval_shape``-only accounting the training-side probes use (no
    allocation, no compile): the pool is laid out by
    ``models.kv_cache.init_cache`` / ``init_quant_cache``, so this IS
    the HBM the pool will pin, not an estimate.  ``init_cache`` lays a
    latent-attention model's pool out as its latent rows (``cfg.mla``:
    ``(kv_lora_rank + qk_rope_head_dim) * itemsize`` bytes a row a
    layer, not K and V of ``kv_heads * head_dim``), and the pool of a
    model that mixes window and full layers by each layer's own length
    (``kv_cache.layer_rows``: ``chunk``, the pool's largest prefill
    chunk, sizes the window layers' rings)."""
    from torchgpipe_tpu.models.kv_cache import init_cache, init_quant_cache

    if kv_quant:
        spec = jax.eval_shape(
            lambda: init_quant_cache(cfg, num_slots, max_len)
        )
    else:
        spec = jax.eval_shape(
            lambda: init_cache(cfg, num_slots, max_len, dtype=dtype,
                               chunk=chunk)
        )
    return tree_bytes(spec)


def serving_max_slots(
    cfg: Any,
    max_len: int,
    hbm_budget_bytes: int,
    *,
    kv_quant: bool = False,
    dtype: Optional[Any] = None,
    param_bytes: int = 0,
    overhead_bytes: int = 0,
    donated: bool = False,
    chunk: int = 1,
) -> int:
    """Largest slot count whose KV pool fits ``hbm_budget_bytes`` after
    ``param_bytes`` (the resident weights — ``tree_bytes(params)``) and
    ``overhead_bytes`` (allocator reserve / program temps) are set aside.
    The serving engine sizes its pool AND caps active slots at this
    value: admitting a request can never grow an array, so a pool built
    to this count is the entire memory-safety story.  Without donation
    (``donated=False``, the engine default — donated buffers cannot be
    retried on transient failures) a compiled step holds the input and
    output cache buffers simultaneously, so the pool is accounted TWICE;
    ``donated=True`` accounts the single aliased copy.  Returns 0 when
    even one slot does not fit (the caller should refuse to build)."""
    one = serving_cache_bytes(
        cfg, 1, max_len, kv_quant=kv_quant, dtype=dtype, chunk=chunk
    )
    two = serving_cache_bytes(
        cfg, 2, max_len, kv_quant=kv_quant, dtype=dtype, chunk=chunk
    )
    per_slot = two - one          # bytes strictly linear in slots
    fixed = one - per_slot        # the shared scalar bookkeeping
    copies = 1 if donated else 2  # non-donated steps double-buffer
    avail = (
        hbm_budget_bytes - param_bytes - overhead_bytes - copies * fixed
    )
    if per_slot <= 0 or avail <= 0:
        return 0
    return int(avail // (copies * per_slot))
