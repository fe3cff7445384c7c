"""Probe-free joint partition × schedule × remat planner, certified by
the event-graph verifier.

torchgpipe's balancing story is runtime profiling
(``balance/profile.py``, the ``balance_by_time`` lineage of the paper):
it costs real device time, its numbers vary with co-tenants, and it can
only measure the ONE configuration it runs — the schedule × remat
cross-product stays unexplored.  Everything a planner needs is
statically knowable (BaPipe, arXiv:2012.12544; schedule scoring by
bubble structure rather than measurement, arXiv:2412.14374), and this
repo already holds both halves: analytic FLOPs
(:func:`torchgpipe_tpu.analysis.jaxpr.flops_estimate` + ``tune.py``'s
static step accounting) and the event-graph IR every shipped scheduler
is rebuilt into (:mod:`torchgpipe_tpu.analysis.events` /
:mod:`torchgpipe_tpu.analysis.schedule`).  :func:`plan` closes the loop:

* **candidates** — balance cut (MPMD: the current cut plus the analytic
  :func:`torchgpipe_tpu.balance.balance_by_flops` cut — per-layer costs
  by abstract eval, ZERO device probes) × schedule (MPMD gpipe/1F1B;
  SPMD fill-drain/1F1B/ZB, interleaved for pipes built interleaved) ×
  micro-batch count × remat mode/policy (``offload`` included);
* **scoring** — each candidate's schedule is rebuilt as an event graph
  and scored by (a) predicted MFU from the static flop accounting
  (cell-level fwd/bwd/recompute FLOPs from traced jaxprs, numerator from
  the un-pipelined step — ``tune.py``'s conventions) over the graph's
  critical-path makespan (:func:`torchgpipe_tpu.analysis.events.makespan`),
  and (b) the bubble fraction read off the same graph;
* **certification** — the memory-certification pass
  (:func:`torchgpipe_tpu.analysis.schedule.certify_memory`) computes each
  candidate's per-rank high-water mark from the graph's live intervals
  (byte weights from ``eval_shape``, the same accounting
  ``tune.mpmd_stage_memory_profile`` cross-checks), rejecting over-budget
  candidates, and the deadlock/ordering rules
  (:func:`torchgpipe_tpu.analysis.schedule.verify_ordering`) must pass —
  every emitted plan is *certified*, not just estimated.

One call applies the winner::

    from torchgpipe_tpu.analysis import planner

    report = planner.plan(pipe, batch, hbm_budget_bytes=15 << 30)
    print(report.table())
    pipe = planner.apply_plan(pipe, report.best)

``tools/plan_report.py`` prints the frontier for the llama presets (and
is the ``plan-verify`` CI gate); the ``plan-drift`` lint rule warns when
a pipe declaring ``hbm_budget_bytes`` runs a configuration more than
:data:`PLAN_DRIFT_THRESHOLD` below its certified top plan.

Prediction model (auditable):

* per-cell atoms ``fwd`` / ``bwd`` / ``bwd_remat`` are walker FLOPs of
  the plain block forward, its vjp pullback, and the remat'd (policy-
  wrapped) vjp — so each policy's recompute replay is measured from its
  own traced jaxpr, not guessed;
* a candidate's lane time is the event graph's critical-path makespan
  under those per-event costs (fwd cells cost ``fwd``; backward cells
  ``bwd`` plus the replay when their micro-batch is checkpointed;
  zero-bubble's B/W split the backward) plus the per-lane epilogue
  share;
* ``predicted_mfu = model_flops / (n_chips × lane_time)`` — chip peak
  cancels, the RANKING is hardware-independent; the MPMD ``offload``
  mode carries ``tune.OFFLOAD_RANK_TAX`` until hardware numbers exist.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Callable, Dict, List, Optional, Sequence, Tuple, Union

import jax
from jax.sharding import PartitionSpec as _P

from torchgpipe_tpu.analysis import events as ev
from torchgpipe_tpu.analysis import schedule as sched
from torchgpipe_tpu.analysis.diagnostics import Finding, Severity
from torchgpipe_tpu.analysis.jaxpr import avalify, flops_estimate

Pytree = Any

GiB = 2 ** 30

# A configured pipe whose predicted MFU trails its certified top plan by
# more than this fraction triggers the plan-drift WARNING.
PLAN_DRIFT_THRESHOLD = 0.10


# --------------------------------------------------------------------- #
# candidate enumeration — the canonical space (tune.py sweeps this too) #
# --------------------------------------------------------------------- #

# MPMD checkpoint modes, in tune.py's sweep order.
MPMD_CHECKPOINT_SPACE: Tuple[str, ...] = (
    "except_last", "offload", "never", "always",
)


def spmd_remat_space(pipe: Any) -> List[Tuple[str, Optional[str], Any]]:
    """(checkpoint, policy-label, policy) candidates for an SPMD pipe:
    the engine's four modes plus the named-save presets on the remat'd
    mode — THE candidate axis ``tune.tune_step`` and :func:`plan` share.
    """
    del pipe  # the space is engine-wide today; kept for future narrowing
    from torchgpipe_tpu.checkpoint import policies

    return [
        ("never", None, None),
        ("except_last", None, None),
        ("always", None, None),
        ("always", "save_attn_out", policies.save_attn_out),
        ("always", "save_block_outputs", policies.save_block_outputs),
        ("always", "dots_no_batch", policies.dots_no_batch),
        ("offload", "offload_default", None),
    ]


def spmd_chunk_options(
    pipe: Any, batch_size: int, requested: Optional[Sequence[int]],
    dp: Optional[int] = None, ep: Optional[int] = None,
) -> List[int]:
    """Micro-batch counts to sweep: divisors of the per-(dp, ep) batch
    drawn from {2, 4, 8, 16, 32, pipe.chunks}.  ``dp``/``ep`` override
    the pipe's own widths (the 3D planner's candidate meshes)."""
    if requested is not None:
        return list(requested)
    if dp is None:
        dp = pipe.mesh.shape[pipe.dp_axis] if pipe.dp_axis else 1
    if ep is None:
        ep = pipe.mesh.shape[pipe.ep_axis] if pipe.ep_axis else 1
    per = batch_size // (dp * ep)
    opts = sorted({
        c for c in (2, 4, 8, 16, 32, pipe.chunks)
        if c >= 1 and per % c == 0
    })
    return opts or [pipe.chunks]


def mpmd_chunk_options(
    batch_size: int, requested: Optional[Sequence[int]], default: int
) -> List[int]:
    """MPMD chunk candidates: divisors of the batch from
    {2, 4, 8, 16, default}.  May be EMPTY (a batch with no divisor in
    the set) — the scoring model sizes micro-batches as ``B // chunks``,
    so a non-dividing fallback would certify shapes the engine never
    runs; no candidates is the honest answer."""
    if requested is not None:
        return list(requested)
    return sorted({
        c for c in (2, 4, 8, 16, default)
        if c >= 1 and batch_size % c == 0
    })


# Megastep candidates: K optimizer steps per compiled program
# (make_train_step(megastep=K)).
MEGASTEP_SPACE: Tuple[int, ...] = (1, 4, 16)


def megastep_options(
    requested: Optional[Sequence[int]] = None,
    steps: Optional[int] = None,
) -> List[int]:
    """Megastep K candidates — THE axis :func:`plan`, ``tune`` and the
    bench ladder share.  ``steps`` (the caller's checkpoint/preemption
    cadence — hooks move to megastep boundaries, so K must divide it)
    filters the space; a requested K that doesn't divide it is DROPPED,
    and an all-indivisible request returns the honest EMPTY list (no
    candidates — the ``mpmd_chunk_options`` precedent), which
    ``plan``/``plan_report`` surface as an empty frontier."""
    opts = list(requested) if requested is not None else list(MEGASTEP_SPACE)
    opts = [int(k) for k in opts if int(k) >= 1]
    if steps is not None:
        opts = [k for k in opts if steps % k == 0]
    return sorted(dict.fromkeys(opts))


def scan_unroll_options(schedule: str) -> List[Any]:
    """scan_unroll candidates per schedule: the slot-buffer schedules
    measured faster fully unrolled (BENCH_NOTES round 4 —
    ``tune.UNROLL_LANE_DISCOUNT``), fill_drain measured slower, so its
    axis stays at the default."""
    if schedule == "fill_drain":
        return [1]
    return [1, True]


def mesh_width_options(
    pipe: Any, requested: Optional[Sequence[Sequence[int]]]
) -> List[Tuple[int, int, int]]:
    """(dp, tp, ep) width candidates for the mesh search.  Default: the
    pipe's OWN widths only — the planner never silently plans a mesh
    the user didn't ask about; pass ``mesh_options=[(1, 1), (2, 1),
    (2, 2)]`` to open the axis.  Entries may be (dp, tp) pairs (ep
    defaults to the pipe's own expert width — the pre-MoE call shape)
    or (dp, tp, ep) triples.  Candidate meshes are ABSTRACT (axis
    sizes only, no devices), so widths beyond the host are searchable;
    ``apply_plan`` refuses a width the pipe's real mesh doesn't have."""
    own_dp = pipe.mesh.shape[pipe.dp_axis] if pipe.dp_axis else 1
    own_tp = pipe.mesh.shape[pipe.tp_axis] if pipe.tp_axis else 1
    own_ep = pipe.mesh.shape[pipe.ep_axis] if getattr(pipe, "ep_axis", None) else 1
    if requested is None:
        return [(own_dp, own_tp, own_ep)]
    out: List[Tuple[int, int, int]] = []
    for entry in requested:
        widths = tuple(int(w) for w in entry)
        if len(widths) == 2:
            widths = widths + (own_ep,)
        if len(widths) != 3:
            raise ValueError(
                f"mesh_options entries must be (dp, tp) or (dp, tp, ep) "
                f"(got {tuple(entry)!r})"
            )
        out.append(widths)  # type: ignore[arg-type]
    return out


def zero_options_for(
    requested: Optional[Sequence[Union[bool, int]]], dp: int
) -> List[int]:
    """ZeRO sharding-LEVEL candidates: 0 (replicated), 1 (optimizer
    state ÷ N_dp) or 3 (fully sharded — params/grads/state stored at
    the fsdp layout, gathered at use).  Bools normalize to the levels
    they historically meant (``False`` → 0, ``True`` → 1).  With one
    data replica there is nothing to shard, so the axis only opens at
    dp > 1; level 3 is opt-in (``zero_options=[0, 3]``) because it
    changes the STORAGE layout, not just the optimizer state."""
    if requested is not None:
        out: List[int] = []
        for z in requested:
            level = int(z) if not isinstance(z, bool) else (1 if z else 0)
            if level not in (0, 1, 3):
                raise ValueError(
                    f"zero_options entries must be levels 0, 1 or 3 "
                    f"(got {z!r}); level 2 does not exist here — see "
                    "SpmdGPipe.make_train_step"
                )
            out.append(level)
        return out
    return [0, 1] if dp > 1 else [0]


def spmd_schedule_space(pipe: Any) -> List[str]:
    """Schedules an existing SPMD pipe can be re-planned onto WITHOUT
    changing the model: a pipe built interleaved keeps its block
    granularity (the v > 1 cut changes the model, so interleaved is
    planned only where it already holds); the explicit-gradient
    schedules need a micro-batch-decomposable loss."""
    if pipe.virtual_stages != 1:
        return ["interleaved"]
    out = ["fill_drain"]
    if pipe.loss_reduction in ("mean", "sum"):
        out.extend(["1f1b", "zb"])
    return out


def remat_space_for(
    pipe: Any, schedule: str
) -> List[Tuple[str, Optional[str], Any]]:
    """The remat axis restricted to what ``schedule`` supports: the
    explicit-gradient schedules hand-write their recompute (no offload,
    no named-save policies), and zero-bubble's split backward supports
    only 'never'/'always'."""
    space = spmd_remat_space(pipe)
    if schedule == "fill_drain":
        return space
    modes = (
        ("never", "always") if schedule == "zb"
        else ("never", "except_last", "always")
    )
    return [
        (mode, label, pol) for mode, label, pol in space
        if mode in modes and label is None
    ]


# --------------------------------------------------------------------- #
# plan + report                                                         #
# --------------------------------------------------------------------- #


@dataclasses.dataclass(frozen=True)
class Plan:
    """One scored-and-certified point of the joint search space."""

    engine: str  # "spmd" | "mpmd"
    schedule: str  # fill_drain|1f1b|zb|interleaved (spmd); gpipe|1f1b (mpmd)
    balance: Optional[Tuple[int, ...]]  # MPMD layer cut; None for stacked SPMD
    chunks: int
    checkpoint: str
    policy: Optional[str]  # preset label, None = engine default
    virtual_stages: int
    predicted_mfu: Optional[float]
    bubble_fraction: Optional[float]
    hwm_bytes: int  # certified per-rank device high-water mark (worst rank)
    host_bytes: int  # host-offloaded bytes at the peak (checkpoint='offload')
    feasible: bool
    certified: bool  # ordering + memory + sharding certification ran clean
    # Dispatch-granularity axes (SPMD engine): K optimizer steps per
    # compiled program and the tick scan's unroll factor.  MPMD plans
    # keep the defaults (megastep needs the fused single-device path,
    # which the planner's per-cell candidates don't build).
    megastep: int = 1
    scan_unroll: Any = 1
    # 3D axes (SPMD engine): data/tensor widths of the candidate mesh
    # (pp is n_stages), the ZeRO sharding LEVEL (0 replicated; 1 =
    # optimizer state ÷ N_dp — the acceptance the ZeRO gate pins; 3 =
    # fully sharded, params/grads/state stored at the fsdp layout and
    # gathered at use), the layout-certified per-device optimizer-state
    # bytes, and the priced per-lane collective volume charged against
    # the makespan (level 3 adds the per-step all_gather plus the
    # reduce-scatter grad sync).
    dp: int = 1
    tp: int = 1
    ep: int = 1  # expert-parallel width (MoE all_to_all group size)
    zero: int = 0
    opt_state_bytes: int = 0
    comm_bytes: int = 0
    # Profile-guided pricing (plan(cost_model=...)): which cost source
    # ranked this candidate — 'analytic' (walker FLOPs), 'measured'
    # (every cell priced from the cost model's measured atoms) or
    # 'mixed' (a missing backward bucket was derived, see
    # obs.costmodel.CostModel.stage_atoms).  Both makespans are kept so
    # the report can show prediction vs measurement side by side:
    # ``makespan_analytic`` in the analytic cost unit (FLOPs of the
    # critical path), ``makespan_measured`` in SECONDS.
    priced_by: str = "analytic"
    makespan_analytic: Optional[float] = None
    makespan_measured: Optional[float] = None
    reason: str = ""

    def describe(self) -> str:
        mfu = (
            f"{self.predicted_mfu:.4f}"
            if self.predicted_mfu is not None else "n/a"
        )
        bub = (
            f"{self.bubble_fraction:.3f}"
            if self.bubble_fraction is not None else "n/a"
        )
        bal = "x".join(str(b) for b in self.balance) if self.balance else "-"
        status = (
            ("ok" if self.certified else "UNCERTIFIED")
            if self.feasible else f"REJECT ({self.reason})"
        )
        host = (
            f" +{self.host_bytes / GiB:.2f} host" if self.host_bytes else ""
        )
        unroll = "full" if self.scan_unroll is True else self.scan_unroll
        mesh3d = f"{self.dp}x{self.tp}" + {1: "Z", 3: "Z3"}.get(
            int(self.zero), ""
        )
        if self.ep != 1:
            mesh3d += f"xE{self.ep}"
        priced = {"analytic": "a", "measured": "M", "mixed": "x"}.get(
            self.priced_by, "?"
        )
        span = (
            f"{self.makespan_measured * 1e3:8.2f}ms"
            if self.makespan_measured is not None else f"{'-':>10}"
        )
        return (
            f"{self.schedule:<11} {self.checkpoint:<12} "
            f"{self.policy or '-':<20} m={self.chunks:<3} "
            f"K={self.megastep:<3} u={unroll:<4} dxt={mesh3d:<6} "
            f"bal={bal:<9} "
            f"mfu~{mfu:<8} bubble={bub:<6} p={priced} span={span} "
            f"hwm={self.hwm_bytes / GiB:6.2f} GiB{host}  {status}"
        )


@dataclasses.dataclass
class PlanReport:
    """Ranked plans, feasible-and-certified first, best MFU first.

    ``cost_model_stale`` is set when a ``cost_model=`` was passed whose
    fingerprint no longer matches the pipe's current configuration: the
    search then fell back to analytic pricing (every plan
    ``priced_by='analytic'``) and the note says why — the
    ``stale-cost-model`` lint rule and ``tools/plan_report.py`` surface
    it."""

    candidates: List[Plan]
    hbm_budget_bytes: int
    cost_model_stale: Optional[str] = None

    @property
    def best(self) -> Optional[Plan]:
        for p in self.candidates:
            if p.feasible and p.certified:
                return p
        return None

    def table(self) -> str:
        head = (
            f"{'schedule':<11} {'checkpoint':<12} {'policy':<20} "
            f"{'m':<5} {'K':<5} {'u':<6} {'dpxtp':<10} {'balance':<13} "
            f"{'pred-mfu':<13} {'bubble':<13} {'priced/span':<22} "
            f"per-rank HWM (budget {self.hbm_budget_bytes / GiB:.2f} GiB)"
        )
        rows = [head] + [p.describe() for p in self.candidates]
        if self.cost_model_stale:
            rows.append(
                f"# cost model STALE ({self.cost_model_stale}) — "
                "analytic pricing used"
            )
        return "\n".join(rows)


def _ranked(candidates: List[Plan], budget: int) -> PlanReport:
    candidates.sort(
        key=lambda p: (
            not (p.feasible and p.certified),
            -(p.predicted_mfu or 0.0),
        )
    )
    return PlanReport(candidates=candidates, hbm_budget_bytes=budget)


# --------------------------------------------------------------------- #
# shared cost/certification machinery                                   #
# --------------------------------------------------------------------- #


def _spmd_graph(
    schedule: str, n: int, m: int, stop: int, v: int
) -> ev.EventGraph:
    if schedule == "fill_drain":
        return ev.spmd_fill_drain_events(n, m, stop)
    if schedule == "1f1b":
        return ev.spmd_1f1b_events(n, m, stop)
    if schedule == "zb":
        return ev.spmd_zb_events(n, m)
    if schedule == "interleaved":
        return ev.spmd_interleaved_events(n, m, v)
    raise ValueError(f"unknown SPMD schedule {schedule!r}")


def _certify(
    g: ev.EventGraph,
    bytes_of: Callable[[ev.Buffer], int],
) -> Tuple[Optional[sched.MemoryCertificate], List[Finding]]:
    """Ordering rules + memory certification for one candidate graph.

    Returns ``(certificate, findings)``; a non-empty findings list means
    the candidate must not be emitted as certified."""
    findings = sched.verify_ordering(g)
    if findings:
        return None, findings
    return sched.certify_memory(g, bytes_of), []


def _graph_score(
    g: ev.EventGraph,
    cost_of: Callable[[ev.Event], float],
    model_flops: Optional[float],
    n_chips: int,
    epilogue_per_lane: float,
    lane_tax: float = 0.0,
) -> Tuple[Optional[float], Optional[float]]:
    """(predicted MFU, bubble fraction) of one candidate graph."""
    try:
        span, busy = ev.makespan(g, cost_of)
    except ValueError:
        return None, None
    denom = g.n_ranks * span
    bubble = (
        max(0.0, 1.0 - sum(busy) / denom) if denom > 0 else None
    )
    mfu = None
    lane = span * (1.0 + lane_tax) + epilogue_per_lane
    if model_flops is not None and lane > 0:
        mfu = model_flops / (n_chips * lane)
    return mfu, bubble


# --------------------------------------------------------------------- #
# SPMD planning                                                         #
# --------------------------------------------------------------------- #


def _spmd_cell_atoms(
    pipe_variant: Any,
    stage_params_spec: Pytree,
    mb_spec: Pytree,
    plain: bool,
) -> Optional[Tuple[float, float]]:
    """(fwd, bwd) walker FLOPs of one micro-batch cell.

    ``plain=False`` traces the variant's REMAT'D block (``_block_fn``),
    so the backward number includes that policy's actual recompute
    replay — the per-policy refinement is measured, never modeled."""
    fn = (
        pipe_variant._block_fn_plain if plain else pipe_variant._block_fn
    )

    def f(p: Pytree, x: Pytree) -> Pytree:
        return fn(p, x, None, 1.0, True)

    def fb(p: Pytree, x: Pytree, ct: Pytree) -> Pytree:
        _, pull = jax.vjp(f, p, x)
        return pull(ct)

    try:
        fwd = flops_estimate(
            jax.make_jaxpr(f)(stage_params_spec, mb_spec)
        )
        ct_spec = avalify(jax.eval_shape(f, stage_params_spec, mb_spec))
        both = flops_estimate(
            jax.make_jaxpr(fb)(stage_params_spec, mb_spec, ct_spec)
        )
    except Exception:  # noqa: BLE001 - scoring stands down
        return None
    return fwd, max(both - fwd, 0.0)


def _spmd_cost_fn(
    schedule: str,
    stop: int,
    fwd: float,
    bwd: float,
    bwd_remat: float,
) -> Callable[[ev.Event], float]:
    """Per-event durations: checkpointed micro-batches (mb < stop) pay
    the remat'd backward (replay included); zero-bubble splits the
    backward into B (dx half, plus the replay when checkpointed) and W
    (dw half)."""

    def cost(e: ev.Event) -> float:
        if e.phase == ev.FWD:
            return fwd
        back = bwd_remat if e.mb < stop else bwd
        if e.phase == ev.BWD:
            if schedule == "zb":
                return 0.5 * bwd + (back - bwd if e.mb < stop else 0.0)
            return back
        if e.phase == ev.WGT:
            return 0.5 * bwd
        return 0.0

    return cost


def _spmd_measured_cost_fn(
    schedule: str,
    stop: int,
    atoms: Dict[int, Tuple[float, float, float]],
    scale: float,
) -> Callable[[ev.Event], float]:
    """The measured twin of :func:`_spmd_cost_fn`: per-event SECONDS
    from a cost model's per-stage ``(fwd, bwd, bwd_remat)`` atoms
    (:meth:`torchgpipe_tpu.obs.costmodel.CostModel.stage_atoms`),
    ``scale`` carrying the chunks re-scaling (cell rows go as
    ``1/chunks``).  Same phase structure: checkpointed micro-batches
    pay the remat'd backward; zero-bubble splits the backward into B
    (half, plus the measured replay delta when checkpointed) and W."""

    def cost(e: ev.Event) -> float:
        f, b, br = atoms[e.stage]
        if e.phase == ev.FWD:
            s = f
        elif e.phase == ev.BWD:
            if schedule == "zb":
                s = 0.5 * b + (max(br - b, 0.0) if e.mb < stop else 0.0)
            else:
                s = br if e.mb < stop else b
        elif e.phase == ev.WGT:
            s = 0.5 * b
        else:
            s = 0.0
        return s * scale

    return cost


def _layout_reject_reason(layout: Any) -> Optional[str]:
    """Why a candidate layout fails sharding certification, or None.

    ERROR findings (unmatched leaf, unknown mesh axis, indivisible dim)
    reject outright; a propagation ``reshard`` event rejects because a
    per-tick gather would silently dominate the step; an unused
    declared axis rejects because the candidate width buys nothing
    (accidental full replication)."""
    from torchgpipe_tpu.analysis.diagnostics import Severity

    for f in layout.findings:
        if f.severity >= Severity.ERROR:
            return f"layout: {f.message[:90]}"
    reshards = layout.reshards()
    if reshards:
        e = reshards[0]
        return (
            f"implicit reshard: {e.detail or e.primitive} over "
            f"{list(e.axes)}"
        )
    if layout.unused_axes:
        return (
            f"layout: declared axis {layout.unused_axes} of size > 1 "
            "shards no param leaf (accidental full replication)"
        )
    return None


def _plan_spmd(
    pipe: Any,
    batch: Pytree,
    hbm_budget_bytes: int,
    *,
    target: Optional[Pytree],
    schedules: Optional[Sequence[str]],
    chunks_options: Optional[Sequence[int]],
    megastep_opts: Optional[Sequence[int]],
    steps: Optional[int],
    mesh_options: Optional[Sequence[Sequence[int]]],
    zero_options: Optional[Sequence[Union[bool, int]]],
    overhead_bytes: int,
    param_scale: float,
    real_token_fraction: float = 1.0,
    cost_model: Any = None,
) -> PlanReport:
    from torchgpipe_tpu import tune
    from torchgpipe_tpu.analysis import sharding as shd
    from torchgpipe_tpu.checkpoint import checkpoint_stop

    x_spec = avalify(batch)
    tgt_spec = avalify(target) if target is not None else x_spec
    n = pipe.n_stages
    v = pipe.virtual_stages
    own_ep = pipe.mesh.shape[pipe.ep_axis] if pipe.ep_axis else 1
    sp = pipe.mesh.shape[pipe.sp_axis] if pipe.sp_axis else 1
    B = jax.tree_util.tree_leaves(x_spec)[0].shape[0]

    plain_step, params_spec = tune._spmd_plain_step(pipe, x_spec, tgt_spec)
    model_flops = (
        tune._model_flops(plain_step, params_spec, x_spec, tgt_spec)
        if plain_step is not None else None
    )
    # real_token_fraction scales ONLY the MFU numerator at the scoring
    # site below: the pad FLOPs still execute, so lane-time models
    # (lane_flops epilogue) keep the full traced figure — scaling them
    # would shrink predicted lane time non-uniformly across candidates
    # and could reorder the frontier.
    stage_params_spec = (
        jax.tree_util.tree_map(
            lambda a: jax.ShapeDtypeStruct(a.shape[1:], a.dtype),
            params_spec["blocks"],
        )
        if params_spec is not None else None
    )
    block_in_spec = x_spec
    if pipe.pre is not None and params_spec is not None:
        try:
            block_in_spec, _ = jax.eval_shape(
                lambda p, xx: pipe.pre.apply(p, (), xx, rng=None, train=True),
                params_spec["pre"], x_spec,
            )
        except Exception:  # noqa: BLE001 - probes below stand down
            block_in_spec = None

    sched_space = list(schedules or spmd_schedule_space(pipe))
    # The dispatch-granularity axis: an all-indivisible megastep request
    # (K not dividing the hook cadence) yields the honest EMPTY frontier.
    mega_space = megastep_options(megastep_opts, steps)
    dp_name = pipe.dp_axis or "dp"
    tp_name = pipe.tp_axis or "tp"
    ep_name = pipe.ep_axis or "ep"
    # MoE hyperparams declared on the block's meta (static — the ep
    # all_to_all is gated on axis presence inside shard_map, so it never
    # appears in the width-independent block trace; pricing is analytic).
    moe_metas = ev.find_moe_meta(pipe.block)
    # The block trace is width-independent; one cache serves every
    # candidate width's layout verification.
    layout_cache: Dict[str, Any] = {}
    plans: List[Plan] = []

    def rejected(
        dp: int, tp: int, reason: str, *,
        schedule: str = "*", mode: str = "-", label: Optional[str] = None,
        chunks: Optional[int] = None, zero: int = 0,
        ep: Optional[int] = None,
    ) -> Plan:
        return Plan(
            engine="spmd", schedule=schedule, balance=None,
            chunks=pipe.chunks if chunks is None else chunks,
            checkpoint=mode, policy=label, virtual_stages=v,
            predicted_mfu=None, bubble_fraction=None, hwm_bytes=0,
            host_bytes=0, feasible=False, certified=False,
            dp=dp, tp=tp, ep=cand_ep if ep is None else ep,
            zero=zero, reason=reason,
        )

    cand_ep = own_ep  # resolved per candidate below; rejected() reads it
    for dp, tp, ep in mesh_width_options(pipe, mesh_options):
        cand_ep = ep
        n_chips = n * dp * tp * ep * sp
        # A width > 1 on an axis the pipe never declared would append a
        # PHANTOM mesh axis: no leaf shards over it, the replication
        # check cannot see it (it keys on the declared axis names), and
        # the per-chip compute division would certify fictitious
        # speedup.  Reject the width outright.
        if dp > 1 and pipe.dp_axis is None:
            plans.append(rejected(
                dp, tp,
                f"dp={dp} needs the pipe to declare dp_axis (an "
                "undeclared axis shards nothing — the width would "
                "certify fictitious speedup)",
            ))
            continue
        if tp > 1 and pipe.tp_axis is None:
            plans.append(rejected(
                dp, tp,
                f"tp={tp} needs the pipe to declare tp_axis (an "
                "undeclared axis shards nothing — the width would "
                "certify fictitious speedup)",
            ))
            continue
        if ep > 1 and pipe.ep_axis is None:
            plans.append(rejected(
                dp, tp,
                f"ep={ep} needs the pipe to declare ep_axis (an "
                "undeclared axis shards nothing — the width would "
                "certify fictitious speedup)",
            ))
            continue
        if ep > 1 and not any(
            m.get("ep_axis") for m in moe_metas
        ):
            plans.append(rejected(
                dp, tp,
                f"ep={ep} needs an expert-parallel MoE layer in the "
                "block (no layer meta declares moe with ep_axis — the "
                "a2a the width implies would never run)",
            ))
            continue
        moe_ep_bad = next(
            (
                m for m in moe_metas
                if m.get("ep_axis") and int(m["n_experts"]) % ep != 0
            ),
            None,
        ) if ep > 1 else None
        if moe_ep_bad is not None:
            plans.append(rejected(
                dp, tp,
                f"n_experts={moe_ep_bad['n_experts']} does not divide "
                f"by ep={ep} (validate_mesh would refuse this mesh)",
            ))
            continue
        # Cheap rejections BEFORE the (retraced) layout verification.
        if B % (dp * ep) != 0:
            plans.append(rejected(
                dp, tp, f"batch {B} does not divide by dp*ep={dp * ep}"
            ))
            continue
        # ---- sharding certification of the candidate layout (3D) ---- #
        overrides = {dp_name: dp, tp_name: tp}
        if pipe.ep_axis is not None:
            overrides[ep_name] = ep
        try:
            layout = shd.verify_layout(
                pipe, batch, params_spec=params_spec,
                mesh_sizes=overrides, jaxpr_cache=layout_cache,
            )
        except Exception as e:  # noqa: BLE001 - stand down -> reject
            plans.append(rejected(dp, tp, f"layout: {e}"))
            continue
        reason = _layout_reject_reason(layout)
        if reason is not None:
            plans.append(rejected(dp, tp, reason))
            continue
        param_bytes = layout.param_bytes_local
        cell_comm_probe = layout.comm_bytes()
        probe_rows = max(B // max(pipe.chunks, 1), 1)
        grad_sync_lane = (
            2.0 * (dp - 1) / dp * param_bytes if dp > 1 else 0.0
        )
        lane_flops = (
            model_flops / (dp * ep * tp)
            if model_flops is not None else None
        )
        zero_space = list(dict.fromkeys(zero_options_for(zero_options, dp)))
        explicit_zero = zero_options is not None
        # Per-LEVEL compatibility, mirroring the engine's own refusals
        # (a frontier must never rank a plan its own engine would crash
        # on).  Level 1 needs dp >= 2 and dp-REPLICATED params (the
        # segment math shards replicated state); level 3 needs a
        # certifiable fsdp storage layout at this width.  An explicitly
        # requested incompatible level gets an honest REJECT row; the
        # default space just drops it.
        z1_reason: Optional[str] = None
        if dp < 2 or pipe.dp_axis is None:
            z1_reason = (
                "zero=1 is incompatible here (needs dp >= 2 and a "
                "declared dp_axis); drop it from zero_options"
            )
        elif pipe.fsdp:
            z1_reason = (
                "zero=1 is incompatible here (the fsdp layout already "
                "shards params/grads/state over dp — zero=3 IS this "
                "layout's update); drop it from zero_options"
            )
        elif any(
            pipe.dp_axis in shd.spec_axes(s)
            for _, s in shd.tree_leaf_paths(layout.specs)
            if isinstance(s, _P)
        ):
            z1_reason = (
                "zero=1 is incompatible here (a param leaf is sharded "
                "over the dp axis; the segment math needs dp-replicated "
                "params); drop it from zero_options"
            )
        # On an fsdp pipe at dp > 1, level 0 and level 3 are the SAME
        # program (the plain update against the stored-sharded layout)
        # — relabel 0 as 3 so the frontier carries the honest level.
        if pipe.fsdp and dp > 1:
            zero_space = list(dict.fromkeys(
                3 if z in (0, 3) else z for z in zero_space
            ))
        layout3: Optional[Any] = None
        z3_reason: Optional[str] = None
        if 3 in zero_space:
            if dp < 2 or pipe.dp_axis is None:
                z3_reason = (
                    "zero=3 is incompatible here (needs dp >= 2 and a "
                    "declared dp_axis); drop it from zero_options"
                )
            elif pipe.fsdp:
                layout3 = layout
            else:
                try:
                    pipe3 = dataclasses.replace(
                        pipe, fsdp=True, zero_update=3
                    )
                    layout3 = shd.verify_layout(
                        pipe3, batch, params_spec=params_spec,
                        mesh_sizes=overrides, jaxpr_cache=layout_cache,
                    )
                except Exception as e:  # noqa: BLE001 - honest reject
                    z3_reason = f"zero=3 layout: {e}"
                if layout3 is not None:
                    r3 = _layout_reject_reason(layout3)
                    if r3 is not None:
                        layout3, z3_reason = None, f"zero=3 {r3}"
        kept: List[int] = []
        for z in zero_space:
            if z == 1 and z1_reason is not None:
                if explicit_zero:
                    plans.append(rejected(dp, tp, z1_reason, zero=1))
                continue
            if z == 3 and layout3 is None:
                if explicit_zero:
                    plans.append(rejected(
                        dp, tp, z3_reason or "zero=3 unavailable",
                        zero=3,
                    ))
                continue
            kept.append(z)
        zero_space = kept
        if not zero_space:
            if not explicit_zero:
                plans.append(rejected(
                    dp, tp, "no compatible ZeRO level at this width"
                ))
            continue
        # Level-3 pricing inputs: the fully-sharded layout's resident
        # bytes, its transient gathered window, and the split of the
        # grad sync into replicated leaves (psum, 2(dp-1)/dp) vs
        # gathered leaves (reduce_scatter of the FULL grads, (dp-1)/dp).
        # The per-step all_gather itself rides on gather_lane3 — charged
        # ONCE per step (the compiled gather_schedule='block' gathers
        # before the tick scan), never scaled by chunks.
        pbl3 = gwin3 = gfull3 = 0
        cell_comm_probe3 = gather_lane3 = grad_sync_lane3 = 0.0
        if 3 in zero_space and layout3 is not None:
            pbl3 = layout3.param_bytes_local
            gwin3 = layout3.gathered_window_bytes
            gfull3 = layout3.gather_full_bytes
            cell_comm_probe3 = layout3.comm_bytes()
            gather_lane3 = float(layout3.gather_comm_bytes())
            rest3 = max(pbl3 - layout3.gather_stored_bytes, 0)
            grad_sync_lane3 = (
                (2.0 * (dp - 1) / dp * rest3 + (dp - 1) / dp * gfull3)
                if dp > 1 else 0.0
            )

        for chunks in spmd_chunk_options(
            pipe, B, chunks_options, dp=dp, ep=ep
        ):
            if B % (chunks * dp * ep) != 0:
                continue
            mb_spec = (
                jax.tree_util.tree_map(
                    lambda a: jax.ShapeDtypeStruct(
                        (a.shape[0] // (chunks * dp * ep),) + a.shape[1:],
                        a.dtype,
                    ),
                    block_in_spec,
                )
                if block_in_spec is not None else None
            )
            mb_bytes = tune.tree_bytes(mb_spec) if mb_spec is not None else 0
            mb_rows = B // (chunks * dp * ep)
            cell_comm = cell_comm_probe * mb_rows / probe_rows
            cell_comm3 = cell_comm_probe3 * mb_rows / probe_rows
            # Expert-parallel staging the block trace can't see (the ep
            # reshuffle holds send+recv live only inside shard_map):
            # charge the widest MoE layer's delta over the traced
            # single-chip capacity layout.  Zero at ep=1 by construction.
            moe_staging = 0
            if ep > 1 and moe_metas and mb_spec is not None:
                _wide = [
                    a for a in jax.tree_util.tree_leaves(mb_spec)
                    if len(a.shape) >= 2
                ]
                if _wide:
                    lane_tokens = int(_wide[0].shape[0]) * int(
                        _wide[0].shape[1]
                    )
                    moe_staging = max(
                        ev.expert_parallel_bytes(m, lane_tokens, ep=ep)
                        - ev.expert_parallel_bytes(m, lane_tokens, ep=1)
                        for m in moe_metas
                    )
            atom_cache: Dict[Any, Optional[Tuple[float, float]]] = {}
            resid_cache: Dict[Any, Optional[int]] = {}

            def atoms(variant: Any, plain: bool, key: Any) -> Optional[Tuple[float, float]]:
                if key not in atom_cache:
                    atom_cache[key] = _spmd_cell_atoms(
                        variant, stage_params_spec, mb_spec, plain=plain
                    )
                return atom_cache[key]

            def resid(variant: Any, plain: bool, key: Any) -> Optional[int]:
                if key not in resid_cache:
                    resid_cache[key] = tune._spmd_cell_residual_bytes(
                        variant, stage_params_spec, mb_spec, plain=plain
                    )
                return resid_cache[key]

            for schedule in sched_space:
                for mode, label, policy in remat_space_for(pipe, schedule):
                    try:
                        variant = dataclasses.replace(
                            pipe, schedule=schedule, checkpoint=mode,
                            remat_policy=policy, chunks=chunks,
                        )
                    except Exception as e:  # noqa: BLE001 - invalid combo
                        plans.append(rejected(
                            dp, tp, f"build: {e}", schedule=schedule,
                            mode=mode, label=label, chunks=chunks,
                        ))
                        continue
                    stop = checkpoint_stop(mode, chunks, train=True)
                    try:
                        g = _spmd_graph(schedule, n, chunks, stop, v)
                    except Exception as e:  # noqa: BLE001 - e.g. m % n != 0
                        plans.append(rejected(
                            dp, tp, f"schedule: {e}", schedule=schedule,
                            mode=mode, label=label, chunks=chunks,
                        ))
                        continue
                    remat = mode in ("always", "offload", "except_last")
                    plain_atoms = atoms(variant, True, "plain")
                    remat_atoms = (
                        atoms(variant, False, ("remat", label))
                        if remat else plain_atoms
                    )
                    resid_full = resid(variant, True, "plain")
                    resid_cell = (
                        resid(variant, False, ("remat", label))
                        if remat else resid_full
                    )
                    if (
                        plain_atoms is None or remat_atoms is None
                        or resid_full is None or resid_cell is None
                    ):
                        plans.append(rejected(
                            dp, tp, "cell probe failed", schedule=schedule,
                            mode=mode, label=label, chunks=chunks,
                        ))
                        continue
                    # Per-CHIP cell atoms: tensor parallelism splits each
                    # cell's matmuls over tp lanes.
                    fwd, bwd = (a / tp for a in plain_atoms)
                    bwd_remat = remat_atoms[1] / tp
                    # Offload: named points ride to host; the device keeps
                    # what a nothing-saveable remat would (tune's law).
                    host_cell = 0
                    if mode == "offload" and getattr(
                        variant.remat_policy, "offload", False
                    ):
                        nothing = dataclasses.replace(
                            pipe, schedule=schedule, checkpoint="always",
                            remat_policy=None, chunks=chunks,
                        )
                        device_cell = resid(nothing, False, ("remat", None))
                        if device_cell is not None:
                            host_cell = max(resid_cell - device_cell, 0)
                            resid_cell = device_cell

                    def bytes_of(
                        buf: ev.Buffer,
                        _rf: int = resid_full,
                        _rc: int = resid_cell,
                        _mode: str = mode,
                        _mb: int = mb_bytes,
                    ) -> int:
                        if buf.kind == "resid":
                            # Interleaved annotates every cell "resid".
                            return _rc if _mode != "never" else _rf
                        if buf.kind == "saved":
                            return _rc
                        if buf.kind == "out":
                            return _mb
                        return 0

                    cert, findings = _certify(g, bytes_of)
                    if cert is None:
                        plans.append(rejected(
                            dp, tp,
                            f"verifier: {findings[0].message[:80]}",
                            schedule=schedule, mode=mode, label=label,
                            chunks=chunks,
                        ))
                        continue
                    # Fixed per-lane residents beyond the schedule-managed
                    # buffers: params + optimizer state under the LAYOUT
                    # (tp-sharded leaves store 1/tp per chip; ZeRO divides
                    # the optimizer state by dp), the stacked per-tick
                    # scan outputs (fill-drain's ys; the explicit-
                    # gradient schedules keep an O(n) ring instead), and
                    # the allocator/temp overhead allowance.
                    ticks = (
                        chunks + n - 1 if schedule == "fill_drain" else n
                    )
                    # Send-ahead on the slot-buffer 1f1b schedule carries
                    # the permuted act/gact BESIDE the raw ones (two extra
                    # activation-sized pytrees per lane; fill_drain's
                    # send-ahead carry REPLACES the raw one — no growth).
                    send_ahead_carry = (
                        2 * mb_bytes
                        if schedule == "1f1b"
                        and bool(getattr(pipe, "send_ahead", False))
                        else 0
                    )
                    host_peak = max(
                        (
                            pl.get("saved", 0) + pl.get("resid", 0)
                            for pl in cert.peak_live
                        ),
                        default=0,
                    ) * host_cell
                    # SPMD 'offload' remats EVERY cell (offload save
                    # policy): the replay is charged for all micro-
                    # batches even though the buffer annotation's stop
                    # is 0 (residuals stored, host-side).
                    cost_stop = chunks if mode == "offload" else stop
                    cost_of = _spmd_cost_fn(
                        schedule, cost_stop, fwd, bwd, bwd_remat
                    )
                    epilogue = 0.0
                    if lane_flops is not None:
                        useful_cells = n * chunks * (fwd + bwd)
                        epilogue = max(lane_flops - useful_cells, 0.0) / n
                    # One graph walk per base candidate; the megastep ×
                    # scan_unroll × zero refinements are arithmetic over
                    # the same span (the graph/cert/atoms do not depend
                    # on K, the unroll factor or the optimizer layout —
                    # only the lane-time/memory models do).
                    try:
                        span, busy = ev.makespan(g, cost_of)
                    except ValueError:
                        span = None
                    bubble = None
                    if span is not None and g.n_ranks * span > 0:
                        bubble = max(
                            0.0, 1.0 - sum(busy) / (g.n_ranks * span)
                        )
                    # Profile-guided pricing: when a fresh cost model
                    # covers this stage structure at these widths, the
                    # candidate's makespan is re-priced from measured
                    # per-stage atoms (seconds), then calibrated back
                    # into the analytic FLOP unit by pinning the total
                    # measured forward to the total analytic forward —
                    # so measured- and analytic-priced candidates rank
                    # in ONE unit and only the measured RELATIVE
                    # structure (backward ratios, stage skew) replaces
                    # the analytic guess.
                    priced_by = "analytic"
                    span_rank = span
                    span_measured = None
                    # v > 1 stands down: interleaved events carry GLOBAL
                    # stage ids (c*n + j, model chunks) while the model's
                    # atoms are per PHYSICAL stage — indexing would lie.
                    if v == 1 and cost_model is not None and (
                        cost_model.prices_structure(
                            engine="spmd", n_stages=n, dp=dp, tp=tp
                        )
                    ):
                        m_atoms, m_exact = cost_model.stage_atoms(n)
                        k_scale = (
                            float(cost_model.fingerprint["chunks"]) / chunks
                        )
                        if m_atoms is not None:
                            meas_fwd = sum(
                                a[0] for a in m_atoms.values()
                            ) * k_scale
                            ana_fwd = n * fwd
                            if meas_fwd > 0 and ana_fwd > 0:
                                cost_s = _spmd_measured_cost_fn(
                                    schedule, cost_stop, m_atoms, k_scale
                                )
                                try:
                                    span_s, busy_s = ev.makespan(g, cost_s)
                                except ValueError:
                                    span_s = None
                                if span_s is not None:
                                    span_measured = span_s
                                    span_rank = span_s * (ana_fwd / meas_fwd)
                                    if g.n_ranks * span_s > 0:
                                        bubble = max(
                                            0.0,
                                            1.0 - sum(busy_s)
                                            / (g.n_ranks * span_s),
                                        )
                                    priced_by = (
                                        "measured" if m_exact else "mixed"
                                    )
                    # param_scale's head-room splits into the gradient
                    # tree (~1x params) and the optimizer moments (the
                    # rest).  Level 1 shards ONLY the moments over dp;
                    # level 3 stores params, grads AND moments at the
                    # fsdp layout (everything scales with the SHARDED
                    # param bytes) plus the transient gathered window.
                    grad_share = param_bytes * min(
                        max(param_scale - 1.0, 0.0), 1.0
                    )
                    moment_total = param_bytes * max(
                        param_scale - 2.0, 0.0
                    )
                    for zero in zero_space:
                        if zero == 3:
                            opt_bytes = int(
                                pbl3 * max(param_scale - 2.0, 0.0)
                            )
                            fixed = int(
                                pbl3 + gwin3
                                + pbl3 * min(
                                    max(param_scale - 1.0, 0.0), 1.0
                                )
                                + opt_bytes
                                + ticks * mb_bytes
                                + send_ahead_carry
                                + overhead_bytes
                                + moe_staging
                            )
                            lane_comm = (
                                chunks * cell_comm3
                                + grad_sync_lane3 + gather_lane3
                            )
                        else:
                            opt_bytes = int(
                                moment_total / (dp if zero else 1)
                            )
                            fixed = int(
                                param_bytes + grad_share + opt_bytes
                                + ticks * mb_bytes
                                + send_ahead_carry
                                + overhead_bytes
                                + moe_staging
                            )
                            lane_comm = chunks * cell_comm + grad_sync_lane
                        comm_flops = shd.COMM_FLOPS_PER_BYTE * lane_comm
                        hwm = cert.high_water + fixed
                        feasible = hwm <= hbm_budget_bytes
                        for K in mega_space:
                            for u in scan_unroll_options(schedule):
                                mfu = None
                                if (
                                    span_rank is not None
                                    and model_flops is not None
                                ):
                                    disc = (
                                        tune.UNROLL_LANE_DISCOUNT
                                        if u is True else 1.0
                                    )
                                    lane = (
                                        span_rank * disc + epilogue
                                        + comm_flops
                                        + tune.DISPATCH_OVERHEAD_FLOPS / K
                                    )
                                    if lane > 0:
                                        # Ragged-data honesty: only the
                                        # real-token fraction of the
                                        # traced flops is useful work (a
                                        # uniform numerator scale —
                                        # ranking unchanged).
                                        mfu = (
                                            model_flops
                                            * real_token_fraction
                                            / (n_chips * lane)
                                        )
                                plans.append(Plan(
                                    engine="spmd", schedule=schedule,
                                    balance=None,
                                    chunks=chunks, checkpoint=mode,
                                    policy=label,
                                    virtual_stages=v, predicted_mfu=mfu,
                                    bubble_fraction=bubble, hwm_bytes=hwm,
                                    host_bytes=host_peak, feasible=feasible,
                                    certified=True, megastep=K,
                                    scan_unroll=u, dp=dp, tp=tp, ep=ep,
                                    zero=zero,
                                    opt_state_bytes=opt_bytes,
                                    comm_bytes=int(lane_comm),
                                    priced_by=priced_by,
                                    makespan_analytic=span,
                                    makespan_measured=span_measured,
                                    reason=(
                                        "" if feasible
                                        else "over HBM budget"
                                    ),
                                ))
    return _ranked(plans, hbm_budget_bytes)


# --------------------------------------------------------------------- #
# MPMD planning                                                         #
# --------------------------------------------------------------------- #


def _mpmd_balance_options(
    pipe: Any,
    requested: Optional[Sequence[Sequence[int]]],
    layer_fb: Optional[List[float]],
) -> List[Tuple[int, ...]]:
    """Balance cuts to score: the pipe's current cut plus the analytic
    FLOPs-balanced cut (``balance_by_flops``' exact block partition of
    the same per-layer costs), deduplicated."""
    from torchgpipe_tpu.balance import balance_cost

    opts: List[Tuple[int, ...]] = []
    if requested is not None:
        opts.extend(tuple(b) for b in requested)
    else:
        opts.append(tuple(pipe.balance))
        if layer_fb is not None and any(f > 0 for f in layer_fb):
            try:
                opts.append(tuple(
                    balance_cost(layer_fb, len(pipe.balance))
                ))
            except Exception:  # noqa: BLE001 - infeasible cut request
                pass
    return list(dict.fromkeys(opts))


def _plan_mpmd(
    pipe: Any,
    batch: Pytree,
    hbm_budget_bytes: int,
    *,
    chunks_options: Optional[Sequence[int]],
    balance_options: Optional[Sequence[Sequence[int]]],
    overhead_bytes: int,
    param_scale: float,
    real_token_fraction: float = 1.0,
    cost_model: Any = None,
) -> PlanReport:
    from torchgpipe_tpu import tune
    from torchgpipe_tpu.balance import layer_flops
    from torchgpipe_tpu.checkpoint import checkpoint_stop
    from torchgpipe_tpu.gpipe import GPipe

    del param_scale  # per-stage params are not modeled on MPMD (multi-chip)
    x_spec = avalify(batch)
    B = jax.tree_util.tree_leaves(x_spec)[0].shape[0]
    try:
        layer_fb: Optional[List[float]] = layer_flops(pipe.layers, x_spec)
    except Exception:  # noqa: BLE001 - scoring degrades, memory still runs
        layer_fb = None
    model_flops = (
        sum(layer_fb) * real_token_fraction if layer_fb else None
    )
    balances = _mpmd_balance_options(pipe, balance_options, layer_fb)
    schedules = ["gpipe"]
    if pipe.schedule == "1f1b" or pipe.loss_reduction in ("mean", "sum"):
        schedules.append("1f1b")

    plans: List[Plan] = []
    for balance in balances:
        stage_fwd: Optional[List[float]] = None
        if layer_fb is not None:
            stage_fwd, i = [], 0
            for size in balance:
                stage_fwd.append(sum(layer_fb[i:i + size]) / 3.0)
                i += size
        for chunks in mpmd_chunk_options(B, chunks_options, pipe.chunks):
            profile_cache: Dict[Tuple[int, ...], Optional[Tuple]] = {}
            for schedule in schedules:
                for mode in MPMD_CHECKPOINT_SPACE:
                    plans.append(_score_mpmd_candidate(
                        pipe, x_spec, balance, chunks, schedule, mode,
                        stage_fwd, model_flops, hbm_budget_bytes,
                        overhead_bytes, profile_cache,
                        GPipe, checkpoint_stop, tune,
                        cost_model=cost_model,
                    ))
    return _ranked(plans, hbm_budget_bytes)


def _score_mpmd_candidate(
    pipe: Any,
    x_spec: Pytree,
    balance: Tuple[int, ...],
    chunks: int,
    schedule: str,
    mode: str,
    stage_fwd: Optional[List[float]],
    model_flops: Optional[float],
    hbm_budget_bytes: int,
    overhead_bytes: int,
    profile_cache: Dict,
    GPipe: Any,
    checkpoint_stop: Callable,
    tune: Any,
    cost_model: Any = None,
) -> Plan:
    def rejected(reason: str) -> Plan:
        return Plan(
            engine="mpmd", schedule=schedule, balance=balance,
            chunks=chunks, checkpoint=mode, policy=None,
            virtual_stages=1, predicted_mfu=None, bubble_fraction=None,
            hwm_bytes=0, host_bytes=0, feasible=False, certified=False,
            reason=reason,
        )

    try:
        variant = GPipe(
            pipe.layers, balance=list(balance), chunks=chunks,
            checkpoint=mode, schedule=schedule,
            # GPipe rejects loss_reduction outside 1f1b (fill-drain
            # computes the loss on the gathered mini-batch).
            loss_reduction=(
                pipe.loss_reduction if schedule == "1f1b" else None
            ),
        )
    except Exception as e:  # noqa: BLE001 - invalid combo
        return rejected(f"build: {e}")
    n = len(balance)
    m = chunks
    stop = checkpoint_stop(mode, m, train=True)
    g = (
        ev.mpmd_1f1b_events(n, m, stop) if schedule == "1f1b"
        else ev.mpmd_fill_drain_events(n, m, stop)
    )
    key = tuple(balance) + (chunks,)
    if key not in profile_cache:
        profile_cache[key] = tune.mpmd_stage_memory_profile(variant, x_spec)
    profile = profile_cache[key]
    if profile is None:
        return rejected("memory profile failed")
    resid_b, saved_b, out_b = profile

    def bytes_of(buf: ev.Buffer) -> int:
        if buf.kind == "resid":
            return resid_b[buf.stage]
        if buf.kind == "saved":
            return saved_b[buf.stage]
        if buf.kind == "out":
            return out_b
        return 0

    offload = mode == "offload"
    host_kinds: Tuple[str, ...] = ("resid",) if offload else ()
    findings = sched.verify_ordering(g)
    if findings:
        return rejected(f"verifier: {findings[0].message[:80]}")
    cert = sched.certify_memory(g, bytes_of, host_kinds=host_kinds)
    hwm = cert.high_water + overhead_bytes
    host = max(cert.host_per_rank, default=0)
    feasible = hwm <= hbm_budget_bytes
    mfu = bubble = None
    priced_by = "analytic"
    span_analytic = span_measured = None
    if stage_fwd is not None:
        # stage_fwd is the FULL-batch forward cost; one schedule cell
        # computes a single micro-batch (1/m of the rows).
        cell_fwd = [f / m for f in stage_fwd]

        def cost_of(e: ev.Event) -> float:
            f = cell_fwd[e.stage]
            if e.phase == ev.FWD:
                return f
            if e.phase == ev.BWD:
                return 2.0 * f + (f if e.mb < stop else 0.0)
            return 0.0

        tax = tune.OFFLOAD_RANK_TAX if offload else 0.0
        try:
            span_analytic, _busy = ev.makespan(g, cost_of)
        except ValueError:
            span_analytic = None
        mfu, bubble = _graph_score(
            g, cost_of, model_flops, n, 0.0, lane_tax=tax
        )
        # Profile-guided pricing (see the SPMD twin's comment): measured
        # per-stage atoms price the candidate in seconds, calibrated
        # back into the analytic FLOP unit by pinning the total
        # measured forward to the total analytic forward — one ranking
        # unit across measured- and analytic-priced candidates.
        if cost_model is not None and cost_model.prices_structure(
            engine="mpmd", n_stages=n, balance=tuple(balance)
        ):
            m_atoms, m_exact = cost_model.stage_atoms(n)
            if m_atoms is not None:
                k_scale = float(cost_model.fingerprint["chunks"]) / m

                def cost_s(e: ev.Event) -> float:
                    f_s, b_s, br_s = m_atoms[e.stage]
                    if e.phase == ev.FWD:
                        s = f_s
                    elif e.phase == ev.BWD:
                        s = br_s if e.mb < stop else b_s
                    else:
                        s = 0.0
                    return s * k_scale

                meas_fwd = sum(a[0] for a in m_atoms.values()) * k_scale
                ana_fwd = sum(cell_fwd)
                if meas_fwd > 0 and ana_fwd > 0:
                    cal = ana_fwd / meas_fwd
                    try:
                        span_measured, _sb = ev.makespan(g, cost_s)
                    except ValueError:
                        span_measured = None
                    if span_measured is not None:
                        mfu, bubble = _graph_score(
                            g, lambda e: cost_s(e) * cal, model_flops,
                            n, 0.0, lane_tax=tax,
                        )
                        priced_by = "measured" if m_exact else "mixed"
    return Plan(
        engine="mpmd", schedule=schedule, balance=balance, chunks=chunks,
        checkpoint=mode, policy=None, virtual_stages=1,
        predicted_mfu=mfu, bubble_fraction=bubble, hwm_bytes=hwm,
        host_bytes=host, feasible=feasible, certified=True,
        priced_by=priced_by, makespan_analytic=span_analytic,
        makespan_measured=span_measured,
        reason="" if feasible else "over HBM budget",
    )


# --------------------------------------------------------------------- #
# entry points: plan / apply_plan / verify_plan                         #
# --------------------------------------------------------------------- #


def plan(
    pipe: Any,
    batch: Pytree,
    hbm_budget_bytes: int,
    *,
    target: Optional[Pytree] = None,
    schedules: Optional[Sequence[str]] = None,
    chunks_options: Optional[Sequence[int]] = None,
    balance_options: Optional[Sequence[Sequence[int]]] = None,
    megastep_options: Optional[Sequence[int]] = None,
    steps: Optional[int] = None,
    mesh_options: Optional[Sequence[Sequence[int]]] = None,
    zero_options: Optional[Sequence[Union[bool, int]]] = None,
    overhead_bytes: Optional[int] = None,
    param_scale: Optional[float] = None,
    real_token_fraction: float = 1.0,
    cost_model: Any = None,
) -> PlanReport:
    """Search balance × schedule × chunks × remat × dispatch granularity
    × (dp, tp) mesh width × ZeRO statically and return the certified
    frontier.

    ``cost_model`` (a :class:`torchgpipe_tpu.obs.costmodel.CostModel`,
    distilled from a measured reconciliation or flight-recorder dumps)
    turns the search profile-guided: candidates sharing the measured
    stage structure (same engine / stage count / balance cut / mesh
    widths) are re-priced with MEASURED per-stage atoms — the backward
    split into plain and remat'd buckets, scaled across chunks —
    calibrated into the analytic FLOP unit so measured- and
    analytic-priced candidates rank together (``Plan.priced_by`` says
    which source ranked each candidate; both makespans ride on the
    plan).  Certification is UNCHANGED — memory, deadlock and sharding
    stay static; only the ranking listens to the measurement.  A STALE
    model (fingerprint no longer matching the pipe's current config —
    :meth:`~torchgpipe_tpu.obs.costmodel.CostModel.stale_reason`) is
    ignored with a note on ``PlanReport.cost_model_stale`` (the
    ``stale-cost-model`` lint rule's condition).

    ``real_token_fraction`` (``utils.data.real_token_fraction`` of the
    training batches) keeps predicted MFU honest on ragged data: the
    analytic FLOPs price the traced (padded) shapes, so only this
    fraction counts as useful work.  A uniform scale — it never changes
    candidate RANKING, only the reported ``predicted_mfu``; pack the
    corpus (``utils.data.pack_documents``) to move the fraction toward
    1 and the real MFU with it.

    ``megastep_options`` / ``steps`` control the SPMD dispatch axis:
    megastep K candidates (default :data:`MEGASTEP_SPACE`) filtered to
    divisors of ``steps`` when given — checkpoint/preemption hooks run
    at megastep boundaries, so K must divide the hook cadence; an
    all-indivisible request yields an EMPTY frontier rather than a
    silently-adjusted one.

    ``mesh_options`` (SPMD) opens the mesh axis: a list of ``(dp, tp)``
    width pairs or ``(dp, tp, ep)`` triples to search (default: the
    pipe's own widths only).  Every width candidate is certified by the
    static sharding verifier
    (:func:`torchgpipe_tpu.analysis.sharding.verify_layout`) — an
    unmatched param leaf, a mesh-axis mismatch, an implicit reshard or
    an unused declared axis REJECTS the width — and its collective
    volume (required tp psums from the propagation + the dp gradient
    all-reduce + the MoE expert ``all_to_all`` dispatch/combine pair at
    ep > 1) is priced into the lane time at
    :data:`~torchgpipe_tpu.analysis.sharding.COMM_FLOPS_PER_BYTE`.
    An ep > 1 candidate is rejected outright unless the pipe declares
    ``ep_axis`` AND the block contains an expert-parallel MoE layer
    whose ``n_experts`` divides by ep (``validate_mesh``'s refusal,
    surfaced as an honest REJECT row before any tracing); certified
    MoE candidates additionally charge the a2a staging bytes the
    block trace cannot see into the memory high-water mark.
    ``zero_options`` controls the ZeRO sharding-level axis (levels
    ``0``/``1``/``3``; bools normalize ``False`` → 0, ``True`` → 1;
    default ``[0, 1]`` at dp > 1): level-1 candidates charge optimizer
    state ÷ N_dp in the memory certification
    (``Plan.opt_state_bytes``); level-3 candidates are priced against
    the FULLY-SHARDED (fsdp / gather-at-use) layout — resident
    params/grads/state ÷ N_dp plus the transient gathered window from
    the sharding verifier's gather accounting, with the per-step
    ``all_gather`` and the reduce-scatter grad sync charged into the
    lane time at :data:`~torchgpipe_tpu.analysis.sharding.
    COMM_FLOPS_PER_BYTE`.  ``apply_plan`` on a level-3 winner flips
    ``fsdp=True``; an fsdp pipe's own candidates carry level 3
    natively (its plain update IS the zero=3 update).

    ``pipe`` is a :class:`~torchgpipe_tpu.spmd.SpmdGPipe` or
    :class:`~torchgpipe_tpu.gpipe.GPipe`; ``batch`` a representative
    batch (arrays or ``ShapeDtypeStruct`` — only shapes/dtypes are
    read).  No device is timed, nothing compiles for an accelerator:
    the whole search is traced jaxprs + ``eval_shape`` + pure-Python
    event graphs (candidate meshes are abstract axis-size maps).  Every
    emitted feasible plan passed the schedule verifier's ordering
    rules, the sharding certification and the memory-certification
    pass against ``hbm_budget_bytes``.
    """
    from torchgpipe_tpu import tune
    from torchgpipe_tpu.gpipe import GPipe

    overhead = (
        tune.DEFAULT_OVERHEAD_BYTES if overhead_bytes is None
        else overhead_bytes
    )
    scale = (
        tune.DEFAULT_PARAM_SCALE if param_scale is None else param_scale
    )
    if not 0.0 <= real_token_fraction <= 1.0:
        raise ValueError(
            f"real_token_fraction must be in [0, 1], got "
            f"{real_token_fraction}"
        )
    stale: Optional[str] = None
    if cost_model is not None:
        stale = cost_model.stale_reason(pipe)
        if stale is not None:
            cost_model = None  # analytic fallback, noted on the report
    if isinstance(pipe, GPipe):
        report = _plan_mpmd(
            pipe, batch, hbm_budget_bytes,
            chunks_options=chunks_options,
            balance_options=balance_options,
            overhead_bytes=overhead, param_scale=scale,
            real_token_fraction=real_token_fraction,
            cost_model=cost_model,
        )
    else:
        report = _plan_spmd(
            pipe, batch, hbm_budget_bytes, target=target,
            schedules=schedules, chunks_options=chunks_options,
            megastep_opts=megastep_options, steps=steps,
            mesh_options=mesh_options, zero_options=zero_options,
            overhead_bytes=overhead, param_scale=scale,
            real_token_fraction=real_token_fraction,
            cost_model=cost_model,
        )
    report.cost_model_stale = stale
    return report


def apply_plan(pipe: Any, chosen: Plan) -> Any:
    """Rebuild ``pipe`` with a plan applied — the one-call handoff from
    the frontier table to a runnable engine."""
    from torchgpipe_tpu import tune
    from torchgpipe_tpu.gpipe import GPipe

    if chosen.engine == "mpmd":
        if not isinstance(pipe, GPipe):
            raise TypeError("an mpmd plan applies to a GPipe pipeline")
        if getattr(pipe, "_deferred_batch_norm", False):
            raise ValueError(
                "apply_plan cannot rebuild a deferred-batch-norm "
                "pipeline: its layers were converted for the ORIGINAL "
                "chunks (stats commit on the chunks-th micro-batch), so "
                "a rebuilt pipe at the plan's chunks would commit at the "
                "wrong cadence — rebuild the GPipe from unconverted "
                "layers with the plan's settings instead"
            )
        # Carry the runtime configuration a replan loop depends on: the
        # stage devices, the tracer (the NEXT measurement's source) and
        # — where the chosen plan still supports them — the fused path
        # and its megastep.  fused cannot express 1f1b or per-cell
        # offload; the per-cell tracer records nothing under fused.
        fused = (
            bool(getattr(pipe, "fused", False))
            and chosen.schedule == "gpipe"
            and chosen.checkpoint != "offload"
        )
        applied = GPipe(
            pipe.layers,
            balance=list(chosen.balance or pipe.balance),
            chunks=chosen.chunks,
            checkpoint=chosen.checkpoint,
            schedule=chosen.schedule,
            loss_reduction=(
                pipe.loss_reduction if chosen.schedule == "1f1b" else None
            ),
            devices=list(pipe.devices),
            fused=fused,
            megastep=(getattr(pipe, "megastep", 1) if fused else 1),
            tracer=(None if fused else getattr(pipe, "tracer", None)),
            hbm_budget_bytes=getattr(pipe, "hbm_budget_bytes", None),
        )
        # pipe.layers already carry the precision policy's wrapping
        # (applied at the ORIGINAL ctor) — re-passing compute_dtype
        # would double-wrap, so only the declared attribute is restored
        # (the precision-drift lint rule reads it off the pipe).
        applied.compute_dtype = pipe.compute_dtype
        return applied
    own_dp = pipe.mesh.shape[pipe.dp_axis] if pipe.dp_axis else 1
    own_tp = pipe.mesh.shape[pipe.tp_axis] if pipe.tp_axis else 1
    own_ep = pipe.mesh.shape[pipe.ep_axis] if getattr(pipe, "ep_axis", None) else 1
    if (chosen.dp, chosen.tp, chosen.ep) != (own_dp, own_tp, own_ep):
        raise ValueError(
            f"the chosen plan wants a dp×tp×ep width of "
            f"{chosen.dp}x{chosen.tp}x{chosen.ep} but this pipe's mesh "
            f"is {own_dp}x{own_tp}x{own_ep}: apply_plan cannot resize "
            "a device mesh — build one with make_mesh(n_stages, dp, "
            "tp=tp, ep=ep) and construct the pipe on it, then apply "
            "the plan there"
        )
    # Level 3 is a STORAGE-layout decision: applying it flips fsdp on
    # (params/grads/state stored sharded, gathered at use).  Levels 0/1
    # keep the pipe's own storage layout; zero_update carries the
    # historical bool spelling for them so round-trips stay stable.
    level = int(chosen.zero)
    return dataclasses.replace(
        pipe,
        schedule=chosen.schedule,
        checkpoint=chosen.checkpoint,
        remat_policy=tune.resolve_policy(chosen.policy),
        chunks=chosen.chunks,
        megastep=chosen.megastep,
        scan_unroll=chosen.scan_unroll,
        fsdp=(True if level == 3 else pipe.fsdp),
        zero_update=(3 if level == 3 else bool(level)),
    )


def verify_plan(
    pipe: Any, chosen: Plan, batch: Optional[Pytree] = None
) -> List[Finding]:
    """Re-run the event-graph verifier on a chosen plan: build the
    plan's engine, extract its event graph, and return the ordering +
    donation + equivalence findings (empty = the plan is certified by
    the SAME rules ``analysis.lint`` enforces).  With ``batch`` given,
    an SPMD plan's layout is ALSO re-verified by the static sharding
    analysis at the plan's (dp, tp) widths — the ``sharding-verify`` CI
    gate's shape.  The ``plan-verify`` CI step calls this on the top
    plan of each llama preset."""
    applied = apply_plan(pipe, chosen)
    m = chosen.chunks
    g = ev.events_for(applied, chunks=m)
    findings = sched.verify_ordering(g)
    findings.extend(sched.verify_buffers(ev.with_update(g, donate=True)))
    findings.extend(sched.verify_equivalence(g))
    if batch is not None and chosen.engine == "spmd":
        from torchgpipe_tpu.analysis import sharding as shd

        overrides = {
            (pipe.dp_axis or "dp"): chosen.dp,
            (pipe.tp_axis or "tp"): chosen.tp,
        }
        if getattr(pipe, "ep_axis", None) is not None:
            overrides[pipe.ep_axis] = chosen.ep
        report = shd.verify_layout(
            applied, batch, mesh_sizes=overrides
        )
        findings.extend(report.findings)
    return findings


# --------------------------------------------------------------------- #
# plan-drift lint rule (registered in analysis.rules)                   #
# --------------------------------------------------------------------- #


def _policy_identity(policy: Any) -> Any:
    """What makes two remat policies THE SAME policy: named-save
    policies by their (names, offload) declaration — the presets are
    properties returning a fresh instance per access, so object identity
    never holds — and raw jax policy functions by identity (jax's
    module-level functions ARE stable objects)."""
    names = getattr(policy, "names", None)
    if names is not None:
        return ("named", tuple(names), bool(getattr(policy, "offload", False)))
    return ("fn", policy)


def _spmd_policy_label(pipe: Any) -> Optional[str]:
    """The pipe's remat policy resolved to the PLANNER'S preset name
    (the ``Plan.policy`` vocabulary), or None for the engine default.
    A ``NamedSavePolicy.label`` ("save:attn_out") is a display string,
    not the preset name ("save_attn_out") — resolve through the
    canonical candidate space instead.  Unknown/custom policies return
    a sentinel no candidate carries, so the drift rule stands down
    rather than mis-keying onto the wrong candidate."""
    policy = getattr(pipe, "remat_policy", None)
    if policy is None or getattr(policy, "default_preset", False):
        # The 'offload' mode installs its catch-all default in
        # __post_init__; both spellings are the offload_default plan.
        return "offload_default" if pipe.checkpoint == "offload" else None
    key = _policy_identity(policy)
    for _mode, label, candidate in spmd_remat_space(pipe):
        if candidate is not None and _policy_identity(candidate) == key:
            return label
    return f"<custom:{getattr(policy, 'label', policy)!r}>"


def _unroll_key(u: Any) -> Any:
    """Disambiguating key for a scan_unroll value: ``True == 1`` in
    Python, so raw tuple comparison would conflate the full-unroll
    candidate with the default — and the drift rule would resolve a
    pipe onto the wrong candidate's MFU."""
    return "full" if u is True else int(u)


def effective_zero_level(pipe: Any) -> int:
    """The ZeRO level an SPMD pipe ACTUALLY runs, in the planner's
    ``Plan.zero`` vocabulary: bools resolve through the layout
    (``True`` → 3 under fsdp, else 1), and an fsdp pipe at dp > 1 runs
    the zero=3 program even when ``zero_update`` is 0/``False`` (the
    plain update against the stored-sharded layout IS the zero=3
    update) — matching the planner's 0 → 3 relabel on fsdp pipes."""
    own_dp = pipe.mesh.shape[pipe.dp_axis] if pipe.dp_axis else 1
    zu = getattr(pipe, "zero_update", False)
    fsdp = bool(getattr(pipe, "fsdp", False))
    if isinstance(zu, bool):
        level = (3 if fsdp else 1) if zu else 0
    else:
        level = int(zu)
    if fsdp and own_dp > 1 and level == 0:
        level = 3
    return level


def _config_of(pipe: Any) -> Tuple:
    """The (schedule, checkpoint, policy-label, chunks, balance,
    megastep, scan_unroll-key, dp, tp, ep, zero-level) key a pipe
    actually runs — matched against the planner's candidates."""
    from torchgpipe_tpu.gpipe import GPipe

    if isinstance(pipe, GPipe):
        return (pipe.schedule, pipe.checkpoint, None, pipe.chunks,
                tuple(pipe.balance), getattr(pipe, "megastep", 1),
                _unroll_key(1), 1, 1, 1, 0)
    own_dp = pipe.mesh.shape[pipe.dp_axis] if pipe.dp_axis else 1
    own_tp = pipe.mesh.shape[pipe.tp_axis] if pipe.tp_axis else 1
    own_ep = pipe.mesh.shape[pipe.ep_axis] if getattr(pipe, "ep_axis", None) else 1
    return (pipe.schedule, pipe.checkpoint, _spmd_policy_label(pipe),
            pipe.chunks, None, pipe.megastep,
            _unroll_key(pipe.scan_unroll), own_dp, own_tp, own_ep,
            effective_zero_level(pipe))


def check_plan_drift(trace: Any) -> List[Finding]:
    """WARNING when a pipe that declares ``hbm_budget_bytes`` runs a
    configuration whose predicted MFU trails the planner's certified top
    plan by more than :data:`PLAN_DRIFT_THRESHOLD` (10%).

    Opt-in by construction: without a declared budget the planner cannot
    certify feasibility, so the rule stands down (the same gate the
    memory-certification budget check uses).

    MEASURED drift: when the pipe carries a runtime reconciliation
    (:func:`torchgpipe_tpu.obs.reconcile` called with ``pipe=`` attaches
    its report), the rule also consumes the MEASURED bubble fraction —
    a run whose measured bubble exceeds the schedule's prediction by
    more than the documented tolerance WARNs even without a declared
    budget (the report's own :meth:`~torchgpipe_tpu.obs.
    ReconcileReport.drift_findings`, which stands down on dispatch-only
    timelines and <50% span coverage)."""
    measured: List[Finding] = []
    recon = getattr(trace.pipe, "_measured_reconcile", None)
    if recon is not None:
        # Stale-measurement guard: the attached report describes ONE
        # (schedule, chunks) configuration; if the pipe was reconfigured
        # since it was measured, its figures no longer apply — stand
        # down rather than re-emit findings about the old plan.  (A
        # rebalance at the same schedule/chunks is not detectable here;
        # re-run obs.reconcile after any reconfiguration.)
        g = recon.graph
        sched = getattr(trace.pipe, "schedule", g.schedule)
        if g.schedule == sched and g.chunks == trace.pipe.chunks:
            measured = list(recon.drift_findings())
    budget = getattr(trace.pipe, "hbm_budget_bytes", None)
    if budget is None:
        return measured
    try:
        report = plan(trace.pipe, trace.x_spec, budget)
    except Exception:  # noqa: BLE001 - the planner stands down, not lint
        return measured
    # Dispatch-granularity coherence with the dispatch-per-step rule:
    # unless the pipe built a DONATED train step (which already forfeits
    # per-step StepGuard retry), the user may be keeping megastep=1 /
    # scan_unroll for per-step guard semantics — compare only against
    # candidates at the pipe's OWN dispatch granularity rather than
    # recommending the coarsening that rule deliberately stands down
    # for.  A donated step makes the full K x unroll space fair game.
    if getattr(trace.pipe, "_train_step_donate", None) is not True:
        own_k = getattr(trace.pipe, "megastep", 1)
        own_u = _unroll_key(getattr(trace.pipe, "scan_unroll", 1))
        candidates = [
            p for p in report.candidates
            if p.megastep == own_k and _unroll_key(p.scan_unroll) == own_u
        ]
        report = dataclasses.replace(report, candidates=candidates)
    top = report.best
    if top is None or top.predicted_mfu is None:
        return measured
    def plan_key(p: Plan) -> Tuple:
        return (p.schedule, p.checkpoint, p.policy, p.chunks, p.balance,
                p.megastep, _unroll_key(p.scan_unroll), p.dp, p.tp,
                p.ep, p.zero)

    actual_key = _config_of(trace.pipe)
    actual = next(
        (p for p in report.candidates if plan_key(p) == actual_key),
        None,
    )
    if actual is None or actual.predicted_mfu is None:
        return measured
    top_key = plan_key(top)
    if top_key == actual_key:
        return measured
    drift = 1.0 - actual.predicted_mfu / top.predicted_mfu
    if drift <= PLAN_DRIFT_THRESHOLD and actual.feasible:
        return measured
    what = (
        "is over the declared HBM budget"
        if not actual.feasible
        else f"predicts {drift:.0%} lower MFU"
    )
    return measured + [Finding(
        rule="plan-drift",
        severity=Severity.WARNING,
        path=f"plan/{trace.engine}",
        message=(
            f"the configured plan (schedule={actual.schedule!r}, "
            f"checkpoint={actual.checkpoint!r}, "
            f"policy={actual.policy or '-'}, chunks={actual.chunks}, "
            f"megastep={actual.megastep}"
            + (f", balance={list(actual.balance)}" if actual.balance else "")
            + f") {what} than the certified top plan "
            f"(schedule={top.schedule!r}, checkpoint={top.checkpoint!r}, "
            f"policy={top.policy or '-'}, chunks={top.chunks}, "
            f"megastep={top.megastep}"
            + (f", balance={list(top.balance)}" if top.balance else "")
            + f", predicted MFU {top.predicted_mfu:.4f}, certified "
            f"HWM {top.hwm_bytes / GiB:.2f} GiB) — the drift threshold "
            f"is {PLAN_DRIFT_THRESHOLD:.0%}; apply it with "
            "analysis.planner.apply_plan(pipe, report.best)"
        ),
    )]
