"""SPMD pipeline engine: the whole GPipe schedule as ONE compiled XLA program.

This is the TPU-native flagship path.  Where the MPMD engine
(:mod:`torchgpipe_tpu.pipeline`) drives per-stage programs from Python —
mirroring the reference's scheduler (torchgpipe/pipeline.py:96-249) — this
engine expresses the entire fill-drain schedule *inside* one
``jax.shard_map``-ped, ``jax.jit``-ed training step:

* the ``n`` stages live on a ``"pp"`` mesh axis; every device runs the same
  block program on its own stage's parameter slice (stacked layout),
* stage hand-off is ``lax.ppermute`` over the ring — on TPU hardware this is a
  neighbor ICI transfer that XLA's latency-hiding scheduler overlaps with the
  block computation,
* the clock-cycle loop (reference ``clock_cycles``, pipeline.py:49-65) becomes
  a ``lax.scan`` over ``m + n - 1`` ticks: at tick ``t`` stage ``j`` computes
  micro-batch ``t - j`` — identical cell scheduling, but the *compiler* sees
  the whole pipeline and there is no per-tick host round-trip,
* backward is ``jax.grad`` through the scan: XLA reverses the schedule
  (transposed ``ppermute`` rings gradients backwards) — the explicit
  reverse-schedule the reference builds from autograd-edge surgery emerges
  from the scan transpose,
* activation checkpointing is ``jax.checkpoint`` on the block: boundary
  activations (the scan carries) are saved, block internals are recomputed —
  the GPipe memory profile (reference checkpoint.py:1-19) expressed as a
  remat policy,
* data parallelism composes on a second mesh axis: batch sharded over
  ``"dp"``, gradients ``psum``-reduced across it — replacing the reference
  fork's RPC+CPU-staging distributed mode (torchgpipe/distributed/) with XLA
  collectives over ICI/DCN.

Constraints (vs the MPMD engine): stages must be *stacked* — same block
structure with equal input/output shapes (transformer-style) — the batch must
divide evenly by ``chunks`` × dp, and layer state must be empty (use the MPMD
engine for BatchNorm-style stateful CNNs).
"""

from __future__ import annotations

import contextlib
import dataclasses
from typing import Any, Callable, Dict, List, Optional, Sequence, Tuple, Union

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from torchgpipe_tpu import microbatch
from torchgpipe_tpu.auxgrad import aux_scale
from torchgpipe_tpu.layers import Layer, Spec
from torchgpipe_tpu.parallel.tensor import all_gather_value
from torchgpipe_tpu.resilience import faults as _faults
from torchgpipe_tpu.utils.tracing import default_timeline

Pytree = Any


def _row_coupled(layer: Layer) -> list:
    """Row-coupled mechanisms in ``layer`` whose AUXILIARY terms see ragged
    padding rows (batch-norm statistics average over the padded micro-batch;
    a MoE balance penalty counts the duplicated tokens).  Task-loss
    gradients stay exact either way — this feeds the one-time ragged-batch
    warning in :meth:`SpmdGPipe.train_step`."""
    out = []
    meta = layer.meta
    if isinstance(meta, dict):
        if meta.get("kind") == "compound":
            children = meta["children"]
            values = (
                children.values() if isinstance(children, dict) else children
            )
            for child in values:
                out.extend(_row_coupled(child))
        else:
            kind = meta.get("kind")
            if kind in ("batch_norm", "deferred_batch_norm"):
                out.append(f"{kind} statistics")
            if meta.get("balance_weight", 0.0) > 0.0:
                out.append("MoE balance_weight penalty")
    return out


def _declared_axes(layer: Layer, key: str) -> list:
    """Collect ``meta[key]`` declarations, recursing into compounds."""
    out = []
    meta = layer.meta
    if isinstance(meta, dict):
        if meta.get("kind") == "compound":
            children = meta["children"]
            values = children.values() if isinstance(children, dict) else children
            for child in values:
                out.extend(_declared_axes(child, key))
        elif key in meta:
            out.append(meta[key])
    return out


def layer_param_specs(layer: Layer, stage_axis: Optional[str] = None) -> Pytree:
    """``PartitionSpec`` pytree *prefix* for a layer's params.

    ``stage_axis`` names the leading stacked-stage dim for pipeline blocks
    (specs get it prepended); pass ``None`` for un-stacked layers (pre/post),
    whose declared specs apply as-is.

    Layers declare sharded leaves via ``meta['param_specs']`` — a dict naming
    *every* param key with its per-stage spec (e.g. the tensor-parallel
    transformer block shards head/hidden dims over the tp axis; the MoE
    layer shards the expert dim over the ep axis).  A declared value may
    itself be a dict (a sub-layer's specs) or a bare ``P`` prefix covering
    that subtree.  Undeclared layers get a single ``P(stage_axis)`` prefix
    covering their whole params subtree (stacked-stage dim sharded,
    everything else replicated).  Compound layers (chain/structured)
    recurse; fully-replicated subtrees collapse back to one prefix spec.
    The result is valid as a shard_map in/out spec and broadcasts to
    per-leaf form via :func:`broadcast_specs`.
    """
    repl = P(stage_axis) if stage_axis else P()
    meta = layer.meta
    if isinstance(meta, dict) and meta.get("kind") == "compound":
        children = meta["children"]
        if isinstance(children, dict):
            sub: Any = {
                k: layer_param_specs(v, stage_axis) for k, v in children.items()
            }
            vals = list(sub.values())
        else:
            sub = tuple(layer_param_specs(c, stage_axis) for c in children)
            vals = list(sub)
        if all(isinstance(v, P) and v == repl for v in vals):
            return repl
        return sub
    declared = meta.get("param_specs") if isinstance(meta, dict) else None
    if declared:

        def with_stage(s):
            if isinstance(s, P):
                return P(stage_axis, *tuple(s)) if stage_axis else s
            return {k: with_stage(v) for k, v in s.items()}

        return {k: with_stage(s) for k, s in declared.items()}
    return repl


def spec_mentions(spec: P, axis: str) -> bool:
    """True if a PartitionSpec shards any dim over ``axis``."""
    for ax in spec:
        if ax is None:
            continue
        if axis in (ax if isinstance(ax, tuple) else (ax,)):
            return True
    return False


def broadcast_specs(prefix: Pytree, tree: Pytree) -> Pytree:
    """Expand a spec pytree-prefix to one ``PartitionSpec`` per leaf of
    ``tree`` (the same broadcasting shard_map applies to its in_specs)."""
    return jax.tree_util.tree_map(
        lambda spec, subtree: jax.tree_util.tree_map(lambda _: spec, subtree),
        prefix,
        tree,
        is_leaf=lambda x: isinstance(x, P),
    )


def _interleaved_rows(tb: Any) -> Tuple[np.ndarray, np.ndarray, np.ndarray]:
    """Schedule tables as scan xs: per-tick (kind, chunk, mb) rows plus the
    previous tick's rows (tick -1 = all idle), for sender classification."""
    from torchgpipe_tpu.parallel.interleaved import IDLE

    n = tb.n
    kind_t = jnp.asarray(tb.kind)
    chunk_t = jnp.asarray(tb.chunk)
    mb_t = jnp.asarray(tb.mb)
    pad = jnp.full((1, n), IDLE, jnp.int32)
    zrow = jnp.zeros((1, n), jnp.int32)
    return (
        kind_t,
        chunk_t,
        mb_t,
        jnp.concatenate([pad, kind_t[:-1]], 0),
        jnp.concatenate([zrow, chunk_t[:-1]], 0),
        jnp.concatenate([zrow, mb_t[:-1]], 0),
    )


def _sub_key(base: Optional[jax.Array], i: jax.Array) -> Optional[jax.Array]:
    """Per-micro-batch sub-key, or None when running without rng."""
    return None if base is None else jax.random.fold_in(base, i)


def _scoped(name: str, fn: Callable) -> Callable:
    """``fn`` traced under ``jax.named_scope(name)``.  Metadata only: the
    scope lands in the ``op_name`` of every operation ``fn`` traces (and,
    wrapped in ``transpose(jvp(...))``, of its autodiff backward), which
    is what a reader of the device trace can hold on to — ``forward`` /
    ``backward`` / ``optimizer`` for a step's parts, ``tick`` for a
    schedule's scan body."""

    def scoped(*args: Any, **kwargs: Any) -> Any:
        with jax.named_scope(name):
            return fn(*args, **kwargs)

    return scoped


# The schedules' validity rules.  Written with operators and ``xp.where``
# alone, so that the SAME function decides a cell inside the traced tick
# body (``xp=jnp`` on the traced tick and stage) and on the host, over
# the whole (tick, stage) grid, for :func:`schedule_shape`.


def _fill_drain_cell(t: Any, stage: Any, m: int) -> Tuple[Any, Any]:
    """Fill-drain: at tick ``t`` stage ``stage`` computes micro-batch
    ``t - stage``; the cell carries a micro-batch (is not fill or drain
    garbage) where that index is in ``[0, m)``."""
    mb = t - stage
    return mb, (mb >= 0) & (mb < m)


def _one_f1b_cell(
    t: Any, stage: Any, n: int, m: int, xp: Any = jnp
) -> Tuple[Any, Any, Any, Any]:
    """1F1B closed form (see ``_build_train_step_1f1b``): ``(do_f, i_f,
    do_b, i_b)`` — whether the stage runs a forward / backward cell at
    tick ``t`` and on which micro-batch (clipped into range where it
    runs none)."""
    tj = t - stage
    warm = (tj >= 0) & (tj <= n - 1 - stage) & (tj < m)
    i_s = xp.where(tj >= 0, tj // 2, 0)
    steady = (
        (tj >= 0) & (tj % 2 == 0) & (i_s > n - 1 - stage) & (i_s < m)
    )
    i_f = xp.clip(xp.where(warm, tj, i_s), 0, m - 1)
    num = t + stage - (2 * n - 1)
    do_b = (num >= 0) & (num % 2 == 0) & (num // 2 < m)
    i_b = xp.clip(xp.where(num >= 0, num // 2, 0), 0, m - 1)
    return warm | steady, i_f, do_b, i_b


def schedule_shape(
    schedule: str, n_stages: int, chunks: int, virtual_stages: int = 1
) -> Dict[str, Any]:
    """What a train step of this schedule is BUILT with: ``ticks`` of its
    scan, ``stage_ticks`` (stages x ticks) and ``busy_stage_ticks``, those
    that carry a micro-batch — counted from the rule the tick body itself
    traces (:func:`_fill_drain_cell`, :func:`_one_f1b_cell`, the ``kind``
    tables the zb / interleaved scans consume), so a schedule whose masks
    change changes the count.  ``1 - busy / stage_ticks`` is the bubble as
    a share of ticks.  Fill-drain counts its forward scan; the backward is
    that scan's transpose, the same ticks again."""
    n, m = n_stages, chunks
    if schedule == "fill_drain":
        ticks = m + n - 1
        t, stage = np.arange(ticks)[:, None], np.arange(n)[None, :]
        busy = _fill_drain_cell(t, stage, m)[1]
    elif schedule == "1f1b":
        ticks = 2 * (m + n - 1)
        t, stage = np.arange(ticks)[:, None], np.arange(n)[None, :]
        do_f, _, do_b, _ = _one_f1b_cell(t, stage, n, m, xp=np)
        busy = do_f | do_b
    elif schedule == "zb":
        from torchgpipe_tpu.parallel import zerobubble

        tb = zerobubble.zero_bubble_tables(n, m)
        ticks, busy = tb.ticks, tb.kind != zerobubble.IDLE
    elif schedule == "interleaved":
        from torchgpipe_tpu.parallel import interleaved

        tb = interleaved.interleaved_tables(n, m, virtual_stages)
        ticks, busy = tb.ticks, tb.kind != interleaved.IDLE
    else:
        raise ValueError(f"unknown schedule {schedule!r}")
    return {
        "schedule": schedule,
        "ticks": int(ticks),
        "stage_ticks": int(ticks) * n,
        "busy_stage_ticks": int(np.count_nonzero(busy)),
    }


def _rule_leaf_specs(spec_tree: Pytree) -> list:
    """(path, PartitionSpec) pairs of a resolved per-leaf spec tree
    (PartitionSpec is itself a pytree leaf, so a plain path flatten
    yields exactly the per-leaf specs)."""
    from torchgpipe_tpu.analysis.partition_rules import tree_leaf_paths

    return [
        (path, s)
        for path, s in tree_leaf_paths(spec_tree)
        if isinstance(s, P)
    ]


try:  # Literal moved between jax.core and jax.extend.core across versions
    from jax.extend.core import Literal as _JaxprLiteral
except Exception:  # pragma: no cover - version fallback
    from jax.core import Literal as _JaxprLiteral


def _never_mode_spec(
    vjp_of: Callable, param_trees: Sequence[Pytree], x0: Pytree
) -> Tuple[Any, List[Any], List[bool]]:
    """Canonical residual spec for the checkpoint='never' stored-vjp path.

    One abstract trace of ``vjp_of(params..., x0)`` yields BOTH the jaxpr
    (to detect identity-forwarded PARAM residuals — vjp residuals of x@W
    include W itself, and buffering those would duplicate the weights once
    per ring slot) and the residual pytree spec (treedef + leaf shapes)
    used to rebuild the closure at backward time.  Returns
    ``(tdef, leaf_specs, passthrough, buffered_idx)`` where ``passthrough``
    maps residual-leaf index -> flat param-leaf index.
    """
    closed, shape = jax.make_jaxpr(vjp_of, return_shape=True)(
        *param_trees, x0
    )
    tdef = jax.tree_util.tree_structure(shape)
    leaf_specs = jax.tree_util.tree_leaves(shape)
    n_param_leaves = len(jax.tree_util.tree_leaves(param_trees))
    invar_pos = {v: k for k, v in enumerate(closed.jaxpr.invars)}
    passthrough = {}
    for oi, ov in enumerate(closed.jaxpr.outvars):
        if isinstance(ov, _JaxprLiteral):  # constant-folded residual
            continue
        k = invar_pos.get(ov)
        if k is not None and k < n_param_leaves:
            passthrough[oi] = k
    buffered_idx = [
        i for i in range(len(leaf_specs)) if i not in passthrough
    ]
    return tdef, leaf_specs, passthrough, buffered_idx


def _never_check_leaves(
    leaves: Sequence[Any], leaf_specs: Sequence[Any], what: str
) -> None:
    """Loud trace-time guard: the live vjp residual structure must match
    the canonical trace leaf-for-leaf, or the rebuild would silently
    misalign."""
    if len(leaves) != len(leaf_specs) or any(
        l.shape != sp.shape or l.dtype != sp.dtype
        for l, sp in zip(leaves, leaf_specs)
    ):
        raise AssertionError(
            f"{what} checkpoint='never': live vjp residual structure "
            "diverged from the canonical trace — file a bug"
        )


def _never_rebuild(
    tdef: Any,
    leaf_specs: Sequence[Any],
    passthrough: Sequence[bool],
    buffered_iter: Any,
    live_flat: Sequence[Any],
) -> Any:
    """Reassemble the full residual list (pass-through param leaves LIVE,
    the rest from the ring buffer) and rebuild the vjp closure."""
    leaves = [
        live_flat[passthrough[i]] if i in passthrough else next(buffered_iter)
        for i in range(len(leaf_specs))
    ]
    return jax.tree_util.tree_unflatten(tdef, leaves)


def _pad_batch(tree: Pytree, pad: int) -> Pytree:
    """Pad dim 0 by ``pad`` rows, edge-replicating the last row — replicas
    are valid inputs for any layer/loss (no NaN traps from zero tokens);
    the ragged-batch mask zeroes their loss and gradient contribution.
    Reference semantics anchor: the reference scatters indivisible batches
    into ragged micro-batches (reference microbatch.py:143-158); a padded
    uniform scatter + masked loss is the SPMD-compatible equivalent."""
    if pad == 0:
        return tree
    return jax.tree_util.tree_map(
        lambda a: jnp.pad(
            a, ((0, pad),) + ((0, 0),) * (a.ndim - 1), mode="edge"
        ),
        tree,
    )


def _slot_read(buf: Pytree, idx: jax.Array) -> Pytree:
    """Read slot ``idx`` from a stacked ring-buffer pytree."""
    return jax.tree_util.tree_map(
        lambda b: lax.dynamic_index_in_dim(b, idx, 0, keepdims=False), buf
    )


def _slot_write(
    buf: Pytree, idx: jax.Array, val: Pytree, valid: jax.Array
) -> Pytree:
    """Write ``val`` into slot ``idx`` where ``valid``, else keep."""
    cur = _slot_read(buf, idx)
    new = jax.tree_util.tree_map(
        lambda c_, v_: jnp.where(valid, v_, c_), cur, val
    )
    return jax.tree_util.tree_map(
        lambda b, nv: lax.dynamic_update_index_in_dim(b, nv, idx, 0),
        buf,
        new,
    )


def _classify_fwd_recv(
    stage: jax.Array,
    n: int,
    v: int,
    S: int,
    pkrow: np.ndarray,
    pcrow: np.ndarray,
    pirow: np.ndarray,
) -> Tuple[jax.Array, jax.Array]:
    """Forward-ring receive routing: the value arriving at this tick is
    whatever the ring predecessor computed last tick.  Returns the inbox
    slot index and a validity mask (the wrap n-1 -> 0 advances the chunk;
    the final chunk's last-stage output has no forward consumer)."""
    from torchgpipe_tpu.parallel.interleaved import FWD

    src = jnp.mod(stage - 1, n)
    pk, pc, pi = pkrow[src], pcrow[src], pirow[src]
    valid = (pk == FWD) & jnp.logical_not((stage == 0) & (pc == v - 1))
    tc = jnp.clip(jnp.where(stage == 0, pc + 1, pc), 0, v - 1)
    return tc * S + pi % S, valid


def _classify_bwd_recv(
    stage: jax.Array,
    n: int,
    v: int,
    S: int,
    pkrow: np.ndarray,
    pcrow: np.ndarray,
    pirow: np.ndarray,
) -> Tuple[jax.Array, jax.Array]:
    """Backward-ring receive routing (the wrap 0 -> n-1 retreats the chunk;
    chunk 0's input cotangent leaves the model and is discarded)."""
    from torchgpipe_tpu.parallel.interleaved import BWD

    src = jnp.mod(stage + 1, n)
    pk, pc, pi = pkrow[src], pcrow[src], pirow[src]
    valid = (pk == BWD) & jnp.logical_not((stage == n - 1) & (pc == 0))
    tc = jnp.clip(jnp.where(stage == n - 1, pc - 1, pc), 0, v - 1)
    return tc * S + pi % S, valid


def _shard_map(
    fn: Callable, mesh: Mesh, in_specs: Any, out_specs: Any
) -> Callable:
    """``jax.shard_map`` with replication checking off — the engines'
    ring programs are intentionally lane-varying."""
    return jax.shard_map(
        fn, mesh=mesh, in_specs=in_specs, out_specs=out_specs,
        check_vma=False,
    )


@dataclasses.dataclass
class SpmdGPipe:
    """GPipe over a stacked block, compiled as a single SPMD program.

    Args:
      block: the per-stage computation (use :func:`torchgpipe_tpu.layers.chain`
        to build it from sub-layers).  Input and output specs must match.
      n_stages: pipeline depth; must equal the ``pp`` mesh axis size.
      mesh: ``jax.sharding.Mesh`` with at least the ``pp`` axis; optionally a
        ``dp`` axis for data parallelism.
      chunks: micro-batches per mini-batch (m).
      loss_fn: ``loss_fn(output, target) -> scalar`` on gathered outputs.
      pre / post: optional layers applied before stage 0 / after stage n-1
        (e.g. embedding / LM head).  Their parameters are replicated over
        ``pp``; their gradients are psum-shared.
      checkpoint: 'always' (remat the block per cell — GPipe memory
        profile), 'except_last' (the last micro-batch's cells skip remat —
        their backward needs no recompute since it runs right after their
        forward; reference gpipe.py:360-367), 'never', or 'offload'
        (fill-drain only): remat the block with an offload-to-host save
        policy — the checkpoint-named intermediates
        (:data:`torchgpipe_tpu.checkpoint.NAMED_SAVE_POINTS`) are copied
        to ``pinned_host`` memory at forward time and read back in the
        backward, so they are neither recomputed nor device-resident —
        the measured 17.7 GiB residual wall's direct fix (docs/tuning.md).
      remat_policy: optional ``jax.checkpoint`` policy refining
        ``checkpoint='always'``/``'except_last'``/``'offload'`` (e.g.
        ``jax.checkpoint_policies.dots_with_no_batch_dims_saveable`` keeps
        matmul outputs and recomputes only cheap elementwise ops, or the
        named-save presets in
        :data:`torchgpipe_tpu.checkpoint.policies` — blocks tag their
        expensive intermediates with ``checkpoint_name``, so e.g.
        ``policies.save_attn_out`` keeps one [b, s, dim] tensor per block
        and recomputes the rest).  Under 'offload' the default is
        ``policies.offload_default()``.
      loss_reduction: 'mean' (default) or 'sum' declares that ``post`` and
        ``loss_fn`` decompose over batch elements with that reduction,
        letting the engine shard the head + loss over the ``pp`` axis (1/n
        of the logits per device) and accept RAGGED batches (B not
        divisible by chunks·dp·ep: the batch is edge-padded and a mask
        weights the padding out of loss and grads exactly — reference
        parity with indivisible-batch scatter, reference
        microbatch.py:143-158).  Pass ``None`` for a non-decomposable
        loss — the head/loss then run replicated on the full batch, and
        ragged batches are rejected with a didactic error.
      fsdp: ZeRO-3/FSDP-style parameter sharding (new capability — the
        reference lists ZeRO/FSDP as absent, SURVEY.md §2.2): block
        parameters are STORED sharded over the ``dp`` axis (each leaf's
        first eligible dim), all-gathered once per step at use, and their
        gradients come back as shards via the all_gather's transpose (a
        reduce-scatter) — per-device parameter + gradient memory drops by
        ~the dp size for one gather/scatter pair per step over ICI.
        Requires ``dp_axis``; incompatible with ``ep_axis`` (expert leaves
        are already dp-style sharded over ep).
      schedule: 'fill_drain' (default; the reference's GPipe schedule),
        '1f1b' (PipeDream-flush), 'interleaved' (Megatron virtual
        pipeline stages; see ``virtual_stages``) or 'zb' (zero-bubble:
        the backward splits into activation-gradient B cells and
        weight-gradient W cells that back-fill bubble ticks — per-tick
        backward work halves; ``checkpoint='never'`` replays F-stored
        vjp residuals in both halves (zero recompute), and
        ``checkpoint='always'`` recomputes once in the B cell with O(1)
        residual slots; see
        :mod:`torchgpipe_tpu.parallel.zerobubble`).  1F1B interleaves each
        micro-batch's backward with later micro-batches' forwards inside
        the same compiled scan, computing gradients explicitly per cell,
        so in-flight activations per stage are bounded by the pipeline
        depth ``n`` instead of the micro-batch count ``m`` — same bubble
        fraction, O(n) instead of O(m) activation memory.  Both
        explicit-gradient schedules require a micro-batch-decomposable
        loss (``loss_reduction`` 'mean'/'sum') and support every
        checkpoint mode: ``'always'`` recomputes each cell in its backward
        tick (per-cell ``jax.vjp``), ``'never'`` stores every in-flight
        cell's vjp residuals in the schedule's ring buffers (more memory,
        zero recompute), and ``'except_last'`` — the reference's default
        (reference gpipe.py:360-367) — recomputes all micro-batches except
        the last, whose residuals fit in a single slot because its
        backward starts right after its forward.  They compose with dp,
        tp, ep (MoE) and fsdp — but not sp, whose ring attention would put
        collective-permutes inside the schedule conditional (see the
        ``__post_init__`` error).  New capability: the reference has
        fill-drain only (SURVEY.md §2.2).
    """

    block: Layer
    n_stages: int
    mesh: Mesh
    chunks: int
    loss_fn: Callable
    pre: Optional[Layer] = None
    post: Optional[Layer] = None
    checkpoint: str = "always"
    # Optional jax.checkpoint policy for checkpoint='always' (e.g.
    # jax.checkpoint_policies.dots_with_no_batch_dims_saveable keeps matmul
    # outputs and recomputes only cheap elementwise ops — less recompute for
    # a bit more memory).  None = save nothing but the scan carries.
    remat_policy: Optional[Callable] = None
    pp_axis: str = "pp"
    dp_axis: Optional[str] = None
    sp_axis: Optional[str] = None
    tp_axis: Optional[str] = None
    ep_axis: Optional[str] = None
    loss_reduction: Optional[str] = "mean"
    fsdp: bool = False
    # 'fill_drain' (GPipe; reference pipeline.py:49-65), '1f1b'
    # (one-forward-one-backward, PipeDream-flush) or 'interleaved'
    # (Megatron virtual pipeline stages, arXiv:2104.04473 §2.2).  1F1B:
    # same bubble as fill-drain, but the schedule interleaves each
    # micro-batch's backward with later forwards, capping in-flight
    # activations per stage at ~n instead of m.  Interleaved: each device
    # additionally owns ``virtual_stages`` non-adjacent model chunks, so
    # the fill/drain bubble shrinks by ~v on top of 1F1B's memory bound.
    # Both compute gradients EXPLICITLY inside the scan (per-cell jax.vjp
    # with recompute — checkpoint='always' semantics) and need a
    # micro-batch-decomposable loss (loss_reduction 'mean'/'sum').
    schedule: str = "fill_drain"
    # Model chunks per device for schedule='interleaved' (v >= 2; the
    # model then has n_stages * virtual_stages blocks, device j holding
    # global blocks c*n + j for c in range(v) — Megatron's round-robin
    # assignment).  Must be 1 for the other schedules.
    virtual_stages: int = 1
    # Unroll factor for the schedule's tick scan (``lax.scan(unroll=...)``;
    # True = fully unroll).  Unrolling makes slot/ring indices static so
    # XLA folds the buffer machinery and fuses across ticks — measured
    # -26%/-14% (1f1b) and -29%/-33% (zb) step time at toy/dim-1024
    # cells on the CPU mesh (BENCH_NOTES round 4) — at compile time
    # roughly linear in the factor (1.6s -> 8.7s fully unrolled there).
    # SCHEDULE-DEPENDENT: it serves the slot-buffer schedules (1f1b, zb,
    # interleaved); fill-drain's remat-structured scans measured SLOWER
    # fully unrolled at large cells — leave fill_drain at the default.
    scan_unroll: Union[int, bool] = 1
    # Send-ahead communication/compute overlap (the JaxPP latency-hiding
    # shape, arXiv:2412.14374): the fill_drain and 1f1b tick bodies issue
    # the ``ppermute`` of tick t's output at tick t's TAIL — right after
    # the cell compute that produced it — instead of at tick t+1's head,
    # carrying the already-permuted value through the scan.  The values
    # flowing are identical (bitwise-tested against send_ahead=False),
    # but the transfer no longer sits between two ticks' compute in
    # program order, so XLA's async collective-permute can hide it under
    # the neighbouring tick's independent work.  zb/interleaved keep
    # their head-of-tick shape (their static tables are not yet
    # software-pipelined); the flag is ignored there.
    send_ahead: bool = True
    # Default megastep K for :meth:`make_train_step`: K optimizer steps
    # compiled into ONE program (``lax.scan`` over the full pipelined
    # step with a donated carry).  Declared here — rather than only at
    # make_train_step call sites — so the static analyses (the
    # ``dispatch-per-step`` lint rule, the planner's megastep axis) can
    # see the configured dispatch granularity.
    megastep: int = 1
    # Declared per-chip HBM budget (bytes).  Opt-in: the schedule
    # verifier's memory certification ERRORs on overrun, and the
    # plan-drift lint rule compares the running configuration against
    # analysis.planner's certified top plan under it.
    hbm_budget_bytes: Optional[int] = None
    # Runtime timeline (utils.tracing.Timeline — the obs trace spine);
    # None records into the process's bounded default timeline.  The
    # compiled scan's cells are not host-visible, so the HONEST recording
    # granularity is the dispatch: make_train_step's returned callable
    # records one "step" (K=1) or "megastep" span per call, at stage -1
    # (the whole-program row), carrying the schedule it was built with
    # (:func:`schedule_shape`).  By default the span is what the host
    # pays to launch the program; with sync=True it is true device time
    # (the span blocks on the step outputs).  Use obs.device_trace for
    # the XLA-level interior of the scan: its operations carry the
    # ``forward`` / ``backward`` / ``optimizer`` / ``tick`` scopes.
    tracer: Any = None
    # Optional user-declared partition-rule table (an ordered
    # analysis.partition_rules.RuleTable or (regex, PartitionSpec)
    # pairs) replacing the structurally-derived layout: ``place()`` and
    # the static sharding verifier resolve every param leaf through it,
    # first match wins, and an UNMATCHED leaf is a didactic error (the
    # ``implicit-reshard`` lint rule's ERROR), never silent replication.
    # None (default): the engine EMITS the equivalent table from its
    # structural declarations — see :meth:`rule_table`.
    partition_rules: Any = None
    # ZeRO-style sharded optimizer update (arXiv:2004.13336 /
    # arXiv:1910.02054): the default for :meth:`make_train_step`'s
    # ``zero=`` — a LEVEL, not a flag (``bool`` accepted for
    # compatibility and normalized by :meth:`_zero_level`):
    #   0 / False  — replicated optimizer state, plain update;
    #   1 / True   — optimizer state partitioned over the dp axis (each
    #                data-parallel lane stores and updates 1/N_dp of
    #                every state leaf), updated params all-gathered at
    #                apply; needs dp-replicated params;
    #   3          — fully-sharded (ZeRO-3/fsdp): params, grads AND
    #                optimizer state all live sharded over dp
    #                (gather-at-use storage layout); requires
    #                ``fsdp=True`` — the update itself is the plain
    #                elementwise apply, which GSPMD keeps sharded
    #                end-to-end because grads exit the step in the fsdp
    #                storage layout (the all_gather's transpose IS the
    #                reduce-scatter).
    # Bitwise-equal to the unsharded update for elementwise optimizers
    # (adam/adamw/sgd) at every level; declared on the pipe so the
    # planner's memory certification sees the configured optimizer
    # layout.
    zero_update: Union[bool, int] = False
    # How the engine materializes gather-at-use (ZeRO-3/fsdp) params:
    # 'block' (default) — all params are gathered ONCE per block scan
    # body and the gathered copies are live for the block's compute
    # window (what ``_gather_fsdp`` compiles today); 'use' — modeled
    # per-use-site gathering (each consuming eqn re-gathers), trading
    # repeated all_gather bytes for a smaller transient window.  The
    # static stack (sharding verifier's gather schedule accounting, the
    # ``redundant-gather`` lint rule, the planner's gathered-window
    # memory term) prices both; the compiled program currently always
    # uses the 'block' shape.
    gather_schedule: str = "block"

    def __repr__(self) -> str:
        axes = {
            name: self.mesh.shape[name] for name in self.mesh.axis_names
        }
        extras = "".join(
            f", {k}={v!r}"
            for k, v, default in (
                ("loss_reduction", self.loss_reduction, "mean"),
                ("fsdp", self.fsdp, False),
                ("schedule", self.schedule, "fill_drain"),
                ("virtual_stages", self.virtual_stages, 1),
                ("scan_unroll", self.scan_unroll, 1),
                ("send_ahead", self.send_ahead, True),
                ("megastep", self.megastep, 1),
                ("zero_update", self.zero_update, False),
                ("gather_schedule", self.gather_schedule, "block"),
            )
            if v != default
        )
        return (
            f"SpmdGPipe(block={self.block.name!r}, n_stages={self.n_stages}, "
            f"chunks={self.chunks}, checkpoint={self.checkpoint!r}, "
            f"mesh={axes}{extras})"
        )

    def __post_init__(self) -> None:
        if self.pp_axis not in self.mesh.axis_names:
            raise ValueError(f"mesh has no {self.pp_axis!r} axis: {self.mesh}")
        # loss_fn may be a parametric LOSS LAYER (init/apply with params;
        # e.g. models.transformer.chunked_lm_loss) instead of a plain
        # callable; its params live under params["loss"], replicated over
        # pp, with grads psum-shared like pre/post.
        self._loss_is_layer = isinstance(self.loss_fn, Layer)
        loss_lyr = self.loss_fn if self._loss_is_layer else None
        for what, lyr in (("block", self.block), ("pre", self.pre), ("post", self.post), ("loss", loss_lyr)):
            if lyr is not None and (lyr.stash or lyr.pop):
                raise ValueError(
                    f"SPMD engine does not support cross-stage skip "
                    f"connections, but {what} layer {lyr.name!r} declares "
                    "stash/pop. Resolve the skips inside a chain() stage "
                    "(runnable demo: examples/spmd_skips.py), or use the "
                    "MPMD GPipe engine for cross-stage skip routing."
                )
        if self.loss_reduction not in ("mean", "sum", None):
            raise ValueError("loss_reduction must be 'mean', 'sum' or None")
        # Weight tying (meta['tie_pre']): the post/loss layer asks for
        # these pre-param entries to be spliced into its param dict at
        # apply time (e.g. a tied lm head reading the embedding table,
        # models.transformer TransformerConfig.tie_embeddings).  Pre
        # params are replicated across pp lanes, so the splice reuses the
        # SAME traced array and autodiff sums both gradient paths into
        # grads['pre'] — no extra reduction machinery.
        def _tie_keys(lyr: Optional[Layer]) -> Tuple[str, ...]:
            if lyr is None or not isinstance(lyr.meta, dict):
                return ()
            return tuple(lyr.meta.get("tie_pre", ()))

        self._tie_post = _tie_keys(self.post)
        self._tie_loss = _tie_keys(loss_lyr)
        if self._tie_post or self._tie_loss:
            if self.pre is None:
                raise ValueError(
                    "meta['tie_pre'] asks for pre-param splicing, but the "
                    "engine has no pre layer to take them from"
                )
            if self.schedule != "fill_drain":
                raise ValueError(
                    f"weight tying (meta['tie_pre']) is supported on the "
                    f"fill_drain schedule, not {self.schedule!r}: the "
                    "explicit-gradient schedules hand-accumulate per-cell "
                    "cotangents and do not yet route the tied "
                    "contribution into grads['pre'].  Use "
                    "schedule='fill_drain', or untie"
                )
        if not (
            self.scan_unroll is True
            or (isinstance(self.scan_unroll, int)
                and not isinstance(self.scan_unroll, bool)
                and self.scan_unroll >= 1)
        ):
            raise ValueError(
                f"scan_unroll must be True or an int >= 1, got "
                f"{self.scan_unroll!r}"
            )
        if not (
            isinstance(self.megastep, int)
            and not isinstance(self.megastep, bool)
            and self.megastep >= 1
        ):
            raise ValueError(
                f"megastep must be an int >= 1, got {self.megastep!r}"
            )
        if self.mesh.shape[self.pp_axis] != self.n_stages:
            raise ValueError(
                f"pp mesh axis size {self.mesh.shape[self.pp_axis]} != "
                f"n_stages {self.n_stages}"
            )
        for ax in (self.dp_axis, self.sp_axis, self.tp_axis, self.ep_axis):
            if ax is not None and ax not in self.mesh.axis_names:
                raise ValueError(f"mesh has no {ax!r} axis: {self.mesh}")
        if self.checkpoint not in ("always", "except_last", "never", "offload"):
            raise ValueError(
                "SPMD engine supports checkpoint="
                "'always'|'except_last'|'never'|'offload'"
            )
        if self.checkpoint == "offload" and self.schedule != "fill_drain":
            raise ValueError(
                f"checkpoint='offload' is a fill_drain feature: the "
                f"{self.schedule!r} schedule hand-writes its per-cell "
                "recompute/residual machinery (no jax.checkpoint region "
                "to attach the offload save policy to).  Use "
                "schedule='fill_drain', or checkpoint='never'/'always'"
            )
        if self.fsdp and self.dp_axis is None:
            raise ValueError(
                "fsdp shards parameters over the data-parallel lanes: set "
                "dp_axis (and give the mesh a dp axis of size > 1)"
            )
        if self.fsdp and self.ep_axis is not None:
            raise ValueError(
                "fsdp + ep is not supported: expert weights are already "
                "sharded over ep; shard the rest with tp instead"
            )
        if self.gather_schedule not in ("block", "use"):
            raise ValueError(
                "gather_schedule must be 'block' (gather each param once "
                "per block scan body) or 'use' (model per-use-site "
                f"gathering), got {self.gather_schedule!r}"
            )
        self._zero_level(self.zero_update)  # validate the declared level
        if self.sp_axis is not None and self.loss_reduction is None:
            raise ValueError(
                "sequence parallelism needs a batch/token-decomposable loss: "
                "set loss_reduction='mean' or 'sum'"
            )
        if self.ep_axis is not None and self.loss_reduction is None:
            raise ValueError(
                "expert parallelism shards the batch over the ep axis, so it "
                "needs a batch-decomposable loss: set loss_reduction='mean' "
                "or 'sum'"
            )
        if self.schedule not in ("fill_drain", "1f1b", "interleaved", "zb"):
            raise ValueError(
                "schedule must be 'fill_drain', '1f1b', 'interleaved' "
                "or 'zb'"
            )
        if self.schedule == "interleaved":
            if self.virtual_stages < 2:
                raise ValueError(
                    "schedule='interleaved' needs virtual_stages >= 2 "
                    "(with one chunk per device it degenerates to "
                    "schedule='1f1b' — use that instead)"
                )
            if self.chunks % self.n_stages != 0:
                raise ValueError(
                    f"schedule='interleaved' needs chunks ({self.chunks}) "
                    f"divisible by n_stages ({self.n_stages}): Megatron's "
                    "micro-batch grouping (arXiv:2104.04473 §2.2) assumes "
                    "full groups"
                )
        elif self.virtual_stages != 1:
            raise ValueError(
                "virtual_stages only applies to schedule='interleaved'"
            )
        if self.schedule == "zb" and self.remat_policy is not None:
            raise ValueError(
                "remat_policy has no effect under schedule='zb': the "
                "recompute split is explicit in the schedule (B cells "
                "recompute whole cells under checkpoint='always'; "
                "checkpoint='never' stores vjp residuals outright)"
            )
        if self.schedule == "zb" and self.checkpoint == "except_last":
            raise ValueError(
                "schedule='zb' supports checkpoint='never' (vjp residuals "
                "stored at forward time, replayed by both backward halves "
                "— zero recompute, O(pipeline window) residual memory) and "
                "checkpoint='always' (the B cell recomputes the forward "
                "once and banks its vjp for the immediately-following W "
                "cell — O(1) residual slots for ~one extra forward per "
                "micro-batch); 'except_last' has no zb counterpart.  Use "
                "schedule='1f1b' for checkpoint='except_last'"
            )
        if self.schedule in ("1f1b", "interleaved", "zb"):
            sched = f"schedule={self.schedule!r}"
            if self.loss_reduction is None:
                raise ValueError(
                    f"{sched} computes per-micro-batch losses inside "
                    "the schedule, so the loss must decompose over "
                    "micro-batches: set loss_reduction='mean' or 'sum'"
                )
            if self.remat_policy is not None:
                raise ValueError(
                    f"{sched} hand-writes the per-cell recompute; "
                    "remat_policy does not apply (use schedule='fill_drain')"
                )
            if self.sp_axis is not None:
                raise ValueError(
                    f"{sched} does not compose with sequence "
                    "parallelism: ring attention's sp ppermutes would sit "
                    "inside the schedule's fwd/bwd conditional, whose "
                    "branches only some pipeline stages execute on a given "
                    "tick — collective-permute participation is global, so "
                    "lanes in the other branch would never join (verified "
                    "failure on the host backend).  psum-based tensor "
                    "parallelism is fine (group-local all-reduce); use "
                    "schedule='fill_drain' for sp"
                )
        # Layers may declare mesh-validation hooks (e.g. the tensor-parallel
        # transformer block checks that the tp size divides its head counts —
        # flat-dim divisibility alone would let a head split across lanes).
        for lyr in (self.block, self.pre, self.post):
            if lyr is not None:
                for validate in _declared_axes(lyr, "validate_mesh"):
                    validate(self.mesh)
        # Layers that collect over a sequence or tensor axis declare it in
        # meta (e.g. TransformerConfig.sp_axis / tp_axis); a mismatch with
        # the engine's axes would silently compute shard-local attention /
        # partial matmul sums, so fail loudly instead.
        for key, mine in (
            ("sp_axis", self.sp_axis),
            ("tp_axis", self.tp_axis),
            ("ep_axis", self.ep_axis),
        ):
            declared = set()
            for lyr in (self.block, self.pre, self.post):
                if lyr is not None:
                    declared.update(_declared_axes(lyr, key))
            if declared and declared != {mine}:
                raise ValueError(
                    f"model layers declare {key} {sorted(map(str, declared))} "
                    f"but the engine was given {key}={mine!r}; set "
                    f"both from the same value (e.g. TransformerConfig.{key} "
                    f"and SpmdGPipe.{key})"
                )

        raw_apply = self.block.apply

        def block_fn(params, x, rng, aux_s, train):
            # aux_s (the per-cell aux-gradient scale) is an explicit INPUT,
            # not a thread-local capture: jax.checkpoint caches the traced
            # jaxpr by avals, and a capture would freeze one schedule
            # position's traced scale into the cache — a dead tracer when
            # the except_last tail scan gets a cache hit on the jaxpr the
            # prefix scan traced.
            with aux_scale(aux_s):
                y, _ = raw_apply(params, (), x, rng=rng, train=train)
            return y

        # _block_fn_plain: the un-remat'd block — the 'never' path and the
        # last micro-batch's cells under 'except_last'.
        self._block_fn_plain = block_fn
        if self.checkpoint == "offload":
            from torchgpipe_tpu.checkpoint import policies as ckpt_policies

            if self.remat_policy is None:
                self.remat_policy = ckpt_policies.offload_default()
            block_fn = jax.checkpoint(
                block_fn, static_argnums=(4,), policy=self.remat_policy
            )
        elif self.checkpoint in ("always", "except_last"):
            block_fn = jax.checkpoint(
                block_fn, static_argnums=(4,), policy=self.remat_policy
            )
        elif self.remat_policy is not None:
            raise ValueError(
                "remat_policy only applies with checkpoint='always', "
                "'except_last' or 'offload'"
            )
        self._block_fn = block_fn
        # A block that counts something as it runs declares
        # ``meta['apply_counts']``: ``(params, x, rng=, train=) -> (y,
        # counts)``, an int array that adds up over cells.  The fill-drain
        # train step runs its cells through that, under the same
        # recomputation, and hands the sum out beside the loss.
        meta = self.block.meta if isinstance(self.block.meta, dict) else {}
        counts_apply = (
            meta.get("apply_counts") if self.schedule == "fill_drain"
            else None
        )
        self._counted = counts_apply is not None
        if self._counted:

            def block_fn_counts(params, x, rng, aux_s, train):
                with aux_scale(aux_s):
                    return counts_apply(params, x, rng=rng, train=train)

            self._cell_fns_counts = (
                block_fn_counts if self.checkpoint == "never"
                else jax.checkpoint(
                    block_fn_counts, static_argnums=(4,),
                    policy=self.remat_policy),
                block_fn_counts,
            )
        # Spec prefix for the stacked block params: stage dim over pp, plus
        # any per-leaf sharding the layers declare (tensor/expert-parallel
        # weights) — see layer_param_specs.
        self._blocks_spec = layer_param_specs(self.block, self.pp_axis)
        if self.virtual_stages > 1:
            # Blocks are stored ``[n, v, ...]`` (stage dim sharded over pp,
            # chunk dim device-local): declared per-stage specs gain a
            # replicated chunk dim at position 1.  Bare ``P(pp)`` prefixes
            # already leave later dims replicated and stay as-is.
            def _with_chunk_dim(spec):
                if len(spec) <= 1:
                    return spec
                return P(spec[0], None, *tuple(spec)[1:])

            self._blocks_spec = jax.tree_util.tree_map(
                _with_chunk_dim,
                self._blocks_spec,
                is_leaf=lambda x: isinstance(x, P),
            )
        # Pre/post are replicated over pp but may declare their own leaf
        # sharding (e.g. the vocab-parallel embedding/head under tp).
        self._pre_spec = (
            layer_param_specs(self.pre) if self.pre is not None else None
        )
        self._post_spec = (
            layer_param_specs(self.post) if self.post is not None else None
        )
        self._loss_spec = (
            layer_param_specs(self.loss_fn) if self._loss_is_layer else None
        )
        # Program caches, keyed by (use_rng, masked, fault-plan token) /
        # fault-plan token: an active resilience.faults plan is baked into
        # the traced program, so (de)activation must miss the cache.
        self._train_step_fns: dict = {}
        self._warned_ragged_coupled = False  # one-time ragged+aux warning
        self._apply_fns: dict = {}
        self._eval_fns: dict = {}
        # FSDP bookkeeping, resolved lazily from the first params tree seen
        # (leaf shapes are needed to pick shard dims): per block leaf, the
        # dim sharded over dp (-1 = replicated) and the augmented specs.
        self._fsdp_dims = None
        self._fsdp_specs = None

    # ------------------------------------------------------------------ #
    # FSDP (ZeRO-3-style parameter sharding over dp)                     #
    # ------------------------------------------------------------------ #

    def _fsdp_layout(
        self, blocks: Pytree, dp: int
    ) -> Tuple[Pytree, Pytree]:
        """The fsdp storage layout at data-parallel width ``dp``: per
        block leaf, the dim sharded over dp (-1 = replicated) and the
        augmented storage specs.  Pure in ``dp`` so the planner can
        evaluate candidate mesh widths that differ from the real mesh
        (divisibility is checked at the CANDIDATE width, not the
        machine's)."""
        base = self._blocks_leaf_specs(blocks)
        is_p = lambda x: isinstance(x, P)  # noqa: E731

        def choose(spec, leaf):
            # First dim after the stacked-stage dim (0) that no other axis
            # shards and that divides by dp; small/indivisible leaves (e.g.
            # norm scales) stay replicated.
            for i in range(1, len(leaf.shape)):
                taken = spec[i] if i < len(spec) else None
                if taken is None and leaf.shape[i] % dp == 0 and leaf.shape[i] >= dp:
                    return i
            return -1

        dims = jax.tree_util.tree_map(choose, base, blocks, is_leaf=is_p)

        def augment(spec, dim):
            if dim < 0:
                return spec
            parts = list(spec) + [None] * (dim + 1 - len(spec))
            parts[dim] = self.dp_axis
            return P(*parts)

        specs = jax.tree_util.tree_map(augment, base, dims, is_leaf=is_p)
        return dims, specs

    def _ensure_fsdp(self, blocks: Pytree) -> None:
        if not self.fsdp or self._fsdp_dims is not None:
            return
        dp = self.mesh.shape[self.dp_axis]
        self._fsdp_dims, self._fsdp_specs = self._fsdp_layout(blocks, dp)

    def _gather_fsdp(self, blocks_local: Pytree) -> Pytree:
        """Reassemble full block params from dp shards (inside shard_map).

        Differentiated: the all_gather's transpose is a psum_scatter, so
        each lane's gradient comes back as its shard, already summed over
        the dp lanes — the FSDP reduce-scatter for free.
        """
        return jax.tree_util.tree_map(
            lambda leaf, dim: (
                leaf
                if dim < 0
                else lax.all_gather(leaf, self.dp_axis, axis=dim, tiled=True)
            ),
            blocks_local,
            self._fsdp_dims,
        )

    # ------------------------------------------------------------------ #
    # per-cell helpers shared by the explicit-gradient schedules         #
    # (1F1B and interleaved)                                            #
    # ------------------------------------------------------------------ #

    def _cell_input_splice(
        self,
        p_pre: Pytree,
        first: jax.Array,
        i: jax.Array,
        fallback: Pytree,
        x_mb: Pytree,
        pre_base: Optional[jax.Array],
    ) -> Pytree:
        """The model's first block input (``pre`` applied to the raw
        micro-batch) where ``first`` holds for this cell; ``fallback`` (the
        ring hand-off, or the saved input in backward cells) elsewhere.

        ``pre`` (e.g. the embedding) runs per cell INSIDE the scan — the
        raw inputs ``x_mb`` it reads are engine inputs (tokens), so no
        O(m) stack of pre outputs ever materializes.  In backward cells
        the recompute doubles as the pre-gradient path: the splice routes
        the first cell's input cotangent through ``pre`` to its
        parameters, while every other cell's splice is dead and
        contributes zeros (keys match the forward cell, so the recomputed
        value is bit-identical).  The aux-injection scale is masked by the
        same predicate so only the real ``pre`` application counts.
        """
        tmap = jax.tree_util.tree_map
        raw = tmap(
            lambda a: lax.dynamic_index_in_dim(a, i, 0, keepdims=False), x_mb
        )
        if self.pre is None:
            return tmap(
                lambda inp, r: jnp.where(first, inp, r), raw, fallback
            )
        with aux_scale(jnp.where(first, 1.0 / self.chunks, 0.0)):
            x0, _ = self.pre.apply(
                p_pre, (), raw, rng=_sub_key(pre_base, i), train=True
            )
        return tmap(lambda a, r: jnp.where(first, a, r), x0, fallback)

    def _tied(
        self, own: Pytree, p_pre: Pytree, keys: Tuple[str, ...]
    ) -> Pytree:
        """Splice tied pre-param entries (meta['tie_pre']) into a post/
        loss layer's param dict.  Reusing the same traced array is the
        whole mechanism: autodiff sums the tied gradient paths into
        grads['pre'] with no further plumbing."""
        if not keys:
            return own
        return dict(own, **{k: p_pre[k] for k in keys})

    def _loss_call(
        self, p_loss: Pytree, y: Pytree, tgt: Pytree, train: bool = True
    ) -> jax.Array:
        """The engine's one loss entry point: a plain ``loss_fn(y, tgt)``
        callable, or a parametric loss layer applied to ``(y, tgt)`` with
        its own params (e.g. the fused chunked-vocab cross-entropy,
        models.transformer.chunked_lm_loss)."""
        if self._loss_is_layer:
            out, _ = self.loss_fn.apply(
                p_loss, (), (y, tgt), rng=None, train=train
            )
            return out
        return self.loss_fn(y, tgt)

    def _masked_loss_sum(
        self,
        p_loss: Pytree,
        y: Pytree,
        tgt: Pytree,
        mask: jax.Array,
        train: bool = True,
    ) -> jax.Array:
        """``Σ_rows mask · loss_fn(row)`` — the ragged-batch weighting
        primitive.

        Fast path: a loss LAYER that declares ``meta={'row_loss': fn}``
        (``fn(params, state, (y, tgt)) -> [B]`` per-row losses, each equal
        to the layer applied to that batch-1 slice) is evaluated ONCE on
        the whole micro-batch and masked — one batched call instead of B
        vmapped batch-1 calls (the chunked vocab cross-entropy takes this
        path; see :func:`models.transformer.chunked_lm_loss`).

        Fallback for opaque scalar losses: each row is presented to
        ``loss_fn`` as a batch-1 slice under ``vmap``.  Either way the
        declared row decomposition (``loss_reduction`` 'mean'/'sum')
        makes the masked sum exact: padded rows contribute zero to both
        value and gradient."""
        tmap = jax.tree_util.tree_map
        row_loss = (
            self.loss_fn.meta.get("row_loss")
            if self._loss_is_layer and isinstance(self.loss_fn.meta, dict)
            else None
        )
        if row_loss is not None:
            rows = row_loss(p_loss, (), (y, tgt)).astype(jnp.float32)
            return jnp.sum(rows * mask)

        def row(yy, tt):
            return self._loss_call(
                p_loss,
                tmap(lambda a: a[None], yy),
                tmap(lambda a: a[None], tt),
                train=train,
            ).astype(jnp.float32)

        return jnp.sum(jax.vmap(row)(y, tgt) * mask)

    def _mask_mean_scale(self, mask_local: jax.Array) -> jax.Array:
        """Traced per-lane scale turning a lane-local masked row-loss SUM
        into a value whose dp/ep ``pmean``s give the global masked mean:
        dp·ep (the later pmeans divide it back) over the REAL row count.
        The count comes from the mask itself (a psum over the
        batch-sharding axes), so ONE compiled step serves every ragged
        size that pads to the same bucket — no per-``B`` rebuild."""
        n_real = jnp.sum(mask_local)
        dpep = 1.0
        for ax in (self.dp_axis, self.ep_axis):
            if ax:
                n_real = lax.psum(n_real, ax)
                dpep *= self.mesh.shape[ax]
        return dpep / n_real

    def _cell_mb_loss(
        self,
        y: Pytree,
        p_post: Pytree,
        p_loss: Pytree,
        i: jax.Array,
        tgt_mb: Pytree,
        post_base: Optional[jax.Array],
        mask_mb: Optional[jax.Array] = None,
        mean_scale: Optional[jax.Array] = None,
    ) -> jax.Array:
        """Per-micro-batch head + loss for a final cell (aux scale 1/m:
        the m cells average to one mini-batch, mirroring the fill-drain
        head's 1/n over n batch slices).  With ``mask_mb`` (ragged
        batches) the loss is the masked per-row sum, scaled so the
        engine's Σ over cells + dp/ep pmeans yield the exact loss over
        the real rows."""
        tmap = jax.tree_util.tree_map
        if self.post is not None:
            with aux_scale(1.0 / self.chunks):
                y, _ = self.post.apply(
                    p_post, (), y, rng=_sub_key(post_base, i), train=True
                )
        tgt_i = tmap(
            lambda a: lax.dynamic_index_in_dim(a, i, 0, keepdims=False),
            tgt_mb,
        )
        if mask_mb is not None:
            mask_i = lax.dynamic_index_in_dim(mask_mb, i, 0, keepdims=False)
            s = self._masked_loss_sum(p_loss, y, tgt_i, mask_i)
            if self.loss_reduction == "mean":
                # ×chunks cancels the engine's /chunks below, leaving
                # dp·ep/N_real per row — pmeans make it 1/N_real globally.
                s = s * (self.chunks * mean_scale)
            loss_i = s
        else:
            loss_i = self._loss_call(p_loss, y, tgt_i).astype(jnp.float32)
        if self.loss_reduction == "mean":
            loss_i = loss_i / self.chunks
        return loss_i

    # ------------------------------------------------------------------ #
    # cross-axis gradient reductions (shared by both schedules)          #
    # ------------------------------------------------------------------ #

    def _reduce_dp(
        self, loss: jax.Array, grads: Pytree, *, scatter_blocks: bool
    ) -> Tuple[jax.Array, Pytree]:
        """dp-axis loss/grad reduction, fsdp-aware.

        ``scatter_blocks=False`` (fill-drain): block grads arrived via the
        all_gather's transpose, i.e. already reduce-scattered shards SUMMED
        over dp — divide for the pmean semantics every other leaf gets.
        ``scatter_blocks=True`` (1F1B): the explicit block grads are w.r.t.
        the GATHERED params, so perform that reduce-scatter here.
        """
        if not self.dp_axis:
            return loss, grads
        loss = lax.pmean(loss, self.dp_axis)
        if not self.fsdp:
            return loss, lax.pmean(grads, self.dp_axis)
        dpn = self.mesh.shape[self.dp_axis]

        def red_leaf(g, dim):
            if dim < 0:  # replicated leaf (norm scales etc.)
                return lax.pmean(g, self.dp_axis)
            if scatter_blocks:
                g = lax.psum_scatter(
                    g, self.dp_axis, scatter_dimension=dim, tiled=True
                )
            return g / dpn

        grads = dict(grads)
        grads["blocks"] = jax.tree_util.tree_map(
            red_leaf, grads["blocks"], self._fsdp_dims
        )
        for k in ("pre", "post", "loss"):
            if k in grads:
                grads[k] = lax.pmean(grads[k], self.dp_axis)
        return loss, grads

    def _reduce_ep(self, loss: jax.Array, grads: Pytree) -> Tuple[jax.Array, Pytree]:
        """ep-axis reduction: ep shards the batch like an extra dp axis,
        but expert weights are *sharded* over it — their lane-local grads
        already sum contributions from every lane's tokens (the all_to_all
        transpose routed the cotangents home), so they take only the
        global-mean scaling (1/ep for 'mean'; nothing for 'sum').
        Replicated leaves reduce like dp."""
        if not self.ep_axis:
            return loss, grads
        ep_n = self.mesh.shape[self.ep_axis]
        mean = self.loss_reduction == "mean"
        red = lax.pmean if mean else lax.psum
        loss = red(loss, self.ep_axis)
        bspecs = self._blocks_leaf_specs(grads["blocks"])

        def red_ep(g, s):
            if spec_mentions(s, self.ep_axis):
                return g / ep_n if mean else g
            return red(g, self.ep_axis)

        grads = dict(grads)
        grads["blocks"] = jax.tree_util.tree_map(
            red_ep, grads["blocks"], bspecs
        )
        for k in ("pre", "post", "loss"):
            if k in grads:
                grads[k] = red(grads[k], self.ep_axis)
        return loss, grads

    def init(self, rng: jax.Array, in_spec: Pytree) -> Pytree:
        """Initialize {'pre', 'blocks', 'post'} params; blocks stacked on a
        leading stage axis and sharded over ``pp``.  Init math runs on the
        host CPU backend (see utils.host_device), then :meth:`place` commits
        the stacked pytrees to the mesh."""
        from torchgpipe_tpu.utils import host_device

        with host_device():
            params = self._init_host(rng, in_spec)
        return self.place(params)

    def _init_host(self, rng: jax.Array, in_spec: Pytree) -> dict:
        params: dict = {}
        spec = in_spec
        if self.pre is not None:
            p, s = self.pre.init(jax.random.fold_in(rng, 1000), spec)
            self._check_stateless(s, "pre")
            params["pre"] = p
            spec, _ = jax.eval_shape(
                lambda pp, x: self.pre.apply(
                    pp, (), x, rng=jax.random.PRNGKey(0), train=True
                ),
                p,
                _zeros(spec),
            )

        v = self.virtual_stages
        if v > 1:
            # [n, v, ...]: device j's chunk c is global block c*n + j
            # (Megatron round-robin; the model executes blocks in global
            # order 0..n*v-1, visiting each device v times).
            block_params = []
            for j in range(self.n_stages):
                chunks_j = []
                for c in range(v):
                    g = c * self.n_stages + j
                    p, s = self.block.init(jax.random.fold_in(rng, g), spec)
                    self._check_stateless(s, "block")
                    chunks_j.append(p)
                block_params.append(
                    jax.tree_util.tree_map(
                        lambda *xs: jnp.stack(xs), *chunks_j
                    )
                )
        else:
            block_params = []
            for j in range(self.n_stages):
                p, s = self.block.init(jax.random.fold_in(rng, j), spec)
                self._check_stateless(s, "block")
                block_params.append(p)
        probe = (
            jax.tree_util.tree_map(lambda a: a[0], block_params[0])
            if v > 1
            else block_params[0]
        )
        out_spec, _ = jax.eval_shape(
            lambda pp, x: self.block.apply(
                pp, (), x, rng=jax.random.PRNGKey(0), train=True
            ),
            probe,
            _zeros(spec),
        )
        if jax.tree_util.tree_structure(out_spec) != jax.tree_util.tree_structure(spec) or any(
            a.shape != b.shape or a.dtype != b.dtype
            for a, b in zip(
                jax.tree_util.tree_leaves(out_spec), jax.tree_util.tree_leaves(spec)
            )
        ):
            raise ValueError(
                "SPMD pipeline blocks must preserve activation shape/dtype "
                f"(got {spec} -> {out_spec}); use the MPMD GPipe engine for "
                "heterogeneous stages"
            )
        params["blocks"] = jax.tree_util.tree_map(
            lambda *xs: jnp.stack(xs), *block_params
        )

        if self.post is not None:
            p, s = self.post.init(jax.random.fold_in(rng, 2000), spec)
            self._check_stateless(s, "post")
            params["post"] = p

        if self._loss_is_layer:
            p, s = self.loss_fn.init(jax.random.fold_in(rng, 3000), spec)
            self._check_stateless(s, "loss")
            params["loss"] = p

        return params

    def _leaf_specs(self, prefix: Pytree, tree: Pytree, what: str) -> Pytree:
        try:
            return broadcast_specs(prefix, tree)
        except ValueError as e:
            raise ValueError(
                f"{what} param structure does not match its declared "
                "meta['param_specs'] (the dict must name every param key of "
                f"the layer): {e}"
            ) from None

    def _blocks_leaf_specs(self, blocks: Pytree) -> Pytree:
        return self._leaf_specs(self._blocks_spec, blocks, "block")

    # The param-dict keys the engine owns a layout for; place() passes
    # anything else through untouched (a caller-managed EMA tree, say).
    _LAYOUT_KEYS: Tuple[str, ...] = ("blocks", "pre", "post", "loss")

    def _structural_layout(
        self, params: dict, dp_size: Optional[int] = None
    ) -> Tuple[dict, dict]:
        """``(specs, gathers)`` trees from the structural declarations
        (the pre-rule-table layout: stacking prefix + meta['param_specs']
        + fsdp augmentation) — what :meth:`rule_table` emits as rules.

        ``specs`` is the STORAGE layout (fsdp leaves carry their
        ``P(dp, ...)`` augmentation); ``gathers`` maps leaf paths
        (``"blocks/wq"``) to gather-at-use axis tuples: ``(dp_axis,)``
        for each fsdp-sharded leaf, ``()`` everywhere else.  ``dp_size``
        overrides the dp width the fsdp dim chooser checks divisibility
        against (the planner's candidate meshes differ from the real
        one); None = the real mesh's dp axis size."""
        from torchgpipe_tpu.analysis import partition_rules as pr

        specs: dict = {}
        gathers: Dict[str, Tuple[str, ...]] = {}
        prefixes = {
            "blocks": self._blocks_spec,
            "pre": self._pre_spec,
            "post": self._post_spec,
            "loss": self._loss_spec,
        }
        for k in params:
            if k not in prefixes:
                continue
            if k == "blocks" and self.fsdp:
                real_dp = self.mesh.shape[self.dp_axis]
                if dp_size is None or dp_size == real_dp:
                    self._ensure_fsdp(params[k])
                    dims, specs[k] = self._fsdp_dims, self._fsdp_specs
                else:
                    dims, specs[k] = self._fsdp_layout(params[k], dp_size)
                paths = [p for p, _ in pr.tree_leaf_paths(params[k])]
                for p, dim in zip(paths, jax.tree_util.tree_leaves(dims)):
                    gathers[f"{k}/{p}"] = (
                        (self.dp_axis,) if dim >= 0 else ()
                    )
            else:
                specs[k] = self._leaf_specs(prefixes[k], params[k], k)
                for p, _ in pr.tree_leaf_paths(params[k]):
                    gathers[f"{k}/{p}"] = ()
        return specs, gathers

    def _structural_specs(
        self, params: dict, dp_size: Optional[int] = None
    ) -> dict:
        """Per-leaf PartitionSpec STORAGE tree — see
        :meth:`_structural_layout` (this is its first result)."""
        return self._structural_layout(params, dp_size=dp_size)[0]

    def rule_table(
        self, params: Pytree, dp_size: Optional[int] = None
    ) -> Any:
        """The pipe's param layout as an ordered regex → PartitionSpec
        rule table (:mod:`torchgpipe_tpu.analysis.partition_rules`).

        A declared :attr:`partition_rules` is returned as-is; otherwise
        the table is EMITTED from the structural declarations (stacking
        prefix over ``pp``, ``meta['param_specs']`` leaf sharding, fsdp
        augmentation) — resolving it against the same params reproduces
        the structural layout leaf-for-leaf, which is the round-trip
        the unified-layer tests pin.  The ONE table covers every layout
        level: replicated and ZeRO-1 leaves are plain rules, ZeRO-3/fsdp
        leaves are storage rules ``P(dp, ...)`` carrying the
        ``gather``-at-use attribute.  ``place()`` and the static
        sharding verifier both resolve through this table, so it IS the
        layout, not documentation of it.  ``dp_size`` overrides the dp
        width used for the fsdp dim chooser (planner candidate meshes);
        ignored for declared :attr:`partition_rules`."""
        from torchgpipe_tpu.analysis import partition_rules as pr

        if self.partition_rules is not None:
            return pr.as_rule_table(self.partition_rules)
        specs, gathers = self._structural_layout(params, dp_size=dp_size)
        return pr.rules_from_specs(
            specs,
            name=f"spmd:{self.block.name}",
            note="emitted by SpmdGPipe",
            gathers=gathers,
        )

    def place(self, params: dict) -> dict:
        """Commit params to the mesh: blocks stage-sharded over ``pp`` (plus
        any tensor/expert-parallel leaf sharding the layers declare),
        pre/post replicated over pp (with their own declared leaf sharding,
        e.g. a vocab-parallel embedding table).  The layout is resolved
        through :meth:`rule_table` — an unmatched param leaf raises (no
        silent replication; the ``implicit-reshard`` lint rule's
        contract)."""
        from torchgpipe_tpu.analysis.partition_rules import (
            match_partition_rules,
        )

        known = {k: params[k] for k in self._LAYOUT_KEYS if k in params}
        specs = match_partition_rules(self.rule_table(known), known)
        self._check_spec_shapes(known, specs)
        out = dict(params)  # unknown keys (caller state) pass through
        for k in known:
            out[k] = jax.tree_util.tree_map(
                lambda a, s: jax.device_put(a, NamedSharding(self.mesh, s)),
                params[k],
                specs[k],
            )
        return out

    def place_tree(self, tree: Pytree) -> Pytree:
        """Commit an arbitrary training-state pytree to this engine's mesh.

        Leaves already laid out on the mesh (params, optimizer moments
        built by ``zeros_like``) keep their sharding; everything else —
        optimizer step counters, EMA scalars, freshly created or
        checkpoint-restored host arrays — is replicated.  Use this on
        ``optimizer.init(params)`` output (and on
        :func:`~torchgpipe_tpu.utils.serialization.restore_sharded`
        templates) so one jitted update never mixes mesh-committed arrays
        with single-device ones, which XLA rejects.
        """
        repl = NamedSharding(self.mesh, P())

        def put(a):
            sh = getattr(a, "sharding", None)
            if isinstance(sh, NamedSharding) and sh.mesh == self.mesh:
                return a
            return jax.device_put(a, repl)

        return jax.tree_util.tree_map(put, tree)

    def _check_spec_shapes(self, blocks: Pytree, specs: Pytree) -> None:
        """Every sharded dim must divide by its mesh-axis size — checked
        eagerly for a didactic error instead of a shard_map failure."""

        def chk(a, spec):
            if len(tuple(spec)) > len(a.shape):
                raise ValueError(
                    f"partition spec {spec} names {len(tuple(spec))} "
                    f"dims but the param has shape {a.shape} "
                    f"({len(a.shape)} dims); trim the rule's spec (a "
                    "user partition_rules table must rank-match every "
                    "leaf its pattern catches — split the rule, or "
                    "order a narrower one first)"
                )
            for i, ax in enumerate(spec):
                if ax is None:
                    continue
                axes = ax if isinstance(ax, tuple) else (ax,)
                for a_ in axes:
                    if a_ not in self.mesh.shape:
                        raise ValueError(
                            f"partition spec {spec} mentions mesh axis "
                            f"{a_!r} which this mesh (axes "
                            f"{list(self.mesh.axis_names)}) does not "
                            "have; fix the rule table / param_specs "
                            "declaration or add the axis to the mesh"
                        )
                size = int(np.prod([self.mesh.shape[a_] for a_ in axes]))
                if a.shape[i] % size != 0:
                    raise ValueError(
                        f"param dim {i} of shape {a.shape} is sharded over "
                        f"mesh axes {axes} (size {size}) but is not "
                        "divisible by it; adjust the model dims (e.g. "
                        "n_heads/kv_heads/mlp_hidden vs the tp size)"
                    )

        jax.tree_util.tree_map(chk, blocks, specs)

    @staticmethod
    def _check_stateless(state: Pytree, what: str) -> None:
        if jax.tree_util.tree_leaves(state):
            raise ValueError(
                f"SPMD engine requires stateless layers, but {what} carries "
                "state (e.g. BatchNorm running stats). Use the MPMD GPipe "
                "engine, or a stateless normalization (LayerNorm/RMSNorm)."
            )

    # ------------------------------------------------------------------ #
    # the per-device program                                             #
    # ------------------------------------------------------------------ #

    def _local_pipeline(
        self, blocks_local: Pytree, x_mb: Pytree, rng: Optional[jax.Array],
        train: bool, counted: bool = False,
    ) -> Pytree:
        """Run the fill-drain schedule locally; returns stacked per-tick
        outputs ``[T, b, ...]`` (garbage except where tick >= n-1 on the last
        stage).  ``counted`` (a block that declares ``apply_counts``):
        ``(outputs, counts)``, the block's counts summed over this lane's
        live cells.

        ``checkpoint='except_last'`` (reference gpipe.py:360-367) peels the
        schedule: ticks ``0..m-2`` — whose cells all belong to micro-batches
        ``< m-1`` — stay inside a remat'd ``lax.scan``, and the final ``n``
        ticks run in a second scan whose body is one ``lax.cond`` on the
        stage index.  At tail tick ``t`` exactly one stage (``t - (m-1)``)
        computes the LAST micro-batch's cell and takes the un-remat'd
        branch (its residuals are saved, no recompute in backward) while
        the drain-phase cells of earlier micro-batches on the other stages
        keep the remat policy.  The scan keeps the block traced twice
        total (once per branch) — compile time independent of ``n``.
        """
        n, m = self.n_stages, self.chunks
        stage = lax.axis_index(self.pp_axis)
        params_local = jax.tree_util.tree_map(lambda a: a[0], blocks_local)
        perm = [(i, (i + 1) % n) for i in range(n)]
        T = m + n - 1

        act0 = jax.tree_util.tree_map(
            lambda a: jnp.zeros(a.shape[1:], a.dtype), x_mb
        )

        def ring(act):
            return jax.tree_util.tree_map(
                lambda a: lax.ppermute(a, self.pp_axis, perm), act
            )

        def splice(recv, t):
            """Everything after the hand-off: splice stage 0's fresh
            micro-batch over the received activation, derive the cell key
            and validity scale.  ``recv`` is the ALREADY-PERMUTED
            neighbour output — under ``send_ahead`` the permute happened
            at the producing tick's tail, otherwise just above."""
            idx = jnp.clip(t, 0, m - 1)
            inp0 = jax.tree_util.tree_map(
                lambda a: lax.dynamic_index_in_dim(a, idx, 0, keepdims=False), x_mb
            )
            x_in = jax.tree_util.tree_map(
                lambda a, b: jnp.where(stage == 0, a, b), inp0, recv
            )
            key = (
                jax.random.fold_in(jax.random.fold_in(rng, t), stage)
                if rng is not None
                else None
            )
            # This lane's cell at tick t is micro-batch t - stage; fill and
            # drain ticks compute masked-out garbage, so injected auxiliary
            # gradients (MoE balance) get a runtime scale of 1/m on valid
            # cells and 0 on garbage ones — the scanned schedule then
            # injects exactly mean-over-microbatches like the MPMD engine.
            mb, valid = _fill_drain_cell(t, stage, m)
            valid_scale = jnp.where(valid, 1.0 / m, 0.0)
            plan = _faults.active_plan()
            if plan is not None and plan.nan_at is not None:
                # Deterministic chaos (resilience.faults): the plan is
                # STATIC at trace time, so the poisoning compiles to a
                # jnp.where mask on the traced (lane, tick - lane) cell
                # indices; entry points key their program caches on
                # faults.plan_token() so plan (de)activation re-traces.
                x_in = _faults.spmd_corrupt_cell_input(stage, mb, x_in)
            return x_in, key, valid_scale

        # Two scan-carry conventions, same math (bitwise-tested):
        #
        # * legacy (send_ahead=False): the carry is the RAW cell output;
        #   each tick permutes it at its HEAD, serializing the hand-off
        #   between tick t's compute and tick t+1's compute;
        # * send-ahead (default): the carry is the output ALREADY
        #   PERMUTED — the ``ppermute`` issues at the producing tick's
        #   TAIL, right after the compute that made it, so the async
        #   collective-permute-start sits next to its producer and can
        #   overlap tick t+1's independent work (input splice, stage-0
        #   gather) instead of gating it.  Initial carry: zeros either
        #   way (``ppermute`` of zeros is zeros — same values).
        send_ahead = self.send_ahead
        fn, fn_plain = (
            self._cell_fns_counts if counted
            else (self._block_fn, self._block_fn_plain)
        )

        def emit(out, valid_scale):
            """What a tick stacks: the cell's output, and under
            ``counted`` its counts, zeroed on a fill or drain tick."""
            if not counted:
                return out, out
            y, c = out
            return y, (y, jnp.where(valid_scale > 0, c, jnp.zeros_like(c)))

        def finish(ys):
            return (ys[0], jnp.sum(ys[1], axis=0)) if counted else ys

        def tick(carry, t):
            recv = carry if send_ahead else ring(carry)
            x_in, key, valid_scale = splice(recv, t)
            y, out = emit(
                fn(params_local, x_in, key, valid_scale, train), valid_scale)
            return (ring(y) if send_ahead else y), out

        if self.checkpoint == "except_last" and train:
            # Remat'd prefix: every cell in ticks 0..m-2 is micro-batch
            # < m-1 (or fill garbage).  Zero-length scan (m == 1) is fine.
            act, ys_scan = lax.scan(
                _scoped("tick", tick), act0, jnp.arange(m - 1),
                unroll=self.scan_unroll,
            )

            # Peeled tail as a SECOND scan (not a Python unroll): the block
            # body is traced twice total — once per cond branch — instead
            # of 2n times, so compile time stays independent of the
            # pipeline depth.  Residual behavior is identical: the scan
            # stacks each tick's cond residuals, exactly what the unrolled
            # form stored.
            def tail_tick(carry, t):
                recv = carry if send_ahead else ring(carry)
                x_in, key, valid_scale = splice(recv, t)
                own = t - (m - 1)  # the stage whose cell is micro-batch m-1

                def plain_cell(x):
                    return fn_plain(
                        params_local, x, key, valid_scale, train
                    )

                def remat_cell(x):
                    return fn(
                        params_local, x, key, valid_scale, train
                    )

                y, out = emit(
                    lax.cond(stage == own, plain_cell, remat_cell, x_in),
                    valid_scale)
                return (ring(y) if send_ahead else y), out

            _, ys_tail = lax.scan(
                _scoped("tick", tail_tick), act, jnp.arange(m - 1, T),
                unroll=self.scan_unroll,
            )
            return finish(jax.tree_util.tree_map(
                lambda a, b: jnp.concatenate([a, b], axis=0), ys_scan, ys_tail
            ))

        _, ys = lax.scan(
            _scoped("tick", tick), act0, jnp.arange(T),
            unroll=self.scan_unroll,
        )
        return finish(ys)

    def _outputs_from_ticks(self, ys: Pytree) -> Pytree:
        """Slice micro-batch outputs [m, b, ...] from the tick stack."""
        n = self.n_stages
        return jax.tree_util.tree_map(lambda a: a[n - 1 :], ys)

    # ------------------------------------------------------------------ #
    # public entry points                                                #
    # ------------------------------------------------------------------ #

    def _data_specs(self) -> P:
        # Stacked data is [m, batch, seq, ...]: micro-batch axis unsharded,
        # batch over dp (and ep — expert parallelism shards tokens too, the
        # all_to_all inside the MoE layer routes them to their experts),
        # sequence over sp (when enabled).
        batch_axes = tuple(
            a for a in (self.dp_axis, self.ep_axis) if a is not None
        )
        batch = batch_axes if batch_axes else None
        if self.sp_axis:
            return P(None, batch, self.sp_axis)
        return P(None, batch)

    def _apply_pre(
        self, pre_params: Pytree, x_mb: Pytree, rng: Optional[jax.Array],
        train: bool,
    ) -> Pytree:
        """Apply ``pre`` per micro-batch with independent keys (matching the
        MPMD engine's per-micro-batch ``fold_in``)."""
        if rng is not None:
            base = jax.random.fold_in(rng, 0x7FFFFFFF)
            keys = jax.vmap(lambda i: jax.random.fold_in(base, i))(
                jnp.arange(self.chunks)
            )
            return jax.vmap(
                lambda mb, k: self.pre.apply(pre_params, (), mb, rng=k, train=train)[0]
            )(x_mb, keys)
        return jax.vmap(
            lambda mb: self.pre.apply(pre_params, (), mb, rng=None, train=train)[0]
        )(x_mb)

    def _build_train_step_1f1b(
        self, use_rng: bool, masked: bool = False
    ) -> Callable:
        """Training step under the 1F1B (PipeDream-flush) schedule.

        Unlike the fill-drain path — which differentiates the whole scanned
        schedule and therefore keeps one saved carry per tick (``m + n - 1``
        of them) — this program computes gradients EXPLICITLY inside a
        single forward-only scan: each stage interleaves forward cells with
        backward cells, so at most ``n - j`` micro-batch inputs are in
        flight on stage ``j`` at any tick.  Activation memory is bounded by
        the depth-``n`` input ring buffer instead of growing with ``m``.

        Schedule closed form (one cell per stage per tick; ``2(m + n - 1)``
        ticks total): stage ``j`` runs forward of micro-batch ``i`` at tick
        ``i + j`` during warmup (``i <= n - 1 - j``) and ``2i + j`` in
        steady state, and backward of ``i`` at tick ``2n - 1 + 2i - j``.
        Forward activations hop ``j -> j+1`` and backward cotangents
        ``j -> j-1`` through one ``ppermute`` each per tick (outside the
        fwd/bwd/idle ``lax.switch``, so collectives stay unconditional);
        the validity predicates are disjoint by parity (forward cells land
        on ``t - j`` even, backward on odd), which a structural test checks
        against a step-by-step simulation.

        Backward cells recompute their forward from the saved input
        (``jax.vjp`` per cell — the reference's checkpoint-'always'
        semantics, checkpoint.py:1-19) or, under ``checkpoint='never'``,
        replay stored vjp residuals from the same depth-n ring buffer
        (zero recompute).  ``checkpoint='except_last'`` — the reference's
        default mode (gpipe.py:360-367) — is the hybrid: micro-batches
        ``< m-1`` take the recompute path while micro-batch ``m-1`` stores
        its residuals in a single slot (its backward begins immediately,
        so no ring is needed), dispatched by a ``lax.cond`` on the
        micro-batch index.  The last stage's backward cell also
        runs ``post`` + per-micro-batch loss, seeding the cotangent ring.
        ``pre`` runs once outside the scan with its vjp kept; stage 0's
        backward cells stack their input cotangents and one outer
        ``vjp_pre`` call turns them into pre-parameter gradients.
        """
        n, m = self.n_stages, self.chunks
        data_spec = self._data_specs()
        tmap = jax.tree_util.tree_map

        def local(params, x_mb, tgt_mb, *rest):
            rest = list(rest)
            mask_mb = rest.pop(0) if masked else None
            rng = rest.pop(0) if use_rng else None
            mean_scale = (
                self._mask_mean_scale(mask_mb)
                if masked and self.loss_reduction == "mean"
                else None
            )
            stage = lax.axis_index(self.pp_axis)
            perm_f = [(i, (i + 1) % n) for i in range(n)]
            perm_b = [(i, (i - 1) % n) for i in range(n)]

            # FSDP: all-gather the stored shards ONCE before the scan (an
            # unconditional group-local collective — safe outside the
            # schedule's switch); the explicit reduce-scatter of the block
            # grads happens after the scan.
            blocks_in = (
                self._gather_fsdp(params["blocks"])
                if self.fsdp
                else params["blocks"]
            )
            params_local = tmap(lambda a: a[0], blocks_in)
            pre_params = params["pre"] if self.pre is not None else ()
            post_params = params["post"] if self.post is not None else ()
            loss_params = params["loss"] if self._loss_is_layer else ()
            pre_base = (
                jax.random.fold_in(rng, 0x7FFFFFFF) if rng is not None else None
            )
            post_base = (
                jax.random.fold_in(rng, 0x7FFFFFFE) if rng is not None else None
            )
            # Valid cells always carry scale 1/m (invalid ticks take the
            # idle branch, so no masking is needed as in _local_pipeline).
            aux_s = 1.0 / m
            def cell_key(i):
                # Matches the fill-drain cell key fold_in(fold_in(rng, t),
                # stage) at t = i + stage, so both schedules (and the
                # backward recompute) produce identical per-cell randomness.
                if rng is None:
                    return None
                return jax.random.fold_in(
                    jax.random.fold_in(rng, i + stage), stage
                )

            def stage_input(p_pre, i, fallback):
                # Shared splice helper (see _cell_input_splice): 1F1B's
                # "first" cell is any stage-0 cell.
                return self._cell_input_splice(
                    p_pre, stage == 0, i, fallback, x_mb, pre_base
                )

            def mb_loss(y, p_post, p_loss, i):
                return self._cell_mb_loss(
                    y, p_post, p_loss, i, tgt_mb, post_base,
                    mask_mb=mask_mb, mean_scale=mean_scale,
                )

            act_spec = jax.eval_shape(
                lambda p, x: self._block_fn_plain(p, x, None, aux_s, False),
                params_local,
                tmap(lambda a: jnp.zeros(a.shape[1:], a.dtype), x_mb)
                if self.pre is None
                else jax.eval_shape(
                    lambda p, x: self.pre.apply(p, (), x, rng=None, train=False)[0],
                    pre_params,
                    tmap(lambda a: jnp.zeros(a.shape[1:], a.dtype), x_mb),
                ),
            )
            act0 = tmap(lambda s: jnp.zeros(s.shape, s.dtype), act_spec)
            store = self.checkpoint == "never"
            # 'except_last' is the hybrid: micro-batches < m-1 take the
            # recompute ('always') path; the LAST micro-batch stores its
            # vjp residuals instead (reference gpipe.py:360-367 — the last
            # chunk's backward begins immediately after its forward, so
            # skipping its recompute costs one residual slot, not a ring).
            hybrid = self.checkpoint == "except_last"

            def cell_fn(p_blk, p_pre, x, i):
                """One forward cell as a function of everything its
                backward differentiates — vjp'd directly in 'never' mode,
                re-vjp'd from the saved input in 'always' mode."""
                xin = stage_input(p_pre, i, x)
                return self._block_fn_plain(
                    p_blk, xin, cell_key(i), aux_s, True
                )

            carry0 = dict(
                act=act0,
                gact=act0,
                gblk=tmap(jnp.zeros_like, params_local),
                gpre=tmap(jnp.zeros_like, pre_params),
                gpost=tmap(jnp.zeros_like, post_params),
                gloss=tmap(jnp.zeros_like, loss_params),
                loss=jnp.float32(0.0),
            )
            if store or hybrid:
                # Stored-vjp machinery: buffer each stored cell's vjp
                # RESIDUAL LEAVES (the closure's pytree leaves — its
                # treedef is static and identical for every cell, so one
                # canonical treedef from an abstract trace rebuilds the
                # closure at backward time) plus the last forward output
                # (the last stage's loss seed; its backward runs on the
                # very next tick, so one slot suffices).  Residual leaves
                # that are PASS-THROUGH PARAMETERS (vjp residuals of x@W
                # include W itself) are detected in the canonical jaxpr
                # (identity-forwarded invars) and re-injected live at
                # backward time instead of being ring-buffered — buffering
                # them would duplicate every stage's weights n times.
                vjp_tdef, vjp_leaf_specs, passthrough, buffered_idx = (
                    _never_mode_spec(
                        lambda p, pp_, x: jax.vjp(
                            lambda a, b, c: cell_fn(a, b, c, jnp.int32(0)),
                            p, pp_, x,
                        )[1],
                        (params_local, pre_params),
                        act0,
                    )
                )
                param_flat = jax.tree_util.tree_leaves(
                    (params_local, pre_params)
                )
                # 'never' stores EVERY in-flight cell: depth-n ring.
                # 'except_last' stores only micro-batch m-1: ONE slot.
                resid_depth = n if store else 1
                carry0["rbuf"] = tuple(
                    jnp.zeros(
                        (resid_depth,) + vjp_leaf_specs[i].shape,
                        vjp_leaf_specs[i].dtype,
                    )
                    for i in buffered_idx
                )
                carry0["ylast"] = act0
            if not store:
                # Depth-n input ring buffer (slot i % n): in-flight
                # micro-batches per stage never exceed n, and slot i + n's
                # write lands strictly after slot i's backward read.
                carry0["buf"] = tmap(
                    lambda s: jnp.zeros((n,) + s.shape, s.dtype), act_spec
                )
            send_ahead = self.send_ahead
            if send_ahead:
                # Send-ahead overlap: the carry ALSO holds the permuted
                # act/gact, produced at the previous tick's tail (right
                # after the switch that computed them) instead of at this
                # tick's head — the hand-off collective sits next to its
                # producer, off the head-of-tick critical path.  Initial
                # values: permutes of the zero act/gact, i.e. zeros —
                # bitwise what the legacy head permute computes at t=0.
                carry0["recv_f"] = act0
                carry0["recv_b"] = act0

            def tick(carry, t):
                if send_ahead:
                    recv_f = carry["recv_f"]
                    recv_b = carry["recv_b"]
                else:
                    recv_f = tmap(
                        lambda a: lax.ppermute(a, self.pp_axis, perm_f),
                        carry["act"],
                    )
                    recv_b = tmap(
                        lambda a: lax.ppermute(a, self.pp_axis, perm_b),
                        carry["gact"],
                    )
                do_f, i_f, do_b, i_b = _one_f1b_cell(t, stage, n, m)

                def fwd_store(c):
                    # Stored-vjp forward cell ('never', or 'except_last's
                    # last micro-batch): vjp directly, buffer the residual
                    # leaves (slot i%n for the ring, slot 0 for the single
                    # 'except_last' slot) and the output (last-stage loss
                    # seed — consumed on the very next tick).
                    y, vjp_fn = jax.vjp(
                        lambda a, b, xx: cell_fn(a, b, xx, i_f),
                        params_local, pre_params, recv_f,
                    )
                    leaves = jax.tree_util.tree_leaves(vjp_fn)
                    _never_check_leaves(leaves, vjp_leaf_specs, "1f1b")
                    slot = i_f % n if store else 0
                    rbuf = tuple(
                        lax.dynamic_update_index_in_dim(
                            b, leaves[i], slot, 0
                        )
                        for b, i in zip(c["rbuf"], buffered_idx)
                    )
                    return dict(c, act=y, rbuf=rbuf, ylast=y)

                def fwd_plain(c):
                    x_f = stage_input(pre_params, i_f, recv_f)
                    y = self._block_fn_plain(
                        params_local, x_f, cell_key(i_f), aux_s, True
                    )
                    buf = tmap(
                        lambda b, x: lax.dynamic_update_index_in_dim(
                            b, x, i_f % n, 0
                        ),
                        c["buf"],
                        x_f,
                    )
                    return dict(c, act=y, buf=buf)

                def fwd_branch(c):
                    if store:
                        return fwd_store(c)
                    if hybrid:
                        return lax.cond(i_f == m - 1, fwd_store, fwd_plain, c)
                    return fwd_plain(c)

                def bwd_store(c):
                    slot = i_b % n if store else 0
                    vjp_cell = _never_rebuild(
                        vjp_tdef,
                        vjp_leaf_specs,
                        passthrough,
                        iter(
                            lax.dynamic_index_in_dim(
                                b, slot, 0, keepdims=False
                            )
                            for b in c["rbuf"]
                        ),
                        param_flat,
                    )

                    def last_fn():
                        y_saved = c["ylast"]

                        def tail(p_post, p_loss, yy):
                            return mb_loss(yy, p_post, p_loss, i_b)

                        loss_i, (d_post, d_loss, dy) = (
                            jax.value_and_grad(tail, argnums=(0, 1, 2))(
                                post_params, loss_params, y_saved
                            )
                        )
                        d_blk, d_pre, dx = vjp_cell(dy)
                        return loss_i, d_blk, d_pre, d_post, d_loss, dx

                    def mid_fn():
                        d_blk, d_pre, dx = vjp_cell(recv_b)
                        return (
                            jnp.float32(0.0),
                            d_blk,
                            d_pre,
                            tmap(jnp.zeros_like, post_params),
                            tmap(jnp.zeros_like, loss_params),
                            dx,
                        )

                    loss_i, d_blk, d_pre, d_post, d_loss, dx = lax.cond(
                        stage == n - 1, last_fn, mid_fn
                    )
                    return dict(
                        c,
                        gact=dx,
                        gblk=tmap(jnp.add, c["gblk"], d_blk),
                        gpre=tmap(jnp.add, c["gpre"], d_pre),
                        gpost=tmap(jnp.add, c["gpost"], d_post),
                        gloss=tmap(jnp.add, c["gloss"], d_loss),
                        loss=c["loss"] + loss_i,
                    )

                def bwd_plain(c):
                    x_saved = tmap(
                        lambda b: lax.dynamic_index_in_dim(
                            b, i_b % n, 0, keepdims=False
                        ),
                        c["buf"],
                    )
                    key = cell_key(i_b)

                    def through_block(p_blk, p_pre, x):
                        # Recompute-with-pre-splice: identical value to the
                        # forward cell (same keys), but differentiable in
                        # p_pre on stage 0.
                        xin = stage_input(p_pre, i_b, x)
                        return self._block_fn_plain(
                            p_blk, xin, key, aux_s, True
                        )

                    def last_fn():
                        def full(p_blk, p_pre, p_post, p_loss, x):
                            y = through_block(p_blk, p_pre, x)
                            return mb_loss(y, p_post, p_loss, i_b)

                        loss_i, (d_blk, d_pre, d_post, d_loss, dx) = (
                            jax.value_and_grad(full, argnums=(0, 1, 2, 3, 4))(
                                params_local, pre_params, post_params,
                                loss_params, x_saved,
                            )
                        )
                        return loss_i, d_blk, d_pre, d_post, d_loss, dx

                    def mid_fn():
                        _, vjp_cell = jax.vjp(
                            through_block, params_local, pre_params, x_saved
                        )
                        d_blk, d_pre, dx = vjp_cell(recv_b)
                        return (
                            jnp.float32(0.0),
                            d_blk,
                            d_pre,
                            tmap(jnp.zeros_like, post_params),
                            tmap(jnp.zeros_like, loss_params),
                            dx,
                        )

                    loss_i, d_blk, d_pre, d_post, d_loss, dx = lax.cond(
                        stage == n - 1, last_fn, mid_fn
                    )
                    return dict(
                        c,
                        gact=dx,
                        gblk=tmap(jnp.add, c["gblk"], d_blk),
                        gpre=tmap(jnp.add, c["gpre"], d_pre),
                        gpost=tmap(jnp.add, c["gpost"], d_post),
                        gloss=tmap(jnp.add, c["gloss"], d_loss),
                        loss=c["loss"] + loss_i,
                    )

                def bwd_branch(c):
                    if store:
                        return bwd_store(c)
                    if hybrid:
                        return lax.cond(i_b == m - 1, bwd_store, bwd_plain, c)
                    return bwd_plain(c)

                idx = jnp.where(do_f, 0, jnp.where(do_b, 1, 2))
                carry = lax.switch(
                    idx,
                    [_scoped("forward", fwd_branch),
                     _scoped("backward", bwd_branch), lambda c: c],
                    carry,
                )
                if send_ahead:
                    # Issue next tick's hand-offs NOW, right after the
                    # switch produced act/gact (unconditional — collective
                    # participation stays global).  Values equal the
                    # legacy head permute of the SAME carried act/gact.
                    carry = dict(
                        carry,
                        recv_f=tmap(
                            lambda a: lax.ppermute(a, self.pp_axis, perm_f),
                            carry["act"],
                        ),
                        recv_b=tmap(
                            lambda a: lax.ppermute(a, self.pp_axis, perm_b),
                            carry["gact"],
                        ),
                    )
                return carry, ()

            carry, _ = lax.scan(
                _scoped("tick", tick), carry0, jnp.arange(2 * (m + n - 1)),
                unroll=self.scan_unroll,
            )
            loss = lax.psum(carry["loss"], self.pp_axis)
            grads = {"blocks": tmap(lambda g: g[None], carry["gblk"])}
            if self.pre is not None:
                grads["pre"] = lax.psum(carry["gpre"], self.pp_axis)
            if self.post is not None:
                grads["post"] = lax.psum(carry["gpost"], self.pp_axis)
            if self._loss_is_layer:
                grads["loss"] = lax.psum(carry["gloss"], self.pp_axis)
            # Cross-axis reductions shared with the fill-drain path (no sp
            # here — rejected in __post_init__).  scatter_blocks: the
            # explicit block grads are w.r.t. the GATHERED params and still
            # need the reduce-scatter the fill-drain autodiff gets from the
            # all_gather transpose.
            loss, grads = self._reduce_dp(loss, grads, scatter_blocks=True)
            loss, grads = self._reduce_ep(loss, grads)
            return loss, grads

        param_specs = {
            "blocks": self._fsdp_specs if self.fsdp else self._blocks_spec
        }
        if self.pre is not None:
            param_specs["pre"] = self._pre_spec
        if self.post is not None:
            param_specs["post"] = self._post_spec
        if self._loss_is_layer:
            param_specs["loss"] = self._loss_spec

        in_specs = (param_specs, data_spec, data_spec)
        if masked:
            in_specs += (self._mask_spec(),)
        if use_rng:
            in_specs += (P(),)
        mapped = _shard_map(
            local,
            self.mesh,
            in_specs=in_specs,
            out_specs=(P(), param_specs),
        )
        return jax.jit(mapped)

    def _build_train_step_zb(
        self, use_rng: bool, masked: bool = False
    ) -> Callable:
        """Training step under the zero-bubble (ZB-H1-style) schedule.

        The backward splits into B cells (activation gradient dx only —
        the critical path the downstream stage waits on) and W cells
        (weight gradients d_blk/d_pre — consumed only at step end), per
        the static tables of :mod:`torchgpipe_tpu.parallel.zerobubble`.
        Each half uses only its own outputs of a shared vjp closure, so
        XLA dead-code-eliminates the other half's matmuls — per-tick
        backward work drops from dx+dW to max(dx, dW), and early stages'
        drain ticks run W work instead of idling (weighted-makespan win
        proven at the table level, tests/test_zerobubble.py).  Two
        residual policies:

        * ``checkpoint='never'`` — the F cell banks its vjp residuals
          (ring depth = the F->W spans) and both halves replay them:
          zero recompute, O(pipeline window) residual memory.
        * ``checkpoint='always'`` — the F cell banks only its INPUT
          (F->B spans); the B cell recomputes the forward once, takes
          dx, and banks the fresh vjp for the W cell (B->W spans — ONE
          slot under the H1 immediate-W placement): O(1) residual
          memory for ~one extra forward per micro-batch.  Any
          ``remat_policy`` is ignored here — the recompute split is
          explicit in the schedule.

        No reference counterpart at any level (the reference has
        fill-drain only; ZB is Qi et al. arXiv:2401.10241 — public
        technique, scheduled here with our own lockstep generator).
        """
        from torchgpipe_tpu.parallel.zerobubble import (
            B as ZB_B,
            F as ZB_F,
            W as ZB_W,
            zero_bubble_tables,
        )

        n, m = self.n_stages, self.chunks
        tb = zero_bubble_tables(n, m)
        S, Sy, Dr, Dy = tb.slots, tb.y_slots, tb.resid_slots, tb.dy_slots
        Sx = tb.x_slots
        # checkpoint='never': F banks the vjp residuals (depth Dr, F->W
        # spans) and both halves replay them — zero recompute.
        # checkpoint='always': F banks only its INPUT (depth Sx, F->B
        # spans); B recomputes the cell once, takes dx, and banks the
        # fresh vjp for the W cell (depth Dy, B->W spans — ONE slot under
        # the H1 immediate-W placement).
        store_at_f = self.checkpoint == "never"
        Dres = Dr if store_at_f else Dy
        data_spec = self._data_specs()
        tmap = jax.tree_util.tree_map
        # Scan xs: this tick's (kind, mb) row plus the PREVIOUS tick's row
        # (receive classification reads the sender's last action).
        idle_row = jnp.full((1, n), 3, jnp.int32)  # IDLE
        kind_rows = jnp.asarray(tb.kind)
        mb_rows = jnp.asarray(tb.mb)
        rows_xs = (
            kind_rows,
            mb_rows,
            jnp.concatenate([idle_row, kind_rows[:-1]]),
            jnp.concatenate([jnp.zeros((1, n), jnp.int32), mb_rows[:-1]]),
        )

        def local(params, x_mb, tgt_mb, *rest):
            rest = list(rest)
            mask_mb = rest.pop(0) if masked else None
            rng = rest.pop(0) if use_rng else None
            mean_scale = (
                self._mask_mean_scale(mask_mb)
                if masked and self.loss_reduction == "mean"
                else None
            )
            stage = lax.axis_index(self.pp_axis)
            perm_f = [(i, (i + 1) % n) for i in range(n)]
            perm_b = [(i, (i - 1) % n) for i in range(n)]

            blocks_in = (
                self._gather_fsdp(params["blocks"])
                if self.fsdp
                else params["blocks"]
            )
            params_local = tmap(lambda a: a[0], blocks_in)
            pre_params = params["pre"] if self.pre is not None else ()
            post_params = params["post"] if self.post is not None else ()
            loss_params = params["loss"] if self._loss_is_layer else ()
            pre_base = (
                jax.random.fold_in(rng, 0x7FFFFFFF) if rng is not None else None
            )
            post_base = (
                jax.random.fold_in(rng, 0x7FFFFFFE) if rng is not None else None
            )
            aux_s = 1.0 / m

            def cell_key(i):
                if rng is None:
                    return None
                return jax.random.fold_in(
                    jax.random.fold_in(rng, i + stage), stage
                )

            def stage_input(p_pre, i, fallback):
                return self._cell_input_splice(
                    p_pre, stage == 0, i, fallback, x_mb, pre_base
                )

            def mb_loss(y, p_post, p_loss, i):
                return self._cell_mb_loss(
                    y, p_post, p_loss, i, tgt_mb, post_base,
                    mask_mb=mask_mb, mean_scale=mean_scale,
                )

            act_spec = jax.eval_shape(
                lambda p, x: self._block_fn_plain(p, x, None, aux_s, False),
                params_local,
                tmap(lambda a: jnp.zeros(a.shape[1:], a.dtype), x_mb)
                if self.pre is None
                else jax.eval_shape(
                    lambda p, x: self.pre.apply(p, (), x, rng=None, train=False)[0],
                    pre_params,
                    tmap(lambda a: jnp.zeros(a.shape[1:], a.dtype), x_mb),
                ),
            )
            act0 = tmap(lambda s: jnp.zeros(s.shape, s.dtype), act_spec)

            def cell_fn(p_blk, p_pre, x, i):
                xin = stage_input(p_pre, i, x)
                return self._block_fn_plain(
                    p_blk, xin, cell_key(i), aux_s, True
                )

            vjp_tdef, vjp_leaf_specs, passthrough, buffered_idx = (
                _never_mode_spec(
                    lambda p, pp_, x: jax.vjp(
                        lambda a, b, c: cell_fn(a, b, c, jnp.int32(0)),
                        p, pp_, x,
                    )[1],
                    (params_local, pre_params),
                    act0,
                )
            )
            param_flat = jax.tree_util.tree_leaves(
                (params_local, pre_params)
            )

            def ring(depth):
                return tmap(
                    lambda s: jnp.zeros((depth,) + s.shape, s.dtype), act_spec
                )

            carry0 = dict(
                act=act0,
                gact=act0,
                inbox=ring(S),
                gbox=ring(S),
                ybox=ring(Sy if store_at_f else 1),
                dybuf=ring(Dy),
                rbuf=tuple(
                    jnp.zeros(
                        (Dres,) + vjp_leaf_specs[i].shape,
                        vjp_leaf_specs[i].dtype,
                    )
                    for i in buffered_idx
                ),
                **({} if store_at_f else {"xbuf": ring(Sx)}),
                gblk=tmap(jnp.zeros_like, params_local),
                gpre=tmap(jnp.zeros_like, pre_params),
                gpost=tmap(jnp.zeros_like, post_params),
                gloss=tmap(jnp.zeros_like, loss_params),
                loss=jnp.float32(0.0),
            )

            def rebuild(c, i):
                return _never_rebuild(
                    vjp_tdef,
                    vjp_leaf_specs,
                    passthrough,
                    iter(
                        lax.dynamic_index_in_dim(
                            b, i % Dres, 0, keepdims=False
                        )
                        for b in c["rbuf"]
                    ),
                    param_flat,
                )

            def bank_vjp(rbuf, vjp_fn, i):
                leaves = jax.tree_util.tree_leaves(vjp_fn)
                _never_check_leaves(leaves, vjp_leaf_specs, "zb")
                return tuple(
                    lax.dynamic_update_index_in_dim(
                        b, leaves[i2], i % Dres, 0
                    )
                    for b, i2 in zip(rbuf, buffered_idx)
                )

            def tick(carry, rows):
                krow, irow, pkrow, pirow = rows
                recv_f = tmap(
                    lambda a: lax.ppermute(a, self.pp_axis, perm_f),
                    carry["act"],
                )
                recv_b = tmap(
                    lambda a: lax.ppermute(a, self.pp_axis, perm_b),
                    carry["gact"],
                )
                # File incoming values by the SENDER's previous-tick row.
                src_f = jnp.mod(stage - 1, n)
                valid_f = (pkrow[src_f] == ZB_F) & (stage > 0)
                inbox = _slot_write(
                    carry["inbox"], pirow[src_f] % S, recv_f, valid_f
                )
                src_b = jnp.mod(stage + 1, n)
                valid_b = (pkrow[src_b] == ZB_B) & (stage < n - 1)
                gbox = _slot_write(
                    carry["gbox"], pirow[src_b] % S, recv_b, valid_b
                )
                carry = dict(carry, inbox=inbox, gbox=gbox)

                k = krow[stage]
                i = irow[stage]

                def f_branch(c):
                    xin = _slot_read(c["inbox"], i % S)
                    if store_at_f:
                        y, vjp_fn = jax.vjp(
                            lambda a, b, xx: cell_fn(a, b, xx, i),
                            params_local, pre_params, xin,
                        )
                        extra = dict(rbuf=bank_vjp(c["rbuf"], vjp_fn, i))
                        # The loss seed: only 'never' needs F's output
                        # saved — the recompute mode re-produces it in the
                        # B cell (its ybox stays a depth-1 dummy).
                        extra["ybox"] = _slot_write(
                            c["ybox"], i % Sy, y, stage == n - 1
                        )
                    else:
                        # Recompute mode: forward only; bank the INPUT for
                        # the B cell's recompute.
                        y = cell_fn(params_local, pre_params, xin, i)
                        extra = dict(
                            xbuf=_slot_write(c["xbuf"], i % Sx, xin, True)
                        )
                    return dict(c, act=y, **extra)

                def b_branch(c):
                    if store_at_f:
                        vjp_cell = rebuild(c, i)
                        rbuf = c["rbuf"]
                        y_re = None
                    else:
                        # Recompute the cell once; its vjp serves BOTH this
                        # dx and the following W cell's weight grads — and
                        # its primal output is the last stage's loss seed.
                        y_re, vjp_fn = jax.vjp(
                            lambda a, b, xx: cell_fn(a, b, xx, i),
                            params_local, pre_params,
                            _slot_read(c["xbuf"], i % Sx),
                        )
                        rbuf = bank_vjp(c["rbuf"], vjp_fn, i)
                        vjp_cell = vjp_fn

                    def last_fn():
                        y_saved = (
                            _slot_read(c["ybox"], i % Sy)
                            if store_at_f
                            else y_re
                        )

                        def tail(p_post, p_loss, yy):
                            return mb_loss(yy, p_post, p_loss, i)

                        loss_i, (d_post, d_loss, dy) = (
                            jax.value_and_grad(tail, argnums=(0, 1, 2))(
                                post_params, loss_params, y_saved
                            )
                        )
                        return loss_i, d_post, d_loss, dy

                    def mid_fn():
                        return (
                            jnp.float32(0.0),
                            tmap(jnp.zeros_like, post_params),
                            tmap(jnp.zeros_like, loss_params),
                            _slot_read(c["gbox"], i % S),
                        )

                    loss_i, d_post, d_loss, dy = lax.cond(
                        stage == n - 1, last_fn, mid_fn
                    )
                    # dx ONLY: the d_blk/d_pre outputs are unused in this
                    # branch, so their matmuls are dead code here.
                    _, _, dx = vjp_cell(dy)
                    return dict(
                        c,
                        gact=dx,
                        rbuf=rbuf,
                        dybuf=_slot_write(c["dybuf"], i % Dy, dy, True),
                        gpost=tmap(jnp.add, c["gpost"], d_post),
                        gloss=tmap(jnp.add, c["gloss"], d_loss),
                        loss=c["loss"] + loss_i,
                    )

                def w_branch(c):
                    vjp_cell = rebuild(c, i)
                    dy = _slot_read(c["dybuf"], i % Dy)
                    # d_blk/d_pre ONLY: dx's matmul is dead code here.
                    d_blk, d_pre, _ = vjp_cell(dy)
                    return dict(
                        c,
                        gblk=tmap(jnp.add, c["gblk"], d_blk),
                        gpre=tmap(jnp.add, c["gpre"], d_pre),
                    )

                sel = jnp.where(
                    k == ZB_F, 0, jnp.where(k == ZB_B, 1, jnp.where(k == ZB_W, 2, 3))
                )
                carry = lax.switch(
                    sel,
                    [_scoped("forward", f_branch),
                     _scoped("backward", b_branch),
                     _scoped("backward_w", w_branch), lambda c: c],
                    carry,
                )
                return carry, ()

            carry, _ = lax.scan(
                _scoped("tick", tick), carry0, rows_xs,
                unroll=self.scan_unroll
            )
            loss = lax.psum(carry["loss"], self.pp_axis)
            grads = {"blocks": tmap(lambda g: g[None], carry["gblk"])}
            if self.pre is not None:
                grads["pre"] = lax.psum(carry["gpre"], self.pp_axis)
            if self.post is not None:
                grads["post"] = lax.psum(carry["gpost"], self.pp_axis)
            if self._loss_is_layer:
                grads["loss"] = lax.psum(carry["gloss"], self.pp_axis)
            loss, grads = self._reduce_dp(loss, grads, scatter_blocks=True)
            loss, grads = self._reduce_ep(loss, grads)
            return loss, grads

        param_specs = {
            "blocks": self._fsdp_specs if self.fsdp else self._blocks_spec
        }
        if self.pre is not None:
            param_specs["pre"] = self._pre_spec
        if self.post is not None:
            param_specs["post"] = self._post_spec
        if self._loss_is_layer:
            param_specs["loss"] = self._loss_spec

        in_specs = (param_specs, data_spec, data_spec)
        if masked:
            in_specs += (self._mask_spec(),)
        if use_rng:
            in_specs += (P(),)
        mapped = _shard_map(
            local,
            self.mesh,
            in_specs=in_specs,
            out_specs=(P(), param_specs),
        )
        return jax.jit(mapped)

    def _build_train_step_interleaved(
        self, use_rng: bool, masked: bool = False
    ) -> Callable:
        """Training step under the interleaved-1F1B (virtual pipeline
        stages) schedule.

        Megatron-style (arXiv:2104.04473 §2.2): each device owns ``v``
        non-adjacent model chunks, so the fill/drain bubble shrinks by ~v
        while activation memory stays bounded by the schedule's in-flight
        window (O(n·v) cells, never O(m)).  The schedule is a *static
        table* computed by lockstep list-scheduling in Python
        (:mod:`torchgpipe_tpu.parallel.interleaved`) and scanned over: one
        forward and one backward ``ppermute`` per tick move activations
        j→j+1 (wrapping n-1→0 advances the chunk index) and cotangents
        j→j-1 (wrapping 0→n-1 retreats it); a receiver classifies the
        incoming value from the *sender's* table row for the previous tick
        and files it into a per-(chunk, mb mod S) ring-buffer slot whose
        depth S the table generator proves collision-free.

        Backward cells recompute their forward from the saved (spliced)
        input per cell (checkpoint='always') or replay stored vjp
        residuals from the c*S + i%S ring slots (checkpoint='never'),
        like the 1F1B path.  checkpoint='except_last' (the reference's
        default, gpipe.py:360-367) recomputes all micro-batches except
        m-1, whose residuals live in one slot per chunk (each of the
        device's v chunks runs exactly one cell of that micro-batch).
        No reference counterpart for the schedule itself: the reference
        has fill-drain only (reference: torchgpipe/pipeline.py:49-65).
        """
        from torchgpipe_tpu.parallel.interleaved import (
            BWD,
            FWD,
            interleaved_tables,
        )

        n, m, v = self.n_stages, self.chunks, self.virtual_stages
        tb = interleaved_tables(n, m, v)
        S = tb.slots
        data_spec = self._data_specs()
        tmap = jax.tree_util.tree_map
        rows_xs = _interleaved_rows(tb)

        def local(params, x_mb, tgt_mb, *rest):
            rest = list(rest)
            mask_mb = rest.pop(0) if masked else None
            rng = rest.pop(0) if use_rng else None
            mean_scale = (
                self._mask_mean_scale(mask_mb)
                if masked and self.loss_reduction == "mean"
                else None
            )
            stage = lax.axis_index(self.pp_axis)
            perm_f = [(i, (i + 1) % n) for i in range(n)]
            perm_b = [(i, (i - 1) % n) for i in range(n)]

            blocks_in = (
                self._gather_fsdp(params["blocks"])
                if self.fsdp
                else params["blocks"]
            )
            params_local = tmap(lambda a: a[0], blocks_in)  # [v, ...]
            pre_params = params["pre"] if self.pre is not None else ()
            post_params = params["post"] if self.post is not None else ()
            loss_params = params["loss"] if self._loss_is_layer else ()
            pre_base = (
                jax.random.fold_in(rng, 0x7FFFFFFF) if rng is not None else None
            )
            post_base = (
                jax.random.fold_in(rng, 0x7FFFFFFE) if rng is not None else None
            )
            aux_s = 1.0 / m

            def p_of(c):
                return tmap(
                    lambda a: lax.dynamic_index_in_dim(a, c, 0, keepdims=False),
                    params_local,
                )

            def cell_key(c, i):
                if rng is None:
                    return None
                g = c * n + stage
                return jax.random.fold_in(jax.random.fold_in(rng, i + g), g)

            def splice(p_pre, c, i, fallback):
                # Shared splice helper: the interleaved schedule's "first"
                # cell is (stage 0, chunk 0) — global block 0.
                return self._cell_input_splice(
                    p_pre, (stage == 0) & (c == 0), i, fallback, x_mb,
                    pre_base,
                )

            def mb_loss(y, p_post, p_loss, i):
                return self._cell_mb_loss(
                    y, p_post, p_loss, i, tgt_mb, post_base,
                    mask_mb=mask_mb, mean_scale=mean_scale,
                )

            act_spec = jax.eval_shape(
                lambda p, x: self._block_fn_plain(p, x, None, aux_s, False),
                p_of(0),
                tmap(lambda a: jnp.zeros(a.shape[1:], a.dtype), x_mb)
                if self.pre is None
                else jax.eval_shape(
                    lambda p, x: self.pre.apply(p, (), x, rng=None, train=False)[0],
                    pre_params,
                    tmap(lambda a: jnp.zeros(a.shape[1:], a.dtype), x_mb),
                ),
            )
            act0 = tmap(lambda s: jnp.zeros(s.shape, s.dtype), act_spec)
            box0 = tmap(
                lambda s: jnp.zeros((v * S,) + s.shape, s.dtype), act_spec
            )
            store = self.checkpoint == "never"
            # 'except_last' hybrid (same design as the 1F1B builder): cells
            # of micro-batch m-1 store their vjp residuals — one slot per
            # CHUNK, since each of this device's v chunks runs exactly one
            # cell of that micro-batch — while all other cells recompute.
            hybrid = self.checkpoint == "except_last"

            def cell_fn(p_blk, p_pre, x, c, i):
                xin = splice(p_pre, c, i, x)
                return self._block_fn_plain(
                    p_blk, xin, cell_key(c, i), aux_s, True
                )

            carry0 = dict(
                act=act0,
                gact=act0,
                inbox=box0,  # received/saved forward inputs, slot c*S + i%S
                gbox=box0,   # received cotangents, same slot layout
                gblk=tmap(jnp.zeros_like, params_local),
                gpre=tmap(jnp.zeros_like, pre_params),
                gpost=tmap(jnp.zeros_like, post_params),
                gloss=tmap(jnp.zeros_like, loss_params),
                loss=jnp.float32(0.0),
            )
            if store or hybrid:
                # checkpoint='never' (same design as the 1F1B builder):
                # buffer each in-flight cell's vjp residual leaves at slot
                # c*S + i%S (liveness covered by the table generator's
                # act-span proof — same fwd -> bwd window as the saved
                # input), with identity-forwarded PARAM residuals detected
                # in the canonical jaxpr and re-injected live (per-chunk
                # params are dynamic slices, so the live value is p_of(c)'s
                # leaf at backward time, not a buffered copy).
                # checkpoint='except_last' buffers only micro-batch m-1:
                # one slot per chunk (indexed by c), 1/S of the ring.
                vjp_tdef, vjp_leaf_specs, passthrough, buffered_idx = (
                    _never_mode_spec(
                        lambda p, pp_, x: jax.vjp(
                            lambda a, b, cc: cell_fn(
                                a, b, cc, jnp.int32(0), jnp.int32(0)
                            ),
                            p, pp_, x,
                        )[1],
                        (p_of(0), pre_params),
                        act0,
                    )
                )
                resid_slots = v * S if store else v
                carry0["rbuf"] = tuple(
                    jnp.zeros(
                        (resid_slots,) + vjp_leaf_specs[i2].shape,
                        vjp_leaf_specs[i2].dtype,
                    )
                    for i2 in buffered_idx
                )
                if store:
                    # Last-CHUNK outputs for the loss seed only: keyed
                    # i % S (the fwd -> bwd window sits inside the
                    # act-span proof), written only by c == v-1 cells —
                    # 1/v of a full box.
                    carry0["ybox"] = tmap(
                        lambda sp: jnp.zeros((S,) + sp.shape, sp.dtype),
                        act_spec,
                    )
                else:
                    # Only cell (stage n-1, chunk v-1, micro-batch m-1)
                    # writes the loss seed — a single slot.
                    carry0["ylast"] = act0

            def tick(carry, rows):
                krow, crow, irow, pkrow, pcrow, pirow = rows
                recv_f = tmap(
                    lambda a: lax.ppermute(a, self.pp_axis, perm_f),
                    carry["act"],
                )
                recv_b = tmap(
                    lambda a: lax.ppermute(a, self.pp_axis, perm_b),
                    carry["gact"],
                )
                # File the incoming values by the SENDER's previous-tick
                # action (the tables are the single source of truth for
                # routing).
                idx_f, valid_f = _classify_fwd_recv(
                    stage, n, v, S, pkrow, pcrow, pirow
                )
                inbox = _slot_write(carry["inbox"], idx_f, recv_f, valid_f)
                idx_b, valid_b = _classify_bwd_recv(
                    stage, n, v, S, pkrow, pcrow, pirow
                )
                gbox = _slot_write(carry["gbox"], idx_b, recv_b, valid_b)
                carry = dict(carry, inbox=inbox, gbox=gbox)

                k = krow[stage]
                c = crow[stage]
                i = irow[stage]
                idx = c * S + i % S

                def fwd_store(cr):
                    # Stored-vjp forward cell ('never', or 'except_last's
                    # last micro-batch): slot c*S + i%S for the full ring,
                    # slot c for the one-per-chunk 'except_last' store.
                    y, vjp_fn = jax.vjp(
                        lambda a, b, xx: cell_fn(a, b, xx, c, i),
                        p_of(c), pre_params,
                        _slot_read(cr["inbox"], idx),
                    )
                    leaves = jax.tree_util.tree_leaves(vjp_fn)
                    _never_check_leaves(
                        leaves, vjp_leaf_specs, "interleaved"
                    )
                    slot = idx if store else c
                    rbuf = tuple(
                        lax.dynamic_update_index_in_dim(
                            b, leaves[i2], slot, 0
                        )
                        for b, i2 in zip(cr["rbuf"], buffered_idx)
                    )
                    out = dict(cr, act=y, rbuf=rbuf)
                    if store:
                        out["ybox"] = _slot_write(
                            cr["ybox"], i % S, y, c == v - 1
                        )
                    else:
                        out["ylast"] = tmap(
                            lambda cur, new: jnp.where(c == v - 1, new, cur),
                            cr["ylast"],
                            y,
                        )
                    return out

                def fwd_plain(cr):
                    x_f = splice(pre_params, c, i, _slot_read(cr["inbox"], idx))
                    y = self._block_fn_plain(
                        p_of(c), x_f, cell_key(c, i), aux_s, True
                    )
                    # Keep the spliced input for this cell's backward
                    # recompute (same slot: the table generator's liveness
                    # check covers receive -> backward-read).
                    return dict(
                        cr,
                        act=y,
                        inbox=_slot_write(cr["inbox"], idx, x_f, True),
                    )

                def fwd_branch(cr):
                    if store:
                        return fwd_store(cr)
                    if hybrid:
                        return lax.cond(i == m - 1, fwd_store, fwd_plain, cr)
                    return fwd_plain(cr)

                def bwd_store(cr):
                    slot = idx if store else c
                    vjp_cell = _never_rebuild(
                        vjp_tdef,
                        vjp_leaf_specs,
                        passthrough,
                        iter(
                            lax.dynamic_index_in_dim(
                                b, slot, 0, keepdims=False
                            )
                            for b in cr["rbuf"]
                        ),
                        jax.tree_util.tree_leaves(
                            (p_of(c), pre_params)
                        ),
                    )

                    def last_fn_s():
                        y_saved = (
                            _slot_read(cr["ybox"], i % S)
                            if store
                            else cr["ylast"]
                        )

                        def tail(p_post, p_loss, yy):
                            return mb_loss(yy, p_post, p_loss, i)

                        loss_i, (d_post, d_loss, dy) = (
                            jax.value_and_grad(tail, argnums=(0, 1, 2))(
                                post_params, loss_params, y_saved
                            )
                        )
                        d_blk, d_pre, dx = vjp_cell(dy)
                        return loss_i, d_blk, d_pre, d_post, d_loss, dx

                    def mid_fn_s():
                        d_blk, d_pre, dx = vjp_cell(
                            _slot_read(cr["gbox"], idx)
                        )
                        return (
                            jnp.float32(0.0),
                            d_blk,
                            d_pre,
                            tmap(jnp.zeros_like, post_params),
                            tmap(jnp.zeros_like, loss_params),
                            dx,
                        )

                    loss_i, d_blk, d_pre, d_post, d_loss, dx = lax.cond(
                        (stage == n - 1) & (c == v - 1),
                        last_fn_s,
                        mid_fn_s,
                    )
                    gblk = tmap(
                        lambda G, d: lax.dynamic_update_index_in_dim(
                            G,
                            lax.dynamic_index_in_dim(
                                G, c, 0, keepdims=False
                            )
                            + d,
                            c,
                            0,
                        ),
                        cr["gblk"],
                        d_blk,
                    )
                    return dict(
                        cr,
                        gact=dx,
                        gblk=gblk,
                        gpre=tmap(jnp.add, cr["gpre"], d_pre),
                        gpost=tmap(jnp.add, cr["gpost"], d_post),
                        gloss=tmap(jnp.add, cr["gloss"], d_loss),
                        loss=cr["loss"] + loss_i,
                    )

                def bwd_plain(cr):
                    x_saved = _slot_read(cr["inbox"], idx)
                    key = cell_key(c, i)

                    def through_block(p_blk, p_pre, x):
                        xin = splice(p_pre, c, i, x)
                        return self._block_fn_plain(
                            p_blk, xin, key, aux_s, True
                        )

                    def last_fn():
                        def full(p_blk, p_pre, p_post, p_loss, x):
                            y = through_block(p_blk, p_pre, x)
                            return mb_loss(y, p_post, p_loss, i)

                        loss_i, (d_blk, d_pre, d_post, d_loss, dx) = (
                            jax.value_and_grad(full, argnums=(0, 1, 2, 3, 4))(
                                p_of(c), pre_params, post_params,
                                loss_params, x_saved,
                            )
                        )
                        return loss_i, d_blk, d_pre, d_post, d_loss, dx

                    def mid_fn():
                        _, vjp_cell = jax.vjp(
                            through_block, p_of(c), pre_params, x_saved
                        )
                        d_blk, d_pre, dx = vjp_cell(_slot_read(cr["gbox"], idx))
                        return (
                            jnp.float32(0.0),
                            d_blk,
                            d_pre,
                            tmap(jnp.zeros_like, post_params),
                            tmap(jnp.zeros_like, loss_params),
                            dx,
                        )

                    loss_i, d_blk, d_pre, d_post, d_loss, dx = lax.cond(
                        (stage == n - 1) & (c == v - 1), last_fn, mid_fn
                    )
                    gblk = tmap(
                        lambda G, d: lax.dynamic_update_index_in_dim(
                            G,
                            lax.dynamic_index_in_dim(
                                G, c, 0, keepdims=False
                            )
                            + d,
                            c,
                            0,
                        ),
                        cr["gblk"],
                        d_blk,
                    )
                    return dict(
                        cr,
                        gact=dx,
                        gblk=gblk,
                        gpre=tmap(jnp.add, cr["gpre"], d_pre),
                        gpost=tmap(jnp.add, cr["gpost"], d_post),
                        gloss=tmap(jnp.add, cr["gloss"], d_loss),
                        loss=cr["loss"] + loss_i,
                    )

                def bwd_branch(cr):
                    if store:
                        return bwd_store(cr)
                    if hybrid:
                        return lax.cond(i == m - 1, bwd_store, bwd_plain, cr)
                    return bwd_plain(cr)

                sel = jnp.where(k == FWD, 0, jnp.where(k == BWD, 1, 2))
                carry = lax.switch(
                    sel,
                    [_scoped("forward", fwd_branch),
                     _scoped("backward", bwd_branch), lambda cr: cr],
                    carry,
                )
                return carry, ()

            carry, _ = lax.scan(
                _scoped("tick", tick), carry0, rows_xs,
                unroll=self.scan_unroll
            )
            loss = lax.psum(carry["loss"], self.pp_axis)
            grads = {"blocks": tmap(lambda g: g[None], carry["gblk"])}
            if self.pre is not None:
                grads["pre"] = lax.psum(carry["gpre"], self.pp_axis)
            if self.post is not None:
                grads["post"] = lax.psum(carry["gpost"], self.pp_axis)
            if self._loss_is_layer:
                grads["loss"] = lax.psum(carry["gloss"], self.pp_axis)
            loss, grads = self._reduce_dp(loss, grads, scatter_blocks=True)
            loss, grads = self._reduce_ep(loss, grads)
            return loss, grads

        param_specs = {
            "blocks": self._fsdp_specs if self.fsdp else self._blocks_spec
        }
        if self.pre is not None:
            param_specs["pre"] = self._pre_spec
        if self.post is not None:
            param_specs["post"] = self._post_spec
        if self._loss_is_layer:
            param_specs["loss"] = self._loss_spec

        in_specs = (param_specs, data_spec, data_spec)
        if masked:
            in_specs += (self._mask_spec(),)
        if use_rng:
            in_specs += (P(),)
        mapped = _shard_map(
            local,
            self.mesh,
            in_specs=in_specs,
            out_specs=(P(), param_specs),
        )
        return jax.jit(mapped)

    def _mask_spec(self) -> P:
        """Spec for the [m, b] ragged-batch mask: batch dim over dp/ep
        (like data), no sequence dim."""
        batch_axes = tuple(
            a for a in (self.dp_axis, self.ep_axis) if a is not None
        )
        return P(None, batch_axes if batch_axes else None)

    def _build_train_step(self, use_rng: bool, masked: bool = False) -> Callable:
        if self.schedule == "1f1b":
            return self._build_train_step_1f1b(use_rng, masked)
        if self.schedule == "interleaved":
            return self._build_train_step_interleaved(use_rng, masked)
        if self.schedule == "zb":
            return self._build_train_step_zb(use_rng, masked)
        n = self.n_stages
        data_spec = self._data_specs()
        counted = self._counted

        def local(params, x_mb, tgt_mb, *rest):
            rest = list(rest)
            mask_mb = rest.pop(0) if masked else None
            rng = rest.pop(0) if use_rng else None
            mean_scale = (
                self._mask_mean_scale(mask_mb)
                if masked and self.loss_reduction == "mean"
                else None
            )
            stage = lax.axis_index(self.pp_axis)

            def loss_of(params):
                # pre runs once per (real) micro-batch on EVERY pp lane but
                # only stage 0's output is consumed; the injection is
                # seed-independent and pre grads are psum'd over pp, so the
                # aux scale must be stage-masked (1/m on stage 0, 0
                # elsewhere) to keep the injected coefficient exact.  The
                # pipeline's own cells handle their tick-validity-aware
                # scale inside _local_pipeline.
                if self.pre is not None:
                    pre_scale = jnp.where(stage == 0, 1.0 / self.chunks, 0.0)
                    with aux_scale(pre_scale):
                        x_in = self._apply_pre(params["pre"], x_mb, rng, True)
                else:
                    x_in = x_mb
                blocks_in = (
                    self._gather_fsdp(params["blocks"])
                    if self.fsdp
                    else params["blocks"]
                )
                ys = self._local_pipeline(blocks_in, x_in, rng, True, counted)
                ys, counts = ys if counted else (ys, None)
                outs = self._outputs_from_ticks(ys)
                gathered = microbatch.gather_stacked(outs)
                tgt = microbatch.gather_stacked(tgt_mb)
                mask_g = (
                    microbatch.gather_stacked(mask_mb) if masked else None
                )
                B = jax.tree_util.tree_leaves(gathered)[0].shape[0]
                post_rng = (
                    jax.random.fold_in(rng, 0x7FFFFFFE) if rng is not None else None
                )
                if self.loss_reduction is not None and B % n == 0 and n > 1:
                    # Shard the post/loss phase over pp: the pipeline's real
                    # outputs exist only on the last stage, so scatter the
                    # batch in n slices (one ppermute each, size/n), run the
                    # head + loss on 1/n of the batch per stage, and sum the
                    # per-slice losses.  This cuts head FLOPs and the
                    # [B, ..., vocab]-sized logits memory to 1/n per device.
                    # Requires loss_fn (and post) to decompose over batch
                    # elements — 'mean'/'sum' declares which way.
                    per = B // n
                    zeroed = jax.tree_util.tree_map(
                        lambda a: jnp.where(stage == n - 1, a, jnp.zeros_like(a)),
                        gathered,
                    )
                    my = None
                    for j in range(n):
                        sl = jax.tree_util.tree_map(
                            lambda a: lax.dynamic_slice_in_dim(a, j * per, per, 0),
                            zeroed,
                        )
                        # Single-pair ppermute: well-defined transpose, so the
                        # backward routes each slice's cotangent straight back
                        # to the last stage (non-destinations receive zeros).
                        recv = jax.tree_util.tree_map(
                            lambda a: lax.ppermute(a, self.pp_axis, [(n - 1, j)]),
                            sl,
                        )
                        my = (
                            recv
                            if my is None
                            else jax.tree_util.tree_map(jnp.add, my, recv)
                        )
                    tgt_my = jax.tree_util.tree_map(
                        lambda a: lax.dynamic_slice_in_dim(a, stage * per, per, 0),
                        tgt,
                    )
                    if self.post is not None:
                        # Every stage runs the head on 1/n of the batch:
                        # aux injections average over the n slices.
                        with aux_scale(1.0 / n):
                            my, _ = self.post.apply(
                                self._tied(
                                    params["post"], params.get("pre", ()),
                                    self._tie_post,
                                ),
                                (), my, rng=post_rng, train=True,
                            )
                    p_loss_t = self._tied(
                        params.get("loss", ()), params.get("pre", ()),
                        self._tie_loss,
                    )
                    if masked:
                        # Masked per-row SUM over this stage's slice: the
                        # n slices add to the lane total (no /n), and the
                        # mean scale folds dp·ep/N_real in (pmeans divide
                        # it back out to the exact global masked mean).
                        mask_my = lax.dynamic_slice_in_dim(
                            mask_g, stage * per, per, 0
                        )
                        l = self._masked_loss_sum(
                            p_loss_t, my, tgt_my, mask_my
                        )
                        if self.loss_reduction == "mean":
                            l = l * mean_scale
                        return l, counts
                    l = self._loss_call(p_loss_t, my, tgt_my)
                    if self.loss_reduction == "mean":
                        l = l / n
                    # LOCAL per-slice loss; the psum after value_and_grad
                    # reassembles the global loss for reporting.
                    return l, counts
                if self.post is not None:
                    # post runs on every pp lane but only the last stage's
                    # activations are real (and its grads are psum'd over
                    # pp): stage-mask the aux scale like pre.
                    with aux_scale(jnp.where(stage == n - 1, 1.0, 0.0)):
                        gathered, _ = self.post.apply(
                            self._tied(
                                params["post"], params.get("pre", ()),
                                self._tie_post,
                            ),
                            (), gathered, rng=post_rng, train=True,
                        )
                p_loss_t = self._tied(
                    params.get("loss", ()), params.get("pre", ()),
                    self._tie_loss,
                )
                if masked:
                    l = self._masked_loss_sum(
                        p_loss_t, gathered, tgt, mask_g
                    )
                    if self.loss_reduction == "mean":
                        l = l * mean_scale
                else:
                    l = self._loss_call(p_loss_t, gathered, tgt)
                # LOCAL loss, nonzero only on the last stage.  Do NOT psum
                # here: differentiating a replicated (psum'd) output would
                # seed one cotangent per device and over-count gradients by
                # the pp size — the transposed ppermutes already carry the
                # cross-stage cotangents back along the ring.
                return jnp.where(stage == n - 1, l, 0.0), counts

            # value_and_grad, taken apart so that each half has its scope:
            # the backward's operations read
            # ``backward/transpose(jvp(forward))/...`` in the trace.
            # loss_of returns (loss, counts); counts is None, and dropped,
            # for a block that declares none.
            forward = loss_of if counted else (lambda p: loss_of(p)[0])
            loss, vjp_loss, *aux = jax.vjp(
                _scoped("forward", forward), params, has_aux=counted)
            with jax.named_scope("backward"):
                (grads,) = vjp_loss(jnp.ones_like(loss))
            loss = lax.psum(loss, self.pp_axis)  # broadcast for reporting
            # pre/post/loss grads land on the consuming stage's lane only;
            # share across pp.  Block grads are per-stage local by
            # construction.
            if self.pre is not None:
                grads["pre"] = lax.psum(grads["pre"], self.pp_axis)
            if self.post is not None:
                grads["post"] = lax.psum(grads["post"], self.pp_axis)
            if self._loss_is_layer:
                grads["loss"] = lax.psum(grads["loss"], self.pp_axis)
            loss, grads = self._reduce_dp(loss, grads, scatter_blocks=False)
            loss, grads = self._reduce_ep(loss, grads)
            if self.sp_axis:
                # Params are replicated over sp; each lane differentiated its
                # own token shard's loss.  mean-reduction: global loss/grad is
                # the lane mean; sum-reduction: the lane sum.
                red = lax.pmean if self.loss_reduction == "mean" else lax.psum
                loss = red(loss, self.sp_axis)
                grads = red(grads, self.sp_axis)
            if counted:
                # This stage's counts, summed over the lanes that each saw
                # a share of the tokens; stages stack on the way out.
                token_axes = tuple(a for a in (
                    self.dp_axis, self.ep_axis, self.sp_axis) if a)
                counts = lax.psum(aux[0], token_axes) if token_axes else aux[0]
                return loss, grads, counts[None]
            return loss, grads

        param_specs = {
            "blocks": self._fsdp_specs if self.fsdp else self._blocks_spec
        }
        if self.pre is not None:
            param_specs["pre"] = self._pre_spec
        if self.post is not None:
            param_specs["post"] = self._post_spec
        if self._loss_is_layer:
            param_specs["loss"] = self._loss_spec

        in_specs = (param_specs, data_spec, data_spec)
        if masked:
            in_specs += (self._mask_spec(),)
        if use_rng:
            in_specs += (P(),)
        mapped = _shard_map(
            local,
            self.mesh,
            in_specs=in_specs,
            out_specs=(P(), param_specs) + (
                (P(self.pp_axis),) if counted else ()),
        )
        return jax.jit(mapped)

    def _check_batch(
        self, x: Pytree, target: Optional[Pytree] = None, *,
        ragged_ok: bool = False,
    ) -> int:
        """Validate batch/sequence divisibility; returns the number of
        padding rows a ragged batch needs (0 when already divisible).
        ``ragged_ok`` callers pad + mask instead of raising (reference
        parity: indivisible batches, reference microbatch.py:143-158)."""
        dp = self.mesh.shape[self.dp_axis] if self.dp_axis else 1
        ep = self.mesh.shape[self.ep_axis] if self.ep_axis else 1
        b = microbatch.batch_size(x)
        pad = (-b) % (self.chunks * dp * ep)
        if pad and not ragged_ok:
            raise ValueError(
                f"batch size {b} must be divisible by chunks*dp*ep = "
                f"{self.chunks}*{dp}*{ep} = {self.chunks * dp * ep} here: "
                "ragged batches need a row-decomposable loss to weight the "
                "padding out — set loss_reduction='mean' or 'sum' (or use "
                "the MPMD GPipe engine, whose scheduler runs ragged "
                "micro-batches natively)"
            )
        if self.sp_axis:
            sp = self.mesh.shape[self.sp_axis]
            trees = [("input", x)]
            if target is not None:
                # Targets ride the same sharding specs as inputs, so they
                # need a compatible sequence dim too.
                trees.append(("target", target))
            for what, tree in trees:
                for leaf in jax.tree_util.tree_leaves(tree):
                    if leaf.ndim < 2 or leaf.shape[1] % sp != 0:
                        raise ValueError(
                            f"sequence parallelism shards data dim 1 over "
                            f"{self.sp_axis}={sp}; got {what} leaf shape "
                            f"{leaf.shape}"
                        )
        return pad

    def _check_params(self, params: Pytree) -> None:
        """Didactic validation of the params tree BEFORE it reaches
        shard_map, whose own failures (spec/shape mismatches deep inside
        one compiled program) are opaque.  Mirrors the reference's eager
        constructor/input validation ethos (reference gpipe.py:34-64)."""
        if not isinstance(params, dict) or "blocks" not in params:
            raise ValueError(
                "params must be the dict returned by SpmdGPipe.init "
                "(keys 'blocks' and, when pre/post are set, 'pre'/'post'); "
                f"got {type(params).__name__} with keys "
                f"{sorted(params) if isinstance(params, dict) else 'n/a'}"
            )
        checks = [("pre", self.pre), ("post", self.post)]
        if self._loss_is_layer:
            checks.append(("loss", self.loss_fn))
        for key, layer in checks:
            if (layer is not None) != (key in params):
                raise ValueError(
                    f"engine {'defines' if layer is not None else 'has no'} "
                    f"{key!r} layer but params "
                    f"{'lacks' if layer is not None else 'contains'} a "
                    f"{key!r} entry — params must come from THIS engine's "
                    "init (pre/post configuration must match)"
                )
        for key, keys in (("post", self._tie_post), ("loss", self._tie_loss)):
            entry = params.get(key)
            if not (keys and isinstance(entry, dict)):
                continue
            dup = [k for k in keys if k in entry]
            if dup:
                raise ValueError(
                    f"params[{key!r}] contains tied pre-param entr"
                    f"{'ies' if len(dup) > 1 else 'y'} {dup}: the engine "
                    "splices these from params['pre'] at apply time "
                    "(meta['tie_pre']), and a duplicated array reference "
                    "would be donated twice under make_train_step and "
                    "double the memory.  Drop them — e.g. assemble "
                    "imported weights with "
                    "models.generation.spmd_params_from_flat"
                )
        v = self.virtual_stages
        want = (self.n_stages,) if v == 1 else (self.n_stages, v)
        for leaf in jax.tree_util.tree_leaves(params["blocks"]):
            got = tuple(leaf.shape[: len(want)])
            if got != want:
                raise ValueError(
                    f"block param leaf has leading dims {got}, expected "
                    f"{want} (= {'(n_stages,)' if v == 1 else '(n_stages, virtual_stages)'}); "
                    "params were initialized for a different pipeline "
                    "configuration"
                )
            break  # leading-dim layout is uniform; one leaf suffices

    def _fault_token_checked(self, *, for_train: bool = False) -> Optional[int]:
        """Fault-plan cache token for the compiled programs, refusing
        plans the requested builder cannot inject: only the fill-drain
        tick loop (``_local_pipeline`` — every non-interleaved forward,
        but only the fill_drain training step) carries the per-cell
        poisoning hook.  A chaos run that silently injects nothing would
        certify recovery code that never executed.  Also evicts cache
        entries from expired plans — each activation's token is unique,
        so poisoned programs would otherwise accumulate forever."""
        plan = _faults.active_plan()
        bad_schedule = (
            self.schedule != "fill_drain"
            if for_train
            else self.schedule == "interleaved"
        )
        if plan is not None and plan.nan_at is not None and bad_schedule:
            raise NotImplementedError(
                "faults.inject(nan_at=...) is supported by the SPMD "
                "fill_drain training step and the non-interleaved "
                "apply/eval programs only (got "
                f"schedule={self.schedule!r}); these are the paths with a "
                "per-cell injection hook"
            )
        token = _faults.plan_token()
        for cache, key_token in (
            (self._train_step_fns, lambda k: k[2]),
            (self._apply_fns, lambda k: k),
            (self._eval_fns, lambda k: k),
        ):
            for k in [
                k for k in cache
                if key_token(k) is not None and key_token(k) != token
            ]:
                del cache[k]
        return token

    @contextlib.contextmanager
    def _annotate_cell_failure(
        self, params: Pytree, x_mb: Pytree
    ) -> Any:
        """Give trace-time partition exceptions the MPMD engine's
        (stage, micro-batch) note (tests/test_failures.py semantics).

        The SPMD schedule traces each cell ONCE inside ``lax.scan``, so a
        Python exception escaping a layer carries no concrete cell
        identity.  On failure, re-localize by abstract-evaluating the
        pre layer and then the block per stage (no FLOPs, no compile):
        the first cell whose probe reproduces the same exception type is
        named.  Cells are shape-uniform across stages and micro-batches,
        so the first failing cell is the earliest the schedule executes —
        micro-batch 0 of the named stage.  Best-effort: if the probe
        cannot reproduce the failure (e.g. collectives needing mesh axes
        raise differently outside shard_map), the original exception
        propagates un-noted, never masked.
        """
        try:
            yield
        except Exception as e:  # noqa: BLE001 — annotate and re-raise as-is
            notes = getattr(e, "__notes__", None) or []
            if hasattr(e, "add_note") and not any(
                "pipeline stage" in n for n in notes
            ):
                cell = self._locate_failing_cell(type(e), params, x_mb)
                if cell is not None:
                    stage, mb, where = cell
                    e.add_note(
                        f"raised in pipeline stage {stage}, micro-batch "
                        f"{mb} ({where}; SPMD {self.schedule} schedule — "
                        "first failing cell of the traced program)"
                    )
            raise

    def _locate_failing_cell(
        self, exc_type: type, params: Pytree, x_mb: Pytree
    ) -> Optional[Tuple[int, int, str]]:
        """Abstract-eval probe behind :meth:`_annotate_cell_failure`;
        returns ``(stage, micro_batch, component)`` or None."""

        def absify(tree, drop=0):
            return jax.tree_util.tree_map(
                lambda a: jax.ShapeDtypeStruct(
                    tuple(np.shape(a))[drop:], jnp.asarray(a).dtype
                ),
                tree,
            )

        x = absify(x_mb, drop=1)  # one micro-batch's input spec
        try:
            if self.pre is not None:
                try:
                    x = jax.eval_shape(
                        lambda p, xx: self.pre.apply(
                            p, (), xx, rng=None, train=True
                        )[0],
                        absify(params["pre"]),
                        x,
                    )
                except exc_type:
                    return (0, 0, f"pre layer {self.pre.name!r}")
            drop = 2 if self.virtual_stages > 1 else 1
            blk = absify(params["blocks"], drop=drop)
            for s in range(self.n_stages):
                try:
                    with aux_scale(0.0):
                        x = jax.eval_shape(
                            lambda p, xx: self.block.apply(
                                p, (), xx, rng=None, train=True
                            )[0],
                            blk,
                            x,
                        )
                except exc_type:
                    return (s, 0, f"block {self.block.name!r}")
        except Exception:  # noqa: BLE001 — probe must never mask the error
            return None
        return None

    def train_step(
        self, params: Pytree, x: Pytree, target: Pytree,
        rng: Optional[jax.Array] = None,
    ) -> Tuple[jax.Array, Pytree]:
        """One pipelined forward+backward; returns ``(loss, grads)``.

        ``x``/``target`` are full mini-batches ``[B, ...]``.  A ragged
        ``B`` (not divisible by chunks·dp·ep) is accepted whenever the
        loss is row-decomposable (``loss_reduction`` 'mean'/'sum'): the
        batch is edge-padded to the next multiple and a mask weights the
        padding out of the loss — and therefore out of every gradient
        that flows from it — exactly (reference parity: indivisible
        batches, reference microbatch.py:143-158 / reference
        tests/test_gpipe.py:107-126).  Caveat: computation that couples
        rows INSIDE the blocks still sees the duplicated padding rows —
        a MoE balance injection (``MoEConfig.balance_weight > 0``) or
        batch-normalization statistics average over the padded
        micro-batch, so those auxiliary terms are mildly perturbed
        (the task-loss gradients remain exact).  Pad to a divisible
        batch yourself if the auxiliary terms must be padding-free.
        Pass ``rng`` if any layer uses
        randomness (dropout raises loudly without it, matching the MPMD
        engine); omit it for deterministic models.
        """
        return self._train_step_out(params, x, target, rng)[:2]

    def _train_step_out(
        self, params: Pytree, x: Pytree, target: Pytree,
        rng: Optional[jax.Array] = None,
    ) -> Tuple:
        """:meth:`train_step`'s ``(loss, grads)``, and behind them the
        block's counts ``[stages, ...]`` where it declares
        ``apply_counts`` (fill-drain schedule)."""
        self._check_params(params)
        token = self._fault_token_checked(for_train=True)
        pad = self._check_batch(
            x, target, ragged_ok=self.loss_reduction is not None
        )
        if self.fsdp:
            self._ensure_fsdp(params["blocks"])
        use_rng = rng is not None
        key = (use_rng, bool(pad), token)
        if key not in self._train_step_fns:
            self._train_step_fns[key] = self._build_train_step(
                use_rng, masked=bool(pad)
            )
        if pad and not self._warned_ragged_coupled:
            self._warned_ragged_coupled = True
            coupled = list(dict.fromkeys(  # dedupe, keep first-seen order
                c
                for lyr in (self.block, self.pre, self.post)
                if lyr is not None
                for c in _row_coupled(lyr)
            ))
            if coupled:
                import warnings

                warnings.warn(
                    "ragged batch padded with duplicated edge rows, and the "
                    f"model has row-coupled auxiliary terms ({', '.join(coupled)}) "
                    "that will see those padding rows; task-loss gradients "
                    "remain exact, but pad to a divisible batch yourself if "
                    "the auxiliary terms must be padding-free (see "
                    "SpmdGPipe.train_step docstring)",
                    stacklevel=2,
                )
        if pad:
            b_real = microbatch.batch_size(x)
            mask = jnp.concatenate(
                [jnp.ones((b_real,), jnp.float32),
                 jnp.zeros((pad,), jnp.float32)]
            )
            x = _pad_batch(x, pad)
            target = _pad_batch(target, pad)
        x_mb = microbatch.scatter_stacked(x, self.chunks)
        tgt_mb = microbatch.scatter_stacked(target, self.chunks)
        args = (params, x_mb, tgt_mb)
        if pad:
            args += (microbatch.scatter_stacked(mask, self.chunks),)
        if use_rng:
            args += (rng,)
        with self._annotate_cell_failure(params, x_mb):
            return self._train_step_fns[key](*args)

    # ------------------------------------------------------------------ #
    # ZeRO-style sharded optimizer update (optimizer state over dp)      #
    # ------------------------------------------------------------------ #

    def _zero_axes(self) -> Tuple[str, ...]:
        """The mesh axes the param layout itself uses — the leading
        explicit dims of the ZeRO state representation (state varies
        over them because the local param shards do)."""
        axes = [self.pp_axis]
        for ax in (self.tp_axis, self.ep_axis):
            if ax is not None and ax not in axes:
                axes.append(ax)
        return tuple(axes)

    def _zero_level(self, zero: Any = None) -> int:
        """Normalize a ``zero=`` argument to a ZeRO LEVEL (0, 1 or 3).

        ``None`` reads the pipe's declared :attr:`zero_update`; a bool
        maps ``False -> 0`` and ``True`` to the natural level for the
        layout (3 under fsdp — params are already gather-at-use sharded,
        so the fully-sharded update is the only coherent one — else 1).
        Levels and layouts must agree: ZeRO-1's segment math needs
        dp-REPLICATED params, and ZeRO-3 IS the fsdp storage layout's
        update, so ``zero=1`` under fsdp and ``zero=3`` without fsdp are
        both refused didactically (there is no ZeRO-2 here: grads
        already leave the step reduce-scattered under fsdp, and without
        fsdp the grad buffer is transient inside one compiled program —
        nothing to shard)."""
        if zero is None:
            zero = self.zero_update
        if isinstance(zero, bool):
            level = ((3 if self.fsdp else 1) if zero else 0)
        elif isinstance(zero, int):
            level = zero
        else:
            raise ValueError(
                f"zero must be a bool or a ZeRO level int, got {zero!r}"
            )
        if level not in (0, 1, 3):
            raise ValueError(
                f"zero={level} is not a supported ZeRO level: use 0/False "
                "(replicated update), 1/True (optimizer state sharded "
                "over dp), or 3 (fully-sharded params+grads+state, "
                "requires fsdp=True).  Level 2 does not exist here: "
                "gradients already leave the fsdp step reduce-scattered, "
                "and without fsdp the grad tree is transient inside the "
                "fused step program"
            )
        if level == 1 and self.fsdp:
            raise ValueError(
                "zero=1 under fsdp is incoherent: the ZeRO-1 segment math "
                "assumes dp-REPLICATED params, but fsdp stores them "
                "sharded over dp (their optimizer state is already "
                "dp-partitioned alongside).  Use zero=3 (or zero=True, "
                "which resolves to 3 under fsdp)"
            )
        if level == 3 and not self.fsdp:
            raise ValueError(
                "zero=3 IS the fully-sharded (gather-at-use) layout's "
                "update: params, grads and optimizer state all live "
                "sharded over dp.  Construct the pipe with fsdp=True to "
                "get that storage layout (zero=1 shards optimizer state "
                "only and works with replicated params)"
            )
        return level

    def _zero_check(self, level: int = 1) -> None:
        if level == 0:
            return
        if self.dp_axis is None or self.mesh.shape[self.dp_axis] < 2:
            raise ValueError(
                "the ZeRO-sharded optimizer update partitions state over "
                "the data-parallel lanes: it needs dp_axis set and a dp "
                "mesh axis of size >= 2 (arXiv:2004.13336 — with one "
                "replica there is nothing to shard; use zero=False)"
            )

    def _zero_machinery(
        self, optimizer: Any, params: Pytree
    ) -> Tuple[Pytree, Pytree, Callable, Callable]:
        """(param_specs, state_specs, local_init, local_update) for the
        ZeRO update's shard_map programs.

        Representation: each optimizer-state leaf that mirrors a param
        is stored FLAT, padded to a dp multiple, with explicit leading
        dims for every layout axis — global shape
        ``(*axis_sizes(zero_axes), Fp)`` sharded
        ``P(*zero_axes, dp)`` — so each device holds exactly
        ``local_param_size / N_dp`` elements of state per leaf: the
        ~N_dp× optimizer-memory drop the planner's certification
        models.  Scalar state (step counters) stays replicated.
        """
        from torchgpipe_tpu.analysis.partition_rules import (
            match_partition_rules,
        )

        param_specs = match_partition_rules(self.rule_table(params), params)
        zaxes = self._zero_axes()
        dpn = int(self.mesh.shape[self.dp_axis])
        # The ZeRO-1 segment math assumes every lane's local param shard
        # is dp-REPLICATED (each dp lane slices its segment of the same
        # data); a layout already sharding a leaf over dp would make
        # to_full reassemble a mixture of different lanes' data —
        # silently wrong training.  (fsdp layouts take the zero=3 path,
        # which never builds segments — see _make_apply_update.)
        for path, spec in _rule_leaf_specs(param_specs):
            entries = tuple(spec)
            for e in entries:
                axes_ = e if isinstance(e, tuple) else (e,)
                if e is not None and self.dp_axis in axes_:
                    raise ValueError(
                        f"zero=True needs dp-replicated parameters, but "
                        f"the layout shards leaf {path!r} over the dp "
                        f"axis ({spec}) — its optimizer state is already "
                        "dp-partitioned alongside the param; use "
                        "zero=False (or fsdp) for this layout"
                    )

        def local_shape(a: Any, spec: P) -> Tuple[int, ...]:
            shape = list(a.shape)
            for i, ax in enumerate(tuple(spec)):
                if ax is None:
                    continue
                axes_ = ax if isinstance(ax, tuple) else (ax,)
                for a_ in axes_:
                    shape[i] //= int(self.mesh.shape[a_])
            return tuple(shape)

        def seg_len(a: Any, spec: P) -> int:
            n = 1
            for d in local_shape(a, spec):
                n *= int(d)
            return -(-n // dpn)  # ceil: the dp padding

        seg_spec = jax.tree_util.tree_map(
            lambda a, s: jax.ShapeDtypeStruct(
                (1,) * len(zaxes) + (seg_len(a, s),), a.dtype
            ),
            params, param_specs,
        )
        state_struct = jax.eval_shape(optimizer.init, seg_spec)
        seg_shapes = {
            leaf.shape
            for leaf in jax.tree_util.tree_leaves(seg_spec)
        }

        def state_spec_of(leaf: Any) -> P:
            if leaf.ndim == 0:
                return P()
            if leaf.shape in seg_shapes or (
                leaf.ndim == len(zaxes) + 1
                and leaf.shape[: len(zaxes)] == (1,) * len(zaxes)
            ):
                return P(*zaxes, self.dp_axis)
            raise ValueError(
                "the ZeRO-sharded update supports optimizers whose "
                "state mirrors the params leaf-for-leaf plus scalar "
                "counters (adam/adamw/sgd-momentum shape); this "
                f"optimizer's state has a leaf of shape {leaf.shape} "
                "that matches neither — use zero=False for it"
            )

        state_specs = jax.tree_util.tree_map(state_spec_of, state_struct)

        def to_seg(a: jax.Array) -> jax.Array:
            flat = a.reshape((-1,))
            f = flat.shape[0]
            seg = -(-f // dpn)
            if seg * dpn > f:
                flat = jnp.pad(flat, (0, seg * dpn - f))
            i = lax.axis_index(self.dp_axis)
            piece = lax.dynamic_slice(flat, (i * seg,), (seg,))
            return piece.reshape((1,) * len(zaxes) + (seg,))

        def local_init(p_loc: Pytree) -> Pytree:
            return optimizer.init(jax.tree_util.tree_map(to_seg, p_loc))

        def local_update(
            p_loc: Pytree, g_loc: Pytree, s_loc: Pytree
        ) -> Tuple[Pytree, Pytree]:
            seg_p = jax.tree_util.tree_map(to_seg, p_loc)
            seg_g = jax.tree_util.tree_map(to_seg, g_loc)
            updates, new_s = optimizer.update(seg_g, s_loc, seg_p)
            new_seg = jax.tree_util.tree_map(
                lambda a, u: (a + u).astype(a.dtype), seg_p, updates
            )

            def to_full(ns: jax.Array, old: jax.Array) -> jax.Array:
                flat = lax.all_gather(
                    ns.reshape((-1,)), self.dp_axis, axis=0, tiled=True
                )
                f = 1
                for d in old.shape:
                    f *= int(d)
                return flat[:f].reshape(old.shape)

            new_p = jax.tree_util.tree_map(to_full, new_seg, p_loc)
            return new_p, new_s

        return param_specs, state_specs, local_init, local_update

    def zero_opt_state(
        self, optimizer: Any, params: Pytree, zero: Any = None
    ) -> Pytree:
        """Initialize dp-SHARDED optimizer state for ``optimizer`` (the
        ZeRO twin of ``place_tree(optimizer.init(params))``): each
        data-parallel lane stores 1/N_dp of every state leaf.  Pair with
        ``make_train_step(optimizer, zero=...)`` at the same level; the
        update is bitwise-equal to the unsharded one for elementwise
        optimizers (adam/adamw/sgd — anything without cross-element
        coupling like global-norm clipping).

        ``zero=None`` defaults to ``True`` — the pipe's natural level
        (3 under fsdp, else 1).  At level 3 the state layout IS the
        param layout: ``optimizer.init``'s ``zeros_like`` moments
        inherit the fsdp storage sharding, so this is exactly
        ``place_tree(optimizer.init(params))`` — each lane already
        stores 1/N_dp of every mirrored leaf without any segment
        machinery."""
        level = self._zero_level(True if zero is None else zero)
        self._zero_check(level)
        if level == 0:
            return self.place_tree(optimizer.init(params))
        if level == 3:
            # Params are stored sharded (gather-at-use); zeros_like-built
            # state inherits their NamedShardings leaf-for-leaf.
            return self.place_tree(optimizer.init(params))
        param_specs, state_specs, local_init, _ = self._zero_machinery(
            optimizer, params
        )
        fn = _shard_map(
            local_init, self.mesh,
            in_specs=(param_specs,), out_specs=state_specs,
        )
        return jax.jit(fn)(params)

    def _timeline(self) -> Any:
        """Where this pipe's spans go: the ``tracer`` it was given, else
        the process's default timeline."""
        return self.tracer if self.tracer is not None else default_timeline()

    def _schedule_shape(self) -> Dict[str, Any]:
        """The fields of this pipe's ``step`` span."""
        return schedule_shape(
            self.schedule, self.n_stages, self.chunks, self.virtual_stages
        )

    def megastep_boundary(self, step: int) -> bool:
        """True when ``step`` completed optimizer steps land on a
        megastep boundary — the cadence checkpoint/preemption hooks run
        at, and the only place
        :class:`torchgpipe_tpu.obs.replan.ReplanOnDrift` may fire (a
        replan can never land inside a compiled K-step program)."""
        k = max(int(self.megastep or 1), 1)
        return step % k == 0

    def make_train_step(
        self, optimizer: Any, *, donate: bool = True,
        megastep: Optional[int] = None,
        zero: Optional[Union[bool, int]] = None,
    ) -> Callable[..., Tuple[jax.Array, Pytree, Pytree]]:
        """The whole update as ONE compiled program: pipelined
        forward+backward plus the optimizer, fused by XLA.

        ``optimizer`` is any optax-style gradient transformation (pytree
        state, ``update(grads, state, params) -> (updates, state)``).
        Returns ``step(params, opt_state, x, target, rng=None) ->
        (loss, new_params, new_opt_state)``; initialize ``opt_state``
        with ``place_tree(optimizer.init(params))``.  A block that
        declares ``meta['apply_counts']`` (an expert stage's held
        experts' token counts) gets its counts, summed over the step's
        micro-batches and stacked over the stages, as a FOURTH result
        (fill-drain schedule), to be fetched with the loss.

        Two wins over calling :meth:`train_step` and applying the
        optimizer in a second jitted program (the reference's shape:
        ``loss.backward()`` then ``optimizer.step()`` as separate host
        calls, reference ``benchmarks/resnet101-speed/main.py``):

        * one host dispatch per step instead of two, and no gradient
          pytree materialized at the program boundary;
        * with ``donate=True`` the incoming ``params``/``opt_state``
          buffers are donated to XLA, so the update happens in place in
          HBM — no transient 2x params+moments footprint.  The caller
          must treat the passed-in arrays as consumed and use the
          returned ones (standard JAX donation contract; XLA ignores
          donation on backends that don't support it, e.g. host CPU).

        The returned callable re-traces per distinct input shape
        signature (ragged batch buckets, rng presence), exactly like
        :meth:`train_step`.

        ``megastep`` (default: the pipe's declared ``megastep`` field)
        compiles K optimizer steps into ONE program — a ``lax.scan``
        over the full pipelined step with the ``(params, opt_state)``
        carry donated, killing the per-step Python dispatch, host sync
        and guard bookkeeping K-fold.  The returned step then consumes
        ``[K, ...]``-stacked batches and returns ``(loss[K], new_params,
        new_opt_state, finite[K])``:

        * NaN skip-step semantics move INSIDE the scan: after each inner
          step a traced all-finite check over exactly what
          :class:`~torchgpipe_tpu.resilience.guard.StepGuard` would
          check (loss, updated params, updated optimizer state) gates
          the carry — a non-finite step k hands step k+1 the step-k
          input state, bitwise what K guarded single steps produce.
          The gate is UNCONDITIONAL (baked into the compiled program —
          ``GuardPolicy.skip_nonfinite`` cannot reach inside it); a
          wrapping guard always counts the skips that happened.
          ``finite[K]`` reports the mask so a wrapping StepGuard (which
          reads ``step.megastep``) can keep its skip statistics and
          loss-scale backoff at scan — not step — granularity.
        * RETRY GRANULARITY CHANGES (documented contract): a transient
          failure retries the whole K-step megastep, and checkpoint /
          preemption hooks run at megastep boundaries only.  With
          ``rng``, inner step k derives its key as ``fold_in(rng, k)``.

        ``zero`` (default: the pipe's declared :attr:`zero_update`)
        selects the ZeRO level of the optimizer apply
        (arXiv:2004.13336 / arXiv:1910.02054):

        * ``0``/``False`` — replicated state, plain elementwise update;
        * ``1``/``True`` (non-fsdp) — optimizer state partitioned over
          the dp axis — initialize it with :meth:`zero_opt_state`
          instead of ``place_tree(optimizer.init(params))`` — each lane
          updates its 1/N_dp segment of every param, and the updated
          params are all-gathered over dp;
        * ``3``/``True`` (fsdp) — the fully-sharded update: grads
          already leave the pipelined step reduce-scattered into the
          fsdp storage layout (the block all_gather's transpose), so
          the plain elementwise apply updates sharded state against
          sharded params with no extra collective — GSPMD keeps every
          leaf in its ``P(dp, ...)`` storage spec end-to-end.
          Initialize state with :meth:`zero_opt_state` (at level 3
          that is exactly ``place_tree(optimizer.init(params))``).

        Every level is bitwise-equal to the unsharded update for
        elementwise optimizers; per-device optimizer memory drops
        ~N_dp× (level 3 additionally drops params and grads ~N_dp×),
        which the planner's memory certification models.
        """
        K = self.megastep if megastep is None else int(megastep)
        if K < 1:
            raise ValueError(f"megastep must be >= 1, got {K}")
        level = self._zero_level(zero)
        self._zero_check(level)
        if K > 1:
            return self._make_megastep(optimizer, K, donate, level)
        apply_update = self._make_apply_update(optimizer, level)

        def whole(
            params: Pytree,
            opt_state: Pytree,
            x: Pytree,
            target: Pytree,
            rng: Optional[jax.Array],
            plan_token: Optional[int],
        ) -> Tuple[jax.Array, Pytree, Pytree]:
            # plan_token is STATIC and unused in the math: it keys the jit
            # cache so a trace with an active resilience.faults injection
            # (baked into the traced train_step) is never reused after the
            # plan ends, or vice versa.
            del plan_token
            loss, grads, *counts = self._train_step_out(
                params, x, target, rng)
            with jax.named_scope("optimizer"):
                new_params, new_state = apply_update(
                    params, grads, opt_state
                )
            return (loss, new_params, new_state, *counts)

        compiled = jax.jit(
            whole,
            static_argnums=(5,),
            donate_argnums=(0, 1) if donate else (),
        )
        # The schedule verifier's donation-safety rule reads this to place
        # the donating update event in the step's event graph.
        self._train_step_donate = donate
        shape = self._schedule_shape()

        def step(
            params: Pytree,
            opt_state: Pytree,
            x: Pytree,
            target: Pytree,
            rng: Optional[jax.Array] = None,
        ) -> Tuple[jax.Array, Pytree, Pytree]:
            # Scan-granularity span (see the ``tracer`` field note).
            with self._timeline().span("step", **shape) as span:
                return span.wait(compiled(
                    params, opt_state, x, target, rng, _faults.plan_token()
                ))

        step.megastep = 1  # type: ignore[attr-defined]
        return step

    def _make_apply_update(
        self, optimizer: Any, level: int
    ) -> Callable[[Pytree, Pytree, Pytree], Tuple[Pytree, Pytree]]:
        """The optimizer-apply half of a fused step for ZeRO ``level``:
        the plain whole-tree elementwise update (levels 0 and 3 — at
        level 3 params/grads/state are all in the fsdp storage layout
        and GSPMD keeps the elementwise math sharded end-to-end), or
        the ZeRO-1 shard_map form (each dp lane updates its 1/N_dp flat
        segment, params all-gathered back)."""

        def plain(
            params: Pytree, grads: Pytree, opt_state: Pytree
        ) -> Tuple[Pytree, Pytree]:
            updates, new_state = optimizer.update(grads, opt_state, params)
            new_params = jax.tree_util.tree_map(
                lambda p, u: (p + u).astype(p.dtype), params, updates
            )
            return new_params, new_state

        if level != 1:
            return plain

        def sharded(
            params: Pytree, grads: Pytree, opt_state: Pytree
        ) -> Tuple[Pytree, Pytree]:
            pspecs, sspecs, _, local_update = self._zero_machinery(
                optimizer, params
            )
            fn = _shard_map(
                local_update, self.mesh,
                in_specs=(pspecs, pspecs, sspecs),
                out_specs=(pspecs, sspecs),
            )
            return fn(params, grads, opt_state)

        return sharded

    def _make_megastep(
        self, optimizer: Any, K: int, donate: bool, level: int = 0
    ) -> Callable[..., Tuple[jax.Array, Pytree, Pytree, jax.Array]]:
        """K optimizer steps as one scanned program (see
        :meth:`make_train_step`'s ``megastep`` contract)."""
        from torchgpipe_tpu.utils import tree_finite

        tmap = jax.tree_util.tree_map
        apply_update = self._make_apply_update(optimizer, level)

        def whole(
            params: Pytree,
            opt_state: Pytree,
            x: Pytree,
            target: Pytree,
            rng: Optional[jax.Array],
            plan_token: Optional[int],
        ) -> Tuple[jax.Array, Pytree, Pytree, jax.Array]:
            del plan_token  # static jit-cache key, as in the K=1 step

            def body(carry: Tuple, xs: Tuple) -> Tuple[Tuple, Tuple]:
                p, o = carry
                x_k, tgt_k, k = xs
                key = (
                    jax.random.fold_in(rng, k) if rng is not None else None
                )
                loss, grads = self.train_step(p, x_k, tgt_k, key)
                with jax.named_scope("optimizer"):
                    new_p, new_o = apply_update(p, grads, o)
                # The in-scan skip-step: cover EXACTLY what StepGuard's
                # host-side check covers on the K=1 step's output tuple
                # (loss, new params, new opt state) so megastep(K) is
                # bitwise K guarded steps.  jnp.where(True, a, b) IS a —
                # applied steps pass through untouched.
                ok = tree_finite((loss, new_p, new_o))
                new_p = tmap(lambda a, b: jnp.where(ok, a, b), new_p, p)
                new_o = tmap(lambda a, b: jnp.where(ok, a, b), new_o, o)
                return (new_p, new_o), (loss, ok)

            (new_p, new_o), (losses, finite) = lax.scan(
                body, (params, opt_state), (x, target, jnp.arange(K))
            )
            return losses, new_p, new_o, finite

        compiled = jax.jit(
            whole,
            static_argnums=(5,),
            donate_argnums=(0, 1) if donate else (),
        )
        self._train_step_donate = donate
        shape = dict(self._schedule_shape(), steps=K)

        def step(
            params: Pytree,
            opt_state: Pytree,
            x: Pytree,
            target: Pytree,
            rng: Optional[jax.Array] = None,
        ) -> Tuple[jax.Array, Pytree, Pytree, jax.Array]:
            for leaf in jax.tree_util.tree_leaves(x):
                if leaf.shape[:1] != (K,):
                    raise ValueError(
                        f"megastep={K} consumes [K, ...]-stacked batches "
                        f"(K steps in one program), got a leading dim of "
                        f"{leaf.shape[0]} — stack K per-step batches with "
                        "jnp.stack, or pass megastep=1"
                    )
                break
            # One span per K-step program (scan granularity).
            with self._timeline().span("megastep", **shape) as span:
                return span.wait(compiled(
                    params, opt_state, x, target, rng, _faults.plan_token()
                ))

        step.megastep = K  # type: ignore[attr-defined]
        return step

    def _build_apply(self, with_loss: bool = False) -> Callable:
        n = self.n_stages
        data_spec = self._data_specs()

        # A head built for sharded-logits training (lm_head with
        # gather_logits=False) declares its output sharding; inference
        # gathers it so apply() returns full logits, never one lane's shard.
        out_gather = (
            _declared_axes(self.post, "out_gather") if self.post else []
        )

        def local(params, x_mb, tgt_mb=None):
            stage = lax.axis_index(self.pp_axis)
            if self.pre is not None:
                x_mb = self._apply_pre(params["pre"], x_mb, None, False)
            blocks_in = (
                self._gather_fsdp(params["blocks"])
                if self.fsdp
                else params["blocks"]
            )
            ys = self._local_pipeline(blocks_in, x_mb, None, False)
            outs = self._outputs_from_ticks(ys)  # [m, b_local, ...]
            if with_loss:
                # post runs per micro-batch INSIDE the loss loop, so at
                # most one micro-batch's logits are ever live.
                return self._eval_loss_from_outs(params, outs, tgt_mb, stage)
            if self.post is not None:
                p_post_t = self._tied(
                    params["post"], params.get("pre", ()), self._tie_post
                )
                outs = jax.vmap(
                    lambda mb: self.post.apply(p_post_t, (), mb, rng=None, train=False)[0]
                )(outs)
                for axis, dim in out_gather:
                    outs = all_gather_value(outs, axis, dim)
            # Only the last stage holds real outputs; broadcast over pp.
            masked = jax.tree_util.tree_map(
                lambda a: jnp.where(stage == n - 1, a, jnp.zeros_like(a)), outs
            )
            return jax.tree_util.tree_map(
                lambda a: lax.psum(a, self.pp_axis), masked
            )

        param_specs = {
            "blocks": self._fsdp_specs if self.fsdp else self._blocks_spec
        }
        if self.pre is not None:
            param_specs["pre"] = self._pre_spec
        if self.post is not None:
            param_specs["post"] = self._post_spec
        if self._loss_is_layer:
            param_specs["loss"] = self._loss_spec

        if with_loss:
            mapped = _shard_map(
                local,
                self.mesh,
                in_specs=(param_specs, data_spec, data_spec),
                out_specs=P(),
            )
        else:
            mapped = _shard_map(
                local,
                self.mesh,
                in_specs=(param_specs, data_spec),
                out_specs=data_spec,
            )
        return jax.jit(mapped)

    def _build_apply_interleaved(self, with_loss: bool = False) -> Callable:
        """Forward-only interleaved pipeline (fill-drain over the n·v
        virtual stages, round-robin device mapping) for inference."""
        from torchgpipe_tpu.parallel.interleaved import (
            FWD,
            interleaved_forward_tables,
        )

        n, m, v = self.n_stages, self.chunks, self.virtual_stages
        tb = interleaved_forward_tables(n, m, v)
        S = tb.slots
        data_spec = self._data_specs()
        tmap = jax.tree_util.tree_map
        out_gather = (
            _declared_axes(self.post, "out_gather") if self.post else []
        )
        rows_xs = _interleaved_rows(tb)

        def local(params, x_mb, tgt_mb=None):
            stage = lax.axis_index(self.pp_axis)
            perm_f = [(i, (i + 1) % n) for i in range(n)]
            if self.pre is not None:
                x_mb = self._apply_pre(params["pre"], x_mb, None, False)
            blocks_in = (
                self._gather_fsdp(params["blocks"])
                if self.fsdp
                else params["blocks"]
            )
            params_local = tmap(lambda a: a[0], blocks_in)

            def p_of(c):
                return tmap(
                    lambda a: lax.dynamic_index_in_dim(a, c, 0, keepdims=False),
                    params_local,
                )

            act_spec = tmap(
                lambda a: jax.ShapeDtypeStruct(a.shape[1:], a.dtype), x_mb
            )
            act0 = tmap(lambda s: jnp.zeros(s.shape, s.dtype), act_spec)
            carry0 = dict(
                act=act0,
                inbox=tmap(
                    lambda s: jnp.zeros((v * S,) + s.shape, s.dtype), act_spec
                ),
                outs=tmap(
                    lambda s: jnp.zeros((m,) + s.shape, s.dtype), act_spec
                ),
            )

            def tick(carry, rows):
                krow, crow, irow, pkrow, pcrow, pirow = rows
                recv_f = tmap(
                    lambda a: lax.ppermute(a, self.pp_axis, perm_f),
                    carry["act"],
                )
                idx_f, valid_f = _classify_fwd_recv(
                    stage, n, v, S, pkrow, pcrow, pirow
                )
                inbox = _slot_write(carry["inbox"], idx_f, recv_f, valid_f)
                carry = dict(carry, inbox=inbox)
                k, c, i = krow[stage], crow[stage], irow[stage]
                idx = c * S + i % S

                def fwd_branch(cr):
                    first = (stage == 0) & (c == 0)
                    x_f = tmap(
                        lambda inp, r: jnp.where(first, inp, r),
                        _slot_read(x_mb, i),
                        _slot_read(cr["inbox"], idx),
                    )
                    y = self._block_fn_plain(p_of(c), x_f, None, 0.0, False)
                    done = (stage == n - 1) & (c == v - 1)
                    outs = tmap(
                        lambda O, yy: lax.dynamic_update_index_in_dim(
                            O,
                            jnp.where(
                                done,
                                yy,
                                lax.dynamic_index_in_dim(
                                    O, i, 0, keepdims=False
                                ),
                            ),
                            i,
                            0,
                        ),
                        cr["outs"],
                        y,
                    )
                    return dict(cr, act=y, outs=outs)

                carry = lax.cond(
                    k == FWD, fwd_branch, lambda cr: cr, carry
                )
                return carry, ()

            carry, _ = lax.scan(
                _scoped("tick", tick), carry0, rows_xs,
                unroll=self.scan_unroll
            )
            outs = carry["outs"]
            if with_loss:
                # The final chunk's outputs land on stage n-1; the loss
                # masks to that stage exactly like the fill-drain variant,
                # and post runs per micro-batch inside the loss loop.
                return self._eval_loss_from_outs(params, outs, tgt_mb, stage)
            if self.post is not None:
                p_post_t = self._tied(
                    params["post"], params.get("pre", ()), self._tie_post
                )
                outs = jax.vmap(
                    lambda mb: self.post.apply(
                        p_post_t, (), mb, rng=None, train=False
                    )[0]
                )(outs)
                for axis, dim in out_gather:
                    outs = all_gather_value(outs, axis, dim)
            masked = tmap(
                lambda a: jnp.where(stage == n - 1, a, jnp.zeros_like(a)),
                outs,
            )
            return tmap(lambda a: lax.psum(a, self.pp_axis), masked)

        param_specs = {
            "blocks": self._fsdp_specs if self.fsdp else self._blocks_spec
        }
        if self.pre is not None:
            param_specs["pre"] = self._pre_spec
        if self.post is not None:
            param_specs["post"] = self._post_spec
        if self._loss_is_layer:
            param_specs["loss"] = self._loss_spec

        if with_loss:
            mapped = _shard_map(
                local,
                self.mesh,
                in_specs=(param_specs, data_spec, data_spec),
                out_specs=P(),
            )
        else:
            mapped = _shard_map(
                local,
                self.mesh,
                in_specs=(param_specs, data_spec),
                out_specs=data_spec,
            )
        return jax.jit(mapped)

    def _eval_loss_from_outs(
        self, params: Pytree, outs: Pytree, tgt_mb: Pytree, stage: jax.Array
    ) -> jax.Array:
        """Per-micro-batch eval loss INSIDE the mapped program: the loss
        consumes each ``[b_local, ...]`` micro-batch output directly, so
        full-batch logits are never gathered (the train path's memory
        discipline carried over to eval; decomposability is declared by
        ``loss_reduction``)."""
        n = self.n_stages
        m = self.chunks
        tmap = jax.tree_util.tree_map
        p_loss = self._tied(
            params["loss"] if self._loss_is_layer else (),
            params.get("pre", ()),
            self._tie_loss,
        )
        p_post_t = (
            self._tied(params["post"], params.get("pre", ()), self._tie_post)
            if self.post is not None
            else ()
        )

        def mb_loss(i, acc):
            y_i = tmap(
                lambda a: lax.dynamic_index_in_dim(a, i, 0, keepdims=False),
                outs,
            )
            if self.post is not None:
                y_i, _ = self.post.apply(
                    p_post_t, (), y_i, rng=None, train=False
                )
            t_i = tmap(
                lambda a: lax.dynamic_index_in_dim(a, i, 0, keepdims=False),
                tgt_mb,
            )
            l_i = self._loss_call(p_loss, y_i, t_i, train=False).astype(
                jnp.float32
            )
            return acc + (l_i / m if self.loss_reduction == "mean" else l_i)

        loss = lax.fori_loop(0, m, mb_loss, jnp.float32(0.0))
        loss = jnp.where(stage == n - 1, loss, 0.0)
        loss = lax.psum(loss, self.pp_axis)
        # Data-parallel lanes each saw their own batch shard.
        for ax in (self.dp_axis, self.ep_axis, self.sp_axis):
            if ax:
                red = (
                    lax.pmean if self.loss_reduction == "mean" else lax.psum
                )
                loss = red(loss, ax)
        return loss

    def eval_loss(self, params: Pytree, x: Pytree, target: Pytree) -> jax.Array:
        """Loss on a mini-batch WITHOUT gradients (eval semantics:
        ``train=False`` through every layer — dropout off, checkpoint
        bypassed — like the reference's eval-mode ``checkpoint_stop=0``,
        reference gpipe.py:360-367).

        Works with plain ``loss_fn`` callables and with parametric loss
        layers (whose loss value cannot be recomputed from :meth:`apply`'s
        outputs alone when ``post=None`` hides no logits — e.g. the
        chunked-vocab CE never materializes them).

        With a decomposable loss (``loss_reduction`` 'mean'/'sum') the
        loss runs per-micro-batch INSIDE the mapped program, so full-batch
        logits are never gathered (matching the train path's memory
        discipline); ``loss_reduction=None`` falls back to the gathered
        host-side computation.  Ragged batches take the gathered fallback
        too (``apply`` pads/slices, then the loss sees exactly the real
        rows) — exact, at full-batch-logit memory cost."""
        self._check_params(params)
        pad = self._check_batch(x, target, ragged_ok=True)
        if self.loss_reduction is None or pad:
            out = self.apply(params, x)
            return self._loss_call(
                self._tied(
                    params["loss"] if self._loss_is_layer else (),
                    params.get("pre", ()),
                    self._tie_loss,
                ),
                out, target, train=False,
            )
        if self.fsdp:
            self._ensure_fsdp(params["blocks"])
        token = self._fault_token_checked()
        if token not in self._eval_fns:
            self._eval_fns[token] = (
                self._build_apply_interleaved(with_loss=True)
                if self.schedule == "interleaved"
                else self._build_apply(with_loss=True)
            )
        x_mb = microbatch.scatter_stacked(x, self.chunks)
        tgt_mb = microbatch.scatter_stacked(target, self.chunks)
        with self._annotate_cell_failure(params, x_mb):
            return self._eval_fns[token](params, x_mb, tgt_mb)

    def apply(self, params: Pytree, x: Pytree) -> Pytree:
        """Pipelined inference forward; returns gathered outputs
        ``[B, ...]``.  Ragged batches are edge-padded through the pipeline
        and the padding rows sliced off the gathered output — exact for
        inference since no loss is involved."""
        self._check_params(params)
        pad = self._check_batch(x, ragged_ok=True)
        if self.fsdp:
            self._ensure_fsdp(params["blocks"])
        token = self._fault_token_checked()
        if token not in self._apply_fns:
            self._apply_fns[token] = (
                self._build_apply_interleaved()
                if self.schedule == "interleaved"
                else self._build_apply()
            )
        b_real = microbatch.batch_size(x)
        x_mb = microbatch.scatter_stacked(_pad_batch(x, pad), self.chunks)
        with self._annotate_cell_failure(params, x_mb):
            out_mb = self._apply_fns[token](params, x_mb)
        out = microbatch.gather_stacked(out_mb)
        if pad:
            out = jax.tree_util.tree_map(lambda a: a[:b_real], out)
        return out


def _zeros(spec: Spec) -> Pytree:
    return jax.tree_util.tree_map(lambda s: jnp.zeros(s.shape, s.dtype), spec)


def make_mesh(
    n_stages: int,
    dp: int = 1,
    sp: int = 1,
    *,
    tp: int = 1,
    ep: int = 1,
    devices: Optional[Sequence] = None,
) -> Mesh:
    """Build a ('pp', 'dp'[, 'ep'][, 'sp'][, 'tp']) mesh from the devices.

    Axis order is bandwidth-aware: ``tp`` innermost (its two psums per block
    are the chattiest collective — they get the fastest ICI neighbors), then
    ``sp`` (one K/V block per ring step), ``ep`` (one all_to_all pair per MoE
    layer), then ``dp`` (one gradient reduction per step) and ``pp``
    outermost (one activation hand-off per tick, smallest payloads —
    cross-host DCN-tolerant).  Axes of size 1 are omitted except ``pp`` and
    ``dp``, which existing callers rely on.
    """
    if devices is None:
        devices = jax.devices()
    need = n_stages * dp * sp * tp * ep
    if len(devices) < need:
        raise ValueError(f"need {need} devices, have {len(devices)}")
    dims = [("pp", n_stages), ("dp", dp), ("ep", ep), ("sp", sp), ("tp", tp)]
    keep = [
        (name, size)
        for name, size in dims
        if size > 1 or name in ("pp", "dp")
    ]
    arr = np.array(devices[:need]).reshape([s for _, s in keep])
    return Mesh(arr, tuple(n for n, _ in keep))
