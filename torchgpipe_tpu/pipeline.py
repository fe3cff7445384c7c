"""MPMD pipeline engine: per-stage compiled programs + clock-cycle scheduling.

TPU-native re-design of the reference engine (reference:
torchgpipe/pipeline.py:49-249).  The reference needs worker threads
(worker.py:94-151), CUDA copy streams (gpipe.py:316-328) and autograd-graph
surgery (dependency.py, copy.py) because eager PyTorch has no other way to
overlap copy with compute and to order backward work.  Under JAX none of that
machinery survives:

* Each stage is a set of XLA-compiled callables pinned to a device; JAX's
  async dispatch queues work on every device while the Python scheduler runs
  ahead — this *replaces* the worker-thread pool (SURVEY.md §2.3).
* Stage hand-off is ``jax.device_put`` device-to-device (ICI on TPU) issued
  asynchronously — replacing ``Copy``/``Wait`` stream surgery.
* Backward ordering is not enforced through phony autograd edges
  (dependency.py:12-48) but by the scheduler itself: the backward schedule is
  the exact reverse of the forward clock cycles, which yields the same
  micro-batch-i-before-i-1 order the reference's ``depend`` fences create
  (pipeline.py:128-132).
* Checkpointed cells run a residual-free forward; during backward the
  scheduler issues a vjp-producing recompute *before* applying the arriving
  cotangent — recompute-ahead, as in reference checkpoint.py:1-19.

The engine supports arbitrary heterogeneous stages (any balance), ragged
micro-batches, cross-stage skip routing, and stateful layers.  For
homogeneous stacked stages inside one jitted program, see
:mod:`torchgpipe_tpu.spmd` — the fully-compiled SPMD engine.
"""

from __future__ import annotations

import contextlib
from typing import Any, Callable, Dict, Iterator, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp

from torchgpipe_tpu import checkpoint as ckpt
from torchgpipe_tpu import microbatch
from torchgpipe_tpu.auxgrad import aux_scale
from torchgpipe_tpu.layers import Layer, apply_layer
from torchgpipe_tpu.resilience import faults as _faults
from torchgpipe_tpu.skip.layout import SkipLayout

Pytree = Any


def one_f1b_orders(m: int, n: int) -> List[List[Tuple[str, int]]]:
    """Per-stage 1F1B (PipeDream-flush) op order: stage ``j`` warms up with
    ``min(m, n - j)`` forwards, then strictly alternates bwd/fwd, then
    drains backwards.  The ONE source of the schedule order — dispatched by
    :meth:`Pipeline.run_train_1f1b` and projected by
    :func:`torchgpipe_tpu.utils.tracing.simulate_pipeline`."""
    orders: List[List[Tuple[str, int]]] = []
    for j in range(n):
        warm = min(m, n - j)
        ops: List[Tuple[str, int]] = [("fwd", i) for i in range(warm)]
        nf, nb = warm, 0
        while nb < m:
            ops.append(("bwd", nb))
            nb += 1
            if nf < m:
                ops.append(("fwd", nf))
                nf += 1
        orders.append(ops)
    return orders


def clock_cycles(m: int, n: int) -> Iterator[List[Tuple[int, int]]]:
    """Generate the GPipe fill-drain schedule.

    Reference: torchgpipe/pipeline.py:49-65.  Cycle ``k`` runs cells
    ``(i, j)`` with ``i + j == k``: micro-batch ``i`` on stage ``j``.
    (A native enumerator existed through round 2 but measured SLOWER than
    this comprehension at every m*n — ctypes marshalling of the tuple list
    dominates — so it was removed; the native library keeps only the
    block-partition solver, where the win is 90-175x measured.)
    """
    for k in range(m + n - 1):
        yield [(k - j, j) for j in range(max(0, k - m + 1), min(k + 1, n))]


def _host_memory_kind(device: Any) -> Optional[str]:
    """The host-side memory kind addressable by ``device`` (``pinned_host``
    on TPU; ``None`` when the device's default memory IS host memory, e.g.
    the CPU backend, where offloading would be a no-op copy)."""
    try:
        default = device.default_memory().kind
        kinds = [m.kind for m in device.addressable_memories()]
    except Exception:  # pragma: no cover - backends without memories API
        return None
    for kind in ("pinned_host", "unpinned_host"):
        if kind in kinds and kind != default:
            return kind
    return None


def _to_memory(tree: Pytree, device: Any, kind: Optional[str]) -> Pytree:
    """device_put every array leaf of ``tree`` (vjp closures included) to
    ``device`` in memory ``kind`` (``None`` = the device's default HBM)."""
    sharding = jax.sharding.SingleDeviceSharding(device, memory_kind=kind)
    return jax.tree_util.tree_map(
        lambda a: jax.device_put(a, sharding) if hasattr(a, "dtype") else a,
        tree,
    )


def _transfer(x: Pytree, device: Any) -> Pytree:
    """Async device-to-device move (ICI on TPU); no-op if already there."""
    return jax.device_put(x, device)


def _reject_nan_plan(where: str) -> None:
    """Fault-injection coverage guard: paths WITHOUT a per-cell poisoning
    hook must refuse an active ``nan_at`` plan loudly — a chaos test that
    silently injects nothing would certify recovery code that never ran."""
    plan = _faults.active_plan()
    if plan is not None and plan.nan_at is not None:
        raise NotImplementedError(
            f"faults.inject(nan_at=...) is not supported under {where}; "
            "use the per-cell scheduler (fused=False) or the SPMD "
            "fill_drain schedule"
        )


@contextlib.contextmanager
def _cell_context(j: int, i: int, phase: str) -> Iterator[None]:
    """Annotate any exception escaping a cell with the offending stage.

    The reference propagates the first exception out of its worker threads
    with the traceback preserved (reference: torchgpipe/pipeline.py:222-249,
    worker.py:81-88) but leaves the user to guess which partition raised;
    here the original exception type/traceback still propagate — the
    schedule simply stops dispatching (early-stop) — plus a note naming the
    cell.
    """
    try:
        yield
    except Exception as e:  # noqa: BLE001 — annotate and re-raise as-is
        if hasattr(e, "add_note"):
            e.add_note(
                f"raised in pipeline stage {j}, micro-batch {i} "
                f"({phase} schedule)"
            )
        raise


class StageExec:
    """Compiled execution variants for one pipeline stage."""

    def __init__(
        self,
        index: int,
        layers: Sequence[Layer],
        layer_offset: int,
        device: Any,
        layout: SkipLayout,
    ) -> None:
        self.index = index
        self.layers = list(layers)
        self.layer_offset = layer_offset
        self.device = device
        self.ext_stash_keys = layout.external_stashes(index)
        self.ext_pop_keys = layout.external_pops(index)
        self._layout = layout

        stage_apply = self._make_stage_apply()
        # Raw (unjitted) variant for the fused single-device engine path.
        self.stage_apply = stage_apply

        def diff_fwd(params, state, x, skips_in, rng):
            def g(p, xx, sk):
                y, ext, new_state = stage_apply(p, state, xx, sk, rng, True)
                return (y, ext), new_state

            (y, ext), pull, new_state = jax.vjp(g, params, x, skips_in, has_aux=True)
            return y, ext, new_state, pull

        def plain_fwd_train(params, state, x, skips_in, rng):
            return stage_apply(params, state, x, skips_in, rng, True)

        def plain_fwd_eval(params, state, x, skips_in, rng):
            return stage_apply(params, state, x, skips_in, rng, False)

        self.fwd_vjp = self._jit_with_phase(diff_fwd)
        self.fwd_recompute = self._jit_with_phase(diff_fwd, recomputing=True)
        self.fwd_ckpt = self._jit_with_phase(plain_fwd_train, checkpointing=True)
        self.fwd_train = self._jit_with_phase(plain_fwd_train)
        self.fwd_eval = self._jit_with_phase(plain_fwd_eval)
        # Buffer donation on accelerators: the vjp closure (arg 0 of bwd) is
        # consumed exactly once — donating lets XLA free/reuse its residual
        # HBM as the backward consumes it; likewise the old gradient
        # accumulator, so accumulation never holds two full gradient
        # buffers per stage.  XLA:CPU ignores donation (and warns), so
        # CPU-placed stages skip it — gate on THIS stage's device, not the
        # process default backend (stages are explicitly placeable).  A
        # memory optimization only, never a semantic difference.
        donate = (0,) if getattr(device, "platform", "cpu") != "cpu" else ()
        self.bwd = jax.jit(lambda pull, cot: pull(cot), donate_argnums=donate)
        self.accum = jax.jit(
            lambda a, b: jax.tree_util.tree_map(jnp.add, a, b),
            donate_argnums=donate,
        )

    @staticmethod
    def _jit_with_phase(
        fn: Callable,
        *,
        checkpointing: bool = False,
        recomputing: bool = False,
    ) -> Callable:
        # aux_s: runtime weight for injected auxiliary gradients (MoE
        # balance) in this cell — the engine passes the exact 1/m of the
        # current run (micro-batch count may differ from `chunks` for
        # ragged batches), so the injected penalty is always a true
        # micro-batch mean (torchgpipe_tpu.auxgrad).
        def wrapped(params, state, x, skips_in, rng, aux_s):
            with ckpt.phase(checkpointing=checkpointing, recomputing=recomputing):
                with aux_scale(aux_s):
                    return fn(params, state, x, skips_in, rng)

        return jax.jit(wrapped)

    def _make_stage_apply(self) -> Callable:
        layers = self.layers
        offset = self.layer_offset
        ext_stash_keys = tuple(self.ext_stash_keys)

        def stage_apply(params, state, x, skips_in, rng, train):
            skips = dict(skips_in)
            new_states = []
            for li, layer in enumerate(layers):
                lrng = (
                    jax.random.fold_in(rng, offset + li) if rng is not None else None
                )
                x, ns = apply_layer(
                    layer, params[li], state[li], x, skips, rng=lrng, train=train
                )
                new_states.append(ns)
            ext = {k: skips[k] for k in ext_stash_keys}
            return x, ext, new_states

        return stage_apply


class LossGradRunner:
    """Cached jitted (gathered loss, per-micro-batch cotangents, aux) runner.

    Shared by the single-process engine and the distributed last rank so the
    hot path never re-traces (cache keyed by chunk sizes / structure /
    loss_fn; bounded so fresh lambdas can't grow it without limit).
    """

    def __init__(self, maxsize: int = 16) -> None:
        self._cache: Dict = {}
        self._maxsize = maxsize

    def __call__(
        self,
        outs: List[Pytree],
        target: Pytree,
        loss_fn: Any,
        loss_params: Optional[Pytree] = None,
    ) -> Tuple[jax.Array, List[Pytree], Pytree]:
        sizes = tuple(
            jax.tree_util.tree_leaves(o)[0].shape[0] for o in outs
        )
        treedef = jax.tree_util.tree_structure(outs[0])
        # A parametric loss is a Layer (frozen dataclass whose meta dict is
        # unhashable) — key by identity; plain callables key by value.
        key = (
            sizes,
            treedef,
            id(loss_fn) if loss_params is not None else loss_fn,
            loss_params is not None,
        )
        if key not in self._cache:
            while len(self._cache) >= self._maxsize:
                self._cache.pop(next(iter(self._cache)))

            if loss_params is not None:
                # Parametric loss layer: loss_fn is a Layer whose params
                # are differentiated alongside the outputs (the big-vocab
                # fused head+CE path — see transformer.chunked_lm_loss).

                def gathered_loss_p(outs_list, lp, tgt):
                    out = microbatch.gather(outs_list)
                    val, st = loss_fn.apply(lp, (), (out, tgt), rng=None,
                                            train=True)
                    if jax.tree_util.tree_leaves(st):
                        raise ValueError(
                            f"parametric loss layer {loss_fn.name!r} must "
                            "be stateless (its state updates would be "
                            "silently dropped)"
                        )
                    return val, None

                def run_p(outs_list, lp, tgt):
                    (loss, aux), (gouts, glp) = jax.value_and_grad(
                        gathered_loss_p, argnums=(0, 1), has_aux=True
                    )(outs_list, lp, tgt)
                    return loss, gouts, glp, aux

                self._cache[key] = jax.jit(run_p)
            else:

                def gathered_loss(outs_list, tgt):
                    out = microbatch.gather(outs_list)
                    res = loss_fn(out, tgt)
                    if isinstance(res, tuple):
                        return res[0], res[1]
                    return res, None

                def run(outs_list, tgt):
                    (loss, aux), gouts = jax.value_and_grad(
                        gathered_loss, has_aux=True
                    )(outs_list, tgt)
                    return loss, gouts, aux

                self._cache[key] = jax.jit(run)

        if loss_params is not None:
            return self._cache[key](outs, loss_params, target)
        return self._cache[key](outs, target)


class Pipeline:
    """Schedules micro-batches over stages following GPipe fill-drain.

    Reference: torchgpipe/pipeline.py:68-115 (``Pipeline.run``), with
    forward *and* backward as explicit schedules (the reference's backward
    rides the autograd engine, SURVEY.md §3.3).
    """

    def __init__(
        self,
        stages: Sequence[StageExec],
        layout: SkipLayout,
        tracer: Any = None,
        remat_policy: Any = None,
    ) -> None:
        self.stages = list(stages)
        self.layout = layout
        self.tracer = tracer  # torchgpipe_tpu.utils.tracing.Timeline or None
        # Optional jax.checkpoint policy for the FUSED path's per-cell
        # remat (GPipe(fused=True, remat_policy=...)); the per-cell
        # scheduler's checkpointed cells keep no residuals at all.
        self.remat_policy = remat_policy
        self._loss_grad = LossGradRunner()
        self._fused: Dict = {}  # fused single-device step cache
        self._loss_jits: Dict = {}  # 1F1B per-micro-batch loss/sum cache

    # ------------------------------------------------------------------ #
    # forward-only (inference / no-grad)                                 #
    # ------------------------------------------------------------------ #

    def run_forward(
        self,
        params: Sequence[Pytree],
        states: Sequence[Pytree],
        mbatches: List[Pytree],
        rng: Optional[jax.Array],
        train: bool,
    ) -> Tuple[List[Pytree], List[Pytree]]:
        """Run all micro-batches through all stages without building vjps."""
        n = len(self.stages)
        m = len(mbatches)
        acts: Dict[int, Pytree] = {}
        skip_vals: Dict = {}
        cur_states = list(states)
        outs: List[Pytree] = [None] * m

        for cycle in clock_cycles(m, n):
            for i, j in cycle:
                stage = self.stages[j]
                x = mbatches[i] if j == 0 else acts.pop(i)
                x = _transfer(x, stage.device)
                x = _faults.corrupt_cell_input(j, i, x)
                skips_in = {k: skip_vals.pop((i, k)) for k in stage.ext_pop_keys}
                rng_i = jax.random.fold_in(rng, i) if rng is not None else None
                fwd = stage.fwd_train if train else stage.fwd_eval
                with _cell_context(j, i, "forward"):
                    y, ext, new_state = fwd(
                        params[j], cur_states[j], x, skips_in, rng_i, 1.0 / m
                    )
                if self.tracer is not None:
                    self.tracer.record("fwd", j, i, y,
                                       settle=_faults.cell_delay_s(j))
                cur_states[j] = new_state
                for k, v in ext.items():
                    dst = self.stages[self.layout.pop_stage(k)].device
                    skip_vals[(i, k)] = _transfer(v, dst)
                if j == n - 1:
                    outs[i] = y
                else:
                    acts[i] = y
        return outs, cur_states

    # ------------------------------------------------------------------ #
    # forward + backward (training)                                      #
    # ------------------------------------------------------------------ #

    def run_train(
        self,
        params: Sequence[Pytree],
        states: Sequence[Pytree],
        mbatches: List[Pytree],
        target: Pytree,
        loss_fn: Any,
        rng: Optional[jax.Array],
        checkpoint_stop: int,
        loss_params: Optional[Pytree] = None,
        offload: bool = False,
    ) -> Tuple[jax.Array, List[Pytree], List[Pytree], List[Pytree], Pytree]:
        """Full pipelined forward, loss, and backward.

        Returns ``(loss, grads_per_stage, new_states, aux)`` where ``aux`` is
        whatever extra output ``loss_fn`` returns (or None); with
        ``loss_params`` set (parametric loss layer),
        ``(loss, grads_per_stage, loss_grads, new_states, aux)``.

        ``offload`` (``GPipe(checkpoint='offload')``): each cell's vjp
        residual closure — an explicit program output in this engine — is
        moved to HOST memory (``pinned_host``) right after its forward and
        brought back just before its backward, so between the two
        schedules the device holds no residuals at all: zero recompute
        (the 'never' schedule) at 'always'-like device memory.  The
        device_puts are async like every stage hand-off; on a host-backed
        device (CPU tests) the move is skipped — residuals already live
        in host memory.
        """
        n = len(self.stages)
        m = len(mbatches)
        host_kinds = (
            {j: _host_memory_kind(s.device) for j, s in enumerate(self.stages)}
            if offload
            else {}
        )
        if offload:
            for j, kind in host_kinds.items():
                dev = self.stages[j].device
                if kind is None and getattr(dev, "platform", "cpu") != "cpu":
                    # Degrading SILENTLY to 'never' (all residuals
                    # device-resident) on an accelerator would reproduce
                    # the exact OOM this mode exists to dodge — say so
                    # loudly.  (CPU stages skip the move by design: their
                    # default memory IS host memory.)
                    import warnings

                    warnings.warn(
                        f"checkpoint='offload': stage {j}'s device "
                        f"({dev.platform}) exposes no host memory kind — "
                        "residuals will stay DEVICE-resident ('never'-"
                        "class HBM use, zero offloading).  This backend "
                        "lacks the memories API the offload mode needs",
                        stacklevel=3,
                    )
                    break

        acts: Dict[int, Pytree] = {}
        outs: List[Pytree] = [None] * m
        pulls: Dict[Tuple[int, int], Any] = {}
        saved: Dict[Tuple[int, int], Any] = {}
        skip_vals: Dict = {}
        cur_states = list(states)

        # ---- forward schedule -------------------------------------------------
        for cycle in clock_cycles(m, n):
            for i, j in cycle:
                stage = self.stages[j]
                x = mbatches[i] if j == 0 else acts.pop(i)
                x = _transfer(x, stage.device)
                # Deterministic chaos hook (torchgpipe_tpu.resilience.faults):
                # poisons exactly the planned (stage, micro-batch) cell's
                # input; no-op unless a plan is active.
                x = _faults.corrupt_cell_input(j, i, x)
                skips_in = {k: skip_vals.pop((i, k)) for k in stage.ext_pop_keys}
                rng_i = jax.random.fold_in(rng, i) if rng is not None else None
                checkpointed = i < checkpoint_stop
                state_in = cur_states[j]
                with _cell_context(j, i, "forward"):
                    if checkpointed:
                        y, ext, new_state = stage.fwd_ckpt(
                            params[j], state_in, x, skips_in, rng_i, 1.0 / m
                        )
                        saved[(i, j)] = (x, skips_in, state_in, rng_i)
                    else:
                        y, ext, new_state, pull = stage.fwd_vjp(
                            params[j], state_in, x, skips_in, rng_i, 1.0 / m
                        )
                        if offload and host_kinds[j] is not None:
                            pull = _to_memory(pull, stage.device, host_kinds[j])
                        pulls[(i, j)] = pull
                if self.tracer is not None:
                    self.tracer.record("fwd", j, i, y,
                                       settle=_faults.cell_delay_s(j))
                cur_states[j] = new_state
                for k, v in ext.items():
                    dst = self.stages[self.layout.pop_stage(k)].device
                    skip_vals[(i, k)] = _transfer(v, dst)
                if j == n - 1:
                    outs[i] = y
                else:
                    acts[i] = y

        # ---- loss + output cotangents ----------------------------------------
        if loss_params is not None:
            loss, gys_last, loss_grads, aux = self._loss_and_grads(
                outs, target, loss_fn, loss_params
            )
        else:
            loss, gys_last, aux = self._loss_and_grads(outs, target, loss_fn)
        if self.tracer is not None:
            # Record the gathered-loss barrier as its OWN span (mb -1):
            # under sync=True this blocks here, so the loss work is not
            # silently absorbed into the first backward cell's measured
            # time (obs.reconcile would read that as stage imbalance).
            self.tracer.record("loss", n - 1, -1, (loss, gys_last))

        # ---- backward schedule (reverse clock cycles) ------------------------
        gys: Dict[Tuple[int, int], Pytree] = {
            (i, n - 1): gys_last[i] for i in range(m)
        }
        gskips: Dict = {}
        acc: List[Optional[Pytree]] = [None] * n

        order = [
            (i, j)
            for cycle in reversed(list(clock_cycles(m, n)))
            for i, j in reversed(cycle)
        ]

        def _fetch_pull(cell: Tuple[int, int]) -> Any:
            """Pop a cell's stored vjp closure, bringing host-offloaded
            residuals back to the stage device (async device_put)."""
            i_, j_ = cell
            pull = pulls.pop(cell)
            if offload and host_kinds[j_] is not None:
                pull = _to_memory(pull, self.stages[j_].device, None)
            return pull

        prefetched: Dict[Tuple[int, int], Any] = {}
        for idx, (i, j) in enumerate(order):
            stage = self.stages[j]
            with _cell_context(j, i, "backward"):
                if (i, j) in saved:
                    x, skips_in, state_in, rng_i = saved.pop((i, j))
                    # Recompute-ahead: rebuild the vjp before consuming
                    # the cotangent (reference checkpoint.py:1-19).
                    _, _, _, pull = stage.fwd_recompute(
                        params[j], state_in, x, skips_in, rng_i, 1.0 / m
                    )
                else:
                    pull = prefetched.pop((i, j), None)
                    if pull is None:
                        pull = _fetch_pull((i, j))
                if offload and idx + 1 < len(order):
                    # ONE-cell prefetch: issue the next cell's
                    # host-to-device residual copy now, so it overlaps
                    # this cell's backward compute instead of stalling
                    # the schedule (mirrors the forward's async
                    # stage-to-stage _transfer hand-offs).  Exactly one
                    # cell deep on purpose — each extra cell of depth
                    # costs a full cell's residuals in peak HBM.
                    nxt = order[idx + 1]
                    if nxt in pulls and nxt not in prefetched:
                        prefetched[nxt] = _fetch_pull(nxt)
                gy = gys.pop((i, j))
                gext = {k: gskips.pop((i, k)) for k in stage.ext_stash_keys}
                gparams, gx, gsk_in = stage.bwd(pull, (gy, gext))
            if self.tracer is not None:
                # Block on the WHOLE cell output (param grads included):
                # gx alone is None/trivial at stage 0, which would let
                # that stage's backward work escape a sync=True
                # measurement — obs.reconcile would then see a fake
                # stage imbalance.
                self.tracer.record("bwd", j, i, (gparams, gx),
                                   settle=_faults.cell_delay_s(j))
            acc[j] = gparams if acc[j] is None else stage.accum(acc[j], gparams)
            if j > 0:
                gys[(i, j - 1)] = _transfer(gx, self.stages[j - 1].device)
            for k, g in gsk_in.items():
                dst = self.stages[self.layout.stash_stage(k)].device
                gskips[(i, k)] = _transfer(g, dst)

        if loss_params is not None:
            return loss, acc, loss_grads, cur_states, aux
        return loss, acc, cur_states, aux

    # ------------------------------------------------------------------ #
    # 1F1B (PipeDream-flush) schedule                                    #
    # ------------------------------------------------------------------ #

    def run_train_1f1b(
        self,
        params: Sequence[Pytree],
        states: Sequence[Pytree],
        mbatches: List[Pytree],
        target_mbs: List[Pytree],
        loss_fn: Any,
        rng: Optional[jax.Array],
        checkpoint_stop: int,
        loss_weights: Sequence[float],
    ) -> Tuple[jax.Array, List[Pytree], List[Pytree], List[Pytree], Pytree]:
        """One-forward-one-backward schedule (no reference counterpart —
        GPipe fill-drain is the reference's only schedule, pipeline.py:49-65).

        Each stage runs a bounded number of warm-up forwards then alternates
        backward/forward, so at most ``n_stages - j`` micro-batches are
        in-flight per stage instead of all ``m`` — the activation-memory
        profile of PipeDream-flush.  Requires a per-micro-batch decomposable
        loss: the engine computes ``loss_i = w_i * loss_fn(out_i, tgt_i)``
        and seeds each micro-batch's backward as soon as its forward leaves
        the last stage (``loss_weights`` carry the mean/sum decomposition).

        Correctness does not depend on the dispatch order (data dependencies
        order the device work); the order shapes per-device memory and
        overlap.  Returns ``(loss, grads, new_states, aux_list)`` where
        ``aux_list`` holds per-micro-batch aux values (or None).
        """
        n = len(self.stages)
        m = len(mbatches)

        orders = one_f1b_orders(m, n)

        acts: Dict[Tuple[int, int], Pytree] = {}  # activation produced by (i, j)
        gys: Dict[Tuple[int, int], Pytree] = {}  # cotangent arriving at (i, j)
        pulls: Dict[Tuple[int, int], Any] = {}
        saved: Dict[Tuple[int, int], Any] = {}
        skip_vals: Dict = {}
        gskips: Dict = {}
        cur_states = list(states)
        acc: List[Optional[Pytree]] = [None] * n
        losses: List[Optional[jax.Array]] = [None] * m
        auxes: List[Any] = [None] * m

        def fwd_ready(i: int, j: int) -> bool:
            return j == 0 or (i, j - 1) in acts

        def bwd_ready(i: int, j: int) -> bool:
            return (i, j) in gys

        def do_fwd(i: int, j: int) -> None:
            stage = self.stages[j]
            x = mbatches[i] if j == 0 else acts.pop((i, j - 1))
            x = _transfer(x, stage.device)
            x = _faults.corrupt_cell_input(j, i, x)
            skips_in = {k: skip_vals.pop((i, k)) for k in stage.ext_pop_keys}
            rng_i = jax.random.fold_in(rng, i) if rng is not None else None
            state_in = cur_states[j]
            with _cell_context(j, i, "1F1B forward"):
                if i < checkpoint_stop:
                    y, ext, new_state = stage.fwd_ckpt(
                        params[j], state_in, x, skips_in, rng_i, 1.0 / m
                    )
                    saved[(i, j)] = (x, skips_in, state_in, rng_i)
                else:
                    y, ext, new_state, pull = stage.fwd_vjp(
                        params[j], state_in, x, skips_in, rng_i, 1.0 / m
                    )
                    pulls[(i, j)] = pull
            if self.tracer is not None:
                self.tracer.record("fwd", j, i, y,
                                       settle=_faults.cell_delay_s(j))
            cur_states[j] = new_state
            for k, v in ext.items():
                dst = self.stages[self.layout.pop_stage(k)].device
                skip_vals[(i, k)] = _transfer(v, dst)
            if j == n - 1:
                # Loss + this micro-batch's output cotangent, immediately.
                loss_i, gy, aux = self._mb_loss(
                    y, _transfer(target_mbs[i], stage.device),
                    loss_weights[i], loss_fn,
                )
                if self.tracer is not None:
                    # Own span (the fill-drain gathered-loss treatment,
                    # per micro-batch here): under sync=True the loss
                    # work blocks HERE instead of inflating the next
                    # recorded backward cell's measured duration.
                    self.tracer.record("loss", j, i, (loss_i, gy))
                losses[i] = loss_i
                auxes[i] = aux
                gys[(i, j)] = gy
            else:
                acts[(i, j)] = y

        def do_bwd(i: int, j: int) -> None:
            stage = self.stages[j]
            with _cell_context(j, i, "1F1B backward"):
                if (i, j) in saved:
                    x, skips_in, state_in, rng_i = saved.pop((i, j))
                    _, _, _, pull = stage.fwd_recompute(
                        params[j], state_in, x, skips_in, rng_i, 1.0 / m
                    )
                else:
                    pull = pulls.pop((i, j))
                gy = gys.pop((i, j))
                gext = {k: gskips.pop((i, k)) for k in stage.ext_stash_keys}
                gparams, gx, gsk_in = stage.bwd(pull, (gy, gext))
            if self.tracer is not None:
                # Block on the WHOLE cell output (param grads included):
                # gx alone is None/trivial at stage 0, which would let
                # that stage's backward work escape a sync=True
                # measurement — obs.reconcile would then see a fake
                # stage imbalance.
                self.tracer.record("bwd", j, i, (gparams, gx),
                                   settle=_faults.cell_delay_s(j))
            acc[j] = gparams if acc[j] is None else stage.accum(acc[j], gparams)
            if j > 0:
                gys[(i, j - 1)] = _transfer(gx, self.stages[j - 1].device)
            for k, g in gsk_in.items():
                dst = self.stages[self.layout.stash_stage(k)].device
                gskips[(i, k)] = _transfer(g, dst)

        # Round-robin dispatch honouring each stage's 1F1B order; an op waits
        # (without blocking other stages) until its Python inputs exist.
        cursors = [0] * n
        total = sum(len(o) for o in orders)
        done = 0
        while done < total:
            progressed = False
            for j in range(n):
                while cursors[j] < len(orders[j]):
                    kind, i = orders[j][cursors[j]]
                    if kind == "fwd" and fwd_ready(i, j):
                        do_fwd(i, j)
                    elif kind == "bwd" and bwd_ready(i, j):
                        do_bwd(i, j)
                    else:
                        break
                    cursors[j] += 1
                    done += 1
                    progressed = True
            if not progressed:
                pending = [
                    (j, orders[j][cursors[j]])
                    for j in range(n)
                    if cursors[j] < len(orders[j])
                ]
                raise RuntimeError(
                    f"1F1B schedule deadlocked; pending {pending}"
                )  # pragma: no cover — schedule generation guarantees progress

        last_dev = self.stages[-1].device
        loss = self._sum_losses([_transfer(l, last_dev) for l in losses])
        return loss, acc, cur_states, auxes

    def _loss_jit(self, key: Any, build: Callable) -> Callable:
        """Bounded cache for the cheap 1F1B loss helpers — separate from
        ``self._fused`` so these never evict expensive whole-step programs."""
        fn = self._loss_jits.get(key)
        if fn is None:
            while len(self._loss_jits) >= 16:
                self._loss_jits.pop(next(iter(self._loss_jits)))
            fn = jax.jit(build())
            self._loss_jits[key] = fn
        return fn

    def _mb_loss(
        self,
        out: Pytree,
        tgt: Pytree,
        weight: float,
        loss_fn: Any,
    ) -> jax.Array:
        """Per-micro-batch weighted loss, cotangent and aux (cached jit)."""
        key = (
            "mb_loss",
            tuple(l.shape for l in jax.tree_util.tree_leaves(out)),
            jax.tree_util.tree_structure(out),
            loss_fn,
        )

        def build():
            def run(out, tgt, w):
                def f(o):
                    res = loss_fn(o, tgt)
                    if isinstance(res, tuple):
                        return w * res[0], res[1]
                    return w * res, None

                (wloss, aux), gy = jax.value_and_grad(f, has_aux=True)(out)
                return wloss, gy, aux

            return run

        fn = self._loss_jit(key, build)
        return fn(out, tgt, jnp.asarray(weight, jnp.float32))

    def _sum_losses(self, losses: Sequence[jax.Array]) -> jax.Array:
        fn = self._loss_jit(
            ("sum_losses", len(losses)), lambda: lambda ls: sum(ls[1:], ls[0])
        )
        return fn(losses)

    # ------------------------------------------------------------------ #
    # fused single-device path                                           #
    # ------------------------------------------------------------------ #

    def single_device(self) -> bool:
        """True when every stage lives on the same physical device."""
        return len({id(s.device) for s in self.stages}) == 1

    def _fused_cell(self, stage: StageExec, checkpointed: bool) -> Callable:
        """One (micro-batch, stage) cell for the fused trace; ``jax.checkpoint``
        reproduces the engine's activation-memory profile per cell."""
        fn = stage.stage_apply

        if not checkpointed:
            return lambda p, s, x, sk, key: fn(p, s, x, sk, key, True)

        # static_argnums: none — train=True baked in; rng may be None, which
        # jax.checkpoint tolerates as a pytree leaf-less input.
        # The checkpointing phase flag is set for the (single) trace of the
        # cell; rematerialization replays the jaxpr at the XLA level, so no
        # separate recompute trace exists for is_recomputing() to observe —
        # phase-sensitive layers (DeferredBatchNorm) are traced once, which
        # is exactly the once-per-mini-batch stats behavior they want.
        def cell(p, s, x, sk, key):
            with ckpt.phase(checkpointing=True):
                return fn(p, s, x, sk, key, True)

        # remat_policy (e.g. checkpoint.policies.save_attn_out) picks which
        # checkpoint-named intermediates each remat'd cell keeps/offloads
        # instead of recomputing — the fused path's point on the
        # recompute/memory curve (docs/tuning.md).
        return jax.checkpoint(cell, policy=self.remat_policy)

    def _fused_forward_loop(
        self,
        cell_of: Callable,
        m: int,
        params: Sequence[Pytree],
        states: Sequence[Pytree],
        mbatches: List[Pytree],
        rng: Optional[jax.Array],
    ) -> Tuple[List[Pytree], List[Pytree], Dict, List[Pytree]]:
        """The micro-batch × stage loop shared by both fused traces.

        ``cell_of(i, j)`` returns the cell callable for micro-batch ``i`` on
        stage ``j`` with signature ``(params, state, x, skips_in, rng)``.
        """
        cur_states = list(states)
        skip_vals: Dict = {}
        outs = []
        for i in range(m):
            rng_i = jax.random.fold_in(rng, i) if rng is not None else None
            x = mbatches[i]
            for j, stage in enumerate(self.stages):
                skips_in = {k: skip_vals.pop((i, k)) for k in stage.ext_pop_keys}
                x, ext, new_state = cell_of(i, j)(
                    params[j], cur_states[j], x, skips_in, rng_i
                )
                cur_states[j] = new_state
                for k, v in ext.items():
                    skip_vals[(i, k)] = v
            outs.append(x)
        return outs, cur_states

    def _fused_jit(
        self,
        kind: str,
        mbatches: List[Pytree],
        extra_key: Any,
        build: Callable,
    ) -> Callable:
        """Bounded cache of fused jitted programs, keyed by micro-batch
        shapes/structure plus ``extra_key``."""
        sizes = tuple(
            tuple(l.shape for l in jax.tree_util.tree_leaves(mb))
            for mb in mbatches
        )
        key = (
            kind, sizes, jax.tree_util.tree_structure(mbatches[0]), extra_key
        )
        fn = self._fused.get(key)
        if fn is None:
            while len(self._fused) >= 8:
                self._fused.pop(next(iter(self._fused)))
            fn = jax.jit(build())
            self._fused[key] = fn
        return fn

    def run_train_fused(
        self,
        params: Sequence[Pytree],
        states: Sequence[Pytree],
        mbatches: List[Pytree],
        target: Pytree,
        loss_fn: Any,
        rng: Optional[jax.Array],
        checkpoint_stop: int,
    ) -> Tuple[jax.Array, List[Pytree], List[Pytree], List[Pytree], Pytree]:
        """Whole training step as ONE compiled XLA program.

        Semantically identical to :meth:`run_train` (same cell math, same
        checkpoint policy via ``jax.checkpoint`` per cell, same gathered
        loss), but with a single device dispatch instead of one per cell:
        XLA schedules the whole step, so host/dispatch latency is paid
        once.  OPT-IN via ``GPipe(fused=True)`` (single-device only) — a
        builder's v5e measurement before PR 1 had the per-cell path 2x
        faster (BENCH_NOTES.md finding #1: JAX's async dispatch already
        keeps the chip saturated, and the monolithic program compiles
        far slower), so nothing auto-fuses.
        """
        _reject_nan_plan("GPipe(fused=True)")
        m = len(mbatches)
        fn = self._fused_jit(
            "train", mbatches, (loss_fn, checkpoint_stop, rng is None),
            lambda: self._build_train_fused(m, loss_fn, checkpoint_stop),
        )
        if rng is None:
            loss, grads, new_states, aux = fn(params, states, mbatches, target)
        else:
            loss, grads, new_states, aux = fn(params, states, mbatches, target, rng)
        return loss, list(grads), list(new_states), aux

    def run_forward_fused(
        self,
        params: Sequence[Pytree],
        states: Sequence[Pytree],
        mbatches: List[Pytree],
        rng: Optional[jax.Array],
        train: bool,
    ) -> Tuple[List[Pytree], List[Pytree]]:
        """Forward-only counterpart of :meth:`run_train_fused`."""
        _reject_nan_plan("GPipe(fused=True)")
        m = len(mbatches)

        def build():
            def cell_of(i, j):
                fn = self.stages[j].stage_apply
                return lambda p, s, x, sk, key: fn(p, s, x, sk, key, train)

            def fwd(params, states, mbatches, rng=None):
                # Same per-cell aux weighting as every other forward path
                # (a user may differentiate through this jit directly).
                with aux_scale(1.0 / m):
                    outs, cur_states = self._fused_forward_loop(
                        cell_of, m, params, states, mbatches, rng
                    )
                return outs, tuple(cur_states)

            return fwd

        fn = self._fused_jit("fwd", mbatches, (train, rng is None), build)
        if rng is None:
            outs, new_states = fn(params, states, mbatches)
        else:
            outs, new_states = fn(params, states, mbatches, rng)
        return list(outs), list(new_states)

    def _build_train_fused(
        self,
        m: int,
        loss_fn: Any,
        checkpoint_stop: int,
    ) -> Callable:
        cells = [
            [self._fused_cell(stage, i < checkpoint_stop) for stage in self.stages]
            for i in range(m)
        ]

        def step(params, states, mbatches, target, rng=None):
            def loss_of(params):
                # Exact per-trace micro-batch count (the fused jit cache is
                # keyed by per-micro-batch shapes, so m is safe to bake).
                with aux_scale(1.0 / m):
                    outs, cur_states = self._fused_forward_loop(
                        lambda i, j: cells[i][j], m, params, states, mbatches, rng
                    )
                out = microbatch.gather(outs)
                res = loss_fn(out, target)
                if isinstance(res, tuple):
                    return res[0], (res[1], cur_states)
                return res, (None, cur_states)

            (loss, (aux, new_states)), grads = jax.value_and_grad(
                loss_of, has_aux=True
            )(tuple(params))
            return loss, grads, tuple(new_states), aux

        return step

    # ------------------------------------------------------------------ #

    def _loss_and_grads(
        self,
        outs: List[Pytree],
        target: Pytree,
        loss_fn: Any,
        loss_params: Optional[Pytree] = None,
    ) -> Tuple[jax.Array, List[Pytree], Pytree]:
        """Gather outputs on the last stage device, compute the loss on the
        full mini-batch (transparency with the un-pipelined model), and split
        the output cotangent back into micro-batch cotangents."""
        last_dev = self.stages[-1].device
        outs = [_transfer(o, last_dev) for o in outs]
        target = _transfer(target, last_dev)
        return self._loss_grad(outs, target, loss_fn, loss_params)
