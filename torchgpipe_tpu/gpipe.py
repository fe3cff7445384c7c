"""GPipe — the user-facing pipeline-parallel wrapper.

TPU-native counterpart of the reference's public API (reference:
torchgpipe/gpipe.py:134-380).  A sequential model (list of
:class:`~torchgpipe_tpu.layers.Layer`) is split by an explicit ``balance``
into stages, each stage's parameters live on its own device, a mini-batch is
scattered into ``chunks`` micro-batches and driven through the GPipe
fill-drain schedule with activation checkpointing.

Differences forced (for the better) by the functional JAX model:

* No module wrapping/mutation: ``GPipe`` holds layer *definitions*; parameters
  are explicit pytrees returned by :meth:`init` and threaded by the caller.
* Training is ``value_and_grad``-shaped rather than ``forward()`` +
  ``loss.backward()``: the engine runs the backward schedule itself
  (the reference rides torch autograd, SURVEY.md §3.3).
* The reference forbids moving a GPipe module off its devices
  (``MOVING_DENIED``, gpipe.py:130, 289-314); here placement is explicit via
  :meth:`place` and simply re-places the pytrees.

Example::

    model = GPipe(layers, balance=[2, 2], chunks=4)
    params, state = model.init(jax.random.PRNGKey(0), in_spec)
    out, _ = model.apply(params, state, x)                      # inference
    loss, grads, state, _ = model.value_and_grad(
        params, state, x, y, loss_fn, rng=step_key)             # training
"""

from __future__ import annotations

from typing import Any, Dict, Iterator, List, Optional, Sequence, Tuple

import jax

from torchgpipe_tpu import microbatch
from torchgpipe_tpu.batchnorm import convert_deferred_batch_norm
from torchgpipe_tpu.checkpoint import CHECKPOINT_MODES, checkpoint_stop
from torchgpipe_tpu.layers import Layer, sequential_init
from torchgpipe_tpu.partition import split_layers, verify_module
from torchgpipe_tpu.pipeline import Pipeline, StageExec
from torchgpipe_tpu.skip import inspect_skip_layout, verify_skippables

Pytree = Any


from torchgpipe_tpu.utils import host_device as _host_device  # noqa: E402


class GPipe:
    """Pipeline parallelism over a sequential layer list.

    Reference: torchgpipe/gpipe.py:211-255 (constructor semantics: balance
    validation, deferred batch-norm conversion, partition placement).
    """

    def __init__(
        self,
        layers: Sequence[Layer],
        balance: Optional[Sequence[int]] = None,
        *,
        devices: Optional[Sequence] = None,
        chunks: int = 1,
        checkpoint: str = 'except_last',
        deferred_batch_norm: bool = False,
        compute_dtype: Optional[Any] = None,
        fused: bool = False,
        schedule: str = 'gpipe',
        loss_reduction: Optional[str] = None,
        remat_policy: Any = None,
        tracer: Any = None,
        hbm_budget_bytes: Optional[int] = None,
        megastep: int = 1,
    ) -> None:
        if balance is None:
            raise ValueError(
                "balance is required — use torchgpipe_tpu.balance.balance_by_time "
                "or balance_by_size for automatic balancing "
                "(reference: torchgpipe/gpipe.py:34-50)"
            )
        if chunks <= 0:
            raise ValueError("number of chunks must be positive integer")
        if checkpoint not in CHECKPOINT_MODES:
            raise ValueError(
                f"checkpoint is not one of {'|'.join(CHECKPOINT_MODES)}"
            )

        layers = list(layers)
        verify_module(layers)
        verify_skippables(layers)

        self._deferred_batch_norm = deferred_batch_norm
        if deferred_batch_norm:
            layers = convert_deferred_batch_norm(layers, chunks)
        if compute_dtype is not None:
            # Mixed precision (no reference counterpart — a TPU-native
            # feature): float32 masters, compute_dtype math/activations,
            # float32 normalization statistics.  Applied after deferred-BN
            # conversion so the converted norm layers get the float32-stats
            # wrapper too.
            from torchgpipe_tpu.precision import apply_policy

            layers = apply_policy(layers, compute_dtype)
        self.compute_dtype = compute_dtype

        if schedule not in ("gpipe", "1f1b"):
            raise ValueError("schedule must be 'gpipe' or '1f1b'")
        if schedule == "1f1b" and loss_reduction not in ("mean", "sum"):
            raise ValueError(
                "schedule='1f1b' seeds each micro-batch's backward before "
                "the mini-batch output exists, so the loss must decompose "
                "over micro-batches: pass loss_reduction='mean' (loss_fn is "
                "a batch-mean) or 'sum' (a batch-sum)"
            )
        if schedule != "1f1b" and loss_reduction is not None:
            raise ValueError(
                "loss_reduction only applies to schedule='1f1b' (the "
                "fill-drain schedule computes the loss on the gathered "
                "mini-batch); drop it or set schedule='1f1b'"
            )
        self.schedule = schedule
        self.loss_reduction = loss_reduction
        # Declared per-chip HBM budget (bytes).  Opt-in: the schedule
        # verifier's memory certification ERRORs on overrun, and the
        # plan-drift lint rule compares the running configuration
        # against analysis.planner's certified top plan under it.
        self.hbm_budget_bytes = hbm_budget_bytes

        self.layers = layers
        self.balance = list(balance)
        self.chunks = chunks
        self.checkpoint = checkpoint

        self.partitions = split_layers(layers, self.balance)

        if devices is None:
            devices = jax.devices()
        n = len(self.partitions)
        # Unlike the reference (which requires one device per partition,
        # gpipe.py:99-113), stages wrap around the available devices so an
        # n-stage pipeline runs (serialized) even on a single chip.
        self.devices = [devices[j % len(devices)] for j in range(n)]

        self.skip_layout = inspect_skip_layout(self.partitions)

        stages: List[StageExec] = []
        offset = 0
        for j, part in enumerate(self.partitions):
            stages.append(
                StageExec(j, part, offset, self.devices[j], self.skip_layout)
            )
            offset += len(part)
        # Optional torchgpipe_tpu.utils.tracing.Timeline recording per-cell
        # dispatch (or, with sync=True, serialized per-cell device time —
        # the overlap-ablation tool, SURVEY.md §5 tracing).
        self.tracer = tracer
        if fused and schedule == "1f1b":
            raise ValueError(
                "fused=True compiles the whole fill-drain step into one "
                "program; it cannot express the 1F1B schedule. Drop "
                "fused=True (1f1b runs on the per-cell scheduler) or use "
                "schedule='gpipe'"
            )
        if fused:
            if len({id(d) for d in self.devices}) > 1:
                raise ValueError(
                    "fused=True requires all stages on one device (the fused "
                    "path compiles the whole step into a single program); "
                    "pass devices=[one_device] or drop fused=True for the "
                    "per-cell multi-device scheduler"
                )
            if tracer is not None:
                raise ValueError(
                    "fused=True compiles the step into one program, so a "
                    "per-cell tracer would record nothing; drop the tracer "
                    "or pass fused=False"
                )
        if checkpoint == 'offload':
            # Per-cell 'offload' = the 'never' schedule (every cell keeps
            # its vjp residuals, zero recompute) with the residual
            # closures moved to HOST memory between the forward and
            # backward schedules — the per-cell engine's residuals are
            # explicit program outputs, so the engine itself relocates
            # them (no save-policy machinery needed).  The fused path
            # keeps its residuals INSIDE one program where only a remat
            # save policy can place them — use fused=False here, or
            # fused=True with remat_policy=policies.offload_names(...).
            if fused:
                raise ValueError(
                    "checkpoint='offload' is a per-cell scheduler feature "
                    "(residuals are program outputs the engine moves to "
                    "host memory); with fused=True pass a "
                    "remat_policy=checkpoint.policies.offload_names(...) "
                    "instead, or drop fused=True"
                )
            if schedule != 'gpipe':
                raise ValueError(
                    "checkpoint='offload' supports the fill-drain "
                    "('gpipe') schedule only — 1F1B already bounds "
                    "in-flight residuals at the pipeline depth"
                )
        if remat_policy is not None and not fused:
            raise ValueError(
                "remat_policy refines the FUSED path's per-cell "
                "jax.checkpoint (GPipe(fused=True, remat_policy=...)); "
                "the per-cell scheduler's checkpointed cells keep no "
                "residuals at all (recompute-ahead), so a save policy "
                "cannot apply — drop remat_policy, or use fused=True / "
                "the SPMD engine's SpmdGPipe.remat_policy"
            )
        if remat_policy is not None and checkpoint == 'never':
            raise ValueError(
                "remat_policy has no effect under checkpoint='never' "
                "(no cell is rematerialized)"
            )
        self.fused = fused
        self.remat_policy = remat_policy
        # Default megastep K for make_train_step (K optimizer steps in one
        # compiled program).  Declared at the pipe so static analysis (the
        # dispatch-per-step lint rule) sees the dispatch granularity.
        if not (isinstance(megastep, int) and not isinstance(megastep, bool)
                and megastep >= 1):
            raise ValueError(f"megastep must be an int >= 1, got {megastep!r}")
        if megastep > 1 and not fused:
            raise ValueError(
                "megastep compiles K optimizer steps into ONE program "
                "(lax.scan over the full step), which needs the whole step "
                "to BE one program: the per-cell scheduler dispatches each "
                "cell separately across stage devices and cannot be "
                "scanned.  Pass fused=True (single-device), or use the "
                "SPMD engine (SpmdGPipe.megastep), or megastep=1"
            )
        self.megastep = megastep
        self._pipeline = Pipeline(
            stages, self.skip_layout, tracer=tracer, remat_policy=remat_policy
        )

    # ------------------------------------------------------------------ #
    # container protocol (reference gpipe.py:257-285)                    #
    # ------------------------------------------------------------------ #

    def __repr__(self) -> str:
        devs = ", ".join(
            f"{p}:{i}"
            for p, i in sorted({(d.platform, d.id) for d in self.devices})
        )
        return (
            f"GPipe(layers={len(self.layers)}, balance={self.balance}, "
            f"chunks={self.chunks}, checkpoint={self.checkpoint!r}, "
            f"schedule={self.schedule!r}, devices=[{devs}])"
        )

    def __len__(self) -> int:
        return len(self.layers)

    def __getitem__(self, index: int) -> Layer:
        return self.layers[index]

    def __iter__(self) -> Iterator[Layer]:
        return iter(self.layers)

    # ------------------------------------------------------------------ #
    # parameters                                                         #
    # ------------------------------------------------------------------ #

    def init(
        self, rng: jax.Array, in_spec: Pytree
    ) -> Tuple[Tuple[List[Pytree], ...], Tuple[List[Pytree], ...]]:
        """Initialize parameters/state, grouped per stage and placed on the
        stage devices (the reference moves partitions in ``split_module``,
        gpipe.py:117).

        Initialization itself runs on the host CPU backend and transfers
        once per stage: init is hundreds of tiny ops (one per weight), each
        a separate dispatch and most a separate tiny compile on the
        accelerator.
        """
        with _host_device():
            flat_params, flat_state, _ = sequential_init(
                self.layers, rng, in_spec
            )
        params, state = [], []
        i = 0
        for part in self.partitions:
            params.append(flat_params[i : i + len(part)])
            state.append(flat_state[i : i + len(part)])
            i += len(part)
        return self.place(tuple(params)), self.place(tuple(state))

    def place(self, per_stage: Tuple[Pytree, ...]) -> Tuple[Pytree, ...]:
        """Commit each stage's pytree to that stage's device."""
        return tuple(
            jax.device_put(stage_tree, self.devices[j])
            for j, stage_tree in enumerate(per_stage)
        )

    def repartition(
        self, per_stage: Tuple[Pytree, ...]
    ) -> Tuple[List[Pytree], ...]:
        """Regroup per-stage per-layer pytrees (params or state in the
        :meth:`init` layout, possibly from a DIFFERENT balance cut)
        onto THIS pipe's cut — the carry path when a replan
        (:class:`torchgpipe_tpu.obs.replan.ReplanOnDrift`) or a manual
        rebuild changes the balance: the old cut's stage lists flatten
        back to the flat layer order and re-split by
        ``self.partitions``.  Pair with :meth:`place` to commit the new
        stages to their devices.  Per-stage OPTIMIZER states do not
        repartition (their trees mirror a whole stage, not a layer) —
        re-initialize them after a balance change."""
        flat = [leaf for stage_list in per_stage for leaf in stage_list]
        if len(flat) != len(self.layers):
            raise ValueError(
                f"repartition got {len(flat)} per-layer entries for a "
                f"{len(self.layers)}-layer pipeline — pass params/state "
                "exactly as init() (or a previous cut) produced them, "
                "one entry per layer grouped per stage"
            )
        out: List[List[Pytree]] = []
        i = 0
        for part in self.partitions:
            out.append(list(flat[i:i + len(part)]))
            i += len(part)
        return tuple(out)

    def megastep_boundary(self, step: int) -> bool:
        """True when ``step`` completed optimizer steps land on a
        megastep boundary — the cadence checkpoint/preemption hooks run
        at, and the only place
        :class:`torchgpipe_tpu.obs.replan.ReplanOnDrift` may fire (a
        replan can never land inside a compiled K-step program)."""
        k = max(int(self.megastep or 1), 1)
        return step % k == 0

    def state_dict(
        self,
        params: Tuple[Pytree, ...],
        state: Tuple[Pytree, ...],
    ) -> Dict[str, Any]:
        """Flat named mapping with reference-style
        ``partitions.<stage>.<layer>`` keys (reference: gpipe.py:257-285
        keeps wrapped layers discoverable via ``state_dict``; here params
        are explicit, so they are arguments rather than attributes)."""
        from torchgpipe_tpu.utils.serialization import state_dict

        return state_dict(self, params, state)

    def load_state_dict(
        self,
        params: Tuple[Pytree, ...],
        state: Tuple[Pytree, ...],
        d: Dict,
    ) -> Tuple[Tuple[Pytree, ...], Tuple[Pytree, ...]]:
        """Strict inverse of :meth:`state_dict` over an initialized
        ``(params, state)`` template; returns new placed pytrees."""
        from torchgpipe_tpu.utils.serialization import load_state_dict

        return load_state_dict(self, params, state, d)

    # ------------------------------------------------------------------ #
    # execution                                                          #
    # ------------------------------------------------------------------ #

    def apply(
        self,
        params: Tuple[Pytree, ...],
        state: Tuple[Pytree, ...],
        x: Pytree,
        *,
        rng: Optional[jax.Array] = None,
        train: bool = False,
    ) -> Tuple[Pytree, Tuple[Pytree, ...]]:
        """Pipelined forward pass (no gradients).

        Reference: torchgpipe/gpipe.py:330-380 (``forward``): scatter,
        schedule, gather.
        """
        microbatch.check(x)
        mbatches = microbatch.scatter(x, self.chunks)
        if self._use_fused():
            outs, new_states = self._pipeline.run_forward_fused(
                params, state, mbatches, rng, train
            )
        else:
            outs, new_states = self._pipeline.run_forward(
                params, state, mbatches, rng, train
            )
        return microbatch.gather(outs), tuple(new_states)

    def _split_microbatches(self, x: Pytree) -> List[Pytree]:
        """Shared training-entry prologue: validate, scatter into
        micro-batches, resolve the checkpoint stop index.

        Deferred BN commits running stats on the chunks-th micro-batch; a
        short batch would never commit and would bleed accumulators into
        the next mini-batch — hence the exact-split requirement."""
        microbatch.check(x)
        mbatches = microbatch.scatter(x, self.chunks)
        if self._deferred_batch_norm and len(mbatches) != self.chunks:
            raise ValueError(
                f"deferred_batch_norm requires the batch to split into exactly "
                f"chunks={self.chunks} micro-batches, got {len(mbatches)} "
                f"(batch size {microbatch.batch_size(x)})"
            )
        return mbatches, checkpoint_stop(
            self.checkpoint, len(mbatches), train=True
        )

    def value_and_grad(
        self,
        params: Tuple[Pytree, ...],
        state: Tuple[Pytree, ...],
        x: Pytree,
        target: Pytree,
        loss_fn: Any,
        *,
        rng: Optional[jax.Array] = None,
    ) -> Tuple[jax.Array, Tuple[Pytree, ...], Tuple[Pytree, ...], Dict]:
        """Pipelined training step: forward, loss, backward.

        Under the default fill-drain schedule ``loss_fn(output, target)``
        sees the *gathered* mini-batch output, so losses (and therefore
        gradients) are exactly those of the un-pipelined model — the
        transparency contract the reference proves with its accuracy
        benchmarks (SURVEY.md §6).  ``loss_fn`` may return ``(loss, aux)``.

        Under ``schedule='1f1b'`` the loss is computed per micro-batch
        (weighted by ``loss_reduction``), so ``target`` must split along the
        batch dimension like the input, and ``aux`` is returned as a LIST of
        per-micro-batch values instead of one gathered value.

        Returns ``(loss, grads, new_state, aux)`` with ``grads`` shaped like
        ``params``.
        """
        mbatches, stop = self._split_microbatches(x)
        if self.schedule == "1f1b":
            sizes = [microbatch.batch_size(mb) for mb in mbatches]
            total = sum(sizes)
            if self.loss_reduction == "mean":
                weights = [b / total for b in sizes]
            else:
                weights = [1.0] * len(sizes)
            try:
                microbatch.check(target)
                target_ok = microbatch.batch_size(target) == total
            except (ValueError, TypeError, IndexError):
                target_ok = False
            if not target_ok:
                raise ValueError(
                    "schedule='1f1b' computes the loss per micro-batch, so "
                    "target must be a pytree splitting along the batch "
                    f"dimension like the input (batch size {total}); got "
                    f"{type(target).__name__}. Use the default schedule for "
                    "non-batched targets"
                )
            target_mbs = microbatch.scatter(target, self.chunks)
            loss, grads, new_states, aux = self._pipeline.run_train_1f1b(
                params, state, mbatches, target_mbs, loss_fn, rng, stop,
                weights,
            )
            return loss, tuple(grads), tuple(new_states), aux
        if self._use_fused():
            loss, grads, new_states, aux = self._pipeline.run_train_fused(
                params, state, mbatches, target, loss_fn, rng, stop
            )
        else:
            loss, grads, new_states, aux = self._pipeline.run_train(
                params, state, mbatches, target, loss_fn, rng, stop,
                offload=self.checkpoint == 'offload',
            )
        return loss, tuple(grads), tuple(new_states), aux

    def init_opt_state(
        self, optimizer: Any, params: Tuple[Pytree, ...]
    ) -> Tuple[Pytree, ...]:
        """Per-stage optimizer states, each committed to its stage's
        device (pair with :meth:`make_train_step`)."""
        return tuple(
            jax.device_put(optimizer.init(p_j), self.devices[j])
            for j, p_j in enumerate(params)
        )

    def make_train_step(
        self, optimizer: Any, loss_fn: Any, *, donate: bool = True,
        megastep: Optional[int] = None,
    ) -> Any:
        """Training step with the optimizer applied PER STAGE.

        ``optimizer`` is any optax-style gradient transformation.
        Returns ``step(params, opt_state, state, x, target, rng=None)
        -> (loss, new_params, new_opt_state, new_state, aux)``;
        initialize ``opt_state`` with :meth:`init_opt_state`.

        Why this exists: GPipe's per-stage params live on DIFFERENT
        devices, so jitting one optax update over the whole tuple
        (e.g. plain ``optimizer.update(grads, opt_state, params)``)
        fails with "incompatible devices for jitted computation" — a
        sharp edge every first MPMD training loop hits.  Here each
        stage's update compiles as its own program and runs on that
        stage's device, dispatched asynchronously like the engine's
        cells; gradients never leave their stage.

        The SPMD twin (:meth:`SpmdGPipe.make_train_step
        <torchgpipe_tpu.spmd.SpmdGPipe.make_train_step>`) fuses the
        whole update into ONE program instead — possible there because
        all params live in one mesh computation.

        ``megastep`` (default: the pipe's ``megastep`` ctor arg)
        compiles K optimizer steps into one scanned program with a
        donated ``(params, opt_state)`` carry — fused path only (the
        per-cell scheduler cannot be scanned; the ctor enforces it).
        The megastep step consumes ``[K, ...]``-stacked ``x``/``target``
        and returns ``(loss[K], params, opt_state, state, aux[K],
        finite[K])``: NaN skip-step moves inside the scan (a non-finite
        inner step passes its input params/opt_state/state through,
        bitwise what a StepGuard-wrapped single step returns), and
        checkpoint/preemption/retry granularity becomes the megastep —
        the same contract as the SPMD twin."""
        K = self.megastep if megastep is None else int(megastep)
        if K < 1:
            raise ValueError(f"megastep must be >= 1, got {K}")
        if K > 1 and not self._use_fused():
            raise ValueError(
                "make_train_step(megastep>1) needs GPipe(fused=True): "
                "the per-cell scheduler dispatches each cell separately "
                "and cannot be compiled into one scanned program; use "
                "fused=True or the SPMD engine"
            )
        if K > 1:
            return self._make_megastep_fused(optimizer, loss_fn, K, donate)

        def _upd(g: Pytree, os: Pytree, p: Pytree) -> Tuple[Pytree, Pytree]:
            u, nos = optimizer.update(g, os, p)
            newp = jax.tree_util.tree_map(
                lambda a, b: (a + b).astype(a.dtype), p, u
            )
            return newp, nos

        # Donate the optimizer state and old params: the update happens
        # in place in each stage's HBM (no transient 2x params+moments),
        # matching the SPMD twin's donate=True.  Callers must treat the
        # passed-in params/opt_state as consumed (standard donation
        # contract; XLA ignores donation where unsupported, e.g. CPU).
        # Pass donate=False when the OLD params must survive the call —
        # the resilience.StepGuard skip-step contract restores them after
        # a non-finite update.
        upd = jax.jit(_upd, donate_argnums=(1, 2) if donate else ())
        # The schedule verifier's donation-safety rule reads this to place
        # the donating update event in the step's event graph.
        self._train_step_donate = donate

        def step(
            params: Tuple[Pytree, ...],
            opt_state: Tuple[Pytree, ...],
            state: Tuple[Pytree, ...],
            x: Pytree,
            target: Pytree,
            rng: Optional[jax.Array] = None,
        ) -> Tuple[jax.Array, Tuple, Tuple, Tuple, Dict]:
            loss, grads, new_state, aux = self.value_and_grad(
                params, state, x, target, loss_fn, rng=rng
            )
            new_p = []
            new_os = []
            for p_j, g_j, os_j in zip(params, grads, opt_state):
                np_j, nos_j = upd(g_j, os_j, p_j)
                new_p.append(np_j)
                new_os.append(nos_j)
            return loss, tuple(new_p), tuple(new_os), new_state, aux

        step.megastep = 1  # type: ignore[attr-defined]
        return step

    def _make_megastep_fused(
        self, optimizer: Any, loss_fn: Any, K: int, donate: bool
    ) -> Any:
        """K fused steps as one scanned program (see
        :meth:`make_train_step`'s ``megastep`` contract)."""
        import jax.numpy as jnp

        from torchgpipe_tpu.utils import tree_finite

        tmap = jax.tree_util.tree_map

        def whole(
            params: Tuple,
            opt_state: Tuple,
            states: Tuple,
            x: Pytree,
            target: Pytree,
            rng: Optional[jax.Array],
        ) -> Tuple:
            def body(carry: Tuple, xs: Tuple) -> Tuple:
                p, o, st = carry
                x_k, tgt_k, k = xs
                key = (
                    jax.random.fold_in(rng, k) if rng is not None else None
                )
                mbatches, stop = self._split_microbatches(x_k)
                loss, grads, new_st, aux = self._pipeline.run_train_fused(
                    list(p), list(st), mbatches, tgt_k, loss_fn, key, stop
                )
                new_p, new_o = [], []
                for p_j, g_j, o_j in zip(p, grads, o):
                    u_j, no_j = optimizer.update(g_j, o_j, p_j)
                    new_p.append(tmap(
                        lambda a, b: (a + b).astype(a.dtype), p_j, u_j
                    ))
                    new_o.append(no_j)
                new_p, new_o = tuple(new_p), tuple(new_o)
                # The fused loop may hand stage states back in different
                # CONTAINER types (tuple vs list) than init produced; the
                # scan carry needs one stable treedef, so rebuild on the
                # input state's structure (same leaves, same order).
                new_st = jax.tree_util.tree_unflatten(
                    jax.tree_util.tree_structure(st),
                    jax.tree_util.tree_leaves(new_st),
                )
                # In-scan skip-step over exactly what StepGuard's
                # host-side check covers for the K=1 step: the whole
                # output tuple (loss, params, opt state, model state,
                # aux).  On skip the INPUT state passes through — what
                # StepGuard(extra_state_argnums=(2,)) restores.
                ok = tree_finite((loss, new_p, new_o, new_st, aux))
                sel = lambda a, b: jnp.where(ok, a, b)  # noqa: E731
                new_p = tmap(sel, new_p, p)
                new_o = tmap(sel, new_o, o)
                new_st = tmap(sel, new_st, st)
                return (new_p, new_o, new_st), (loss, aux, ok)

            (p, o, st), (losses, auxs, finite) = jax.lax.scan(
                body, (params, opt_state, states),
                (x, target, jnp.arange(K)),
            )
            return losses, p, o, st, auxs, finite

        compiled = jax.jit(whole, donate_argnums=(0, 1) if donate else ())
        self._train_step_donate = donate

        def step(
            params: Tuple[Pytree, ...],
            opt_state: Tuple[Pytree, ...],
            state: Tuple[Pytree, ...],
            x: Pytree,
            target: Pytree,
            rng: Optional[jax.Array] = None,
        ) -> Tuple[jax.Array, Tuple, Tuple, Tuple, Dict, jax.Array]:
            for leaf in jax.tree_util.tree_leaves(x):
                if leaf.shape[:1] != (K,):
                    raise ValueError(
                        f"megastep={K} consumes [K, ...]-stacked batches "
                        f"(K steps in one program), got a leading dim of "
                        f"{leaf.shape[0]} — stack K per-step batches with "
                        "jnp.stack, or pass megastep=1"
                    )
                break
            return compiled(
                tuple(params), tuple(opt_state), tuple(state), x, target, rng
            )

        step.megastep = K  # type: ignore[attr-defined]
        return step

    def value_and_grad_with_loss_params(
        self,
        params: Tuple[Pytree, ...],
        loss_params: Pytree,
        state: Tuple[Pytree, ...],
        x: Pytree,
        target: Pytree,
        loss_layer: Layer,
        *,
        rng: Optional[jax.Array] = None,
    ) -> Tuple[jax.Array, Tuple[Pytree, ...], Pytree, Tuple[Pytree, ...], Dict]:
        """Pipelined training step with a PARAMETRIC loss layer.

        ``loss_layer`` is a :class:`~torchgpipe_tpu.layers.Layer` applied to
        ``(gathered_output, target)`` whose own parameters train too — the
        big-vocabulary fused head+cross-entropy
        (:func:`torchgpipe_tpu.models.transformer.chunked_lm_loss`) being
        the motivating case: build the model WITHOUT its lm_head (the
        ``[tokens, vocab]`` logits then never materialize) and let the loss
        layer own the head weights.

        Returns ``(loss, grads, loss_grads, new_state, aux)``.  Fill-drain
        schedule only (the 1F1B/fused paths compute losses inside their own
        programs); initialize ``loss_params`` via ``loss_layer.init``.
        """
        if self.schedule != "gpipe":
            raise ValueError(
                "value_and_grad_with_loss_params supports the fill-drain "
                f"('gpipe') schedule only (got schedule={self.schedule!r})"
            )
        if self._use_fused():
            raise ValueError(
                "value_and_grad_with_loss_params is not supported with "
                "fused=True (the fused program computes its loss inline); "
                "use the per-cell scheduler"
            )
        mbatches, stop = self._split_microbatches(x)
        loss, grads, loss_grads, new_states, aux = self._pipeline.run_train(
            params, state, mbatches, target, loss_layer, rng, stop,
            loss_params=loss_params,
            offload=self.checkpoint == 'offload',
        )
        return loss, tuple(grads), loss_grads, tuple(new_states), aux

    def _use_fused(self) -> bool:
        """Per-cell scheduling is the default everywhere; ``fused=True``
        opts into compiling the whole step as one XLA program.

        An earlier heuristic auto-fused whenever all stages shared one
        device, on the theory that dispatch latency dominates there — but
        a builder's v5e measurement before PR 1 said otherwise: the
        per-cell path ran 2x FASTER than the monolithic program (65.9
        vs 32.4 samples/s) and skipped its far longer compile
        (BENCH_NOTES.md finding #1; not re-measured, ROADMAP A7).  JAX's async dispatch keeps the chip
        saturated; fusing remains available (and bit-identical,
        tests/test_fused.py) for latency-sensitive small models.
        """
        return bool(self.fused)
