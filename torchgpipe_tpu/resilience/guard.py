"""Guarded training steps: skip bad updates, retry transient failures.

A multi-week pipeline run dies three ways that a correct *model* cannot
prevent: a non-finite loss/gradient poisons the optimizer state forever, a
transient infrastructure error (XLA ``RESOURCE_EXHAUSTED`` from a
fragmented allocator, a dropped transport send) kills the process even
though the very next attempt would succeed, and a genuine model bug gets
retried into oblivion instead of surfacing.  :class:`StepGuard` wraps a
step function with exactly those three policies:

* **Non-finite guard** — after each step, device-side ``isfinite``
  reductions over the loss and the updated params collapse to boolean
  scalars fetched in ONE host sync (lint-clean under the
  ``host-sync-in-loop`` rule: the reductions are their own tiny
  programs, not callbacks inside the pipelined loop).  A bad step is *skipped*:
  the caller gets back the params/opt-state it passed in, and the
  optional :class:`~torchgpipe_tpu.precision.DynamicLossScale` backs off
  (the mixed-precision overflow protocol).
* **Transient retry** — exceptions classified transient by
  :func:`classify_error` (XLA ``RESOURCE_EXHAUSTED``/``DATA_LOSS``,
  ``ConnectionError``, ``TimeoutError``) are retried under bounded
  exponential backoff.  Everything else — shape errors, user exceptions
  from a layer (the :mod:`tests.test_failures` semantics), a
  :class:`~torchgpipe_tpu.distributed.context.PeerDiedError` whose
  pipeline state cannot be retried in-process — re-raises immediately.

Contract: the wrapped step has the engines' ``make_train_step`` shape —
``step(params, opt_state, *data, **kw) -> (loss, new_params,
new_opt_state, *extras)``.  **Both policies require non-donated
buffers**: build the step with ``donate=False`` (both engines'
``make_train_step`` take it) — skip-step must return the params the
step would have consumed, and a retry must re-feed inputs the failed
attempt would have donated (the guard detects consumed buffers and
refuses the retry didactically rather than crash on deleted arrays).

**Megastep steps** (``make_train_step(megastep=K)``, detected via the
step's ``megastep`` attribute) move the finite check INSIDE the
compiled scan: the engine gates each inner step's update on a traced
all-finite reduction and reports the per-step mask as the step's last
output, so skip-step works even under ``donate=True`` (the returned
params are already protected — nothing needs restoring).  The guard
then only folds the mask into its statistics and backs the loss scale
off at MEGASTEP granularity; transient RETRY still needs
``donate=False``, and retries re-run the whole K-step program — the
documented granularity change of compiling K steps into one dispatch.
"""

from __future__ import annotations

import dataclasses
import time
from typing import Any, Callable, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

# The standalone registry module only (obs/__init__ lazy-loads the
# reconcile half, so this does NOT drag the analysis stack in here).
from torchgpipe_tpu.obs.registry import (
    MetricsRegistry,
    counter_property as _counter_property,
)
from torchgpipe_tpu.precision import DynamicLossScale

Pytree = Any

# XLA status codes that indicate infrastructure, not model, failure:
# allocator pressure (retry often succeeds after the async streams drain)
# and torn data movement.
_TRANSIENT_XLA_CODES = ("RESOURCE_EXHAUSTED", "DATA_LOSS")


def classify_error(err: BaseException) -> str:
    """``'transient'`` (retry can help) or ``'fatal'`` (re-raise now).

    Transient: ``ConnectionError`` and subclasses, ``TimeoutError``
    (covers ``socket.timeout``), and XLA runtime errors carrying
    ``RESOURCE_EXHAUSTED`` / ``DATA_LOSS`` codes.  Fatal: everything
    else — including :class:`~torchgpipe_tpu.distributed.context.
    PeerDiedError` (a dead rank leaves stale channel state; restart the
    worker, don't retry the step — see
    ``DistributedGPipe.recv_timeout``'s contract).
    """
    from torchgpipe_tpu.distributed.context import PeerDiedError

    if isinstance(err, PeerDiedError):
        return "fatal"
    if isinstance(err, (ConnectionError, TimeoutError)):
        return "transient"
    if isinstance(err, jax.errors.JaxRuntimeError):
        msg = str(err)
        if any(code in msg for code in _TRANSIENT_XLA_CODES):
            return "transient"
    return "fatal"


@dataclasses.dataclass(frozen=True)
class GuardPolicy:
    """Knobs for :class:`StepGuard` (defaults are production-shaped)."""

    max_retries: int = 3          # transient retries per step
    backoff_base: float = 0.25    # seconds; doubles per attempt
    backoff_max: float = 8.0      # cap on a single sleep
    skip_nonfinite: bool = True   # skip-step on non-finite loss/params

    def backoff(self, attempt: int) -> float:
        return min(self.backoff_base * (2.0 ** attempt), self.backoff_max)


class GuardStats:
    """Counters the guard maintains across steps — registry-backed.

    Re-based on :class:`torchgpipe_tpu.obs.MetricsRegistry` so guard
    skips/retries export next to every other telemetry series (JSONL /
    Prometheus via ``stats.registry``), while the original attribute
    API — ``stats.steps``, ``stats.skipped``, ``stats.retries``, read
    and ``+=``-assigned as plain ints — is unchanged.  Series names are
    fixed (``guard_*``): ONE guard per shared registry (a second guard
    on the same registry writes the same series); give concurrent
    guards their own registries.
    """

    def __init__(self, registry: Any = None) -> None:
        self.registry = registry or MetricsRegistry()
        self._steps = self.registry.counter(
            "guard_steps", help="successful (applied) steps")
        self._skipped = self.registry.counter(
            "guard_skipped", help="non-finite steps skipped")
        self._retries = self.registry.counter(
            "guard_retries", help="transient retries performed")
        # Labeled error anatomy: every exception the guard sees, by the
        # classification that decided its fate and the concrete type —
        # and, for PeerDiedError, the OFFENDING RANK, so guard retries
        # and the flight-recorder postmortem dumps cross-reference the
        # same incident instead of telling disjoint stories.
        self._errors = self.registry.counter(
            "guard_errors",
            help="step exceptions seen, by classification and type",
            labels=("classification", "error"),
        )
        self._peer_died = self.registry.counter(
            "guard_peer_died",
            help="PeerDiedError occurrences by offending rank",
            labels=("rank",),
        )

    steps = _counter_property("_steps")
    skipped = _counter_property("_skipped")
    retries = _counter_property("_retries")

    def record_error(self, classification: str, err: BaseException) -> None:
        """Count one step exception under its classification/type; a
        :class:`~torchgpipe_tpu.distributed.context.PeerDiedError` also
        names its dead rank in the ``guard_peer_died`` series."""
        self._errors.inc(
            classification=classification, error=type(err).__name__
        )
        rank = getattr(err, "rank", None)
        if rank is not None:
            self._peer_died.inc(rank=str(rank))

    def __repr__(self) -> str:
        return (
            f"GuardStats(steps={self.steps}, skipped={self.skipped}, "
            f"retries={self.retries})"
        )

    def __eq__(self, other: object) -> bool:
        if not isinstance(other, GuardStats):
            return NotImplemented
        return (self.steps, self.skipped, self.retries) == (
            other.steps, other.skipped, other.retries
        )


def _any_deleted(tree: Pytree) -> bool:
    """True if any jax array leaf was consumed by buffer donation."""
    for a in jax.tree_util.tree_leaves(tree):
        deleted = getattr(a, "is_deleted", None)
        if deleted is not None:
            try:
                if deleted():
                    return True
            except Exception:  # noqa: BLE001 — probing must never raise
                continue
    return False


def _all_finite(tree: Pytree) -> bool:
    """Finiteness of every inexact leaf, with ONE host synchronization.

    Each leaf's ``isfinite`` reduction runs on the leaf's OWN device (the
    MPMD engine's params deliberately live on different stage devices, so
    a single cross-device jit is impossible); the per-leaf boolean
    scalars then come back in one blocking ``device_get`` — the single
    host sync the guard adds per step.
    """
    flags = [
        jnp.all(jnp.isfinite(a))
        for a in jax.tree_util.tree_leaves(tree)
        if jnp.issubdtype(jnp.asarray(a).dtype, jnp.inexact)
    ]
    if not flags:
        return True
    return bool(np.all(jax.device_get(flags)))


class StepGuard:
    """Wrap a ``make_train_step``-shaped callable with skip/retry policy.

    Example::

        step = pipe.make_train_step(optax.adamw(3e-4), donate=False)
        guard = StepGuard(step, loss_scale=DynamicLossScale())
        for batch in data:
            loss, params, opt_state = guard(params, opt_state, x, y)
            # a skipped step returns (nan_loss, params, opt_state) unchanged;
            # guard.stats.skipped counts them, guard.loss_scale backs off.

    ``finite_of(outputs) -> pytree`` overrides what the finiteness check
    covers (default: the ENTIRE output tuple, so NaNs in extras — e.g. a
    stateful model's updated running statistics — trigger the skip too).
    ``on_event(kind, info)`` observes ``'skip'`` / ``'retry'`` decisions
    (logging, metrics).

    Steps that thread extra mutable state (``GPipe.make_train_step``'s
    ``step(params, opt_state, state, x, y) -> (loss, p, o, state, aux)``)
    must tell the guard which INPUT positions carry it, or a skipped
    step would hand back state computed from the poisoned batch::

        guard = StepGuard(step, extra_state_argnums=(2,))
        # on skip, outputs[3] (the new state) is replaced by the state
        # the caller passed in at position 2 — positions map in order
        # onto outputs[3:].
    """

    def __init__(
        self,
        step: Callable[..., Tuple],
        *,
        loss_scale: Optional[DynamicLossScale] = None,
        policy: Optional[GuardPolicy] = None,
        finite_of: Optional[Callable[[Tuple], Pytree]] = None,
        extra_state_argnums: Tuple[int, ...] = (),
        classify: Callable[[BaseException], str] = classify_error,
        sleep: Callable[[float], None] = time.sleep,
        on_event: Optional[Callable[[str, dict], None]] = None,
        registry: Any = None,
    ) -> None:
        self._step = step
        self.loss_scale = loss_scale
        self.policy = policy or GuardPolicy()
        self._finite_of = finite_of
        self.extra_state_argnums = tuple(extra_state_argnums)
        self._classify = classify
        self._sleep = sleep
        self._on_event = on_event
        # ``registry`` (torchgpipe_tpu.obs.MetricsRegistry) shares the
        # guard's counters with the rest of the run's telemetry; None
        # gives the stats their own private registry (legacy shape).
        self.stats = GuardStats(registry)

    def _event(self, kind: str, **info: Any) -> None:
        if self._on_event is not None:
            self._on_event(kind, info)

    def __call__(self, params: Pytree, opt_state: Pytree, *args: Any,
                 **kwargs: Any) -> Tuple:
        out = self._call_with_retries(params, opt_state, *args, **kwargs)
        if not (isinstance(out, tuple) and len(out) >= 3):
            raise TypeError(
                "StepGuard expects the wrapped step to return "
                "(loss, new_params, new_opt_state, *extras) — the "
                "make_train_step shape — got "
                f"{type(out).__name__} of length "
                f"{len(out) if isinstance(out, tuple) else 'n/a'}"
            )
        loss = out[0]
        megastep = int(getattr(self._step, "megastep", 1) or 1)
        if megastep > 1:
            # A megastep step already ran the skip-step INSIDE its scan
            # (the engines' traced all-finite check gates the carry per
            # inner step — an UNCONDITIONAL property of the compiled
            # program; ``GuardPolicy.skip_nonfinite`` only controls the
            # K=1 host-side check and cannot reach inside) and reports
            # the per-step mask as its LAST output.  The guard's job
            # shrinks to the scan boundary: fold the mask into the
            # statistics — skips that HAPPENED are always counted, so
            # no optimizer step vanishes from the accounting whatever
            # the policy says — and back the loss scale off once per
            # megastep containing any skip: the documented granularity
            # change (docs/robustness.md).  The whole-output finite
            # check would be wrong here: the loss VECTOR legitimately
            # carries the skipped steps' non-finite losses while the
            # params stayed protected.
            mask = np.asarray(jax.device_get(out[-1])).astype(bool).ravel()
            skipped = int(mask.size - mask.sum())
            self.stats.steps += int(mask.sum())
            if skipped:
                self.stats.skipped += skipped
                if self.loss_scale is not None:
                    self.loss_scale = self.loss_scale.bad()
                self._event(
                    "skip", loss=loss, skipped=self.stats.skipped,
                    megastep=megastep,
                    loss_scale=(
                        self.loss_scale.scale
                        if self.loss_scale is not None else None
                    ),
                )
            elif self.loss_scale is not None:
                self.loss_scale = self.loss_scale.ok()
            return out
        if self.policy.skip_nonfinite:
            checked = (
                self._finite_of(out) if self._finite_of is not None else out
            )
            # The ONE host sync the guard adds per step.
            if not _all_finite(checked):
                self.stats.skipped += 1
                if self.loss_scale is not None:
                    self.loss_scale = self.loss_scale.bad()
                self._event(
                    "skip",
                    loss=loss,
                    skipped=self.stats.skipped,
                    loss_scale=(
                        self.loss_scale.scale
                        if self.loss_scale is not None
                        else None
                    ),
                )
                # Skip-step: hand back the state the caller passed in —
                # including threaded extras the step replaced (their input
                # positions map in order onto outputs[3:]).
                fargs = (params, opt_state) + args
                extras = list(out[3:])
                for k, argnum in enumerate(self.extra_state_argnums):
                    extras[k] = fargs[argnum]
                return (loss, params, opt_state) + tuple(extras)
        if self.loss_scale is not None:
            self.loss_scale = self.loss_scale.ok()
        self.stats.steps += 1
        return out

    def _call_with_retries(self, *args: Any, **kwargs: Any) -> Tuple:
        attempt = 0
        while True:
            try:
                return self._step(*args, **kwargs)
            except Exception as err:  # noqa: BLE001 — classified below
                classification = self._classify(err)
                self.stats.record_error(classification, err)
                if (
                    classification != "transient"
                    or attempt >= self.policy.max_retries
                ):
                    if attempt > 0 and hasattr(err, "add_note"):
                        err.add_note(
                            f"StepGuard: giving up after {attempt} transient "
                            "retr" + ("y" if attempt == 1 else "ies")
                        )
                    raise
                if _any_deleted(args) or _any_deleted(kwargs):
                    # The failed attempt already CONSUMED donated input
                    # buffers (donate=True is both engines' default, and
                    # XLA honors it on accelerators even when the step
                    # later fails) — re-invoking would crash with a cryptic
                    # "Array has been deleted".  Convert the dead end into
                    # a didactic error instead.
                    if hasattr(err, "add_note"):
                        err.add_note(
                            "StepGuard: cannot retry — the failed attempt "
                            "donated its input buffers to XLA; build the "
                            "step with make_train_step(..., donate=False) "
                            "to make it retryable"
                        )
                    raise
                delay = self.policy.backoff(attempt)
                attempt += 1
                self.stats.retries += 1
                self._event(
                    "retry", attempt=attempt, delay=delay,
                    error=type(err).__name__,
                )
                self._sleep(delay)
