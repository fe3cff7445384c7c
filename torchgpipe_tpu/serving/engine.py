"""The serving loop: a statically bounded program set, arbitrary churn.

Steady-state contract (the whole point, and what the compile-counter
test in ``tests/test_serving.py`` pins): after warmup the engine
executes a STATICALLY BOUNDED set of compiled programs — exactly two
with a single ``prefill_chunk``, ``len(ladder) + 1`` with a prefill
bucket ladder (``prefill_chunk=(1, 2, 4, 8)``; certified by
``analysis.serving.certify_ladder``) —

* **prefill** — ``decode_slots`` at ``g = prefill_chunk`` over a
  COMPACT batch of ``R`` rows (one program per ladder bucket, all at
  the one ``R``; each step dispatches the smallest bucket covering the
  largest chunk among the rows it takes): the step takes the first
  ``R`` pending prompts in admission order, hands the program their
  slot indices, and each row's chunk is teacher-forced at its slot's
  own frontier — K/V rows scattered into the pool at that slot, the
  read over that slot's rows only, unused rows masked no-ops.  A step
  so costs ``R x g`` positions, not ``num_slots x g``; prompts beyond
  ``R`` wait for the next prefill step.  Rows finishing their prompt
  sample their FIRST token from the chunk's last-valid-position logits
  (so prefill and decode share one sampling site semantics-wise);
* **decode** — ``decode_slots`` at ``g = 1`` over the whole pool: one
  token per occupied slot, each at its own position.  The tokens it
  consumes are the previous programs' sampled tokens, and they never
  leave the device for it: the engine holds a device vector of every
  slot's current token, the decode program returns it with its samples
  written at the rows that ran, and the prefill program writes a first
  token into it where a prompt completes.

One step in flight.  Nothing the host does between two steps needs the
VALUE of a token but handing it to ``on_token`` and comparing it with
``eos_id``: which rows run, ``n_valid``, how far ``pool.lengths`` /
``prefilled`` advance, who ends by length, which slot frees and who is
admitted into it are functions of counts known at launch.  So a step's
bookkeeping is split in two — the ADVANCE, from counts, when the step is
launched; the DELIVERY, when its tokens are on the host — and
:meth:`Engine.step` launches step ``k+1`` before it waits for step
``k``'s tokens: the device runs ``k+1`` while the host delivers ``k``
and builds ``k+2``.  Whatever observes or changes a request from outside
(``result``, ``status``, ``cancel``, ``drain``, ...) first settles the
step in flight, so a caller sees the state a strictly serial loop shows.
A request that ends by EOS at step ``k`` has a row in step ``k+1``
already: that row's token is discarded at delivery — it is not a token
(never appended, streamed or counted).  There is no switch: a step is
launched ahead exactly when its inputs are known without the tokens and
nothing has to be known of the step before it.  Who needs the tokens
first (speculative decoding's acceptance, a prefill-role engine's
hand-off, QoS preemption under pressure) settles first and so runs the
same code at depth 0; so does an engine that promises to RETRY a failed
step (``donate=False``): the retry needs the step's inputs alive until
the step is known good, a step launched behind it would hold a third
KV pool beside them, so such an engine waits for every step where it
launches it, as it always did, and keeps two.

Request arrival, completion, cancellation, drain — all of it changes
only the VALUES of ``slots`` / ``tokens`` / ``lengths`` / ``n_valid`` /
the cache arrays, never a shape, so XLA never retraces.  The engine
works from the SAME trained pipeline params the training engines
produce (``mpmd_params_for_generation`` / ``spmd_params_for_generation``
— the flat per-layer list), with no conversion step.

Resilience: every compiled-step dispatch retries transient failures
under :func:`torchgpipe_tpu.resilience.guard.classify_error` (bounded
backoff, :class:`~torchgpipe_tpu.resilience.guard.GuardPolicy`); a
:class:`~torchgpipe_tpu.resilience.preemption.PreemptionHandler` wired
in at build time triggers a cooperative drain between iterations —
unfinished requests snapshot (prompt + tokens emitted so far) through
:class:`~torchgpipe_tpu.resilience.checkpoint.CheckpointManager`, and
:meth:`Engine.restore_requests` resubmits them to the next incarnation,
which continues each stream exactly where it stopped (greedy decode is
prefix-deterministic, so resumed outputs equal never-preempted ones —
tested).
"""

from __future__ import annotations

import dataclasses
import time
from typing import Any, Callable, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from torchgpipe_tpu.models import kv_cache
from torchgpipe_tpu.models.generation import (
    _check_decodable,
    _sample,
    _split_params,
    attend_rows_counter,
    decode_slots,
)
from torchgpipe_tpu.models.transformer import TransformerConfig
from torchgpipe_tpu.resilience.guard import GuardPolicy, classify_error
from torchgpipe_tpu.serving.cache_pool import CachePool
from torchgpipe_tpu.serving.metrics import ServingMetrics
from torchgpipe_tpu.serving.qos import check_tier
from torchgpipe_tpu.serving.scheduler import (
    Request,
    Scheduler,
    normalize_buckets,
)
from torchgpipe_tpu.utils.tracing import default_timeline

Pytree = Any


@dataclasses.dataclass
class _Launched:
    """One compiled step between its launch and its delivery."""

    kind: str                       # "prefill" | "decode"
    tok: Any                        # device vector holding the samples
    counts: Any                     # held experts' token counts, or None
    positions: int                  # positions the step computed
    # (request, row of ``tok``) of every row that sampled a token.
    rows: List[Tuple[Request, int]]
    t0: float                       # recorder clock at launch
    chunks: List[Tuple[str, int, int]] = dataclasses.field(
        default_factory=list)       # prefill: (rid, g, take) for the recorder
    # Requests that ended BY LENGTH when this step was launched: out of
    # the scheduler, their last token still on the device.
    released: List[Request] = dataclasses.field(default_factory=list)


def _start_host_copy(arr: Any) -> None:
    """Begin an ASYNC device→host copy of ``arr`` (best-effort: not
    every backend/array exposes it).  The engine calls this right after
    a launch so the sampled-token transfer starts the moment the program
    ends, not when the host comes to wait for it."""
    start = getattr(arr, "copy_to_host_async", None)
    if start is not None:
        try:
            start()
        except Exception:  # noqa: BLE001 - a hint, never a failure
            pass


def prefill_rows_for(num_slots: int) -> int:
    """``R``, the rows of the compact prefill program, for a pool of
    ``num_slots``: a fifth of the pool, never under 8 rows (nor over
    the pool).  A rule and not an ``Engine`` argument, because neither
    end is a choice a deployment gains from.  A prefill step costs its
    rows (1.1 ms a row of 32 positions at Mistral-7B widths, whether
    the row carries a prompt or not), so every row over what the
    traffic fills is paid for at every step, and a prompt waits as long
    behind four steps of ``R`` as behind one of ``4 R``.  Under some 8
    rows a step is paid for by reading the weights, so fewer rows buy
    nothing.  And the rows must outnumber what the traffic keeps
    prefilling, or prompts queue for prefill while slots stand empty of
    decode work: conversation traffic (prompts of 36 chunks, 211 output
    tokens) keeps 15 % of the pool prefilling, 9.3 of 64 slots.  At 64
    slots, 8 / 10 / 12 / 16 / 32 rows read 1380 / 1572 / 1530 / 1388 /
    890 tokens a second where the pool-wide program read 565 (PERF.md
    section 6, PR 27): the best lie just over the traffic's share, and
    a fifth is the least that leaves that share a quarter of room."""
    return min(num_slots, max(8, num_slots // 5))


class Engine:
    """Continuous-batching inference engine over a slot-pooled KV cache.

    Example::

        flat = mpmd_params_for_generation(model, params)   # or spmd_...
        eng = Engine(cfg, flat, num_slots=4, max_len=64)
        rid = eng.submit(prompt_tokens, max_new_tokens=16, eos_id=2)
        eng.run()                       # or step() under your own loop
        tokens = eng.result(rid)        # np.int32 [n]

    ``hbm_budget_bytes`` turns on admission control: the slot cap comes
    from :func:`torchgpipe_tpu.tune.serving_max_slots`'s ``eval_shape``
    accounting of the pool (+ resident param bytes, double-buffered
    unless ``donate=True``), and the POOL ITSELF is clamped to it before
    allocation — a pool that fits is guaranteed to KEEP fitting under
    any churn, because churn only changes values.

    ``temperature=0`` (default) is greedy — the mode whose outputs are
    bit-matched against :func:`~torchgpipe_tpu.models.generation.
    generate` per-request; sampling takes ``rng`` and applies the same
    temperature/top-k/top-p filter chain ``generate`` uses, batched over
    slots.

    What a step covers.  Decode runs over all ``num_slots`` rows of the
    pool.  Prefill runs over ``prefill_rows`` rows (``R``): the first
    ``R`` pending prompts in admission order, each absorbing up to
    ``prefill_chunk`` tokens, so the step costs what it prefills and
    time to first token stays first-come first-served; further pending
    prompts wait one prefill step (counted in
    ``serving_prefill_deferred_rows``; ``serving_prefill_rows`` over
    ``serving_prefill_row_capacity`` is the program's fill share).
    ``R`` comes from ``num_slots`` by :func:`prefill_rows_for` and is
    not an argument: there is ONE compact program per ladder bucket, so
    every program exists after one warm-up request.
    """

    def __init__(
        self,
        cfg: TransformerConfig,
        params: Sequence[Pytree],
        *,
        num_slots: int,
        max_len: int,
        prefill_chunk: Any = 8,
        kv_quant: bool = False,
        cache_dtype: Optional[Any] = None,
        moe: Optional[Any] = None,
        temperature: float = 0.0,
        top_k: Optional[int] = None,
        top_p: Optional[float] = None,
        prefix_cache: Optional[Any] = None,
        rng: Optional[jnp.ndarray] = None,
        hbm_budget_bytes: Optional[int] = None,
        overhead_bytes: int = 0,
        wave_admission: bool = False,
        metrics: Optional[ServingMetrics] = None,
        registry: Optional[Any] = None,
        reporter: Optional[Any] = None,
        recorder: Optional[Any] = None,
        clock: Callable[[], float] = time.monotonic,
        preemption: Optional[Any] = None,
        checkpoint_manager: Optional[Any] = None,
        guard_policy: Optional[GuardPolicy] = None,
        sleep: Callable[[float], None] = time.sleep,
        donate: bool = False,
        role: str = "unified",
        qos: Optional[Any] = None,
    ) -> None:
        self.cfg = cfg
        self.params = list(params)
        _split_params(cfg, self.params)  # validates the per-layer list
        # Param VERSION label (live rollout, fleet/rollout.py): every
        # response/flight event stamps the version its tokens were
        # produced under; :meth:`swap_params` bumps it in place.
        self.version = 0
        _check_decodable(cfg, max_len)
        self.moe = moe
        if moe is not None and getattr(moe, "router", "topk") == "expert_choice":
            raise ValueError(
                "expert_choice routing selects the top-C tokens PER "
                "EXPERT across the batch — at decode time the batch is "
                "one token per slot, so the experts compete over "
                "UNRELATED streams and a slot's token can be chosen by "
                "no expert (it silently emits the zero vector, "
                "corrupting that stream); serve MoE models with "
                "token-choice routing (router='topk'), which routes "
                "every token independently of its batch neighbours"
            )
        # A latent-attention model (``cfg.mla``) is served by the plain
        # pool and the two step programs; what moves or shares K/V rows
        # between slots or pools is written for K and V banks.
        if cfg.mla is not None:
            for on, what in (
                (kv_quant, "kv_quant (the int8 QuantKVCache)"),
                (prefix_cache is not None, "a prefix cache"),
                (role != "unified", f"role={role!r} (KV-row migration)"),
            ):
                if on:
                    raise NotImplementedError(
                        f"{what} copies or quantizes K and V rows; a "
                        "latent-attention model's pool holds the KV "
                        "latent (generation.LatentCache) — serve it "
                        "with the plain unified engine"
                    )
        # The same for a model that mixes window and full layers: its
        # window layers' rows live in rings (``kv_cache.layer_rows``),
        # and what copies, ships or quantizes rows takes rows that lie
        # at their position.
        for on, what in (
            (kv_quant, "kv_quant (the int8 QuantKVCache)"),
            (prefix_cache is not None, "a prefix cache (a donor's ring "
             "has dropped the prefix's rows)"),
            (role != "unified", f"role={role!r} (KV-row migration)"),
        ):
            if on:
                kv_cache.refuse_rings(cfg, what)
        # And for a hybrid model whose mixer layers keep a recurrent
        # state a slot (``kv_cache.HybridCache``): it has no row at a
        # position to copy, ship or quantize, and a preempted stream
        # would re-absorb its tokens in prompt chunks where it went one
        # token a step — the state's sums in another order, so not the
        # bitwise resumption preemption promises.
        for on, what in (
            (kv_quant, "kv_quant (the int8 QuantKVCache)"),
            (prefix_cache is not None, "a prefix cache (RadixPrefixCache)"),
            (role != "unified", f"role={role!r} (KV-row migration)"),
            (qos is not None, "QoS preemption (qos=)"),
        ):
            if on:
                kv_cache.refuse_state(cfg, what)
        # Expert layers that are told what they hold (``MoEConfig.held``)
        # report the tokens each held expert received: the step programs
        # then return, in the place of their sampled tokens, the pair
        # ``(tokens, int32 [expert layers, held])``.  The counts ride on
        # the step's record and are read where the host waits for that
        # step, never on their own account (``read_expert_counts``).
        self._expert_counts = (
            moe is not None and getattr(moe, "held", None) is not None
        )
        # ``prefill_chunk`` may be an int (one prefill program — the
        # classic configuration) or a LADDER of chunk sizes (e.g.
        # ``(1, 2, 4, 8)``): one program per bucket, a prefill step
        # dispatching the smallest bucket that covers its work, so short
        # prompts stop paying the max chunk's FLOPs while the program
        # count stays statically bounded at ``len(ladder) + 1``
        # (certified by ``analysis.serving.lint_serving``).
        self.prefill_buckets = normalize_buckets(prefill_chunk)
        self.prefill_chunk = self.prefill_buckets[-1]
        # Phase role (disaggregated serving, DistServe/Splitwise-style):
        # a ``prefill`` engine runs ONLY the bucket ladder and parks each
        # request at prompt completion for migration to a decode replica;
        # a ``decode`` engine runs ONLY ``decode`` + the fixed-shape
        # ``migrate_ingest`` program and receives work exclusively via
        # :meth:`ingest_migration`.  ``unified`` is the classic engine.
        # Disaggregation strictly SHRINKS each replica's program set —
        # ``analysis.serving.certify_disagg`` proves the per-role bound.
        if role not in ("unified", "prefill", "decode"):
            raise ValueError(
                f"role must be 'unified' | 'prefill' | 'decode', "
                f"got {role!r}"
            )
        self.role = role
        if role == "decode" and prefix_cache is not None:
            raise ValueError(
                "a decode-role engine never prefills, so a prefix cache "
                "would never be consulted — attach it to the prefill "
                "pool, whose completed prompts become the donors"
            )
        self.temperature = float(temperature)
        self.top_k = top_k
        self.top_p = top_p
        if self.temperature > 0.0 and rng is None:
            raise ValueError(
                "temperature sampling needs rng=jax.random.PRNGKey"
            )
        self._key = rng if rng is not None else jax.random.PRNGKey(0)
        self.donate = donate
        max_active: Optional[int] = None
        if hbm_budget_bytes is not None:
            from torchgpipe_tpu.tune import serving_max_slots, tree_bytes

            max_active = serving_max_slots(
                cfg, max_len, hbm_budget_bytes,
                kv_quant=kv_quant, dtype=cache_dtype,
                param_bytes=tree_bytes(self.params),
                overhead_bytes=overhead_bytes,
                donated=donate, chunk=self.prefill_chunk,
            )
            if max_active < 1:
                raise ValueError(
                    "admission cap is 0 slots: the cache pool does not "
                    "fit the HBM budget — shrink max_len/num_slots or "
                    "raise the budget (tune.serving_max_slots accounting)"
                )
            # The cap must bound ALLOCATED memory, not just active rows:
            # the pool's banks pin HBM at build time (BEFORE any request
            # arrives), so the pool itself is clamped to the cap here.
            num_slots = min(num_slots, max_active)
        self.pool = CachePool(
            cfg, num_slots, max_len, kv_quant=kv_quant, dtype=cache_dtype,
            chunk=self.prefill_chunk,
        )
        # ``qos`` (serving.qos.QosPolicy) — ONE shared instance across a
        # fleet's engines: tier-ordered admission, per-tenant token
        # budgets, and pressure preemption of batch-tier streams.  The
        # policy object must sit on the BASE registry so a tenant's
        # spend survives its requests migrating replicas.
        self.qos = qos
        self.scheduler = Scheduler(
            self.pool, prefill_chunk=self.prefill_buckets,
            max_active=max_active, wave_admission=wave_admission,
            qos=qos,
        )
        # ``registry`` (torchgpipe_tpu.obs.MetricsRegistry) shares the
        # engine's counters + TTFT/TPOT histograms with the rest of the
        # process's telemetry; ``reporter`` (obs.StepReporter) ticks per
        # engine iteration — periodic structured log lines for the
        # serving loop (docs/observability.md).
        self.metrics = metrics or ServingMetrics(
            clock=clock, registry=registry
        )
        self.metrics.pool_bytes = self.pool.bytes_by_kind()
        # A slot's recurrent state, every mixer layer (0 without one).
        self._slot_state_bytes = self.metrics.pool_bytes.get(
            "state", 0) // num_slots
        self.reporter = reporter
        # ``recorder`` (obs.FlightRecorder) threads a per-request span
        # record through the serving loop: submit/admit, the prefix-
        # cache copy, each prefill chunk, coalesced decode-step groups,
        # and finish/preemption — every event carrying ``rid=`` as the
        # correlation key ``obs.reqtrace.stitch_request`` rebuilds a
        # request's cross-replica span tree from.  Pure host-side ring
        # appends: trace-inert (never a traced value, never a program-
        # cache token) and zero-cost when None.
        self.recorder = recorder
        # The span spine (utils.tracing): every iteration that runs a
        # program records ``engine.step`` with ``engine.admit`` and the
        # action (``engine.prefill`` / ``engine.decode``) under it, and
        # ``engine.build`` / ``dispatch`` / ``fetch`` / ``emit`` under
        # the action — into the process's bounded default timeline,
        # always on (docs/observability.md, "The trace spine").
        self.timeline = default_timeline()
        # Per-request coalescing of decode steps: one ``req_decode``
        # flight event per GROUP (flushed at finish/preempt), not one
        # per token — a 4096-event ring must hold whole requests.
        self._decode_groups: Dict[str, List[float]] = {}
        # Radix prefix-sharing KV cache (torchgpipe_tpu.fleet.
        # prefix_cache): admission consults the trie before prefilling —
        # a request whose prompt extends a cached prefix COPIES the
        # donor slot's KV rows (one fixed-shape compiled program) and
        # prefills only the remainder; completed prefills insert their
        # prompt, pinning the slot via the pool refcounts.
        self._prefix_cache = prefix_cache
        # drain hooks: called with the snapshot dict after every drain —
        # the fleet router registers here so a draining replica's
        # in-flight requests can resume elsewhere.
        self.drain_hooks: List[Callable[[Dict[str, Any]], None]] = []
        self.guard_policy = guard_policy or GuardPolicy()
        self._sleep = sleep
        self._preemption = preemption
        self._checkpoint_manager = checkpoint_manager
        self._drain_requested = False
        self._draining = False
        self._last_drain_sid: Optional[int] = None
        if preemption is not None and hasattr(preemption, "add_callback"):
            preemption.add_callback(self.request_drain)
        self._requests: Dict[str, Request] = {}
        # Requests parked at prompt completion on a prefill-role engine,
        # awaiting handoff to the decode pool: OUT of the scheduler (no
        # step touches them) but still holding their slot — the KV rows
        # ARE the migration payload, freed by :meth:`complete_migration`.
        self._migration_ready: List[Request] = []
        # Every slot's current token.  The DEVICE vector is what the
        # step programs read and write (``None``: upload the mirror);
        # the host mirror is filled at delivery and is what ``drain`` /
        # ``preempt_request`` / speculative decoding read.
        self._cur_tok = np.zeros((num_slots,), np.int32)
        self._tok_dev: Optional[jnp.ndarray] = None
        # The step in flight: launched, advanced, not yet delivered.
        self._inflight: Optional[_Launched] = None
        self._delivering = False
        # Device-resident slot frontiers: the compiled steps RETURN the
        # advanced lengths vector, so steady-state decode re-feeds the
        # previous step's output instead of uploading the host mirror
        # every iteration.  ``_lengths_shadow`` records what the device
        # array holds; any host-side mutation the step didn't mirror
        # (slot alloc/free on admission, eviction, drain) makes the
        # cheap per-step compare miss and triggers ONE re-upload.
        self._lengths_dev: Optional[jnp.ndarray] = None
        self._lengths_shadow: Optional[np.ndarray] = None
        self._rid_counter = 0
        # Program names: the classic single-bucket engine keeps the
        # historical "prefill" name; a ladder names each bucket's
        # program "prefill@g".  ONE source of truth for the token-buffer
        # shapes: the real steps and the lint's step_input_specs() both
        # read this, so a shape that churned with the request mix could
        # not hide.
        self._prefill_names = (
            {} if role == "decode" else {
                g: (
                    "prefill" if len(self.prefill_buckets) == 1
                    else f"prefill@{g}"
                )
                for g in self.prefill_buckets
            }
        )
        self.trace_counts = {
            name: 0 for name in self._prefill_names.values()
        }
        # Rows of the compact prefill program (``R``): ONE value for
        # every ladder bucket, from the pool's size as clamped above.
        self.prefill_rows = prefill_rows_for(num_slots)
        self._token_shapes = {
            name: (self.prefill_rows, g)
            for g, name in self._prefill_names.items()
        }
        if role != "prefill":
            self.trace_counts["decode"] = 0
            self._token_shapes["decode"] = (num_slots, 1)
        # What a step's attention reads, by its program's (rows, g)
        # and by the kind of layer: the first layer of each kind the
        # model has stands for its kind (``window``, whose banks are a
        # ring where the model mixes layer types, and ``full``).
        kinds: Dict[str, int] = {}
        for i in range(cfg.n_layers):
            if cfg.layer_type(i) in ("block", "attention"):
                kinds.setdefault(kv_cache.layer_kind(cfg, i), i)
        self._attend_counters = {
            shape: {
                kind: attend_rows_counter(
                    self.cfg, self.pool.cache, *shape, layer=i)
                for kind, i in kinds.items()
            }
            for shape in set(self._token_shapes.values())
        }
        self._build_programs()

    # ------------------------------------------------------------------ #
    # compiled programs                                                  #
    # ------------------------------------------------------------------ #

    def _sample_row(self, logits, key):
        """[rows, vocab] f32 -> [rows] int32 (traced): ``generate``'s
        exact filter chain."""
        if self.temperature == 0.0:
            return jnp.argmax(logits, axis=-1).astype(jnp.int32), key
        key, sub = jax.random.split(key)
        return _sample(
            logits, sub, self.temperature, self.top_k, self.top_p
        ), key

    def _prefill_body_for(self, g: int, name: str) -> Callable[..., Tuple]:
        """The chunk program's python body at bucket ``g``, counted
        under ``name``.  The bucket is baked into the traced shape
        (``tokens [rows, g]``); the body is otherwise identical across
        buckets.  Called with ``slots [R]`` it is the COMPACT prefill
        program (row ``i`` is slot ``slots[i]``; ``tokens`` /
        ``n_valid`` / ``tok`` / ``grid`` have ``R`` rows); called with
        ``slots=None`` it is the pool-wide form, one row a slot — the
        shape ``fleet.SpeculativeEngine`` jits as its verify program
        (its rows ARE most of the pool; it passes ``cur_tok`` and
        ``finish`` as ``None``: its tokens are the host's to accept).
        The plain engine never calls that form, so never compiles it."""
        cfg, moe = self.cfg, self.moe
        counts = self.trace_counts

        def prefill_body(params, cache, lengths, cur_tok, slots, tokens,
                         n_valid, finish, key):
            counts[name] += 1
            # ``lengths`` comes back advanced ON DEVICE (at ``slots``,
            # by the rows each consumed): the next step reuses the
            # array instead of re-uploading the host mirror.
            last = jnp.clip(n_valid - 1, 0, g - 1)
            # The chunked prefill samples at each row's last valid
            # position and nothing else: the head runs there alone
            # (``logits_at``; at R x g positions its float32 logits
            # were 1.6 GiB of a 102 x 64 step over 65,536 rows of
            # vocabulary, described-chip compile).  Speculative
            # decoding's verify pass (``cur_tok`` None) takes every
            # position's: its per-POSITION greedy tokens [rows, g],
            # what the target model would emit after consuming each
            # input position, are the acceptance oracle
            # (fleet/speculative.py).
            verify = cur_tok is None
            logits, cache, lengths, *held = decode_slots(
                cfg, params, tokens, cache, lengths, n_valid, moe=moe,
                slots=slots, expert_counts=self._expert_counts,
                logits_at=None if verify else last,
            )
            row_logits = (jnp.take_along_axis(
                logits, last[:, None, None], axis=1
            ) if verify else logits)[:, 0]
            tok, key = self._sample_row(row_logits, key)
            grid = (jnp.argmax(logits, axis=-1).astype(jnp.int32)
                    if verify else None)
            if cur_tok is not None:
                # A row whose prompt completes here (``finish``: the
                # host knows it from counts) hands its first token to
                # its slot's entry of the device token vector, where
                # the next decode step reads it; every other row's
                # index is out of range and dropped.
                dst = jnp.where(finish, slots, cur_tok.shape[0])
                cur_tok = cur_tok.at[dst].set(tok, mode="drop")
            return ((tok, *held) if held else tok, grid, cache, lengths,
                    cur_tok, key)
        return prefill_body

    def _build_programs(self) -> None:
        cfg, moe = self.cfg, self.moe
        counts = self.trace_counts

        def decode_body(params, cache, lengths, cur_tok, n_valid, key):
            counts["decode"] += 1
            # ``cur_tok [num_slots]`` is the device token vector: the
            # rows that run consume their entry (the others a 0, as a
            # host-built buffer would hold) and get their sample
            # written back; the vector goes on to the next step.
            live = n_valid > 0
            tokens = jnp.where(live, cur_tok, 0)[:, None]
            logits, cache, lengths, *held = decode_slots(
                cfg, params, tokens, cache, lengths, n_valid, moe=moe,
                expert_counts=self._expert_counts,
            )
            tok, key = self._sample_row(logits[:, 0], key)
            tok = jnp.where(live, tok, cur_tok)
            return (tok, *held) if held else tok, cache, lengths, key

        donate = (1,) if self.donate else ()
        self._prefill_fns = {
            name: jax.jit(
                self._prefill_body_for(g, name), donate_argnums=donate
            )
            for g, name in self._prefill_names.items()
        }
        self._decode_fn = (
            None if self.role == "prefill"
            else jax.jit(decode_body, donate_argnums=donate)
        )

        # Two fixed-shape programs around ONE masked row copy
        # (``kv_cache.copy_rows``: rows [0, n) of a source into slot
        # ``dst`` of every bank): src/dst/n are traced VALUES, so one
        # program serves every reuse and every migration and the static
        # program count holds.  Bitwise either way: the source's rows
        # are exactly what this pool's own cold prefill of the same
        # tokens at the same positions writes (prefill is replica-
        # independent), so a reused or migrated request's cache equals
        # the cold one bit-for-bit and its greedy stream is unchanged
        # (the fleet-verify and disagg-verify gates).
        copy_donate = (0,) if self.donate else ()

        self._ingest_fn = None
        if self.role == "decode":
            counts["migrate_ingest"] = 0

            def ingest_body(cache, rows, dst, n):
                # The source is a migrated request's shipped rows (one
                # slot's worth — see ``export_kv_rows``).
                counts["migrate_ingest"] += 1
                return kv_cache.copy_rows(cache, rows, dst, n)

            self._ingest_fn = jax.jit(ingest_body, donate_argnums=copy_donate)

        self._prefix_copy_fn = None
        if self._prefix_cache is not None:
            counts["prefix_copy"] = 0

            def prefix_copy_body(cache, src, dst, n):
                # The source is the donor slot ``src`` of this pool.
                counts["prefix_copy"] += 1
                return kv_cache.copy_rows(cache, src, dst, n)

            self._prefix_copy_fn = jax.jit(
                prefix_copy_body, donate_argnums=copy_donate
            )

    @property
    def program_count(self) -> int:
        """The statically bounded compiled-program count: one compact
        prefill program per ladder bucket (all at the one row count
        ``prefill_rows``) plus the decode program (plus the one
        fixed-shape ``prefix_copy`` program when a prefix cache is
        attached) — the figure ``analysis.serving`` certifies and the
        compile-counter test confirms dynamically.  Disaggregation
        SHRINKS the bound per replica: a prefill pool drops the decode
        program, a decode pool is exactly ``decode`` +
        ``migrate_ingest``."""
        extra = 1 if self._prefix_cache is not None else 0
        if self.role == "prefill":
            return len(self.prefill_buckets) + extra
        if self.role == "decode":
            return 2
        return len(self.prefill_buckets) + 1 + extra

    def step_input_specs(self) -> Dict[str, Any]:
        """The (shape, dtype) signature of each compiled program's
        inputs — request-independent BY CONSTRUCTION (the real step
        builds its buffers from these same shapes), which is what
        :func:`torchgpipe_tpu.analysis.serving.lint_serving` certifies
        over a request-churn grid."""
        S = self.pool.num_slots
        sds = jax.ShapeDtypeStruct
        cache_spec = jax.tree_util.tree_map(
            lambda a: sds(a.shape, a.dtype), self.pool.cache
        )
        common = {
            "cache": cache_spec,
            "lengths": sds((S,), np.int32),
            "key": sds(self._key.shape, self._key.dtype),
        }
        # ``tokens`` / ``n_valid`` have the program's rows: the pool's
        # ``num_slots`` for decode, whose ``tokens`` IS the device
        # token vector; ``R`` for the compact prefill programs, which
        # also take the rows' slot indices, the token vector
        # (``cur_tok``) and the rows that complete a prompt
        # (``finish``).
        specs = {
            kind: dict(
                common, tokens=sds(shape, np.int32),
                n_valid=sds(shape[:1], np.int32),
            )
            for kind, shape in self._token_shapes.items()
        }
        if "decode" in specs:
            specs["decode"]["tokens"] = sds((S,), np.int32)
        for name in self._prefill_names.values():
            rows = self._token_shapes[name][:1]
            specs[name].update(
                slots=sds(rows, np.int32), finish=sds(rows, np.bool_),
                cur_tok=sds((S,), np.int32),
            )
        if self._prefix_copy_fn is not None:
            scalar = sds((), np.int32)
            specs["prefix_copy"] = {
                "cache": cache_spec, "src": scalar, "dst": scalar,
                "n": scalar,
            }
        if self._ingest_fn is not None:
            scalar = sds((), np.int32)
            specs["migrate_ingest"] = {
                "cache": cache_spec, "rows": self.kv_row_specs(),
                "dst": scalar, "n": scalar,
            }
        return specs

    def kv_row_specs(self) -> Dict[str, Any]:
        """The (shape, dtype) signature of ONE slot's migration payload:
        per-layer KV rows (+ int8 scale rows) with the slot axis sliced
        away — exactly what :meth:`export_kv_rows` produces and the
        ``migrate_ingest`` program consumes.  Cross-pool compatibility
        in a disaggregated fleet is certified by comparing these specs
        between the prefill and decode engines
        (``analysis.serving.certify_disagg``)."""
        if self.cfg.mla is not None:
            raise NotImplementedError(
                "KV-row migration is written for K and V banks; a "
                "latent-attention pool holds the KV latent"
            )
        kv_cache.refuse_rings(self.cfg, "KV-row migration (kv_row_specs)")
        kv_cache.refuse_state(self.cfg, "KV-row migration (kv_row_specs)")
        return kv_cache.slot_row_specs(self.pool.cache)

    def _token_buffer(self, kind: str) -> np.ndarray:
        return np.zeros(self._token_shapes[kind], np.int32)

    def _attended(self, pos0: np.ndarray, n_valid: np.ndarray,
                  g: int) -> Dict[str, Tuple[int, int]]:
        """``(rows read, row capacity)`` of a layer's cache attention in
        the step about to run (``generation.attend_rows_counter``), by
        the kind of layer."""
        return {
            kind: count(pos0, n_valid)
            for kind, count in self._attend_counters[len(n_valid), g].items()
        }

    def _annotate_attended(
        self, attended: Dict[str, Tuple[int, int]]
    ) -> None:
        """The action span's ``rows_read`` / ``rows_cap`` (one layer of
        each kind, summed) and the same by kind where the model has
        both (``rows_read_window``, ``rows_cap_full``, ...)."""
        read, cap = map(sum, zip(*attended.values()))
        by_kind = {
            f"rows_{what}_{kind}": v
            for kind, pair in attended.items()
            for what, v in zip(("read", "cap"), pair)
        } if len(attended) > 1 else {}
        self.timeline.annotate(rows_read=read, rows_cap=cap, **by_kind)

    def _state_moved(self, pos0: np.ndarray, n_valid: np.ndarray) -> int:
        """Recurrent-state bytes the step about to run reads and writes
        (a hybrid model's; 0 otherwise): a row that runs writes its
        slot's tails and states and reads them unless its frontier is
        0.  Annotated on the action span (``state_bytes``)."""
        if not self._slot_state_bytes:
            return 0
        runs = n_valid > 0
        moved = int(runs.sum() + (runs & (pos0 > 0)).sum())
        moved *= self._slot_state_bytes
        self.timeline.annotate(state_bytes=moved)
        return moved

    def _lengths_for_step(self) -> jnp.ndarray:
        """The frontier vector for the next compiled step: the previous
        step's device output when the host mirror still matches its
        shadow, else one fresh upload (``pool.lengths_device()``'s copy
        semantics).  Steady-state decode — no admissions, no evictions —
        pays ZERO host→device lengths transfers."""
        if self._lengths_dev is None or not np.array_equal(
            self.pool.lengths, self._lengths_shadow
        ):
            self._lengths_dev = self.pool.lengths_device()
            self._lengths_shadow = np.array(self.pool.lengths, copy=True)
        return self._lengths_dev

    def _tokens_for_step(self) -> jnp.ndarray:
        """The device token vector for the next compiled step: the last
        step's output, or one upload of the host mirror where something
        other than a step wrote a slot's token (``ingest_migration``,
        which settles first, so the mirror is whole)."""
        if self._tok_dev is None:
            self._tok_dev = jnp.asarray(self._cur_tok.copy())
        return self._tok_dev

    def _dispatch(self, fn: Callable[..., Tuple], *args: Any,
                  wait: bool = True) -> Tuple:
        """Call a compiled program under the transient-retry policy (the
        serving twin of StepGuard's retry half; inputs are not donated
        unless ``donate=True``, in which case retry is impossible and
        transient errors re-raise immediately).

        jit dispatch is ASYNC: a device-execution failure surfaces on
        materialization.  With ``wait`` the result is materialized HERE,
        under the retry, and the caller commits it only after this
        returns: A FAILED STEP'S ARRAYS ARE NEVER LEFT IN THE POOL.
        Every program of an engine that can retry (``donate=False``) is
        called so, the two step programs included — the pool then holds
        the pre-step arrays whenever this raises, and the host's books
        have not moved.  ``wait=False`` is for the step programs of a
        DONATING engine alone (:meth:`_launch`): their inputs are
        consumed by the call, so there is no pre-step state to keep, a
        retry is impossible wherever the failure is met, and it is met
        where the host waits for the step (:meth:`_wait`), one step
        later — the step in flight is dropped (:meth:`_abandon`), the
        error re-raises, and the engine is good for ``drain()`` (a
        host-side snapshot, the router's failover) and nothing else,
        as it was when a donated step failed under this call."""
        attempt = 0
        with self.timeline.span("engine.dispatch"):
            while True:
                try:
                    out = fn(*args)
                    return jax.block_until_ready(out) if wait else out
                except Exception as err:  # noqa: BLE001 — classified below
                    if (
                        self.donate
                        or classify_error(err) != "transient"
                        or attempt >= self.guard_policy.max_retries
                    ):
                        raise
                    delay = self.guard_policy.backoff(attempt)
                    attempt += 1
                    self.metrics.retries += 1
                    self._sleep(delay)

    @property
    def compile_stats(self) -> Dict[str, int]:
        """Times each program's python body was TRACED — the zero-retrace
        contract is ``{'prefill': 1, 'decode': 1}`` after warmup."""
        return dict(self.trace_counts)

    # ------------------------------------------------------------------ #
    # live param rollout (fleet/rollout.py)                              #
    # ------------------------------------------------------------------ #

    def swap_params(self, params: Sequence[Pytree], version: int) -> None:
        """In-place param refresh: serve a NEW weight version with zero
        rebuild.  The compiled programs take ``params`` as a traced
        ARGUMENT, so replacing the list with one whose every leaf keeps
        its (shape, dtype) signature triggers ZERO retraces — the KV
        pool, the program cache and every in-flight request are
        untouched, and subsequent steps simply read the new weights
        (``analysis.serving.certify_swap`` is the static twin of this
        check).  A swap that changes any leaf signature would recompile
        every program mid-serve and is REFUSED — cold-start a fresh
        engine for a re-shaped model.

        Call only on a drained/idle replica (the rollout controller
        drains first): swapping under live decode would splice two
        versions into one stream.  After the swap the engine's streams
        are bitwise what a fresh engine cold-started on ``params``
        produces — the ``rollout-verify`` gate.
        """
        self._settle()
        new = list(params)
        _split_params(self.cfg, new)    # validates the per-layer list

        def sig(tree: Any) -> List[Tuple[Tuple[int, ...], str]]:
            return [
                (tuple(a.shape), str(a.dtype))
                for a in jax.tree_util.tree_leaves(tree)
            ]

        if sig(new) != sig(self.params):
            raise ValueError(
                "swap_params: the published params change a leaf "
                "(shape, dtype) signature — an in-place swap would "
                "retrace every compiled program mid-serve, so a "
                "new-version compile is refused; cold-start a fresh "
                "Engine for a re-shaped model "
                "(analysis.serving.certify_swap names the mismatch)"
            )
        self.params = new
        self.version = int(version)
        if self.recorder is not None:
            self.recorder.record(
                "param_swap", detail=f"version={self.version}"
            )

    # ------------------------------------------------------------------ #
    # request-scoped flight recording                                    #
    # ------------------------------------------------------------------ #

    def _rec(self, kind: str, rid: str, *, dur: Optional[float] = None,
             detail: str = "") -> None:
        """One rid-keyed flight event (no-op without a recorder)."""
        if self.recorder is not None:
            self.recorder.record(kind, rid=rid, dur=dur, detail=detail)

    def _rec_clock(self) -> float:
        """The recorder's clock (0.0 without one — callers only use the
        value when a recorder exists, so durs stay self-consistent with
        the recorder's own event timestamps)."""
        return self.recorder.clock() if self.recorder is not None else 0.0

    def _flush_decode_group(self, rid: str) -> None:
        """Emit the coalesced decode-step span for ``rid`` (if any):
        dur spans first-step start to last-step end, detail carries the
        step count."""
        group = self._decode_groups.pop(rid, None)
        if group is None or self.recorder is None:
            return
        t0, t1, steps = group
        self._rec("req_decode", rid, dur=max(t1 - t0, 0.0),
                  detail=f"steps={int(steps)}")

    # ------------------------------------------------------------------ #
    # request API                                                        #
    # ------------------------------------------------------------------ #

    def submit(
        self,
        prompt: Any,
        max_new_tokens: int,
        *,
        rid: Optional[str] = None,
        eos_id: Optional[int] = None,
        on_token: Optional[Callable[[str, int], None]] = None,
        emitted_prefix: Sequence[int] = (),
        tier: str = "standard",
        tenant: Optional[str] = None,
    ) -> str:
        """Queue a request; returns its id.  Admission happens between
        engine iterations (a free slot + the admission cap permitting).
        """
        if self.role == "decode":
            raise ValueError(
                "decode-role engine: work arrives via ingest_migration() "
                "from a prefill replica, never submit() — route "
                "admissions to the prefill pool"
            )
        check_tier(tier)     # before any registration (no phantom state)
        if rid is None:
            self._rid_counter += 1
            rid = f"r{self._rid_counter}"
        self._check_rid_free(rid)
        req = Request(
            rid=rid,
            prompt=np.asarray(prompt, np.int32).reshape(-1),
            max_new_tokens=int(max_new_tokens),
            eos_id=eos_id,
            on_token=on_token,
            emitted_prefix=list(emitted_prefix),
            tier=tier,
            tenant=tenant,
        )
        self.scheduler.submit(req)   # validates before registration
        self._requests[rid] = req
        self.metrics.arrived(rid)
        # Recorded only AFTER validation accepted the request — a
        # rejected submit must leave no phantom span behind (the same
        # contract the router keeps for its records).
        phase = "" if self.role == "unified" else f" phase={self.role}"
        tenant_tag = "" if tenant is None else f" tenant={tenant}"
        self._rec(
            "req_submit", rid,
            detail=(
                f"prompt={req.prompt_len} new={req.max_new_tokens} "
                f"queued={self.scheduler.queue_depth}"
                f" tier={tier}{tenant_tag}"
                f" version={self.version}{phase}"
            ),
        )
        return rid

    def _check_rid_free(self, rid: str) -> None:
        """A rid may legitimately RETURN to an engine that served it
        before — failover and drain/unpark cycles bounce unfinished
        requests between replicas, and in a disaggregated fleet every
        resumption re-prefills before re-migrating — but only once its
        prior incarnation here is inert.  A still-live duplicate is a
        real bug and stays an error."""
        old = self._requests.get(rid)
        if old is not None and old.status in (
            "queued", "active", "migrating", "finished"
        ):
            raise ValueError(f"duplicate request id {rid!r}")

    def cancel(self, rid: str) -> bool:
        self._settle()
        ok = self.scheduler.cancel(rid)
        if ok:
            self.metrics.finished(rid, status="cancelled")
            self._flush_decode_group(rid)
            self._rec("req_finish", rid, detail="status=cancelled")
        return ok

    def result(self, rid: str) -> np.ndarray:
        """All tokens request ``rid`` has produced so far (across a
        drain/resume), as ``np.int32 [n]``."""
        self._settle()
        return np.asarray(self._requests[rid].tokens(), np.int32)

    def status(self, rid: str) -> str:
        self._settle()
        return self._requests[rid].status

    # ------------------------------------------------------------------ #
    # the loop                                                           #
    # ------------------------------------------------------------------ #

    def step(self) -> bool:
        """ONE engine iteration: admit, pick a phase, build its inputs
        from the ADVANCED state, launch its compiled program — and only
        then wait for the PREVIOUS step's tokens and deliver them, so
        the device runs this step while the host hands out the last
        one's tokens and builds the next (the module docstring, "One
        step in flight").  A call that finds no action but a step in
        flight settles it (under an ``engine.settle`` span: an
        ``engine.step`` span is one launched program) and returns True;
        False means idle, and then nothing is in flight."""
        tl = self.timeline
        action = None
        with tl.span("engine.step") as step_span:
            with tl.span("engine.admit") as admit_span:
                admitted = 0
                if not self._draining:
                    if self.qos is not None:
                        self._preempt_for_pressure()
                    if (
                        self._prefix_cache is not None
                        and self.scheduler.queue
                        and self.pool.num_free == 0
                    ):
                        # Admission pressure: evict idle prefix entries
                        # (their pins are the only remaining references)
                        # so queued requests beat cached prefixes to
                        # slots.
                        self._prefix_cache.reclaim(
                            self.pool, len(self.scheduler.queue)
                        )
                    for req in self.scheduler.admit():
                        self.metrics.admitted(req.rid)
                        self._on_admit(req)
                        admitted += 1
                action = self.scheduler.next_action()
                if action is None:
                    # The timeline holds ``engine.step`` only for
                    # iterations that launched a program.
                    admit_span.drop()
                    step_span.drop()
                else:
                    # What admission left behind: who still waits, and
                    # the room there is for them.
                    tl.annotate(admitted=admitted,
                                queued=len(self.scheduler.queue),
                                free=self.pool.num_free,
                                slots=self.pool.num_slots)
            if action is not None:
                # The action's span opens as soon as the action is
                # known, before its program runs; ``_run_*`` add ``rows``
                # (and ``g``) and record build / dispatch / fetch / emit
                # under it.
                with tl.span("engine." + action):
                    if action == "prefill":
                        ahead = self._run_prefill()
                    else:
                        ahead = self._run_decode()
                    if self.reporter is not None:
                        with tl.span("engine.emit"):
                            self.reporter.step()
                # ``ahead``: the program was launched while the step
                # before it was still in flight.
                tl.annotate(ahead=int(ahead))
        if action is not None:
            return True
        if self._inflight is None:
            return False
        with tl.span("engine.settle"):
            self._settle()
        return True

    def _preempt_for_pressure(self) -> None:
        """QoS pressure valve (runs before admission): when queued work
        OUTRANKS an active preemptible stream and admission is blocked
        (no free slot, or the cap is reached), evict ONE preemptible
        active request through the same teacher-forced snapshot path
        drain uses and requeue it here — it resumes bitwise (greedy
        decode is prefix-deterministic) once pressure clears.  At most
        one eviction per engine iteration; interactive/standard streams
        are never preempted."""
        sched = self.scheduler
        if not sched.queue:
            return
        if sched.pool.num_free > 0 and len(sched.active) < sched.max_active:
            return      # admission can proceed — nothing to yield
        # Whether admission is blocked, and by whom, depends on who has
        # just ended by EOS and on what the tenants have spent: token
        # values.  Under pressure the step in flight is settled first.
        self._settle()
        if sched.pool.num_free > 0 and len(sched.active) < sched.max_active:
            return
        from torchgpipe_tpu.serving.qos import TIER_PRIORITY

        want = min(
            TIER_PRIORITY[self.qos.effective_tier(r.tier, r.tenant)]
            for r in sched.queue
        )
        victims = [
            r for r in sched.active.values()
            if self.qos.preemptible(r.tier)
            and TIER_PRIORITY[r.tier] > want
        ]
        if not victims:
            return
        # Most recently admitted among the worst-priority preemptibles:
        # deterministic, and the stream with the least progress to redo.
        worst = max(TIER_PRIORITY[r.tier] for r in victims)
        victim = [r for r in victims if TIER_PRIORITY[r.tier] == worst][-1]
        kwargs = self.preempt_request(victim.rid)
        self.qos.note_preemption()
        self.submit(**kwargs)

    def preempt_request(self, rid: str) -> Dict[str, Any]:
        """Evict one ACTIVE request NOW (its slot frees immediately) and
        return the ``submit()`` kwargs that resume it: prompt extended
        by the tokens already emitted (teacher-forced), budget shrunk,
        ``emitted_prefix`` extended — exactly the drain/restore schema,
        per-request.  Greedy decode is prefix-deterministic, so the
        resumed stream is bitwise the unpreempted one."""
        kv_cache.refuse_state(self.cfg, "preempt_request (a bitwise "
                              "resumption from re-absorbed tokens)")
        self._settle()
        req = self.scheduler.active.get(rid)
        if req is None:
            raise ValueError(
                f"request {rid!r} is not active — nothing to preempt"
            )
        generated = list(req.generated)
        kwargs: Dict[str, Any] = {
            "rid": req.rid,
            "prompt": np.concatenate([
                np.asarray(req.prompt, np.int32),
                np.asarray(generated, np.int32),
            ]) if generated else np.asarray(req.prompt, np.int32),
            "max_new_tokens": req.max_new_tokens - len(generated),
            "eos_id": req.eos_id,
            "on_token": req.on_token,
            "emitted_prefix": list(req.emitted_prefix) + generated,
            "tier": req.tier,
            "tenant": req.tenant,
        }
        req.status = "preempted"
        self.scheduler.release(req)
        self.metrics.finished(rid, status="preempted")
        self._flush_decode_group(rid)
        self._rec(
            "req_preempt", rid,
            detail=f"qos tier={req.tier} emitted={len(generated)}",
        )
        return kwargs

    def _on_admit(self, req: Request) -> None:
        """Per-admission hook: prefix-cache consult here; subclasses
        extend (``fleet.SpeculativeEngine`` resets the recycled slot's
        draft frontier)."""
        if self._slot_state_bytes:
            # The slot's state is zero for this tenant: its first chunk
            # runs at frontier 0, where the mixers read zeros.
            self.metrics.state_zeroed_slots += 1
        if self.recorder is not None:
            times = self.metrics.requests.get(req.rid)
            wait = times.queue_wait if times is not None else None
            self._rec("req_admit", req.rid, dur=wait,
                      detail=f"slot={req.slot}")
        if self._prefix_cache is not None:
            self._apply_prefix_reuse(req)

    def _apply_prefix_reuse(self, req: Request) -> None:
        """Admission-time trie consult: when the prompt extends a cached
        prefix, copy the donor slot's KV rows into the request's slot
        (one fixed-shape compiled dispatch, bitwise-equal to cold
        prefill of the same tokens) and mark the prefix absorbed.  At
        most ``prompt_len - 1`` tokens reuse — the LAST prompt token
        always prefills, producing the first-token logits."""
        pc = self._prefix_cache
        m, donor = pc.match(req.prompt, limit=req.prompt_len - 1)
        if m <= 0 or donor is None:
            return
        assert req.slot is not None
        # The copy is ordered on the device behind the step in flight,
        # and the donor's books were advanced when that step was
        # launched; it is waited for HERE, under its own retry, so the
        # step in flight is settled first (a failure of that step is
        # met at its own wait, not inside the copy's).
        self._settle()
        t0 = self._rec_clock()
        new_cache = self._dispatch(
            self._prefix_copy_fn, self.pool.cache,
            jnp.int32(donor), jnp.int32(req.slot), jnp.int32(m),
        )
        self.pool.cache = new_cache
        self.pool.lengths[req.slot] = m      # shadow miss -> re-upload
        req.prefilled = m
        self.metrics.prefix_hit(m)
        self._rec("req_prefix_copy", req.rid,
                  dur=max(self._rec_clock() - t0, 0.0),
                  detail=f"reused={m} donor_slot={donor}")

    def _run_prefill(self) -> bool:
        tl = self.timeline
        pending = self.scheduler.prefill_pending()
        # The compact step: the first R pending prompts in admission
        # order (oldest first — time to first token stays FIFO); the
        # rest wait for the next prefill step.
        cap = self.prefill_rows
        reqs = pending[:cap]
        deferred = len(pending) - len(reqs)
        # Ladder admission: the smallest bucket covering the largest
        # chunk among the rows this step takes — short prompts dispatch
        # a small program instead of paying the max chunk's FLOPs.
        g = self.scheduler.prefill_bucket(reqs)
        tl.annotate(rows=len(reqs), g=g, cap=cap, deferred=deferred)
        with tl.span("engine.build"):
            name = self._prefill_names[g]
            tokens = self._token_buffer(name)           # [R, g]
            # Row i is slot slots[i]; rows past len(reqs) are padding
            # (slot 0, n_valid 0: they write and advance nothing).
            slots = np.zeros((tokens.shape[0],), np.int32)
            n_valid = np.zeros((tokens.shape[0],), np.int32)
            # Rows whose prompt completes in this step: they sample
            # their first token (known from counts, not from a token).
            finish = np.zeros((tokens.shape[0],), np.bool_)
            for i, r in enumerate(reqs):
                take = min(g, r.prompt_len - r.prefilled)
                tokens[i, :take] = (
                    r.prompt[r.prefilled:r.prefilled + take]
                )
                slots[i] = r.slot
                n_valid[i] = take
                finish[i] = r.prefilled + take >= r.prompt_len
            lengths_in = self._lengths_for_step()
            args = [
                self.params, self.pool.cache, lengths_in,
                self._tokens_for_step(), jnp.asarray(slots),
                jnp.asarray(tokens), jnp.asarray(n_valid),
                jnp.asarray(finish), self._key,
            ]
        attended = self._attended(self.pool.lengths[slots], n_valid, g)
        self._annotate_attended(attended)
        moved = self._state_moved(self.pool.lengths[slots], n_valid)
        step = self._launch(
            "prefill", self._prefill_fns[name], args,
            rows=[(r, i) for i, r in enumerate(reqs) if finish[i]],
            positions=int(n_valid.sum()),
        )
        if self.recorder is not None:
            step.chunks = [
                (r.rid, g, int(take)) for r, take in zip(reqs, n_valid)
            ]
        # Subclass hook: speculative decoding mirrors every prefill
        # chunk into its draft model's cache (same bucket, same rows)
        # so draft and target stay frontier-aligned.
        self._after_prefill_dispatch(g, slots, tokens, n_valid)

        def advance(ahead: bool) -> None:
            shadow = np.zeros((self.pool.num_slots,), np.int32)
            shadow[slots[:len(reqs)]] = n_valid[:len(reqs)]
            self._lengths_shadow = self._lengths_shadow + shadow
            self.metrics.step(
                "prefill", len(reqs), cap, deferred=deferred,
                attended=attended, ahead=ahead, state_bytes=moved,
            )
            for i, r in enumerate(reqs):
                take = int(n_valid[i])
                self.pool.lengths[r.slot] += take
                r.prefilled += take
                if r.prefill_done:
                    if self._prefix_cache is not None:
                        # The slot holds the full prompt's KV once this
                        # step has run, and whatever copies from it runs
                        # behind this step on the device: it becomes a
                        # donor (the insert pins it via the pool
                        # refcounts, so recycling waits for eviction).
                        self._prefix_cache.insert(
                            r.prompt, r.slot, self.pool
                        )
                    self._advance_token(step, r)

        return self._after_launch(step, advance)

    def _run_decode(self) -> bool:
        tl = self.timeline
        reqs = self.scheduler.decode_ready()
        tl.annotate(rows=len(reqs))
        with tl.span("engine.build"):
            n_valid = np.zeros((self.pool.num_slots,), np.int32)
            for r in reqs:
                n_valid[r.slot] = 1
            lengths_in = self._lengths_for_step()
            # The rows' input tokens are the device token vector's
            # entries: the last programs' samples, never fetched for
            # this.
            args = [
                self.params, self.pool.cache, lengths_in,
                self._tokens_for_step(), jnp.asarray(n_valid), self._key,
            ]
        attended = self._attended(self.pool.lengths, n_valid, 1)
        self._annotate_attended(attended)
        moved = self._state_moved(self.pool.lengths, n_valid)
        step = self._launch(
            "decode", self._decode_fn, args,
            rows=[(r, r.slot) for r in reqs], positions=len(reqs),
        )

        def advance(ahead: bool) -> None:
            self._lengths_shadow = self._lengths_shadow + n_valid
            self.metrics.step(
                "decode", len(reqs), self.pool.num_slots,
                attended=attended, ahead=ahead, state_bytes=moved,
            )
            for r in reqs:
                self.pool.lengths[r.slot] += 1
                self._advance_token(step, r)

        return self._after_launch(step, advance)

    # ------------------------------------------------------------------ #
    # launch / advance / wait / deliver                                  #
    # ------------------------------------------------------------------ #

    def _launch(self, kind: str, fn: Callable[..., Tuple], args: List[Any],
                *, rows: List[Tuple[Request, int]],
                positions: int) -> _Launched:
        """Launch a step program and adopt its outputs.  A donating
        engine does not wait (``jit`` returns at once): the next step is
        built on the outputs, on the device, whether or not this one
        has run yet.  An engine that can retry waits HERE, under the
        retry (:meth:`_dispatch`), and adopts what is known good."""
        step = _Launched(
            kind=kind, tok=None, counts=None, positions=positions,
            rows=rows, t0=self._rec_clock(),
        )
        self._adopt(step, self._dispatch(fn, *args, wait=not self.donate))
        return step

    def _adopt(self, step: _Launched, out: Tuple) -> None:
        """Take a launched step's outputs as the engine's device state
        and the step's token vector (and expert counts) as what its
        delivery reads."""
        if step.kind == "prefill":
            tok, _grid, cache, lengths, cur_tok, key = out
        else:
            tok, cache, lengths, key = out
        if isinstance(tok, tuple):
            tok, step.counts = tok
            _start_host_copy(step.counts)
        if step.kind == "decode":
            cur_tok = tok       # the vector itself, samples written in
        step.tok = tok
        if step.rows:
            # Start the device→host token copy NOW: it runs the moment
            # the program ends (a hint; ``_wait`` materializes).
            _start_host_copy(tok)
        self.pool.cache = cache
        self._lengths_dev = lengths
        self._tok_dev = cur_tok
        self._key = key

    def _advance_token(self, step: _Launched, req: Request) -> None:
        """``req`` samples a token in ``step``: count it, and end the
        request BY LENGTH here, at launch — its slot frees NOW, the
        iteration-level eviction continuous batching is made of — where
        that token is its last.  The request stays ``active`` until the
        token is delivered."""
        req.in_flight += 1
        if req.remaining_launches <= 0:
            step.released.append(req)
            self.scheduler.release(req)

    def _after_launch(self, step: _Launched,
                      advance: Callable[[bool], None]) -> bool:
        """The second half of an iteration, behind the launch of
        ``step``: wait for the step launched BEFORE it and fetch its
        tokens (``engine.fetch``: the one place the host waits on the
        device), then, under ``engine.emit``, advance the engine's books
        by ``step``'s counts and deliver the earlier step's tokens.
        Returns whether ``step`` was launched ahead (another step was
        in flight)."""
        prev, self._inflight = self._inflight, None
        tok_host = self._wait(prev, step) if prev is not None else None
        self._inflight = step
        with self.timeline.span("engine.emit"):
            advance(prev is not None)
            delivered = (
                self._deliver(prev, tok_host) if prev is not None else 0
            )
            self.timeline.annotate(tokens=delivered)
        if self._settles_now(step):
            self._settle()
        return prev is not None

    def _settles_now(self, step: _Launched) -> bool:
        """Whether ``step`` is delivered in its own iteration: the
        engine has waited for it already (``donate=False``: the retry,
        :meth:`_launch`), nothing is left to launch behind it (so
        ``scheduler.idle`` implies that nothing is in flight), or a
        prefill-role engine completed a prompt in it — parking a request
        for the decode pool is a delivery-time act (the hand-off carries
        the first token), and ``take_migration_ready`` must see it
        after this iteration."""
        return not self.donate or self.scheduler.idle or (
            self.role == "prefill" and bool(step.rows)
        )

    def _wait(self, step: _Launched,
              behind: Optional[_Launched] = None) -> Optional[np.ndarray]:
        """Block until ``step``'s program is done and return its token
        vector on the host (``None`` where no row sampled).  A failure
        of a step launched without a wait (a donating engine's) surfaces
        here and cannot be retried; ``behind`` is the step already
        launched on ``step``'s outputs, if any, and goes with it."""
        with self.timeline.span("engine.fetch"):
            try:
                jax.block_until_ready(step.tok)
            except Exception:
                self._abandon(step, behind)
                raise
            tok_host = np.asarray(step.tok) if step.rows else None
            # The span of the wait carries the load of the step waited
            # for (``held`` / ``max_expert``).
            self.timeline.annotate(**self._count_experts(step))
        return tok_host

    def _abandon(self, *steps: Optional[_Launched]) -> None:
        """A step failed for good: forget what is in flight, and put the
        requests that had ended by length in it, their last token never
        delivered, back among the active ones (slotless), so that
        ``drain`` — the router's failover — snapshots them with the
        tokens they did deliver."""
        self._inflight = None
        self._tok_dev = None
        for step in steps:
            for req, _row in (step.rows if step is not None else ()):
                req.in_flight = 0
            for req in (step.released if step is not None else ()):
                if req.status == "active":
                    self.scheduler.active[req.rid] = req

    def _deliver(self, step: _Launched,
                 tok_host: Optional[np.ndarray]) -> int:
        """Hand out ``step``'s tokens; returns how many.  A row whose
        request is no longer active — it ended by EOS, or was cancelled,
        in the step this one was launched behind — is DISCARDED: not
        appended, not streamed, not counted.  The cache row it wrote
        lies past its request's end, in a slot whose frontier was reset
        on release."""
        t1 = self._rec_clock()
        if self.recorder is not None:
            dur = max(t1 - step.t0, 0.0)
            for rid, g, take in step.chunks:
                self._rec("req_prefill", rid, dur=dur,
                          detail=f"g={g} take={take}")
        self._delivering = True
        delivered = 0
        try:
            for req, row in step.rows:
                req.in_flight -= 1
                if req.status != "active":
                    continue
                if step.kind == "decode" and self.recorder is not None:
                    group = self._decode_groups.get(req.rid)
                    if group is None:
                        self._decode_groups[req.rid] = [step.t0, t1, 1.0]
                    else:
                        group[1] = t1
                        group[2] += 1.0
                assert tok_host is not None
                self._emit(req, int(tok_host[row]))
                delivered += 1
        finally:
            self._delivering = False
        return delivered

    def _settle(self) -> None:
        """Wait for the step in flight, if any, and deliver its tokens:
        afterwards the engine's state is what a serial loop shows after
        the same steps.  Everything that observes or changes a request
        from outside ``step`` calls this first.  A no-op while a
        delivery is under way (an ``on_token`` callback that calls back
        into the engine sees the delivery it is part of)."""
        step = self._inflight
        if step is None or self._delivering:
            return
        self._inflight = None
        tok_host = self._wait(step)
        with self.timeline.span("engine.emit"):
            self.timeline.annotate(tokens=self._deliver(step, tok_host))

    def _count_experts(self, step: _Launched) -> Dict[str, int]:
        """``step``'s held-expert token counts into the counters, once;
        returned as ``held`` / ``max_expert`` (empty where the model
        counts none or they were read before)."""
        if step.counts is None:
            return {}
        counts = np.asarray(step.counts)
        step.counts = None
        self.metrics.moe_step(
            step.kind,
            step.positions * self.moe.top_k * counts.shape[0], counts,
        )
        return {"held": int(counts.sum()), "max_expert": int(counts.max())}

    def read_expert_counts(self) -> Dict[str, int]:
        """The counts of the step in flight into the counters (waiting
        for its program), returned as ``held`` / ``max_expert``; empty
        where nothing is in flight.  ``step`` reads a step's counts
        where it waits for that step's tokens, so the counters lag the
        launches by the one step in flight: a reader of ``metrics``
        calls this after a window that time cut short.  No token is
        delivered here."""
        step = self._inflight
        return self._count_experts(step) if step is not None else {}

    def _after_prefill_dispatch(
        self, g: int, slots: np.ndarray, tokens: np.ndarray,
        n_valid: np.ndarray,
    ) -> None:
        """No-op hook, handed the step's COMPACT host buffers (row
        ``i`` is slot ``slots[i]``; padded rows have ``n_valid`` 0);
        ``fleet.SpeculativeEngine`` overrides it to teacher-force the
        same prompt chunks into the draft cache."""

    def _emit(self, req: Request, token: int) -> None:
        """Stream one token; per-row termination FREES THE SLOT NOW —
        the iteration-level eviction continuous batching is made of."""
        req.generated.append(token)
        self.metrics.token(req.rid)
        if self.qos is not None:
            self.qos.spend(req.tenant, 1)
        if req.on_token is not None:
            req.on_token(req.rid, token)
        done = (
            (req.eos_id is not None and token == req.eos_id)
            or req.remaining_new <= 0
        )
        if done:
            req.status = "finished"
            self.scheduler.release(req)
            self.metrics.finished(req.rid)
            self._flush_decode_group(req.rid)
            self._rec(
                "req_finish", req.rid,
                detail=(
                    f"status=finished tokens={len(req.tokens())} "
                    f"version={self.version}"
                ),
            )
        elif self.role == "prefill":
            # Prompt complete, stream live: the decode phase belongs to
            # the decode pool.  Park the request OUT of the scheduler
            # (no step may touch it again here) with its slot still
            # held — the KV rows are the migration payload, released by
            # complete_migration() once a decode replica has ingested
            # them.  Requests finishing on their first token never park.
            req.status = "migrating"
            self.scheduler.active.pop(req.rid, None)
            self._migration_ready.append(req)
            self._flush_decode_group(req.rid)
        else:
            self._cur_tok[req.slot] = token

    # ------------------------------------------------------------------ #
    # KV migration (disaggregated serving)                               #
    # ------------------------------------------------------------------ #

    @property
    def migration_pending(self) -> bool:
        """Requests parked at prompt completion, awaiting handoff to a
        decode replica (prefill role only)."""
        return bool(self._migration_ready)

    def take_migration_ready(self) -> List[Request]:
        """Pop the parked requests (the router hands each to
        :func:`torchgpipe_tpu.fleet.migration.migrate`); append back to
        ``_migration_ready`` to re-park one the decode pool cannot take
        yet."""
        self._settle()
        out = self._migration_ready
        self._migration_ready = []
        return out

    def export_kv_rows(self, req: Request) -> Dict[str, Any]:
        """One slot's migration payload: per-layer KV rows (+ int8
        scale rows) with the slot axis sliced away.  Device-array views
        — zero-copy for an in-process handoff; ``np.asarray`` each leaf
        to stage the snapshot across a process boundary (the
        drain-schema flavor).  Shapes/dtypes match
        :meth:`kv_row_specs`."""
        if req.slot is None:
            raise ValueError(
                f"request {req.rid!r} holds no slot — nothing to export"
            )
        kv_cache.refuse_rings(self.cfg, "KV-row migration (export_kv_rows)")
        self._settle()
        return kv_cache.slot_rows(self.pool.cache, req.slot)

    def complete_migration(self, req: Request) -> None:
        """Donor-side epilogue: the decode replica has ingested the KV
        rows — free the slot (a prefix-cache donor pin, if any, keeps
        the rows alive for future hits) and close the books here."""
        req.status = "migrated"
        self.scheduler.release(req)
        self.metrics.migrated_out(req.rid)
        self._rec(
            "req_handoff", req.rid,
            detail=f"phase={self.role} emitted={len(req.generated)}",
        )

    def ingest_migration(
        self,
        *,
        rid: str,
        prompt: Any,
        max_new_tokens: int,
        rows: Dict[str, Any],
        last_token: int,
        eos_id: Optional[int] = None,
        on_token: Optional[Callable[[str, int], None]] = None,
        emitted_prefix: Sequence[int] = (),
        tier: str = "standard",
        tenant: Optional[str] = None,
    ) -> str:
        """Receive a mid-stream request from a prefill replica: allocate
        a slot, write the shipped KV ``rows`` through the fixed-shape
        ``migrate_ingest`` program, and register the request exactly as
        a unified engine would hold it after emitting its first token
        (``last_token``) — so the decode stream continues bitwise.

        Deliberately BYPASSES admission: no queue, no prefix-cache
        consult (a migrated request whose prompt was a prefix hit on
        the donor must not re-pin donor slots here), no re-fire of
        ``on_token`` for the carried token (the donor already streamed
        it).  ``max_new_tokens`` is the request's ORIGINAL budget; the
        carried token counts against it.  Raises ``RuntimeError`` when
        the pool has no free slot — the router re-parks and retries
        once decode slots free up."""
        kv_cache.refuse_rings(
            self.cfg, "KV-row migration (ingest_migration)")
        if self.role != "decode":
            raise ValueError(
                "ingest_migration is the decode pool's entry point — "
                f"this engine's role is {self.role!r}"
            )
        self._settle()
        self._check_rid_free(rid)
        check_tier(tier)
        req = Request(
            rid=rid,
            prompt=np.asarray(prompt, np.int32).reshape(-1),
            max_new_tokens=int(max_new_tokens),
            eos_id=eos_id,
            on_token=on_token,
            emitted_prefix=list(emitted_prefix),
            tier=tier,
            tenant=tenant,
        )
        if req.prompt_len + req.max_new_tokens > self.pool.max_len:
            raise ValueError(
                f"request {rid!r}: prompt ({req.prompt_len}) + "
                f"max_new_tokens ({req.max_new_tokens}) exceeds this "
                f"pool's max_len={self.pool.max_len} — a disaggregated "
                "fleet needs equal max_len across roles"
            )
        slot = self.pool.alloc(rid)
        if slot is None:
            raise RuntimeError(
                "decode pool full: no free slot for migrated request "
                f"{rid!r} — retry when a stream finishes"
            )
        rows_dev = jax.tree_util.tree_map(jnp.asarray, rows)
        t0 = self._rec_clock()
        try:
            new_cache = self._dispatch(
                self._ingest_fn, self.pool.cache, rows_dev,
                jnp.int32(slot), jnp.int32(req.prompt_len),
            )
        except Exception:
            # Register NOTHING on failure: the router's failover of a
            # replica that broke mid-ingest must find it clean — the
            # request is still parked on the donor, slot and all.
            self.pool.free(slot)
            raise
        req.slot = slot
        req.status = "active"
        req.prefilled = req.prompt_len
        req.generated = [int(last_token)]   # emitted on the donor
        self._requests[rid] = req
        self.scheduler.active[rid] = req
        self.metrics.arrived(rid)
        self.metrics.ingested(rid)
        self.pool.cache = new_cache
        self.pool.lengths[slot] = req.prompt_len  # shadow miss → upload
        self._cur_tok[slot] = int(last_token)
        self._tok_dev = None    # the mirror is whole (settled): upload it
        self._rec(
            "req_ingest", rid,
            dur=max(self._rec_clock() - t0, 0.0),
            detail=(
                f"phase=decode rows={req.prompt_len} slot={slot} "
                f"emitted={len(req.generated)}"
            ),
        )
        return rid

    def run(self, max_steps: Optional[int] = None) -> str:
        """Iterate until idle, preempted, or ``max_steps``.  Returns
        ``'idle'`` | ``'preempted'`` | ``'budget'``; whichever, the
        step in flight is settled first, so the caller sees every token
        of the steps that ran."""
        steps = 0
        while not self.scheduler.idle or self._inflight is not None:
            if self._preempted():
                self.drain()
                return "preempted"
            if not self.step():
                break
            steps += 1
            if max_steps is not None and steps >= max_steps:
                self._settle()
                return "budget"
        return "idle"

    # ------------------------------------------------------------------ #
    # drain / resume (resilience)                                        #
    # ------------------------------------------------------------------ #

    def request_drain(self) -> None:
        """Ask the engine to drain at the next iteration boundary (safe
        from a PreemptionHandler callback or another thread: it sets a
        flag, and the drain it asks for settles the step in flight)."""
        self._drain_requested = True

    def resume_serving(self) -> None:
        """Re-open a drained engine for admissions.  A drain empties the
        scheduler and frees every slot but leaves the engine refusing
        new work; the fleet router calls this when it re-admits a
        recovered (SLO-degraded) replica into rotation — the compiled
        programs and pool are unchanged, so serving resumes without a
        rebuild."""
        kv_cache.refuse_state(self.cfg, "resume_serving (drained streams "
                              "resumed bitwise from re-absorbed tokens)")
        self._draining = False
        self._drain_requested = False

    def _preempted(self) -> bool:
        if self._drain_requested:
            return True
        h = self._preemption
        return bool(h is not None and getattr(h, "preempted", False))

    def _unfinished(self) -> List[Request]:
        """Every request a drain snapshots, the step in flight settled
        first (a request that ends in it is not one of them).
        Migration-parked requests (prefill role) are in-flight too:
        they left the scheduler but not the replica — a drain must
        snapshot them or a dying prefill replica would strand every
        prompt caught between completion and handoff."""
        self._settle()
        return [*self.scheduler.queue, *self.scheduler.active.values(),
                *self._migration_ready]

    def unfinished(self) -> List[str]:
        """The ids of the requests a :meth:`drain` would snapshot now.
        A step in flight that failed for good re-raises here, once: the
        engine has then put its requests back among the active ones
        (:meth:`_abandon`), and the next call lists them."""
        return [r.rid for r in self._unfinished()]

    def drain(self, step_id: Optional[int] = None) -> Dict[str, Any]:
        """Cooperative drain: stop admitting, snapshot every unfinished
        request (original prompt + tokens emitted so far), release all
        slots, and — when a CheckpointManager is wired — persist the
        snapshot.  Returns the snapshot dict."""
        unfinished = self._unfinished()
        self._draining = True
        tree: Dict[str, Dict[str, np.ndarray]] = {}
        meta: Dict[str, Dict[str, Any]] = {}
        for r in unfinished:
            tree[r.rid] = {
                "prompt": np.asarray(r.prompt, np.int32),
                "generated": np.asarray(r.generated, np.int32),
            }
            meta[r.rid] = {
                "max_new_tokens": r.max_new_tokens,
                "eos_id": r.eos_id,
                "emitted_prefix": list(r.emitted_prefix),
                "prompt_len": r.prompt_len,
                "generated_len": len(r.generated),
                "tier": r.tier,
                "tenant": r.tenant,
            }
        if self.recorder is not None:
            for r in unfinished:
                self._flush_decode_group(r.rid)
                self._rec("req_preempt", r.rid,
                          detail=f"drain emitted={len(r.generated)}")
            self.recorder.record(
                "drain", detail=f"{len(unfinished)} in-flight"
            )
        for r in list(self.scheduler.active.values()):
            r.status = "preempted"
            self.scheduler.release(r)
        for r in list(self.scheduler.queue):
            r.status = "preempted"
        self.scheduler.queue.clear()
        for r in self._migration_ready:
            r.status = "preempted"
            self.scheduler.release(r)   # frees the held slot
        self._migration_ready.clear()
        self.metrics.drained(len(unfinished))
        for rid in meta:
            self.metrics.finished(rid, status="preempted")
        # Persist only when there is something to restore, and never at a
        # step id already used by an earlier drain: CheckpointManager.save
        # REPLACES an existing step_<n> snapshot, so an empty (or repeated)
        # drain at the same id would silently destroy the one that holds
        # the in-flight requests.
        if self._checkpoint_manager is not None and meta:
            sid = (
                step_id if step_id is not None
                else self.metrics.engine_steps
            )
            if self._last_drain_sid is not None:
                sid = max(sid, self._last_drain_sid + 1)
            self._checkpoint_manager.save(
                sid, tree, metadata={"requests": meta}
            )
            self._last_drain_sid = sid
        self._drain_requested = False
        snapshot = {"tree": tree, "requests": meta}
        for hook in list(self.drain_hooks):
            hook(snapshot)
        return snapshot

    @staticmethod
    def restore_requests(source: Any) -> List[Dict[str, Any]]:
        """Rebuild submit() kwargs for every request a drain snapshot
        holds — from a CheckpointManager or a :meth:`drain` return.

        Each entry resubmits with the prompt EXTENDED by the tokens
        already emitted (teacher-forced on resume) and the budget shrunk
        accordingly; greedy decode being prefix-deterministic, the
        resumed stream continues exactly where the drained one stopped.
        """
        if isinstance(source, dict):
            meta = source["requests"]
            tree = source["tree"]
        else:
            snap = source.restore_latest()
            if snap is None:
                return []
            meta = snap.metadata["requests"]
            template = {
                rid: {
                    "prompt": np.zeros((m["prompt_len"],), np.int32),
                    "generated": np.zeros((m["generated_len"],), np.int32),
                }
                for rid, m in meta.items()
            }
            tree = source.restore_step(snap.step, template).tree
        out: List[Dict[str, Any]] = []
        for rid, m in meta.items():
            prompt = np.asarray(tree[rid]["prompt"], np.int32)
            generated = np.asarray(tree[rid]["generated"], np.int32)
            out.append({
                "rid": rid,
                "prompt": np.concatenate([prompt, generated]),
                "max_new_tokens": int(m["max_new_tokens"]) - generated.size,
                "eos_id": m["eos_id"],
                "emitted_prefix": (
                    list(m["emitted_prefix"]) + generated.tolist()
                ),
                # QoS identity rides the snapshot (absent in pre-QoS
                # snapshots — the defaults keep them restorable).
                "tier": m.get("tier", "standard"),
                "tenant": m.get("tenant"),
            })
        return out


__all__ = ["Engine", "prefill_rows_for"]
