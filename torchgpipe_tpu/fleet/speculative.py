"""Speculative decoding over the pipelined decode path, slot-pooled.

Decode is memory-bound: every emitted token pays a full forward pass
whose matmuls are starved at batch-of-one-token per slot.  Speculative
decoding (Leviathan et al., arXiv:2211.17192) converts that into
chunked verification: a cheap DRAFT model proposes ``gamma`` tokens per
slot, and the TARGET model scores all of them in ONE chunked
``decode_slots`` step — the same slot-masked body serving already
compiles.  Greedy acceptance keeps the leading run of proposals the
target agrees with plus the target's own next token, so the output
stream is token-for-token what target-only greedy decode emits
(the batch-level theorem is already pinned by
``tests/test_speculative.py``; this module is the SERVING instance over
the slot pool).

The steady-state program count stays fixed, which is the whole design:

* the VERIFY pass is the engine's chunk body (``Engine.
  _prefill_body_for`` — it already returns the per-position greedy
  grid, ``[rows, g]`` argmax) jitted ONCE in its pool-wide form at the
  bucket covering ``gamma + 1``: a verify round's rows are every
  decoding slot, most of the pool, so the pool-wide shape is the right
  one for it, while the engine's own prefill programs are compact
  (``R`` rows, the prompts that are prefilling).  ONE target program
  more than the plain engine, whatever the ladder; acceptance is
  host-side bookkeeping over the grid.
* the draft side compiles one chunk program per prefill bucket (prompt
  mirroring AND post-acceptance catch-up share them — the catch-up lag
  is provably ≤ 2 after the first round) plus the ``g = 1`` proposal
  program.  Fixed count, independent of churn or acceptance history —
  certified statically by
  :func:`torchgpipe_tpu.analysis.serving.certify_speculative` (the
  same exhaustive-walk shape as ``certify_ladder``).

Rollback is free by construction: rejected draft tokens' KV rows sit
ABOVE the rolled-back frontier, where slot masking already makes them
dead (the property ``test_chunk_rollback_then_overwrite_is_clean``
pins).  The engine pays one ``[num_slots]`` lengths re-upload per
round — the host owns per-row acceptance, so the device frontier vector
is re-fed from the host mirror instead of the compiled step's uniform
advance.
"""

from __future__ import annotations

from typing import Any, Callable, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from torchgpipe_tpu.models.generation import (
    _check_decodable,
    _split_params,
    decode_slots,
)
from torchgpipe_tpu.models.transformer import TransformerConfig
from torchgpipe_tpu.serving.cache_pool import CachePool
from torchgpipe_tpu.serving.engine import Engine

Pytree = Any


class SpeculativeEngine(Engine):
    """A serving :class:`Engine` whose decode phase drafts-and-verifies.

    Example::

        eng = SpeculativeEngine(
            cfg, flat_params, draft_cfg, draft_flat,
            gamma=3, num_slots=4, max_len=64, prefill_chunk=8,
        )
        rid = eng.submit(prompt, max_new_tokens=32)
        eng.run()                    # greedy == a plain Engine's output

    ``gamma`` proposals per round need a verify chunk of ``gamma + 1``
    tokens, so ``gamma + 1`` must fit the largest prefill bucket (the
    verify program is the pool-wide form of that bucket's chunk body;
    the draft programs are pool-wide too, and prompt chunks reach them
    from the engine's compact prefill step through
    ``_after_prefill_dispatch``).  Greedy only: the acceptance rule
    is argmax agreement (``temperature > 0`` is refused didactically —
    the distribution-preserving sampled variant lives at the batch
    level in ``models.generation.speculative_generate``).
    """

    def __init__(
        self,
        cfg: TransformerConfig,
        params: Sequence[Pytree],
        draft_cfg: TransformerConfig,
        draft_params: Sequence[Pytree],
        *,
        gamma: int = 3,
        **engine_kwargs: Any,
    ) -> None:
        if gamma < 1:
            raise ValueError(f"gamma must be >= 1, got {gamma}")
        if float(engine_kwargs.get("temperature", 0.0)) != 0.0:
            raise ValueError(
                "SpeculativeEngine is greedy-only: acceptance compares "
                "argmax tokens, which preserves the target distribution "
                "only at temperature=0 — use the plain Engine (or "
                "models.generation.speculative_generate, which "
                "implements the sampled acceptance rule) for sampling"
            )
        if engine_kwargs.get("role", "unified") != "unified":
            raise ValueError(
                "SpeculativeEngine is unified-only: a speculative round "
                "interleaves draft decode with a verify pass through "
                "the prefill program, so neither phase-role's reduced "
                "program set can host it — disaggregate at the fleet "
                "level with plain prefill/decode engines instead"
            )
        if engine_kwargs.get("prefix_cache") is not None:
            raise ValueError(
                "prefix_cache + speculative decoding in ONE engine is "
                "unsupported: prefix reuse copies TARGET KV rows only, "
                "leaving the draft cache cold (an unbounded catch-up "
                "lag) — compose at the fleet level instead (router over "
                "a prefix-cached replica and a speculative replica)"
            )
        if cfg.mla is not None or draft_cfg.mla is not None:
            raise NotImplementedError(
                "speculative decoding rolls K and V rows back after a "
                "rejected draft; a latent-attention model's pool holds "
                "the KV latent — serve it with the plain Engine"
            )
        from torchgpipe_tpu.models.kv_cache import refuse_rings, refuse_state

        for c in (cfg, draft_cfg):
            refuse_rings(c, "speculative decoding's rolled-back rows")
            refuse_state(c, "speculative decoding's rolled-back rows")
        self.gamma = int(gamma)
        self.draft_cfg = draft_cfg
        self.draft_params = list(draft_params)
        _split_params(draft_cfg, self.draft_params)
        super().__init__(cfg, params, **engine_kwargs)
        if self.gamma + 1 > self.prefill_buckets[-1]:
            raise ValueError(
                f"gamma={self.gamma} needs a verify chunk of "
                f"{self.gamma + 1} tokens, but the largest prefill "
                f"bucket is {self.prefill_buckets[-1]} — the verify "
                "program is built at a prefill bucket, so raise "
                "prefill_chunk or lower gamma"
            )
        _check_decodable(draft_cfg, self.pool.max_len)
        self.draft_pool = CachePool(
            draft_cfg, self.pool.num_slots, self.pool.max_len
        )
        # Device-resident draft frontier, the draft twin of the base
        # engine's _lengths_for_step / _adopt: consecutive draft
        # dispatches re-feed the compiled step's own advanced lengths
        # array instead of re-uploading the host mirror; only the
        # per-round rollback (and slot recycling) invalidates it.
        self._draft_lengths_dev: Optional[Any] = None
        # Draft bucket set: the prefill ladder (prompt mirroring) plus
        # g=1 (the proposal step); catch-up lags are <= 2 and always
        # map into this set (certify_speculative walks it).
        self.draft_buckets: Tuple[int, ...] = tuple(
            sorted(set(self.prefill_buckets) | {1})
        )
        self._verify_bucket = self.scheduler.bucket_for(self.gamma + 1)
        # The verify program: the chunk body at the verify bucket in
        # its POOL-WIDE form (called with ``slots=None``), one row a
        # slot.  jit is lazy: it compiles at the first verify round.
        self.trace_counts["verify"] = 0
        self._verify_fn = jax.jit(
            self._prefill_body_for(self._verify_bucket, "verify"),
            donate_argnums=(1,) if self.donate else (),
        )
        self._build_draft_programs()
        reg = self.metrics.registry
        self._c_rounds = reg.counter(
            "serving_spec_rounds", help="speculative verify rounds")
        self._c_proposed = reg.counter(
            "serving_spec_proposed", help="draft tokens proposed")
        self._c_accepted = reg.counter(
            "serving_spec_accepted", help="draft tokens accepted")

    # ------------------------------------------------------------------ #
    # draft programs                                                     #
    # ------------------------------------------------------------------ #

    def _build_draft_programs(self) -> None:
        dcfg = self.draft_cfg
        counts = self.trace_counts

        def draft_body_for(g: int, name: str) -> Callable[..., Tuple]:
            def draft_body(params, cache, lengths, tokens, n_valid):
                counts[name] += 1
                logits, cache, new_lengths = decode_slots(
                    dcfg, params, tokens, cache, lengths, n_valid
                )
                last = jnp.clip(n_valid - 1, 0, g - 1)
                row_logits = jnp.take_along_axis(
                    logits, last[:, None, None], axis=1
                )[:, 0]
                tok = jnp.argmax(row_logits, axis=-1).astype(jnp.int32)
                return tok, cache, new_lengths
            return draft_body

        self._draft_names = {g: f"draft@{g}" for g in self.draft_buckets}
        for name in self._draft_names.values():
            counts[name] = 0
        donate = (1,) if self.donate else ()
        self._draft_fns: Dict[str, Any] = {
            name: jax.jit(draft_body_for(g, name), donate_argnums=donate)
            for g, name in self._draft_names.items()
        }
        self._draft_shapes = {
            name: (self.pool.num_slots, g)
            for g, name in self._draft_names.items()
        }

    @property
    def program_count(self) -> int:
        """Target programs (the base engine's bound plus the ONE
        pool-wide verify program) plus the fixed draft set —
        independent of churn and of acceptance history."""
        return super().program_count + 1 + len(self.draft_buckets)

    def step_input_specs(self) -> Dict[str, Any]:
        specs = super().step_input_specs()
        S = self.pool.num_slots
        sds = jax.ShapeDtypeStruct
        draft_cache_spec = jax.tree_util.tree_map(
            lambda a: sds(a.shape, a.dtype), self.draft_pool.cache
        )
        for name, shape in self._draft_shapes.items():
            specs[name] = {
                "cache": draft_cache_spec,
                "lengths": sds((S,), np.int32),
                "n_valid": sds((S,), np.int32),
                "tokens": sds(shape, np.int32),
            }
        # The verify program: the decode program's pool-wide inputs at
        # the verify bucket (no ``slots``: row i is slot i).
        specs["verify"] = dict(
            specs["decode"],
            tokens=sds((S, self._verify_bucket), np.int32),
        )
        return specs

    @property
    def acceptance_rate(self) -> float:
        proposed = self._c_proposed.value()
        return self._c_accepted.value() / proposed if proposed else 0.0

    # ------------------------------------------------------------------ #
    # dispatch helpers                                                   #
    # ------------------------------------------------------------------ #

    def _dispatch_draft(
        self, g: int, tokens: np.ndarray, n_valid: np.ndarray
    ) -> np.ndarray:
        """One draft step at bucket ``g``; adopts the draft cache AND
        the advanced device frontier, mirrors the advance on the host.
        Returns the per-slot argmax tokens (host)."""
        name = self._draft_names[g]
        lengths = (
            self._draft_lengths_dev
            if self._draft_lengths_dev is not None
            else self.draft_pool.lengths_device()
        )
        tok, cache, new_lengths = self._dispatch(
            self._draft_fns[name], self.draft_params,
            self.draft_pool.cache, lengths,
            jnp.asarray(tokens), jnp.asarray(n_valid),
        )
        self.draft_pool.cache = cache
        self.draft_pool.lengths += n_valid
        self._draft_lengths_dev = new_lengths
        return np.asarray(tok)

    def _on_admit(self, req: Any) -> None:
        """A recycled slot's draft frontier resets with its target one
        (the scheduler only manages the target pool's free list; stale
        draft rows are dead by masking once the frontier is zeroed)."""
        super()._on_admit(req)
        self.draft_pool.lengths[req.slot] = 0
        self._draft_lengths_dev = None      # host mirror is authoritative

    def _after_prefill_dispatch(
        self, g: int, slots: np.ndarray, tokens: np.ndarray,
        n_valid: np.ndarray,
    ) -> None:
        """Mirror the prompt chunks into the draft cache (same bucket,
        same tokens) — draft frontiers track target frontiers through
        prefill, keeping the steady-state catch-up lag <= 2.  The draft
        programs are pool-wide, so the step's compact rows are spread
        into ``[num_slots, g]`` host buffers here, by slot (padded rows
        carry nothing and are left out)."""
        S = self.pool.num_slots
        live = n_valid > 0
        d_tokens = np.zeros((S, g), np.int32)
        d_valid = np.zeros((S,), np.int32)
        d_tokens[slots[live]] = tokens[live]
        d_valid[slots[live]] = n_valid[live]
        self._dispatch_draft(g, d_tokens, d_valid)

    # ------------------------------------------------------------------ #
    # the speculative decode round                                       #
    # ------------------------------------------------------------------ #

    @staticmethod
    def _stream_window(r: Any, start: int, n: int) -> np.ndarray:
        """Tokens ``[start, start + n)`` of the request's conceptual
        prompt+generated stream, without materializing the whole
        concatenation."""
        prompt = np.asarray(r.prompt, np.int32)
        parts: List[np.ndarray] = []
        if start < prompt.size:
            parts.append(prompt[start:start + n])
            n -= parts[-1].size
            start = 0
        else:
            start -= prompt.size
        if n > 0:
            parts.append(np.asarray(
                r.generated[start:start + n], np.int32
            ))
        return parts[0] if len(parts) == 1 else np.concatenate(parts)

    def _run_decode(self) -> bool:
        # The accepted length — a comparison of token VALUES — sets
        # every row's frontier and the next draft's inputs, so a round
        # is never launched ahead: it settles the step in flight (a
        # prefill step, launched ahead by the base engine) before it
        # builds, and delivers its own tokens itself, at depth 0.
        self._settle()
        reqs = self.scheduler.decode_ready()
        S = self.pool.num_slots
        gamma = self.gamma
        t_round = self._rec_clock()

        # Phase A1 — draft catch-up: feed each row the accepted tokens
        # the draft has not consumed yet, INCLUDING the current last
        # emitted token; the chunk's last-position argmax is proposal 1.
        # Only the [d_len, d_len + lag) window of the prompt+generated
        # stream is needed (lag <= 2 in steady state, <= gamma + 1
        # always) — slicing it directly keeps this hot path O(gamma)
        # per request instead of re-concatenating the whole stream
        # (O(prompt + generated), quadratic over a request's lifetime).
        lags = np.zeros((S,), np.int32)
        for r in reqs:
            t_len = int(self.pool.lengths[r.slot])
            d_len = int(self.draft_pool.lengths[r.slot])
            lags[r.slot] = t_len + 1 - d_len
        g_c = self.scheduler.bucket_for(int(lags.max()))
        cu_tokens = np.zeros((S, g_c), np.int32)
        cu_valid = np.zeros((S,), np.int32)
        for r in reqs:
            s = r.slot
            lag = int(lags[s])
            d_len = int(self.draft_pool.lengths[s])
            cu_tokens[s, :lag] = self._stream_window(r, d_len, lag)
            cu_valid[s] = lag
        proposals = np.zeros((S, gamma), np.int32)
        proposals[:, 0] = self._dispatch_draft(g_c, cu_tokens, cu_valid)

        # Phase A2 — remaining proposals, one g=1 draft step each.
        one_valid = np.zeros((S,), np.int32)
        for r in reqs:
            one_valid[r.slot] = 1
        for k in range(1, gamma):
            proposals[:, k] = self._dispatch_draft(
                1, proposals[:, k - 1:k].copy(), one_valid
            )

        # Phase B — ONE chunked target step over [cur_tok, proposals]
        # through the pool-wide verify program (every decoding slot is
        # a row); its per-position argmax grid is the acceptance oracle.
        v_tokens = np.zeros((S, self._verify_bucket), np.int32)
        v_valid = np.zeros((S,), np.int32)
        for r in reqs:
            s = r.slot
            v_tokens[s, 0] = self._cur_tok[s]
            v_tokens[s, 1:gamma + 1] = proposals[s]
            v_valid[s] = gamma + 1
        _tok, grid, cache, _lengths_dev, _cur, key = self._dispatch(
            self._verify_fn, self.params, self.pool.cache,
            self._lengths_for_step(), None, None, jnp.asarray(v_tokens),
            jnp.asarray(v_valid), None, self._key,
        )
        self.pool.cache = cache
        self._key = key
        grid_host = np.asarray(grid)
        # The compiled step advanced every row's device frontier by
        # gamma+1; acceptance is PER-ROW, so the host mirror is
        # authoritative and the device vector re-uploads next step.
        self._lengths_dev = None
        self._lengths_shadow = None
        self.metrics.step("decode", len(reqs), S)
        self._c_rounds.inc()
        self._c_proposed.inc(gamma * len(reqs))

        # Phase C — greedy acceptance + rollback, all host-side.  The
        # per-row rollback makes the host mirror authoritative for BOTH
        # pools: the draft device frontier re-uploads at the next
        # round's catch-up (its one per-round host→device copy).
        self._draft_lengths_dev = None
        for r in reqs:
            s = r.slot
            target = grid_host[s, :gamma + 1]
            n = 0
            while n < gamma and proposals[s, n] == target[n]:
                n += 1
            emitted = [int(t) for t in proposals[s, :n]] + [int(target[n])]
            self._c_accepted.inc(n)
            # One rid-keyed span per speculative round: draft catch-up
            # + proposals + the chunked verify, with the acceptance
            # count — the request-trace twin of the round counters.
            if self.recorder is not None:
                self._rec(
                    "req_spec_round", r.rid,
                    dur=max(self._rec_clock() - t_round, 0.0),
                    detail=f"proposed={gamma} accepted={n}",
                )
            # Frontiers BEFORE emission (emission may free the slot):
            # target keeps [.., cur_tok, d1..dn]; rejected rows above
            # the frontier are dead by masking.  The draft consumed
            # d1..d_{gamma-1} — its valid run is d1..dn capped there.
            t_len = int(self.pool.lengths[s])
            self.pool.lengths[s] = t_len + 1 + n
            self.draft_pool.lengths[s] = t_len + 1 + min(n, gamma - 1)
            for tok in emitted:
                if r.status != "active":
                    break       # budget/eos hit mid-round: drop the rest
                self._emit(r, tok)
        return False


__all__ = ["SpeculativeEngine"]
