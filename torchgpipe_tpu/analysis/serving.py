"""Static verification of the serving engine's steady-state contract.

The serving engine promises a STATICALLY BOUNDED compiled-program count
under arbitrary request churn (``docs/serving.md``): one prefill
program per declared ladder bucket plus one decode program —
``len(ladder) + 1`` total (the classic single-chunk engine is the
2-program special case).  The dynamic half of the proof is the
compile-counter test in ``tests/test_serving.py``; this module is the
STATIC half, the serving twin of ``tools/pipeline_lint``:

* **recompilation-hazard** — drive a request-churn grid (ragged prompt
  lengths, token budgets, arrival patterns) through the engine's OWN
  input-spec helper (:meth:`~torchgpipe_tpu.serving.engine.Engine.
  step_input_specs` — the same shapes the real step buffers are built
  from) and certify every admissible request maps onto the declared
  program signatures.  A request the pool cannot hold must be
  statically REJECTED at submit (a shape-growing admission is exactly
  how a serving engine starts recompiling per request).
* **ladder-bound** (:func:`certify_ladder`) — the bucket choice is a
  pure function of the largest pending chunk, so an EXHAUSTIVE walk
  over every reachable chunk size ``1..max_len`` certifies the
  program-count bound for arbitrary request mixes, not just the
  sampled grid.
* **trace check** — abstractly trace both step programs
  (``jax.make_jaxpr`` over the specs; no device compute, no XLA
  compile) so a model/config combination that cannot build its serving
  programs fails the gate in seconds, not at first request.
* **host-sync-in-step** — walk the traced jaxprs for host-callback
  primitives: a callback inside a compiled serving step would serialize
  every iteration on the host (the serving twin of the pipeline
  linter's ``host-sync-in-loop`` rule).

CLI (the ``serve-verify`` step of ``tools/ci_lint.py``)::

    python -m torchgpipe_tpu.analysis.serving      # builds a tiny CPU
                                                   # engine, lints it
"""

from __future__ import annotations

from typing import Any, Dict, List, Optional, Sequence, Set, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from torchgpipe_tpu.analysis import jaxpr as jx
from torchgpipe_tpu.analysis.diagnostics import Finding, Severity

# (prompt_len, max_new_tokens) churn grid the default lint drives — the
# ragged/staggered mix the dynamic compile-counter test uses, plus the
# boundary cases (1-token prompt, budget-filling request).
DEFAULT_GRID: Tuple[Tuple[int, int], ...] = (
    (1, 1), (1, 8), (3, 5), (4, 2), (5, 16), (7, 3), (8, 8), (9, 1),
    (2, 30), (16, 16), (31, 1), (40, 40),
)


def _signature(tree: Any) -> Tuple:
    leaves = jax.tree_util.tree_leaves(tree)
    return tuple((tuple(a.shape), str(a.dtype)) for a in leaves)


def _drive_signatures(
    engine: Any, plen: int, mnew: int, tag: str,
) -> Dict[str, Set[Tuple]]:
    """Serve ONE request through the engine's real submit/schedule/
    buffer-construction machinery with the compiled programs stubbed
    out (zero device compute), capturing the argument signature of
    every would-be dispatch — keyed by the PROGRAM the engine chose
    (each prefill ladder bucket is its own program).  This is what
    makes the churn check non-vacuous: an engine that sized a step
    buffer from the request shows up here, not in production."""
    prefill_names = list(engine._prefill_fns)
    sigs: Dict[str, Set[Tuple]] = {
        **{name: set() for name in prefill_names}, "decode": set(),
    }
    S = engine.pool.num_slots

    def decode_stub(params, cache, lengths, tokens, n_valid, key):
        sigs["decode"].add(_signature({
            "cache": cache, "lengths": lengths, "tokens": tokens,
            "n_valid": n_valid, "key": key,
        }))
        # Token 0 for every slot: requests terminate by budget.  Same
        # output arity as the real body (``tokens`` is the device token
        # vector ``[num_slots]``, returned with the samples written in);
        # the engine adopts the advanced frontiers as its
        # device-resident lengths.
        return jnp.zeros((S,), jnp.int32), cache, lengths + n_valid, key

    def chunk_stub(kind):
        # The chunk body's arity (Engine._prefill_body_for): the
        # compact prefill programs take the rows' ``slots``, the
        # speculative verify program is called with ``slots=None``.
        def fn(params, cache, lengths, cur_tok, slots, tokens, n_valid,
               finish, key):
            args = {
                "cache": cache, "lengths": lengths, "tokens": tokens,
                "n_valid": n_valid, "key": key,
            }
            if slots is not None:
                args.update(slots=slots, cur_tok=cur_tok, finish=finish)
                lengths = lengths.at[slots].add(n_valid)
            else:
                lengths = lengths + n_valid
            sigs.setdefault(kind, set()).add(_signature(args))
            tok = jnp.zeros(tokens.shape[:1], jnp.int32)
            grid = jnp.zeros(tokens.shape, jnp.int32)
            return tok, grid, cache, lengths, cur_tok, key
        return fn

    def copy_stub(cache, src, dst, n):
        sigs.setdefault("prefix_copy", set()).add(_signature({
            "cache": cache, "src": src, "dst": dst, "n": n,
        }))
        return cache

    def draft_stub(kind):
        def fn(params, cache, lengths, tokens, n_valid):
            sigs.setdefault(kind, set()).add(_signature({
                "cache": cache, "lengths": lengths, "tokens": tokens,
                "n_valid": n_valid,
            }))
            return (jnp.zeros((S,), jnp.int32), cache,
                    lengths + n_valid)
        return fn

    draft_fns = getattr(engine, "_draft_fns", None)
    real = (
        dict(engine._prefill_fns), engine._decode_fn,
        engine._prefix_copy_fn,
        dict(draft_fns) if draft_fns is not None else None,
        getattr(engine, "_verify_fn", None),
    )
    engine._prefill_fns = {n: chunk_stub(n) for n in prefill_names}
    if engine._decode_fn is not None:
        engine._decode_fn = decode_stub
    if engine._prefix_copy_fn is not None:
        engine._prefix_copy_fn = copy_stub
    if draft_fns is not None:
        engine._draft_fns = {n: draft_stub(n) for n in draft_fns}
        engine._verify_fn = chunk_stub("verify")
    try:
        engine.submit(np.zeros((plen,), np.int32), mnew, rid=tag)
        engine.run()
        # A prefill-role engine parks the probe at prompt completion
        # (status "migrating", slot held); nobody migrates it during a
        # lint, so complete the handoff to release the slot and pins.
        for req in engine.take_migration_ready():
            engine.complete_migration(req)
    finally:
        engine._prefill_fns, engine._decode_fn = real[0], real[1]
        engine._prefix_copy_fn = real[2]
        if real[3] is not None:
            engine._draft_fns = real[3]
            engine._verify_fn = real[4]
    return sigs


def _program_parts(engine: Any) -> str:
    """ONE human description of an engine's declared program set, used
    by every message that cites it — prefix-cached, speculative and
    phase-role engines carry other mixes than 'one per bucket +
    decode'."""
    if getattr(engine, "role", "unified") == "decode":
        return "decode + migrate_ingest"
    has_prefix = getattr(engine, "_prefix_copy_fn", None) is not None
    n_draft = len(getattr(engine, "draft_buckets", ()))
    has_decode = getattr(engine, "_decode_fn", True) is not None
    return "one per bucket" + (
        " + decode" if has_decode else " (prefill role: no decode)"
    ) + (
        " + prefix_copy" if has_prefix else ""
    ) + (
        f" + verify + {n_draft} draft" if n_draft else ""
    )


def certify_ladder(engine: Any) -> List[Finding]:
    """Statically certify the prefill bucket ladder's program-count
    bound against ARBITRARY request mixes — not just a sampled grid.

    A prefill step's bucket is a pure function of its largest pending
    chunk ``n`` (``Scheduler.bucket_for``), and ``n`` ranges over
    ``1..max_len`` (admission rejects anything longer), so walking every
    ``n`` exhaustively proves: every reachable dispatch selects a
    declared bucket, every bucket's token-buffer shape — ``(R, g)`` at
    the engine's ONE compact row count ``prefill_rows``, however many
    prompts are pending — is a declared program signature, and the
    steady-state program count is exactly ``len(ladder) + 1``
    (``Engine.program_count``).  An INFO finding records the certified
    bound; any violation is an ERROR.

    Phase roles shrink the set and the walk follows: a prefill-role
    engine certifies at ``len(ladder)`` (no decode program — streams
    leave at the first token), a decode-role engine at exactly 2
    (``decode`` + ``migrate_ingest``; it owns no ladder, so the
    chunk walk is vacuous and skipped)."""
    findings: List[Finding] = []
    role = getattr(engine, "role", "unified")
    if role == "decode":
        n_programs = len(engine.step_input_specs())
        if n_programs != 2 or engine.program_count != 2:
            findings.append(Finding(
                rule="ladder-bound",
                severity=Severity.ERROR,
                path="serving/engine",
                message=(
                    f"decode-role engine declares {n_programs} step "
                    f"programs (program_count="
                    f"{engine.program_count}) but the role certifies "
                    "exactly 2 (decode + migrate_ingest)"
                ),
            ))
        else:
            findings.append(Finding(
                rule="ladder-bound",
                severity=Severity.INFO,
                path="serving/engine",
                message=(
                    "decode role: steady-state program count "
                    "statically bounded at 2 (decode + migrate_ingest) "
                    "for every migration mix"
                ),
            ))
        return findings
    buckets = tuple(getattr(engine, "prefill_buckets",
                            (engine.prefill_chunk,)))
    R = engine.prefill_rows
    declared = {
        tuple(spec["tokens"].shape)
        for kind, spec in engine.step_input_specs().items()
        if kind.startswith("prefill")
    }
    bad: Set[int] = set()
    for n in range(1, engine.pool.max_len + 1):
        g = engine.scheduler.bucket_for(min(n, buckets[-1]))
        if g not in buckets or (R, g) not in declared:
            bad.add(n)
    if bad:
        findings.append(Finding(
            rule="ladder-bound",
            severity=Severity.ERROR,
            path="serving/prefill",
            message=(
                f"pending-chunk sizes {sorted(bad)[:8]} select a bucket "
                f"outside the declared ladder {buckets} — the program "
                "count is not bounded by the ladder"
            ),
        ))
    n_programs = len(engine.step_input_specs())
    has_prefix = getattr(engine, "_prefix_copy_fn", None) is not None
    n_draft = len(getattr(engine, "draft_buckets", ()))
    has_decode = getattr(engine, "_decode_fn", True) is not None
    expected = (
        len(buckets) + (1 if has_decode else 0)
        + (1 if has_prefix else 0)
        + (n_draft + 1 if n_draft else 0)   # the drafts + verify
    )
    parts = _program_parts(engine)
    if n_programs != expected:
        findings.append(Finding(
            rule="ladder-bound",
            severity=Severity.ERROR,
            path="serving/engine",
            message=(
                f"engine declares {n_programs} step programs but the "
                f"ladder {buckets} certifies {expected} ({parts})"
            ),
        ))
    else:
        findings.append(Finding(
            rule="ladder-bound",
            severity=Severity.INFO,
            path="serving/engine",
            message=(
                f"prefill ladder {buckets}: steady-state program count "
                f"statically bounded at {expected} ({parts}) for every "
                "admissible request mix"
            ),
        ))
    return findings


def certify_speculative(engine: Any) -> List[Finding]:
    """Statically certify a ``fleet.SpeculativeEngine``'s fixed
    steady-state program count (the ``certify_ladder`` exhaustive-walk
    shape, applied to speculation's three dispatch sites):

    1. the VERIFY chunk ``gamma + 1`` maps onto a declared ladder
       bucket and the engine declares exactly ONE ``verify`` program
       there, pool-wide (``[num_slots, bucket]``: a round's rows are
       every decoding slot), so speculation adds one target program
       whatever the ladder and the acceptance history;
    2. every reachable draft CATCH-UP lag maps onto a declared draft
       bucket: lags are ``1..gamma + 1`` (bounded by construction — the
       round consumes every accepted token), walked exhaustively;
    3. every prefill MIRROR chunk (sizes ``1..ladder max``, same walk
       as ``certify_ladder``) maps onto a declared draft bucket.

    Passing all three bounds the total program set at
    ``engine.program_count`` for every request mix and every acceptance
    history; an INFO finding records the certified figure."""
    findings: List[Finding] = []
    buckets = tuple(engine.prefill_buckets)
    draft_buckets = tuple(getattr(engine, "draft_buckets", ()))
    gamma = getattr(engine, "gamma", None)
    if gamma is None or not draft_buckets:
        findings.append(Finding(
            rule="speculative-bound",
            severity=Severity.ERROR,
            path="fleet/speculative",
            message=(
                "engine declares no draft program set (gamma/"
                "draft_buckets missing) — not a SpeculativeEngine"
            ),
        ))
        return findings
    bad: List[str] = []
    # 1. verify chunk lands in a declared bucket, where the engine
    # declares its one pool-wide verify program
    g_v = engine.scheduler.bucket_for(gamma + 1)
    if g_v < gamma + 1 or g_v not in buckets:
        bad.append(
            f"verify chunk gamma+1={gamma + 1} does not fit a declared "
            f"prefill bucket {buckets} — the verify pass would need a "
            "program per round size"
        )
    verify = engine.step_input_specs().get("verify")
    want = (engine.pool.num_slots, g_v)
    if verify is None or tuple(verify["tokens"].shape) != want:
        bad.append(
            f"no pool-wide verify program declared at {want} — the "
            "verify pass would dispatch outside the declared set"
        )
    # 2. exhaustive catch-up lag walk (1..gamma+1)
    for lag in range(1, gamma + 2):
        g = engine.scheduler.bucket_for(lag)
        if g < lag or g not in draft_buckets:
            bad.append(
                f"catch-up lag {lag} selects bucket {g} outside the "
                f"declared draft set {draft_buckets}"
            )
    # 3. exhaustive prefill-mirror walk (every reachable target chunk)
    for n in range(1, buckets[-1] + 1):
        g = engine.scheduler.bucket_for(n)
        if g not in draft_buckets:
            bad.append(
                f"prefill mirror chunk {n} dispatches target bucket "
                f"{g} with no matching draft program"
            )
    for msg in bad:
        findings.append(Finding(
            rule="speculative-bound",
            severity=Severity.ERROR,
            path="fleet/speculative",
            message=msg,
        ))
    if not bad:
        total = engine.program_count
        findings.append(Finding(
            rule="speculative-bound",
            severity=Severity.INFO,
            path="fleet/speculative",
            message=(
                f"speculative steady state statically bounded at "
                f"{total} programs ({len(buckets)} target prefill + "
                f"decode + verify@{g_v} + {len(draft_buckets)} draft) "
                "for every request mix and acceptance history"
            ),
        ))
    return findings


def certify_disagg(
    prefill_engine: Any, decode_engine: Any,
) -> List[Finding]:
    """Statically certify a prefill/decode pool pair for
    phase-disaggregated serving (the ``certify_ladder`` shape applied
    to both roles at once):

    1. **per-role program bounds** — the prefill engine certifies its
       ladder with NO decode program (streams leave at the first
       token: a decode fn on a prefill replica means the split is not
       real), the decode engine certifies at exactly 2 programs
       (``decode`` + ``migrate_ingest``) — disaggregation SHRINKS each
       replica's compiled set below the unified ``len(ladder) + 1``;
    2. **migration compatibility** — the pair passes
       :func:`fleet.migration.validate_pools`: equal ``max_len`` and
       bit-identical per-slot KV row specs, so every exported payload
       fits the ingest program without a reshape (a mismatch here is
       a per-handoff recompile in production).

    An INFO finding records the certified pair; violations are ERROR.
    """
    findings: List[Finding] = []
    findings.extend(certify_ladder(prefill_engine))
    findings.extend(certify_ladder(decode_engine))
    if getattr(prefill_engine, "_decode_fn", None) is not None:
        findings.append(Finding(
            rule="disagg-bound",
            severity=Severity.ERROR,
            path="serving/engine",
            message=(
                "prefill-role engine carries a compiled decode program "
                "— the phase split is not real; streams must leave at "
                "the first token"
            ),
        ))
    from torchgpipe_tpu.fleet import migration as _migration
    try:
        _migration.validate_pools(prefill_engine, decode_engine)
    except _migration.MigrationError as exc:
        findings.append(Finding(
            rule="disagg-bound",
            severity=Severity.ERROR,
            path="fleet/migration",
            message=str(exc),
        ))
    if not any(f.severity >= Severity.WARNING for f in findings):
        buckets = tuple(prefill_engine.prefill_buckets)
        findings.append(Finding(
            rule="disagg-bound",
            severity=Severity.INFO,
            path="fleet/migration",
            message=(
                f"disaggregated pair certified: prefill pool "
                f"{prefill_engine.program_count} program(s) (ladder "
                f"{buckets}, no decode), decode pool 2 (decode + "
                "migrate_ingest), KV row specs bit-compatible at "
                f"max_len={prefill_engine.pool.max_len}"
            ),
        ))
    findings.sort(key=lambda f: (-int(f.severity), f.path, f.rule))
    return findings


def certify_swap(engine: Any, new_params: Any) -> List[Finding]:
    """Statically certify a live param swap (``Engine.swap_params`` —
    the rolling-rollout path, ``fleet/rollout.py``).

    The compiled serving programs take ``params`` as a traced ARGUMENT:
    a swap is retrace-free iff every leaf of the published version keeps
    the serving params' exact (shape, dtype) signature.  A mismatch is
    an ERROR — swapping it in would recompile every program mid-serve,
    so the engine refuses and the rollout controller must not publish
    it (a re-shaped model cold-starts a fresh engine instead).  An INFO
    finding records the certified leaf count.
    """
    findings: List[Finding] = []
    old_sig = _signature(list(engine.params))
    new_sig = _signature(list(new_params))
    if old_sig != new_sig:
        n = min(len(old_sig), len(new_sig))
        detail = f"leaf count {len(old_sig)} vs {len(new_sig)}"
        for i in range(n):
            if old_sig[i] != new_sig[i]:
                detail = (
                    f"leaf {i}: serving {old_sig[i]} vs "
                    f"published {new_sig[i]}"
                )
                break
        findings.append(Finding(
            rule="swap-bound",
            severity=Severity.ERROR,
            path="serving/engine",
            message=(
                "published params change the serving leaf signature "
                f"({detail}) — an in-place swap would retrace every "
                "compiled program mid-serve; new-version compile "
                "refused (cold-start a fresh engine for a re-shaped "
                "model)"
            ),
        ))
    else:
        findings.append(Finding(
            rule="swap-bound",
            severity=Severity.INFO,
            path="serving/engine",
            message=(
                f"param swap certified retrace-free: {len(old_sig)} "
                "leaves keep their (shape, dtype) signatures — KV pool "
                "and compiled programs untouched"
            ),
        ))
    findings.sort(key=lambda f: (-int(f.severity), f.path, f.rule))
    return findings


def lint_serving(
    engine: Any,
    grid: Optional[Sequence[Tuple[int, int]]] = None,
) -> List[Finding]:
    """Lint a built :class:`~torchgpipe_tpu.serving.engine.Engine`.

    Returns findings sorted most-severe-first; empty means the engine's
    steady-state compile contract holds statically over ``grid`` (a
    sequence of ``(prompt_len, max_new_tokens)`` request shapes;
    default: :data:`DEFAULT_GRID`).  Requests the engine statically
    rejects (they cannot fit a slot) are fine — INFO findings record
    them; a request that would be ADMITTED with a signature outside the
    two steady-state programs is the ERROR this lint exists to catch.

    Lint an IDLE, dedicated engine: admissible grid requests are served
    through the engine's real scheduling/buffer machinery with the
    compiled programs stubbed out (no device compute, but the probe
    requests do land in the engine's request log and metrics, under
    ``lint-*`` rids).
    """
    findings: List[Finding] = []
    grid = list(grid if grid is not None else DEFAULT_GRID)
    if not engine.scheduler.idle or getattr(engine, "_draining", False):
        raise ValueError(
            "lint_serving drives the engine with stubbed programs — "
            "lint an idle (and undrained) engine, not one serving "
            "real requests"
        )

    # 1. the steady-state signatures, from the engine's own helper: one
    # per prefill ladder bucket plus decode — the statically bounded
    # program set every dispatch must land in.
    base = engine.step_input_specs()
    base_sig = {kind: _signature(spec) for kind, spec in base.items()}
    buckets = tuple(getattr(engine, "prefill_buckets",
                            (engine.prefill_chunk,)))
    if buckets == (1,) and "decode" in base_sig:
        findings.append(Finding(
            rule="serving-program-split",
            severity=Severity.WARNING,
            path="serving/engine",
            message=(
                "prefill and decode steps share one signature "
                f"(prefill_chunk={engine.prefill_chunk} == 1?) — legal "
                "but prompts then absorb one token per iteration; a "
                "LADDER with a 1-bucket (prefill_chunk=(1, ..)) keeps "
                "the fast path for longer prompts"
            ),
        ))
    findings.extend(certify_ladder(engine))
    if getattr(engine, "draft_buckets", None):
        findings.extend(certify_speculative(engine))

    # 2. churn grid: serve every admissible request through the real
    # submit/schedule/buffer path (programs stubbed, no device compute)
    # and require every captured dispatch to hit the two signatures.
    # A live prefix cache is swapped for a SCRATCH trie for the drive:
    # the stubs write no KV, so letting the probes insert into the real
    # trie would index garbage rows as donors (and pin slots past the
    # lint).  The scratch accumulates across grid points, so later
    # probes still hit earlier ones and the prefix-copy dispatch
    # signature is exercised; its pins are dropped afterwards.
    role = getattr(engine, "role", "unified")
    real_prefix_cache = getattr(engine, "_prefix_cache", None)
    if real_prefix_cache is not None:
        engine._prefix_cache = type(real_prefix_cache)(
            min_prefix_len=real_prefix_cache.min_prefix_len,
            max_entries=real_prefix_cache.max_entries,
        )
    max_len = engine.pool.max_len
    try:
        for i, (plen, mnew) in enumerate(grid):
            if role == "decode":
                # submit() refuses by contract (work arrives only via
                # ingest_migration); the churn grid is vacuous here and
                # the abstract trace below still covers both programs.
                findings.append(Finding(
                    rule="serving-admission",
                    severity=Severity.INFO,
                    path="serving/scheduler",
                    message=(
                        "decode role refuses submit() — churn grid "
                        "skipped; decode + migrate_ingest certified by "
                        "the role bound and the abstract trace"
                    ),
                ))
                break
            if plen < 1 or mnew < 1 or plen + mnew > max_len:
                findings.append(Finding(
                    rule="serving-admission",
                    severity=Severity.INFO,
                    path="serving/scheduler",
                    message=(
                        f"request (prompt={plen}, new={mnew}) is "
                        f"statically rejected (pool max_len={max_len}) "
                        "— shapes stay fixed because admission refuses "
                        "what cannot fit"
                    ),
                ))
                continue
            churn = _drive_signatures(
                engine, plen, mnew,
                # request-log length makes the rid unique across
                # repeated lint calls on one engine
                tag=f"lint-{len(engine._requests)}-{plen}-{mnew}",
            )
            for kind, seen in churn.items():
                for sig in seen:
                    if sig != base_sig[kind]:
                        findings.append(Finding(
                            rule="recompilation-hazard",
                            severity=Severity.ERROR,
                            path=f"serving/{kind}",
                            message=(
                                f"request (prompt={plen}, new={mnew}) "
                                f"dispatches the {kind} step with a "
                                "signature outside the declared program "
                                f"set ({len(base_sig)} programs: "
                                f"{_program_parts(engine)}) — every "
                                "such request compiles a new program; "
                                "the engine must pad into its fixed "
                                "(rows, bucket) buffers instead"
                            ),
                        ))
    finally:
        if real_prefix_cache is not None:
            # Drop the scratch trie's pins and put the real one back —
            # the lint leaves trie and pool refcounts untouched.
            engine._prefix_cache.clear(engine.pool)
            engine._prefix_cache = real_prefix_cache

    # 3. abstract-trace every program (each ladder bucket + decode +
    # the prefix-copy program when a prefix cache is attached); walk
    # for host callbacks
    programs: List[Tuple[str, Any]] = list(engine._prefill_fns.items())
    if engine._decode_fn is not None:
        programs.append(("decode", engine._decode_fn))
    if getattr(engine, "_prefix_copy_fn", None) is not None:
        programs.append(("prefix_copy", engine._prefix_copy_fn))
    if getattr(engine, "_ingest_fn", None) is not None:
        programs.append(("migrate_ingest", engine._ingest_fn))
    programs.extend(getattr(engine, "_draft_fns", {}).items())
    if getattr(engine, "_verify_fn", None) is not None:
        programs.append(("verify", engine._verify_fn))
    for kind, fn in programs:
        spec = base[kind]
        try:
            if kind == "prefix_copy":
                traced = jax.make_jaxpr(fn)(
                    spec["cache"], spec["src"], spec["dst"], spec["n"]
                )
            elif kind == "migrate_ingest":
                traced = jax.make_jaxpr(fn)(
                    spec["cache"], spec["rows"], spec["dst"], spec["n"]
                )
            elif kind.startswith("draft@"):
                traced = jax.make_jaxpr(
                    lambda c, l, t, n, _fn=fn: _fn(
                        engine.draft_params, c, l, t, n
                    )
                )(spec["cache"], spec["lengths"], spec["tokens"],
                  spec["n_valid"])
            elif kind == "decode":
                traced = jax.make_jaxpr(
                    lambda c, l, t, n, k, _fn=fn: _fn(
                        engine.params, c, l, t, n, k
                    )
                )(spec["cache"], spec["lengths"], spec["tokens"],
                  spec["n_valid"], spec["key"])
            else:
                # A chunk program: compact prefill (``slots [R]``, the
                # device token vector and the rows that complete a
                # prompt) or the pool-wide verify (none of the three in
                # its spec).
                traced = jax.make_jaxpr(
                    lambda c, l, ct, s, t, n, f, k, _fn=fn: _fn(
                        engine.params, c, l, ct, s, t, n, f, k
                    )
                )(spec["cache"], spec["lengths"], spec.get("cur_tok"),
                  spec.get("slots"), spec["tokens"], spec["n_valid"],
                  spec.get("finish"), spec["key"])
        except Exception as exc:  # noqa: BLE001 — converted to a finding
            findings.append(Finding(
                rule="serving-trace",
                severity=Severity.ERROR,
                path=f"serving/{kind}",
                message=f"step does not trace abstractly: {exc}",
            ))
            continue
        for site in jx.walk_eqns(traced.jaxpr):
            name = site.eqn.primitive.name
            if name in jx.HOST_CALLBACK_PRIMS:
                findings.append(Finding(
                    rule="host-sync-in-step",
                    severity=Severity.ERROR,
                    path=f"serving/{kind}",
                    eqn=site.index,
                    primitive=name,
                    message=(
                        "host callback inside a compiled serving step — "
                        "every iteration would synchronize with the "
                        "host; move the side effect to the engine loop"
                    ),
                ))
    findings.sort(key=lambda f: (-int(f.severity), f.path, f.rule))
    return findings


def main(argv: Optional[Sequence[str]] = None) -> int:
    """CLI self-check: build a tiny CPU engine over both param layouts'
    flat schema and lint it over the default churn grid plus a
    shape-churny stress grid.  Exit 0 iff no finding reaches WARNING."""
    import argparse
    import dataclasses
    import os

    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("-v", "--verbose", action="store_true")
    args = ap.parse_args(argv)

    if os.environ.get("TGPU_LINT_ON_BACKEND") != "1":
        jax.config.update("jax_platforms", "cpu")

    import jax.numpy as jnp

    from torchgpipe_tpu.layers import sequential_init
    from torchgpipe_tpu.models.transformer import TransformerConfig, llama
    from torchgpipe_tpu.serving import Engine

    cfg = TransformerConfig(
        vocab=64, dim=32, n_layers=2, n_heads=4, n_kv_heads=2
    )
    params, _, _ = sequential_init(
        llama(cfg), jax.random.PRNGKey(0),
        jax.ShapeDtypeStruct((1, 8), jnp.int32),
    )
    worst = 0
    cases = [
        ("fp", dict(prefill_chunk=4)),
        ("int8-kv", dict(prefill_chunk=4, kv_quant=True)),
        # The bucket LADDER: program count statically bounded at
        # len(ladder)+1 and certified over the churn grid + the
        # exhaustive pending-chunk walk (certify_ladder).
        ("ladder", dict(prefill_chunk=(1, 2, 4, 8))),
        # Phase roles: prefill drops decode, decode drops the ladder.
        ("prefill-role", dict(prefill_chunk=(1, 2, 4, 8),
                              role="prefill")),
        ("decode-role", dict(prefill_chunk=4, role="decode")),
    ]
    engines = {}
    for tag, kw in cases:
        eng = Engine(cfg, params, num_slots=4, max_len=48, **kw)
        engines[tag] = eng
        findings = lint_serving(eng)
        errors = [f for f in findings if f.severity >= Severity.WARNING]
        worst = max(worst, len(errors))
        if args.verbose or errors:
            for f in findings:
                print(f.format())
        print(f"[serving-lint] {tag}: {len(findings)} finding(s), "
              f"{len(errors)} at warning+, "
              f"{eng.program_count} program(s) certified")
    # The pair certification the disaggregated router runs at build.
    findings = certify_disagg(
        engines["prefill-role"], engines["decode-role"]
    )
    errors = [f for f in findings if f.severity >= Severity.WARNING]
    worst = max(worst, len(errors))
    if args.verbose or errors:
        for f in findings:
            print(f.format())
    print(f"[serving-lint] disagg-pair: {len(findings)} finding(s), "
          f"{len(errors)} at warning+")
    # The swap certification the rollout controller runs at publish:
    # same-signature params certify, a re-shaped model is refused.
    swap_ok = certify_swap(engines["fp"], params)
    bad_params, _, _ = sequential_init(
        llama(dataclasses.replace(cfg, dim=64)), jax.random.PRNGKey(0),
        jax.ShapeDtypeStruct((1, 8), jnp.int32),
    )
    swap_bad = certify_swap(engines["fp"], bad_params)
    ok = (
        not any(f.severity >= Severity.WARNING for f in swap_ok)
        and any(f.severity >= Severity.ERROR for f in swap_bad)
    )
    if not ok:
        worst += 1
    if args.verbose or not ok:
        for f in swap_ok + swap_bad:
            print(f.format())
    print(f"[serving-lint] swap: same-signature certified="
          f"{not any(f.severity >= Severity.WARNING for f in swap_ok)}, "
          f"re-shaped refused="
          f"{any(f.severity >= Severity.ERROR for f in swap_bad)}")
    return 1 if worst else 0


__all__ = [
    "DEFAULT_GRID",
    "certify_disagg",
    "certify_ladder",
    "certify_speculative",
    "certify_swap",
    "lint_serving",
    "main",
]


if __name__ == "__main__":
    raise SystemExit(main())
