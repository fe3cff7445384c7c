"""Joint static planner tests (analysis.planner).

Covers the PR-6 contract end to end: the cost-model extensions to the
jaxpr walker (bounded ``while`` loops, ``custom_vjp`` call primitives —
each with its broken twin showing what the old convention read), the
event-graph makespan/bubble scoring, the analytic ``balance_by_flops``
cut, the certified frontier itself (every emitted plan passed the
ordering rules AND the memory certification, whose numbers must match
``tune.mpmd_stage_memory_profile`` exactly), the one-call
``apply_plan`` handoff, and the CLI exit codes of
``tools/plan_report.py`` / the ``plan-verify`` step in
``tools/ci_lint.py``.
"""

import jax
import jax.numpy as jnp
import pytest

from torchgpipe_tpu import GPipe, SpmdGPipe, make_mesh
from torchgpipe_tpu.analysis import events as ev
from torchgpipe_tpu.analysis import planner
from torchgpipe_tpu.analysis import schedule as sched
from torchgpipe_tpu.analysis.jaxpr import (
    CUSTOM_CALL_PRIMS,
    flops_estimate,
    while_trip_bound,
)
from torchgpipe_tpu.balance import balance_by_flops, balance_cost, layer_flops
from torchgpipe_tpu.layers import chain, named
from torchgpipe_tpu.ops import dense, gelu, layer_norm


def mse(out, tgt):
    return jnp.mean((out - tgt) ** 2)


X = jax.ShapeDtypeStruct((8, 16), jnp.float32)
Y = jax.ShapeDtypeStruct((8, 8), jnp.float32)


def _mpmd_model(checkpoint="always", chunks=2, balance=(2, 2), **kw):
    layers = named([dense(16, name="fc1"), gelu("a1"),
                    dense(16, name="fc2"), dense(8, name="head")])
    return GPipe(layers, balance=list(balance), chunks=chunks,
                 checkpoint=checkpoint, **kw)


# --------------------------------------------------------------------- #
# cost-model extensions: while trip bounds + custom_vjp call primitives #
# --------------------------------------------------------------------- #


def test_flops_while_bounded_multiplies_by_trip_bound():
    """Broken twin: the old convention counted EVERY while body once, so
    a 7-iteration bounded-decode loop read 1/7 of its real work.  Fixed:
    the bound is recovered from the cond's literal comparison."""

    def f(x):
        def cond(c):
            i, _ = c
            return i < 7

        def body(c):
            i, v = c
            return i + 1, v @ v

        return jax.lax.while_loop(cond, body, (0, x))

    jaxpr = jax.make_jaxpr(f)(jnp.ones((4, 4)))
    (while_eqn,) = [e for e in jaxpr.jaxpr.eqns
                    if e.primitive.name == "while"]
    assert while_trip_bound(while_eqn) == 7
    body_flops = 2 * 4 * 4 * 4  # one 4x4 @ 4x4 matmul
    assert flops_estimate(jaxpr) == 7 * body_flops  # not 1 * body_flops


def test_flops_while_unbounded_counts_body_once():
    """No literal bound in the cond (the limit is a traced value): the
    walker falls back to XLA's count-once convention, never zero."""

    def f(x, limit):
        def cond(c):
            i, _ = c
            return i < limit

        def body(c):
            i, v = c
            return i + 1, v @ v

        return jax.lax.while_loop(cond, body, (0, x))

    jaxpr = jax.make_jaxpr(f)(jnp.ones((4, 4)), 100)
    assert flops_estimate(jaxpr) == 2 * 4 * 4 * 4


def test_flops_custom_vjp_counts_one_executed_body():
    """Broken twin: custom_vjp call primitives were unhandled, so their
    matmuls read 0 — planner costs on flash-attention graphs silently
    vanished.  Fixed: the ONE executed body is counted (max over the
    param sub-jaxprs, never the sum — fwd carries a residual-saving
    variant of the same body)."""

    @jax.custom_vjp
    def g(x):
        return x @ x

    def g_fwd(x):
        return x @ x, x

    def g_bwd(x, ct):
        return (ct @ x.T + x.T @ ct,)

    g.defvjp(g_fwd, g_bwd)

    one_matmul = 2 * 4 * 4 * 4
    jaxpr = jax.make_jaxpr(g)(jnp.ones((4, 4)))
    prims = {e.primitive.name for e in jaxpr.jaxpr.eqns}
    assert prims & set(CUSTOM_CALL_PRIMS), prims
    assert flops_estimate(jaxpr) == one_matmul  # was 0

    grad_jaxpr = jax.make_jaxpr(jax.grad(lambda x: jnp.sum(g(x))))(
        jnp.ones((4, 4))
    )
    # fwd body + the two backward matmuls — nothing double-counted.
    assert flops_estimate(grad_jaxpr) == 3 * one_matmul


# --------------------------------------------------------------------- #
# event-graph scoring: makespan + bubble fraction                       #
# --------------------------------------------------------------------- #


def test_bubble_fraction_fill_drain_closed_form():
    n, m = 4, 8
    g = ev.spmd_fill_drain_events(n, m, 0)
    cost = lambda e: 1.0 if e.phase in (ev.FWD, ev.BWD) else 0.0  # noqa: E731
    span, busy = ev.makespan(g, cost)
    assert span == 2 * (m + n - 1)
    assert busy == [2.0 * m] * n
    assert ev.bubble_fraction(g, cost) == pytest.approx((n - 1) / (m + n - 1))


def test_makespan_rejects_cyclic_schedule():
    g = ev.spmd_fill_drain_events(2, 2, 0)
    a, b = g.order[0][0], g.order[0][1]
    g.deps.append((b, a))  # back-edge against the rank order: a cycle
    with pytest.raises(ValueError, match="cycle"):
        ev.makespan(g, lambda e: 1.0)


# --------------------------------------------------------------------- #
# analytic balancing: layer_flops / balance_by_flops                    #
# --------------------------------------------------------------------- #


def test_balance_by_flops_splits_fat_layers(monkeypatch):
    import torchgpipe_tpu.balance as bal
    import torchgpipe_tpu.balance.profile as prof

    def _no_probe(*a, **k):  # pragma: no cover - must never run
        raise AssertionError("balance_by_flops must not touch a device")

    monkeypatch.setattr(prof, "profile_times", _no_probe)
    monkeypatch.setattr(prof, "profile_sizes", _no_probe)
    monkeypatch.setattr(bal, "profile_times", _no_probe)
    monkeypatch.setattr(bal, "profile_sizes", _no_probe)

    from torchgpipe_tpu.ops import relu

    layers = [dense(512, name="fat0"), relu("r0"), dense(8, name="thin"),
              dense(512, name="fat1"), relu("r1"), dense(8, name="out")]
    sample = jax.ShapeDtypeStruct((16, 512), jnp.float32)
    costs = layer_flops(layers, sample)
    assert len(costs) == 6
    assert costs[1] == 0.0 and costs[4] == 0.0  # elementwise glue is free
    assert costs[0] > 10 * costs[2]  # the fat matmuls dominate
    balance = balance_by_flops(2, layers, sample)
    assert balance == balance_cost(costs, 2)
    # The two fat layers must land on different stages.
    assert balance[0] <= 3  # [fat0, ...] | [..., fat1, ...]


# --------------------------------------------------------------------- #
# MPMD planning: certified frontier, exact memory match, apply_plan     #
# --------------------------------------------------------------------- #


def test_mpmd_frontier_certified_and_ranked():
    model = _mpmd_model(checkpoint="always", chunks=2)
    report = planner.plan(model, X, hbm_budget_bytes=64 << 30,
                          chunks_options=(2, 4),
                          balance_options=[model.balance])
    assert report.candidates
    best = report.best
    assert best is not None and best.feasible and best.certified
    # Ranking: feasible-and-certified first, best predicted MFU first.
    ok = [p for p in report.candidates if p.feasible and p.certified]
    assert report.candidates[: len(ok)] == ok
    mfus = [p.predicted_mfu for p in ok if p.predicted_mfu is not None]
    assert mfus == sorted(mfus, reverse=True)

    def pick(mode, chunks):
        return next(p for p in report.candidates
                    if p.checkpoint == mode and p.chunks == chunks
                    and p.schedule == "gpipe")

    # Physics of the ranking: recompute costs MFU, more chunks less
    # bubble, and 'always' stores less than 'never'.
    assert pick("never", 2).predicted_mfu > pick("always", 2).predicted_mfu
    assert pick("never", 4).predicted_mfu > pick("never", 2).predicted_mfu
    assert pick("always", 2).hwm_bytes < pick("never", 2).hwm_bytes
    assert pick("never", 2).bubble_fraction > pick("never", 4).bubble_fraction
    # The report renders every candidate.
    table = report.table()
    assert "pred-mfu" in table and "never" in table and "offload" in table


@pytest.mark.parametrize("ckpt", ["always", "except_last", "never"])
def test_mpmd_plan_memory_matches_tune_profile_exactly(ckpt):
    """The planner's certified HWM is the event-graph liveness analysis
    weighted with tune.mpmd_stage_memory_profile's eval_shape bytes —
    assert the STRONG form: bit-for-bit equality with an independent
    reconstruction, not a tolerance."""
    from torchgpipe_tpu import tune
    from torchgpipe_tpu.checkpoint import checkpoint_stop

    model = _mpmd_model(checkpoint="always", chunks=2)
    report = planner.plan(model, X, hbm_budget_bytes=64 << 30,
                          chunks_options=(2,),
                          balance_options=[model.balance])
    p = next(c for c in report.candidates
             if c.schedule == "gpipe" and c.checkpoint == ckpt)
    assert p.certified

    variant = _mpmd_model(checkpoint=ckpt, chunks=2)
    resid_b, saved_b, out_b = tune.mpmd_stage_memory_profile(variant, X)
    g = ev.mpmd_fill_drain_events(
        len(model.balance), 2, checkpoint_stop(ckpt, 2, train=True)
    )

    def bytes_of(buf):
        if buf.kind == "resid":
            return resid_b[buf.stage]
        if buf.kind == "saved":
            return saved_b[buf.stage]
        if buf.kind == "out":
            return out_b
        return 0

    cert = sched.certify_memory(g, bytes_of)
    assert p.hwm_bytes == cert.high_water + tune.DEFAULT_OVERHEAD_BYTES


def test_mpmd_plan_includes_analytic_balance_cut():
    """A deliberately lopsided pipe: the planner must also score the
    balance_by_flops cut and rank it above the bad one."""
    layers = named([dense(16, name="fc1"), gelu("a1"),
                    dense(16, name="fc2"), dense(16, name="fc3"),
                    dense(8, name="head")])
    model = GPipe(layers, balance=[1, 4], chunks=2, checkpoint="always")
    report = planner.plan(model, X, hbm_budget_bytes=64 << 30,
                          chunks_options=(2,))
    balances = {p.balance for p in report.candidates}
    assert (1, 4) in balances and len(balances) >= 2
    analytic = next(b for b in balances if b != (1, 4))
    assert analytic == (3, 2)  # fc1+gelu+fc2 | fc3+head balances the flops
    best_of = {
        b: max(p.predicted_mfu for p in report.candidates
               if p.balance == b and p.predicted_mfu is not None)
        for b in ((1, 4), analytic)
    }
    assert best_of[analytic] > best_of[(1, 4)]
    assert report.best.balance == analytic


def test_plan_is_probe_free(monkeypatch):
    """Acceptance criterion: zero device-time probes — the profiling
    lineage must be unreachable from plan()."""
    import torchgpipe_tpu.balance as bal
    import torchgpipe_tpu.balance.profile as prof

    def _no_probe(*a, **k):  # pragma: no cover - must never run
        raise AssertionError("plan() must never run a device probe")

    for mod in (prof, bal):
        monkeypatch.setattr(mod, "profile_times", _no_probe)
        monkeypatch.setattr(mod, "profile_sizes", _no_probe)

    model = _mpmd_model(chunks=2)
    report = planner.plan(model, X, hbm_budget_bytes=64 << 30,
                          chunks_options=(2,),
                          balance_options=[model.balance])
    assert report.best is not None


def test_apply_plan_mpmd_round_trip():
    model = _mpmd_model(checkpoint="always", chunks=2,
                        hbm_budget_bytes=64 << 30)
    report = planner.plan(model, X, hbm_budget_bytes=64 << 30,
                          chunks_options=(2, 4),
                          balance_options=[model.balance])
    best = report.best
    applied = planner.apply_plan(model, best)
    assert isinstance(applied, GPipe)
    assert applied.schedule == best.schedule
    assert applied.checkpoint == best.checkpoint
    assert applied.chunks == best.chunks
    assert tuple(applied.balance) == best.balance
    assert applied.hbm_budget_bytes == 64 << 30  # budget rides along
    # verify_plan: the applied engine's OWN event graph passes the same
    # ordering/donation/equivalence rules analysis.lint enforces.
    assert planner.verify_plan(model, best) == []


def test_apply_plan_engine_mismatch_raises(cpu_devices):
    model = _mpmd_model(chunks=2)
    report = planner.plan(model, X, hbm_budget_bytes=64 << 30,
                          chunks_options=(2,),
                          balance_options=[model.balance])
    block = chain([layer_norm(name="ln"), dense(16, name="fc")], name="blk")
    mesh = make_mesh(2, 1, devices=cpu_devices[:2])
    spmd = SpmdGPipe(block, 2, mesh, chunks=2, loss_fn=mse)
    with pytest.raises(TypeError, match="mpmd plan"):
        planner.apply_plan(spmd, report.best)


def test_mpmd_1f1b_pipe_can_replan_onto_gpipe():
    """Regression: re-planning a 1f1b pipe onto gpipe must not leak
    loss_reduction into the fill-drain constructor (which rejects it)."""
    model = _mpmd_model(checkpoint="always", chunks=2, schedule="1f1b",
                        loss_reduction="mean")
    report = planner.plan(model, X, hbm_budget_bytes=64 << 30,
                          chunks_options=(2,),
                          balance_options=[model.balance])
    by_sched = {p.schedule for p in report.candidates if p.certified}
    assert {"gpipe", "1f1b"} <= by_sched
    gpipe_best = next(p for p in report.candidates
                      if p.schedule == "gpipe" and p.certified)
    applied = planner.apply_plan(model, gpipe_best)
    assert applied.schedule == "gpipe" and applied.loss_reduction is None


# --------------------------------------------------------------------- #
# SPMD planning                                                         #
# --------------------------------------------------------------------- #


def test_spmd_frontier_and_apply(cpu_devices):
    block = chain([layer_norm(name="ln"), dense(16, name="fc")], name="blk")
    mesh = make_mesh(2, 1, devices=cpu_devices[:2])
    pipe = SpmdGPipe(block, 2, mesh, chunks=2, loss_fn=mse,
                     checkpoint="always")
    report = planner.plan(pipe, X, hbm_budget_bytes=64 << 30,
                          chunks_options=(2, 4))
    best = report.best
    assert best is not None and best.feasible and best.certified
    # All three re-plannable schedules were scored.
    assert {"fill_drain", "1f1b", "zb"} <= {
        p.schedule for p in report.candidates
    }
    # Named-save presets rode along on the remat'd mode.
    assert any(p.policy == "save_attn_out" for p in report.candidates)
    applied = planner.apply_plan(pipe, best)
    assert isinstance(applied, SpmdGPipe)
    assert applied.schedule == best.schedule
    assert applied.checkpoint == best.checkpoint
    assert applied.chunks == best.chunks
    assert planner.verify_plan(pipe, best) == []


def test_megastep_options_canonical_space():
    """The shared dispatch axis: defaults, steps-filtering, and the
    honest EMPTY frontier on an indivisible K request."""
    from torchgpipe_tpu import tune

    assert planner.megastep_options() == [1, 4, 16]
    # K must divide the checkpoint/preemption hook cadence.
    assert planner.megastep_options(steps=8) == [1, 4]
    assert planner.megastep_options(steps=48) == [1, 4, 16]
    # A requested K that doesn't divide it is dropped — empty is honest.
    assert planner.megastep_options([3], steps=16) == []
    assert planner.megastep_options([0, -2]) == []
    # tune re-exports the SAME definition.
    assert tune.megastep_options(steps=8) == [1, 4]
    assert tune.scan_unroll_options("fill_drain") == [1]
    assert tune.scan_unroll_options("1f1b") == [1, True]


def test_spmd_plan_sweeps_megastep_and_scan_unroll(cpu_devices):
    block = chain([layer_norm(name="ln"), dense(16, name="fc")], name="blk")
    mesh = make_mesh(2, 1, devices=cpu_devices[:2])
    pipe = SpmdGPipe(block, 2, mesh, chunks=2, loss_fn=mse,
                     checkpoint="always", loss_reduction="mean")
    report = planner.plan(pipe, X, hbm_budget_bytes=64 << 30,
                          chunks_options=(2,))
    ks = {p.megastep for p in report.candidates}
    assert ks == {1, 4, 16}
    # scan_unroll=True only rides the slot-buffer schedules.
    unrolled = {p.schedule for p in report.candidates
                if p.scan_unroll is True}
    assert "fill_drain" not in unrolled and "1f1b" in unrolled
    # Megastep amortizes dispatch: for a fixed base config, bigger K
    # never predicts lower MFU.
    def mfu(schedule, mode, K, u=1):
        return next(p.predicted_mfu for p in report.candidates
                    if (p.schedule, p.checkpoint, p.megastep,
                        p.scan_unroll) == (schedule, mode, K, u))
    assert mfu("fill_drain", "always", 16) > mfu("fill_drain", "always", 4)
    assert mfu("fill_drain", "always", 4) > mfu("fill_drain", "always", 1)
    # The K/u table columns render.
    assert "K=" in report.table().splitlines()[1]
    # apply_plan carries the dispatch axes onto the pipe.
    applied = planner.apply_plan(pipe, report.best)
    assert applied.megastep == report.best.megastep
    assert applied.scan_unroll == report.best.scan_unroll


def test_spmd_indivisible_megastep_yields_empty_frontier(cpu_devices):
    """A requested megastep that doesn't divide the hook cadence leaves
    NO candidates (no silent fallback) — plan_report's exit-1 contract."""
    block = chain([layer_norm(name="ln"), dense(16, name="fc")], name="blk")
    mesh = make_mesh(2, 1, devices=cpu_devices[:2])
    pipe = SpmdGPipe(block, 2, mesh, chunks=2, loss_fn=mse)
    report = planner.plan(pipe, X, hbm_budget_bytes=64 << 30,
                          chunks_options=(2,),
                          megastep_options=[3], steps=16)
    assert report.candidates == [] and report.best is None


def test_makespan_comm_cost_hidden_vs_serial():
    """The overlapped-edge cost model: with per-transfer comm cost, the
    send-ahead graph's critical path is strictly shorter than the
    serial head-of-tick graph's (the transfer rides under the next
    tick's compute instead of gating it), and with zero comm cost both
    collapse to the historical model."""
    n, m = 4, 8
    serial = ev.spmd_fill_drain_events(n, m)
    ahead = ev.spmd_fill_drain_events(n, m, send_ahead=True)
    assert all(t.overlapped for t in ahead.transfers)
    assert not any(t.overlapped for t in serial.transfers)
    cost = lambda e: 1.0  # noqa: E731
    comm = lambda t: 0.25  # noqa: E731
    span_serial, _ = ev.makespan(serial, cost, comm)
    span_ahead, _ = ev.makespan(ahead, cost, comm)
    assert span_ahead < span_serial
    # Zero comm cost: identical, and equal to the comm-free model.
    s0, _ = ev.makespan(serial, cost)
    a0, _ = ev.makespan(ahead, cost, lambda t: 0.0)
    assert s0 == a0
    # The receiver still pays the wire even when overlapped: latency is
    # hidden, not deleted.
    assert span_ahead > s0


def test_spmd_over_budget_candidates_are_rejected_not_dropped(cpu_devices):
    block = chain([layer_norm(name="ln"), dense(16, name="fc")], name="blk")
    mesh = make_mesh(2, 1, devices=cpu_devices[:2])
    pipe = SpmdGPipe(block, 2, mesh, chunks=2, loss_fn=mse)
    report = planner.plan(pipe, X, hbm_budget_bytes=1, chunks_options=(2,))
    assert report.best is None
    assert report.candidates  # scored and visible, just infeasible
    assert all(not p.feasible for p in report.candidates)
    assert any("budget" in p.reason for p in report.candidates)


# --------------------------------------------------------------------- #
# CLI exit codes: tools/plan_report.py + the plan-verify ci_lint step   #
# --------------------------------------------------------------------- #


def test_plan_report_cli_rejects_unknown_preset(capsys):
    from tools.plan_report import main

    assert main(["--preset", "nope", "--chunks", "2"]) == 2
    assert "unknown preset" in capsys.readouterr().err


@pytest.mark.slow  # full tiny-llama searches (traced jaxprs, no device)
def test_report_presets_reproduce_published_mlp_hidden():
    """The llama3-8b / 1b presets must reproduce the published MLP hidden
    sizes through TransformerConfig's SwiGLU 2/3 scaling."""
    import jax.numpy as jnp

    from tools.presets import PRESETS
    from torchgpipe_tpu.models.transformer import TransformerConfig

    want = {"llama3-8b": 14336, "1b": 8192}
    for name, hidden in want.items():
        dim, n_layers, n_heads, n_kv, vocab, ratio = PRESETS[name]
        cfg = TransformerConfig(
            vocab=vocab, dim=dim, n_layers=n_layers, n_heads=n_heads,
            n_kv_heads=n_kv, mlp_ratio=ratio, dtype=jnp.bfloat16,
        )
        assert cfg.mlp_hidden == hidden, (name, cfg.mlp_hidden, hidden)


def test_plan_report_cli_exit_codes(capsys):
    from tools.plan_report import main

    argv = ["--preset", "tiny", "--seq", "64", "--batch", "4",
            "--stages", "4", "--chunks", "2"]
    assert main(argv + ["--budget-gib", "64", "--verify"]) == 0
    out = capsys.readouterr().out
    assert "best:" in out and "plan-verify: top plan clean" in out
    # The contract the CI gate relies on: NO candidate fits -> non-zero.
    assert main(argv + ["--budget-gib", "0.0001"]) == 1
    assert "NO certified candidate" in capsys.readouterr().err


@pytest.mark.slow  # tier-1 870s budget: top offender, covered by the CI full job
def test_ci_lint_wires_the_plan_gate():
    """--skip-plan exists and skipping every gate is clean (wiring)."""
    from tools.ci_lint import main

    assert main(["--skip-typegate", "--skip-schedule", "--skip-pipeline",
                 "--skip-serving", "--skip-plan"]) == 0


@pytest.mark.slow  # subprocess: the real plan-verify gate on 2 presets
def test_ci_lint_plan_verify_gate_passes():
    from tools.ci_lint import main

    assert main(["--skip-typegate", "--skip-schedule", "--skip-pipeline",
                 "--skip-serving"]) == 0


# --------------------------------------------------------------------- #
# review regressions: policy-label resolution + indivisible batches     #
# --------------------------------------------------------------------- #


def test_spmd_policy_resolves_to_preset_names(cpu_devices):
    """NamedSavePolicy.label is a display string ("save:attn_out"), not
    the planner's preset vocabulary ("save_attn_out") — the drift rule's
    config key must resolve through the canonical candidate space, and
    custom policies must map to a sentinel no candidate carries (rule
    stands down instead of mis-keying onto the plain-'always' plan)."""
    from torchgpipe_tpu.checkpoint import policies

    block = chain([layer_norm(name="ln"), dense(16, name="fc")], name="blk")
    mesh = make_mesh(2, 1, devices=cpu_devices[:2])

    def build(**kw):
        return SpmdGPipe(block, 2, mesh, chunks=2, loss_fn=mse, **kw)

    cases = [
        (build(checkpoint="always"), None),
        (build(checkpoint="always", remat_policy=policies.save_attn_out),
         "save_attn_out"),
        (build(checkpoint="always", remat_policy=policies.dots_no_batch),
         "dots_no_batch"),
        (build(checkpoint="offload"), "offload_default"),
    ]
    for pipe, expect in cases:
        assert planner._spmd_policy_label(pipe) == expect, (
            pipe.checkpoint, pipe.remat_policy, expect,
        )
    custom = build(checkpoint="always",
                   remat_policy=policies.save_names("attn_out", "ce_logits"))
    label = planner._spmd_policy_label(custom)
    assert label.startswith("<custom:")
    assert label not in {lbl for _, lbl, _ in planner.spmd_remat_space(custom)}


def test_spmd_applied_plan_with_policy_is_drift_clean(cpu_devices):
    """End to end: apply a plan that CARRIES a named-save policy; the
    drift rule must recognize the applied pipe as its own top plan
    (before the label fix it mis-keyed the policy and warned the user to
    apply the plan they had already applied)."""
    from torchgpipe_tpu import analysis

    block = chain([layer_norm(name="ln"), dense(16, name="fc")], name="blk")
    mesh = make_mesh(2, 1, devices=cpu_devices[:2])
    pipe = SpmdGPipe(block, 2, mesh, chunks=2, loss_fn=mse,
                     checkpoint="always", hbm_budget_bytes=64 << 30)
    report = planner.plan(pipe, X, hbm_budget_bytes=64 << 30,
                          chunks_options=(2, 4))
    with_policy = next(
        (p for p in report.candidates
         if p.feasible and p.certified and p.policy is not None), None)
    assert with_policy is not None
    applied = planner.apply_plan(pipe, with_policy)
    assert planner._config_of(applied) == (
        with_policy.schedule, with_policy.checkpoint, with_policy.policy,
        with_policy.chunks, None, with_policy.megastep,
        planner._unroll_key(with_policy.scan_unroll),
        with_policy.dp, with_policy.tp, with_policy.ep, with_policy.zero,
    )
    # True == 1 in Python: the key must NOT conflate full unroll with
    # the default, or drift matching resolves onto the wrong candidate.
    assert planner._unroll_key(True) != planner._unroll_key(1)
    top = planner.apply_plan(pipe, report.best)
    assert analysis.lint(top, X, rules=["plan-drift"]) == []


def test_mpmd_indivisible_batch_yields_no_candidates():
    """B=7 has no divisor in the sweep set: the old fallback scored
    chunks=pipe.chunks on micro-batch shapes the engine never runs;
    the honest answer is an empty frontier."""
    assert planner.mpmd_chunk_options(7, None, 4) == []
    model = _mpmd_model(chunks=4)
    x7 = jax.ShapeDtypeStruct((7, 16), jnp.float32)
    report = planner.plan(model, x7, hbm_budget_bytes=64 << 30)
    assert report.best is None and report.candidates == []
    # An explicit user override is honored as-given.
    assert planner.mpmd_chunk_options(7, (7,), 4) == [7]


# --------------------------------------------------------------------- #
# 3D search: dp x tp x pp widths, sharding certification, ZeRO          #
# --------------------------------------------------------------------- #


def _tp_bias_block(spec_b):
    """A block whose bias sharding the 3D-reject tests vary."""
    from jax.sharding import PartitionSpec as P  # noqa: F401
    from torchgpipe_tpu.layers import Layer

    def init(rng, spec):
        d = spec.shape[-1]
        return {"w": jax.random.normal(rng, (d, d)) * 0.02,
                "b": jnp.zeros((d,))}, ()

    def apply(params, state, x, *, rng=None, train=True):
        del rng, train
        return x @ params["w"] + params["b"], state

    return Layer(name="bd", init=init, apply=apply,
                 meta={"param_specs": {"w": P(), "b": spec_b}})


def _llama_dp_pipe(cpu_devices):
    from torchgpipe_tpu.models.transformer import (
        TransformerConfig, cross_entropy, llama_spmd,
    )

    cfg = TransformerConfig(vocab=64, dim=32, n_layers=2, n_heads=4,
                            n_kv_heads=2)
    block, pre, post = llama_spmd(cfg, 2)
    mesh = make_mesh(2, 2, devices=cpu_devices[:4])
    pipe = SpmdGPipe(block, 2, mesh, chunks=2, loss_fn=cross_entropy,
                     pre=pre, post=post, dp_axis="dp")
    return pipe, jax.ShapeDtypeStruct((8, 8), jnp.int32)


def test_plan_3d_enumerates_and_certifies_widths(cpu_devices):
    """planner.plan over mesh_options: dp x tp x pp candidates appear,
    every ranked (certified) candidate passed the sharding verifier,
    and the ZeRO candidates' optimizer-state bytes drop ~N_dp x
    (arXiv:2004.13336 — the planner's memory certification models the
    sharded update)."""
    pipe, x = _llama_dp_pipe(cpu_devices)
    report = planner.plan(
        pipe, x, hbm_budget_bytes=15 << 30,
        mesh_options=[(1, 1), (2, 1)], megastep_options=[1],
        chunks_options=[2], schedules=["fill_drain"],
    )
    widths = {(p.dp, p.tp) for p in report.candidates}
    assert widths == {(1, 1), (2, 1)}
    assert all(p.certified for p in report.candidates if p.feasible)
    at2 = [p for p in report.candidates if p.dp == 2 and p.certified]
    assert {p.zero for p in at2} == {False, True}
    z = {p.zero: p.opt_state_bytes for p in at2}
    assert z[False] == pytest.approx(2 * z[True], rel=0.01)
    # dp=2 candidates carry the priced gradient all-reduce volume.
    assert all(p.comm_bytes > 0 for p in at2)
    assert all(p.comm_bytes == 0 for p in report.candidates
               if p.dp == 1 and p.certified)


def test_plan_3d_rejects_implicit_reshard_candidate(cpu_devices):
    """Acceptance: a tp=2 width whose layout leaks sharding across the
    stage boundary is REJECTED with an implicit-reshard reason, never
    ranked."""
    from jax.sharding import PartitionSpec as P

    mesh = make_mesh(2, 1, tp=2, devices=cpu_devices[:4])
    pipe = SpmdGPipe(
        _tp_bias_block(P("tp")), 2, mesh, chunks=2, loss_fn=mse,
        tp_axis="tp",
    )
    x = jax.ShapeDtypeStruct((4, 8), jnp.float32)
    report = planner.plan(
        pipe, x, hbm_budget_bytes=15 << 30,
        mesh_options=[(1, 2)], megastep_options=[1],
    )
    assert report.best is None
    assert report.candidates
    assert all(not p.certified for p in report.candidates)
    assert any("implicit reshard" in p.reason for p in report.candidates)


def test_plan_3d_rejects_memory_overrun_candidate(cpu_devices):
    """Acceptance: a width whose certified per-device HWM exceeds the
    budget is REJECTED ('over HBM budget'), not ranked; the sharding +
    schedule certification itself ran clean."""
    pipe, x = _llama_dp_pipe(cpu_devices)
    report = planner.plan(
        pipe, x, hbm_budget_bytes=1 << 20,  # 1 MiB: nothing fits
        mesh_options=[(2, 1)], megastep_options=[1],
        chunks_options=[2], schedules=["fill_drain"],
    )
    assert report.best is None
    assert any(p.reason == "over HBM budget" for p in report.candidates)
    assert any(p.certified and not p.feasible for p in report.candidates)


def test_apply_plan_refuses_foreign_widths_and_roundtrips_zero(cpu_devices):
    """apply_plan cannot resize a device mesh: a plan at widths the
    pipe's mesh doesn't have is a didactic error; a same-width ZeRO
    plan round-trips into the pipe's zero_update field (which
    make_train_step reads as its default)."""
    import dataclasses as dc

    pipe, x = _llama_dp_pipe(cpu_devices)
    report = planner.plan(
        pipe, x, hbm_budget_bytes=15 << 30, megastep_options=[1],
        chunks_options=[2], schedules=["fill_drain"],
    )
    best = report.best
    assert (best.dp, best.tp) == (2, 1)  # defaults: the pipe's widths
    zero_plan = next(p for p in report.candidates
                     if p.certified and p.feasible and p.zero)
    applied = planner.apply_plan(pipe, zero_plan)
    assert applied.zero_update is True
    foreign = dc.replace(best, dp=4)
    with pytest.raises(ValueError, match="cannot resize"):
        planner.apply_plan(pipe, foreign)


def test_plan_3d_rejects_phantom_axis_widths(cpu_devices):
    """A width > 1 on an axis the pipe never declared must be REJECTED:
    an undeclared axis shards nothing, and dividing per-chip compute by
    it would certify fictitious speedup."""
    from jax.sharding import PartitionSpec as P

    mesh = make_mesh(2, 1, devices=cpu_devices[:2])
    pipe = SpmdGPipe(_tp_bias_block(P()), 2, mesh, chunks=2, loss_fn=mse)
    x = jax.ShapeDtypeStruct((4, 8), jnp.float32)
    report = planner.plan(
        pipe, x, hbm_budget_bytes=15 << 30,
        mesh_options=[(1, 2), (2, 1)], megastep_options=[1],
    )
    assert report.best is None
    assert all(not p.certified for p in report.candidates)
    reasons = {p.reason for p in report.candidates}
    assert any("tp_axis" in r for r in reasons)
    assert any("dp_axis" in r for r in reasons)


def test_plan_3d_never_ranks_zero1_for_fsdp_or_dp_sharded_layouts(cpu_devices):
    """The ZeRO-1 update refuses fsdp and dp-sharded layouts at
    make_train_step; the frontier must never rank a zero=1 plan its own
    engine would crash on.  An fsdp pipe's certified candidates carry
    the HONEST level instead — zero=3, the label its plain update
    actually runs as."""
    from torchgpipe_tpu.models.transformer import (
        TransformerConfig, cross_entropy, llama_spmd,
    )

    cfg = TransformerConfig(vocab=64, dim=32, n_layers=2, n_heads=4,
                            n_kv_heads=2)
    block, pre, post = llama_spmd(cfg, 2)
    mesh = make_mesh(2, 2, devices=cpu_devices[:4])
    pipe = SpmdGPipe(block, 2, mesh, chunks=2, loss_fn=cross_entropy,
                     pre=pre, post=post, dp_axis="dp", fsdp=True)
    x = jax.ShapeDtypeStruct((8, 8), jnp.int32)
    report = planner.plan(
        pipe, x, hbm_budget_bytes=15 << 30, megastep_options=[1],
        chunks_options=[2], schedules=["fill_drain"],
    )
    certified = [p for p in report.candidates if p.certified]
    assert certified and all(p.zero == 3 for p in certified)
    # An explicit zero_options=[True] (level 1) request is an honest
    # REJECT row, not a crash-later plan.
    report2 = planner.plan(
        pipe, x, hbm_budget_bytes=15 << 30, megastep_options=[1],
        chunks_options=[2], schedules=["fill_drain"],
        zero_options=[True],
    )
    assert report2.best is None
    assert any("zero=1 is incompatible" in p.reason
               and "fsdp" in p.reason for p in report2.candidates)


def test_plan_3d_rejects_explicit_zero_without_dp(cpu_devices):
    """An explicit zero_options=[True] request on a dp=1 pipe is an
    honest REJECT row — never a certified plan make_train_step would
    crash on.  Level 2 is refused at the option-normalization layer."""
    from jax.sharding import PartitionSpec as P

    mesh = make_mesh(2, 1, devices=cpu_devices[:2])
    pipe = SpmdGPipe(_tp_bias_block(P()), 2, mesh, chunks=2, loss_fn=mse)
    x = jax.ShapeDtypeStruct((4, 8), jnp.float32)
    report = planner.plan(
        pipe, x, hbm_budget_bytes=15 << 30, megastep_options=[1],
        chunks_options=[2], schedules=["fill_drain"],
        zero_options=[True],
    )
    assert report.best is None
    assert any("zero=1 is incompatible" in p.reason
               for p in report.candidates)
    with pytest.raises(ValueError, match="levels 0, 1 or 3"):
        planner.zero_options_for([2], dp=2)


def test_plan_zero3_certifies_where_replicated_is_over_budget(cpu_devices):
    """Acceptance (ZeRO-3 pricing, arXiv:1910.02054): on a budget the
    REPLICATED layout cannot fit, the frontier keeps an honest
    'over HBM budget' REJECT row for zero=0 and ranks a CERTIFIED
    zero=3 winner whose per-rank HWM — sharded residents plus the
    transient gathered window from the sharding verifier — fits.
    apply_plan on the winner flips fsdp on."""
    from torchgpipe_tpu.models.transformer import (
        TransformerConfig, cross_entropy, llama_spmd,
    )

    cfg = TransformerConfig(vocab=64, dim=32, n_layers=2, n_heads=4,
                            n_kv_heads=2)
    block, pre, post = llama_spmd(cfg, 2)
    mesh = make_mesh(2, 4, devices=cpu_devices[:8])
    pipe = SpmdGPipe(block, 2, mesh, chunks=2, loss_fn=cross_entropy,
                     pre=pre, post=post, dp_axis="dp")
    x = jax.ShapeDtypeStruct((16, 8), jnp.int32)
    kw = dict(
        megastep_options=[1], chunks_options=[2],
        schedules=["fill_drain"], zero_options=[0, 3],
        overhead_bytes=0,
    )
    # Scout pass at an unconstrained budget to read both levels' HWMs.
    wide = planner.plan(pipe, x, hbm_budget_bytes=1 << 40, **kw)
    by_level = {p.zero: p for p in wide.candidates if p.certified}
    assert set(by_level) == {0, 3}
    hwm0, hwm3 = by_level[0].hwm_bytes, by_level[3].hwm_bytes
    assert hwm3 < hwm0  # sharded residents + window < replicated
    # zero=3 stores optimizer state against the SHARDED params.
    assert by_level[3].opt_state_bytes < by_level[0].opt_state_bytes
    # ...and pays for it in priced collective volume (per-step
    # all_gather + reduce-scatter grad sync).
    assert by_level[3].comm_bytes > 0
    report = planner.plan(
        pipe, x, hbm_budget_bytes=(hwm0 + hwm3) // 2, **kw
    )
    rows0 = [p for p in report.candidates if p.zero == 0]
    assert rows0 and all(
        p.certified and not p.feasible and p.reason == "over HBM budget"
        for p in rows0
    )
    best = report.best
    assert best is not None and best.zero == 3
    assert best.certified and best.feasible
    applied = planner.apply_plan(pipe, best)
    assert applied.fsdp is True and applied.zero_update == 3


# --------------------------------------------------------------------- #
# profile-guided pricing: plan(cost_model=...)                          #
# --------------------------------------------------------------------- #


def _synthetic_cost_model(pipe, fwd=1e-3, bwd=8e-3, bwd_remat=2e-3):
    """A deliberately skewed measured profile (storing residuals slow,
    replaying cheap — unphysical here, which is the point: the analytic
    model can never produce it)."""
    from torchgpipe_tpu.obs.costmodel import (
        CellCost, CostModel, config_fingerprint,
    )

    n = pipe.n_stages if isinstance(pipe, SpmdGPipe) else len(pipe.balance)
    cells = {}
    for j in range(n):
        cells[(j, "fwd")] = CellCost(fwd, 4)
        cells[(j, "bwd")] = CellCost(bwd, 4)
        cells[(j, "bwd_remat")] = CellCost(bwd_remat, 4)
    return CostModel(fingerprint=config_fingerprint(pipe), cells=cells,
                     source="synthetic")


def test_plan_cost_model_flips_mpmd_winner():
    """The measured ranking must be able to DISAGREE with the analytic
    one: under bwd >> bwd_remat the certified winner flips from 'never'
    (least analytic work) to 'always', priced 'measured', with both
    makespans on the plan."""
    pipe = _mpmd_model(checkpoint="never")
    opts = {"chunks_options": (2,), "balance_options": [pipe.balance]}
    analytic = planner.plan(pipe, X, 64 << 30, **opts)
    assert analytic.best.checkpoint == "never"
    assert analytic.best.priced_by == "analytic"
    assert analytic.best.makespan_measured is None
    cm = _synthetic_cost_model(pipe)
    measured = planner.plan(pipe, X, 64 << 30, cost_model=cm, **opts)
    best = measured.best
    assert best.checkpoint == "always"
    assert best.priced_by == "measured"
    assert best.makespan_measured is not None
    assert best.makespan_analytic is not None
    assert measured.cost_model_stale is None
    # Certification did not change — same feasible/certified set.
    assert (
        {(p.schedule, p.checkpoint, p.chunks, p.certified, p.feasible)
         for p in analytic.candidates}
        == {(p.schedule, p.checkpoint, p.chunks, p.certified, p.feasible)
            for p in measured.candidates}
    )
    # The table shows the pricing source + measured span.
    assert "p=M" in measured.table() and "span=" in measured.table()


def test_plan_cost_model_stale_falls_back_to_analytic():
    pipe = _mpmd_model(checkpoint="never")
    cm = _synthetic_cost_model(pipe)
    other = _mpmd_model(checkpoint="always")  # reconfigured pipe
    report = planner.plan(other, X, 64 << 30, cost_model=cm,
                          chunks_options=(2,),
                          balance_options=[other.balance])
    assert report.cost_model_stale is not None
    assert "checkpoint" in report.cost_model_stale
    assert all(p.priced_by == "analytic" for p in report.candidates)
    assert "STALE" in report.table()


def test_plan_cost_model_foreign_balance_prices_analytic():
    """Measured per-stage atoms are tied to the measured cut: a
    candidate at a DIFFERENT balance must stay analytic (mixed
    frontier), in one consistent ranking unit."""
    pipe = _mpmd_model(checkpoint="never")
    cm = _synthetic_cost_model(pipe)
    report = planner.plan(
        pipe, X, 64 << 30, cost_model=cm, chunks_options=(2,),
        balance_options=[pipe.balance, (1, 3)],
    )
    by_balance = {}
    for p in report.candidates:
        by_balance.setdefault(p.balance, set()).add(p.priced_by)
    assert by_balance[(2, 2)] == {"measured"}
    assert by_balance[(1, 3)] == {"analytic"}


def test_plan_cost_model_spmd_pricing(cpu_devices):
    """The SPMD frontier prices through the same atoms: candidates at
    the measured widths re-rank measured; the remat axis flips exactly
    like the MPMD twin."""
    block = chain([layer_norm(name="ln"), dense(16, name="fc")],
                  name="blk")
    mesh = make_mesh(2, 1, devices=cpu_devices[:2])
    pipe = SpmdGPipe(block, 2, mesh, chunks=2, loss_fn=mse,
                     checkpoint="never")
    cm = _synthetic_cost_model(pipe)
    report = planner.plan(
        pipe, X, 64 << 30, cost_model=cm, chunks_options=(2,),
        schedules=["fill_drain"], megastep_options=[1],
    )
    modes = {p.checkpoint: p for p in report.candidates
             if p.policy is None and p.feasible}
    assert modes["always"].priced_by == "measured"
    assert modes["always"].makespan_measured is not None
    # bwd >> bwd_remat: full remat must outrank storing residuals.
    assert (modes["always"].predicted_mfu
            > modes["never"].predicted_mfu)


def test_plan_cost_model_derived_buckets_report_mixed():
    """A profile measured under 'never' has no remat'd backward: plans
    needing that bucket price through the documented derivation and
    must say so (priced_by='mixed', never 'measured')."""
    from torchgpipe_tpu.obs.costmodel import (
        CellCost, CostModel, config_fingerprint,
    )

    pipe = _mpmd_model(checkpoint="never")
    cells = {}
    for j in range(2):
        cells[(j, "fwd")] = CellCost(1e-3, 4)
        cells[(j, "bwd")] = CellCost(2e-3, 4)  # no bwd_remat bucket
    cm = CostModel(fingerprint=config_fingerprint(pipe), cells=cells)
    report = planner.plan(pipe, X, 64 << 30, cost_model=cm,
                          chunks_options=(2,),
                          balance_options=[pipe.balance])
    assert report.candidates
    assert all(p.priced_by == "mixed" for p in report.candidates
               if p.predicted_mfu is not None)


def test_apply_plan_carries_tracer_for_the_replan_loop():
    """apply_plan must keep the runtime configuration attached: the
    per-cell tracer (the NEXT measurement's source), the stage devices,
    and the declared compute dtype — a mid-training replan must not
    silently change placement or the precision-drift rule's gating."""
    from torchgpipe_tpu.utils.tracing import Timeline

    tracer = Timeline(sync=True)
    pipe = _mpmd_model(checkpoint="always", tracer=tracer,
                       compute_dtype=jnp.bfloat16,
                       hbm_budget_bytes=64 << 30)
    report = planner.plan(pipe, X, 64 << 30, chunks_options=(2,),
                          balance_options=[pipe.balance])
    applied = planner.apply_plan(pipe, report.best)
    assert applied.tracer is tracer
    assert applied.hbm_budget_bytes == 64 << 30
    assert applied.devices == pipe.devices
    assert applied.compute_dtype == jnp.bfloat16
    # The layers arrive already precision-wrapped; a rebuild must not
    # double-wrap them.
    assert applied.layers is pipe.layers or applied.layers == pipe.layers


def test_apply_plan_refuses_deferred_batch_norm_rebuild():
    """Deferred-BN layers were converted for the ORIGINAL chunks (stats
    commit on the chunks-th micro-batch); a rebuild at the plan's
    chunks would commit at the wrong cadence — refuse didactically."""
    pipe = _mpmd_model(checkpoint="always", deferred_batch_norm=True,
                       hbm_budget_bytes=64 << 30)
    report = planner.plan(pipe, X, 64 << 30, chunks_options=(2,),
                          balance_options=[pipe.balance])
    with pytest.raises(ValueError, match="deferred-batch-norm"):
        planner.apply_plan(pipe, report.best)


@pytest.mark.slow  # two subprocess CLI runs incl. a full measured trace
def test_cost_model_cli_round_trip(tmp_path):
    """The CLI pair: trace_report --cost-model persists a measured
    profile; plan_report --cost-model re-ranks with it (rc 0) and
    refuses a stale fingerprint (rc 1)."""
    import pathlib
    import subprocess
    import sys

    from tests.subproc_env import REPO, cpu_subproc_env

    cm_path = str(tmp_path / "cm.json")
    proc = subprocess.run(
        [sys.executable,
         str(pathlib.Path(REPO) / "tools" / "trace_report.py"),
         "--steps", "1", "--cost-model", cm_path],
        env=cpu_subproc_env(), capture_output=True, text=True,
        timeout=300, cwd=REPO,
    )
    assert proc.returncode == 0, proc.stdout + proc.stderr
    assert "cost model:" in proc.stdout
    proc = subprocess.run(
        [sys.executable,
         str(pathlib.Path(REPO) / "tools" / "plan_report.py"),
         "--cost-model", cm_path],
        env=cpu_subproc_env(), capture_output=True, text=True,
        timeout=300, cwd=REPO,
    )
    assert proc.returncode == 0, proc.stdout + proc.stderr
    assert "priced_by=" in proc.stdout
    # A mismatched configuration is stale: exit 1, didactic message.
    proc = subprocess.run(
        [sys.executable,
         str(pathlib.Path(REPO) / "tools" / "plan_report.py"),
         "--cost-model", cm_path, "--mpmd-schedule", "1f1b"],
        env=cpu_subproc_env(), capture_output=True, text=True,
        timeout=300, cwd=REPO,
    )
    assert proc.returncode == 1
    assert "STALE" in proc.stderr


@pytest.mark.slow  # a full (tiny) planner search in a subprocess
def test_replan_verify_gate():
    """ci_lint step 10: the skewed synthetic cost model flips the
    winner and the flipped plan round-trips through apply_plan."""
    import pathlib
    import subprocess
    import sys

    from tests.subproc_env import REPO, cpu_subproc_env

    proc = subprocess.run(
        [sys.executable,
         str(pathlib.Path(REPO) / "tools" / "replan_verify.py")],
        env=cpu_subproc_env(), capture_output=True, text=True,
        timeout=300, cwd=REPO,
    )
    assert proc.returncode == 0, proc.stdout + proc.stderr
    assert "measured winner 'always'" in proc.stdout


# --------------------------------------------------------------------- #
# expert-parallel (ep) width axis                                       #
# --------------------------------------------------------------------- #


def _llama_moe_ep_pipe(cpu_devices, n_experts=4):
    from torchgpipe_tpu.models.moe import MoEConfig, llama_moe_spmd
    from torchgpipe_tpu.models.transformer import (
        TransformerConfig, cross_entropy,
    )

    cfg = TransformerConfig(vocab=64, dim=16, n_layers=2, n_heads=2,
                            n_kv_heads=2)
    moe = MoEConfig(n_experts=n_experts, top_k=2, capacity_factor=8.0,
                    ep_axis="ep")
    block, pre, post = llama_moe_spmd(cfg, moe, 2)
    mesh = make_mesh(2, 1, ep=2, devices=cpu_devices[:4])
    pipe = SpmdGPipe(block, 2, mesh, chunks=2, loss_fn=cross_entropy,
                     pre=pre, post=post, ep_axis="ep")
    return pipe, jax.ShapeDtypeStruct((8, 8), jnp.int32)


def test_mesh_width_options_pairs_inherit_pipe_ep(cpu_devices):
    """Back-compat: (dp, tp) pairs stay valid and inherit the pipe's OWN
    expert width (the pre-MoE call shape); explicit triples override it;
    anything else is refused loudly."""
    pipe, _ = _llama_moe_ep_pipe(cpu_devices)
    assert planner.mesh_width_options(pipe, [(1, 1), (1, 1, 1)]) == [
        (1, 1, 2), (1, 1, 1),
    ]
    with pytest.raises(ValueError, match="mesh_options entries"):
        planner.mesh_width_options(pipe, [(1, 1, 2, 1)])


def test_plan_ep_certifies_and_prices_a2a(cpu_devices):
    """planner.plan searches the ep width next to dp x tp x pp: the ep=2
    candidates certify (sharding verifier ran clean over the expert
    layout) and carry a PRICED all_to_all volume, while the ep=1
    candidates on the same pipe move no collective bytes at all.  The
    describe() line names the expert width (xE2)."""
    pipe, x = _llama_moe_ep_pipe(cpu_devices)
    report = planner.plan(
        pipe, x, hbm_budget_bytes=15 << 30,
        mesh_options=[(1, 1, 1), (1, 1, 2)], megastep_options=[1],
        chunks_options=[2], schedules=["fill_drain"],
    )
    assert {p.ep for p in report.candidates} == {1, 2}
    at2 = [p for p in report.candidates if p.ep == 2 and p.certified]
    assert at2, [p.reason for p in report.candidates if not p.feasible]
    assert all(p.comm_bytes > 0 for p in at2)
    assert "xE2" in at2[0].describe()
    at1 = [p for p in report.candidates if p.ep == 1 and p.certified]
    assert at1
    assert all(p.comm_bytes == 0 for p in at1)


def test_plan_ep_rejections_are_honest(cpu_devices):
    """Every unplannable ep width gets a REJECT row with the real
    reason, never a silent drop: a width the expert count cannot divide
    (validate_mesh would refuse the mesh), a pipe that never declared
    ep_axis, and a declared axis with no expert-parallel layer to use
    it."""
    # E=4 does not divide over ep=3.
    pipe, x = _llama_moe_ep_pipe(cpu_devices)
    report = planner.plan(
        pipe, x, hbm_budget_bytes=15 << 30,
        mesh_options=[(1, 1, 3)], megastep_options=[1],
        chunks_options=[2], schedules=["fill_drain"],
    )
    (rej,) = [p for p in report.candidates if p.ep == 3]
    assert not rej.feasible and not rej.certified
    assert "n_experts=4 does not divide by ep=3" in rej.reason
    assert "validate_mesh" in rej.reason

    # A dense pipe never declared the axis.
    dense_pipe, dx = _llama_dp_pipe(cpu_devices)
    report = planner.plan(
        dense_pipe, dx, hbm_budget_bytes=15 << 30,
        mesh_options=[(1, 1, 2)], megastep_options=[1],
        chunks_options=[2], schedules=["fill_drain"],
    )
    (rej,) = [p for p in report.candidates if p.ep == 2]
    assert "ep=2 needs the pipe to declare ep_axis" in rej.reason

    # Axis declared, but the block holds no expert-parallel MoE layer:
    # the a2a the width implies would never run.
    from torchgpipe_tpu.models.transformer import (
        TransformerConfig, cross_entropy, llama_spmd,
    )

    cfg = TransformerConfig(vocab=64, dim=16, n_layers=2, n_heads=2,
                            n_kv_heads=2)
    block, pre, post = llama_spmd(cfg, 2)
    mesh = make_mesh(2, 1, ep=2, devices=cpu_devices[:4])
    no_moe = SpmdGPipe(block, 2, mesh, chunks=2, loss_fn=cross_entropy,
                       pre=pre, post=post, ep_axis="ep")
    report = planner.plan(
        no_moe, jax.ShapeDtypeStruct((8, 8), jnp.int32),
        hbm_budget_bytes=15 << 30,
        mesh_options=[(1, 1, 2)], megastep_options=[1],
        chunks_options=[2], schedules=["fill_drain"],
    )
    (rej,) = [p for p in report.candidates if p.ep == 2]
    assert "ep=2 needs an expert-parallel MoE layer" in rej.reason
