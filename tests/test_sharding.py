"""Static sharding analysis tests: the unified partition-rule layer,
the comm-cost walker, the propagation verifier, and the 3D planner axis.

Covers the PR contract end to end, following the per-rule broken+fixed
convention: the rule layer (first-match-wins, unmatched-leaf ERROR,
emitted-table round trip against the structural layout — the
"constructors now emit rule tables" refactor gate),
``analysis.jaxpr.comm_bytes_estimate`` (each collective's ring model,
scan × length, cond → max — with broken twins showing what a naive
count reads), the propagation's implicit-reshard detection (sharded
bias at the stage boundary: broken WARNs, fixed is clean), and the
planner's dp × tp × pp enumeration where every ranked candidate is
sharding-certified — one candidate REJECTED for an implicit reshard
and one for per-device memory overrun, and the ZeRO candidate's
optimizer-state bytes dropping ~N_dp× (the arXiv:2004.13336 gate; its
bitwise twin lives beside the engine-equivalence tests in
tests/test_optimizer.py).

Budget note: everything here is abstract (make_jaxpr/eval_shape + pure
Python) except the fixtures' traced block, which is shared
module-scoped; the wider multi-width searches are slow-marked.
"""

import jax
import jax.numpy as jnp
import pytest
from jax import lax
from jax import shard_map
from jax.sharding import AbstractMesh, PartitionSpec as P

from torchgpipe_tpu import SpmdGPipe, make_mesh
from torchgpipe_tpu.analysis import jaxpr as jx
from torchgpipe_tpu.analysis import partition_rules as pr
from torchgpipe_tpu.analysis import sharding as shd
from torchgpipe_tpu.analysis.diagnostics import Severity
from torchgpipe_tpu.layers import Layer
from torchgpipe_tpu.models.transformer import (
    TransformerConfig,
    cross_entropy,
    llama_spmd,
)


def mse(out, tgt):
    return jnp.mean((out - tgt) ** 2)


def biased_dense(spec_b, spec_w=P()):
    """A block with one weight and one bias whose declared shardings the
    tests vary — the minimal implicit-reshard laboratory."""

    def init(rng, spec):
        d = spec.shape[-1]
        return {
            "w": jax.random.normal(rng, (d, d)) * 0.02,
            "b": jnp.zeros((d,)),
        }, ()

    def apply(params, state, x, *, rng=None, train=True):
        del rng, train
        return x @ params["w"] + params["b"], state

    return Layer(
        name="bd", init=init, apply=apply,
        meta={"param_specs": {"w": spec_w, "b": spec_b}},
    )


X32 = jax.ShapeDtypeStruct((4, 8), jnp.float32)
TOK = jax.ShapeDtypeStruct((8, 8), jnp.int32)


# --------------------------------------------------------------------- #
# shared module-scoped fixture: ONE tiny tp-llama pipe + abstract init  #
# (the suite runs near its budget — tests share this trace)             #
# --------------------------------------------------------------------- #


@pytest.fixture(scope="module")
def tp_llama(cpu_devices):
    cfg = TransformerConfig(
        vocab=64, dim=32, n_layers=2, n_heads=4, n_kv_heads=2,
        tp_axis="tp",
    )
    block, pre, post = llama_spmd(cfg, 2)
    mesh = make_mesh(2, 1, tp=2, devices=cpu_devices[:4])
    pipe = SpmdGPipe(
        block, 2, mesh, chunks=2, loss_fn=cross_entropy,
        pre=pre, post=post, tp_axis="tp",
    )
    params_spec = jax.eval_shape(
        lambda r: pipe._init_host(r, TOK), jax.random.PRNGKey(0)
    )
    return pipe, params_spec


# --------------------------------------------------------------------- #
# the unified rule layer                                                #
# --------------------------------------------------------------------- #


def test_rule_table_first_match_wins_and_scalars_never_partition():
    table = pr.RuleTable(rules=(
        pr.PartitionRule(r"blocks/.*w", P("pp", None, "tp")),
        pr.PartitionRule(r"blocks/.*", P("pp")),
        pr.PartitionRule(r".*", P()),
    ))
    tree = {
        "blocks": {"w": jnp.zeros((2, 4, 4)), "b": jnp.zeros((2, 4))},
        "lr": jnp.zeros(()),  # scalar: P() without consuming a rule
    }
    specs, unmatched = table.resolve(tree)
    assert unmatched == []
    assert specs["blocks"]["w"] == P("pp", None, "tp")  # rule 0, not 1
    assert specs["blocks"]["b"] == P("pp")
    assert specs["lr"] == P()


def test_unmatched_leaf_is_an_error_not_silent_replication():
    """The SNIPPETS-idiom contract: a leaf no rule names raises (strict
    path) / reports (findings path) — never silently replicates."""
    table = pr.RuleTable(rules=(
        pr.PartitionRule(r"blocks/w$", P("pp")),
    ))
    tree = {"blocks": {"w": jnp.zeros((2, 4)), "b": jnp.zeros((2,))}}
    with pytest.raises(ValueError, match="matches no rule.*blocks/b"):
        pr.match_partition_rules(table, tree)
    _, unmatched = table.resolve(tree)
    assert unmatched == ["blocks/b"]


def test_emitted_table_round_trips_the_structural_layout(tp_llama):
    """The refactor gate: SpmdGPipe's ctor declarations now EMIT a rule
    table, and resolving that table reproduces the structural per-leaf
    layout exactly — the table IS the layout."""
    pipe, params_spec = tp_llama
    table = pipe.rule_table(params_spec)
    resolved, unmatched = table.resolve(params_spec)
    assert unmatched == []
    structural = pipe._structural_specs(params_spec)
    flat_r = jax.tree_util.tree_leaves(
        resolved, is_leaf=lambda s: isinstance(s, P)
    )
    flat_s = jax.tree_util.tree_leaves(
        structural, is_leaf=lambda s: isinstance(s, P)
    )
    assert flat_r == flat_s and len(flat_r) >= 10
    # And place() resolves THROUGH the table: an unmatched user table
    # fails loudly at placement, not silently at run time.
    broken = pr.RuleTable(rules=(
        pr.PartitionRule(r"blocks/.*", P("pp")),
    ))
    import dataclasses as dc

    broken_pipe = dc.replace(pipe, partition_rules=broken)
    with pytest.raises(ValueError, match="matches no rule"):
        broken_pipe.place(
            jax.tree_util.tree_map(
                lambda a: jnp.zeros(a.shape, a.dtype), params_spec
            )
        )


def test_fsdp_emitted_table_round_trips_storage_and_gather(cpu_devices):
    """The ZeRO-3 unification gate: the fsdp augmentation is ordinary
    ordered rules — the emitted table carries each matched leaf's
    STORAGE layout (``P(..., dp, ...)``) plus the declared
    gather-at-use attribute, and resolving it reproduces
    ``_structural_layout`` exactly for every leaf, specs AND gathers.
    ``compute_spec()`` drops the gather axes (what the block jaxpr
    sees); a planner-candidate ``dp_size`` override round-trips too."""
    cfg = TransformerConfig(vocab=64, dim=32, n_layers=2, n_heads=4,
                            n_kv_heads=2)
    block, pre, post = llama_spmd(cfg, 2)
    mesh = make_mesh(2, 2, devices=cpu_devices[:4])
    pipe = SpmdGPipe(block, 2, mesh, chunks=2, loss_fn=cross_entropy,
                     pre=pre, post=post, dp_axis="dp", fsdp=True)
    params_spec = jax.eval_shape(
        lambda r: pipe._init_host(r, TOK), jax.random.PRNGKey(0)
    )

    def flat(t):
        return jax.tree_util.tree_leaves(
            t, is_leaf=lambda s: isinstance(s, P)
        )

    for dp_size in (None, 4):
        table = pipe.rule_table(params_spec, dp_size=dp_size)
        specs, gathers, unmatched = table.resolve_layout(params_spec)
        assert unmatched == []
        want_specs, want_gathers = pipe._structural_layout(
            params_spec, dp_size=dp_size
        )
        assert flat(specs) == flat(want_specs)
        assert gathers == want_gathers
        gathered = {p: a for p, a in gathers.items() if a}
        assert gathered and all(a == ("dp",) for a in gathered.values())
        for path, axes in gathered.items():
            rule = table.rule_for(path)
            assert rule.gather == axes
            assert "dp" in shd.spec_axes(rule.spec)  # storage layout
            assert "dp" not in shd.spec_axes(rule.compute_spec())
        # Non-block leaves (pre/post) stay replicated-over-dp with no
        # gather attribute.
        assert all(not gathers[p] for p in gathers
                   if not p.startswith("blocks/"))


def test_parallel_tensor_rules_match_the_declared_tp_layout(tp_llama):
    """parallel.tensor.partition_rules: the hand-written Megatron table
    resolves a tp transformer's STACKED block params to exactly the
    layout the block's meta['param_specs'] declares structurally."""
    from torchgpipe_tpu.parallel import tensor

    pipe, params_spec = tp_llama
    table = tensor.partition_rules("tp", pp_axis="pp")
    got, unmatched = table.resolve(params_spec["blocks"])
    assert unmatched == []
    want = pipe._structural_specs(params_spec)["blocks"]
    assert jax.tree_util.tree_leaves(
        got, is_leaf=lambda s: isinstance(s, P)
    ) == jax.tree_util.tree_leaves(
        want, is_leaf=lambda s: isinstance(s, P)
    )


def test_parallel_sp_modules_emit_replicated_param_tables():
    from torchgpipe_tpu.parallel import ring_attention as ring_mod
    from torchgpipe_tpu.parallel import ulysses as ulysses_mod
    import sys

    # The package re-exports functions under the module names; reach
    # the MODULES for their rule emitters.
    ulysses = sys.modules["torchgpipe_tpu.parallel.ulysses"]
    ring = sys.modules["torchgpipe_tpu.parallel.ring_attention"]
    del ring_mod, ulysses_mod
    for mod in (ulysses, ring):
        table = mod.partition_rules("sp")
        specs, unmatched = table.resolve({"w": jnp.zeros((2, 4))})
        assert unmatched == [] and specs["w"] == P("pp")


# --------------------------------------------------------------------- #
# comm_bytes_estimate (the flops_estimate companion)                    #
# --------------------------------------------------------------------- #


def _first_comm(jaxpr, sizes):
    return jx.comm_bytes_estimate(jaxpr, sizes)


def test_comm_bytes_allreduce_ring_model():
    """Broken twin: counting a psum's operand bytes once reads half the
    wire traffic — a ring all-reduce moves 2·(N-1)/N × bytes per device
    (reduce-scatter + all-gather)."""

    def f(x):
        return shard_map(
            lambda v: lax.psum(v, "dp"),
            mesh=AbstractMesh((4,), ("dp",)),
            in_specs=P(), out_specs=P(),
        )(x)

    x = jnp.zeros((8, 8), jnp.float32)  # 256 bytes
    closed = jax.make_jaxpr(f)(x)
    got = _first_comm(closed, {"dp": 4})
    naive = 256.0
    assert got == pytest.approx(2.0 * 3 / 4 * 256.0)
    assert got != naive  # the broken convention
    # An axis the mesh doesn't size contributes zero volume (existence
    # is the lint rules' job, not the cost model's).
    assert _first_comm(closed, {}) == 0.0


def test_comm_bytes_collectives_and_loop_structure():
    mesh = AbstractMesh((4,), ("sp",))

    def ring(x):
        def body(c, _):
            c = lax.ppermute(c, "sp", [(i, (i + 1) % 4) for i in range(4)])
            return c, ()

        c, _ = lax.scan(body, x, None, length=3)
        return c

    def f(x):
        return shard_map(
            ring, mesh=mesh, in_specs=P(), out_specs=P(),
            check_vma=False,
        )(x)

    x = jnp.zeros((4, 8), jnp.float32)  # 128 bytes
    closed = jax.make_jaxpr(f)(x)
    # Broken twin: counting the scan body ONCE (XLA's convention) reads
    # 128; the schedule runs it length=3 times.
    assert _first_comm(closed, {"sp": 4}) == pytest.approx(3 * 128.0)

    def g(x, pred):
        def gather(v):
            return lax.all_gather(v, "sp", axis=0, tiled=True)

        def branch_a(v):
            return shard_map(
                gather, mesh=mesh, in_specs=P("sp"), out_specs=P(),
                check_vma=False,
            )(v)

        return lax.cond(pred, branch_a, lambda v: v, x)

    closed = jax.make_jaxpr(g)(x, True)
    # all_gather: (N-1)/N × OUTPUT bytes; cond takes the max over
    # branches (one executes), not the sum.
    assert _first_comm(closed, {"sp": 4}) == pytest.approx(3 / 4 * 128.0)


def test_eqn_comm_bytes_reduce_scatter_and_all_to_all():
    mesh = AbstractMesh((4,), ("tp",))

    def f(x):
        return shard_map(
            lambda v: lax.psum_scatter(v, "tp", scatter_dimension=0,
                                       tiled=True),
            mesh=mesh, in_specs=P(), out_specs=P("tp"),
        )(x)

    x = jnp.zeros((8, 4), jnp.float32)  # 128 bytes in
    closed = jax.make_jaxpr(f)(x)
    assert _first_comm(closed, {"tp": 4}) == pytest.approx(3 / 4 * 128.0)

    def g(x):
        return shard_map(
            lambda v: lax.all_to_all(v, "tp", split_axis=1, concat_axis=0,
                                     tiled=True),
            mesh=mesh, in_specs=P("tp"), out_specs=P(None, "tp"),
        )(x)

    closed = jax.make_jaxpr(g)(x)
    local = 128.0 / 4  # shard_map local view: [2, 4] per lane
    assert _first_comm(closed, {"tp": 4}) == pytest.approx(3 / 4 * local)


def test_collective_comm_bytes_zero3_grad_path_conventions():
    """Broken twins pinning the two sides of the ZeRO-3 grad path under
    a dp axis: ``all_gather`` prices (N-1)/N × OUTPUT bytes (the input
    convention reads N× too little — each device RECEIVES every other
    shard), ``reduce_scatter`` prices (N-1)/N × INPUT bytes (the output
    convention reads N× too little — every full-grad shard but your own
    goes on the wire).  Only the ring all-reduce side was pinned by the
    optimizer gates before."""
    n, shard = 4, 1024.0  # bytes of one stored (1/N) param shard
    full = n * shard
    up = jx.collective_comm_bytes("all_gather", n, shard)
    assert up == pytest.approx((n - 1) / n * full)
    assert up != pytest.approx((n - 1) / n * shard)  # broken: input conv
    # An explicit out_bytes must agree with the tiled n×in derivation.
    assert jx.collective_comm_bytes("all_gather", n, shard, full) == up
    down = jx.collective_comm_bytes("reduce_scatter", n, full)
    assert down == pytest.approx((n - 1) / n * full)
    assert down != pytest.approx((n - 1) / n * shard)  # broken: out conv
    assert jx.collective_comm_bytes("psum_scatter", n, full) == down
    # The ZeRO-3 round trip (gather params up, reduce-scatter grads
    # down) moves exactly the ring all-reduce volume the replicated
    # layout pays in its ONE grad psum — the wire cost is layout-
    # invariant; only the RESIDENT bytes change.
    assert up + down == pytest.approx(
        jx.collective_comm_bytes("psum", n, full)
    )
    # dp width 1: nothing to move on either side.
    assert jx.collective_comm_bytes("all_gather", 1, shard) == 0.0
    assert jx.collective_comm_bytes("reduce_scatter", 1, full) == 0.0


# --------------------------------------------------------------------- #
# propagation: implicit reshard, mesh mismatch, memory under layout     #
# --------------------------------------------------------------------- #


def test_implicit_reshard_broken_and_fixed(cpu_devices):
    """Broken: a bias sharded over tp leaks sharding to the block
    output, which the replicated pipeline carry must gather every tick
    — WARNING with the reshard event.  A half-open column-parallel
    region (sharded weight, no closing psum) is flagged the same way.
    Fixed: a replicated layout is clean."""
    mesh = make_mesh(2, 1, tp=2, devices=cpu_devices[:4])
    broken = SpmdGPipe(
        biased_dense(P("tp")), 2, mesh, chunks=2, loss_fn=mse,
        tp_axis="tp",
    )
    rep = shd.verify_layout(broken, X32)
    assert rep.propagated and len(rep.reshards()) == 1
    warn = [f for f in rep.findings if f.rule == "implicit-reshard"]
    assert warn and any("stage boundary" in f.message for f in warn)

    half_open = SpmdGPipe(
        biased_dense(P(), spec_w=P(None, "tp")), 2, mesh, chunks=2,
        loss_fn=mse, tp_axis="tp",
    )
    assert shd.verify_layout(half_open, X32).reshards()

    fixed = SpmdGPipe(
        biased_dense(P()), 2, make_mesh(2, 1, devices=cpu_devices[:2]),
        chunks=2, loss_fn=mse,
    )
    rep3 = shd.verify_layout(fixed, X32)
    assert rep3.ok() and not rep3.reshards() and rep3.findings == []


def test_tp_llama_layout_certifies_with_two_required_psums(tp_llama):
    """The Megatron block CLOSES its parallel regions (psum_value after
    wo and w_down): the propagation certifies the layout clean and
    prices exactly the two required psums per block."""
    pipe, params_spec = tp_llama
    rep = shd.verify_layout(pipe, TOK, params_spec=params_spec)
    assert rep.ok() and rep.propagated
    assert not rep.reshards() and rep.findings == []
    psums = [e for e in rep.comm if e.kind == "psum"]
    assert len(psums) == 2 and all(e.axes == ("tp",) for e in psums)
    assert rep.comm_bytes() > 0


def test_mesh_axis_mismatch_is_an_error(cpu_devices):
    """A rule table naming an axis the mesh doesn't have is an ERROR
    (the didactic twin of a shard_map unbound-axis crash)."""
    import dataclasses as dc

    mesh = make_mesh(2, 1, devices=cpu_devices[:2])
    pipe = SpmdGPipe(biased_dense(P()), 2, mesh, chunks=2, loss_fn=mse)
    table = pr.RuleTable(rules=(
        pr.PartitionRule(r"blocks/w$", P("pp", "model")),
        pr.PartitionRule(r".*", P("pp")),
    ))
    rep = shd.verify_layout(dc.replace(pipe, partition_rules=table), X32)
    errs = [f for f in rep.findings if f.severity >= Severity.ERROR]
    assert errs and "model" in errs[0].message
    # place() refuses the same table didactically.
    with pytest.raises(ValueError, match="mesh axis 'model'"):
        dc.replace(pipe, partition_rules=table).place(
            pipe._init_host(jax.random.PRNGKey(0), X32)
        )


def test_layout_bytes_divides_by_shard_widths(tp_llama):
    pipe, params_spec = tp_llama
    from torchgpipe_tpu.tune import tree_bytes

    mesh = shd.MeshSpec.from_mesh(pipe.mesh)
    specs, _ = pipe.rule_table(params_spec).resolve(params_spec)
    local = shd.layout_bytes(params_spec, specs, mesh)
    wide = shd.layout_bytes(
        params_spec, specs, mesh.with_sizes(tp=4)
    )
    total = tree_bytes(params_spec)
    assert local < total  # pp + tp sharding both divide
    assert wide < local  # doubling tp shrinks the tp-sharded share


def test_accidental_full_replication_warns(cpu_devices):
    """A declared tp axis of size > 1 that NO leaf uses: the user asked
    for sharding and silently got replication — WARNING."""
    mesh = make_mesh(2, 1, tp=2, devices=cpu_devices[:4])
    pipe = SpmdGPipe(
        biased_dense(P()), 2, mesh, chunks=2, loss_fn=mse, tp_axis="tp"
    )
    rep = shd.verify_layout(pipe, X32)
    assert any("fully replicates" in f.message for f in rep.findings)


# --------------------------------------------------------------------- #
# the 3D planner axis lives in tests/test_planner.py (the acceptance    #
# REJECT demonstrations ride with the rest of the planner contract)     #
# --------------------------------------------------------------------- #


# --------------------------------------------------------------------- #
# ZeRO guard rails (the bitwise gate lives in tests/test_optimizer.py)  #
# --------------------------------------------------------------------- #


def test_zero_levels_validate_against_the_layout(cpu_devices):
    """The zero= LEVEL contract: no dp axis refuses any sharded level;
    zero=1 under fsdp and zero=3 without fsdp are refused didactically
    (level/layout mismatch); zero=True resolves to the layout's natural
    level (3 under fsdp, 1 otherwise); level 2 does not exist."""
    import optax

    mesh = make_mesh(2, 1, devices=cpu_devices[:2])
    pipe = SpmdGPipe(biased_dense(P()), 2, mesh, chunks=2, loss_fn=mse)
    with pytest.raises(ValueError, match="needs dp_axis"):
        pipe.make_train_step(optax.sgd(1e-2), zero=True)
    with pytest.raises(ValueError, match="fsdp=True"):
        pipe.make_train_step(optax.sgd(1e-2), zero=3)
    with pytest.raises(ValueError, match="not a supported ZeRO level"):
        pipe.make_train_step(optax.sgd(1e-2), zero=2)
    import dataclasses as dc

    mesh2 = make_mesh(2, 2, devices=cpu_devices[:4])
    fpipe = dc.replace(pipe, mesh=mesh2, dp_axis="dp", fsdp=True)
    # fsdp + zero is no longer refused: True resolves to the fully-
    # sharded level 3; the incoherent segment level 1 still raises.
    assert fpipe._zero_level(True) == 3
    assert fpipe._zero_level(None) == 0  # declared zero_update=False
    with pytest.raises(ValueError, match="zero=1 under fsdp"):
        fpipe.make_train_step(optax.sgd(1e-2), zero=1)
    rpipe = dc.replace(pipe, mesh=mesh2, dp_axis="dp")
    assert rpipe._zero_level(True) == 1


@pytest.mark.slow  # full tiny-llama 3D searches across 3 widths
def test_sharding_report_ci_gate_passes():
    import pathlib
    import subprocess
    import sys

    repo = pathlib.Path(__file__).resolve().parent.parent
    proc = subprocess.run(
        [sys.executable, str(repo / "tools" / "sharding_report.py"),
         "--preset", "tiny", "--stages", "2", "--batch", "8"],
        capture_output=True, text=True, timeout=600,
    )
    assert proc.returncode == 0, proc.stdout + proc.stderr
    assert "sharding-verify: top 3D plan clean" in proc.stdout


def test_ci_lint_wires_the_sharding_gate():
    import pathlib

    src = (
        pathlib.Path(__file__).resolve().parent.parent
        / "tools" / "ci_lint.py"
    ).read_text()
    assert "sharding_report.py" in src and "sharding-verify" in src
    assert "--skip-sharding" in src


def test_place_passes_unknown_keys_through(cpu_devices):
    """place() owns the layout of blocks/pre/post/loss only; a caller-
    managed extra tree (an EMA copy, say) passes through unplaced
    instead of crashing the rule resolution."""
    mesh = make_mesh(2, 1, devices=cpu_devices[:2])
    pipe = SpmdGPipe(biased_dense(P()), 2, mesh, chunks=2, loss_fn=mse)
    params = pipe._init_host(jax.random.PRNGKey(0), X32)
    ema = {"w": jnp.ones((3,))}
    placed = pipe.place({**params, "ema": ema})
    assert placed["ema"] is ema  # untouched
    assert placed["blocks"] is not params["blocks"]


def test_zero_refuses_dp_sharded_param_layout(cpu_devices):
    """A layout that already shards a leaf over dp breaks the ZeRO
    segment math (each lane would slice a DIFFERENT underlying shard);
    refused didactically like fsdp is."""
    import optax

    mesh = make_mesh(2, 2, devices=cpu_devices[:4])
    pipe = SpmdGPipe(
        biased_dense(P(), spec_w=P("dp")), 2, mesh, chunks=2,
        loss_fn=mse, dp_axis="dp",
    )
    params = pipe._init_host(jax.random.PRNGKey(0), X32)
    with pytest.raises(ValueError, match="dp-replicated parameters"):
        pipe.zero_opt_state(optax.sgd(1e-2), params)


def test_overrank_rule_spec_is_didactic_not_indexerror(cpu_devices):
    """A user rule whose spec names more dims than a matched leaf has
    must fail didactically at place() AND as a verifier ERROR — never
    a raw IndexError."""
    mesh = make_mesh(2, 1, tp=2, devices=cpu_devices[:4])
    table = pr.RuleTable(rules=(
        pr.PartitionRule(r".*", P("pp", None, "tp")),  # 3 dims, bias has 2
    ))
    import dataclasses as dc

    pipe = dc.replace(
        SpmdGPipe(biased_dense(P()), 2, mesh, chunks=2, loss_fn=mse,
                  tp_axis="tp"),
        partition_rules=table,
    )
    with pytest.raises(ValueError, match="rank-match"):
        pipe.place(pipe._init_host(jax.random.PRNGKey(0), X32))
    rep = shd.verify_layout(pipe, X32)
    errs = [f for f in rep.findings if f.severity >= Severity.ERROR]
    assert errs and "rank-match" in errs[0].message
