"""Lint rule engine tests: one deliberately-broken pipeline per rule.

Positive case: the rule fires with the right stage/eqn anchor; negative
case: the fixed pipeline lints clean.  Plus: every ``examples/*.py``
``build_for_lint`` model lints clean (the CLI contract of
``tools/pipeline_lint.py``), and the promoted walker still serves the
structural tests through the ``tests/jaxpr_utils.py`` shim.
"""

import dataclasses
import importlib.util
import pathlib
import sys

import jax
import jax.numpy as jnp
import pytest
from jax import lax

from torchgpipe_tpu import GPipe, SpmdGPipe, analysis, make_mesh
from torchgpipe_tpu.analysis import Severity
from torchgpipe_tpu.checkpoint import is_checkpointing
from torchgpipe_tpu.layers import Layer, chain, named
from torchgpipe_tpu.ops import dense, gelu, layer_norm


def mse(out, tgt):
    return jnp.mean((out - tgt) ** 2)


def _stateless(name, fn):
    def init(rng, in_spec):
        del rng, in_spec
        return (), ()

    def apply(params, state, x, *, rng=None, train=True):
        del params, rng, train
        return fn(x), state

    return Layer(name=name, init=init, apply=apply)


X = jax.ShapeDtypeStruct((4, 16), jnp.float32)
Y = jax.ShapeDtypeStruct((4, 8), jnp.float32)


def _mpmd_layers():
    return named([dense(16, name="fc1"), gelu("a1"), dense(8, name="head")])


def _rules_of(findings):
    return {f.rule for f in findings}


def _by_rule(findings, rule):
    return [f for f in findings if f.rule == rule]


# --------------------------------------------------------------------- #
# remat-coverage                                                        #
# --------------------------------------------------------------------- #


def test_remat_coverage_spmd_fires_and_anchors(cpu_devices):
    block = chain([layer_norm(name="ln"), dense(16, name="fc")], name="blk")
    mesh = make_mesh(2, 1, devices=cpu_devices[:2])
    pipe = SpmdGPipe(block, 2, mesh, chunks=2, loss_fn=mse,
                     checkpoint="always", dp_axis="dp")
    # The seeded bug: the engine's remat wrapper dropped — the configured
    # checkpoint mode no longer matches the compiled program.
    pipe._block_fn = pipe._block_fn_plain
    x = jax.ShapeDtypeStruct((8, 16), jnp.float32)
    found = _by_rule(analysis.lint(pipe, x), "remat-coverage")
    assert found and found[0].severity == Severity.ERROR
    assert found[0].path == "spmd/train"


def test_remat_coverage_spmd_clean(cpu_devices):
    block = chain([layer_norm(name="ln"), dense(16, name="fc")], name="blk")
    mesh = make_mesh(2, 1, devices=cpu_devices[:2])
    pipe = SpmdGPipe(block, 2, mesh, chunks=2, loss_fn=mse,
                     checkpoint="always", dp_axis="dp")
    x = jax.ShapeDtypeStruct((8, 16), jnp.float32)
    assert analysis.lint(pipe, x) == []


def _shady_dense(dim, name):
    """Skips its matmul while tracing the checkpointed forward — the
    recompute can then never reproduce the forward graph."""
    inner = dense(dim, name=name)

    def apply(params, state, x, *, rng=None, train=True):
        if is_checkpointing():
            return x, state
        return inner.apply(params, state, x, rng=rng, train=train)

    return dataclasses.replace(inner, apply=apply)


def test_remat_coverage_mpmd_divergence_fires():
    layers = named([dense(16, name="a"), _shady_dense(16, "shady"),
                    dense(8, name="h")])
    model = GPipe(layers, balance=[2, 1], chunks=2, checkpoint="always")
    found = _by_rule(
        analysis.lint(model, X, target=Y, loss_fn=mse), "remat-coverage"
    )
    assert found and found[0].severity == Severity.ERROR
    assert found[0].path == "stage0/checkpoint"


def test_remat_coverage_mpmd_clean():
    model = GPipe(_mpmd_layers(), balance=[2, 1], chunks=2,
                  checkpoint="always")
    assert analysis.lint(model, X, target=Y, loss_fn=mse) == []


# --------------------------------------------------------------------- #
# precision-drift                                                       #
# --------------------------------------------------------------------- #


def _upcasting_dense(dim, name):
    """Escapes the bf16 policy by re-upcasting params and input inside."""
    inner = dense(dim, name=name)

    def apply(params, state, x, *, rng=None, train=True):
        p32 = jax.tree_util.tree_map(lambda a: a.astype(jnp.float32), params)
        return inner.apply(p32, state, x.astype(jnp.float32), rng=rng,
                           train=train)

    return dataclasses.replace(inner, apply=apply)


def _bf16_norm(name):
    """An rms-norm that computes its statistics in the compute dtype."""
    return _stateless(
        name,
        lambda x: x * lax.rsqrt(
            jnp.mean(x * x, axis=-1, keepdims=True) + 1e-6
        ),
    )


def test_precision_drift_fires_on_upcast_matmul_and_bf16_stats():
    layers = named([_upcasting_dense(16, "up"), _bf16_norm("badnorm"),
                    dense(8, name="h")])
    model = GPipe(layers, balance=[2, 1], chunks=2,
                  compute_dtype=jnp.bfloat16)
    found = _by_rule(
        analysis.lint(model, X, target=Y, loss_fn=mse), "precision-drift"
    )
    prims = {f.primitive for f in found}
    assert "dot_general" in prims, found
    assert "rsqrt" in prims, found
    assert all(f.path.startswith("stage0") and f.eqn is not None
               for f in found)


def test_precision_drift_clean_on_policy_layers():
    layers = named([dense(16, name="up"), layer_norm(name="norm"),
                    dense(8, name="h")])
    model = GPipe(layers, balance=[2, 1], chunks=2,
                  compute_dtype=jnp.bfloat16)
    assert analysis.lint(model, X, target=Y, loss_fn=mse) == []


# --------------------------------------------------------------------- #
# collective-mismatch                                                   #
# --------------------------------------------------------------------- #


def _pp_psum_layer(name):
    """Mesh-guarded (inits fine outside shard_map) but reduces over the
    PIPELINE axis inside the schedule — mixes unrelated micro-batches."""

    def init(rng, in_spec):
        del rng, in_spec
        return (), ()

    def apply(params, state, x, *, rng=None, train=True):
        del params, rng, train
        try:
            return lax.psum(x, "pp") / 2.0, state
        except NameError:
            return x, state

    return Layer(name=name, init=init, apply=apply)


def test_collective_mismatch_pp_reduction_in_scan(cpu_devices):
    block = chain([dense(16, name="fc"), _pp_psum_layer("bad")], name="blk")
    mesh = make_mesh(2, 1, devices=cpu_devices[:2])
    pipe = SpmdGPipe(block, 2, mesh, chunks=2, loss_fn=mse,
                     checkpoint="always", dp_axis="dp")
    x = jax.ShapeDtypeStruct((8, 16), jnp.float32)
    found = _by_rule(analysis.lint(pipe, x), "collective-mismatch")
    assert found and all(f.severity == Severity.ERROR for f in found)
    assert found[0].path == "spmd/train" and found[0].eqn is not None


def test_collective_mismatch_unbound_axis_mpmd():
    bad = _stateless("bad", lambda x: lax.psum(x, "tp"))
    layers = named([dense(16, name="a"), bad, dense(8, name="h")])
    model = GPipe(layers, balance=[2, 1], chunks=2)
    found = _by_rule(
        analysis.lint(model, X, target=Y, loss_fn=mse),
        "collective-mismatch",
    )
    assert found and found[0].severity == Severity.ERROR
    assert "'tp'" in found[0].message


def test_collective_mismatch_clean_spmd(cpu_devices):
    block = chain([dense(16, name="fc"), gelu("act")], name="blk")
    mesh = make_mesh(2, 1, devices=cpu_devices[:2])
    pipe = SpmdGPipe(block, 2, mesh, chunks=2, loss_fn=mse,
                     checkpoint="always", dp_axis="dp")
    x = jax.ShapeDtypeStruct((8, 16), jnp.float32)
    assert analysis.lint(pipe, x) == []


# --------------------------------------------------------------------- #
# recompilation-hazard                                                  #
# --------------------------------------------------------------------- #


def test_recompilation_hazard_on_ragged_microbatches():
    model = GPipe(_mpmd_layers(), balance=[2, 1], chunks=4)
    x = jax.ShapeDtypeStruct((10, 16), jnp.float32)  # 10 % 4 != 0
    y = jax.ShapeDtypeStruct((10, 8), jnp.float32)
    found = _by_rule(
        analysis.lint(model, x, target=y, loss_fn=mse),
        "recompilation-hazard",
    )
    assert found and found[0].severity == Severity.WARNING
    assert "distinct shape signatures" in found[0].message


def test_recompilation_hazard_clean_on_even_split():
    model = GPipe(_mpmd_layers(), balance=[2, 1], chunks=4)
    x = jax.ShapeDtypeStruct((8, 16), jnp.float32)
    y = jax.ShapeDtypeStruct((8, 8), jnp.float32)
    assert analysis.lint(model, x, target=y, loss_fn=mse) == []


# --------------------------------------------------------------------- #
# pad-waste                                                             #
# --------------------------------------------------------------------- #


def _pad_waste_fixture():
    import numpy as np

    from torchgpipe_tpu.models.transformer import (
        TransformerConfig,
        llama,
        packed_cross_entropy_sum,
    )
    from torchgpipe_tpu.utils import data as D

    cfg = TransformerConfig(vocab=37, dim=16, n_layers=4, n_heads=2)
    model = GPipe(llama(cfg), balance=[3, 3], chunks=2)
    rng = np.random.RandomState(0)
    docs = [
        rng.randint(1, 37, size=int(rng.randint(2, 9))).astype(np.int32)
        for _ in range(8)
    ]
    return model, docs, D, packed_cross_entropy_sum


def test_pad_waste_fires_on_padded_concrete_batch():
    """Broken: a packing-capable llama linted on a concretely ~60%-
    padded batch WARNs with the pack_documents pointer."""
    model, docs, D, loss = _pad_waste_fixture()
    xt, yt = next(D.padded_batches(docs, 16, batch_rows=8))
    found = _by_rule(
        analysis.lint(model, jnp.asarray(xt), target=yt, loss_fn=loss),
        "pad-waste",
    )
    assert len(found) == 1
    assert found[0].severity == Severity.WARNING
    assert "pack_documents" in found[0].message


def test_pad_waste_stands_down_on_packed_and_abstract():
    """Fixed: the SAME pipeline on the packed batch lints fully clean
    (segment_ids present), and an abstract sample (shapes only, no
    values) cannot fire the rule."""
    model, docs, D, loss = _pad_waste_fixture()
    pk = D.pack_documents(docs, 16)
    # Batch rows padded to a multiple of chunks (all-pad no-op rows),
    # so the packed example is clean under EVERY rule.
    x, y = next(D.packed_batches(pk, pk.n_blocks + pk.n_blocks % 2))
    xj = {k: jnp.asarray(v) for k, v in x.items()}
    assert analysis.lint(model, xj, target=y, loss_fn=loss) == []
    assert analysis.lint(
        model, jax.ShapeDtypeStruct((8, 16), jnp.int32)
    ) == []


def test_pad_waste_detects_nonzero_pad_id():
    """eos-padded corpora (pad id != 0): the rule probes the batch's
    most-common final-column token, so a nonzero pad does not let it
    silently stand down."""
    import numpy as np

    model, docs, D, loss = _pad_waste_fixture()
    xt, yt = next(D.padded_batches(docs, 16, batch_rows=8, pad_id=2))
    assert np.all(np.asarray(xt)[:, -1] == 2)  # eos-style trailing pad
    found = _by_rule(
        analysis.lint(model, jnp.asarray(xt), target=yt, loss_fn=loss),
        "pad-waste",
    )
    assert len(found) == 1 and "pad id 2" in found[0].message


def test_pad_waste_stands_down_on_non_transformer():
    """A dense MLP is not packing-capable: heavy zero-padding in a
    float batch is not this rule's business."""
    model = GPipe(_mpmd_layers(), balance=[2, 1], chunks=2)
    x = jnp.zeros((4, 16), jnp.int32)  # int plane, all "pad"
    assert _by_rule(
        analysis.lint(model, x), "pad-waste"
    ) == []


# --------------------------------------------------------------------- #
# host-sync-in-loop                                                     #
# --------------------------------------------------------------------- #


def _chatty(name):
    def init(rng, in_spec):
        del rng, in_spec
        return (), ()

    def apply(params, state, x, *, rng=None, train=True):
        del params, rng, train
        jax.debug.print("mean {m}", m=jnp.mean(x))
        return x, state

    return Layer(name=name, init=init, apply=apply)


def test_host_sync_fires_inside_spmd_schedule(cpu_devices):
    block = chain([dense(16, name="fc"), _chatty("dbg")], name="blk")
    mesh = make_mesh(2, 1, devices=cpu_devices[:2])
    pipe = SpmdGPipe(block, 2, mesh, chunks=2, loss_fn=mse,
                     checkpoint="always", dp_axis="dp")
    x = jax.ShapeDtypeStruct((8, 16), jnp.float32)
    found = _by_rule(analysis.lint(pipe, x), "host-sync-in-loop")
    # Inside the schedule scan: ERROR severity, anchored into spmd/train.
    assert found and found[0].severity == Severity.ERROR
    assert found[0].path == "spmd/train"
    assert found[0].primitive == "debug_print"


def test_host_sync_warns_in_mpmd_stage_program():
    layers = named([dense(16, name="a"), _chatty("dbg"), dense(8, name="h")])
    model = GPipe(layers, balance=[2, 1], chunks=2)
    found = _by_rule(
        analysis.lint(model, X, target=Y, loss_fn=mse), "host-sync-in-loop"
    )
    assert found
    assert any(f.path.startswith("stage0") for f in found)
    fixed = GPipe(_mpmd_layers(), balance=[2, 1], chunks=2)
    assert analysis.lint(fixed, X, target=Y, loss_fn=mse) == []


# --------------------------------------------------------------------- #
# dead-code                                                             #
# --------------------------------------------------------------------- #


def _wasteful_dense(dim, name):
    inner = dense(dim, name=name)

    def apply(params, state, x, *, rng=None, train=True):
        y, s = inner.apply(params, state, x, rng=rng, train=train)
        _ = x @ jnp.ones((x.shape[-1], 4), x.dtype)  # never consumed
        return y, s

    return dataclasses.replace(inner, apply=apply)


def _biasless_dense(dim, name):
    inner = dense(dim, name=name)

    def apply(params, state, x, *, rng=None, train=True):
        del state, rng, train
        return x @ params["w"], ()  # params['b'] never read

    return dataclasses.replace(inner, apply=apply)


def test_dead_code_fires_on_dead_matmul_and_unused_param():
    layers = named([_wasteful_dense(16, "waste"),
                    _biasless_dense(8, "nb")])
    model = GPipe(layers, balance=[1, 1], chunks=2)
    found = _by_rule(
        analysis.lint(model, X, target=Y, loss_fn=mse), "dead-code"
    )
    msgs = [f.message for f in found]
    assert any("dot_general" == f.primitive for f in found), found
    assert any("nb['b']" in m for m in msgs), msgs
    # anchored per stage
    assert {f.path for f in found} == {"stage0/forward", "stage1/forward"}


def test_dead_code_clean():
    model = GPipe(_mpmd_layers(), balance=[2, 1], chunks=2)
    assert analysis.lint(model, X, target=Y, loss_fn=mse) == []


# --------------------------------------------------------------------- #
# remat-policy-names                                                    #
# --------------------------------------------------------------------- #


def _named_dense(dim, name, tag="attn_out"):
    """A dense layer whose output is a checkpoint-named save point."""
    from jax.ad_checkpoint import checkpoint_name

    inner = dense(dim, name=name)

    def apply(params, state, x, *, rng=None, train=True):
        y, s = inner.apply(params, state, x, rng=rng, train=train)
        return checkpoint_name(y, tag), s

    return dataclasses.replace(inner, apply=apply)


def test_remat_policy_names_fires_on_silent_noop(cpu_devices):
    from torchgpipe_tpu.checkpoint import policies

    # The seeded bug: a named-save policy over a model that emits NO
    # checkpoint_name tags — the policy saves nothing and the engine
    # silently recomputes everything ('always' cost at 'policy' spelling).
    block = chain([layer_norm(name="ln"), dense(16, name="fc")], name="blk")
    mesh = make_mesh(2, 1, devices=cpu_devices[:2])
    pipe = SpmdGPipe(block, 2, mesh, chunks=2, loss_fn=mse,
                     checkpoint="always", dp_axis="dp",
                     remat_policy=policies.save_attn_out)
    x = jax.ShapeDtypeStruct((8, 16), jnp.float32)
    found = _by_rule(analysis.lint(pipe, x), "remat-policy-names")
    assert found and found[0].severity == Severity.ERROR
    assert "silent no-op" in found[0].message
    assert "attn_out" in found[0].message


def test_remat_policy_names_clean_when_tags_exist(cpu_devices):
    from torchgpipe_tpu.checkpoint import policies

    block = chain([layer_norm(name="ln"), _named_dense(16, "fc")],
                  name="blk")
    mesh = make_mesh(2, 1, devices=cpu_devices[:2])
    pipe = SpmdGPipe(block, 2, mesh, chunks=2, loss_fn=mse,
                     checkpoint="always", dp_axis="dp",
                     remat_policy=policies.save_attn_out)
    x = jax.ShapeDtypeStruct((8, 16), jnp.float32)
    assert analysis.lint(pipe, x) == []


def test_remat_policy_names_warns_on_partially_missing(cpu_devices):
    from torchgpipe_tpu.checkpoint import policies

    block = chain([layer_norm(name="ln"), _named_dense(16, "fc")],
                  name="blk")
    mesh = make_mesh(2, 1, devices=cpu_devices[:2])
    pipe = SpmdGPipe(block, 2, mesh, chunks=2, loss_fn=mse,
                     checkpoint="always", dp_axis="dp",
                     remat_policy=policies.save_names("attn_out", "nope"))
    x = jax.ShapeDtypeStruct((8, 16), jnp.float32)
    found = _by_rule(analysis.lint(pipe, x), "remat-policy-names")
    assert found and found[0].severity == Severity.WARNING
    assert "'nope'" in found[0].message


def test_remat_policy_names_default_offload_is_quiet(cpu_devices):
    # checkpoint='offload' installs the catch-all default preset: models
    # that emit SOME canonical tag must not warn about the tags they
    # don't (e.g. no flash kernel in the path).
    block = chain([layer_norm(name="ln"), _named_dense(16, "fc")],
                  name="blk")
    mesh = make_mesh(2, 1, devices=cpu_devices[:2])
    pipe = SpmdGPipe(block, 2, mesh, chunks=2, loss_fn=mse,
                     checkpoint="offload", dp_axis="dp")
    x = jax.ShapeDtypeStruct((8, 16), jnp.float32)
    assert _by_rule(analysis.lint(pipe, x), "remat-policy-names") == []


# --------------------------------------------------------------------- #
# suppression + API surface                                             #
# --------------------------------------------------------------------- #


def test_suppression_by_rule_and_path():
    layers = named([_wasteful_dense(16, "waste"), dense(8, name="h")])
    model = GPipe(layers, balance=[1, 1], chunks=2)
    assert _by_rule(
        analysis.lint(model, X, target=Y, loss_fn=mse,
                      suppress=("dead-code",)),
        "dead-code",
    ) == []
    assert _by_rule(
        analysis.lint(model, X, target=Y, loss_fn=mse,
                      suppress=("dead-code@stage0",)),
        "dead-code",
    ) == []
    # a non-matching path prefix must NOT suppress
    assert _by_rule(
        analysis.lint(model, X, target=Y, loss_fn=mse,
                      suppress=("dead-code@stage1",)),
        "dead-code",
    ) != []


def test_rule_subset_selection():
    layers = named([_wasteful_dense(16, "waste"), _chatty("dbg"),
                    dense(8, name="h")])
    model = GPipe(layers, balance=[2, 1], chunks=2)
    found = analysis.lint(model, X, target=Y, loss_fn=mse,
                          rules=["host-sync-in-loop"])
    assert _rules_of(found) == {"host-sync-in-loop"}


def test_findings_sorted_and_formatted():
    layers = named([_wasteful_dense(16, "waste"), _chatty("dbg"),
                    dense(8, name="h")])
    model = GPipe(layers, balance=[2, 1], chunks=2)
    found = analysis.lint(model, X, target=Y, loss_fn=mse)
    sevs = [int(f.severity) for f in found]
    assert sevs == sorted(sevs, reverse=True)
    report = analysis.format_findings(found)
    assert "finding(s)" in report
    for f in found:
        assert f.anchor in report


def test_unknown_rule_name_fails_before_tracing():
    model = GPipe(_mpmd_layers(), balance=[2, 1], chunks=2)
    with pytest.raises(ValueError, match="unknown lint rule.*remat-coverage"):
        analysis.lint(model, X, rules=["remat"])  # typo'd name


def test_register_rule_is_selectable_by_name():
    calls = []

    def check(trace):
        calls.append(trace.engine)
        return []

    rule = analysis.Rule("custom-check", "test rule", check)
    analysis.register_rule(rule)
    try:
        with pytest.raises(ValueError, match="already registered"):
            analysis.register_rule(rule)
        model = GPipe(_mpmd_layers(), balance=[2, 1], chunks=2)
        assert analysis.lint(model, X, rules=["custom-check"]) == []
        assert calls == ["mpmd"]
    finally:
        analysis.RULES.remove(rule)
        del analysis.RULES_BY_NAME["custom-check"]


def test_lint_rejects_non_pipeline():
    with pytest.raises(TypeError, match="GPipe or SpmdGPipe"):
        analysis.lint(object(), X)


def test_cli_exits_nonzero_on_seeded_violation(capsys):
    from tools.pipeline_lint import main

    fixture = str(
        pathlib.Path(__file__).parent / "fixtures" / "lint_violation.py"
    )
    assert main([fixture]) == 1
    out = capsys.readouterr().out
    assert "host-sync-in-loop" in out and "dead-code" in out
    # --fail-on error relaxes past warnings but host-sync in a stage
    # program is itself only a warning; suppressing both rules is clean.
    assert main([fixture, "--suppress", "host-sync-in-loop",
                 "--suppress", "dead-code"]) == 0


# --------------------------------------------------------------------- #
# examples must lint clean (the CLI contract)                           #
# --------------------------------------------------------------------- #

_EXAMPLES = [
    # hf_finetune imports torch + transformers (~50 s cold) — slow-marked
    # so the tier-1 budget holds; tools/ci_lint.py still gates it.
    pytest.param(p, marks=pytest.mark.slow)
    if p.stem == "hf_finetune"
    else p
    for p in sorted(
        (pathlib.Path(__file__).parent.parent / "examples").glob("*.py")
    )
]


@pytest.mark.parametrize("path", _EXAMPLES, ids=lambda p: p.stem)
def test_examples_lint_clean(path, cpu_devices):
    if path.stem == "hf_finetune":
        pytest.importorskip("transformers")
        pytest.importorskip("torch")
    modname = f"_lint_example_{path.stem}"
    spec = importlib.util.spec_from_file_location(modname, path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[modname] = mod
    spec.loader.exec_module(mod)
    assert hasattr(mod, "build_for_lint"), (
        f"{path.name} must expose build_for_lint() for tools/pipeline_lint.py"
    )
    from tools.pipeline_lint import normalize_cases

    for case in normalize_cases(mod.build_for_lint()):
        findings = analysis.lint(
            case["pipe"], case["x"], target=case["target"],
            loss_fn=case["loss_fn"], suppress=case["suppress"],
        )
        assert findings == [], (
            f"{path.name}[{case['name']}]:\n"
            + analysis.format_findings(findings)
        )


# --------------------------------------------------------------------- #
# the jaxpr_utils shim stays walker-free                                #
# --------------------------------------------------------------------- #


def test_jaxpr_utils_is_a_pure_shim():
    src = (
        pathlib.Path(__file__).parent / "jaxpr_utils.py"
    ).read_text()
    import ast

    tree = ast.parse(src)
    defs = [n for n in ast.walk(tree)
            if isinstance(n, (ast.FunctionDef, ast.AsyncFunctionDef))]
    assert defs == [], "tests/jaxpr_utils.py must hold no traversal logic"
    import tests.jaxpr_utils as shim
    from torchgpipe_tpu.analysis import jaxpr as core

    for name in shim.__all__:
        assert getattr(shim, name) is getattr(core, name)


# --------------------------------------------------------------------- #
# plan-drift (the planner's lint rule; see tests/test_planner.py for    #
# the planner itself)                                                   #
# --------------------------------------------------------------------- #


def _driftable_model(**kw):
    layers = named([dense(16, name="fc1"), gelu("a1"),
                    dense(16, name="fc2"), dense(8, name="head")])
    return GPipe(layers, balance=[2, 2], chunks=2, **kw)


def test_plan_drift_fires_on_stale_config():
    # The seeded drift: full recompute at 2 chunks when the certified
    # top plan under this budget is no-recompute at more chunks — well
    # past the 10% MFU threshold.
    model = _driftable_model(checkpoint="always",
                             hbm_budget_bytes=64 * 2 ** 30)
    found = _by_rule(
        analysis.lint(model, X, target=Y, loss_fn=mse,
                      rules=["plan-drift"]),
        "plan-drift",
    )
    assert found and found[0].severity == Severity.WARNING
    assert "certified top plan" in found[0].message
    assert "apply_plan" in found[0].message  # the fix is named in the message


def test_plan_drift_clean_after_apply_plan():
    from torchgpipe_tpu.analysis import planner

    model = _driftable_model(checkpoint="always",
                             hbm_budget_bytes=64 * 2 ** 30)
    report = planner.plan(model, X, hbm_budget_bytes=64 * 2 ** 30)
    fixed = planner.apply_plan(model, report.best)
    assert fixed.hbm_budget_bytes == 64 * 2 ** 30
    assert analysis.lint(fixed, X, target=Y, loss_fn=mse,
                         rules=["plan-drift"]) == []


def test_plan_drift_stands_down_without_declared_budget():
    model = _driftable_model(checkpoint="always")  # no hbm_budget_bytes
    assert analysis.lint(model, X, target=Y, loss_fn=mse,
                         rules=["plan-drift"]) == []


# --------------------------------------------------------------------- #
# stale-cost-model (obs.costmodel's lint rule; the measured-pricing     #
# mirror of the PR 8 stale-report stand-down)                           #
# --------------------------------------------------------------------- #


def _cost_model_for(model):
    from torchgpipe_tpu.obs.costmodel import (
        CellCost, CostModel, config_fingerprint,
    )

    cells = {}
    for j in range(len(model.balance)):
        cells[(j, "fwd")] = CellCost(1e-3, 2)
        cells[(j, "bwd")] = CellCost(2e-3, 2)
    return CostModel(fingerprint=config_fingerprint(model), cells=cells)


def test_stale_cost_model_fires_on_reconfigured_pipe():
    # Broken: the model was measured under checkpoint='always'; the pipe
    # now runs 'never' — its measurements describe a plan that no longer
    # exists, and plan(cost_model=...) silently degrades to analytic.
    measured = _driftable_model(checkpoint="always")
    cm = _cost_model_for(measured)
    current = _driftable_model(checkpoint="never")
    cm.attach(current)
    found = _by_rule(
        analysis.lint(current, X, target=Y, loss_fn=mse,
                      rules=["stale-cost-model"]),
        "stale-cost-model",
    )
    assert found and found[0].severity == Severity.WARNING
    assert "STALE" in found[0].message
    assert "checkpoint" in found[0].message  # names the drifted key
    assert "Re-measure" in found[0].message  # the fix is named


def test_stale_cost_model_fresh_attachment_stands_down():
    # Fixed: the attachment matches the running configuration.
    model = _driftable_model(checkpoint="always")
    _cost_model_for(model).attach(model)
    assert analysis.lint(model, X, target=Y, loss_fn=mse,
                         rules=["stale-cost-model"]) == []


def test_stale_cost_model_no_attachment_stands_down():
    model = _driftable_model(checkpoint="always")
    assert analysis.lint(model, X, target=Y, loss_fn=mse,
                         rules=["stale-cost-model"]) == []


# --------------------------------------------------------------------- #
# dispatch-per-step (megastep availability)                             #
# --------------------------------------------------------------------- #


def _dispatchy_spmd(cpu_devices, **kw):
    import optax

    block = chain([layer_norm(name="ln"), dense(16, name="fc")], name="blk")
    mesh = make_mesh(2, 1, devices=cpu_devices[:2])
    pipe = SpmdGPipe(block, 2, mesh, chunks=2, loss_fn=mse,
                     checkpoint="always", **kw)
    return pipe, optax.sgd(1e-2)


def test_dispatch_per_step_fires_on_donated_k1_step(cpu_devices):
    # The seeded inefficiency: a DONATED train step (per-step StepGuard
    # retry already impossible) dispatched once per optimizer step.
    pipe, opt = _dispatchy_spmd(cpu_devices)
    pipe.make_train_step(opt, donate=True)
    x = jax.ShapeDtypeStruct((8, 16), jnp.float32)
    found = _by_rule(analysis.lint(pipe, x, rules=["dispatch-per-step"]),
                     "dispatch-per-step")
    assert found and found[0].severity == Severity.WARNING
    assert "megastep" in found[0].message
    assert "donate=False" in found[0].message  # the stand-down is named


def test_dispatch_per_step_clean_with_megastep(cpu_devices):
    pipe, opt = _dispatchy_spmd(cpu_devices, megastep=4)
    pipe.make_train_step(opt, donate=True)
    x = jax.ShapeDtypeStruct((8, 16), jnp.float32)
    assert analysis.lint(pipe, x, rules=["dispatch-per-step"]) == []


def test_dispatch_per_step_stands_down_for_guard_semantics(cpu_devices):
    # donate=False means the user wants StepGuard's per-step retry —
    # which NEEDS the Python boundary; the rule must not fight it.
    pipe, opt = _dispatchy_spmd(cpu_devices)
    pipe.make_train_step(opt, donate=False)
    x = jax.ShapeDtypeStruct((8, 16), jnp.float32)
    assert analysis.lint(pipe, x, rules=["dispatch-per-step"]) == []


def test_dispatch_per_step_stands_down_without_train_step(cpu_devices):
    # No train step built: nothing to judge.
    pipe, _ = _dispatchy_spmd(cpu_devices)
    x = jax.ShapeDtypeStruct((8, 16), jnp.float32)
    assert analysis.lint(pipe, x, rules=["dispatch-per-step"]) == []


def test_plan_drift_respects_per_step_guard_choice(cpu_devices):
    """Dispatch-granularity coherence between plan-drift and
    dispatch-per-step: WITHOUT a donated train step the drift rule
    compares only candidates at the pipe's own megastep/scan_unroll
    (per-step StepGuard semantics may be deliberate), so a tiny pipe is
    not flagged merely for running K=1; WITH a donated step the full
    K x unroll space applies and the K=1 config drifts."""
    import optax

    pipe, opt = _dispatchy_spmd(cpu_devices,
                                hbm_budget_bytes=64 * 2 ** 30)
    x = jax.ShapeDtypeStruct((8, 16), jnp.float32)
    # donate=False (or no step at all): the K axis is filtered out.
    pipe.make_train_step(opt, donate=False)
    assert _by_rule(analysis.lint(pipe, x, rules=["plan-drift"]),
                    "plan-drift") == []
    # A donated step opens the megastep axis: on this tiny model the
    # dispatch term dominates, so K=1 drifts far past the threshold.
    pipe2, opt2 = _dispatchy_spmd(cpu_devices,
                                  hbm_budget_bytes=64 * 2 ** 30)
    pipe2.make_train_step(opt2, donate=True)
    found = _by_rule(analysis.lint(pipe2, x, rules=["plan-drift"]),
                     "plan-drift")
    assert found and "megastep" in found[0].message


def test_dispatch_per_step_stands_down_on_per_cell_mpmd():
    import optax

    model = GPipe(_mpmd_layers(), balance=[2, 1], chunks=2)
    model.make_train_step(optax.sgd(1e-2), mse, donate=True)
    assert analysis.lint(model, X, target=Y, loss_fn=mse,
                         rules=["dispatch-per-step"]) == []


# --------------------------------------------------------------------- #
# dispatch-only-timeline (obs trace-spine hygiene)                      #
# --------------------------------------------------------------------- #


def test_dispatch_only_timeline_fires_on_async_tracer():
    # The seeded hazard: a sync=False timeline records dispatch
    # intervals, whose simulate_pipeline/obs.reconcile projections would
    # be meaningless — the rule names the fix.
    from torchgpipe_tpu.utils.tracing import Timeline

    model = GPipe(_mpmd_layers(), balance=[2, 1], chunks=2,
                  tracer=Timeline(sync=False))
    found = _by_rule(
        analysis.lint(model, X, target=Y, loss_fn=mse,
                      rules=["dispatch-only-timeline"]),
        "dispatch-only-timeline",
    )
    assert found and found[0].severity == Severity.WARNING
    assert "sync=True" in found[0].message


def test_dispatch_only_timeline_stands_down_on_sync_tracer():
    from torchgpipe_tpu.utils.tracing import Timeline

    model = GPipe(_mpmd_layers(), balance=[2, 1], chunks=2,
                  tracer=Timeline(sync=True))
    assert analysis.lint(model, X, target=Y, loss_fn=mse,
                         rules=["dispatch-only-timeline"]) == []


def test_dispatch_only_timeline_stands_down_without_tracer():
    model = GPipe(_mpmd_layers(), balance=[2, 1], chunks=2)
    assert analysis.lint(model, X, target=Y, loss_fn=mse,
                         rules=["dispatch-only-timeline"]) == []


# --------------------------------------------------------------------- #
# implicit-reshard (the sharding verifier's lint rule; see              #
# tests/test_sharding.py for the verifier itself)                       #
# --------------------------------------------------------------------- #


def _sharded_bias_block(spec_b):
    from jax.sharding import PartitionSpec as P  # noqa: F401

    def init(rng, spec):
        d = spec.shape[-1]
        return {"w": jax.random.normal(rng, (d, d)) * 0.02,
                "b": jnp.zeros((d,))}, ()

    def apply(params, state, x, *, rng=None, train=True):
        del rng, train
        return x @ params["w"] + params["b"], state

    return Layer(name="bd", init=init, apply=apply,
                 meta={"param_specs": {"w": P(), "b": spec_b}})


def test_implicit_reshard_warns_on_layout_induced_gather(cpu_devices):
    """Broken: a tp-sharded bias leaks sharding to the block output,
    which the replicated pipeline carry must gather EVERY schedule tick
    — the rule WARNs through the lint path with the fix named."""
    from jax.sharding import PartitionSpec as P

    mesh = make_mesh(2, 1, tp=2, devices=cpu_devices[:4])
    pipe = SpmdGPipe(_sharded_bias_block(P("tp")), 2, mesh, chunks=2,
                     loss_fn=mse, tp_axis="tp")
    found = _by_rule(
        analysis.lint(pipe, jax.ShapeDtypeStruct((4, 8), jnp.float32),
                      rules=["implicit-reshard"]),
        "implicit-reshard",
    )
    assert found
    warns = [f for f in found if f.severity == Severity.WARNING]
    assert any("stage boundary" in f.message for f in warns)
    assert any("psum_value" in f.message for f in warns)  # the fix


def test_implicit_reshard_errors_on_unmatched_leaf(cpu_devices):
    """Broken: a user partition-rule table that names no rule for a
    leaf — silent replication — is an ERROR, anchored at the leaf."""
    from jax.sharding import PartitionSpec as P
    from torchgpipe_tpu.analysis import partition_rules as pr

    mesh = make_mesh(2, 1, devices=cpu_devices[:2])
    pipe = SpmdGPipe(
        _sharded_bias_block(P()), 2, mesh, chunks=2, loss_fn=mse,
        partition_rules=pr.RuleTable(rules=(
            pr.PartitionRule(r"blocks/w$", P("pp")),
        )),
    )
    found = _by_rule(
        analysis.lint(pipe, jax.ShapeDtypeStruct((4, 8), jnp.float32),
                      rules=["implicit-reshard"]),
        "implicit-reshard",
    )
    errors = [f for f in found if f.severity == Severity.ERROR]
    assert errors and "blocks/b" in errors[0].path
    assert "silently replicate" in errors[0].message


def test_implicit_reshard_clean_on_replicated_and_closed_tp(cpu_devices):
    """Fixed twins: a replicated layout, and a PROPERLY CLOSED Megatron
    tp block (psum_value after the row-parallel matmuls), both lint
    clean — the required tp psums are priced, not flagged."""
    from torchgpipe_tpu.models.transformer import (
        TransformerConfig, cross_entropy, llama_spmd,
    )
    from jax.sharding import PartitionSpec as P

    mesh = make_mesh(2, 1, devices=cpu_devices[:2])
    plain = SpmdGPipe(_sharded_bias_block(P()), 2, mesh, chunks=2,
                      loss_fn=mse)
    assert analysis.lint(plain, jax.ShapeDtypeStruct((4, 8), jnp.float32),
                         rules=["implicit-reshard"]) == []

    cfg = TransformerConfig(vocab=64, dim=32, n_layers=2, n_heads=4,
                            n_kv_heads=2, tp_axis="tp")
    block, pre, post = llama_spmd(cfg, 2)
    tp_mesh = make_mesh(2, 1, tp=2, devices=cpu_devices[:4])
    tp_pipe = SpmdGPipe(block, 2, tp_mesh, chunks=2,
                        loss_fn=cross_entropy, pre=pre, post=post,
                        tp_axis="tp")
    assert analysis.lint(tp_pipe, jax.ShapeDtypeStruct((8, 8), jnp.int32),
                         rules=["implicit-reshard"]) == []


# --------------------------------------------------------------------- #
# redundant-gather (gather-at-use / ZeRO-3 hygiene)                     #
# --------------------------------------------------------------------- #


def _double_use_block():
    """A block whose weight feeds TWO matmuls — under
    gather_schedule='use' each consumption would re-gather it."""
    from jax.sharding import PartitionSpec as P

    def init(rng, spec):
        d = spec.shape[-1]
        return {"w": jax.random.normal(rng, (d, d)) * 0.02}, ()

    def apply(params, state, x, *, rng=None, train=True):
        del rng, train
        return x @ params["w"] @ params["w"], state

    return Layer(name="dw", init=init, apply=apply,
                 meta={"param_specs": {"w": P()}})


def test_redundant_gather_warns_on_per_use_schedule(cpu_devices):
    """Broken: an fsdp (gather-at-use) leaf consumed by two equations of
    the block body under gather_schedule='use' — block params are
    read-only, so the second gather is pure wasted all_gather traffic;
    the rule names the fix (gather once per block)."""
    pipe = SpmdGPipe(_double_use_block(), 2,
                     make_mesh(2, 2, devices=cpu_devices[:4]), chunks=2,
                     loss_fn=mse, dp_axis="dp", fsdp=True,
                     gather_schedule="use")
    found = _by_rule(
        analysis.lint(pipe, jax.ShapeDtypeStruct((4, 8), jnp.float32),
                      rules=["redundant-gather"]),
        "redundant-gather",
    )
    warns = [f for f in found if f.severity == Severity.WARNING]
    assert warns and any("blocks/w" in f.path for f in warns)
    assert "gather_schedule='block'" in warns[0].message  # the fix


def test_redundant_gather_clean_on_block_schedule(cpu_devices):
    """Fixed twin: the same double-use layout under the compiled
    gather_schedule='block' (one gather per block body) lints clean."""
    pipe = SpmdGPipe(_double_use_block(), 2,
                     make_mesh(2, 2, devices=cpu_devices[:4]), chunks=2,
                     loss_fn=mse, dp_axis="dp", fsdp=True)
    assert analysis.lint(pipe, jax.ShapeDtypeStruct((4, 8), jnp.float32),
                         rules=["redundant-gather"]) == []


def test_redundant_gather_errors_when_window_exceeds_budget(cpu_devices):
    """Broken: the ZeRO-3 gathered window ALONE over the declared
    hbm_budget_bytes is an ERROR — sharded storage cannot save a model
    whose transient gathered copies don't fit.  Fixed twin: a budget
    with head-room for the window lints clean."""
    pipe = SpmdGPipe(_double_use_block(), 2,
                     make_mesh(2, 2, devices=cpu_devices[:4]), chunks=2,
                     loss_fn=mse, dp_axis="dp", fsdp=True,
                     hbm_budget_bytes=64)
    found = _by_rule(
        analysis.lint(pipe, jax.ShapeDtypeStruct((4, 8), jnp.float32),
                      rules=["redundant-gather"]),
        "redundant-gather",
    )
    errors = [f for f in found if f.severity == Severity.ERROR]
    assert errors and "gathered window alone" in errors[0].message
    import dataclasses as dc

    roomy = dc.replace(pipe, hbm_budget_bytes=1 << 30)
    assert analysis.lint(roomy, jax.ShapeDtypeStruct((4, 8), jnp.float32),
                         rules=["redundant-gather"]) == []


def test_redundant_gather_stands_down_without_gather_leaves(cpu_devices):
    """Stand-downs: a replicated (non-fsdp, no declared rules) pipe has
    no gather-at-use leaves; and single-use fsdp leaves under
    gather_schedule='use' gather once — nothing is redundant."""
    from jax.sharding import PartitionSpec as P

    plain = SpmdGPipe(_sharded_bias_block(P()), 2,
                      make_mesh(2, 1, devices=cpu_devices[:2]), chunks=2,
                      loss_fn=mse)
    assert analysis.lint(plain, jax.ShapeDtypeStruct((4, 8), jnp.float32),
                         rules=["redundant-gather"]) == []
    single = SpmdGPipe(_sharded_bias_block(P()), 2,
                       make_mesh(2, 2, devices=cpu_devices[:4]), chunks=2,
                       loss_fn=mse, dp_axis="dp", fsdp=True,
                       gather_schedule="use")
    assert analysis.lint(single, jax.ShapeDtypeStruct((4, 8), jnp.float32),
                         rules=["redundant-gather"]) == []


# --------------------------------------------------------------------- #
# capacity-overflow                                                     #
# --------------------------------------------------------------------- #


def _moe_mpmd_pipe(capacity_factor, dispatch="dense"):
    from torchgpipe_tpu.models.moe import MoEConfig, llama_moe
    from torchgpipe_tpu.models.transformer import TransformerConfig

    cfg = TransformerConfig(vocab=64, dim=16, n_layers=2, n_heads=2,
                            n_kv_heads=2)
    moe = MoEConfig(n_experts=4, top_k=2, capacity_factor=capacity_factor,
                    dispatch=dispatch)
    return GPipe(llama_moe(cfg, moe), balance=[2, 2], chunks=2)


_MOE_TOK = jax.ShapeDtypeStruct((4, 8), jnp.int32)


def test_capacity_overflow_warns_on_tight_factor():
    """Broken twin: capacity_factor=0.25 at top_k=2 gives the 4 experts
    2 slots each for 32 routed assignments per lane — even a PERFECT
    router drops 75% of them, silently, every step.  One WARNING per
    MoE block, anchored to the meta index, telling the user about the
    dropless escape hatch."""
    pipe = _moe_mpmd_pipe(0.25)
    found = _by_rule(
        analysis.lint(pipe, _MOE_TOK, rules=["capacity-overflow"]),
        "capacity-overflow",
    )
    assert len(found) == 2  # llama_moe: one MoE feed-forward per block
    assert all(f.severity == Severity.WARNING for f in found)
    assert found[0].path == "mpmd/moe[0]"
    assert found[1].path == "mpmd/moe[1]"
    assert "capacity_factor=0.25" in found[0].message
    assert "dropless" in found[0].message  # names the escape hatch


def test_capacity_overflow_stands_down_when_slots_suffice():
    """Fixed twins: a generous factor has slots >= demand (zero forced
    drops), and dropless dispatch has no capacity buffer at all — both
    lint clean even with the tight factor that fired above."""
    assert analysis.lint(_moe_mpmd_pipe(8.0), _MOE_TOK,
                         rules=["capacity-overflow"]) == []
    assert analysis.lint(_moe_mpmd_pipe(0.25, dispatch="dropless"),
                         _MOE_TOK, rules=["capacity-overflow"]) == []


def test_capacity_overflow_top_k_exceeds_experts_is_error():
    """top_k > n_experts cannot arise through `moe_mlp` (its ctor
    refuses), but layer metas are open — a hand-made record must surface
    as an ERROR (the iterative top-k would repeat experts and the
    combine would double-count them), not as a capacity warning."""
    bad = dataclasses.replace(
        _stateless("fake_moe", lambda x: x),
        meta={"moe": {"n_experts": 2, "top_k": 3, "capacity_factor": 1.0}},
    )
    pipe = GPipe(named([dense(16, name="fc1"), bad,
                        dense(8, name="head")]),
                 balance=[2, 1], chunks=2)
    found = _by_rule(
        analysis.lint(pipe, X, rules=["capacity-overflow"]),
        "capacity-overflow",
    )
    assert len(found) == 1
    assert found[0].severity == Severity.ERROR
    assert "top_k=3 exceeds n_experts=2" in found[0].message
