#!/usr/bin/env python
"""Pipeline linter CLI: statically verify pipeline invariants on a model.

Usage::

    python tools/pipeline_lint.py examples/quickstart.py [more.py ...]
    python tools/pipeline_lint.py examples/*.py --fail-on error
    python tools/pipeline_lint.py mypkg.models:build_for_lint

Each target is a Python file (or ``module:function`` spec) exposing a
``build_for_lint()`` entrypoint that BUILDS the pipeline without training
it, returning one lint case or a list of them.  A case is either a tuple
``(pipe, sample_input[, target[, loss_fn]])`` or a dict with keys ``pipe``,
``x`` and optionally ``target``, ``loss_fn``, ``name``, ``suppress``.

The model is traced abstractly (no device compute, no XLA compile) and the
rule engine of :mod:`torchgpipe_tpu.analysis` reports findings as
``path/stage:eqn``-anchored diagnostics.  Exit status is 0 iff no finding
reaches ``--fail-on`` (default: warning).  Rule catalog and suppression
syntax: docs/analysis.md.
"""

from __future__ import annotations

import argparse
import importlib
import importlib.util
import os
import pathlib
import sys
from typing import Any, List, Sequence, Tuple

# Lint builds SPMD meshes (up to 8 lanes in the examples); pin the platform
# to CPU FIRST and force virtual host devices — a static tool needs no
# accelerator and must never hold one.
os.environ.setdefault("TF_CPP_MIN_LOG_LEVEL", "3")
import jax  # noqa: E402

if os.environ.get("TGPU_LINT_ON_BACKEND") != "1":
    jax.config.update("jax_platforms", "cpu")
    os.environ["XLA_FLAGS"] = (
        os.environ.get("XLA_FLAGS", "")
        + " --xla_force_host_platform_device_count=8"
    ).strip()

REPO = pathlib.Path(__file__).resolve().parent.parent
if str(REPO) not in sys.path:
    sys.path.insert(0, str(REPO))

from torchgpipe_tpu import analysis  # noqa: E402

ENTRYPOINT = "build_for_lint"


def load_entrypoint(target: str) -> Tuple[str, Any]:
    """Resolve ``path/to/file.py[:fn]`` or ``module.path:fn`` to a callable."""
    spec = target
    fn_name = ENTRYPOINT
    if ":" in target:
        spec, _, fn_name = target.rpartition(":")
    if spec.endswith(".py") or os.path.sep in spec:
        path = pathlib.Path(spec)
        modname = f"_lint_{path.stem}"
        mspec = importlib.util.spec_from_file_location(modname, path)
        if mspec is None or mspec.loader is None:
            raise SystemExit(f"pipeline_lint: cannot load {spec}")
        mod = importlib.util.module_from_spec(mspec)
        sys.modules[modname] = mod
        mspec.loader.exec_module(mod)
        label = str(path)
    else:
        mod = importlib.import_module(spec)
        label = spec
    if not hasattr(mod, fn_name):
        raise SystemExit(
            f"pipeline_lint: {label} has no {fn_name}() entrypoint — add "
            "one that builds the pipeline (no training) and returns "
            "(pipe, sample_input[, target[, loss_fn]]) or a list of such "
            "cases"
        )
    return label, getattr(mod, fn_name)


def normalize_cases(built: Any) -> List[dict]:
    """Entrypoint return value -> list of {name, pipe, x, target, loss_fn,
    suppress} dicts."""
    if isinstance(built, (tuple, dict)):
        built = [built]
    cases = []
    for i, case in enumerate(built):
        if isinstance(case, tuple):
            pipe, x = case[0], case[1]
            target = case[2] if len(case) > 2 else None
            loss_fn = case[3] if len(case) > 3 else None
            case = {"pipe": pipe, "x": x, "target": target,
                    "loss_fn": loss_fn}
        case = dict(case)
        case.setdefault("name", f"case{i}")
        case.setdefault("target", None)
        case.setdefault("loss_fn", None)
        case.setdefault("suppress", ())
        return_missing = {"pipe", "x"} - set(case)
        if return_missing:
            raise SystemExit(
                f"pipeline_lint: case {case['name']} is missing keys "
                f"{sorted(return_missing)}"
            )
        cases.append(case)
    return cases


def lint_target(
    target: str,
    rules: Any,
    suppress: Sequence[str],
    verbose: bool,
) -> List[analysis.Finding]:
    label, build = load_entrypoint(target)
    findings: List[analysis.Finding] = []
    for case in normalize_cases(build()):
        got = analysis.lint(
            case["pipe"],
            case["x"],
            target=case["target"],
            loss_fn=case["loss_fn"],
            rules=rules,
            suppress=tuple(suppress) + tuple(case["suppress"]),
        )
        tag = f"{label}[{case['name']}]"
        if verbose or got:
            print(f"== {tag}")
            print(analysis.format_findings(got))
        findings.extend(got)
    return findings


def main(argv: Sequence[str] | None = None) -> int:
    ap = argparse.ArgumentParser(
        description="Static pipeline linter (torchgpipe_tpu.analysis)."
    )
    ap.add_argument("targets", nargs="+",
                    help="Python files or module:function lint entrypoints")
    ap.add_argument("--rules", default=None,
                    help="comma-separated rule subset (default: all)")
    ap.add_argument("--suppress", action="append", default=[],
                    metavar="RULE[@PATH]",
                    help="suppress a rule (optionally under a path prefix); "
                    "repeatable")
    ap.add_argument("--fail-on", choices=["info", "warning", "error"],
                    default="warning",
                    help="lowest severity that fails the run "
                    "(default: warning)")
    ap.add_argument("-v", "--verbose", action="store_true",
                    help="print per-target reports even when clean")
    args = ap.parse_args(argv)

    rules = args.rules.split(",") if args.rules else None
    try:
        analysis.validate_rule_names(rules)
    except ValueError as e:
        raise SystemExit(f"pipeline_lint: {e}") from None
    threshold = analysis.Severity[args.fail_on.upper()]

    all_findings: List[analysis.Finding] = []
    for target in args.targets:
        all_findings.extend(
            lint_target(target, rules, args.suppress, args.verbose)
        )
    worst = analysis.max_severity(all_findings)
    n_fail = sum(1 for f in all_findings if f.severity >= threshold)
    print(
        f"pipeline_lint: {len(args.targets)} target(s), "
        f"{len(all_findings)} finding(s), "
        f"{n_fail} at or above --fail-on={args.fail_on}"
    )
    return 1 if (worst is not None and worst >= threshold) else 0


if __name__ == "__main__":
    raise SystemExit(main())
