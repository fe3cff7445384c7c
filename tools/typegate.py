#!/usr/bin/env python
"""Strict annotation gate for ``torchgpipe_tpu/`` — the runnable
``disallow_untyped_defs`` equivalent (reference: setup.cfg ``[mypy]``
enforces ``disallow_untyped_defs`` over its package with ~1,000 LoC of
stubs; this container cannot install mypy, so the same contract is
enforced by AST inspection, which CI *can* run anywhere).

Rules (package files only):
* every module-level function and every class method must annotate ALL
  parameters (``self``/``cls`` exempt) and the return type;
* nested functions (closures) are exempt: they implement the ``Layer``
  init/apply protocol whose types are fixed by ``layers.InitFn/ApplyFn``
  — annotating each closure would restate those aliases hundreds of
  times (mypy's equivalent escape is ``disallow_untyped_defs = False``
  per-section; ours is structural and narrower);
* ``# typegate: ignore`` on the ``def`` line skips that one function.

Exit 0 iff clean; prints one ``path:line: message`` per violation.
Run: ``python tools/typegate.py`` (from the repo root), or via the CI
lint job.
"""

from __future__ import annotations

import ast
import pathlib
import sys

# The resolution check imports every package module; pin the platform to
# CPU FIRST: a static gate needs no accelerator and must never hold one
# (a chip belongs to one process at a time).
import jax

jax.config.update("jax_platforms", "cpu")

PACKAGE = pathlib.Path(__file__).resolve().parent.parent / "torchgpipe_tpu"
TOOLS = pathlib.Path(__file__).resolve().parent


def _violations_in(path: pathlib.Path) -> list[str]:
    src = path.read_text()
    tree = ast.parse(src, filename=str(path))
    lines = src.splitlines()
    out: list[str] = []

    def check_fn(fn: ast.FunctionDef | ast.AsyncFunctionDef,
                 *, method: bool) -> None:
        if "typegate: ignore" in lines[fn.lineno - 1]:
            return
        a = fn.args
        params = list(a.posonlyargs) + list(a.args) + list(a.kwonlyargs)
        if method and params and params[0].arg in ("self", "cls"):
            params = params[1:]
        missing = [p.arg for p in params if p.annotation is None]
        for star in (a.vararg, a.kwarg):
            if star is not None and star.annotation is None:
                missing.append("*" + star.arg)
        where = f"{path.relative_to(PACKAGE.parent)}:{fn.lineno}"
        if missing:
            out.append(
                f"{where}: def {fn.name}: unannotated parameter(s) "
                f"{', '.join(missing)}"
            )
        if fn.returns is None and fn.name != "__init__":
            out.append(f"{where}: def {fn.name}: missing return annotation")

    class V(ast.NodeVisitor):
        def visit_FunctionDef(self, node: ast.FunctionDef) -> None:
            check_fn(node, method=False)
            # Do NOT recurse: nested defs are protocol closures (exempt).

        visit_AsyncFunctionDef = visit_FunctionDef  # type: ignore[assignment]

        def visit_ClassDef(self, node: ast.ClassDef) -> None:
            for item in node.body:
                if isinstance(item, (ast.FunctionDef, ast.AsyncFunctionDef)):
                    check_fn(item, method=True)
                elif isinstance(item, ast.ClassDef):
                    self.visit_ClassDef(item)

    for node in tree.body:
        if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
            check_fn(node, method=False)
        elif isinstance(node, ast.ClassDef):
            V().visit_ClassDef(node)
    return out


def _unresolved_annotation_names(path: pathlib.Path) -> list[str]:
    """Annotation names that resolve neither in the imported module nor in
    builtins — lazy ``from __future__ import annotations`` hides these at
    runtime, so the gate catches them (the local stand-in for ruff F821)."""
    import builtins
    import importlib

    if str(PACKAGE.parent) not in sys.path:
        sys.path.insert(0, str(PACKAGE.parent))
    rel = path.relative_to(PACKAGE.parent).with_suffix("")
    modname = ".".join(rel.parts)
    if rel.name == "__init__":
        modname = ".".join(rel.parts[:-1]) or "torchgpipe_tpu"
    try:
        mod = importlib.import_module(modname)
    except Exception as e:  # pragma: no cover - import errors surface in CI
        return [f"{path}: cannot import {modname}: {e}"]
    tree = ast.parse(path.read_text(), filename=str(path))
    out: list[str] = []
    for node in ast.walk(tree):
        if not isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
            continue
        a = node.args
        anns = [p.annotation for p in
                list(a.posonlyargs) + list(a.args) + list(a.kwonlyargs)]
        anns += [s.annotation for s in (a.vararg, a.kwarg) if s is not None]
        anns.append(node.returns)
        for ann in anns:
            if ann is None:
                continue
            for x in ast.walk(ann):
                if isinstance(x, ast.Name) and not hasattr(mod, x.id) \
                        and not hasattr(builtins, x.id):
                    out.append(
                        f"{path.relative_to(PACKAGE.parent)}:{node.lineno}: "
                        f"def {node.name}: annotation name {x.id!r} does "
                        "not resolve in the module"
                    )
    return out


def main() -> int:
    files = sorted(PACKAGE.rglob("*.py"))
    if not files:
        print(f"typegate: no package files under {PACKAGE}", file=sys.stderr)
        return 2
    bad: list[str] = []
    for f in files:
        bad.extend(_violations_in(f))
        bad.extend(_unresolved_annotation_names(f))
    # tools/ scripts get the annotation rule too (no import-resolution
    # pass: scripts are entrypoints, not package modules — importing them
    # here would run their CLI setup twice).
    tool_files = sorted(TOOLS.glob("*.py"))
    for f in tool_files:
        bad.extend(_violations_in(f))
    for msg in bad:
        print(msg)
    print(
        f"typegate: {len(files)} package + {len(tool_files)} tool files, "
        f"{len(bad)} violation(s)",
        file=sys.stderr,
    )
    return 1 if bad else 0


if __name__ == "__main__":
    raise SystemExit(main())
