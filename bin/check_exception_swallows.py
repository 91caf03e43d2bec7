#!/usr/bin/env python
"""Repo lint: forbid silent broad-exception swallows.

A bare ``except Exception: pass`` inside ``deepspeed_tpu/`` is how recovery
paths eat the very faults the resilience layer (runtime/resilience.py)
exists to surface — a checkpoint commit error or a watchdog report that
dies in a silent handler looks exactly like a healthy run until the job is
unrecoverable. Every broad handler must DO something: log, re-raise,
return a fallback, or record the error.

Allowed:
- narrow handlers (``except OSError: pass`` documents a specific, expected
  condition);
- ``__del__`` bodies (interpreter-shutdown teardown races are idiomatic).

Usage: ``python bin/check_exception_swallows.py [root]`` — prints
violations as ``path:line: message`` and exits nonzero if any. Enforced
from tests/test_repo_lint.py.
"""
from __future__ import annotations

import ast
import os
import sys

#: exception names whose silent swallow is banned
BROAD = ("Exception", "BaseException")

#: files allowed to swallow (none)
ALLOWED_FILES: tuple[str, ...] = ()

#: enclosing function names where swallowing is idiomatic
ALLOWED_FUNCS = ("__del__",)


def _names(expr: ast.expr | None) -> list[str]:
    """Exception class names a handler catches ('' for bare ``except:``)."""
    if expr is None:
        return [""]
    if isinstance(expr, ast.Tuple):
        return [n for e in expr.elts for n in _names(e)]
    if isinstance(expr, ast.Name):
        return [expr.id]
    if isinstance(expr, ast.Attribute):
        return [expr.attr]
    return []


def _is_silent(body: list[ast.stmt]) -> bool:
    """True when the handler body does nothing observable."""
    for stmt in body:
        if isinstance(stmt, ast.Pass):
            continue
        if isinstance(stmt, ast.Expr) and isinstance(stmt.value, ast.Constant):
            continue  # docstring / bare `...`
        return False
    return True


class _Visitor(ast.NodeVisitor):
    def __init__(self, path: str):
        self.path = path
        self.violations: list[str] = []
        self._func_stack: list[str] = []

    def _visit_fn(self, node):
        self._func_stack.append(node.name)
        self.generic_visit(node)
        self._func_stack.pop()

    visit_FunctionDef = _visit_fn
    visit_AsyncFunctionDef = _visit_fn

    def visit_ExceptHandler(self, node: ast.ExceptHandler):
        caught = _names(node.type)
        broad = any(n in BROAD or n == "" for n in caught)
        if broad and _is_silent(node.body) \
                and not any(f in ALLOWED_FUNCS for f in self._func_stack):
            what = caught[0] or "bare except"
            self.violations.append(
                f"{self.path}:{node.lineno}: silent '{what}' swallow — "
                f"log, narrow the exception, or handle it")
        self.generic_visit(node)


def check_file(path: str) -> list[str]:
    with open(path, encoding="utf-8") as f:
        src = f.read()
    try:
        tree = ast.parse(src, filename=path)
    except SyntaxError as e:
        return [f"{path}:{e.lineno}: unparseable ({e.msg})"]
    v = _Visitor(path)
    v.visit(tree)
    return v.violations


def check_repo(root: str) -> list[str]:
    out: list[str] = []
    pkg = os.path.join(root, "deepspeed_tpu")
    targets = []
    for dirpath, _, files in os.walk(pkg):
        targets += [os.path.join(dirpath, f) for f in files
                    if f.endswith(".py") and f not in ALLOWED_FILES]
    for path in sorted(targets):
        out += check_file(path)
    return out


def main(argv: list[str]) -> int:
    root = argv[1] if len(argv) > 1 else \
        os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    violations = check_repo(root)
    for v in violations:
        print(v)
    if violations:
        print(f"{len(violations)} silent broad-exception swallow(s) found")
    return 1 if violations else 0


if __name__ == "__main__":
    sys.exit(main(sys.argv))
