#!/usr/bin/env python
"""Repo lint: forbid module-IMPORT-time jax device probes.

``jax.devices()`` (and friends) at import time initializes the backend as a
side effect of ``import``. A chip belongs to one process at a time, so a
parent that merely IMPORTS this package must stay free to start the child
that needs the chip (``chip_smoke.py``'s parent, the serving ``Router``
over engine workers); and an import-time probe permanently fixes the
platform before ``_jax_compat.set_cpu_devices`` can run, which is why the
conftest must win that race. No module makes a device decision at import;
any may probe at CALL time (inside a function), where callers control
bring-up.

Usage: ``python bin/check_import_time_devices.py [root]`` — prints
violations as ``path:line: message`` and exits nonzero if any. Checked
from tests/test_repo_lint.py so CI enforces it.
"""
from __future__ import annotations

import ast
import os
import sys

#: jax attributes whose call initializes the backend
FORBIDDEN = ("devices", "local_devices", "device_count",
             "local_device_count")

#: modules allowed to make import-time platform decisions (none)
ALLOWED_FILES: tuple[str, ...] = ()


def _is_jax_probe(node: ast.Call) -> str | None:
    f = node.func
    if isinstance(f, ast.Attribute) and f.attr in FORBIDDEN \
            and isinstance(f.value, ast.Name) and f.value.id == "jax":
        return f.attr
    return None


class _Visitor(ast.NodeVisitor):
    """Flags jax device probes reachable at import time: module level,
    class bodies, and default-argument expressions — anything outside a
    function/lambda body."""

    def __init__(self, path: str):
        self.path = path
        self.violations: list[str] = []
        self._depth = 0

    def _visit_fn(self, node):
        # defaults/decorators evaluate at DEF time (import time for
        # top-level defs) — scan them at the current depth
        for expr in (*getattr(node.args, "defaults", ()),
                     *getattr(node.args, "kw_defaults", ()),
                     *node.decorator_list):
            if expr is not None:
                self.visit(expr)
        self._depth += 1
        for stmt in node.body:
            self.visit(stmt)
        self._depth -= 1

    visit_FunctionDef = _visit_fn
    visit_AsyncFunctionDef = _visit_fn

    def visit_Lambda(self, node):
        self._depth += 1
        self.visit(node.body)
        self._depth -= 1

    def visit_Call(self, node):
        attr = _is_jax_probe(node)
        if attr and self._depth == 0:
            self.violations.append(
                f"{self.path}:{node.lineno}: import-time jax.{attr}() — "
                f"move it inside a function")
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
    entry = os.path.join(root, "__graft_entry__.py")
    if os.path.exists(entry):
        targets.append(entry)
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
        print(f"{len(violations)} import-time device probe(s) found")
    return 1 if violations else 0


if __name__ == "__main__":
    sys.exit(main(sys.argv))
