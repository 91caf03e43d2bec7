#!/usr/bin/env python
"""Repo lint: the serving wire protocol cannot rot silently.

Every message on the router<->replica line protocol is a dict literal
with a ``"t"`` type tag (protocol.py documents the vocabulary), and
every receiver dispatches on that tag (``t == "put"``,
``t in ("chunk", "done", ...)``, ``msg["t"] == "chunk"``). Nothing
structural used to tie the two ends together: a new sender whose type
tag no receiver matches streams messages into the void (the resync
vocabulary this lint was built for is exactly such an easy-to-miss
addition), and a handler branch whose type nobody constructs anymore is
dead protocol surface that reads as supported. This AST check (the
check_reqtrace_events.py shape) enforces both directions across
``deepspeed_tpu/serving/``:

- **every sent type is handled**: each ``{"t": "<literal>", ...}`` dict
  constructed anywhere in the package must appear in at least one
  receiver-side comparison against a message type tag;
- **every handled type is sent**: each string a dispatch comparison
  names must be constructed as a ``{"t": ...}`` literal somewhere (a
  relay that forwards ``{**msg}`` rides the original literal);
- **pinned fields ride their message**: every literal of a type listed
  in ``REQUIRED_FIELDS`` carries those keys (a ``ready`` that stops
  naming the ``platform`` it runs on would let a worker serve from the
  CPU on a machine with a chip, and nothing would say so).

Comparison sites recognized as dispatch: ``Eq``/``NotEq``/``In``/
``NotIn`` compares where one side is the conventional tag expression —
a bare ``t`` name, ``<x>["t"]`` or ``<x>.get("t")`` — and the other is
a string literal or a tuple/list/set of them. Dynamic tags cannot be
checked statically; keep them literals — the protocol is grep'd by tag.

Usage: ``python bin/check_protocol_msgs.py [root]`` — prints violations
as ``path:line: message`` and exits nonzero if any. Enforced from
tests/test_repo_lint.py.
"""
from __future__ import annotations

import ast
import os
import sys

#: the directory whose wire protocol this lint governs
SERVING_DIR = os.path.join("deepspeed_tpu", "serving")

#: the message type-tag key
TAG = "t"

#: types legitimately one-sided (none today; additions need a reason)
ALLOWED_UNHANDLED: set[str] = set()
ALLOWED_UNSENT: set[str] = set()

#: keys every literal of a message type must carry
REQUIRED_FIELDS: dict[str, tuple[str, ...]] = {
    "ready": ("platform", "device_kind"),
}


def _is_tag_expr(node: ast.AST) -> bool:
    """The conventional 'message type tag' expressions: a bare ``t``
    name (the ``t = msg.get("t")`` idiom), ``<x>["t"]``, or
    ``<x>.get("t")``."""
    if isinstance(node, ast.Name) and node.id == TAG:
        return True
    if isinstance(node, ast.Subscript):
        sl = node.slice
        return isinstance(sl, ast.Constant) and sl.value == TAG
    if isinstance(node, ast.Call) and isinstance(node.func, ast.Attribute) \
            and node.func.attr == "get" and node.args \
            and isinstance(node.args[0], ast.Constant) \
            and node.args[0].value == TAG:
        return True
    return False


def _str_consts(node: ast.AST) -> list[str]:
    if isinstance(node, ast.Constant) and isinstance(node.value, str):
        return [node.value]
    if isinstance(node, (ast.Tuple, ast.List, ast.Set)):
        out = []
        for el in node.elts:
            if isinstance(el, ast.Constant) and isinstance(el.value, str):
                out.append(el.value)
        return out
    return []


def scan_file(path: str) -> tuple[dict, dict, list[str]]:
    """(sent, handled, errors): type -> first ``path:line`` site."""
    with open(path, encoding="utf-8") as f:
        try:
            tree = ast.parse(f.read(), filename=path)
        except SyntaxError as e:
            return {}, {}, [f"{path}:{e.lineno}: unparseable ({e.msg})"]
    sent: dict[str, str] = {}
    handled: dict[str, str] = {}
    errors: list[str] = []
    for node in ast.walk(tree):
        if isinstance(node, ast.Dict):
            keys = {k.value for k in node.keys
                    if isinstance(k, ast.Constant)}
            for k, v in zip(node.keys, node.values):
                if isinstance(k, ast.Constant) and k.value == TAG \
                        and isinstance(v, ast.Constant) \
                        and isinstance(v.value, str):
                    sent.setdefault(v.value, f"{path}:{node.lineno}")
                    for need in REQUIRED_FIELDS.get(v.value, ()):
                        if need not in keys:
                            errors.append(
                                f"{path}:{node.lineno}: protocol message "
                                f"{v.value!r} is built without its "
                                f"required field {need!r}")
        elif isinstance(node, ast.Compare) and len(node.ops) == 1 \
                and isinstance(node.ops[0], (ast.Eq, ast.NotEq,
                                             ast.In, ast.NotIn)):
            sides = [node.left, node.comparators[0]]
            if any(_is_tag_expr(s) for s in sides):
                for s in sides:
                    for val in _str_consts(s):
                        handled.setdefault(val, f"{path}:{node.lineno}")
    return sent, handled, errors


def check_repo(root: str) -> list[str]:
    serving = os.path.join(root, SERVING_DIR)
    if not os.path.isdir(serving):
        return [f"{serving}:0: serving package missing — the protocol "
                f"lint has nothing to govern (wrong root?)"]
    sent: dict[str, str] = {}
    handled: dict[str, str] = {}
    violations: list[str] = []
    for dirpath, _, files in os.walk(serving):
        for f in sorted(files):
            if not f.endswith(".py"):
                continue
            s, h, errs = scan_file(os.path.join(dirpath, f))
            violations += errs
            for k, site in s.items():
                sent.setdefault(k, site)
            for k, site in h.items():
                handled.setdefault(k, site)
    for k in sorted(set(sent) - set(handled) - ALLOWED_UNHANDLED):
        violations.append(
            f"{sent[k]}: protocol message type {k!r} is sent but no "
            f"receiver dispatches on it — the message streams into the "
            f"void (add the handler branch, or the allowlist entry with "
            f"a reason)")
    for k in sorted(set(handled) - set(sent) - ALLOWED_UNSENT):
        violations.append(
            f"{handled[k]}: protocol handler matches type {k!r} but "
            f"nothing constructs it — dead protocol surface (delete the "
            f"branch, or send it)")
    return violations


def main(argv: list[str]) -> int:
    root = argv[1] if len(argv) > 1 else \
        os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    violations = check_repo(root)
    for v in violations:
        print(v)
    if violations:
        print(f"{len(violations)} protocol-vocabulary violation(s) found")
    return 1 if violations else 0


if __name__ == "__main__":
    sys.exit(main(sys.argv))
