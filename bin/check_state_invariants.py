#!/usr/bin/env python
"""Repo lint: block-list mutations must go through the refcounted API.

With the shared-prefix KV cache (inference/prefix_cache.py), a pool block
can be owned by the free list, the prefix trie (refcounted, shared by live
sequences), or one sequence's owned tail. That invariant only holds while
every mutation flows through ``StateManager``'s refcounted alloc/free API
(``admit`` / ``release`` / ``_alloc``): a stray ``allocator.free(...)`` in
engine code would free a page the trie still serves (stale-read), and a
direct ``seq.blocks = ...`` would skip the refcount bookkeeping entirely.
This AST check (the check_exception_swallows.py shape) rejects, anywhere
in ``deepspeed_tpu/`` outside the allowlisted ``StateManager`` methods:

- calls through an ``allocator`` attribute to ``allocate``/``free``;
- calls through a ``prefix_cache`` attribute to the ownership-mutating
  surface (``match``/``acquire``/``release``/``publish``/``evict`` —
  ``match`` included because a matched chain must be acquired in the same
  host operation, before any other admit/evict can run);
- assignments to a ``.blocks`` attribute, and mutating method calls on
  one (``.blocks.append(...)`` etc.);
- assignments to a ``.n_provisional`` attribute (speculative decoding's
  provisional-slot marker): legal ONLY inside the rollback-aware
  ``StateManager`` methods (``provision`` / ``commit_speculative`` /
  ``rollback_provisional`` / ``rewind``) — a stray mutation elsewhere
  would let a verify round's rejected candidates skip the rollback
  bookkeeping and desync the full-pool ``audit()``;
- assignments to a ``.migrating`` attribute (KV-page migration's
  pin/freeze flag): legal ONLY inside the refcounted
  export/import/abort API (``migrate_out`` / ``export_ack`` /
  ``export_abort`` / ``migrate_in_begin`` / ``import_commit`` /
  ``abort_import``) — a stray mutation would let a pinned export's
  pages be scheduled or released mid-transfer;
- assignments to a ``.weight_version`` / ``._weight_version`` attribute
  (the serving weight hot-swap's version stamp, serving/deploy.py):
  legal ONLY inside the swap API (``engine_v2.swap_weights``, the
  replica backends' ``swap_weights``, ``PrefixCache.set_weight_version``
  and the respective ``__init__``\\ s) — the version gates cross-replica
  KV transfer, so a stray mutation would let skewed pages migrate as
  "same version" (exactly the silent corruption the guard exists to
  stop). The router-side heartbeat MIRROR deliberately uses a different
  attribute name (``ReplicaHandle.wv``) so it stays writable.

- calls of ``merge_records`` (the write of a RECORD kind's per-slot
  state — a "conv" layer's last inputs, addressed by the slot a live
  sequence holds, with no allocator behind it): legal ONLY where a program
  writes its records ONCE, for the rows live in it (``forward.merge_step``
  for a step plan, ``engine_v2._window_program`` after the window's scan).
  A second write inside a program — or one from the host — could land a
  decode window's record over a half-prefilled sequence's, which no
  ``audit()`` of slots can see.

Reads (``allocator.free_blocks``, ``prefix_cache.stats()``, iterating
``seq.blocks``) are fine anywhere.

Usage: ``python bin/check_state_invariants.py [root]`` — prints violations
as ``path:line: message`` and exits nonzero if any. Enforced from
tests/test_repo_lint.py.
"""
from __future__ import annotations

import ast
import os
import sys

#: the one file hosting the refcounted API
STATE_FILE = "deepspeed_tpu/inference/ragged.py"

#: (rule, function name) pairs allowed inside STATE_FILE
ALLOWED = {
    #: _reserve_more/_free_more: the tables of a model's further kinds of
    #: layer (a window kind's ring beside the primary's growing table),
    #: reserved in every kind or in none and freed with the sequence
    "allocator": {"_alloc", "release", "migrate_in_begin",
                  "import_commit", "abort_import", "adopt_prefix",
                  "flush_prefix_cache", "_reserve_more", "_free_more"},
    #: snapshot_prefix/release_prefix/adopt_prefix are the cross-replica
    #: radix-pull surface (placement-time distributed cache): the export
    #: leg's gather-scoped pin and the import leg's unreferenced adopt
    #: both mutate trie ownership and so must live behind the same
    #: refcounted API as admit/release; flush_prefix_cache is the weight
    #: hot-swap's skew guard (evict-everything-unreferenced at swap
    #: commit — stale pages must not seed post-swap prefills)
    "prefix_cache": {"admit", "release", "_alloc", "import_commit",
                     "snapshot_prefix", "release_prefix", "adopt_prefix",
                     "flush_prefix_cache"},
    "blocks": {"admit", "migrate_in_begin", "import_commit",
               "abort_import"},
    "n_provisional": {"provision", "commit_speculative",
                      "rollback_provisional", "rewind"},
    #: KV-page migration (inference/migration.py): the pin/freeze flag.
    #: A stray mutation would let a "pinned" export's pages be scheduled
    #: or released mid-transfer — exactly the double-own/stale hazard the
    #: refcounted export/import/abort API exists to prevent.
    "migrating": {"migrate_out", "export_ack", "export_abort",
                  "migrate_in_begin", "import_commit", "abort_import"},
}

#: weight-version mutation sites: (file basename, function) pairs — the
#: swap API plus the constructors that establish the initial version.
#: Unlike the StateManager rules these span three files, so the rule
#: carries its own location set instead of riding STATE_FILE.
WEIGHT_VERSION_ALLOWED = {
    ("engine_v2.py", "__init__"), ("engine_v2.py", "swap_weights"),
    ("replica.py", "__init__"), ("replica.py", "swap_weights"),
    ("prefix_cache.py", "__init__"),
    ("prefix_cache.py", "set_weight_version"),
}

#: KV tiering (inference/kvtier.py): the tier's demote/promote
#: mutators. ``absorb`` ingests an evicted chain (only the eviction
#: sink may feed it — a stray absorb could tier pages whose pool
#: content doesn't match the chain key, exactly the stale-serve hazard
#: the trie's mutator pinning prevents); ``extract`` pairs with the
#: refcounted adopt + scatter path (a stray extract whose bundle never
#: adopts would inflate promote stats and skip the version-skew gate's
#: counters); ``extract_begin``/``extract_finish`` are the promote-
#: ahead two-phase form of ``extract`` and carry the same hazard (a
#: begin whose finish never runs must leave the tier byte-identical —
#: only the pinned wrappers uphold that, so a stray begin/finish
#: elsewhere could split the promote across incompatible state);
#: ``set_weight_version``/``close`` mutate tier membership.
#: The implementation file itself (kvtier.py) is exempt like ragged.py
#: is for the StateManager rules.
KV_TIER_MUTATORS = {"absorb", "extract", "extract_begin",
                    "extract_finish", "set_weight_version", "close"}
KV_TIER_FILE = "deepspeed_tpu/inference/kvtier.py"
KV_TIER_ALLOWED = {
    ("engine_v2.py", "_demote_evicted"),
    ("engine_v2.py", "_tier_promote"),
    ("engine_v2.py", "tier_promote_begin"),
    ("engine_v2.py", "tier_promote_finish"),
    ("engine_v2.py", "swap_weights"),
    ("replica.py", "_demote_evicted"),
    ("replica.py", "_tier_promote"),
    ("replica.py", "tier_promote_begin"),
    ("replica.py", "tier_promote_finish"),
    ("replica.py", "kv_export"),
    ("replica.py", "swap_weights"),
    ("replica.py", "_flush_radix"),
    ("replica.py", "serve"),            # graceful-shutdown close(flush)
}

#: the prefix cache's eviction sink (the demotion hook): assignment is
#: pinned to the attach sites so a stray handler can't silently
#: redirect (or drop) demotions
EVICT_SINK_ALLOWED = {
    ("prefix_cache.py", "__init__"),
    ("engine_v2.py", "__init__"),
    ("replica.py", "__init__"),
}

#: the one write of a program's records (a record kind's state a slot):
#: (file basename, function) pairs
RECORD_WRITE_ALLOWED = {("forward.py", "merge_step"),
                        ("engine_v2.py", "_window_program")}

#: mutating list-method names (on a ``.blocks`` attribute)
LIST_MUTATORS = {"append", "extend", "insert", "pop", "remove", "clear",
                 "sort", "reverse"}

#: prefix-cache methods that change block ownership / pinning
CACHE_MUTATORS = {"match", "acquire", "release", "publish", "evict",
                  "adopt"}


def _chain(node: ast.expr) -> list[str]:
    """Attribute chain names, outermost last: self.allocator.free ->
    ['self', 'allocator', 'free'] ('' for non-name bases)."""
    out: list[str] = []
    while isinstance(node, ast.Attribute):
        out.append(node.attr)
        node = node.value
    out.append(node.id if isinstance(node, ast.Name) else "")
    return out[::-1]


class _Visitor(ast.NodeVisitor):
    def __init__(self, path: str, in_state_file: bool,
                 in_kvtier_file: bool = False):
        self.path = path
        self.fname = os.path.basename(path)
        self.in_state_file = in_state_file
        self.in_kvtier_file = in_kvtier_file
        self.violations: list[str] = []
        self._func_stack: list[str] = []

    def _visit_fn(self, node):
        self._func_stack.append(node.name)
        self.generic_visit(node)
        self._func_stack.pop()

    visit_FunctionDef = _visit_fn
    visit_AsyncFunctionDef = _visit_fn

    def _allowed(self, rule: str) -> bool:
        return self.in_state_file and any(
            f in ALLOWED[rule] for f in self._func_stack)

    def _flag(self, node: ast.AST, rule: str, what: str) -> None:
        if not self._allowed(rule):
            ok = ", ".join(sorted(ALLOWED[rule]))
            self.violations.append(
                f"{self.path}:{node.lineno}: {what} outside the refcounted "
                f"StateManager API (allowed only in {STATE_FILE} "
                f"{ok}) — route through admit/release")

    def visit_Call(self, node: ast.Call):
        name = node.func.id if isinstance(node.func, ast.Name) \
            else getattr(node.func, "attr", "")
        if name == "merge_records" and not any(
                (self.fname, f) in RECORD_WRITE_ALLOWED
                for f in self._func_stack):
            ok = ", ".join(sorted(f"{f}:{fn}"
                                  for f, fn in RECORD_WRITE_ALLOWED))
            self.violations.append(
                f"{self.path}:{node.lineno}: merge_records() called outside "
                f"the one record write of a program (allowed only in {ok}) "
                f"— a record kind's state is written once a program, for "
                f"the rows live in it")
        if isinstance(node.func, ast.Attribute):
            chain = _chain(node.func)
            if len(chain) >= 2:
                # private aliases count: engine_v2 holds the cache as
                # self._prefix_cache — a stray mutator through THAT name
                # is exactly the misuse this lint exists to catch
                base, meth = chain[-2].lstrip("_"), chain[-1]
                if base == "allocator" and meth in ("allocate", "free"):
                    self._flag(node, "allocator",
                               f"direct allocator.{meth}() call")
                elif base == "prefix_cache" and meth in CACHE_MUTATORS:
                    self._flag(node, "prefix_cache",
                               f"direct prefix_cache.{meth}() call")
                elif base == "kv_tier" and meth in KV_TIER_MUTATORS \
                        and not self.in_kvtier_file \
                        and not any((self.fname, f) in KV_TIER_ALLOWED
                                    for f in self._func_stack):
                    ok = ", ".join(sorted(
                        f"{f}:{fn}" for f, fn in KV_TIER_ALLOWED))
                    self.violations.append(
                        f"{self.path}:{node.lineno}: direct "
                        f"kv_tier.{meth}() call outside the demote/"
                        f"promote wrappers (allowed only in {ok}) — "
                        f"demotes feed through the eviction sink, "
                        f"promotes through adopt_prefix + the scatter")
                elif base == "blocks" and meth in LIST_MUTATORS \
                        and len(chain) >= 3:
                    # len >= 3: only ATTRIBUTE block lists (seq.blocks.*);
                    # a bare local list that happens to be named `blocks`
                    # (the scheduler's plan-building scratch) is fine
                    self._flag(node, "blocks",
                               f"block-list mutation .blocks.{meth}()")
        self.generic_visit(node)

    def _flag_weight_version(self, node: ast.AST) -> None:
        if any((self.fname, f) in WEIGHT_VERSION_ALLOWED
               for f in self._func_stack):
            return
        ok = ", ".join(sorted(f"{f}:{fn}"
                              for f, fn in WEIGHT_VERSION_ALLOWED))
        self.violations.append(
            f"{self.path}:{node.lineno}: assignment to a "
            f".weight_version attribute outside the swap API (allowed "
            f"only in {ok}) — the version gates cross-replica KV "
            f"transfer; route through swap_weights/set_weight_version")

    def _check_targets(self, node, targets) -> None:
        for t in targets:
            if isinstance(t, ast.Attribute) and t.attr == "blocks":
                self._flag(node, "blocks",
                           "assignment to a .blocks attribute")
            elif isinstance(t, ast.Attribute) and t.attr == "n_provisional":
                self._flag(node, "n_provisional",
                           "assignment to a .n_provisional attribute")
            elif isinstance(t, ast.Attribute) and t.attr == "migrating":
                self._flag(node, "migrating",
                           "assignment to a .migrating attribute")
            elif isinstance(t, ast.Attribute) \
                    and t.attr.lstrip("_") == "weight_version":
                self._flag_weight_version(node)
            elif isinstance(t, ast.Attribute) and t.attr == "evict_sink" \
                    and not any((self.fname, f) in EVICT_SINK_ALLOWED
                                for f in self._func_stack):
                ok = ", ".join(sorted(f"{f}:{fn}"
                                      for f, fn in EVICT_SINK_ALLOWED))
                self.violations.append(
                    f"{self.path}:{node.lineno}: assignment to a "
                    f".evict_sink attribute outside the tier attach "
                    f"sites (allowed only in {ok}) — a stray handler "
                    f"could silently redirect or drop demotions")
            elif isinstance(t, (ast.Tuple, ast.List)):
                self._check_targets(node, t.elts)

    def visit_Assign(self, node: ast.Assign):
        self._check_targets(node, node.targets)
        self.generic_visit(node)

    def visit_AugAssign(self, node: ast.AugAssign):
        self._check_targets(node, [node.target])
        self.generic_visit(node)

    def visit_AnnAssign(self, node: ast.AnnAssign):
        # annotated attribute assignment (`self._weight_version: dict =
        # ...`) — only the weight-version rule inspects these; the
        # StateManager rules predate annotated writes and stay as-is
        if node.value is not None:
            self._check_targets(node, [node.target])
        self.generic_visit(node)


def check_file(path: str) -> list[str]:
    with open(path, encoding="utf-8") as f:
        src = f.read()
    try:
        tree = ast.parse(src, filename=path)
    except SyntaxError as e:
        return [f"{path}:{e.lineno}: unparseable ({e.msg})"]
    norm = path.replace(os.sep, "/")
    v = _Visitor(path, norm.endswith(STATE_FILE),
                 norm.endswith(KV_TIER_FILE))
    v.visit(tree)
    return v.violations


#: attention-formulation registry pin (inference/attn_registry.py): the
#: engine's kernel-vs-gather decision is the registry's static per-mode
#: selection, consulted in exactly ONE forward dispatch site. History:
#: per-call-site `if self._pallas_decode` conditionals are how the
#: tree-verify path silently pinned the gather formulation — this check
#: makes that regression structural. The boundary is a MODULE: the serving
#: forward (inference/forward.py) is the only module of the package that
#: imports the paged kernel, so no other can dispatch it.
ENGINE_FILE = "deepspeed_tpu/inference/engine_v2.py"
FORWARD_FILE = "deepspeed_tpu/inference/forward.py"
#: the paged kernel's entry points, and the package that defines them
ATTN_KERNEL_NAMES = {"paged_ragged_attention", "paged_work_list"}
ATTN_KERNEL_HOME = "deepspeed_tpu/ops/pallas/"
#: where the engine may READ the selections it computed (the counter + the
#: init-time config-pin composition) and where it computes them
ATTN_SEL_READ_ALLOWED = {"_emit_attn_kernel", "__init__"}
ATTN_SEL_WRITE_ALLOWED = {"__init__"}


class _AttnVisitor(ast.NodeVisitor):
    """Engine-file walk for the registry pin: flags selection reads
    outside the allowlisted functions (an ad-hoc second dispatch site)
    and stray selection rebinds."""

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

    def _in(self, allowed: set) -> bool:
        return any(f in allowed for f in self._func_stack)

    def visit_Call(self, node: ast.Call):
        name = node.func.attr if isinstance(node.func, ast.Attribute) \
            else node.func.id if isinstance(node.func, ast.Name) else ""
        if name == "select_attention" \
                and not self._in(ATTN_SEL_WRITE_ALLOWED):
            self.violations.append(
                f"{self.path}:{node.lineno}: select_attention() called "
                f"outside {sorted(ATTN_SEL_WRITE_ALLOWED)} — the "
                f"selection is static per engine; consult "
                f"_attn_decode_sel/_attn_tree_sel instead")
        self.generic_visit(node)

    def visit_Attribute(self, node: ast.Attribute):
        if node.attr in ("_attn_decode_sel", "_attn_tree_sel"):
            if isinstance(node.ctx, ast.Store):
                if not self._in(ATTN_SEL_WRITE_ALLOWED):
                    self.violations.append(
                        f"{self.path}:{node.lineno}: {node.attr} "
                        f"assigned outside "
                        f"{sorted(ATTN_SEL_WRITE_ALLOWED)} — the "
                        f"registry selection is computed once at init")
            elif not self._in(ATTN_SEL_READ_ALLOWED):
                self.violations.append(
                    f"{self.path}:{node.lineno}: {node.attr} read "
                    f"outside {sorted(ATTN_SEL_READ_ALLOWED)} — no "
                    f"ad-hoc second dispatch site; the forward "
                    f"({FORWARD_FILE}) dispatches, _emit_attn_kernel "
                    f"counts")
        self.generic_visit(node)


def _kernel_imports(path: str) -> list[str]:
    """Imports of the paged kernel's entry points in one file."""
    with open(path, encoding="utf-8") as f:
        src = f.read()
    try:
        tree = ast.parse(src, filename=path)
    except SyntaxError as e:
        return [f"{path}:{e.lineno}: unparseable ({e.msg})"]
    return [
        f"{path}:{node.lineno}: imports {a.name} — only {FORWARD_FILE} "
        f"may: the registry-routed forward is the ONLY kernel dispatch "
        f"site"
        for node in ast.walk(tree) if isinstance(node, ast.ImportFrom)
        for a in node.names if a.name in ATTN_KERNEL_NAMES]


def check_attn_registry(root: str) -> list[str]:
    """Pin the kernel-vs-gather routing to the attention registry: of the
    package, inference/forward.py alone imports the paged kernel; it
    consults BOTH selections (a forward that reads neither would mean
    dispatch regressed to an inline conditional); engine_v2 computes them
    once and reads them only to count (see _AttnVisitor)."""
    path = os.path.join(root, *ENGINE_FILE.split("/"))
    if not os.path.exists(path):
        return []
    out: list[str] = []
    for dirpath, _, files in os.walk(os.path.join(root, "deepspeed_tpu")):
        for f in sorted(files):
            full = os.path.join(dirpath, f)
            norm = full.replace(os.sep, "/")
            if f.endswith(".py") and ATTN_KERNEL_HOME not in norm \
                    and not norm.endswith(FORWARD_FILE):
                out += _kernel_imports(full)
    with open(path, encoding="utf-8") as f:
        src = f.read()
    try:
        tree = ast.parse(src, filename=path)
    except SyntaxError as e:
        return out + [f"{path}:{e.lineno}: unparseable ({e.msg})"]
    v = _AttnVisitor(path)
    v.visit(tree)
    out += v.violations
    fwd = os.path.join(root, *FORWARD_FILE.split("/"))
    fwd_src = ""
    if os.path.exists(fwd):
        with open(fwd, encoding="utf-8") as f:
            fwd_src = f.read()
    if "attn_tree_sel" not in fwd_src or "attn_decode_sel" not in fwd_src:
        out.append(
            f"{fwd}:1: the forward no longer consults the attention "
            f"registry selections (attn_decode_sel/attn_tree_sel) — "
            f"kernel-vs-gather must route through "
            f"inference/attn_registry.py")
    return out


def check_repo(root: str) -> list[str]:
    out: list[str] = []
    pkg = os.path.join(root, "deepspeed_tpu")
    targets = []
    for dirpath, _, files in os.walk(pkg):
        targets += [os.path.join(dirpath, f) for f in files
                    if f.endswith(".py")]
    for path in sorted(targets):
        out += check_file(path)
    out += check_attn_registry(root)
    return out


def main(argv: list[str]) -> int:
    root = argv[1] if len(argv) > 1 else \
        os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    violations = check_repo(root)
    for v in violations:
        print(v)
    if violations:
        print(f"{len(violations)} block-list ownership violation(s) found")
    return 1 if violations else 0


if __name__ == "__main__":
    sys.exit(main(sys.argv))
