"""Device trace capture + xplane analysis — the nsight/NVTX-report analogue.

Reference profiling surfaces kernel timelines via nsight/torch profiler;
on TPU the equivalent is a ``jax.profiler`` trace whose xplane protobuf
carries per-op device timings. This module reads it with
``jax.profiler.ProfileData`` (no TensorFlow) and aggregates SELF device
time per op (:func:`op_breakdown`) or per named scope
(:func:`scope_breakdown`).

The device trace names an op by its HLO instruction (``%fusion.12``) and
carries no ``op_name`` metadata, so the op → scope table comes from the
program: the engines hand every jitted program they create to
:func:`register_program`, and :func:`program_scope_maps` lowers, fetches
and parses the compiled HLO of the registered programs — only when a
reader asks. Until then an entry is the jitted function and the abstract
arguments of its first call.
"""
from __future__ import annotations

import bisect
import collections
import glob
import os
import re
import weakref
from contextlib import contextmanager

import jax

from ..utils.annotations import DEVICE_SCOPES, MODULE_SCOPES


@contextmanager
def trace(log_dir: str):
    """Capture a device trace: ``with trace(dir): run_steps()``. Pair with
    :func:`op_breakdown` / :func:`scope_breakdown` to read it back."""
    jax.profiler.start_trace(log_dir)
    try:
        yield
    finally:
        jax.profiler.stop_trace()


def _latest_xplane(log_dir: str) -> str:
    if os.path.isfile(log_dir):
        return log_dir
    paths = sorted(glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                             recursive=True))
    if not paths:
        raise FileNotFoundError(f"no xplane.pb under {log_dir} — did the "
                                f"trace() context run any device work?")
    return paths[-1]


# ---- the scope map: compiled HLO text -> {instruction: op_name} ---------

_COMPUTATION = re.compile(r"^(?:ENTRY\s+)?%?([\w.\-]+)\s.*\{\s*$")
_INSTRUCTION = re.compile(r"^\s+(ROOT\s+)?%?([\w.\-]+)\s*=\s")
_OP_NAME = re.compile(r'op_name="([^"]*)"')
_CALLS = re.compile(r"\bcalls=%?([\w.\-]+)")
_LAYER_N = re.compile(r"^layer_\d+$")
_WRAPPED = re.compile(r"^(?:\w+\()+|\)+$")
_SCOPE_NAMES = frozenset(DEVICE_SCOPES) | frozenset(MODULE_SCOPES)
UNSCOPED = "unscoped"
AMBIGUOUS = "ambiguous"


def scope_of(op_name: str | None) -> tuple[str, str]:
    """(scope, direction) of one ``op_name`` path. The scope is the first
    declared name on the path (``DEVICE_SCOPES`` or a flax module name;
    ``layer_N`` folds to ``layer`` and takes the next name with it:
    ``layer/attn``), or ``unscoped``. The direction needs no scope: the
    path says ``transpose(`` for the backward pass and
    ``rematted_computation`` for what remat runs again."""
    parts = (op_name or "").split("/")
    direction = ("recompute" if "rematted_computation" in parts else
                 "bwd" if any(p.startswith("transpose(") for p in parts)
                 else "fwd")
    # a transform wraps the first scope it meets (``jvp(head_loss)``) and
    # the path can carry that scope twice over:
    # ``transpose(jvp(layer_1))/jvp(layer_1)/checkpoint/ffn/mul``
    names: list[str] = []
    for p in parts:
        p = _WRAPPED.sub("", p)
        n = "layer" if _LAYER_N.match(p) else p
        if n in _SCOPE_NAMES and names[-1:] != [n]:
            names.append(n)
    if not names:
        return UNSCOPED, direction
    if names[0] == "layer" and len(names) > 1:
        return f"layer/{names[1]}", direction
    return names[0], direction


#: scopes declared INSIDE another (``attn_core/attn_window``): which kind
#: of layer an attention core belongs to
SUB_SCOPES = frozenset({"attn_full", "attn_window"})


def sub_scope_of(op_name: str | None) -> str | None:
    """The declared sub-scope on one ``op_name`` path, or None.
    :func:`scope_of` names the FIRST declared scope on a path
    (``attn_core``), so that a table by scope still sums the kinds; this
    names the kind of layer inside it."""
    for p in (op_name or "").split("/"):
        if _WRAPPED.sub("", p) in SUB_SCOPES:
            return _WRAPPED.sub("", p)
    return None


def parse_hlo_scopes(text: str) -> tuple[str, dict[str, str]]:
    """(module name, {instruction name: op_name path}) from the text of a
    COMPILED module. A fusion (or call) takes its own ``op_name``; where
    the compiler left none on it, that of the root of the computation it
    calls, or failing that the op_name of the most common scope in there.
    Instructions with no name at all map to ``""``."""
    module = text.split(None, 2)[1].rstrip(",") if text.startswith(
        "HloModule") else ""
    own: dict[str, str] = {}
    calls: dict[str, str] = {}
    roots: dict[str, str] = {}
    members: dict[str, list[str]] = collections.defaultdict(list)
    comp = None
    for line in text.splitlines():
        m = _INSTRUCTION.match(line)
        if m is None:
            c = _COMPUTATION.match(line)
            comp = c.group(1) if c else comp
            continue
        is_root, name = m.groups()
        head = line[:line.find("backend_config=")] \
            if "backend_config=" in line else line
        op = _OP_NAME.search(line)
        own[name] = op.group(1) if op else ""
        called = _CALLS.search(head)
        if called:
            calls[name] = called.group(1)
        if comp is not None:
            members[comp].append(name)
            if is_root:
                roots[comp] = name
    out = dict(own)
    for name, callee in calls.items():
        if own[name]:
            continue
        root = own.get(roots.get(callee, ""), "")
        named = [own[i] for i in members.get(callee, ()) if own[i]]
        if not root and named:
            best = collections.Counter(map(scope_of, named)).most_common(1)
            root = next(o for o in named if scope_of(o) == best[0][0])
        out[name] = root
    return module, out


class RegisteredProgram:
    """A jitted program as its engine holds it: calls go straight through;
    the first one leaves the abstract arguments behind, from which the
    compiled HLO can be had again later (jit's own cache answers). One
    entry stands for ONE compiled program: the engines hold a jit of its
    own for every shape; a jit that went on to compile for other shapes
    too is read by its first."""
    __slots__ = ("fn", "avals", "_parsed", "__weakref__")

    def __init__(self, fn):
        self.fn = fn
        self.avals = None
        self._parsed = None

    def __call__(self, *args, **kwargs):
        if self.avals is None:
            self.avals = jax.tree.map(_abstract, (args, kwargs))
        return self.fn(*args, **kwargs)

    def __getattr__(self, name):
        return getattr(self.fn, name)

    @property
    def module_name(self) -> str:
        return "jit_" + re.sub(r"[^\w.\-]", "_", self.fn.__name__)

    def compiled_text(self) -> str:
        """The COMPILED module's text: lowered and compiled again from the
        first call's abstract arguments (or read from the compile cache)."""
        args, kwargs = self.avals
        return self.fn.lower(*args, **kwargs).compile().as_text()

    def scopes(self) -> dict:
        """``{"module", "ops": {instruction: op_name}, "hlo_bytes"}`` —
        lowered, compiled (or read from the compile cache) and parsed on
        the first request, kept after."""
        if self._parsed is None:
            text = self.compiled_text()
            module, ops = parse_hlo_scopes(text)
            self._parsed = {"module": module, "ops": ops,
                            "hlo_bytes": len(text)}
        return self._parsed


def _abstract(x):
    if isinstance(x, jax.Array):
        return jax.ShapeDtypeStruct(
            x.shape, x.dtype, sharding=x.sharding if x.committed else None)
    a = jax.api_util.shaped_abstractify(x)
    return jax.ShapeDtypeStruct(a.shape, a.dtype, weak_type=a.weak_type)


#: every live registered program of this process (weak: an engine that is
#: dropped takes its programs along)
_PROGRAMS: "weakref.WeakSet[RegisteredProgram]" = weakref.WeakSet()


def register_program(jitted) -> RegisteredProgram:
    """Called by an engine where it CREATES a jitted program; costs one
    set insertion. Returns what the engine keeps and calls."""
    prog = RegisteredProgram(jitted)
    _PROGRAMS.add(prog)
    return prog


def registered_programs() -> list[RegisteredProgram]:
    return list(_PROGRAMS)


def merge_scope_maps(maps: list[dict[str, str]]) -> dict[str, str]:
    """One map for several compiled programs of ONE module name (a window
    program per size, a prefill step per shape: instruction names are
    unique only inside one module). An instruction on whose (scope,
    direction) they disagree maps to ``ambiguous`` — never to a guess."""
    out: dict[str, str] = {}
    folded: dict[str, tuple] = {}
    for ops in maps:
        for name, op in ops.items():
            key = scope_of(op)
            if name not in folded:
                folded[name], out[name] = key, op
            elif folded[name] != key:
                out[name] = AMBIGUOUS
    return out


def program_scope_maps(names=None) -> dict[str, dict]:
    """``{module name: {"ops": {instruction: op_name | "ambiguous"},
    "programs": n, "hlo_bytes": largest text}}`` for the registered
    programs that have run (optionally only the module ``names`` given).
    THIS is where lowering, compiling and parsing happen."""
    per: dict[str, list[dict]] = collections.defaultdict(list)
    for prog in registered_programs():
        if prog.avals is None or (names is not None
                                  and prog.module_name not in names):
            continue
        parsed = prog.scopes()
        per[parsed["module"] or prog.module_name].append(parsed)
    return {mod: {"ops": merge_scope_maps([p["ops"] for p in ps]),
                  "programs": len(ps),
                  "hlo_bytes": max(p["hlo_bytes"] for p in ps)}
            for mod, ps in per.items()}


_COPY = re.compile(
    r"^\s+(?:ROOT\s+)?%?([\w.\-]+)\s*=\s*\w+\[([\d,]*)\](\{[^}]*\})?\s+"
    r"copy(?:-start)?\((.*)$")
_OPERAND = re.compile(r"(?:\w+\[[\d,]*\](\{[^}]*\})?\s+)?%?([\w.\-]+)")
_RESULT = re.compile(r"=\s*\(?\w+\[[\d,]*\](\{[^}]*\})?")


def pool_sized_copies(program, pool_shapes) -> list[dict]:
    """Every ``copy`` of a compiled program whose result has the shape of
    one of ``pool_shapes`` (an engine's ``kv_pool`` arrays): ``{"instruction",
    "shape", "operand", "operand_layout", "result_layout", "users"}``. A
    step program reads its donated pool, writes it once in place and
    returns it; a copy of a pool's size inside it is a pool read and
    written again for nothing — a LAYOUT copy where the two layouts differ
    (an update form that draws another layout than the pinned one), a
    HAZARD copy where they are equal (a write the compiler could not order
    after every read of the old value). ``program`` is a
    :class:`RegisteredProgram` that has run, or a compiled module's
    text."""
    text = program if isinstance(program, str) else program.compiled_text()
    want = {tuple(int(d) for d in s) for s in pool_shapes}
    lines = text.splitlines()
    found = []
    for line in lines:
        m = _COPY.match(line)
        if m is None:
            continue
        name, dims, layout, rest = m.groups()
        shape = [int(d) for d in dims.split(",") if d]
        if tuple(shape) not in want:
            continue
        op_layout, operand = _OPERAND.match(rest).groups()
        found.append({"instruction": name, "shape": shape,
                      "operand": operand, "operand_layout": op_layout,
                      "result_layout": layout, "users": []})
    if not found:
        return found
    # (a second pass only where there is something to say: the operand's
    # layout where the text prints operands bare, and who reads the copy)
    by_name = {f["instruction"]: f for f in found}
    bare = collections.defaultdict(list)
    for f in found:
        if f["operand_layout"] is None:
            bare[f["operand"]].append(f)
    refs = re.compile(r"(?<![\w.\-])(" + "|".join(map(re.escape, by_name))
                      + r")(?![\w.\-])")
    for line in lines:
        m = _INSTRUCTION.match(line)
        if m is None:
            continue
        name = m.group(2)
        if name in bare:
            r = _RESULT.search(line)
            for f in bare[name]:
                f["operand_layout"] = r.group(1) if r else None
        for used in set(refs.findall(line.split("=", 1)[1])):
            if used != name:
                by_name[used]["users"].append(name)
    return found


# ---- reading a trace ------------------------------------------------------

def _instruction(event_name: str) -> str:
    """``%fusion.12 = bf16[…] fusion(…)`` -> ``fusion.12``."""
    return event_name.lstrip("%").split(" ", 1)[0]


def _base_name(instr: str) -> str:
    return re.sub(r"[.\d]+$", "", instr) or instr


def _self_times(events):
    """(name, start, self_ns) per event of one "XLA Ops" line: an op's time
    minus that of the ops nested directly inside it (a ``while`` and its
    body: a naive sum counts the body twice)."""
    ev = sorted(events, key=lambda e: (e[1], -(e[2] - e[1])))
    child = [0.0] * len(ev)
    stack: list[int] = []
    for i, (_, a, b) in enumerate(ev):
        while stack and ev[stack[-1]][2] <= a:
            stack.pop()
        if stack:
            child[stack[-1]] += b - a
        stack.append(i)
    return [(n, a, max(b - a - c, 0.0)) for (n, a, b), c in zip(ev, child)]


def device_op_times(log_dir: str, device_substr: str = "TPU"):
    """Per device plane of the newest trace under ``log_dir`` (or of that
    ``.xplane.pb`` file): ``[(program, instruction, self_ns)]``, the
    program being the run on the "XLA Modules" line that holds the op's
    start (``"none"`` outside any). Device planes only — a CPU trace has
    none and gives ``[]``."""
    from jax.profiler import ProfileData

    out = []
    for plane in ProfileData.from_file(_latest_xplane(log_dir)).planes:
        if not plane.name.startswith("/device:") \
                or device_substr not in plane.name:
            continue
        lines = {ln.name: [(e.name, float(e.start_ns),
                            float(e.start_ns) + float(e.duration_ns))
                           for e in ln.events] for ln in plane.lines}
        ops = lines.get("XLA Ops")
        if not ops:
            continue
        runs = sorted((a, b, n.split("(")[0])
                      for n, a, b in lines.get("XLA Modules", ()))
        starts = [r[0] for r in runs]
        rows = []
        for name, a, self_ns in _self_times(ops):
            j = bisect.bisect_right(starts, a) - 1
            prog = runs[j][2] if j >= 0 and a < runs[j][1] else "none"
            rows.append((prog, _instruction(name), self_ns))
        out.append(rows)
    return out


def op_breakdown(log_dir: str, *, by_base_name: bool = True,
                 device_substr: str = "TPU") -> dict[str, float]:
    """{op name: SELF device ms} from the newest trace under ``log_dir``,
    summed over every matching device plane. ``by_base_name`` strips the
    ``.123`` instance suffix so repeated ops (one per layer) aggregate.
    ``{}`` for a trace with no device plane (the CPU backend)."""
    totals: dict[str, float] = collections.Counter()
    for rows in device_op_times(log_dir, device_substr):
        for _, instr, self_ns in rows:
            totals[_base_name(instr) if by_base_name else instr] \
                += self_ns / 1e6
    return dict(totals)


def scope_breakdown(log_dir: str, *, maps: dict | None = None,
                    device_substr: str = "TPU") -> dict[str, dict]:
    """``{program: {(scope, direction): SELF device ms}}`` from the newest
    trace under ``log_dir``, summed over the device planes: every op joined
    with the scope map of the program whose run holds it (``maps`` as
    :func:`program_scope_maps` returns them — asked for here, for the
    programs in the trace, when not given). Ops of a program without a
    map, or unknown to it, count as ``unscoped``."""
    planes = device_op_times(log_dir, device_substr)
    if maps is None:
        maps = program_scope_maps({p for rows in planes for p, _, _ in rows})
    table: dict = collections.defaultdict(collections.Counter)
    for rows in planes:
        for prog, instr, self_ns in rows:
            op = maps.get(prog, {}).get("ops", {}).get(instr)
            key = (AMBIGUOUS, "fwd") if op == AMBIGUOUS else scope_of(op)
            table[prog][key] += self_ns / 1e6
    return {p: dict(t) for p, t in table.items()}


#: HLO name fragments → collective kind (CommsLogger op names)
_COLLECTIVE_KINDS = (
    ("all-reduce", "all_reduce"),
    ("reduce-scatter", "reduce_scatter"),
    ("all-gather", "all_gather"),
    ("all-to-all", "all_to_all"),
    ("collective-permute", "ppermute"),
)


def collective_breakdown(log_dir: str | None = None, *,
                         totals: dict[str, float] | None = None,
                         device_substr: str = "TPU") -> dict[str, float]:
    """Measured device milliseconds per collective KIND from the newest
    trace — the half of the comms-logging story the bandwidth model can't
    see (XLA owns wall time; CommsLogger owns sizes). Feed the result to
    ``comm.validate_against_trace`` to compare model vs reality.

    Only device planes carry per-op timings: real-TPU traces have them;
    CPU-backend traces expose host threads only, so the result is empty
    there (the model side of the validation still works).
    ``totals`` bypasses the trace read (tests / pre-aggregated data)."""
    if totals is None:
        totals = op_breakdown(log_dir, device_substr=device_substr)
    out: dict[str, float] = collections.Counter()
    for name, ms in totals.items():
        low = name.lower()
        for frag, kind in _COLLECTIVE_KINDS:
            if frag in low:
                out[kind] += ms
                break
    return dict(out)


def overlap_breakdown(log_dir: str | None = None, *,
                      totals: dict[str, float] | None = None,
                      device_substr: str = "TPU") -> dict:
    """Ring (overlappable) vs blocking collective device time from the
    newest trace — the measurement side of the ring collective-matmul
    counters (parallel/tensor.py records trace-time ring structure; this
    reads what the device actually spent).

    ``collective-permute`` is overlappable transport: its transfers are
    schedulable under independent compute, so its share of total
    collective time is the *upper bound* on comm that ring decompositions
    can hide — NB it counts EVERY permute producer (ring collective-
    matmuls, ring attention in parallel/sequence.py, pipeline 1F1B), so
    on runs mixing those features the fraction bounds their combined
    overlap, not the TP rings alone (cross-check engine
    stats["tp_ring_steps"] for attribution). all-reduce / all-gather /
    reduce-scatter / all-to-all sit on the critical path as barriers.
    ``comm_hidden_fraction`` = ppermute / (ppermute + blocking); None
    when the trace carries no collectives (single chip, or a CPU trace
    without device planes). ``totals`` bypasses the trace read (tests /
    pre-aggregated data)."""
    coll = collective_breakdown(log_dir, totals=totals,
                                device_substr=device_substr)
    ring_ms = coll.get("ppermute", 0.0)
    blocking_ms = sum(v for k, v in coll.items() if k != "ppermute")
    total = ring_ms + blocking_ms
    return {
        "ring_ms": round(ring_ms, 6),
        "blocking_ms": round(blocking_ms, 6),
        "comm_hidden_fraction": (ring_ms / total) if total else None,
    }


def print_breakdown(log_dir: str, top: int = 20, steps: int = 1,
                    device_substr: str = "TPU", by_scope: bool = False,
                    maps: dict | None = None) -> str:
    """Human-readable top-N table (ms per step): by op, or with
    ``by_scope`` by program, scope and direction (``maps`` as for
    :func:`scope_breakdown`)."""
    if by_scope:
        totals = {f"{prog}  {scope}  {direction}": ms
                  for prog, t in scope_breakdown(
                      log_dir, maps=maps,
                      device_substr=device_substr).items()
                  for (scope, direction), ms in t.items()}
    else:
        totals = op_breakdown(log_dir, device_substr=device_substr)
    lines = [f"{'ms/step':>10}  {'program  scope  direction' if by_scope else 'op'}"]
    for name, ms in sorted(totals.items(), key=lambda kv: -kv[1])[:top]:
        lines.append(f"{ms / max(steps, 1):10.3f}  {name}")
    text = "\n".join(lines)
    print(text)
    return text
