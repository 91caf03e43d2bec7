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

The same seam keeps the BUILD LEDGER (:func:`build_records`): what every
registered program cost to build where it was first called, every build
after that (a rebuild, with the argument that differed), every build of a
jitted function outside the table, and an engine's constructor by phase
(:func:`engine_build`).
"""
from __future__ import annotations

import bisect
import collections
import functools
import glob
import itertools
import os
import re
import sys
import threading
import time
import weakref
from contextlib import contextmanager

import jax

from ..utils.annotations import DEVICE_SCOPES, MODULE_SCOPES
from ..utils.logging import logger


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
    too is read by its first.

    ``key`` is the engine's own name for the program (its ``_programs``
    key), ``cause`` the span that asked for it (``("dispatch", seq)``,
    ``("warm", None)``): both go into the build ledger's records. The
    FIRST call runs inside a ``program_build`` span and books a record of
    what it cost; a later call reads the process's count of backend
    compiles before and after, and books a rebuild only where it moved."""
    __slots__ = ("fn", "avals", "key", "cause", "_parsed", "__weakref__")

    def __init__(self, fn, key=None, cause=None):
        self.fn = fn
        self.avals = None
        self.key = key
        self.cause = cause
        self._parsed = None

    def __call__(self, *args, **kwargs):
        if self.avals is None:
            return self._first_call(args, kwargs)
        seen = _BACKEND_EVENTS
        out = self.fn(*args, **kwargs)
        if _BACKEND_EVENTS != seen:
            self._book_rebuild(seen, args, kwargs)
        return out

    def _first_call(self, args, kwargs):
        from ..telemetry import get_telemetry

        self.avals = jax.tree.map(_abstract, (args, kwargs))
        module = self.module_name
        with get_telemetry().span("program_build", key=str(self.key),
                                  module=module):
            rec = _record("program", self.key, module, variant=1,
                          cause=self.cause)
            outer, _TL.open = getattr(_TL, "open", None), rec
            try:
                return self.fn(*args, **kwargs)
            finally:
                _TL.open = outer
                rec["seconds"] = time.perf_counter() - rec["t0"]
                _book(rec)

    def _book_rebuild(self, seen: int, args, kwargs) -> None:
        """The count of backend compiles moved during a later call: the
        listener booked what it heard under this function's name as builds
        outside any call — the first of them becomes the ``program`` record
        of the rebuild, with what differed. None of this name: another
        thread's compile."""
        rec = None
        for r in reversed(_BUILDS):
            if 0 < r.get("backend_ord", 0) <= seen:
                break
            if r["kind"] != "phase" and not r["variant"] \
                    and r["backend_ord"] > seen \
                    and r["module"] == self.module_name:
                rec = r
        if rec is None:
            return
        size = getattr(self.fn, "_cache_size", None)
        rec.update(kind="program", key=self.key, cause=self.cause,
                   variant=size() if size else 2,
                   differs=first_difference(
                       self.avals, jax.tree.map(_abstract, (args, kwargs))))
        logger.warning(build_line(rec, "build: REBUILT"))

    def __getattr__(self, name):
        return getattr(self.fn, name)

    def lower(self, *args, **kwargs):
        """The jit's own ``lower``, with the build ledger told whose it is:
        ``prog.lower(...).compile()`` AHEAD of the first call (the remat
        judge, a runner) is this program's record, ``variant`` 0, and not
        one of a function outside the table."""
        outer, _TL.ahead = getattr(_TL, "ahead", None), self
        try:
            return self.fn.lower(*args, **kwargs)
        finally:
            _TL.ahead = outer

    @property
    def module_name(self) -> str:
        return _module_name(self.fn.__name__)

    def compiled_text(self) -> str:
        """The COMPILED module's text: lowered and compiled again from the
        first call's abstract arguments (or read from the compile cache).
        The build ledger books nothing of it."""
        args, kwargs = self.avals
        _TL.muted = getattr(_TL, "muted", 0) + 1
        try:
            return self.fn.lower(*args, **kwargs).compile().as_text()
        finally:
            _TL.muted -= 1

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


_JIT_OF = re.compile(r"^\w+\((.*)\)$")


def _module_name(fun_name: str) -> str:
    return "jit_" + re.sub(r"[^\w.\-]", "_", fun_name)


def _abstract(x):
    if isinstance(x, jax.Array):
        return jax.ShapeDtypeStruct(
            x.shape, x.dtype, sharding=x.sharding if x.committed else None)
    a = jax.api_util.shaped_abstractify(x)
    return jax.ShapeDtypeStruct(a.shape, a.dtype, weak_type=a.weak_type)


#: every live registered program of this process (weak: an engine that is
#: dropped takes its programs along)
_PROGRAMS: "weakref.WeakSet[RegisteredProgram]" = weakref.WeakSet()


def register_program(jitted, key=None, cause=None) -> RegisteredProgram:
    """Called by an engine where it CREATES a jitted program; costs one
    set insertion. Returns what the engine keeps and calls. ``key`` and
    ``cause`` as :class:`RegisteredProgram` says."""
    _listen()
    prog = RegisteredProgram(jitted, key, cause)
    _PROGRAMS.add(prog)
    return prog


# ---- the build ledger -----------------------------------------------------
#
# Plain dicts beside ``_PROGRAMS``, one a build:
#
# ``kind``      ``program`` (a registered program: built ahead of its first
#               call, at its first call, or by a later call that built
#               again), ``phase`` (a stretch of an engine's constructor,
#               :func:`engine_build`), ``outside`` (a jitted function that
#               is in no engine's table)
# ``key``       the engine's name for the program; a phase's name; None
# ``module``    the compiled module's name (``jit_step_prefill``); a
#               phase's owner (``EngineBackend``)
# ``variant``   0 for a build of a registered program's own jit AHEAD of
#               its first call (``fn.lower(...).compile()``: the remat
#               judge, a runner), 1 for a first call; on a rebuild the
#               jit's own count of its cache entries (``fn._cache_size()``)
# ``t0``        start, on ``time.perf_counter()`` — the telemetry spans'
#               clock and, on Linux, the clock of ``time.monotonic()``
#               (both CLOCK_MONOTONIC), which the benchmark's window marks
#               and ``worker_status.json`` use: a build can be laid against
#               the measured window and, through the spans mirrored into
#               the xplane, against the device trace
# ``seconds``   a first call's wall time (trace + lower + compile or cache
#               read + enqueue); a phase's; else the sum of what the events
#               reported
# ``trace_s`` ``lower_s`` ``backend_s``   what JAX's own events reported
#               (``jaxpr_trace_duration`` of the outermost trace,
#               ``jaxpr_to_mlir_module_duration``,
#               ``backend_compile_duration``: a compile or a cache read)
# ``backend_events``  how many of the last, ``cache_hit`` whether every
#               one of them was read from the persistent cache (None: none
#               happened), ``backend_ord`` the process's count of backend
#               events after the record's last one (0: none)
# ``differs``   on a rebuild: the first argument leaf that is not what the
#               first call's was (:func:`first_difference`)
# ``cause``     the span that caused it, as the engine handed it over;
#               ``("ahead", None)`` on a ``variant`` 0 record
# ``phase`` ``site``   of an ``outside`` record: the ``engine_build`` phase
#               open on its thread at its first event (None: none), and the
#               innermost frame of this checkout's own code on the stack
#               there (``deepspeed_tpu/inference/tp.py:212``): WHO jitted it
# ``outside_s`` ``outside_backend_s`` ``outside_builds``   of a ``phase``
#               record: the seconds (all of them; the backend's alone) of
#               the ``outside`` records that fell in it, and how many of
#               them the backend built or read from the cache
# ``n``         the record's own number (:func:`build_count` when booked)
# ``build``     a phase's engine build (phases of one constructor share it)

BUILD_RING = 4096
_BUILDS: collections.deque = collections.deque(maxlen=BUILD_RING)
_BUILD_COUNT = 0
#: backend compiles (or cache reads) this PROCESS has seen: what a steady
#: call of a registered program reads twice. It is the process's: where two
#: threads call programs a rebuild can be booked to the wrong one of two
#: programs of one module name (the replica worker's serving thread is
#: alone).
_BACKEND_EVENTS = 0
#: per thread: ``open`` the record of the first call under way, ``depth``
#: traces and lowerings nested in one another, ``muted`` (``compiled_text``), ``hit`` a
#: cache hit waiting for its backend event, ``outside`` the record that
#: events outside any call are gathering into (with its stage and the
#: ``phase`` record it fell in), ``ahead`` the registered program whose
#: ``lower`` is under way, ``build`` the engine build under way
_TL = threading.local()
_LISTENING = False
_EVENT_FIELDS = {
    "/jax/core/compile/jaxpr_trace_duration": "trace_s",
    "/jax/core/compile/jaxpr_to_mlir_module_duration": "lower_s",
    "/jax/core/compile/backend_compile_duration": "backend_s"}
_STAGES = tuple(_EVENT_FIELDS.values())       # (in a build's own order)
#: what a ``site`` is told from and said against: this file (the listener's
#: own frames are on every stack) and the checkout's root
_HERE = os.path.abspath(__file__)
_ROOT = os.path.dirname(os.path.dirname(os.path.dirname(_HERE))) + os.sep


def _record(kind: str, key, module: str, **more) -> dict:
    return {"kind": kind, "key": key, "module": module,
            "t0": time.perf_counter(), "seconds": 0.0, "trace_s": 0.0,
            "lower_s": 0.0, "backend_s": 0.0, "backend_events": 0,
            "cache_hit": None, "backend_ord": 0, **more}


def _book(rec: dict) -> dict:
    global _BUILD_COUNT
    rec["n"] = _BUILD_COUNT
    _BUILD_COUNT += 1
    _BUILDS.append(rec)                 # (the ring drops its oldest)
    return rec


def _listen() -> None:
    """ONE listener a process on ``jax.monitoring``'s hooks, registered at
    the first :func:`register_program` or :func:`engine_build`."""
    global _LISTENING
    if _LISTENING:
        return
    _LISTENING = True
    import jax.monitoring as mon

    mon.register_scalar_listener(_on_scalar)
    mon.register_event_listener(_on_event)
    mon.register_event_duration_secs_listener(_on_duration)


def _on_scalar(event: str, value, **_) -> None:
    # (what ``LogElapsedTimeContextManager`` says as it ENTERS: a trace or
    # a lowering, inside which others nest)
    if _EVENT_FIELDS.get(event, "backend_s") != "backend_s":
        _TL.depth = getattr(_TL, "depth", 0) + 1


def _on_event(event: str, **_) -> None:
    if event == "/jax/compilation_cache/cache_hits":
        _TL.hit = True


def _on_duration(event: str, secs: float, fun_name: str = "", **_) -> None:
    field = _EVENT_FIELDS.get(event)
    if field is None:
        return
    tl = _TL
    if field != "backend_s":
        # a jit traced inside another's trace, a rule traced inside a
        # lowering: the outer seconds hold it
        tl.depth = depth = max(getattr(tl, "depth", 1) - 1, 0)
        if depth:
            return
    if getattr(tl, "muted", 0):
        tl.hit = False
        return
    rec = getattr(tl, "open", None)
    if rec is None:
        rec = _outside(tl, field, str(fun_name), secs)
    rec[field] += secs
    if field == "backend_s":
        global _BACKEND_EVENTS
        _BACKEND_EVENTS += 1
        hit, tl.hit = getattr(tl, "hit", False), False
        rec["backend_events"] += 1
        rec["backend_ord"] = _BACKEND_EVENTS
        rec["cache_hit"] = hit and rec["cache_hit"] is not False


def _outside(tl, field: str, fun_name: str, secs: float) -> dict:
    """The record that an event outside any registered program's first
    call belongs to. One build's events come in order (trace, lower,
    backend) under one function's name (the trace says ``step``, the other
    two ``jit(step)``): an event out of that order, or under another name,
    starts the next record — that of the registered program whose
    ``lower`` is under way, built AHEAD of its first call (``variant`` 0),
    else an ``outside`` one with the phase and the site it came from (a
    later call of a registered program that built again claims its own in
    ``_book_rebuild``). A record is booked where its
    lowering begins: a trace alone (an operation inside ``eval_shape``, a
    loop's body) is no build, and a model's has thousands."""
    module = _module_name(_JIT_OF.sub(r"\1", fun_name))
    rec, last, phase = getattr(tl, "outside", None) or (None, 0, None)
    stage = _STAGES.index(field)
    if rec is None or rec["module"] != module or last >= stage \
            or rec["variant"]:
        prog, phase = getattr(tl, "ahead", None), None
        rec = _record("program", prog.key, module, variant=0,
                      cause=("ahead", None)) \
            if prog is not None and prog.module_name == module \
            else _record("outside", None, module, variant=None)
        rec["t0"] -= secs
    if stage and "n" not in rec:
        if rec["kind"] == "outside":
            build = getattr(tl, "build", None)
            phase = build and build.open
            rec.update(phase=phase and phase["key"], site=_site())
            if phase is not None:
                phase["outside_s"] += rec["seconds"]
        _book(rec)
    rec["seconds"] += secs              # (no call to time: the events' sum)
    if phase is not None:
        phase["outside_s"] += secs
        if field == "backend_s":
            phase["outside_backend_s"] += secs
            phase["outside_builds"] += rec["backend_events"] == 0
    tl.outside = (rec, stage, phase)
    return rec


def _site() -> str | None:
    """``file.py:line`` (from the checkout's root) of the innermost frame on
    this thread's stack that is the checkout's own code and not this file:
    the line that jitted, or called the eager operation, that is being
    built. Paid where a build is already being paid."""
    frame = sys._getframe(1)
    while frame is not None:
        name = frame.f_code.co_filename
        if name.startswith(_ROOT) and name != _HERE \
                and "site-packages" not in name:
            return f"{name[len(_ROOT):]}:{frame.f_lineno}"
        frame = frame.f_back
    return None


def first_difference(first, now) -> str:
    """Where two trees of abstract arguments (``_abstract``'s) part: the
    path of the first leaf that differs and the field — ``shape``,
    ``dtype``, ``weak_type``, ``committed`` (one call's array was placed by
    the user or a program, the other's was not: jit keys its cache on it)
    or ``sharding`` — or ``"abstract arguments equal"`` (what is left: a
    layout, a donation, something only the device decides)."""
    a, tree_a = jax.tree.flatten_with_path(first)
    b, tree_b = jax.tree.flatten_with_path(now)
    if tree_a != tree_b:
        return f"tree structure: {tree_a} at the first call, {tree_b} now"
    for (path, x), (_, y) in zip(a, b):
        # (the trees are ``(args, kwargs)``)
        where = ("args", "kwargs")[path[0].idx] \
            + jax.tree_util.keystr(path[1:])
        for field in ("shape", "dtype", "weak_type"):
            if getattr(x, field) != getattr(y, field):
                return (f"{where}: {field} {getattr(x, field)} at the "
                        f"first call, {getattr(y, field)} now")
        if (x.sharding is None) != (y.sharding is None):
            return (f"{where}: committed {x.sharding is not None} at the "
                    f"first call, {y.sharding is not None} now")
        if x.sharding != y.sharding:
            return (f"{where}: sharding {x.sharding} at the first call, "
                    f"{y.sharding} now")
    return "abstract arguments equal"


def build_records() -> list[dict]:
    """The ledger, oldest first: the newest ``BUILD_RING`` records. A
    record's ``t0`` is on ``time.perf_counter()``: the telemetry spans'
    clock and, on Linux, ``time.monotonic()``'s — the benchmark's window
    marks and ``worker_status.json`` — so a build can be laid against the
    measured window and, through the spans in the xplane, the device
    trace."""
    return list(_BUILDS)


def build_count() -> int:
    """Records ever booked (the ring's dropped ones too): what a loop
    compares to learn that there is something new."""
    return _BUILD_COUNT


_PHASE_SUMS = ("seconds", "outside_s", "outside_backend_s", "outside_builds")


def _fold_phases(phase_recs) -> dict[str, dict]:
    """``phase`` records by name, in order of first appearance (a
    constructor may come back to a phase): ``_PHASE_SUMS`` of each."""
    phases: dict[str, dict] = {}
    for r in phase_recs:
        into = phases.setdefault(r["key"], dict.fromkeys(_PHASE_SUMS, 0))
        for k in _PHASE_SUMS:
            into[k] += r.get(k, 0)
    return phases


def build_summary(records: list[dict] | None = None) -> dict:
    """The ledger's sums, as the worker prints them and the benchmark's
    readers read them. Over the registered programs' builds up to their
    first calls (``variant`` 0 and 1): ``trace_s`` and ``lower_s`` (Python:
    paid on every start, whatever the compile cache holds), ``backend_s``
    (compiles on a cold start, cache reads on a warm one), and of those
    that reached the backend (``backend_builds``) how many were read from
    the cache (``cache_hits``); ``programs`` first-called in
    ``first_call_s`` (wall), ``ahead`` built before that; ``rebuilt``
    programs; builds ``outside`` the table with a backend event
    (``outside_s`` theirs); every record's ``backend_events`` together
    (what an observer of ``backend_compile_duration`` counts over the same
    stretch); and the ``phases`` of the NEWEST engine build, by name, in
    order, with ``phase_s`` their wall time and ``phase_outside_backend_s``
    what of it the backend spent on builds outside the table."""
    recs = build_records() if records is None else records
    progs = [r for r in recs if r["kind"] == "program"]
    own = [r for r in progs if r["variant"] <= 1]
    first = [r for r in own if r["variant"] == 1]
    built = [r for r in own if r["backend_events"]]
    outside = [r for r in recs
               if r["kind"] == "outside" and r["backend_events"]]
    phase_recs = [r for r in recs if r["kind"] == "phase"]
    newest = max((r["build"] for r in phase_recs), default=None)
    phases = _fold_phases(r for r in phase_recs if r["build"] == newest)
    return {"programs": len(first),
            "first_call_s": sum(r["seconds"] for r in first),
            "ahead": len(own) - len(first),
            "trace_s": sum(r["trace_s"] for r in own),
            "lower_s": sum(r["lower_s"] for r in own),
            "backend_s": sum(r["backend_s"] for r in own),
            "backend_builds": len(built),
            "cache_hits": sum(bool(r["cache_hit"]) for r in built),
            "rebuilt": len(progs) - len(own),
            "outside": len(outside),
            "outside_s": sum(r["seconds"] for r in outside),
            "backend_events": sum(r.get("backend_events", 0) for r in recs),
            "phases": phases,
            "phase_s": sum(p["seconds"] for p in phases.values()),
            "phase_outside_backend_s": sum(p["outside_backend_s"]
                                           for p in phases.values())}


def build_line(rec: dict, head: str = "build:") -> str:
    """One ``program`` or ``outside`` record as a log line."""
    hit = rec["cache_hit"]
    line = (f"{head} key={rec['key']} module={rec['module']} "
            f"variant={rec['variant']} "
            f"cache={'none' if hit is None else 'hit' if hit else 'miss'} "
            f"trace {rec['trace_s']:.2f} s lower {rec['lower_s']:.2f} s "
            f"backend {rec['backend_s']:.2f} s of {rec['seconds']:.2f} s")
    if rec["kind"] == "outside":
        line += f" phase={rec['phase']} site={rec['site']}"
    if rec.get("cause"):
        line += f" cause={rec['cause'][0]}:{rec['cause'][1]}"
    if rec.get("differs"):
        line += f" differs: {rec['differs']}"
    return line


def phases_line(phases: dict[str, dict]) -> str:
    """``weights 21.30 s (6 built outside the table, 19.90 s); stack 4.10
    s; ...`` of a summary's ``phases``."""
    return "; ".join(
        f"{k} {p['seconds']:.2f} s" + (
            f" ({p['outside_builds']} built outside the table, "
            f"{p['outside_s']:.2f} s)" if p["outside_builds"] else "")
        for k, p in phases.items())


def builds_lines(records: list[dict] | None = None) -> list[str]:
    """What a process says of its ledger when it leaves: the sums, then
    one line for each rebuild and each build outside the table that took
    over 50 ms."""
    recs = build_records() if records is None else records
    s = build_summary(recs)
    lines = [f"builds: {s['programs']} programs first-called in "
             f"{s['first_call_s']:.2f} s (trace {s['trace_s']:.2f}, lower "
             f"{s['lower_s']:.2f}, backend {s['backend_s']:.2f}; "
             f"{s['cache_hits']} from the cache); {s['rebuilt']} rebuilt; "
             f"{s['outside']} outside the table ({s['outside_s']:.2f} s); "
             f"{s['backend_events']} backend events in all"]
    for r in recs:
        if (r["kind"] == "program" and r["variant"] > 1) or (
                r["kind"] == "outside" and r["backend_events"]
                and r["seconds"] > 0.05):
            lines.append(build_line(r))
    return lines


_BUILD_IDS = itertools.count()


class EngineBuild:
    """An engine's constructor, booked by phase: ``with engine_build(owner)
    as build: build.phase("weights"); ...``. A phase ends where the next
    begins (or the block does), so the phases PARTITION the block's wall
    time; each is a ``phase`` record and a span ``engine_build(phase=...)``.
    A constructor that runs inside another's block (the engine's inside
    ``EngineBackend``'s, a draft engine's inside its target's) joins it:
    its phases go on under the same ``build`` number and the outermost
    block says the one INFO line ``build: weights 21.3 s (6 built outside
    the table, 19.9 s); ...; total``."""

    def __init__(self, owner: str):
        self.owner = owner
        self.id = next(_BUILD_IDS)
        self.depth = 0
        self.t0 = 0.0
        self.records: list[dict] = []           # the phases that ended
        #: the ``phase`` record under way: what the listener adds a build
        #: outside the table to
        self.open: dict | None = None
        self._span = None

    def phase(self, name: str) -> None:
        from ..telemetry import get_telemetry

        now = self._end_phase()
        self.open = {"kind": "phase", "key": name, "module": self.owner,
                     "build": self.id, "t0": now,
                     **dict.fromkeys(_PHASE_SUMS, 0)}
        self._span = get_telemetry().span("engine_build", phase=name)
        self._span.__enter__()

    def _end_phase(self) -> float:
        now = time.perf_counter()
        if self.open is not None:
            self._span.__exit__(None, None, None)
            rec, self.open = _book(self.open), None
            rec["seconds"] = now - rec["t0"]
            self.records.append(rec)
        return now

    def __enter__(self):
        if not self.depth:
            self.t0 = time.perf_counter()
            _TL.build = self
        self.depth += 1
        return self

    def __exit__(self, exc_type, *exc):
        self.depth -= 1
        if self.depth:
            return False
        total = self._end_phase() - self.t0
        _TL.build = None
        if exc_type is None:
            logger.info(f"build: {phases_line(_fold_phases(self.records))}; "
                        f"total {total:.2f} s")
        return False


def books_its_build(init):
    """For an engine's ``__init__``: the whole constructor runs inside
    ``engine_build(type(self).__name__)``, in phase ``rest`` until it names
    another (``engine_build(...).phase(name)``), whatever it raises."""
    @functools.wraps(init)
    def constructor(self, *args, **kwargs):
        with engine_build(type(self).__name__) as build:
            build.phase("rest")
            init(self, *args, **kwargs)
    return constructor


def engine_build(owner: str) -> EngineBuild:
    """The build this thread's constructor books into: the one under way,
    or a new one of ``owner``'s. (The ledger listens from here on, so that
    what a constructor compiles is heard too: ``outside`` records, under
    their functions' names.)"""
    _listen()
    return getattr(_TL, "build", None) or EngineBuild(owner)


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
