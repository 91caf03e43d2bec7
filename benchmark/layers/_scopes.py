"""Device time by named scope: the traced window's ops joined with the scope
maps the program publishes (``deepspeed_tpu.profiling.trace.
program_scope_maps``: compiled HLO instruction -> ``op_name`` path).

The device trace names an op by its HLO instruction and nothing else, so
the table op -> scope has to come from the program, which owns its compiled
programs. A program without that function (a parent commit) gives no table
and every reader here returns None. The join is held to the old reader:
per program, the self time summed here must equal ``summarize``'s
``ops_by_program`` within 1 %, else nothing is reported.

A share is of the device SELF time inside the named programs in the traced
window, averaged over the device planes. Scopes partition a program's time
(``unscoped`` and ``ambiguous`` are the remainder); direction (fwd / bwd /
recompute) cuts across them.
"""
from __future__ import annotations

import bisect
import os
import time
from collections import defaultdict

from benchmark import common, reduce_trace
from benchmark.common import say

DECODE_PROGRAMS = ("jit_run", "jit_step_decode")
TRAIN_PROGRAMS = ("jit_train_step",)
REMAINDER = ("unscoped", "ambiguous")
TOLERANCE = 0.01
_tables: dict = {}


def trace_dir() -> str:
    """Where the runner of this process wrote its trace — built as both
    runners build it (``ctx`` carries no path)."""
    return os.path.join(common.OUT_DIR, common.runner_args().workload,
                        "trace")


def join(planes: list[dict], maps: dict, scope_of) -> dict:
    """``{program: {(scope, direction): seconds}}``: every op's SELF time
    in the window under the scope its program's map gives its instruction,
    the program being the run on the "XLA Modules" line that holds the
    op's start — as ``reduce_trace.summarize`` builds ``ops_by_program``."""
    dev = reduce_trace.device_planes(planes)
    lo, hi = reduce_trace.window_of(planes)
    out: dict = defaultdict(lambda: defaultdict(float))
    for p in dev:
        line = {ln["name"]: ln["events"] for ln in p["lines"]}
        ops = [e for e in line.get(reduce_trace.OPS_LINE, ())
               if e[2] > lo and e[1] < hi]
        runs = sorted((a, b, name.split("(")[0])
                      for name, a, b in line.get(reduce_trace.MODULES_LINE, ())
                      if b > lo and a < hi)
        starts = [r[0] for r in runs]
        for name, a, _, self_ns in reduce_trace.self_times(ops):
            j = bisect.bisect_right(starts, a) - 1
            prog = runs[j][2] if j >= 0 and a < runs[j][1] else "none"
            op = maps.get(prog, {}).get("ops", {}).get(
                name.lstrip("%").split(" ", 1)[0])
            key = ("ambiguous", "fwd") if op == "ambiguous" else scope_of(op)
            out[prog][key] += self_ns / 1e9 / len(dev)
    return {p: dict(t) for p, t in out.items()}


def check(table: dict, ops_by_program: dict) -> list[str]:
    """Programs whose total here and in ``summarize`` differ by more than
    1 % (empty: the two readers agree)."""
    bad = []
    for prog in set(table) | set(ops_by_program):
        mine = sum(table.get(prog, {}).values())
        old = sum(s for s, _ in ops_by_program.get(prog, {}).values())
        if abs(mine - old) > TOLERANCE * max(mine, old):
            bad.append(f"{prog}: {mine:.6f} s here, {old:.6f} s in "
                       f"ops_by_program")
    return bad


def show(table: dict) -> None:
    """The whole table: program x scope x direction, seconds and the share
    of that program's device time."""
    for prog in sorted(table, key=lambda p: -sum(table[p].values())):
        total = sum(table[prog].values())
        rows = sorted(table[prog].items(), key=lambda kv: -kv[1])
        say(f"scopes of {prog} ({total:.4f} s): " + "; ".join(
            f"{scope} {direction} {s:.4f} s {100 * s / total:.2f} %"
            for (scope, direction), s in rows))


def table(ctx) -> dict | None:
    """The window's scope table, built once per process and ``say``-ed;
    None (quietly) under ``--rehearse`` and where the program publishes no
    maps, None (loudly) where the join fails its check."""
    if ctx["trace"].get("host_only"):
        return None
    path = ctx.get("trace_dir") or trace_dir()
    if path not in _tables:
        _tables[path] = _build(ctx, path)
    return _tables[path]


def _build(ctx, path: str) -> dict | None:
    try:
        from deepspeed_tpu.profiling.trace import (program_scope_maps,
                                                   scope_of)
    except ImportError:
        say("scope table: this program publishes no scope maps")
        return None
    t0 = time.monotonic()
    maps = ctx.get("scope_maps")
    if maps is None:
        maps = program_scope_maps(set(ctx["trace"]["programs"]))
    t1 = time.monotonic()
    planes = reduce_trace.load(reduce_trace.find_xplane(path)) \
        if os.path.isdir(path) else reduce_trace.load(path)
    tab = join(planes, maps, scope_of)
    say(f"scope maps: {sum(m['programs'] for m in maps.values())} compiled "
        f"programs of {sorted(maps)} lowered, fetched and parsed in "
        f"{t1 - t0:.2f} s (largest HLO text "
        f"{max((m['hlo_bytes'] for m in maps.values()), default=0)} bytes); "
        f"trace read again and joined in {time.monotonic() - t1:.2f} s")
    bad = check(tab, ctx["trace"]["ops_by_program"])
    if bad:
        say("SCOPE TABLE DISAGREES WITH ops_by_program, nothing reported: "
            + "; ".join(bad))
        return None
    show(tab)
    return tab


def share(ctx, programs, scopes=None, direction=None):
    """Per cent of the device self time inside ``programs`` that lies under
    one of ``scopes`` (any, if None) in ``direction`` (any, if None)."""
    tab = table(ctx)
    if tab is None:
        return None
    rows = [(k, s) for p in programs for k, s in tab.get(p, {}).items()]
    total = sum(s for _, s in rows)
    if not total:
        return None
    hit = sum(s for (scope, d), s in rows
              if (scopes is None or scope in scopes)
              and (direction is None or d == direction))
    return 100.0 * hit / total
