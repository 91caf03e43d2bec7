"""From a profiler trace (``.xplane.pb``) to numbers — on
``jax.profiler.ProfileData`` alone (no TensorFlow import).

What is read: the device planes (``/device:TPU:<n>``), on each the line
"XLA Modules" (one event per run of a jitted program: ``jit_step_prefill``,
``jit_run`` …) and the line "XLA Ops" (one event per HLO operation, nested
where an op contains others: a ``while`` and its body); and the host
planes' named spans (``jax.profiler.TraceAnnotation``, into which the
program's telemetry spans are mirrored). A trace with no TPU plane is an
error, never ``None``.

  busy      union of the op intervals of a device, inside the window
  idle      window minus busy; each long gap named after the host span
            that covers most of it
  programs  device seconds and runs by program name
  ops       SELF time by op (an op's time minus its children's — a naive
            sum counts a ``while`` body twice); ``ops_by_program`` has the
            same, with the number of calls, under the program whose run
            on the "XLA Modules" line holds the op's start
  collectives  time in all-gather / reduce-scatter / all-reduce /
            all-to-all / collective-permute and the TPU compiler's
            ``async-collective`` fusions (an async pair counts from its
            ``-start`` to the end of its ``-done``), and the EXPOSED part:
            collective time during which no other op ran on that device

Everything is averaged over the device planes unless it says otherwise.
Host and device clocks agree to about a millisecond in a v5e trace (the
recorded fixture: device events lead the host's by ~1.1 ms), so a window
of seconds is clipped soundly and a gap of milliseconds finds its span.
"""
from __future__ import annotations

import bisect
import glob
import os
import re
from collections import defaultdict

DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
MODULES_LINE = "XLA Modules"
OPS_LINE = "XLA Ops"
WINDOW_SPAN = "bench_window"
COLLECTIVE = re.compile(
    r"^%?(all-gather|all-reduce|reduce-scatter|all-to-all|"
    r"collective-permute|collective-broadcast|async-collective)"
    r"(-start|-done)?\b")
_DONE_OPERAND = re.compile(r"-done\(%?([\w.-]+?-start[\w.]*)")
ASYNC_OPS_LINE = "Async XLA Ops"
_SUFFIX = re.compile(r"[.\d]+$")


class TraceError(RuntimeError):
    pass


def find_xplane(trace_dir: str) -> str:
    found = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not found:
        raise TraceError(f"no .xplane.pb under {trace_dir}")
    return found[-1]


def load(path: str) -> list[dict]:
    """The trace as plain data: ``[{"name", "lines": [{"name", "events":
    [(name, start_ns, end_ns)]}]}]``."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    planes = []
    for pl in data.planes:
        lines = []
        for ln in pl.lines:
            ev = [(e.name, float(e.start_ns),
                   float(e.start_ns) + float(e.duration_ns))
                  for e in ln.events]
            lines.append({"name": ln.name, "events": ev})
        planes.append({"name": pl.name, "lines": lines})
    return planes


def device_planes(planes: list[dict]) -> list[dict]:
    dev = [p for p in planes if DEVICE_PLANE.match(p["name"])]
    if not dev:
        raise TraceError("the trace has no TPU device plane (planes: "
                         f"{[p['name'] for p in planes]})")
    return sorted(dev, key=lambda p: int(p["name"].rsplit(":", 1)[1]))


def _line(plane: dict, name: str) -> list[tuple]:
    for ln in plane["lines"]:
        if ln["name"] == name:
            return ln["events"]
    return []


def union(intervals) -> list[tuple[float, float]]:
    """Disjoint sorted intervals covering the same points."""
    out: list[list[float]] = []
    for a, b in sorted(i for i in intervals if i[1] > i[0]):
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return [(a, b) for a, b in out]


def total(intervals) -> float:
    return sum(b - a for a, b in intervals)


def clip(intervals, lo: float, hi: float):
    return [(max(a, lo), min(b, hi)) for a, b in intervals
            if min(b, hi) > max(a, lo)]


def subtract(a, b) -> list[tuple[float, float]]:
    """Points of ``a`` not in ``b`` (both disjoint and sorted)."""
    out, j = [], 0
    for lo, hi in a:
        cur = lo
        while j < len(b) and b[j][1] <= cur:
            j += 1
        k = j
        while k < len(b) and b[k][0] < hi:
            if b[k][0] > cur:
                out.append((cur, b[k][0]))
            cur = max(cur, b[k][1])
            k += 1
        if cur < hi:
            out.append((cur, hi))
    return out


def self_times(events) -> list[tuple[str, float, float, float]]:
    """(name, start, end, self_ns) for every event of one line: an event's
    duration minus that of the events nested directly inside it."""
    ev = sorted(events, key=lambda e: (e[1], -(e[2] - e[1])))
    child = [0.0] * len(ev)
    stack: list[int] = []
    for i, (_, a, b) in enumerate(ev):
        while stack and ev[stack[-1]][2] <= a:
            stack.pop()
        if stack:
            child[stack[-1]] += b - a
        stack.append(i)
    return [(n, a, b, max(b - a - c, 0.0)) for (n, a, b), c in zip(ev, child)]


def op_key(name: str) -> str:
    """``%fusion.123`` and ``fusion.7`` are the same kind of op."""
    return _SUFFIX.sub("", name.lstrip("%").split(" ")[0]) or name


def host_spans(planes: list[dict]) -> list[tuple]:
    """Named spans on the host planes: TraceAnnotations (the Python
    tracer's own events start with ``$`` and are left out)."""
    out = []
    for p in planes:
        if not p["name"].startswith("/host:"):
            continue
        for ln in p["lines"]:
            out += [e for e in ln["events"] if not e[0].startswith("$")]
    return out


def window_of(planes: list[dict]) -> tuple[float, float]:
    """The traced window: the ``bench_window`` span where the host wrote
    one that overlaps device work, else first to last device event."""
    dev = device_planes(planes)
    ev = [e for p in dev for e in _line(p, OPS_LINE) + _line(p, MODULES_LINE)]
    if not ev:
        raise TraceError("no operation ran on the device in this trace")
    lo, hi = min(e[1] for e in ev), max(e[2] for e in ev)
    for name, a, b in host_spans(planes):
        if name == WINDOW_SPAN and a < hi and b > lo:
            return a, b
    return lo, hi


def _collective_spans(ops, async_ops=()) -> tuple[list, dict]:
    """Intervals in which a collective was in flight, and the same by kind.
    A ``-done`` op names its ``-start`` as its operand in the HLO text of
    its event (``all-gather-done(%all-gather-start.3)``); without one, it
    pairs with the oldest open start of the same name. The TPU compiler
    wraps most collectives into ``async-collective-start``/``-done``
    fusions: kind ``async-collective``. Events of the "Async XLA Ops" line
    already span the flight and are taken as they are."""
    by_kind: dict = defaultdict(list)
    started: dict[str, float] = {}
    fifo: dict[str, list] = defaultdict(list)
    for name, a, b in sorted(ops, key=lambda e: e[1]):
        m = COLLECTIVE.match(name)
        if not m:
            continue
        kind, phase = m.group(1), m.group(2)
        ident = name.lstrip("%").split(" ")[0]
        if phase == "-start":
            started[ident] = a
            fifo[kind].append(ident)
        elif phase == "-done":
            arg = _DONE_OPERAND.search(name)
            src = arg.group(1) if arg and arg.group(1) in started else (
                fifo[kind][0] if fifo[kind] else None)
            a0 = started.pop(src, a) if src else a
            if src in fifo[kind]:
                fifo[kind].remove(src)
            by_kind[kind].append((a0, b))
        else:
            by_kind[kind].append((a, b))
    for name, a, b in async_ops:
        m = COLLECTIVE.match(name)
        if m:
            by_kind[m.group(1)].append((a, b))
    spans = [iv for v in by_kind.values() for iv in v]
    return spans, {k: total(union(v)) for k, v in by_kind.items()}


def summarize(planes: list[dict], top: int = 10) -> dict:
    dev = device_planes(planes)
    lo, hi = window_of(planes)
    window_s = (hi - lo) / 1e9
    n = len(dev)
    busy_s = 0.0
    programs: dict = defaultdict(lambda: {"s": 0.0, "runs": 0})
    ops: dict = defaultdict(float)
    by_program: dict = defaultdict(lambda: defaultdict(lambda: [0.0, 0.0]))
    coll = {"total_s": 0.0, "exposed_s": 0.0, "by_kind": defaultdict(float)}
    gaps: list[tuple[float, float]] = []
    for i, p in enumerate(dev):
        op_ev = [e for e in _line(p, OPS_LINE) if e[2] > lo and e[1] < hi]
        mod_ev = [e for e in _line(p, MODULES_LINE) if e[2] > lo and e[1] < hi]
        busy = clip(union([(a, b) for _, a, b in op_ev or mod_ev]), lo, hi)
        busy_s += total(busy) / 1e9 / n
        if i == 0:                       # gaps under 1 us are seams between ops
            gaps = [g for g in subtract([(lo, hi)], busy) if g[1] - g[0] >= 1e3]
        for name, a, b in mod_ev:
            key = name.split("(")[0]
            programs[key]["s"] += (min(b, hi) - max(a, lo)) / 1e9 / n
            programs[key]["runs"] += 1.0 / n
        timed = self_times(op_ev)
        runs = sorted((a, b, name.split("(")[0]) for name, a, b in mod_ev)
        run_starts = [r[0] for r in runs]
        for name, a, b, self_ns in timed:
            ops[op_key(name)] += self_ns / 1e9 / n
            j = bisect.bisect_right(run_starts, a) - 1
            prog = runs[j][2] if j >= 0 and a < runs[j][1] else "none"
            cell = by_program[prog][op_key(name)]
            cell[0] += self_ns / 1e9 / n
            cell[1] += 1.0 / n
        spans, by_kind = _collective_spans(op_ev, [
            e for e in _line(p, ASYNC_OPS_LINE) if e[2] > lo and e[1] < hi])
        inflight = clip(union(spans), lo, hi)
        compute = clip(union(
            [(a, b) for name, a, b, s in timed
             if not COLLECTIVE.match(name) and s > 0
             and op_key(name) not in ("while", "conditional", "call")]),
            lo, hi)
        coll["total_s"] += total(inflight) / 1e9 / n
        coll["exposed_s"] += total(subtract(inflight, compute)) / 1e9 / n
        for k, v in by_kind.items():
            coll["by_kind"][k] += v / 1e9 / n
    spans = [s for s in host_spans(planes) if s[0] != WINDOW_SPAN]
    named = []
    for a, b in sorted(gaps, key=lambda g: g[0] - g[1])[:top]:
        cover: dict = defaultdict(float)
        for name, sa, sb in spans:
            ov = min(b, sb) - max(a, sa)
            if ov > 0:
                cover[name] += ov
        best = max(cover.items(), key=lambda kv: kv[1], default=("none", 0.0))
        named.append([best[0] if best[1] >= 0.1 * (b - a) else "none",
                      (b - a) / 1e9])
    coll["by_kind"] = dict(coll["by_kind"])
    return {
        "devices": n, "window_s": window_s, "busy_s": busy_s,
        "idle_share": 1.0 - busy_s / window_s if window_s > 0 else None,
        "programs": {k: dict(v) for k, v in programs.items()},
        "ops": sorted(([k, v] for k, v in ops.items()),
                      key=lambda kv: -kv[1]),
        "ops_by_program": {p: {k: list(v) for k, v in d.items()}
                           for p, d in by_program.items()},
        "collectives": coll,
        "idle_gaps": named,
    }


def breakdown(summary: dict, top: int = 10) -> dict:
    """The contract's ``breakdown``: the device operations that took most
    time, and the longest idle gaps by what the host was doing."""
    return {"device_ops": [[k, v] for k, v in summary["ops"][:top]],
            "idle_gaps": summary["idle_gaps"][:top]}


def start(trace_dir: str) -> None:
    """Start the profiler with the Python tracer off (it slows the host
    loop it is meant to observe and bloats the trace)."""
    import jax

    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 2
    jax.profiler.start_trace(trace_dir, profiler_options=opts)


def host_only_summary(planes: list[dict]) -> dict:
    """``--rehearse`` only: the CPU backend writes no device plane, so the
    rehearsal gets the window and nothing that would pass for a device
    number (readers of device lines find nothing and leave their metric
    out)."""
    w = [(a, b) for n, a, b in host_spans(planes) if n == WINDOW_SPAN]
    if not w:
        raise TraceError("no bench_window span in the trace")
    return {"devices": 0, "window_s": (w[0][1] - w[0][0]) / 1e9,
            "busy_s": 0.0, "idle_share": None, "programs": {}, "ops": [],
            "ops_by_program": {},
            "collectives": {"total_s": 0.0, "exposed_s": 0.0, "by_kind": {}},
            "idle_gaps": [], "host_only": True}


def stop_and_summarize(trace_dir: str, host_only: bool = False) -> dict:
    import jax

    jax.profiler.stop_trace()
    planes = load(find_xplane(trace_dir))
    return host_only_summary(planes) if host_only else summarize(planes)
