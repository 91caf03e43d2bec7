"""The entry timeline: every step the engine dispatched in the traced
window, followed from the host's ``dispatch`` to its run on the device to
the host's ``commit`` — on ONE clock, the profiler's.

The engine numbers each entry it appends to its pipeline (``seq``) and its
``dispatch``, ``drain_block`` and ``commit`` spans carry the number into the
xplane as event stats (``telemetry/spans.py``). The device's side has no
such number: device 0's "XLA Modules" line holds one event a run of
``jit_run`` / ``jit_step_decode`` / ``jit_step_prefill``, and the engine's
programs run in dispatch order on one stream, so entry ``k`` is run
``k + offset``. The offset is VOTED by the blocked drains — a
``drain_block`` of entry ``k`` returns when ``k``'s run has ended and its
tokens have crossed, so each votes for the last run that ended before it
did (to the 2 ms by which the two clocks agree, as ``reduce_trace``
documents; how long after is the host's wake-up, 1.3 to 2.6 ms in the
median on a shared host, so no bound nearer than the next run's end is put
on it) and more than half must agree — and then held to three refusals:
for EVERY joined entry ``dispatch start - 2 ms <= run start`` and
``run end <= commit end + 2 ms``, the run is of the program the dispatch's
``kind`` and ``T`` name, and the median of |queue wait + run + readback -
residence| is under 2 ms (each wait clipped at 0, so the sum telescopes
only where the run lies between its dispatch and its commit). A join that
fails any of them reports NOTHING, loudly: a wrong offset must never pass
for a number.

  queue wait   run start - dispatch end: the entry waits behind the entries
               queued on the device before it
  run          its program on the device
  readback     commit start - run end: the device has the tokens, the host
               does not yet (d2h, wake-up, and whatever the host did first)
  residence    commit end - dispatch end: what ``inflight_residence_s``
               sums on the host's clock

A program without the numbered spans (a parent commit) gives no entry and
every reader here returns None; so does a trace with no device plane
(``--rehearse``). The counters' readers need no trace.
"""
from __future__ import annotations

import bisect
import statistics
from collections import Counter

from benchmark import reduce_trace
from benchmark.common import say
from benchmark.layers import _scopes, _shared

SPANS = ("dispatch", "drain_block", "commit")
ENGINE_PROGRAMS = _shared.DECODE_PROGRAMS + _shared.PREFILL_PROGRAMS
CLOCK_NS = 2e6          # host and device clocks agree to about a millisecond
MIN_JOINED = 0.9        # of the window's entries, or nothing is reported


def load(path: str) -> list[dict]:
    """``reduce_trace.load``'s plain-data form with a host span's stats as
    a fourth element, ``(name, start_ns, end_ns, {stat: value})`` — and only
    what the join reads: the host planes' engine-loop spans and the window's,
    and the devices' "XLA Modules" lines."""
    from jax.profiler import ProfileData

    keep = SPANS + (reduce_trace.WINDOW_SPAN,)
    planes = []
    for pl in ProfileData.from_file(path).planes:
        host = pl.name.startswith("/host:")
        if not (host or reduce_trace.DEVICE_PLANE.match(pl.name)):
            continue
        lines = []
        for ln in pl.lines:
            if not (host or ln.name == reduce_trace.MODULES_LINE):
                continue
            ev = [(e.name, float(e.start_ns),
                   float(e.start_ns) + float(e.duration_ns),
                   dict(e.stats) if host else {})
                  for e in ln.events if not host or e.name in keep]
            if ev:
                lines.append({"name": ln.name, "events": ev})
        planes.append({"name": pl.name, "lines": lines})
    return planes


def _spans(planes: list[dict]) -> tuple[dict, tuple | None]:
    """``{span name: {seq: (start, end, stats)}}`` and the window."""
    by_name: dict = {n: {} for n in SPANS}
    window = None
    for p in planes:
        if not p["name"].startswith("/host:"):
            continue
        for ln in p["lines"]:
            for name, a, b, *rest in ln["events"]:
                args = rest[0] if rest else {}
                if name == reduce_trace.WINDOW_SPAN:
                    window = (a, b)
                elif name in by_name and "seq" in args:
                    by_name[name][int(args["seq"])] = (a, b, args)
    return by_name, window


def _runs(planes: list[dict]) -> list[tuple] | None:
    """(start, end, program) of the engine's runs on device 0, in order."""
    dev = [p for p in planes if reduce_trace.DEVICE_PLANE.match(p["name"])]
    if not dev:
        return None
    first = min(dev, key=lambda p: int(p["name"].rsplit(":", 1)[1]))
    runs = [(e[1], e[2], e[0].split("(")[0])
            for ln in first["lines"] if ln["name"] == reduce_trace.MODULES_LINE
            for e in ln["events"] if e[0].split("(")[0] in ENGINE_PROGRAMS]
    return sorted(runs)


def _program_of(args: dict) -> str:
    """The program an entry's dispatch ran, from its span's arguments, as
    ``engine_v2`` names them: a window ``run``; a step plan ``step_prefill``
    where it is wider than a token, else ``step_decode``."""
    if args.get("kind") == "window":
        return "jit_run"
    return "jit_step_prefill" if int(args.get("T", 1)) > 1 \
        else "jit_step_decode"


def join(planes: list[dict]) -> dict | None:
    """The window's entries, each with its four intervals in seconds, or
    None: quietly where the trace has no device plane or no numbered span,
    LOUDLY where the join fails one of its refusals."""
    runs = _runs(planes)
    spans, window = _spans(planes)
    if runs is None or not spans["dispatch"] or window is None:
        return None
    lo, hi = window
    inside = sorted(k for k, d in spans["dispatch"].items()
                    if d[0] >= lo and k in spans["commit"]
                    and spans["commit"][k][1] <= hi)
    if not inside:
        say("entry timeline: no entry was dispatched and committed inside "
            "the window; nothing reported")
        return None
    ends = [r[1] for r in runs]
    votes: Counter = Counter()
    for k, (_, b1, _) in spans["drain_block"].items():
        j = bisect.bisect_right(ends, b1 + CLOCK_NS) - 1
        if j >= 0:
            votes[j - k] += 1
    if not votes:
        say("ENTRY TIMELINE REFUSED: no blocked drain ends after a run of "
            f"the engine's programs ({len(spans['drain_block'])} drains, "
            f"{len(runs)} runs): the offset cannot be fixed")
        return None
    offset, n_votes = votes.most_common(1)[0]
    lag = statistics.median(b1 - ends[k + offset]
                            for k, (_, b1, _) in spans["drain_block"].items()
                            if 0 <= k + offset < len(ends))
    rows, bad = [], []
    if 2 * n_votes < len(spans["drain_block"]):
        bad.append("fewer than half of the blocked drains agree on it")
    lost = sorted(set(range(inside[0], inside[-1] + 1)) - set(inside))
    if lost:                  # the pipeline is first in, first out
        bad.append(f"seq {lost} lack a dispatch or a commit span between "
                   f"entries that have both")
    for k in inside:
        j = k + offset
        if not 0 <= j < len(runs):
            continue                      # its run lies outside the trace
        d0, d1, dargs = spans["dispatch"][k]
        c0, c1, _ = spans["commit"][k]
        r0, r1, prog = runs[j]
        kind = str(dargs.get("kind"))
        if prog != _program_of(dargs):
            bad.append(f"seq {k} ({kind}) paired with a run of {prog}")
        if r0 < d0 - CLOCK_NS:
            bad.append(f"seq {k}: its run starts {(d0 - r0) / 1e6:.2f} ms "
                       f"before its dispatch")
        if r1 > c1 + CLOCK_NS:
            bad.append(f"seq {k}: its run ends {(r1 - c1) / 1e6:.2f} ms "
                       f"after its commit")
        rows.append({"seq": k, "prefill": kind == "prefill",
                     "blocked": k in spans["drain_block"],
                     "queue_s": max(r0 - d1, 0.0) / 1e9,
                     "run_s": (r1 - r0) / 1e9,
                     "readback_s": max(c0 - r1, 0.0) / 1e9,
                     "residence_s": (c1 - d1) / 1e9})
    residual = statistics.median(
        abs(r["queue_s"] + r["run_s"] + r["readback_s"] - r["residence_s"])
        for r in rows) if rows else 0.0
    if residual * 1e9 >= CLOCK_NS:
        bad.append(f"median |queue wait + run + readback - residence| "
                   f"{residual * 1e3:.2f} ms")
    if len(rows) < MIN_JOINED * len(inside):
        bad.append(f"only {len(rows)} of the window's {len(inside)} entries "
                   f"have their run in the trace")
    head = (f"{len(rows)} of {len(inside)} entries of the window joined "
            f"(run = seq {offset:+d}, voted by {n_votes} of "
            f"{len(spans['drain_block'])} blocked drains, each for the last "
            f"run that ended before it returned: median {lag / 1e6:.2f} ms "
            f"after it), median residual "
            f"{residual * 1e3:.3f} ms")
    if bad:
        say(f"ENTRY TIMELINE REFUSED, nothing reported: {head}; "
            + "; ".join(bad[:6])
            + (f"; and {len(bad) - 6} more" if len(bad) > 6 else ""))
        return None
    out = {"rows": rows, "in_window": len(inside), "offset": offset,
           "residual_s": residual}
    say(f"entry timeline: {head}; ms an entry: " + _means(rows)
        + "; prefill entries apart: "
        + _means([r for r in rows if r["prefill"]]))
    return out


def _means(rows: list[dict]) -> str:
    if not rows:
        return "none"
    return ", ".join(
        f"{key[:-2]} {_mean_ms(rows, (key,)):.2f}"
        for key in ("queue_s", "run_s", "readback_s", "residence_s")) \
        + f" ({len(rows)} entries, {sum(r['blocked'] for r in rows)} blocked)"


def timeline(ctx) -> dict | None:
    """The window's join, built once a run (kept in ``ctx``)."""
    if "entry_timeline" not in ctx:
        ctx["entry_timeline"] = _build(ctx)
    return ctx["entry_timeline"]


def _build(ctx) -> dict | None:
    if ctx["trace"].get("host_only"):
        return None
    planes = ctx.get("entry_planes")
    if planes is None:
        planes = load(reduce_trace.find_xplane(
            ctx.get("trace_dir") or _scopes.trace_dir()))
    _, window = _spans(planes)
    mine = (window[1] - window[0]) / 1e9 if window else None
    if mine is None or abs(mine - ctx["trace"]["window_s"]) > 1e-6:
        say(f"ENTRY TIMELINE REFUSED: the trace found holds a window of "
            f"{mine} s, this run's is {ctx['trace']['window_s']} s")
        return None
    tl = join(planes)
    res = residence_ms(ctx)
    if tl and res is not None:
        parts = _mean_ms(tl["rows"], ("queue_s", "run_s", "readback_s"))
        say(f"entry timeline against the counters: the joined entries' "
            f"queue wait + run + readback {parts:.2f} ms, "
            f"inflight_residence_s / entries_committed {res:.2f} ms "
            f"({100 * (parts / res - 1):+.2f} %)")
    return tl


def _mean_ms(rows: list[dict], keys) -> float | None:
    if not rows:
        return None
    return 1e3 * statistics.fmean(sum(r[k] for k in keys) for r in rows)


def mean_ms(ctx, key: str, prefill_only: bool = False):
    """Mean of one interval over the joined entries (or the prefill ones
    alone), in ms; None where nothing was joined."""
    tl = timeline(ctx)
    return _mean_ms([r for r in (tl or {}).get("rows", ())
                     if r["prefill"] or not prefill_only], (key,))


def depth_mean(ctx):
    s = ctx["stats"]
    if not s.get("entries_dispatched"):
        return None
    return s["inflight_depth_sum"] / s["entries_dispatched"]


def residence_ms(ctx):
    s = ctx["stats"]
    if not s.get("entries_committed"):
        return None
    return 1e3 * s["inflight_residence_s"] / s["entries_committed"]


def replica_step_host_share(ctx):
    s = ctx["stats"]
    if not s.get("replica_step_s"):
        return None
    return _shared.pct(s["replica_step_s"] - s["engine_step_s"],
                       ctx["window_s"])
