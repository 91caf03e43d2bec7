"""``layers/_entries.py`` on synthetic planes (``reduce_trace.load``'s
plain-data form, an event's stats as its fourth element): a pipeline four
deep whose every interval is known — an exact join gives them back, and a
trace from which the join could only guess gives nothing."""
import pytest

from benchmark import common
from benchmark.layers import (_entries, device_queue_wait_ms,
                              inflight_depth_mean, inflight_residence_ms,
                              prefill_queue_wait_ms, readback_ms,
                              replica_step_host_share)

MS = 1e6                                   # the planes' clock is in ns
RUN, D2H, DEPTH = 40 * MS, 0.4 * MS, 4
FIRST, N = 100, 12                         # seq of the first entry; entries
WINDOW = (50 * MS, (N + DEPTH + 2) * RUN)


def planes(drop_run=None, drop_commit=None, device=True, lag=D2H):
    """A pipeline that stays ``DEPTH`` deep: ``DEPTH`` runs of entries
    dispatched before the trace lead the device's line, the runs follow
    each other without a gap, every entry is committed from a blocked drain
    that ends ``lag`` after its run, and the host dispatches entry ``i``
    0.15 ms after it has committed entry ``i - DEPTH``. Every third entry
    is a prefill chunk."""
    host, dev = [("bench_window", *WINDOW, {})], []
    for i in range(-DEPTH, N):
        r0 = (i + DEPTH + 1) * RUN
        prefill = i % 3 == 0
        name = "jit_step_prefill(123)" if prefill else "jit_run(456)"
        if i != drop_run:
            dev.append((name, r0, r0 + RUN, {"run_id": i}))
        if i < 0:
            continue                       # dispatched before the trace
        seq = FIRST + i
        d0 = (i + 2) * RUN + lag + 0.3 * MS
        kind = {"kind": "prefill", "T": 128} if prefill \
            else {"kind": "window", "W": 8}
        host.append(("dispatch", d0, d0 + 0.5 * MS, {**kind, "seq": seq}))
        b1 = r0 + RUN + lag
        host.append(("drain_block", b1 - 30 * MS, b1,
                     {"kind": "plan", "seq": seq}))
        if i != drop_commit:
            host.append(("commit", b1 + 0.05 * MS, b1 + 0.15 * MS,
                         {"seq": seq, "depth": DEPTH - 1}))
    out = [{"name": "/host:CPU",
            "lines": [{"name": "python3", "events": host}]}]
    if device:
        out.append({"name": "/device:TPU:0", "lines": [
            {"name": "XLA Modules", "events": dev},
            {"name": "XLA Ops", "events": []}]})
    return out


def ctx(pl, **stats):
    return {"trace": {"window_s": (WINDOW[1] - WINDOW[0]) / 1e9},
            "window_s": 0.5,
            "entry_planes": pl, "stats": stats}


COUNTERS = {"entries_dispatched": 10, "inflight_depth_sum": 35,
            "entries_committed": 8, "inflight_residence_s": 1.0,
            "replica_step_s": 0.40, "engine_step_s": 0.39}


def test_an_exact_join_gives_the_three_intervals():
    tl = _entries.join(planes())
    assert tl["in_window"] == len(tl["rows"]) == N
    assert tl["offset"] == DEPTH - FIRST
    # an entry waits for the DEPTH - 1 runs ahead of it, less what the host
    # took from the end of the drain to the end of its dispatch
    queue_s = ((DEPTH - 1) * RUN - D2H - 0.8 * MS) / 1e9
    for r in tl["rows"]:
        assert r["prefill"] == ((r["seq"] - FIRST) % 3 == 0) and r["blocked"]
        assert r["queue_s"] == pytest.approx(queue_s)
        assert r["run_s"] == pytest.approx(RUN / 1e9)
        assert r["readback_s"] == pytest.approx((D2H + 0.05 * MS) / 1e9)
        assert r["residence_s"] == pytest.approx(
            queue_s + (RUN + D2H + 0.15 * MS) / 1e9)
    c = ctx(planes(), **COUNTERS)
    assert readback_ms.read(c) == pytest.approx(0.45)
    assert device_queue_wait_ms.read(c) == pytest.approx(1e3 * queue_s)
    assert prefill_queue_wait_ms.read(c) == pytest.approx(1e3 * queue_s)
    assert c["entry_timeline"]["rows"] == tl["rows"]        # built once


def test_a_host_that_wakes_late_still_joins():
    """A drain votes for the last run that ended before it returned, however
    long the host took to wake (seen on the chip: 2.56 ms in the median,
    past ``d2h_latency_s`` + 2 ms) — the lag is the readback, not a fault."""
    late = 6 * MS
    tl = _entries.join(planes(lag=late))
    assert tl["offset"] == DEPTH - FIRST and len(tl["rows"]) == N
    assert all(r["readback_s"] == pytest.approx((late + 0.05 * MS) / 1e9)
               for r in tl["rows"])


@pytest.mark.parametrize("broken, why", [
    # the device's line lost a run: the entries after it pair one run late
    ({"drop_run": 5}, "paired with a run of"),
    # nothing says when entry 4 left a pipeline that is first in, first out
    ({"drop_commit": 4}, "lack a dispatch or a commit span"),
], ids=["offset_of_one", "missing_commit"])
def test_a_join_that_would_guess_is_refused(broken, why, capsys):
    c = ctx(planes(**broken), **COUNTERS)
    assert _entries.timeline(c) is None
    said = capsys.readouterr().out
    assert "ENTRY TIMELINE REFUSED" in said and why in said
    for reader in (device_queue_wait_ms, prefill_queue_wait_ms, readback_ms):
        assert reader.read(c) is None
    assert inflight_depth_mean.read(c) == 3.5       # the counters need none


def test_a_shifted_pairing_is_refused(capsys):
    """Runs and drains that agree with each other, but on runs that start
    before their entries were dispatched: the vote passes, the refusals do
    not."""
    pl = planes()
    host = pl[0]["lines"][0]["events"]
    pl[0]["lines"][0]["events"] = [
        (n, a + 4 * RUN, b + 4 * RUN, s) if n == "dispatch" else (n, a, b, s)
        for n, a, b, s in host]
    assert _entries.join(pl) is None
    assert "before its dispatch" in capsys.readouterr().out


def test_another_runs_window_is_refused(capsys):
    c = ctx(planes(), **COUNTERS)
    c["trace"]["window_s"] += 0.25
    assert _entries.timeline(c) is None
    assert "holds a window of" in capsys.readouterr().out


def test_without_a_device_line_the_counters_still_read():
    c = ctx(planes(device=False), **COUNTERS)
    entry = {"metrics": {"per_layer": [
        {"name": n, "unit": "x"} for n in (
            "inflight_depth_mean", "inflight_residence_ms",
            "device_queue_wait_ms", "prefill_queue_wait_ms", "readback_ms",
            "replica_step_host_share")]}}
    got = common.read_layers(entry, c)
    assert {k: v["value"] for k, v in got.items()} == {
        "inflight_depth_mean": 3.5, "inflight_residence_ms": 125.0,
        "replica_step_host_share": pytest.approx(2.0)}
    # --rehearse: the runner says the trace is the host's alone
    c = ctx(planes(), **COUNTERS)
    c["trace"]["host_only"] = True
    assert readback_ms.read(c) is None


def test_a_parent_commit_gives_nothing_and_raises_nothing():
    """No counters, and spans without ``seq`` (three-element events, as
    ``reduce_trace.load`` gives them)."""
    pl = planes()
    pl[0]["lines"][0]["events"] = [e[:3] for e in pl[0]["lines"][0]["events"]]
    c = ctx(pl, plan_s=0.1)
    for reader in (inflight_depth_mean, inflight_residence_ms,
                   device_queue_wait_ms, prefill_queue_wait_ms, readback_ms,
                   replica_step_host_share):
        assert reader.read(c) is None
