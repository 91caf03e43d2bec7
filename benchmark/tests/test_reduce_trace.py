"""reduce_trace.py on hand-made planes (where every number can be worked
out on paper) and on one small trace recorded on a v5e."""
import json
import os

import pytest

from benchmark import reduce_trace as rt

HERE = os.path.dirname(os.path.abspath(__file__))
FIXTURE = os.path.join(HERE, "data", "tpu_v5e_small.xplane.pb")


def plane(name, **lines):
    """Times below are written in microseconds (events hold nanoseconds)."""
    return {"name": name, "lines": [
        {"name": k.replace("_", " "),
         "events": [(n, a * 1e3, b * 1e3) for n, a, b in v]}
        for k, v in lines.items()]}


def two_device_trace():
    """Device 0: a while (100-500) holding two fusions and an async
    all-gather whose flight (300-500) overlaps fusion.2 (310-400); then a
    blocking all-reduce (500-600); gap 600-700 under the host's ``plan``
    span; fusion.3 (700-1000). Device 1: busy 100-900 with one fusion."""
    d0 = plane("/device:TPU:0",
               XLA_Modules=[("jit_step(1)", 100, 600), ("jit_step(2)", 700, 1000)],
               XLA_Ops=[("while.1", 100, 500), ("fusion.1", 100, 300),
                        ("all-gather-start.1", 300, 310), ("fusion.2", 310, 400),
                        ("all-gather-done.1", 400, 500),
                        ("all-reduce.3", 500, 600), ("fusion.3", 700, 1000)])
    d1 = plane("/device:TPU:1", XLA_Modules=[("jit_step(1)", 100, 900)],
               XLA_Ops=[("fusion.9", 100, 900)])
    host = plane("/host:CPU", python=[("bench_window", 0, 1000),
                                      ("plan", 590, 710),
                                      ("$profiler.py:1 noise", 0, 1000)])
    return [host, d1, d0]


def test_busy_idle_and_window():
    s = rt.summarize(two_device_trace())
    assert s["devices"] == 2
    assert s["window_s"] == pytest.approx(1000e-6)
    # device 0 busy 100-600 and 700-1000 = 800; device 1 busy 800 -> mean 800
    assert s["busy_s"] == pytest.approx(800e-6)
    assert s["idle_share"] == pytest.approx(0.2)


def test_programs_by_name_and_runs():
    s = rt.summarize(two_device_trace())
    # (500 + 300 + 800) / 2 devices; 3 runs over 2 devices
    assert s["programs"]["jit_step"]["s"] == pytest.approx(800e-6)
    assert s["programs"]["jit_step"]["runs"] == pytest.approx(1.5)


def test_ops_are_self_time_not_a_double_count():
    ops = dict(rt.summarize(two_device_trace())["ops"])
    # the while's 400 ns are all its children's; fusions: 200+90+300 on
    # device 0 and 800 on device 1, halved
    assert ops["while"] == pytest.approx(0.0)
    assert ops["fusion"] == pytest.approx((590 + 800) / 2 * 1e-6)
    assert sum(ops.values()) == pytest.approx(800e-6)


def test_ops_by_program_follow_the_run_that_holds_them():
    d0 = plane("/device:TPU:0",
               XLA_Modules=[("jit_run(1)", 0, 400), ("jit_step_prefill(2)", 500, 900)],
               XLA_Ops=[("while.1", 0, 400), ("closed_call.1", 0, 100),
                        ("closed_call.2", 200, 300), ("fusion.1", 300, 400),
                        ("closed_call.5", 500, 800), ("fusion.2", 950, 1000)])
    by = rt.summarize([d0])["ops_by_program"]
    assert by["jit_run"]["closed_call"] == [pytest.approx(200e-6), 2.0]
    assert by["jit_run"]["fusion"] == [pytest.approx(100e-6), 1.0]
    assert by["jit_run"]["while"] == [pytest.approx(100e-6), 1.0]   # 100-200
    assert by["jit_step_prefill"] == {"closed_call": [pytest.approx(300e-6), 1.0]}
    assert by["none"] == {"fusion": [pytest.approx(50e-6), 1.0]}    # no run holds it


def test_collective_time_and_its_exposed_part():
    c = rt.summarize(two_device_trace())["collectives"]
    # in flight 300-500 (async pair) and 500-600 (blocking) on device 0,
    # nothing on device 1 -> mean 150; fusion.2 hides 90 of it -> 210 / 2
    assert c["total_s"] == pytest.approx(150e-6)
    assert c["exposed_s"] == pytest.approx(105e-6)
    assert c["by_kind"]["all-gather"] == pytest.approx(100e-6)
    assert c["by_kind"]["all-reduce"] == pytest.approx(50e-6)


def test_async_collective_fusions_pair_by_operand():
    """The TPU compiler's form: start and done carry different suffixes
    and the done names its start; two are in flight at once."""
    d0 = plane("/device:TPU:0", XLA_Modules=[("jit_step(1)", 0, 1000)],
               XLA_Ops=[
        ("%async-collective-start.7 = (f32[8]) async-collective-start(%p)", 0, 10),
        ("%async-collective-start.9 = (f32[8]) async-collective-start(%q)", 10, 20),
        ("%fusion.1 = f32[8] fusion(%p)", 20, 500),
        ("%async-collective-done.2 = f32[8] async-collective-done(%async-collective-start.9)", 500, 600),
        ("%async-collective-done.3 = f32[8] async-collective-done(%async-collective-start.7)", 600, 800),
        ("%fusion.2 = f32[8] fusion(%p)", 800, 1000)])
    c = rt.summarize([d0])["collectives"]
    # in flight 0-800 (union of 0-800 and 10-600); fusion.1 hides 20-500
    assert c["total_s"] == pytest.approx(800e-6)
    assert c["exposed_s"] == pytest.approx(320e-6)
    assert c["by_kind"] == {"async-collective": pytest.approx(800e-6)}


def test_idle_gaps_are_named_after_the_host_span():
    gaps = rt.summarize(two_device_trace())["idle_gaps"]
    assert ["plan", pytest.approx(100e-6)] in gaps        # 600-700
    assert ["none", pytest.approx(100e-6)] in gaps        # 0-100: no span
    assert not any(name.startswith("$") for name, _ in gaps)
    b = rt.breakdown(rt.summarize(two_device_trace()))
    assert set(b) == {"device_ops", "idle_gaps"}
    assert len(b["device_ops"]) <= 10 and len(b["idle_gaps"]) <= 10


def test_no_tpu_plane_is_an_error_not_none():
    host_only = [p for p in two_device_trace() if p["name"].startswith("/host")]
    with pytest.raises(rt.TraceError, match="no TPU device plane"):
        rt.summarize(host_only)
    assert rt.host_only_summary(host_only)["host_only"] is True


def test_interval_arithmetic():
    assert rt.union([(5, 7), (0, 2), (1, 3)]) == [(0, 3), (5, 7)]
    assert rt.subtract([(0, 10)], [(2, 3), (5, 12)]) == [(0, 2), (3, 5)]
    assert rt.subtract([(0, 4), (6, 9)], [(3, 7)]) == [(0, 3), (7, 9)]
    assert rt.op_key("%fusion.123") == rt.op_key("fusion.7") == "fusion"


def test_no_tensorflow_import():
    import sys
    rt.summarize(two_device_trace())
    assert "tensorflow" not in sys.modules
    with open(rt.__file__) as f:
        assert "import tensorflow" not in f.read()


@pytest.mark.skipif(not os.path.exists(FIXTURE), reason="no recorded trace")
def test_recorded_v5e_trace():
    with open(FIXTURE.replace(".xplane.pb", ".expected.json")) as f:
        exp = json.load(f)
    s = rt.summarize(rt.load(FIXTURE))
    assert s["devices"] == 1
    # both programs ran three times on the device; the device's clock leads
    # the host's by ~1 ms here, so the host's window may clip the first run
    for prog in exp["programs"]:
        assert exp["runs_each"] - 1 <= s["programs"][prog]["runs"] <= exp["runs_each"]
        assert s["programs"][prog]["s"] > 0
    # the window the host wrote, and the host's own clock for it
    assert s["window_s"] == pytest.approx(exp["host_wall_s"], rel=0.05)
    assert 0 < s["busy_s"] < s["window_s"]
    # three 4 ms sleeps under "plan" are the three longest idle gaps
    top = s["idle_gaps"][:3]
    assert [n for n, _ in top] == [exp["sleep_span"]] * 3
    assert all(g >= 0.004 for _, g in top)
    # device ops were read, self times add up to the busy time
    assert sum(v for _, v in s["ops"]) == pytest.approx(s["busy_s"], rel=0.02)
    # and the reducer says today what it said on the chip
    assert s["busy_s"] == pytest.approx(exp["summary"]["busy_s"], rel=1e-6)
    assert dict(s["ops"])["fusion"] == pytest.approx(
        dict(exp["summary"]["ops"])["fusion"], rel=1e-6)
    # every op lies inside a run of one of the two programs, each has its own
    by = s["ops_by_program"]
    assert set(by) == set(exp["programs"])
    assert "multiply_reduce_fusion" in by["jit_reduce_step"]
    assert "multiply_reduce_fusion" not in by["jit_mm_step"]
    assert sum(v[0] for d in by.values() for v in d.values()) == \
        pytest.approx(s["busy_s"], rel=0.02)
