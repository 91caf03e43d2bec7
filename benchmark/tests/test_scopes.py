"""layers/_scopes.py: the join of a trace with the program's scope maps — on
hand-made planes (where every number can be worked out on paper) and on the
small scoped trace recorded on a v5e (``record_scope_fixture.py``), held to
``reduce_trace.summarize`` as it is in a run."""
import json
import os

import pytest

from benchmark import reduce_trace as rt
from benchmark.layers import _scopes
from benchmark.tests.test_reduce_trace import plane
from deepspeed_tpu.profiling import trace as ptrace

HERE = os.path.dirname(os.path.abspath(__file__))
FIXTURE = os.path.join(HERE, "data", "tpu_v5e_scopes.xplane.pb")


@pytest.fixture(autouse=True)
def fresh_tables():
    _scopes._tables.clear()
    yield
    _scopes._tables.clear()


@pytest.fixture(scope="module")
def recorded():
    with open(FIXTURE.replace(".xplane.pb", ".expected.json")) as f:
        expected = json.load(f)
    planes = rt.load(FIXTURE)
    return planes, expected, rt.summarize(planes)


def ctx_of(summary, maps, path=FIXTURE):
    return {"trace": summary, "scope_maps": maps, "trace_dir": path}


# ---- hand-made planes ---------------------------------------------------

def hand_made():
    """One device. ``jit_run`` twice: a while (100-500) holding a weight
    slice (100-250) and a kernel (250-500), then a matmul fusion (500-600);
    its second run (700-800) is one fusion whose name the two compiled
    programs of ``jit_run`` give different scopes. An op outside any run."""
    d0 = plane("/device:TPU:0",
               XLA_Modules=[("jit_run(11)", 100, 600), ("jit_run(22)", 700, 800)],
               XLA_Ops=[("%while.1 = ...", 100, 500),
                        ("%dynamic-slice_bitcast_fusion.4 = ...", 100, 250),
                        ("%paged_attn_decode.2 = ...", 250, 500),
                        ("%fusion.7 = ...", 500, 600),
                        ("%fusion.9 = ...", 700, 800),
                        ("%copy.1 = ...", 850, 900)])
    host = plane("/host:CPU", python=[("bench_window", 0, 1000)])
    a = {"while.1": "jit(run)/while",
         "dynamic-slice_bitcast_fusion.4": "jit(run)/while/body/weight_walk/dynamic_slice",
         "paged_attn_decode.2": "jit(run)/while/body/attn_core/paged_attn_decode/pallas_call",
         "fusion.7": "jit(run)/head/dot_general",
         "fusion.9": "jit(run)/ffn/dot_general"}
    b = dict(a, **{"fusion.9": "jit(run)/norm/mul"})
    maps = {"jit_run": {"ops": ptrace.merge_scope_maps([a, b]),
                        "programs": 2, "hlo_bytes": 1}}
    return [host, d0], maps


def test_join_on_paper():
    planes, maps = hand_made()
    tab = _scopes.join(planes, maps, ptrace.scope_of)
    us = {k: round(v * 1e6, 6) for k, v in tab["jit_run"].items()}
    assert us == {("unscoped", "fwd"): 0.0,         # the while: all children
                  ("weight_walk", "fwd"): 150.0,
                  ("attn_core", "fwd"): 250.0,
                  ("head", "fwd"): 100.0,
                  ("ambiguous", "fwd"): 100.0}
    assert round(tab["none"][("unscoped", "fwd")] * 1e6, 6) == 50.0
    assert _scopes.check(tab, rt.summarize(planes)["ops_by_program"]) == []


def test_shares_partition_a_programs_time():
    planes, maps = hand_made()
    ctx = ctx_of(rt.summarize(planes), maps, path="hand-made")
    _scopes._tables["hand-made"] = _scopes.join(planes, maps, ptrace.scope_of)
    progs = _scopes.DECODE_PROGRAMS
    assert _scopes.share(ctx, progs, ("weight_walk",)) == pytest.approx(25.0)
    assert _scopes.share(ctx, progs, ("attn_core", "kv_stage")) \
        == pytest.approx(100 * 250 / 600)
    assert _scopes.share(ctx, progs, ("ffn", "attn_qkv", "attn_out", "head")) \
        == pytest.approx(100 * 100 / 600)
    # an ambiguous instruction is never given to either scope
    assert _scopes.share(ctx, progs, _scopes.REMAINDER) \
        == pytest.approx(100 * 100 / 600)
    assert _scopes.share(ctx, progs) == pytest.approx(100.0)
    assert _scopes.share(ctx, ("jit_train_step",), ("optimizer",)) is None
    assert _scopes.share(ctx, progs, direction="recompute") == 0.0


def test_a_join_that_loses_time_reports_nothing(capsys):
    planes, maps = hand_made()
    summary = rt.summarize(planes)
    bent = json.loads(json.dumps(summary))
    bent["ops_by_program"]["jit_run"]["fusion"][0] *= 1.05
    tab = _scopes.join(planes, maps, ptrace.scope_of)
    assert _scopes.check(tab, summary["ops_by_program"]) == []
    bad = _scopes.check(tab, bent["ops_by_program"])
    assert len(bad) == 1 and bad[0].startswith("jit_run:")


def test_rehearsal_and_a_program_without_maps_read_nothing(monkeypatch, capsys):
    assert _scopes.share({"trace": {"host_only": True}},
                         _scopes.DECODE_PROGRAMS) is None
    assert capsys.readouterr().out == ""
    # a parent commit: profiling.trace has no program_scope_maps
    monkeypatch.delattr(ptrace, "program_scope_maps")
    ctx = {"trace": {"programs": {}, "ops_by_program": {}},
           "trace_dir": FIXTURE}
    assert _scopes.share(ctx, _scopes.DECODE_PROGRAMS) is None
    assert "publishes no scope maps" in capsys.readouterr().out


# ---- the recorded trace -------------------------------------------------

def test_recorded_join_matches_what_the_chip_run_wrote(recorded):
    planes, expected, summary = recorded
    assert expected["check"] == []
    tab = _scopes.join(planes, expected["maps"], ptrace.scope_of)
    want = {p: {(s, d): v for s, d, v in rows}
            for p, rows in expected["table"].items()}
    assert tab.keys() == want.keys()
    for prog in want:
        assert tab[prog] == pytest.approx(want[prog], rel=1e-9), prog


def test_recorded_join_agrees_with_summarize_within_a_per_cent(recorded):
    planes, expected, summary = recorded
    tab = _scopes.join(planes, expected["maps"], ptrace.scope_of)
    assert _scopes.check(tab, summary["ops_by_program"]) == []
    for prog, rows in tab.items():
        old = sum(s for s, _ in summary["ops_by_program"][prog].values())
        assert sum(rows.values()) == pytest.approx(old, rel=1e-9)


def test_recorded_programs_show_their_scopes_and_directions(recorded):
    planes, expected, summary = recorded
    ctx = ctx_of(summary, expected["maps"])
    dec, trn = ("jit_fx_decode",), ("jit_fx_train",)
    assert _scopes.share(ctx, dec) == pytest.approx(100.0)
    for scope in ("weight_walk", "attn_core", "ffn", "head"):
        assert _scopes.share(ctx, dec, (scope,)) > 0, scope
    # (the compiler fuses the tiny loss and update into the backward
    # matmuls' fusions, which keep the scope of their root)
    for scope in ("layer/attn", "layer/ffn"):
        assert _scopes.share(ctx, trn, (scope,)) > 0, scope
    for direction in ("fwd", "bwd", "recompute"):
        assert _scopes.share(ctx, trn, direction=direction) > 0, direction
    assert sum(_scopes.share(ctx, trn, direction=d)
               for d in ("fwd", "bwd", "recompute")) == pytest.approx(100.0)
    # two compiled programs share the name jit_fx_decode
    assert expected["maps"]["jit_fx_decode"]["programs"] == 2
    assert len([m for m in expected["modules_line"]
                if m.startswith("jit_fx_decode(")]) == 2


def test_recorded_kernel_keeps_its_name_on_the_op_line(recorded):
    """What ``name=`` on a ``pallas_call`` buys: the "XLA Ops" line prints
    the kernel's own name (PR 22's traces said ``closed_call``), once a
    layer and run (the window's edges cut whole runs off), and the map puts
    it under ``attn_core``."""
    planes, expected, summary = recorded
    kernel = expected["kernel"]
    secs, calls = summary["ops_by_program"]["jit_fx_decode"][kernel]
    assert secs > 0 and calls > 0 and calls % expected["layers"] == 0
    assert "closed_call" not in summary["ops_by_program"]["jit_fx_decode"]
    ops = expected["maps"]["jit_fx_decode"]["ops"]
    named = [k for k in ops if rt.op_key(k) == kernel]
    assert named and all(ptrace.scope_of(ops[k]) == ("attn_core", "fwd")
                         for k in named)
