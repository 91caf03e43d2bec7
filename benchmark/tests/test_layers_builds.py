"""The readers of the program's build ledger (PR 57): each on a made-up
ledger, cold and warm (the two cache-proof ones read the same), None — not
an exception — on a parent commit that has no ledger, left out by
``read_layers`` there, and every new entry of ``BENCHMARK.json`` with its
reader file and its cells."""
import importlib
import json
import os

import pytest

from benchmark import common
from benchmark.layers import (_builds, engine_build_s, outside_build_s,
                              program_backend_s, program_rebuilds,
                              program_trace_lower_s)

trace = pytest.importorskip("deepspeed_tpu.profiling.trace")
#: name -> (unit, moves, cells, whether the compile cache moves it)
NEW = {"program_trace_lower_s": ("s", "setup_s", 6, False),
       "program_backend_s": ("s", "setup_s", 6, True),
       "engine_build_s": ("s", "setup_s", 5, False),
       "outside_build_s": ("s", "setup_s", 6, True),
       "program_rebuilds": ("programs", "tpot_p90_ms", 5, False)}
READERS = (program_trace_lower_s, program_backend_s, engine_build_s,
           outside_build_s, program_rebuilds)


def rec(kind, **kw):
    return {"kind": kind, "key": None, "module": "jit_step_prefill",
            "variant": 1, "seconds": 0.0, "trace_s": 0.0, "lower_s": 0.0,
            "backend_s": 0.0, "backend_events": 1, "cache_hit": False, **kw}


def made_up(warm: bool) -> list[dict]:
    """One start of one engine: ``warm`` reads from the cache what the cold
    one compiles — an eighth of the backend's seconds, the Python as it
    was."""
    b = 0.125 if warm else 1.0
    return [
        rec("phase", key="model", build=3, seconds=1.5),
        rec("phase", key="weights", build=3, seconds=4.0 + 16.0 * b,
            outside_s=1.0 + 16.0 * b, outside_backend_s=16.0 * b,
            outside_builds=1),
        rec("phase", key="weights", build=2, seconds=99.0),
        rec("outside", variant=None, seconds=1.0 + 16.0 * b,
            backend_s=16.0 * b, phase="weights", cache_hit=warm,
            site="deepspeed_tpu/inference/weights.py:40"),
        rec("outside", variant=None, seconds=0.5, backend_events=0),
        rec("program", key=("train_step", "gspmd"), variant=0,
            seconds=1.0 + 8.0 * b, trace_s=0.75, lower_s=0.25,
            backend_s=8.0 * b, cache_hit=warm),
        rec("program", key=(512, 1), seconds=2.5 + 8.0 * b, trace_s=1.5,
            lower_s=0.75, backend_s=8.0 * b, cache_hit=warm),
        rec("program", key=("win", 8), seconds=1.0, trace_s=0.5,
            lower_s=0.25, backend_events=0, cache_hit=None),
        rec("program", key=(512, 1), variant=2, seconds=17.0,
            trace_s=1.0, backend_s=16.0)]


@pytest.fixture
def ledger(monkeypatch):
    def set_to(warm):
        monkeypatch.setattr(trace, "build_records", lambda: made_up(warm))
    monkeypatch.setattr(_builds, "_said", False)
    set_to(False)
    return set_to


def read_all() -> dict:
    return {r.__name__.rsplit(".", 1)[1]: r.read({}) for r in READERS}


def test_each_reader_cold_and_warm(ledger, capsys):
    cold = read_all()
    assert cold == {"program_trace_lower_s": 4.0, "program_backend_s": 16.0,
                    "engine_build_s": 5.5,       # the newest build's alone
                    "outside_build_s": 17.0, "program_rebuilds": 1.0}
    ledger(True)
    warm = read_all()
    assert warm == {"program_trace_lower_s": 4.0, "program_backend_s": 2.0,
                    "engine_build_s": 5.5, "outside_build_s": 3.0,
                    "program_rebuilds": 1.0}
    # what a metric's definition says of the cache is what the two show
    for name, (_, _, _, moved) in NEW.items():
        assert (cold[name] != warm[name]) == moved
    # the cache's state is said ONCE a process, by the first reader
    said = [ln.split("] ", 1)[1]
            for ln in capsys.readouterr().out.splitlines()]
    assert said[0] == "builds: 0 of 2 first calls from the cache"
    # ... followed by the program's own leaving lines: the sums, the build
    # outside the table with its phase and site, the rebuild
    assert said[1].startswith("builds: 2 programs first-called in ")
    assert said[2].endswith(
        " phase=weights site=deepspeed_tpu/inference/weights.py:40")
    assert " variant=2 " in said[3] and len(said) == 4


def test_a_warm_start_says_so(ledger, capsys):
    ledger(True)
    program_backend_s.read({})
    assert "builds: 2 of 2 first calls from the cache" \
        in capsys.readouterr().out


def test_an_empty_ledger_reads_no_build_and_no_rebuild(monkeypatch):
    monkeypatch.setattr(trace, "build_records", lambda: [])
    assert read_all() == {"program_trace_lower_s": None,
                          "program_backend_s": None, "engine_build_s": None,
                          "outside_build_s": 0.0, "program_rebuilds": 0.0}


@pytest.mark.parametrize("reader", READERS,
                         ids=[r.__name__.rsplit(".", 1)[1] for r in READERS])
def test_a_parent_without_the_ledger_reads_none(reader, monkeypatch):
    monkeypatch.delattr(trace, "build_summary")
    assert _builds.summary() is None
    assert reader.read({}) is None


def test_a_ledger_that_raises_reads_none(monkeypatch):
    def broken():
        raise RuntimeError("half a ledger")

    monkeypatch.setattr(trace, "build_records", broken)
    assert all(r.read({}) is None for r in READERS)


def test_read_layers_leaves_them_out_on_a_parent(ledger, monkeypatch):
    entry = {"metrics": {"per_layer": [{"name": n, "unit": u}
                                       for n, (u, _, _, _) in NEW.items()]}}
    assert common.read_layers(entry, {}) == {
        "program_trace_lower_s": {"value": 4.0, "unit": "s"},
        "program_backend_s": {"value": 16.0, "unit": "s"},
        "engine_build_s": {"value": 5.5, "unit": "s"},
        "outside_build_s": {"value": 17.0, "unit": "s"},
        "program_rebuilds": {"value": 1.0, "unit": "programs"}}
    monkeypatch.delattr(trace, "build_summary")
    assert common.read_layers(entry, {}) == {}


def test_every_new_entry_has_its_reader_and_its_cells():
    with open(os.path.join(common.ROOT, "BENCHMARK.json"),
              encoding="utf-8") as f:
        manifest = json.load(f)
    cells = [w["name"] for w in manifest["workloads"]]
    tail = manifest["per_layer"][-len(NEW):]
    assert [m["name"] for m in tail] == list(NEW)   # at the END of the list
    for m in tail:
        unit, moves, n, moved = NEW[m["name"]]
        assert set(m) == {"name", "unit", "better", "source", "layer",
                          "moves", "workloads"}
        assert (m["unit"], m["moves"], m["better"]) == (unit, moves, "lower")
        assert (m["source"], m["layer"]) == ("program_counter",
                                             "engine programs")
        assert len(m["workloads"]) == n and set(m["workloads"]) <= set(cells)
        assert ("mistral7b-zero3-sft" in m["workloads"]) == (n == 6)
        reader = importlib.import_module(f"benchmark.layers.{m['name']}")
        assert callable(reader.read)
        # its definition says whether the compile cache moves it
        assert ("The compile cache MOVES it" in reader.__doc__) == moved
        assert moved or "compile cache do" in reader.__doc__
