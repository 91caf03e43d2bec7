"""The build ledger (PR 57, ``profiling/trace.py``): what every registered
program cost where it was built ahead or first called, every rebuild with
the argument that differed, every build outside the table with the phase
and the line it came from, an engine's constructor by phase — and the
lines a replica worker says of it."""
import collections
import contextlib
import inspect
import logging
import re
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deepspeed_tpu.inference.engine_v2 import InferenceEngineV2
from deepspeed_tpu.models import build_model
from deepspeed_tpu.profiling import trace as ptrace
from deepspeed_tpu.utils.logging import logger

ENGINE = {"block_size": 16, "num_blocks": 128, "max_seqs": 4, "chunk": 16,
          "max_seq_len": 256, "decode_window": 4, "dtype": jnp.float32}


def since(n0: int) -> list[dict]:
    """The ledger is the process's: a test reads what IT booked."""
    return [r for r in ptrace.build_records() if r["n"] >= n0]


def committed(shape=(8, 8), dtype=jnp.float32):
    return jax.device_put(jnp.ones(shape, dtype), jax.devices()[0])


def program(key=("t", 1), cause=("warm", None)):
    def step(x, y):
        return jnp.sin(x) @ y

    return ptrace.register_program(jax.jit(step), key=key, cause=cause)


@contextlib.contextmanager
def logged(level=logging.INFO):
    got = []
    handler = logging.Handler(level)
    handler.emit = lambda r: got.append((r.levelno, r.getMessage()))
    was = logger.level
    logger.addHandler(handler)
    logger.setLevel(min(level, was))
    try:
        yield got
    finally:
        logger.removeHandler(handler)
        logger.setLevel(was)


def test_first_call_leaves_one_record_with_its_key():
    fn, y = program(key=(512, 9), cause=("dispatch", 7)), np.ones((8, 8), "f")
    x = committed()
    jnp.sin(x) @ y                      # (the eager ops' own builds first)
    n0, t0 = ptrace.build_count(), time.perf_counter()
    fn(x, y)
    t1 = time.perf_counter()
    (rec,) = since(n0)
    assert rec["kind"] == "program" and rec["key"] == (512, 9)
    assert rec["module"] == fn.module_name == "jit_step"
    assert rec["variant"] == 1 and rec["cause"] == ("dispatch", 7)
    assert rec["backend_events"] == 1 and rec["backend_ord"] > 0
    assert rec["cache_hit"] in (False, True)
    assert 0 < rec["trace_s"] and 0 < rec["lower_s"] and 0 < rec["backend_s"]
    assert rec["trace_s"] + rec["lower_s"] + rec["backend_s"] \
        <= rec["seconds"] <= t1 - t0
    assert t0 <= rec["t0"] <= t1        # the spans' clock
    assert "differs" not in rec


def test_second_call_with_equal_arguments_leaves_none():
    fn, x, y = program(), committed(), np.ones((8, 8), "f")
    fn(x, y)
    n0 = ptrace.build_count()
    for _ in range(3):
        fn(x, y)
    assert ptrace.build_count() == n0


@pytest.mark.parametrize("field, make, says", [
    ("shape", lambda: committed((4, 8)),
     "args[0]: shape (8, 8) at the first call, (4, 8) now"),
    ("dtype", lambda: committed(dtype=jnp.bfloat16),
     "args[0]: dtype float32 at the first call, bfloat16 now"),
    ("committed", lambda: jnp.ones((8, 8)),
     "args[0]: committed True at the first call, False now")])
def test_a_rebuild_names_the_argument_and_the_field(field, make, says):
    fn, y = program(key=("t", field)), np.ones((8, 8), "f")
    fn(committed(), y)
    other = make()
    jnp.sin(other) @ y
    n0 = ptrace.build_count()
    with logged(logging.WARNING) as got:
        fn(other, y)
    (rec,) = since(n0)
    assert rec["kind"] == "program" and rec["key"] == ("t", field)
    assert rec["variant"] == 2 and rec["differs"] == says
    assert rec["backend_events"] == 1 and rec["seconds"] > 0
    assert rec["cause"] == ("warm", None)
    (line,) = [m for lv, m in got if lv == logging.WARNING]
    assert line.startswith(f"build: REBUILT key=('t', '{field}') "
                           f"module=jit_step variant=2 ")
    assert line.endswith("differs: " + says)
    # ... and the third form of it is variant 3, the second's again none
    fn(other, y)
    assert ptrace.build_count() == n0 + 1
    assert ptrace.build_summary(since(n0))["rebuilt"] == 1


def test_a_donated_argument_still_says_what_it_was():
    def step(pool, x):
        return pool + x

    fn = ptrace.register_program(jax.jit(step, donate_argnums=(0,)),
                                 key="donates")
    pool = fn(committed(), 1.0)
    n0 = ptrace.build_count()
    loose = jnp.ones((8, 8))            # uncommitted, and deleted by the call
    fn(loose, 1.0)
    assert loose.is_deleted()
    (rec,) = since(n0)
    assert rec["differs"] == ("args[0]: committed True at the first call, "
                              "False now")
    fn(pool, 1.0)                       # (the warmed form still runs)
    assert ptrace.build_count() == n0 + 1


def here() -> str:
    """``tests/test_build_ledger.py:<the caller's line>``, as a ``site``."""
    return f"tests/test_build_ledger.py:{inspect.stack()[1].lineno}"


def test_a_jit_outside_any_program_says_where_it_came_from():
    def lonely(x):
        return x * 3

    x = committed()
    n0 = ptrace.build_count()
    jax.jit(lonely)(x); site = here()   # noqa: E702 — ONE line: the site
    (rec,) = since(n0)
    assert rec["kind"] == "outside" and rec["module"] == "jit_lonely"
    assert rec["key"] is None and rec["variant"] is None
    assert rec["backend_events"] == 1
    assert rec["site"] == site and rec["phase"] is None
    assert rec["seconds"] == pytest.approx(
        rec["trace_s"] + rec["lower_s"] + rec["backend_s"])
    s = ptrace.build_summary(since(n0))
    assert (s["outside"], s["programs"], s["rebuilt"]) == (1, 0, 0)
    assert s["outside_s"] == pytest.approx(rec["seconds"])
    assert ptrace.build_line(rec).endswith(f" phase=None site={site}")


def test_a_build_outside_the_table_is_booked_to_the_phase_it_fell_in():
    def lonelier(x):
        return x * 5

    x = committed()
    n0 = ptrace.build_count()
    with logged() as got, ptrace.engine_build("Toy") as build:
        build.phase("weights")
        jax.jit(lonelier)(x); site = here()   # noqa: E702
        build.phase("pools")
    weights, pools = [r for r in since(n0) if r["kind"] == "phase"]
    (rec,) = [r for r in since(n0) if r["kind"] == "outside"]
    assert (rec["phase"], rec["site"]) == ("weights", site)
    assert weights["outside_builds"] == 1
    assert weights["outside_s"] == pytest.approx(rec["seconds"])
    assert weights["outside_backend_s"] == pytest.approx(rec["backend_s"])
    assert 0 < weights["outside_s"] <= weights["seconds"]
    assert (pools["outside_builds"], pools["outside_s"]) == (0, 0)
    s = ptrace.build_summary(since(n0))
    assert s["phase_outside_backend_s"] == pytest.approx(rec["backend_s"])
    (line,) = [m for _, m in got if m.startswith("build: ")]
    assert re.fullmatch(
        r"build: weights \d+\.\d\d s \(1 built outside the table, "
        r"\d+\.\d\d s\); pools \d+\.\d\d s; total \d+\.\d\d s", line)
    # the phase is gone with its block: the next one outside has none
    n1 = ptrace.build_count()
    jax.jit(lambda x: x * 7)(x)
    assert [r["phase"] for r in since(n1)] == [None]


@pytest.mark.parametrize("calls_after", [0, 1])
def test_a_build_ahead_of_the_first_call_is_the_programs_own(calls_after):
    fn, y = program(key=("ahead", calls_after)), np.ones((8, 8), "f")
    x = committed()
    jnp.sin(x) @ y
    n0 = ptrace.build_count()
    fn.lower(x, y).compile()
    (rec,) = since(n0)
    assert rec["kind"] == "program" and rec["key"] == ("ahead", calls_after)
    assert rec["module"] == "jit_step" and rec["variant"] == 0
    assert rec["cause"] == ("ahead", None) and rec["backend_events"] == 1
    assert 0 < rec["trace_s"] and 0 < rec["lower_s"] and 0 < rec["backend_s"]
    assert "site" not in rec
    if calls_after:
        # ... and the first call is a record of its own, which the sums of
        # the program's builds take together with it
        fn(x, y)
        ahead, first = since(n0)
        assert ahead is rec and first["variant"] == 1
        assert first["key"] == ("ahead", 1) and "differs" not in first
    s = ptrace.build_summary(since(n0))
    assert (s["ahead"], s["programs"], s["outside"], s["rebuilt"]) == \
        (1, calls_after, 0, 0)
    assert s["trace_s"] >= rec["trace_s"] and s["backend_builds"] >= 1
    # another function of the program's NAME is none of the program's
    n1 = ptrace.build_count()

    def step(x):
        return x - 1

    jax.jit(step).lower(x).compile()
    assert [(r["kind"], r["module"]) for r in since(n1)] == \
        [("outside", "jit_step")]


def test_a_jit_traced_inside_a_program_is_in_its_seconds_once():
    inner = jax.jit(lambda x: jnp.tanh(x) * 2)

    def outer(x):
        return inner(x) + inner(x + 1)

    fn = ptrace.register_program(jax.jit(outer), key="nest")
    x = committed()
    n0 = ptrace.build_count()
    fn(x)
    (rec,) = since(n0)
    assert rec["key"] == "nest" and rec["backend_events"] == 1
    assert rec["trace_s"] + rec["lower_s"] + rec["backend_s"] \
        <= rec["seconds"]


def test_a_rule_traced_inside_a_lowering_does_not_split_the_build():
    def noisy(r):
        return jax.random.normal(jax.random.fold_in(r, 1), (4,))

    r = jax.device_put(jax.random.PRNGKey(0), jax.devices()[0])
    n0 = ptrace.build_count()
    jax.jit(noisy)(r)
    (rec,) = since(n0)                  # (threefry's rules trace as it lowers)
    assert rec["module"] == "jit_noisy" and rec["backend_events"] == 1
    assert 0 < rec["trace_s"] and 0 < rec["lower_s"]
    # ... and a trace with no lowering after it (``eval_shape``) is no build
    jax.eval_shape(lambda x: jnp.cos(x) * 11, r)
    assert ptrace.build_count() == n0 + 1


def test_reading_the_compiled_text_books_nothing():
    fn, x, y = program(key="read"), committed(), np.ones((8, 8), "f")
    fn(x, y)
    n0 = ptrace.build_count()
    assert "HloModule" in fn.compiled_text()
    maps = ptrace.program_scope_maps({"jit_step"})
    assert maps["jit_step"]["programs"] >= 1
    assert ptrace.pool_sized_copies(fn, [(8, 8)]) == []
    assert ptrace.build_count() == n0
    fn(x, y)                            # and no call reads it as a rebuild
    assert ptrace.build_count() == n0


def test_the_ring_drops_its_oldest(monkeypatch):
    monkeypatch.setattr(ptrace, "_BUILDS", collections.deque(maxlen=4))
    n0 = ptrace.build_count()
    x = committed()
    for i in range(6):
        ptrace.register_program(jax.jit(lambda x: x + 1), key=i)(x)
    assert ptrace.build_count() == n0 + 6
    assert [r["key"] for r in ptrace.build_records()] == [2, 3, 4, 5]
    assert [r["n"] for r in ptrace.build_records()] == \
        list(range(n0 + 2, n0 + 6))


def test_a_first_call_runs_inside_a_program_build_span(monkeypatch):
    from deepspeed_tpu import telemetry

    telem = telemetry.Telemetry(enabled=True)
    monkeypatch.setattr(telemetry, "get_telemetry", lambda: telem)
    fn, x, y = program(key=(16, 2)), committed(), np.ones((8, 8), "f")
    fn(x, y)
    fn(x, y)
    (span,) = [e for e in telem.tracer.events()
               if e["name"] == "program_build"]
    assert span["args"] == {"key": "(16, 2)", "module": "jit_step"}
    (rec,) = [r for r in ptrace.build_records() if r["key"] == (16, 2)]
    assert span["t0"] <= rec["t0"] and rec["seconds"] <= span["dur"]
    # telemetry off: the shared null span, and the record all the same
    telem.tracer.enabled = telem.enabled = False
    n0 = ptrace.build_count()
    program(key=(16, 3))(x, y)
    assert since(n0)[0]["key"] == (16, 3)
    assert len(telem.tracer.events()) == 1


@pytest.mark.parametrize("first, now, says", [
    (((jax.ShapeDtypeStruct((2,), jnp.int32),), {}),
     ((jax.ShapeDtypeStruct((2,), jnp.int32),), {}),
     "abstract arguments equal"),
    (((jax.ShapeDtypeStruct((2,), jnp.int32),), {}),
     ((jax.ShapeDtypeStruct((2,), jnp.int32),) * 2, {}),
     "tree structure: "),
    (((), {"rng": jax.ShapeDtypeStruct((), jnp.int32, weak_type=True)}),
     ((), {"rng": jax.ShapeDtypeStruct((), jnp.int32)}),
     "kwargs['rng']: weak_type True at the first call, False now")])
def test_first_difference(first, now, says):
    assert ptrace.first_difference(first, now).startswith(says)


def test_first_difference_tells_a_sharding_from_a_placement():
    from jax.sharding import NamedSharding, PartitionSpec as P

    mesh = jax.make_mesh((2,), ("x",))
    a = jax.ShapeDtypeStruct((4,), jnp.float32,
                             sharding=NamedSharding(mesh, P("x")))
    b = jax.ShapeDtypeStruct((4,), jnp.float32,
                             sharding=NamedSharding(mesh, P()))
    got = ptrace.first_difference(((a, a), {}), ((a, b), {}))
    assert got.startswith("args[1]: sharding ") and "at the first call" in got


def test_build_summary_of_a_made_up_ledger():
    def rec(kind, **kw):
        return {"kind": kind, "key": None, "module": "jit_step_prefill",
                "variant": 1, "seconds": 0.0, "trace_s": 0.0, "lower_s": 0.0,
                "backend_s": 0.0, "backend_events": 1, "cache_hit": False,
                **kw}

    def phase(seconds, outside_s=0, outside_backend_s=0, outside_builds=0):
        return {"seconds": seconds, "outside_s": outside_s,
                "outside_backend_s": outside_backend_s,
                "outside_builds": outside_builds}

    recs = [rec("phase", key="weights", build=0, seconds=9.0),
            rec("phase", key="model", build=1, seconds=1.0),
            rec("phase", key="pools", build=1, seconds=2.0, outside_s=1.5,
                outside_backend_s=1.0, outside_builds=2),
            rec("phase", key="pools", build=1, seconds=0.5, outside_s=0.25,
                outside_backend_s=0.25, outside_builds=1),
            rec("program", seconds=4.0, trace_s=2.0, lower_s=1.0,
                backend_s=0.5, cache_hit=True),
            rec("program", seconds=2.0, trace_s=0.5, lower_s=0.5,
                backend_s=1.0),
            rec("program", variant=0, seconds=3.0, trace_s=1.0, lower_s=0.5,
                backend_s=1.5, cache_hit=True),
            rec("program", seconds=0.5, backend_events=0, cache_hit=None),
            rec("program", variant=3, seconds=7.0),
            rec("outside", variant=None, seconds=0.25, phase="pools",
                site="deepspeed_tpu/x.py:1"),
            rec("outside", variant=None, seconds=5.0, backend_events=0)]
    s = ptrace.build_summary(recs)
    assert s == {"programs": 3, "first_call_s": 6.5, "ahead": 1,
                 "trace_s": 3.5, "lower_s": 2.0, "backend_s": 3.0,
                 "backend_builds": 3, "cache_hits": 2,
                 "rebuilt": 1, "outside": 1, "outside_s": 0.25,
                 "backend_events": 9,
                 "phases": {"model": phase(1.0),
                            "pools": phase(2.5, 1.75, 1.25, 3)},
                 "phase_s": 3.5, "phase_outside_backend_s": 1.25}
    assert ptrace.phases_line(s["phases"]) == (
        "model 1.00 s; pools 2.50 s (3 built outside the table, 1.75 s)")
    assert ptrace.build_summary([])["phases"] == {}
    (head, rebuilt, outside) = ptrace.builds_lines(recs)
    assert head == (
        "builds: 3 programs first-called in 6.50 s (trace 3.50, lower 2.00, "
        "backend 3.00; 2 from the cache); 1 rebuilt; 1 outside the table "
        "(0.25 s); 9 backend events in all")
    assert " variant=3 " in rebuilt and " variant=None " in outside


# ---- the engine ------------------------------------------------------------

def serve(eng, prompts, new=6):
    for uid, p in enumerate(prompts):
        eng.put(uid, p, max_new_tokens=new)
    for _ in range(400):
        eng.step()
        if all(s.done for s in eng.state.seqs.values()) \
                and not eng._inflight:
            return
    raise AssertionError("the engine did not finish")


def test_an_engine_books_its_constructor_and_every_program_it_ran():
    model, rng = build_model("tiny-llama", dtype=jnp.float32), \
        jax.random.PRNGKey(0)
    n0, t0 = ptrace.build_count(), time.perf_counter()
    with logged() as got:
        eng = InferenceEngineV2(model, config=ENGINE, rng=rng)
    wall = time.perf_counter() - t0
    phases = [r for r in since(n0) if r["kind"] == "phase"]
    assert {r["key"] for r in phases} == {"rest", "weights", "stack",
                                          "pools", "probes"}
    assert len({r["build"] for r in phases}) == 1
    assert all(r["module"] == "InferenceEngineV2" for r in phases)
    # a phase ends where the next begins: they partition the constructor
    for a, b in zip(phases, phases[1:]):
        assert b["t0"] == pytest.approx(a["t0"] + a["seconds"], abs=1e-4)
    total = sum(r["seconds"] for r in phases)
    assert total <= wall and total == pytest.approx(wall, rel=0.05)
    s = ptrace.build_summary(since(n0))
    assert s["phase_s"] == pytest.approx(total) and s["programs"] == 0
    (line,) = [m for _, m in got if m.startswith("build: ")]
    assert re.fullmatch(
        r"build: (\w+ \d+\.\d\d s( \(\d+ built outside the table, "
        r"\d+\.\d\d s\))?; )+total \d+\.\d\d s", line)
    # what the constructor jitted outside the table fell in its phases,
    # each with the line of this checkout that called for it
    outside = [r for r in since(n0) if r["kind"] == "outside"]
    assert outside and all(r["phase"] in s["phases"] for r in outside)
    assert all(re.fullmatch(r"deepspeed_tpu/[\w/]+\.py:\d+", r["site"])
               for r in outside)
    assert sum(r["seconds"] for r in outside) == pytest.approx(
        sum(p["outside_s"] for p in s["phases"].values()))
    assert float(line.rsplit("total ", 1)[1][:-2]) == pytest.approx(
        total, abs=0.02)

    rng = np.random.default_rng(0)
    n1 = ptrace.build_count()
    serve(eng, [rng.integers(0, eng.mcfg.vocab_size, n).tolist()
                for n in (70, 5, 9)])
    progs = [r for r in since(n1) if r["kind"] == "program"]
    ran = {k for k, p in eng._programs.items() if p.avals is not None}
    assert ran and sorted(map(str, ran)) == sorted(
        str(r["key"]) for r in progs)
    assert all(r["variant"] == 1 and r["backend_events"] >= 1
               for r in progs)
    assert {r["module"] for r in progs} <= {
        "jit_step_prefill", "jit_step_decode", "jit_run"}
    # each was asked for by the dispatch of an entry, under its number
    assert all(r["cause"][0] == "dispatch"
               and 0 <= r["cause"][1] < eng._entry_seq for r in progs)
    s = ptrace.build_summary(since(n1))
    assert s["programs"] == len(ran) and s["rebuilt"] == 0
    assert s["trace_s"] + s["lower_s"] + s["backend_s"] <= s["first_call_s"]
    # serving the same again builds nothing: no record of any kind
    n2 = ptrace.build_count()
    for uid in list(eng.state.seqs):
        eng.flush(uid)
    serve(eng, [rng.integers(0, eng.mcfg.vocab_size, n).tolist()
                for n in (70, 5, 9)])
    assert [r for r in since(n2) if r["kind"] == "program"
            and r["key"] in ran] == []
    assert ptrace.build_summary(since(n2))["rebuilt"] == 0


def test_warmed_windows_name_their_cause():
    eng = InferenceEngineV2(build_model("tiny-llama", dtype=jnp.float32),
                            config=ENGINE, rng=jax.random.PRNGKey(0))
    n0 = ptrace.build_count()
    eng.warm_decode_windows()
    progs = [r for r in since(n0) if r["kind"] == "program"]
    assert sorted(r["key"] for r in progs) == [("win", 2), ("win", 4)]
    assert all(r["cause"] == ("warm", None) for r in progs)


def test_a_constructor_that_refuses_leaves_no_build_open():
    with pytest.raises(ValueError, match="kv_cache_dtype"):
        InferenceEngineV2(build_model("tiny-llama", dtype=jnp.float32),
                          config={**ENGINE, "kv_cache_dtype": "int3"},
                          rng=jax.random.PRNGKey(0))
    assert getattr(ptrace._TL, "build", None) is None
    n0 = ptrace.build_count()
    InferenceEngineV2(build_model("tiny-llama", dtype=jnp.float32),
                      config=ENGINE, rng=jax.random.PRNGKey(0))
    builds = {r["build"] for r in since(n0) if r["kind"] == "phase"}
    assert len(builds) == 1


# ---- the replica worker's lines ---------------------------------------------

def test_a_worker_says_its_setup_its_builds_and_a_rebuild(monkeypatch):
    from deepspeed_tpu.runtime.resilience import FaultInjector
    from deepspeed_tpu.serving import replica
    from deepspeed_tpu.serving.protocol import RequestRecord

    n0 = ptrace.build_count()
    backend = replica.EngineBackend({"model": "tiny-gpt2", "seed": 0,
                                     "engine": {"decode_window": 4}})
    phases = [r for r in since(n0) if r["kind"] == "phase"]
    assert phases[0]["key"] == "model"
    assert {r["module"] for r in phases} == {"EngineBackend"}
    assert len({r["build"] for r in phases}) == 1
    setup = backend.setup_line()
    m = re.fullmatch(r"setup: ((?:\w+ \d+\.\d\d s(?: \([^)]*\))?; )+)"
                     r"(\d+) programs first-called in (\d+\.\d\d) s", setup)
    assert m and m.group(1).startswith("model ")
    # (the ledger is the process's, and a worker's process is its own)
    assert int(m.group(2)) == ptrace.build_summary()["programs"]
    assert [p.split()[0] for p in m.group(1).split("; ")[:-1]] == \
        list(ptrace.build_summary(since(n0))["phases"])
    backend.note_ready()
    assert backend.new_builds() == []

    inj = FaultInjector(spec={}, env="", hard=False)

    def run(tag):
        assert backend.put(RequestRecord(
            trace_id=tag, prompt=[3, 4, 5, 6, 7], max_new_tokens=6)) is None
        lines = []
        for _ in range(200):
            done = [k for _, k, _, _ in backend.step(inj) if k == "done"]
            lines += backend.new_builds()
            if done:
                return lines
        raise AssertionError("the request did not finish")

    lines = run("r0")
    keys = {str(k) for k, p in backend.eng._programs.items()
            if p.avals is not None}
    said = [ln for ln in lines if " key=None " not in ln]
    # a build outside the table says the line that called for it
    assert all(re.search(r" phase=None site=deepspeed_tpu/[\w/]+\.py:\d+ at "
                         r"\+\d+\.\d s since ready; ", ln)
               for ln in lines if ln not in said)
    assert {re.match(r"build: key=(.*?) module=", ln).group(1)
            for ln in said} == keys
    for ln in said:
        assert re.fullmatch(
            r"build: key=.* module=jit_\w+ variant=1 cache=(hit|miss) trace "
            r"\d+\.\d\d s lower \d+\.\d\d s backend \d+\.\d\d s of "
            r"\d+\.\d\d s cause=dispatch:\d+ at \+\d+\.\d s since ready; "
            r"\d+ live, \d+ pending", ln), ln
    assert run("r1") == []              # warmed: nothing to say

    # the case ``engine_v2`` guards by comment: a ``_last_tok`` that is
    # not committed keys another cache entry of every warmed program
    eng = backend.eng
    eng._last_tok = jnp.zeros(eng._last_tok.shape, jnp.int32)
    with logged(logging.WARNING) as got:
        lines = run("r2")
    assert lines and all(" variant=2 " in ln and "committed True at the "
                         "first call, False now" in ln for ln in lines)
    warned = [m for lv, m in got if m.startswith("build: REBUILT ")]
    assert len(warned) == len(lines)
    assert all(re.match(r"build: REBUILT key=(\(.*?\)) module=", w).group(1)
               in keys for w in warned)

    with logged() as got:
        replica._log_pipeline(backend)
    said = [m for _, m in got]
    assert said[0].startswith("pipeline: depth ")
    m = re.fullmatch(
        r"builds: (\d+) programs first-called in \d+\.\d\d s \(trace "
        r"\d+\.\d\d, lower \d+\.\d\d, backend \d+\.\d\d; \d+ from the "
        r"cache\); (\d+) rebuilt; \d+ outside the table \(\d+\.\d\d s\); "
        r"(\d+) backend events in all", said[1])
    assert m and int(m.group(1)) >= len(keys)
    assert int(m.group(2)) >= len(lines)
    assert int(m.group(3)) == ptrace.build_summary()["backend_events"]
    assert sum(" variant=2 " in ln for ln in said[2:]) >= len(lines)
