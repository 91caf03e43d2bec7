"""One numbered record per dispatched entry (``engine_v2._enqueue`` /
``_drain``): the counters every run books, the spans that carry ``seq``
when telemetry is on, and the replica loop's own share of a step.

One tiny engine serves every case: ``max_inflight`` is read at each drain
and the telemetry handle is an attribute, so both are switched between runs.
``_drain_age`` is set out of reach, so an entry leaves the pipeline only
when the pipeline is full or the host has nothing else to dispatch — the
depth is then a matter of ``max_inflight`` and not of this host's timing.
"""
import jax
import numpy as np
import pytest

from deepspeed_tpu import telemetry as T
from deepspeed_tpu.inference.engine_v2 import InferenceEngineV2
from deepspeed_tpu.models import build_model
from deepspeed_tpu.telemetry import spans

ENGINE = {"block_size": 8, "num_blocks": 64, "max_seqs": 2, "chunk": 8,
          "max_seq_len": 128, "decode_window": 4, "telemetry": False}
COUNTS = ("dispatches", "entries_dispatched", "entries_committed",
          "inflight_depth_sum", "prefill_entries_committed", "prefill_steps",
          "windows", "decode_steps", "prefill_tokens", "decode_tokens")
NEW = 28                # 1 by the prefill, 6 windows of 4, one of 2, 1 single step


@pytest.fixture(scope="module")
def eng():
    e = InferenceEngineV2(build_model("tiny-gpt2"), config=ENGINE,
                          rng=jax.random.PRNGKey(0))
    e._drain_age = 1e9
    return e


def serve(eng, max_inflight, telem=None):
    """Two prompts to completion; the deltas of the engine's counters."""
    eng.config.max_inflight = max_inflight
    eng._telem = telem or T.Telemetry(enabled=False)
    rng = np.random.default_rng(1)
    prompts = [list(map(int, rng.integers(1, 200, n))) for n in (19, 5)]
    before = dict(eng.stats)
    out = eng.generate(prompts, max_new_tokens=NEW)
    assert [len(o) for o in out] == [NEW, NEW] and not eng._inflight
    return {k: v - before[k] for k, v in eng.stats.items()
            if isinstance(v, (int, float))}


@pytest.mark.parametrize("max_inflight", [0, 1, 3, 8])
def test_counters_follow_every_entry(eng, max_inflight):
    st = serve(eng, max_inflight)
    n = st["dispatches"]
    assert n == st["prefill_steps"] + st["windows"] + st["decode_steps"] > 0
    assert st["entries_dispatched"] == st["entries_committed"] == n
    assert st["prefill_entries_committed"] == st["prefill_steps"]
    assert 0.0 <= st["prefill_residence_s"] <= st["inflight_residence_s"]
    depth = st["inflight_depth_sum"] / st["entries_dispatched"]
    if max_inflight == 0:
        assert depth == 0
    else:
        # the drain ahead of a dispatch leaves at most max_inflight - 1
        assert depth <= max_inflight - 1
        assert st["inflight_depth_sum"] >= min(max_inflight - 1, n - 1)


def test_decode_tokens_are_the_tokens_of_steps_and_windows(eng):
    st = serve(eng, 8)
    assert st["windows"] > 0 and st["decode_steps"] > 0
    # every generated token but each prompt's first, which its prefill made
    assert st["decode_tokens"] == 2 * (NEW - 1)


def test_depth_and_residence_grow_with_max_inflight(eng):
    sync, deep = serve(eng, 0), serve(eng, 8)

    def mean(st, key, per):
        return st[key] / st[per]

    assert mean(deep, "inflight_depth_sum", "entries_dispatched") > 1.0
    assert mean(deep, "inflight_residence_s", "entries_committed") \
        > mean(sync, "inflight_residence_s", "entries_committed") > 0.0


def test_spans_carry_every_seq_once_in_fifo_order(eng):
    telem = T.Telemetry(enabled=True, span_buffer=4096)
    first = eng._entry_seq
    st = serve(eng, 8, telem)
    ev = telem.tracer.events()
    want = list(range(first, first + st["entries_dispatched"]))
    for name in ("dispatch", "commit"):
        assert [e["args"]["seq"] for e in ev if e["name"] == name] == want
    blocked = [e["args"]["seq"] for e in ev if e["name"] == "drain_block"]
    assert blocked == sorted(set(blocked)) and set(blocked) <= set(want)
    # a plan span carries the number of the entry it plans (the last
    # plans of a run find nothing left and dispatch nothing)
    planned = [e["args"]["seq"] for e in ev if e["name"] == "plan"]
    assert set(want) <= set(planned)
    kinds = {e["args"]["seq"]: e["args"] for e in ev
             if e["name"] == "dispatch"}
    assert {a["kind"] for a in kinds.values()} == {"prefill", "decode",
                                                    "window"}
    assert all(("W" in a) == (a["kind"] == "window") and
               ("T" in a) == (a["kind"] != "window") for a in kinds.values())
    depths = [e["args"]["depth"] for e in ev if e["name"] == "commit"]
    assert sum(depths) == st["inflight_depth_sum"] and max(depths) == 7


def test_telemetry_off_makes_no_span_and_books_the_same(eng, monkeypatch):
    on = serve(eng, 8, T.Telemetry(enabled=True, span_buffer=4096))
    made = []
    monkeypatch.setattr(spans._Span, "__init__",
                        lambda self, *a, **k: made.append(a))
    monkeypatch.setattr(jax.profiler, "TraceAnnotation",
                        lambda *a, **k: made.append(a))
    off = serve(eng, 8)
    assert made == []
    assert {k: off[k] for k in COUNTS} == {k: on[k] for k in COUNTS}


def test_replica_step_books_its_own_time_and_says_so():
    from deepspeed_tpu.runtime.resilience import FaultInjector
    from deepspeed_tpu.serving.protocol import RequestRecord
    from deepspeed_tpu.serving.replica import EngineBackend

    backend = EngineBackend({"model": "tiny-gpt2", "seed": 0,
                             "engine": {"decode_window": 4}})
    inj = FaultInjector(spec={}, env="", hard=False)
    assert backend.step(inj) == []                  # no work: not a step
    assert backend.eng.stats["replica_step_s"] == 0.0
    for i in range(2):
        assert backend.put(RequestRecord(
            trace_id=f"r{i}", prompt=[3, 4, 5, 6, 7],
            max_new_tokens=6)) is None
    done = 0
    for _ in range(200):
        done += sum(1 for _, kind, _, _ in backend.step(inj)
                    if kind == "done")
        if done == 2:
            break
    st = backend.eng.stats
    assert done == 2 and st["replica_step_s"] >= st["engine_step_s"] > 0.0
    line = backend.pipeline_line()
    # (prefill steps ran: what their decode blocks carried follows)
    assert line.startswith("pipeline: depth ") and line.endswith(
        f" % outside the engine; fused: {st['fused_steps']} steps carried "
        f"{st['fused_decode_tokens']} decode tokens, "
        f"{st['fused_empty_steps']} carried none")
    assert st["fused_steps"] + st["fused_empty_steps"] == st["prefill_steps"]
    assert f"over {st['entries_committed']} entries (prefill " in line
