"""``rag-chat-fixed`` (kind ``open_loop_fixed``): the classes, lengths and
pattern ISSUE 50 gives; every seed offers the same work; at least 100
requests judged in 51 s; and the warm-up drill reaches every prefill shape
the scheduler can emit for up to twelve prompts pending together — held to
the program's OWN scheduler under the engine section of the configuration
the cell runs (a model that keeps a record a slot packs rows only)."""
import json
import os

import numpy as np
import pytest

from benchmark.traffic.generate import generate

HERE = os.path.dirname(os.path.abspath(__file__))
VOCAB = 65536
SEEDS = (5, 6, 2 ** 31 + 12345)           # one past 32 signed bits


def load(kind, name):
    with open(os.path.join(HERE, "..", kind, f"{name}.json")) as f:
        return json.load(f)


def sums(reqs, t0, t1):
    inside = [r for r in reqs if t0 <= r["due_s"] < t1]
    return (len(inside), sum(len(r["prompt"]) for r in inside),
            sum(r["max_new"] for r in inside))


def test_every_seed_offers_the_same_work_and_enough_of_it():
    t = load("traffic", "rag-chat-fixed")
    lead, secs = t["lead_in_s"], 51.0
    runs = [generate(t, seed, VOCAB, lead + secs)["requests"]
            for seed in SEEDS]
    assert len({sums(r, lead, lead + secs) for r in runs}) == 1
    assert len({sums(r, 0.0, lead) for r in runs}) == 1
    n, _, _ = sums(runs[0], lead, lead + secs)
    assert n == int(secs * t["arrivals"]["rate"] + 1e-9) >= 100
    assert [len(r["prompt"]) for r in runs[0]] != \
        [len(r["prompt"]) for r in runs[1]]
    assert t["arrivals"]["rate"] == pytest.approx(
        0.8 * t["arrivals"]["knee"], rel=0.02)


def test_classes_lengths_and_pattern():
    t = load("traffic", "rag-chat-fixed")
    s = generate(dict(t, arrivals={"process": "fixed_slots", "rate": 20.0}),
                 1, VOCAB, 215.0)["requests"]
    short = np.array([len(r["prompt"]) for r in s if r["class"] == "short"])
    long_ = np.array([len(r["prompt"]) for r in s if r["class"] == "long"])
    assert len(short) == len(long_)                         # dealt 1 : 1
    assert np.median(short) == pytest.approx(384, rel=0.05)
    assert short.min() >= 32 and short.max() <= 2048
    assert long_.min() >= 3072 and long_.max() <= 12288
    assert np.median(long_) == pytest.approx(np.sqrt(3072 * 12288), rel=0.05)
    o_short = np.array([r["max_new"] for r in s if r["class"] == "short"])
    o_long = np.array([r["max_new"] for r in s if r["class"] == "long"])
    assert np.median(o_short) == pytest.approx(256, rel=0.05)
    assert o_short.min() >= 64 and o_short.max() <= 768
    assert np.median(o_long) == pytest.approx(192, rel=0.05)
    assert o_long.min() >= 48 and o_long.max() <= 512
    total = np.array([len(r["prompt"]) + r["max_new"] for r in s])
    assert total.max() <= 15872 < 16384
    # every long prompt is 6 to 24 chunks of 512
    assert (np.ceil(long_ / 512).min(), np.ceil(long_ / 512).max()) == (6, 24)
    toks = np.concatenate([r["prompt"][:8] for r in s])
    assert toks.min() >= 0 and 65000 < toks.max() < VOCAB
    cell = load("workloads", "lfm2-mixed-queue")
    assert t["lead_in_s"] == 15.0 and t["drain_s"] <= 45.0
    assert cell["reference"]["long_prompt_min"] == 3072
    assert cell["reference"]["max_tokens"] == 15872


def test_drill_reaches_the_whole_menu_of_prefill_shapes():
    from deepspeed_tpu.inference.engine_v2 import (RaggedInferenceConfig,
                                                   cache_kinds)
    from deepspeed_tpu.inference.ragged import StateManager
    from deepspeed_tpu.inference.scheduler import SplitFuseScheduler
    from deepspeed_tpu.models import get_model_config

    t = load("traffic", "rag-chat-fixed")
    conf = load("configs", "lfm2-24b-a2b-serve")
    eng = conf["engine"]
    kinds = cache_kinds(get_model_config(conf["preset"], **conf["overrides"]),
                        RaggedInferenceConfig(**eng))
    # ONE paged pool (the one attention layer's) and the records
    assert [(k.name, len(k.layers), k.max_blocks, k.rows) for k in kinds] \
        == [("full", 1, 128, 0), ("conv", 4, 0, 2)]

    def scheduler():
        k0 = kinds[0]
        st = StateManager(k0.num_blocks, eng["block_size"], eng["max_seqs"],
                          k0.max_blocks, kind=k0.name,
                          records={k.name: k.rows for k in kinds[1:]})
        assert st.not_a_page_chain           # rows-only packing
        return st, SplitFuseScheduler(st, eng["chunk"], pack=True,
                                      grow_chunk=False,
                                      max_rows=eng["prefill_max_rows"])

    reached = set()
    for k, plen, max_new in t["warmup"]["drill"]:
        st, sc = scheduler()
        for uid in range(k):
            st.admit(uid, [1] * plen, max_new)
        while (plan := sc.next_step()) is not None and plan.kind == "prefill":
            reached.add(tuple(plan.token_ids.shape[::-1]))
            write, read = plan.more["conv"]
            assert write.shape == read.shape == (k,)
            sc.mark_dispatched(plan)
    menu = scheduler()[1].program_shape_menu()
    assert {T for T, _ in menu} == {eng["chunk"]}
    # the WHOLE menu: no rate can make the scheduler emit a prefill shape
    # the warm-up has not compiled
    assert reached == set(menu) and len(menu) == eng["prefill_max_rows"]
    cell = load("workloads", "lfm2-mixed-queue")
    assert cell["trace_warm"]["max_rows"] == eng["prefill_max_rows"]
    # more prompts pending than the cap: the plan carries the oldest
    st, sc = scheduler()
    for uid in range(eng["prefill_max_rows"] + 5):
        st.admit(uid, [1] * 600, 4)
    plan = sc.next_step()
    assert plan.token_ids.shape == (eng["prefill_max_rows"], eng["chunk"])
    assert sorted(plan.uids) == list(range(eng["prefill_max_rows"]))
