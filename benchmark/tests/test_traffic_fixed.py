"""``open_loop_fixed`` / ``mixed-queue-fixed``: the seed chooses order,
placement and token ids and NOTHING else — two seeds offer the same count
and the same sum of prompt and of output tokens in the judged window and in
every block of 8 slots (every 4 s where 4 s is whole blocks); the same
(file, seed) gives the same schedule; the lengths are the quantiles the
file states; and the warm-up drill reaches every prefill shape the
scheduler can emit for up to six prompts pending together — held to the
program's OWN scheduler under the engine section of the configuration the
cell runs."""
import json
import os

import numpy as np
import pytest

from benchmark.traffic.generate import generate
from benchmark.traffic.kinds import open_loop_fixed

HERE = os.path.dirname(os.path.abspath(__file__))
VOCAB = 151936
SEEDS = (5, 6, 2 ** 31 + 12345)           # one past 32 signed bits


def load(kind, name):
    with open(os.path.join(HERE, "..", kind, f"{name}.json")) as f:
        return json.load(f)


def sums(reqs, t0, t1):
    inside = [r for r in reqs if t0 <= r["due_s"] < t1]
    return (len(inside), sum(len(r["prompt"]) for r in inside),
            sum(r["max_new"] for r in inside))


def test_same_seed_same_schedule():
    t = load("traffic", "mixed-queue-fixed")
    key = lambda s: [(r["due_s"], r["prompt"], r["max_new"])
                     for r in s["requests"]]
    assert key(generate(t, 5, VOCAB, 24.0)) == key(generate(t, 5, VOCAB, 24.0))
    assert key(generate(t, 5, VOCAB, 24.0)) != key(generate(t, 6, VOCAB, 24.0))
    assert generate(t, 5, VOCAB, 24.0)["kind"] == "open_loop_fixed"


def test_every_seed_offers_the_same_work():
    t = load("traffic", "mixed-queue-fixed")
    lead, secs = t["lead_in_s"], 51.0
    rate = t["arrivals"]["rate"]
    runs = [generate(t, seed, VOCAB, lead + secs)["requests"]
            for seed in SEEDS]
    # the judged window, and the lead-in before it
    assert len({sums(r, lead, lead + secs) for r in runs}) == 1
    assert len({sums(r, 0.0, lead) for r in runs}) == 1
    n, _, _ = sums(runs[0], lead, lead + secs)
    assert n == int(secs * rate + 1e-9)           # one arrival a slot
    # every block of 8 slots from the window's opening edge on
    block = 8 / rate
    for b in range(int(secs / block)):
        assert len({sums(r, lead + b * block, lead + (b + 1) * block)
                    for r in runs}) == 1, b
    # ...in a different order, at different times, with different tokens
    assert [len(r["prompt"]) for r in runs[0]] != \
        [len(r["prompt"]) for r in runs[1]]
    assert runs[0][3]["prompt"][:8] != runs[1][3]["prompt"][:8]


def test_every_four_seconds_where_four_seconds_are_whole_blocks():
    t = dict(load("traffic", "mixed-queue-fixed"),
             arrivals={"process": "fixed_slots", "rate": 4.0}, lead_in_s=8.0)
    runs = [generate(t, seed, VOCAB, 48.0)["requests"] for seed in SEEDS]
    for k in range(12):
        got = {sums(r, 4.0 * k, 4.0 * k + 4.0) for r in runs}
        assert len(got) == 1 and next(iter(got))[0] == 16, (k, got)


def test_classes_lengths_and_pattern():
    t = load("traffic", "mixed-queue-fixed")
    s = generate(dict(t, arrivals={"process": "fixed_slots", "rate": 20.0}),
                 1, VOCAB, 215.0)["requests"]
    assert [r["class"] for r in s[:4]] == ["long", "short", "long", "short"] \
        or [r["class"] for r in s[:4]] == ["short", "long", "short", "long"]
    short = np.array([len(r["prompt"]) for r in s if r["class"] == "short"])
    long_ = np.array([len(r["prompt"]) for r in s if r["class"] == "long"])
    olen = np.array([r["max_new"] for r in s])
    assert len(short) == len(long_)                         # dealt 1 : 1
    assert np.median(short) == pytest.approx(512, rel=0.05)
    assert short.min() >= 32 and short.max() <= 2048
    assert long_.min() >= 6144 and long_.max() <= 14336     # past the window
    assert np.median(long_) == pytest.approx(np.sqrt(6144 * 14336), rel=0.05)
    assert np.median(olen) == pytest.approx(512, rel=0.05)
    assert olen.min() >= 128 and olen.max() <= 1536
    plen = np.array([len(r["prompt"]) for r in s])
    assert (plen + olen).max() <= 15872 < 16384
    toks = np.concatenate([r["prompt"][:8] for r in s])
    assert toks.min() >= 0 and 150000 < toks.max() < VOCAB
    # no arrival outside its slot: due times rise, one in each 1/rate
    due = np.array([r["due_s"] for r in s])
    assert (np.diff(due) > 0).all() and np.diff(due).max() < 2 / 20.0
    cell = load("workloads", "smallthinker-mixed-queue")
    assert t["lead_in_s"] == 15.0 and t["drain_s"] <= 45.0
    assert cell["reference"]["long_prompt_min"] == 6144


def test_quantiles_are_stratified():
    q = open_loop_fixed.quantiles({"dist": "uniform", "min": 0, "max": 99},
                                  100)
    assert q.tolist() == list(range(100))
    q = open_loop_fixed.quantiles({"dist": "loguniform", "min": 8, "max": 512},
                                  3)
    assert q.tolist() in ([16, 64, 256], [15, 64, 256], [15, 63, 255])


def test_drill_reaches_every_prefill_shape_of_up_to_six_prompts():
    from deepspeed_tpu.inference.engine_v2 import (RaggedInferenceConfig,
                                                   cache_kinds)
    from deepspeed_tpu.inference.ragged import StateManager
    from deepspeed_tpu.inference.scheduler import SplitFuseScheduler
    from deepspeed_tpu.models import get_model_config

    t = load("traffic", "mixed-queue-fixed")
    conf = load("configs", "smallthinker-21ba3b-serve")
    eng = conf["engine"]
    kinds = cache_kinds(get_model_config(conf["preset"], **conf["overrides"]),
                        RaggedInferenceConfig(**eng))
    assert [(k.name, k.max_blocks) for k in kinds] == [("full", 128),
                                                       ("window", 37)]

    def scheduler():
        k0 = kinds[0]
        st = StateManager(k0.num_blocks, eng["block_size"], eng["max_seqs"],
                          k0.max_blocks, kind=k0.name,
                          more_kinds={k.name: (k.num_blocks, k.max_blocks,
                                               True) for k in kinds[1:]})
        # a model that keeps a ring packs rows only
        return st, SplitFuseScheduler(st, eng["chunk"], pack=True,
                                      grow_chunk=False)

    reached = set()
    for k, plen, max_new in t["warmup"]["drill"]:
        st, sc = scheduler()
        for uid in range(k):
            st.admit(uid, [1] * plen, max_new)
        while (plan := sc.next_step()) is not None and plan.kind == "prefill":
            reached.add(tuple(plan.token_ids.shape[::-1]))
            assert plan.more["window"][1].shape == (k, 37)
            sc.mark_dispatched(plan)
    menu = scheduler()[1].program_shape_menu()
    assert {T for T, _ in menu} == {eng["chunk"]}
    assert reached == {(T, k) for T, k in menu if k <= 6}
