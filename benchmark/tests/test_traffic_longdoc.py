"""``longdoc-chat-fixed`` (kind ``open_loop_fixed``): the classes, lengths
and pattern ISSUE 54 gives; every seed offers the same multiset; no request
over 30,208 tokens; and the warm-up drill reaches the scheduler's WHOLE menu
of prefill shapes under the configuration's own engine settings — both when
a burst's prompts are all admitted before the next step and when the worker
admits ONE a step (``serving/replica.py``'s loop reads one message, then
steps: a burst of short prompts never has more than two pending together
there, which is how the first sweep of this cell compiled eight programs
under load; PERF.md, Findings, PR 54)."""
import json
import os

import numpy as np
import pytest

from benchmark.traffic.generate import generate

HERE = os.path.dirname(os.path.abspath(__file__))
VOCAB = 128256
SEEDS = (5, 6, 2 ** 31 + 12345)           # one past 32 signed bits


def load(kind, name):
    with open(os.path.join(HERE, "..", kind, f"{name}.json")) as f:
        return json.load(f)


def sums(reqs, t0, t1):
    inside = [r for r in reqs if t0 <= r["due_s"] < t1]
    return (len(inside), sorted(len(r["prompt"]) for r in inside),
            sorted(r["max_new"] for r in inside))


def test_every_seed_offers_the_same_multiset():
    t = load("traffic", "longdoc-chat-fixed")
    lead, secs = t["lead_in_s"], 51.0
    runs = [generate(t, seed, VOCAB, lead + secs)["requests"]
            for seed in SEEDS]
    first = sums(runs[0], lead, lead + secs)
    assert all(sums(r, lead, lead + secs) == first for r in runs)
    assert all(sums(r, 0.0, lead) == sums(runs[0], 0.0, lead) for r in runs)
    assert first[0] == int(secs * t["arrivals"]["rate"] + 1e-9)
    assert [len(r["prompt"]) for r in runs[0]] != \
        [len(r["prompt"]) for r in runs[1]]
    assert t["kind"] == "open_loop_fixed" and t["block_slots"] == 8
    assert t["pattern"] == ["short", "long"]
    knee = t["arrivals"]["knee"]
    assert t["arrivals"]["rate"] in (pytest.approx(0.8 * knee, rel=0.02),
                                     pytest.approx(0.6 * knee, rel=0.02))


def test_classes_lengths_and_the_cap():
    t = load("traffic", "longdoc-chat-fixed")
    s = generate(dict(t, arrivals={"process": "fixed_slots", "rate": 20.0}),
                 1, VOCAB, 215.0)["requests"]
    short = np.array([len(r["prompt"]) for r in s if r["class"] == "short"])
    long_ = np.array([len(r["prompt"]) for r in s if r["class"] == "long"])
    assert len(short) == len(long_)                         # dealt 1 : 1
    assert np.median(short) == pytest.approx(512, rel=0.05)
    assert short.min() >= 32 and short.max() <= 2048
    assert long_.min() >= 8192 and long_.max() <= 28672
    assert np.median(long_) == pytest.approx(np.sqrt(8192 * 28672), rel=0.05)
    for cls in ("short", "long"):
        out = np.array([r["max_new"] for r in s if r["class"] == cls])
        assert np.median(out) == pytest.approx(512, rel=0.05)
        assert out.min() >= 128 and out.max() <= 1536
    total = np.array([len(r["prompt"]) + r["max_new"] for r in s])
    assert total.max() <= 30208 < 32768
    # every long prompt is 17 to 56 chunks of 512
    assert (np.ceil(long_ / 512).min(), np.ceil(long_ / 512).max()) \
        == (17, 56)
    toks = np.concatenate([r["prompt"][:8] for r in s])
    assert toks.min() >= 0 and 127000 < toks.max() < VOCAB
    cell = load("workloads", "kanana2-longdoc-queue")
    assert t["lead_in_s"] == 15.0 and t["drain_s"] <= 45.0
    assert cell["reference"]["long_prompt_min"] == 8192
    assert cell["reference"]["max_tokens"] == 30208


@pytest.mark.parametrize("one_a_step", [False, True],
                         ids=["all-admitted", "one-admitted-a-step"])
def test_drill_reaches_the_whole_menu_of_prefill_shapes(one_a_step):
    from deepspeed_tpu.inference.engine_v2 import (RaggedInferenceConfig,
                                                   cache_kinds)
    from deepspeed_tpu.inference.ragged import StateManager
    from deepspeed_tpu.inference.scheduler import SplitFuseScheduler
    from deepspeed_tpu.models import get_model_config

    t = load("traffic", "longdoc-chat-fixed")
    conf = load("configs", "kanana-2-30b-a3b-serve")
    eng = conf["engine"]
    (k0,) = cache_kinds(get_model_config(conf["preset"],
                                         **conf["overrides"]),
                        RaggedInferenceConfig(**eng))
    # ONE paged kind, primary, every layer: a table of 256 blocks
    assert (k0.name, len(k0.layers), k0.max_blocks, k0.halves) \
        == ("latent", 5, 256, 1)

    def scheduler():
        st = StateManager(k0.num_blocks, eng["block_size"], eng["max_seqs"],
                          k0.max_blocks, kind=k0.name)
        assert not st.not_a_page_chain       # a chain of pages: it COULD grow
        return st, SplitFuseScheduler(
            st, eng["chunk"], pack=True,
            grow_chunk=eng["prefill_grow_chunk"],
            max_rows=eng["prefill_max_rows"])

    reached = set()
    for k, plen, max_new in t["warmup"]["drill"]:
        st, sc = scheduler()
        waiting = list(range(k))
        while True:
            for uid in (waiting[:1] if one_a_step else list(waiting)):
                st.admit(uid, [1] * plen, max_new)
                waiting.remove(uid)
            plan = sc.next_step()
            if plan is None or (plan.kind != "prefill" and not waiting):
                break
            if plan.kind == "prefill":
                reached.add(tuple(plan.token_ids.shape[::-1]))
            sc.mark_dispatched(plan)
    menu = scheduler()[1].program_shape_menu()
    # rows only: a prefill step is at most 12 x 512 = 6,144 tokens
    assert {T for T, _ in menu} == {eng["chunk"]}
    assert max(T * k for T, k in menu) == 6144
    # the WHOLE menu, either way the worker admits a burst
    assert reached == set(menu) and len(menu) == eng["prefill_max_rows"]
    cell = load("workloads", "kanana2-longdoc-queue")
    assert cell["trace_warm"]["max_rows"] == eng["prefill_max_rows"]
