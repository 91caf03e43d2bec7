"""The generators: the same (file, seed) gives the same schedule, another
seed another one, and the lengths and arrivals have the statistics the
traffic files state."""
import json
import os

import numpy as np
import pytest

from benchmark.traffic.generate import draw_arrivals, draw_lengths, generate

HERE = os.path.dirname(os.path.abspath(__file__))


def traffic(name):
    with open(os.path.join(HERE, "..", "traffic", f"{name}.json")) as f:
        return json.load(f)


def flat(s):
    if "requests" in s:
        return [(r["due_s"], r["prompt"], r["max_new"]) for r in s["requests"]]
    if "clients" in s:
        return [[(r["prompt"], r["max_new"]) for r in q] for q in s["clients"]]
    return s["batches"].tolist()


@pytest.mark.parametrize("name", ["chat-steady", "doc-batch", "zero3-sft"])
def test_same_seed_same_schedule(name):
    t = traffic(name)
    a, b = generate(t, 5, 32768, 12.0), generate(t, 5, 32768, 12.0)
    c = generate(t, 6, 32768, 12.0)
    assert flat(a) == flat(b)
    assert flat(a) != flat(c)


def test_chat_lengths_and_rate():
    t = traffic("chat-steady")
    s = generate(dict(t, arrivals={"process": "poisson", "rate": 50.0}),
                 1, 32768, 200.0)
    plen = np.array([len(r["prompt"]) for r in s["requests"]])
    olen = np.array([r["max_new"] for r in s["requests"]])
    due = np.array([r["due_s"] for r in s["requests"]])
    assert len(due) == pytest.approx(50 * 200, rel=0.05)
    assert np.all(np.diff(due) >= 0) and due.max() < 200.0
    assert np.median(plen) == pytest.approx(256, rel=0.08)
    assert np.median(olen) == pytest.approx(128, rel=0.08)
    assert plen.min() >= 16 and plen.max() <= 2048
    assert olen.min() >= 8 and olen.max() <= 512
    gaps = np.diff(due)                     # Poisson: CV of the gaps is 1
    assert gaps.std() / gaps.mean() == pytest.approx(1.0, rel=0.1)
    toks = np.concatenate([r["prompt"][:8] for r in s["requests"]])
    assert toks.min() >= 0 and toks.max() < 32768 and toks.max() > 30000


def test_doc_lengths_are_loguniform():
    t = traffic("doc-batch")
    s = generate(t, 2, 32768, 200.0)
    plen = np.array([len(r["prompt"]) for q in s["clients"] for r in q])
    olen = np.array([r["max_new"] for q in s["clients"] for r in q])
    assert len(s["clients"]) == 6
    assert plen.min() >= 2048 and plen.max() <= 8192
    assert np.median(plen) == pytest.approx(4096, rel=0.08)   # sqrt(2048*8192)
    assert olen.min() >= 64 and olen.max() <= 128


def test_packed_rows_are_full_and_closed_by_eos():
    t = traffic("zero3-sft")
    s = generate(t, 3, 32768, 5.0)
    b = s["batches"]
    assert b.shape[1:] == (8, 2048) and b.dtype == np.int32
    assert len(np.unique(b)) <= 64                       # the sub-vocabulary
    doc_lens = np.diff(np.flatnonzero(b.reshape(-1) == s["eos"]))
    assert np.median(doc_lens) == pytest.approx(601, rel=0.15)
    assert doc_lens.max() <= 2049


def test_length_distributions_clip():
    rng = np.random.default_rng(0)
    x = draw_lengths(rng, {"dist": "lognormal", "median": 100, "sigma": 2.0,
                           "min": 10, "max": 200}, 5000)
    assert x.min() == 10 and x.max() == 200
    with pytest.raises(ValueError):
        draw_lengths(rng, {"dist": "zipf", "min": 1, "max": 2}, 1)
    with pytest.raises(ValueError):
        draw_arrivals(rng, {"process": "gamma", "rate": 1.0, "cv": 3.0}, 1.0)


def test_the_judged_count_varies_with_the_seed_as_poisson_does():
    t = traffic("chat-steady")
    lead, secs = t["lead_in_s"], 51.0
    counts = [sum(1 for r in generate(t, seed, 32768, lead + secs)["requests"]
                  if r["due_s"] >= lead) for seed in range(12)]
    mean = t["arrivals"]["rate"] * secs
    assert np.mean(counts) == pytest.approx(mean, rel=0.1)
    assert len(set(counts)) > 1 and min(counts) >= 100


@pytest.mark.parametrize("name", ["chat-steady", "doc-batch"])
def test_drill_reaches_every_prefill_shape_of_up_to_three_prompts(name):
    """The warm-up drill against the program's OWN scheduler, off the
    device: each burst is admitted and planned as the engine would, and
    together the bursts reach every (T, rows) of ``program_shape_menu()``
    that up to three prompts of this mix's lengths pending together can
    produce."""
    from deepspeed_tpu.inference.ragged import StateManager
    from deepspeed_tpu.inference.scheduler import SplitFuseScheduler

    t = traffic(name)
    with open(os.path.join(HERE, "..", "configs",
                           "mistral-7b-v0.3-serve-l12.json")) as f:
        eng = json.load(f)["engine"]

    def scheduler():
        st = StateManager(eng["num_blocks"], eng["block_size"],
                          eng["max_seqs"],
                          eng["max_seq_len"] // eng["block_size"])
        return st, SplitFuseScheduler(st, eng["chunk"], pack=True)

    reached = set()
    for k, plen, max_new in t["warmup"]["drill"]:
        st, sc = scheduler()
        for uid in range(k):
            st.admit(uid, [1] * plen, max_new)
        while (plan := sc.next_step()) is not None and plan.kind == "prefill":
            reached.add(tuple(plan.token_ids.shape[::-1]))
            sc.mark_dispatched(plan)
    menu = scheduler()[1].program_shape_menu()
    want = set()
    for k in (1, 2, 3):
        chain = sorted(T for T, rows in menu if rows == k)
        # T is taken when the longest pending prompt exceeds the step below
        want |= {(T, k) for below, T in zip([0] + chain, chain)
                 if below < t["prompt_len"]["max"]}
    assert reached == want
