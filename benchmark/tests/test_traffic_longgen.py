"""``longgen-steady``: the same (file, seed) gives the same schedule, the
lengths and arrivals have the statistics the file states, and the warm-up
drill reaches every prefill shape the scheduler can emit for up to three
prompts of this mix pending together — held to the program's OWN scheduler
under the engine section of the configuration the cell runs."""
import json
import os

import numpy as np
import pytest

from benchmark.traffic.generate import generate

HERE = os.path.dirname(os.path.abspath(__file__))
VOCAB = 50304


def load(kind, name):
    with open(os.path.join(HERE, "..", kind, f"{name}.json")) as f:
        return json.load(f)


def test_same_seed_same_schedule():
    t = load("traffic", "longgen-steady")
    key = lambda s: [(r["due_s"], r["prompt"], r["max_new"])
                     for r in s["requests"]]
    assert key(generate(t, 5, VOCAB, 12.0)) == key(generate(t, 5, VOCAB, 12.0))
    assert key(generate(t, 5, VOCAB, 12.0)) != key(generate(t, 6, VOCAB, 12.0))
    # a seed past 32 signed bits, as the driver's are
    assert generate(t, 2 ** 31 + 12345, VOCAB, 12.0)["requests"]


def test_lengths_and_rate():
    t = load("traffic", "longgen-steady")
    s = generate(dict(t, arrivals={"process": "poisson", "rate": 50.0}),
                 1, VOCAB, 200.0)
    plen = np.array([len(r["prompt"]) for r in s["requests"]])
    olen = np.array([r["max_new"] for r in s["requests"]])
    assert np.median(plen) == pytest.approx(128, rel=0.08)
    assert np.median(olen) == pytest.approx(384, rel=0.08)
    assert plen.min() >= 16 and plen.max() <= 1024
    assert olen.min() >= 64 and olen.max() <= 1024
    assert (plen + olen).max() <= 2048
    assert np.median(olen) > 2.5 * np.median(plen)      # decode-heavy
    toks = np.concatenate([r["prompt"][:8] for r in s["requests"]])
    assert toks.min() >= 0 and 50000 < toks.max() < VOCAB
    # the drain holds a 1024-token answer at half the TPOT limit
    cell = load("workloads", "olmoe-longgen-steady")
    assert t["drain_s"] >= 1024 * cell["limits"]["tpot_ms"] / 2 / 1e3
    assert t["lead_in_s"] == 12.0 and t["drain_s"] >= 35.0


def test_drill_reaches_every_prefill_shape_of_up_to_three_prompts():
    from deepspeed_tpu.inference.ragged import StateManager
    from deepspeed_tpu.inference.scheduler import SplitFuseScheduler

    t = load("traffic", "longgen-steady")
    eng = load("configs", "olmoe-1b-7b-0125-serve")["engine"]

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
        want |= {(T, k) for below, T in zip([0] + chain, chain)
                 if below < t["prompt_len"]["max"]}
    assert reached == want
