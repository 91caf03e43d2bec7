"""The arithmetic that turns client timestamps into end-to-end numbers, and
the cache-directory repair, on hand-made inputs."""
import os

import pytest

from benchmark.run import heal_cache_dir
from benchmark.runners.serve import Req, judge


def req(prompt, max_new, sent, first, done, due=None, status="done"):
    r = Req(0, [1] * prompt, max_new, due=due)
    r.sent, r.first, r.done, r.status = sent, first, done, status
    r.tokens = [2] * max_new if status == "done" else []
    return r


def test_closed_loop_rate_is_between_the_first_and_last_completion():
    w0, w1 = 100.0, 110.0
    reqs = [
        req(1000, 10, 101.0, 103.0, 104.0),     # first completion: the clock starts
        req(800, 20, 98.0, 102.0, 105.0),       # sent in the lead-in, done inside
        req(600, 40, 106.0, 108.0, 109.0),      # last completion: the clock stops
        req(500, 10, 90.0, 95.0, 99.0),         # done before the window
        req(500, 10, 107.0, 109.0, 113.0),      # done after it
    ]
    res = judge(reqs, w0, w1, "closed_loop")
    assert (res["attempted"], res["failed"], res["n_completed"]) == (3, 0, 3)
    assert res["serve_tok_per_s"] == pytest.approx(
        ((800 + 20) + (600 + 40)) / (109.0 - 104.0))
    assert res["doc_ttft_p50_s"] == pytest.approx(2.0)      # 2, 4, 2


def test_closed_loop_counts_a_request_that_ended_badly_as_failed():
    reqs = [req(1000, 10, 101.0, 103.0, 104.0),
            req(900, 10, 105.0, 106.0, 107.0),
            req(900, 10, 108.0, None, 108.0, status="refused:capacity")]
    res = judge(reqs, 100.0, 110.0, "closed_loop")
    assert (res["attempted"], res["failed"]) == (3, 1)
    assert res["serve_tok_per_s"] == pytest.approx(910 / 3.0)


def test_open_loop_judges_requests_due_in_the_window_from_the_time_due():
    reqs = [req(10, 5, 100.5, 101.0, 101.4, due=100.0),     # sent 0.5 s late
            req(10, 3, 105.0, 105.2, 105.6, due=105.0),
            req(10, 5, 99.0, 99.5, 100.5, due=99.0),        # lead-in: not judged
            req(10, 5, 109.0, None, None, due=109.0, status="failed")]
    res = judge(reqs, 100.0, 110.0, "open_loop")
    assert (res["attempted"], res["failed"]) == (3, 1)
    # lateness of the generator is INSIDE time to first token: 1.0 and 0.2
    assert res["ttft_p50_s"] == pytest.approx(0.6)
    # per request: (done - first) / (n - 1) -> 100 ms and 200 ms
    assert res["tpot_p50_ms"] == pytest.approx(150.0)
    assert res["late_max_s"] == pytest.approx(0.5)


def test_heal_cache_dir_gives_orphaned_entries_their_atime(tmp_path):
    for n in ("a-cache", "b-cache", "b-atime", "notes.txt"):
        (tmp_path / n).write_bytes(b"x")
    heal_cache_dir(str(tmp_path))
    assert sorted(os.listdir(tmp_path)) == ["a-atime", "a-cache", "b-atime",
                                            "b-cache", "notes.txt"]
    assert len((tmp_path / "a-atime").read_bytes()) == 8
    assert (tmp_path / "b-atime").read_bytes() == b"x"      # left alone
    heal_cache_dir(str(tmp_path / "missing"))               # no directory: no error
