"""``runners/serve_mixed.py``: the two limits of the lfm2 cell's reference
check, on made-up margins (no model runs here)."""
import json
import os

import numpy as np

from benchmark.runners import serve_mixed

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SPEC = {"rows": 2, "rows_tail": 1, "long_prompt_min": 50,
        "precision": {"requests": 2}}


def test_worst_margin_reads_the_checked_rows_and_the_mean_reads_all():
    a = np.array([0.0, 0.1, 9.0, 0.0, 0.3])     # rows 0, 1 and the last
    b = np.array([0.5, 0.0, 0.0])
    c = np.array([4.0, 4.0])                    # not of the judged sample
    d = serve_mixed.summarize([(7, a), (60, b), (3, c)], 2, SPEC)
    assert d["requests"] == 2 and d["rows"] == 6
    assert d["worst_margin"] == {"prefill_form": 0.5, "decode_form": 0.3}
    assert d["rows_off_the_reference_argmax_share"] == 3 / 6
    p = d["precision"]
    assert (p["requests"], p["rows"]) == (3, 10)
    assert p["mean_margin"] == np.concatenate([a, b, c]).mean()
    assert p["rows_off_the_reference_argmax_share"] == 6 / 10
    assert d["longest_stream_tokens"] == 63


def test_the_precision_sample_is_short_streams_outside_the_first():
    class R:
        def __init__(self, n):
            self.prompt = [0] * n
    ok = [R(n) for n in (10, 80, 20, 30, 90, 40)]
    taken = [ok[1], ok[0]]
    more = serve_mixed.precision_sample(ok, taken, SPEC, seed=3)
    assert len(more) == 2 and all(len(r.prompt) in (20, 30, 40) for r in more)
    assert more == serve_mixed.precision_sample(ok, taken, SPEC, seed=3)
    assert serve_mixed.precision_sample(ok[:3], taken, SPEC, seed=3) is None


def test_the_cell_states_both_limits_with_their_readings():
    with open(os.path.join(HERE, "workloads", "lfm2-mixed-queue.json")) as f:
        ref = json.load(f)["reference"]
    assert 0 < ref["precision"]["mean_margin_tolerance"] < ref["logit_tolerance"]
    assert "int8" in ref["precision"]["tolerance_why"]
    assert ref["precision"]["requests"] >= 16
