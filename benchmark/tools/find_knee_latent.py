#!/usr/bin/env python3
"""tools/find_knee_latent.py — ``tools/find_knee_mixed.py`` for a
``serve_latent`` cell whose two classes have their OWN first-token limits
(the cell file's ``limits``: ``ttft_s`` for a prompt under
``reference.long_prompt_min`` tokens, ``ttft_long_s`` from there on — a
question over a 28k-token document is ~56 prefill chunks before its first
token). The sweep, its arguments and its result file are
``tools/find_knee.py``'s; beside each of its ``RATE`` lines (whose
``attainment`` holds EVERY request to ``ttft_s``) this prints a
``RATE_BY_CLASS`` line with the share of judged requests inside their own
class's limits — the number the cell's knee is read from:

    python benchmark/tools/find_knee_latent.py \
        --workload kanana2-longdoc-queue --rates 0.5,1,1.5,2,2.5,3,4,5 \
        --seconds 30
"""
import functools
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))

from benchmark import common  # noqa: E402
from benchmark.runners import serve, serve_hybrid  # noqa: E402
from benchmark.tools import find_knee  # noqa: E402

find_knee.generate = serve_hybrid.generate
_judge = serve.judge


@functools.cache
def class_limits() -> tuple[dict, int]:
    """The cell's ``limits`` and the prompt length from which a request is
    of the long class (the cell named on this process's command line)."""
    _, cell, _, _ = common.load_cell(
        sys.argv[sys.argv.index("--workload") + 1])
    cellp = common.pick(cell, "--rehearse" in sys.argv)
    return cellp["limits"], int(cellp["reference"]["long_prompt_min"])


def judge(reqs, w0, w1, kind):
    """``serve.judge``, and one line: attainment by class."""
    res = _judge(reqs, w0, w1, kind)
    lim, cut = class_limits()
    met = {"short": [0, 0], "long": [0, 0]}
    for r in res["ok"]:
        name = "long" if len(r.prompt) >= cut else "short"
        ttft = lim["ttft_long_s"] if name == "long" else lim["ttft_s"]
        tpot = 0.0 if r.max_new < 2 else \
            (r.done - r.first) / (r.max_new - 1) * 1e3
        met[name][0] += r.first - r.due <= ttft and tpot <= lim["tpot_ms"]
        met[name][1] += 1
    n_met = met["short"][0] + met["long"][0]
    common.say("RATE_BY_CLASS " + json.dumps({
        "judged": res["attempted"], "failed": res["failed"],
        "attainment": n_met / max(res["attempted"], 1),
        "short_met_of": met["short"], "long_met_of": met["long"]}))
    return res


serve.judge = judge

if __name__ == "__main__":
    sys.exit(find_knee.main())
