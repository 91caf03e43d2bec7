#!/usr/bin/env python3
"""tools/find_knee.py — the one sweep that fixes a serving cell's rate.

Not part of a benchmark run: run once on the chip when a cell is defined
(or re-defined), and the result goes into the traffic file as a number and
into PERF.md as a table.

    python benchmark/tools/find_knee.py --workload mistral7b-chat-steady \
        --rates 2,3,4,5,6,8 --seconds 20 [--engine '{"chunk": 256}'] \
        [--closed doc-batch --closed-seconds 25]

One Router and one worker serve every rate in turn (set-up is paid once):
the cell's own traffic file with only the arrival rate replaced, a lead-in,
``--seconds`` of judged arrivals, then a drain. For each rate: the share of
judged requests that met BOTH limits of the cell file (time to first token,
time per output token), p50/p90 of each, and whether a backlog grew (open
requests at the end of sending against the middle). The knee is the highest
rate with attainment >= 90 % and no growing backlog; the cell runs at 0.8 x.
``--engine`` lays overrides on the configuration's engine section (the
``chunk`` sweep); ``--closed`` adds one closed-loop leg of another traffic
file on the same worker (its tokens/s under the same engine).
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))

from benchmark import common  # noqa: E402
from benchmark.common import say  # noqa: E402
from benchmark.runners import serve as S  # noqa: E402
from benchmark.traffic.generate import generate  # noqa: E402


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seconds", type=float, default=20.0)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--engine", default="{}")
    ap.add_argument("--closed", default=None)
    ap.add_argument("--closed-seconds", type=float, default=25.0)
    ap.add_argument("--rehearse", action="store_true")
    ap.add_argument("--tag", default="knee")
    args = ap.parse_args()
    try:
        os.setsid()        # the worker watcher stops this whole session
    except OSError:
        pass
    os.environ.setdefault("JAX_COMPILATION_CACHE_DIR", common.CACHE_DIR)
    entry, cell, config, traffic = common.load_cell(args.workload)
    conf = common.pick(config, args.rehearse)
    traffic = common.pick(traffic, args.rehearse)
    cellp = common.pick(cell, args.rehearse)
    conf["engine"] = {**conf["engine"], **json.loads(args.engine)}
    from deepspeed_tpu.models import get_model_config

    vocab = get_model_config(conf["preset"], **conf["overrides"]).vocab_size
    out_dir = os.path.join(common.OUT_DIR, "find_knee", args.tag)
    router, status_path, stop = S.start_router(conf, args.seed, out_dir, 1,
                                               args.rehearse)
    want = "cpu" if args.rehearse else "tpu"
    limits = cellp["limits"]
    rows, closed = [], None
    t_build = time.monotonic()
    try:
        router.start(min_ready=1)
        stop.set()
        h = router.fleet.replicas[0]
        say(f"worker ready in {time.monotonic() - t_build:.1f}s: "
            f"{h.platform} {h.device_kind}")
        if h.platform != want:
            raise SystemExit(f"worker runs on {h.platform!r}")
        client = S.RouterClient(router)
        t0 = time.monotonic()
        S.warm_up(client, traffic, args.seed, vocab)
        st = S.read_status(status_path)
        say(f"warm-up {time.monotonic() - t0:.1f}s, {len(st['compiles'])} "
            f"compiles ({sum(s for _, s in st['compiles']):.1f}s), memory "
            f"peak {st['memory_peak_bytes'] / 2**30:.2f} GiB")
        lead = float(traffic["lead_in_s"])
        for rate in [float(r) for r in args.rates.split(",")]:
            mix = dict(traffic, arrivals=dict(traffic["arrivals"], rate=rate))
            sched = generate(mix, args.seed + int(rate * 100), vocab,
                             lead + args.seconds)
            t_start = time.monotonic() + 0.05
            w0, w1 = t_start + lead, t_start + lead + args.seconds
            n_compiles = len(S.read_status(status_path)["compiles"])
            reqs = S.run_load(client, sched, t_start, w1,
                              float(traffic["drain_s"]))
            res = S.judge(reqs, w0, w1, "open_loop")
            ok = res["ok"]
            met = [r for r in ok
                   if r.first - r.due <= limits["ttft_s"]
                   and (r.max_new < 2 or (r.done - r.first)
                        / (r.max_new - 1) * 1e3 <= limits["tpot_ms"])]
            mid = (w0 + w1) / 2

            def open_at(t):
                return sum(1 for r in reqs if r.sent <= t
                           and (r.done is None or r.done > t))

            row = {"rate": rate, "judged": res["attempted"],
                   "failed": res["failed"],
                   "attainment": len(met) / max(res["attempted"], 1),
                   "ttft_p50_s": res["ttft_p50_s"],
                   "ttft_p90_s": res["ttft_p90_s"],
                   "tpot_p50_ms": res["tpot_p50_ms"],
                   "tpot_p90_ms": res["tpot_p90_ms"],
                   "open_mid": open_at(mid), "open_end": open_at(w1),
                   "new_compiles": len(S.read_status(status_path)["compiles"])
                   - n_compiles,
                   "tokens_out_per_s": sum(len(r.tokens) for r in ok)
                   / args.seconds}
            rows.append(row)
            say("RATE " + json.dumps({k: round(v, 4) if isinstance(v, float)
                                      else v for k, v in row.items()}))
        if args.closed:
            with open(os.path.join(common.HERE, "traffic",
                                   f"{args.closed}.json"),
                      encoding="utf-8") as f:
                t2 = common.pick(json.load(f), args.rehearse)
            lead2 = float(t2["lead_in_s"])
            sched = generate(t2, args.seed, vocab, lead2 + args.closed_seconds)
            t_start = time.monotonic() + 0.05
            w0 = t_start + lead2
            w1 = w0 + args.closed_seconds
            n_compiles = len(S.read_status(status_path)["compiles"])
            reqs = S.run_load(client, sched, t_start, w1, float(t2["drain_s"]))
            res = S.judge(reqs, w0, w1, "closed_loop")
            st = S.read_status(status_path)
            closed = {"traffic": args.closed,
                      "serve_tok_per_s": res["serve_tok_per_s"],
                      "completed": res["n_completed"],
                      "failed": res["failed"],
                      "doc_ttft_p50_s": res["doc_ttft_p50_s"],
                      "compiles_in_window": sum(
                          1 for t, _ in st["compiles"] if w0 <= t <= w1),
                      "new_compiles": len(st["compiles"]) - n_compiles,
                      "memory_peak_gib": st["memory_peak_bytes"] / 2**30}
            say("CLOSED " + json.dumps(closed))
    finally:
        stop.set()
        router.close()
    good = [r for r in rows if r["attainment"] >= 0.9
            and r["open_end"] <= max(2 * r["open_mid"], r["open_mid"] + 8)]
    knee = max((r["rate"] for r in good), default=None)
    result = {"workload": args.workload, "engine": conf["engine"],
              "limits": limits, "seconds": args.seconds, "rates": rows,
              "knee": knee, "cell_rate": None if knee is None else 0.8 * knee,
              "closed": closed}
    with open(os.path.join(out_dir, "result.json"), "w",
              encoding="utf-8") as f:
        json.dump(result, f, indent=1)
    say("KNEE " + json.dumps({"knee": knee, "cell_rate": result["cell_rate"],
                              "engine": conf["engine"]}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
