#!/usr/bin/env python3
"""tools/check_precision_mixed.py — what the two limits of a ``serve_mixed``
cell's reference check tell apart. Run by hand on the chip when the cell's
``logit_tolerance`` and ``precision.mean_margin_tolerance`` are set (not
part of a benchmark run):

    python benchmark/tools/check_precision_mixed.py \
        --workload lfm2-mixed-queue --short 20 --long 4 --new 256 \
        --variants '{"bf16": {}, "int8_weights": {"quant_bits": 8}}'

For each variant the configuration's engine is built in this process with
the variant's keys laid over its engine section and serves, greedily, the
first ``--long`` long and ``--short`` short prompts of the cell's own
traffic (its generator, ``--seed``), ``--new`` tokens each. Then the plain
reference, on the same seeded weights, gives every served token's margin
as ``runners/serve_mixed.py`` takes it, and the run's numbers as that
runner judges them. Every stream's margins go to
``chiprun_out/benchmark/check_precision_mixed.json``: ``--groups`` resamples
them into runs of the cell's size (the streams a run checks) for the
spread of each statistic. The variant the cell serves must pass both
limits; a lower precision must fail one."""
from __future__ import annotations

import argparse
import gc
import importlib
import json
import os
import sys

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))

from benchmark import common  # noqa: E402
from benchmark.runners import serve_mixed  # noqa: E402
from benchmark.traffic.generate import generate  # noqa: E402


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--variants", default='{"bf16": {}}')
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--short", type=int, default=20)
    ap.add_argument("--long", type=int, default=4)
    ap.add_argument("--new", type=int, default=256)
    ap.add_argument("--groups", type=int, default=200)
    ap.add_argument("--rehearse", action="store_true")
    args = ap.parse_args()
    os.environ.setdefault("JAX_COMPILATION_CACHE_DIR", common.CACHE_DIR)
    import jax

    from deepspeed_tpu.inference.engine_v2 import InferenceEngineV2
    from deepspeed_tpu.models import build_model

    entry, cell, config, traffic = common.load_cell(args.workload)
    conf = common.pick(config, args.rehearse)
    spec = common.pick(cell, args.rehearse)["reference"]
    traffic = common.pick(traffic, args.rehearse)
    dev = common.require_device(entry["chips"], args.rehearse)
    model = build_model(conf["preset"], **conf["overrides"])
    cut = int(spec["long_prompt_min"])
    offered = [r["prompt"] for r in generate(
        traffic, args.seed, model.config.vocab_size, 60.0)["requests"]
        if len(r["prompt"]) + args.new <= int(spec["max_tokens"])]
    # the runner's order: its stratified sample (long first), then the rest
    prompts = [p for p in offered if len(p) >= cut][:args.long] \
        + [p for p in offered if len(p) < cut][:args.short]
    n_judged = min(int(spec["requests"]), len(prompts))
    served = {}
    for name, over in json.loads(args.variants).items():
        eng = InferenceEngineV2(model, rng=jax.random.PRNGKey(args.seed),
                                config={**conf["engine"], **over})
        served[name] = eng.generate(prompts, max_new_tokens=args.new)
        common.say(f"{name}: served {len(prompts)} prompts of "
                   f"{[len(p) for p in prompts]} tokens")
        # drop the engine's device buffers before the next build
        for leaf in jax.tree.leaves((eng.params, eng.kv_pool)):
            leaf.delete()
        del eng
        gc.collect()
    ref = importlib.import_module(spec["module"])
    model_cfg, params = serve_mixed.seeded_params(conf, args.seed)
    out = {"device": dev, "tolerance": spec["logit_tolerance"],
           "precision": spec["precision"], "variants": {}}
    n_long = sum(len(p) >= cut for p in prompts)
    rng = np.random.default_rng([args.seed, 23])
    for name, streams in served.items():
        margins = [(len(p), serve_mixed.stream_margins(
            ref, model_cfg, params, p, s, spec))
            for p, s in zip(prompts, streams)]
        detail = serve_mixed.summarize(margins, n_judged, spec)
        # runs of the cell's size: its long streams and as many short ones
        # as the cell checks, drawn with replacement
        k_long = min(-(-int(spec["requests"]) // 2), n_long)
        k_short = int(spec["requests"]) - k_long \
            + int(spec["precision"]["requests"])
        groups = []
        for _ in range(args.groups if len(prompts) > n_long else 0):
            pick = np.concatenate([rng.integers(0, n_long, k_long),
                                   rng.integers(n_long, len(prompts),
                                                k_short)])
            every = np.concatenate([margins[i][1] for i in pick])
            groups.append((float(every.mean()), float((every > 0).mean())))
        if groups:
            g = np.asarray(groups)
            detail["resampled_runs"] = {
                "runs": len(groups), "streams_a_run": k_long + k_short,
                "mean_margin_min_p50_max": [
                    float(np.min(g[:, 0])), float(np.median(g[:, 0])),
                    float(np.max(g[:, 0]))],
                "off_argmax_share_min_p50_max": [
                    float(np.min(g[:, 1])), float(np.median(g[:, 1])),
                    float(np.max(g[:, 1]))]}
        out["variants"][name] = dict(
            detail, margins=[[int(P), [round(float(x), 6) for x in mg]]
                             for P, mg in margins])
        common.say(f"PRECISION {name}: " + json.dumps(detail))
    os.makedirs(common.OUT_DIR, exist_ok=True)
    with open(os.path.join(common.OUT_DIR, "check_precision_mixed.json"),
              "w") as f:
        json.dump(out, f)
    return 0


if __name__ == "__main__":
    sys.exit(main())
