#!/usr/bin/env python3
"""tools/check_precision.py — what the reference check's tolerance tells
apart. Run by hand on the chip when a ``serve_moe`` cell's
``logit_tolerance`` is set (not part of a benchmark run):

    python benchmark/tools/check_precision.py --workload olmoe-longgen-steady \
        --variants '{"bf16": {}, "int8_weights": {"quant_bits": 8}, "fp8_kv": {"kv_cache_dtype": "fp8"}}'

For each variant the configuration's engine is built in this process with
the variant's keys laid over its engine section, serves a few seeded
prompts greedily, and is closed; then the plain reference, on the same
seeded weights, gives the worst margin (its maximum logit less its logit
of the served token) of the first served token and of the later ones —
the numbers ``runners/serve_moe.py`` holds to the tolerance. The variant
the cell serves must pass; a lower precision must not."""
from __future__ import annotations

import argparse
import gc
import json
import os
import sys

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))

from benchmark import common  # noqa: E402


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--variants", default='{"bf16": {}}')
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--requests", type=int, default=4)
    ap.add_argument("--rehearse", action="store_true")
    args = ap.parse_args()
    os.environ.setdefault("JAX_COMPILATION_CACHE_DIR", common.CACHE_DIR)
    import importlib

    import jax

    from benchmark.runners import serve_moe
    from deepspeed_tpu.inference.engine_v2 import InferenceEngineV2
    from deepspeed_tpu.models import build_model

    entry, cell, config, _ = common.load_cell(args.workload)
    conf = common.pick(config, args.rehearse)
    spec = common.pick(cell, args.rehearse)["reference"]
    dev = common.require_device(entry["chips"], args.rehearse)
    model = build_model(conf["preset"], **conf["overrides"])
    rng = np.random.default_rng([args.seed, 17])
    room = int(spec["max_tokens"]) - int(spec["rows"])
    prompts = [rng.integers(0, model.config.vocab_size,
                            int(rng.integers(room // 8, room // 2))).tolist()
               for _ in range(args.requests)]
    served = {}
    for name, over in json.loads(args.variants).items():
        eng = InferenceEngineV2(model, rng=jax.random.PRNGKey(args.seed),
                                config={**conf["engine"], **over})
        served[name] = eng.generate(prompts, max_new_tokens=int(spec["rows"]))
        common.say(f"{name}: served {len(prompts)} prompts of "
                   f"{[len(p) for p in prompts]} tokens")
        # drop the engine's device buffers before the next build
        for leaf in jax.tree.leaves((eng.params, eng.kv_pool)):
            leaf.delete()
        del eng
        gc.collect()
    ref = importlib.import_module(spec["module"])
    model_cfg, params = serve_moe.seeded_params(conf, args.seed)
    out = {"device": dev, "tolerance": spec["logit_tolerance"], "variants": {}}
    for name, streams in served.items():
        detail = serve_moe.reference_margins(
            ref, model_cfg, params, list(zip(prompts, streams)), spec)
        out["variants"][name] = detail
        common.say(f"PRECISION {name}: " + json.dumps(detail))
    os.makedirs(common.OUT_DIR, exist_ok=True)
    with open(os.path.join(common.OUT_DIR, "check_precision.json"), "w") as f:
        json.dump(out, f, indent=1)
    return 0


if __name__ == "__main__":
    sys.exit(main())
