#!/usr/bin/env python3
"""tools/probe_build.py — does the serving engine of a configuration fit,
and with how large a KV pool? Run by hand on the chip when a serving
configuration is sized (not part of a benchmark run):

    python benchmark/tools/probe_build.py --config mistral-7b-v0.3-serve-l12 \
        --blocks 700,560,460

Builds ``InferenceEngineV2`` in this process as the worker would, for each
``num_blocks`` in turn until one build succeeds, and prints the device's
bytes in use and peak after the build and after one short generation."""
from __future__ import annotations

import argparse
import gc
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))

from benchmark import common  # noqa: E402


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--config", required=True)
    ap.add_argument("--blocks", required=True)
    ap.add_argument("--layers", type=int, default=None)
    args = ap.parse_args()
    os.environ.setdefault("JAX_COMPILATION_CACHE_DIR", common.CACHE_DIR)
    import jax

    from deepspeed_tpu.inference.engine_v2 import InferenceEngineV2
    from deepspeed_tpu.models import build_model

    with open(os.path.join(common.HERE, "configs", f"{args.config}.json")) as f:
        conf = json.load(f)
    dev = jax.devices()[0]
    print(f"device {dev.platform} {dev.device_kind}", flush=True)
    ov = dict(conf["overrides"])
    if args.layers:
        ov["num_layers"] = args.layers
    model = build_model(conf["preset"], **ov)
    result = None
    for nb in [int(b) for b in args.blocks.split(",")]:
        t0 = time.monotonic()
        try:
            eng = InferenceEngineV2(model, rng=jax.random.PRNGKey(0),
                                    config={**conf["engine"], "num_blocks": nb})
        except Exception as e:  # noqa: BLE001 — the probe reports and goes on
            print(f"PROBE layers={ov['num_layers']} num_blocks={nb}: FAILED "
                  f"{str(e)[:300]}", flush=True)
            gc.collect()
            continue
        st = dev.memory_stats()
        built = {"layers": ov["num_layers"], "num_blocks": nb,
                 "build_s": round(time.monotonic() - t0, 1),
                 "in_use_gib": st["bytes_in_use"] / 2**30,
                 "peak_gib": st["peak_bytes_in_use"] / 2**30,
                 "limit_gib": st["bytes_limit"] / 2**30}
        eng.generate([[1, 2, 3] * 100], max_new_tokens=12)
        st = dev.memory_stats()
        built.update(after_gen_in_use_gib=st["bytes_in_use"] / 2**30,
                     after_gen_peak_gib=st["peak_bytes_in_use"] / 2**30)
        print("PROBE " + json.dumps(built), flush=True)
        result = built
        break
    os.makedirs(common.OUT_DIR, exist_ok=True)
    with open(os.path.join(common.OUT_DIR, "probe_build.json"), "w") as f:
        json.dump(result, f)
    return 0 if result else 1


if __name__ == "__main__":
    sys.exit(main())
