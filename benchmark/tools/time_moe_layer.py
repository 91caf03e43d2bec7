#!/usr/bin/env python3
"""tools/time_moe_layer.py — ONE routed-expert layer at a configuration's
published widths, alone on the chip: the capacity form (one-hot dispatch
einsums, every expert computing ``k x T`` slots a row — what serving ran
for unquantised experts before PR 25) against the sorted grouped form at
each tile height, at a decode step's and at prefill steps' row counts. Run
by hand when the tile-height rule (``engine_v2.moe_tile_rows``) is set or
questioned; not part of a benchmark run.

    python benchmark/tools/time_moe_layer.py --config olmoe-1b-7b-0125-serve \
        --rows 48,128,512,2048 --tiles 16,32,64,128 [--capacity-rows 48,512]

Times are N back-to-back dispatches and one sync, per call, in ms."""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))

from benchmark import common  # noqa: E402


def timed(fn, *args, n=20):
    import jax

    jax.block_until_ready(fn(*args))
    jax.block_until_ready(fn(*args))
    t0 = time.monotonic()
    out = None
    for _ in range(n):
        out = fn(*args)
    jax.block_until_ready(out)
    return (time.monotonic() - t0) / n * 1e3


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--config", required=True)
    ap.add_argument("--rows", default="48,128,512,2048")
    ap.add_argument("--tiles", default="16,32,64,128")
    ap.add_argument("--capacity-rows", default="48,512")
    ap.add_argument("--rehearse", action="store_true")
    args = ap.parse_args()
    os.environ.setdefault("JAX_COMPILATION_CACHE_DIR", common.CACHE_DIR)
    import jax
    import jax.numpy as jnp

    from deepspeed_tpu.inference.engine_v2 import (moe_padded_rows,
                                                   moe_tile_rows)
    from deepspeed_tpu.models import get_model_config
    from deepspeed_tpu.models.transformer import moe_layer_kwargs
    from deepspeed_tpu.moe.layer import MoE, dropless_dispatch_combine
    from deepspeed_tpu.moe.sharded_moe import topk_dropless_gating
    from deepspeed_tpu.ops.pallas.grouped_matmul import grouped_matmul_layer

    with open(os.path.join(common.HERE, "configs", f"{args.config}.json")) as f:
        conf = common.pick(json.load(f), args.rehearse)
    dev = common.require_device(1, args.rehearse)
    m = get_model_config(conf["preset"], **conf["overrides"])
    mo, E, F = m.moe, m.hidden_size, m.ffn_size
    n, k = mo.num_experts, mo.top_k
    keys = jax.random.split(jax.random.PRNGKey(0), 5)
    init = lambda key, shape, fan: (jax.random.normal(key, shape, jnp.float32)
                                    / fan ** 0.5).astype(jnp.bfloat16)
    ml = {"gate": {"wg": init(keys[0], (E, n), E).astype(jnp.float32)},
          "experts": {"w_gate": init(keys[1], (n, E, F), E),
                      "w_up": init(keys[2], (n, E, F), E),
                      "w_down": init(keys[3], (n, F, E), F)}}
    jax.block_until_ready(ml)
    out = {"device": dev, "config": args.config, "experts": n, "top_k": k,
           "capacity_ms": {}, "grouped_ms": {}}

    def grouped(bm):
        def f(ml, h):
            flat = h.reshape(-1, E)
            logits = jnp.einsum("te,en->tn", flat.astype(jnp.float32),
                                ml["gate"]["wg"])
            gate = topk_dropless_gating(logits[None], k,
                                        normalize_gates=mo.normalize_gates)
            ex = ml["experts"]

            def gemm(buf, srt):
                mm = lambda x, w: grouped_matmul_layer(
                    x, w, srt.tile_expert, srt.n_tiles, bm)
                z = jax.nn.silu(mm(buf, ex["w_gate"])) * mm(buf, ex["w_up"])
                return mm(z, ex["w_down"])

            return dropless_dispatch_combine(flat, gate.gates[0],
                                             gate.experts[0], n, k, bm, gemm)
        return jax.jit(f)

    capacity = jax.jit(lambda ml, h: MoE(**moe_layer_kwargs(
        m, drop_tokens=False, dropless=False)).apply({"params": ml}, h, True))

    for rows in [int(r) for r in args.rows.split(",")]:
        h = jax.random.normal(keys[4], (rows, 1, E), jnp.float32).astype(
            jnp.bfloat16)
        rule = moe_tile_rows(rows, k, n)
        for bm in [int(t) for t in args.tiles.split(",")]:
            ms = timed(grouped(bm), ml, h)
            out["grouped_ms"][f"{rows}x{bm}"] = ms
            common.say(f"GROUPED rows {rows:5d} tile {bm:4d}"
                       f"{' (rule)' if bm == rule else '       '}: "
                       f"{ms:8.3f} ms  buffer {moe_padded_rows(rows, k, n, bm)}"
                       f" rows for {rows * k} routed")
    for rows in [int(r) for r in args.capacity_rows.split(",") if r]:
        # as serving shaped them: a decode step is [rows, 1], a prefill
        # chunk [1, rows] (capacity k x T a row)
        shape = (rows, 1, E) if rows <= 64 else (1, rows, E)
        h = jax.random.normal(keys[4], shape, jnp.float32).astype(jnp.bfloat16)
        try:
            ms = timed(capacity, ml, h, n=5)
        except Exception as e:  # noqa: BLE001 — report and go on
            common.say(f"CAPACITY {shape}: FAILED {str(e)[:200]}")
            continue
        out["capacity_ms"][str(rows)] = ms
        common.say(f"CAPACITY shape {shape}: {ms:8.3f} ms")
    os.makedirs(common.OUT_DIR, exist_ok=True)
    with open(os.path.join(common.OUT_DIR, "time_moe_layer.json"), "w") as f:
        json.dump(out, f, indent=1)
    return 0


if __name__ == "__main__":
    sys.exit(main())
