#!/usr/bin/env python3
"""tools/time_latent_layer.py — ONE layer's latent attention over the cache
at a configuration's published widths, alone on the chip, for a prefill
chunk over a long context: the ABSORBED form the engine serves (the latent
kernel over the paged ``[c | k_r]`` rows: ``W_uk`` folded into the query,
``W_uv`` after the weighted sum) against the EXPANDED form over the same
cache (gather the slot's rows, up-project them to per-head keys and values,
plain attention: 1.9 x fewer FLOPs at these contexts, but the up-projection
and the per-head keys and values of the whole context are made anew every
chunk, and the scores are not fused). Run by hand when the prefill form is
chosen or questioned (ISSUE 54, part C); not part of a benchmark run.

    python benchmark/tools/time_latent_layer.py \
        --config kanana-2-30b-a3b-serve --chunk 512 --contexts 16384,28672

Times are N back-to-back dispatches and one sync, per call, in ms."""
from __future__ import annotations

import argparse
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))

from benchmark import common  # noqa: E402
from benchmark.tools.time_moe_layer import timed  # noqa: E402


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--config", required=True)
    ap.add_argument("--chunk", type=int, default=512)
    ap.add_argument("--contexts", default="16384,28672")
    ap.add_argument("--rehearse", action="store_true")
    args = ap.parse_args()
    os.environ.setdefault("JAX_COMPILATION_CACHE_DIR", common.CACHE_DIR)
    import jax
    import jax.numpy as jnp

    from deepspeed_tpu.inference.engine_v2 import RaggedInferenceConfig
    from deepspeed_tpu.inference.forward import cache_kinds
    from deepspeed_tpu.models import get_model_config
    from deepspeed_tpu.ops.pallas.paged_attention import \
        paged_ragged_attention

    with open(os.path.join(common.HERE, "configs", f"{args.config}.json")) as f:
        conf = common.pick(json.load(f), args.rehearse)
    dev = common.require_device(1, args.rehearse)
    m = get_model_config(conf["preset"], **conf["overrides"])
    ecfg = RaggedInferenceConfig(**conf["engine"])
    (kind,) = cache_kinds(m, ecfg)
    bs, lanes = ecfg.block_size, kind.lanes
    H, R = m.num_heads, m.kv_lora_rank
    dn, dr, dv = m.qk_nope_head_dim, m.qk_rope_head_dim, m.v_head_dim
    scale = (dn + dr) ** -0.5
    T = args.chunk
    keys = jax.random.split(jax.random.PRNGKey(0), 6)
    bf = lambda key, shape, fan=1.0: (jax.random.normal(
        key, shape, jnp.float32) / fan ** 0.5).astype(jnp.bfloat16)
    w_uk, w_uv = bf(keys[0], (R, H, dn), R), bf(keys[1], (R, H, dv), R)
    out = {"device": dev, "config": args.config, "chunk": T, "ms": {}}
    for ctx in [int(c) for c in args.contexts.split(",")]:
        pages = -(-(ctx + T) // bs)
        pool = bf(keys[2], (1, 1, 1, pages + 1, bs, lanes))
        pool = pool.at[..., R + dr:].set(0)
        table = jnp.arange(1, pages + 1, dtype=jnp.int32)[None]
        q_nope = bf(keys[3], (1, T, H, dn))
        q_rope = bf(keys[4], (1, T, H, dr))
        row = bf(keys[5], (1, 1, T, lanes)).at[..., R + dr:].set(0)
        lens = jnp.asarray([ctx + T], jnp.int32)
        start = jnp.asarray([ctx], jnp.int32)

        @jax.jit
        def absorbed(pool, q_nope, q_rope, row):
            q = jnp.einsum("sthd,rhd->sthr", q_nope, w_uk)
            q = jnp.pad(jnp.concatenate([q, q_rope], -1),
                        [(0, 0)] * 3 + [(0, lanes - R - dr)])
            o = paged_ragged_attention(
                q, pool, row, None, table, lens, start, start,
                block_size=bs, layer_index=0, scale=scale, value_lanes=R)
            return jnp.einsum("sthr,rhd->sthd", o, w_uv)

        @jax.jit
        def expanded(pool, q_nope, q_rope, row):
            rows = jnp.concatenate(
                [pool[0, 0, 0, 1:].reshape(-1, lanes)[:ctx], row[0, 0]])
            c, k_r = rows[:, :R], rows[:, R:R + dr]
            k = jnp.einsum("cr,rhd->chd", c, w_uk)
            v = jnp.einsum("cr,rhd->chd", c, w_uv)
            s = (jnp.einsum("thd,chd->htc", q_nope[0], k)
                 + jnp.einsum("thd,cd->htc", q_rope[0], k_r)
                 ).astype(jnp.float32) * scale
            seen = (ctx + jnp.arange(T))[:, None] >= jnp.arange(ctx + T)[None]
            p = jax.nn.softmax(jnp.where(seen[None], s, -jnp.inf), -1)
            return jnp.einsum("htc,chd->thd", p.astype(v.dtype), v)[None]

        a = absorbed(pool, q_nope, q_rope, row)
        e = expanded(pool, q_nope, q_rope, row)
        diff = float(jnp.max(jnp.abs(a.astype(jnp.float32)
                                     - e.astype(jnp.float32))))
        for name, fn in (("absorbed", absorbed), ("expanded", expanded)):
            ms = timed(fn, pool, q_nope, q_rope, row, n=10)
            out["ms"][f"{name}@{ctx}"] = ms
            common.say(f"LATENT {name:8s} chunk {T} over {ctx:6d} tokens: "
                       f"{ms:8.3f} ms a layer (forms differ by {diff:.4f})")
    os.makedirs(common.OUT_DIR, exist_ok=True)
    with open(os.path.join(common.OUT_DIR, "time_latent_layer.json"),
              "w") as f:
        json.dump(out, f, indent=1)
    return 0


if __name__ == "__main__":
    sys.exit(main())
