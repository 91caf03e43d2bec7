#!/usr/bin/env python3
"""tools/time_latent_prefill.py — the latent kernel's two forms of ONE
layer's prefill chunk over a long context, alone on the chip, at a
configuration's published widths: ABSORBED (``paged_ragged_attention`` with
``value_lanes``: ``W_uk`` folded into the query, ``W_uv`` after the weighted
sum — what every decode program runs) against EXPANDED
(``paged_latent_prefill``: a page up-projected in VMEM a head, attention a
head — what a prefill chunk runs since ISSUE 60), both over the same paged
cache, with the einsums each needs round its kernel. The expanded form also
at forced plans (``--groups``: heads a group; ``--passes``: heads a pass of
its inner loop). Run by hand when the prefill form is questioned; not part of
a benchmark run. ``tools/time_latent_layer.py`` holds the XLA einsum the
kernel replaced.

    python benchmark/tools/time_latent_prefill.py \
        --config kanana-2-30b-a3b-serve --chunk 512 --contexts 16384,28672

Times are N back-to-back dispatches and one sync, per call, in ms; the pace
is that over the pages a call walks (context + chunk), in us a page."""
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
    ap.add_argument("--groups", default="",
                    help="heads a group to force beside the plan's, e.g. 8,32")
    ap.add_argument("--passes", default="",
                    help="heads a pass of the inner loop to force, e.g. 2,4")
    ap.add_argument("--rehearse", action="store_true")
    args = ap.parse_args()
    os.environ.setdefault("JAX_COMPILATION_CACHE_DIR", common.CACHE_DIR)
    import jax
    import jax.numpy as jnp

    import deepspeed_tpu.ops.pallas.paged_attention as pa
    from deepspeed_tpu.inference.engine_v2 import RaggedInferenceConfig
    from deepspeed_tpu.inference.forward import cache_kinds
    from deepspeed_tpu.models import get_model_config

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
    plan0 = pa.latent_prefill_plan(T, H, R, dn, dr, dv, lanes, bs,
                                   jnp.bfloat16)
    common.say(f"LATENT plan: {plan0.describe() if plan0 else 'absorbed'}")
    forced = [(None, None)]
    forced += [(int(g), None) for g in args.groups.split(",") if g]
    forced += [(None, int(p)) for p in args.passes.split(",") if p]
    out = {"device": dev, "config": args.config, "chunk": T, "ms": {},
           "plan": plan0._asdict() if plan0 else None}
    real_plan, real_pass = pa.latent_prefill_plan, pa.LATENT_HEADS_A_PASS
    real_limit = pa.VMEM_LIMIT_BYTES
    for ctx in [int(c) for c in args.contexts.split(",")]:
        pages = -(-(ctx + T) // bs)
        pool = bf(keys[2], (1, 1, 1, pages + 1, bs, lanes))
        pool = pool.at[..., R + dr:].set(0)
        table = jnp.arange(1, pages + 1, dtype=jnp.int32)[None]
        q = bf(keys[3], (1, T, H, dn + dr))
        row = bf(keys[5], (1, 1, T, lanes)).at[..., R + dr:].set(0)
        lens = jnp.asarray([ctx + T], jnp.int32)
        start = jnp.asarray([ctx], jnp.int32)

        def absorbed(pool, q, row):
            qa = jnp.einsum("sthd,rhd->sthr", q[..., :dn], w_uk)
            qa = jnp.pad(jnp.concatenate([qa, q[..., dn:]], -1),
                         [(0, 0)] * 3 + [(0, lanes - R - dr)])
            o = pa.paged_ragged_attention(
                qa, pool, row, None, table, lens, start, start,
                block_size=bs, layer_index=0, scale=scale, value_lanes=R)
            return jnp.einsum("sthr,rhd->sthd", o, w_uv)

        def expanded(pool, q, row):
            return pa.paged_latent_prefill(
                q, w_uk, w_uv, pool, row, table, lens, start, start,
                block_size=bs, layer_index=0, scale=scale)

        want = jax.jit(absorbed)(pool, q, row).astype(jnp.float32)
        ms = timed(jax.jit(absorbed), pool, q, row, n=10)
        out["ms"][f"absorbed@{ctx}"] = ms
        common.say(f"LATENT absorbed            chunk {T} over {ctx:6d} "
                   f"tokens: {ms:8.3f} ms a layer, "
                   f"{ms * 1e3 / pages:6.2f} us a page")
        for hg, hb in forced:
            pa.LATENT_HEADS_A_PASS = hb or real_pass
            # a forced group may be larger than the plan's limit allows
            pa.VMEM_LIMIT_BYTES = 100 * 2 ** 20 if hg else real_limit
            pa.latent_prefill_plan = (
                lambda *a, _hg=hg: real_plan(*a)._replace(hg=_hg)) \
                if hg else real_plan
            tag = f"expanded[hg={hg or plan0.hg},hb={hb or real_pass}]"
            try:
                # a function of its own: the forced plan is no part of
                # jit's cache key
                fn = jax.jit(lambda *a: expanded(*a))
                got = fn(pool, q, row).astype(jnp.float32)
                diff = float(jnp.max(jnp.abs(got - want)))
                ms = timed(fn, pool, q, row, n=10)
            except Exception as e:  # noqa: BLE001 — a forced plan may not fit
                common.say(f"LATENT {tag}: refused: {str(e)[:300]}")
                continue
            finally:
                pa.latent_prefill_plan = real_plan
                pa.LATENT_HEADS_A_PASS = real_pass
                pa.VMEM_LIMIT_BYTES = real_limit
            out["ms"][f"{tag}@{ctx}"] = ms
            common.say(f"LATENT {tag:19s} chunk {T} over {ctx:6d} tokens: "
                       f"{ms:8.3f} ms a layer, {ms * 1e3 / pages:6.2f} us "
                       f"a page (forms differ by {diff:.4f})")
    os.makedirs(common.OUT_DIR, exist_ok=True)
    with open(os.path.join(common.OUT_DIR, "time_latent_prefill.json"),
              "w") as f:
        json.dump(out, f, indent=1)
    return 0


if __name__ == "__main__":
    sys.exit(main())
