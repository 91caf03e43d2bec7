"""Arithmetic shared by the readers of a model of two kinds of layer (full
attention over a table that grows; a window over a bounded ring).
Everything the program must publish for them is new in PR 41 — the
``attn_window`` / ``attn_full`` scopes inside ``attn_core``
(``profiling.trace.sub_scope_of``), the counters by kind
(``kv_blocks_peak_<kind>``, ``attn_pages_clipped``) and the engine's
``_kinds``: where a program lacks one (a parent commit), the reader finds
nothing and returns None."""
from __future__ import annotations

import os

from benchmark import reduce_trace, work, work_hybrid
from benchmark.common import say
from benchmark.layers import _scopes, _shared

_tables: dict = {}


def pool_peak_util(ctx, kind: str):
    """Peak of the blocks live sequences held in one kind's pool over that
    pool's usable blocks (the whole run's peak: blocks are reserved at
    admission, and the counter is sampled after every dispatch)."""
    peak = ctx["stats_total"].get(f"kv_blocks_peak_{kind}")
    pools = ctx.get("kv_pools") or pool_blocks(ctx["engine"], ctx["model"])
    if peak is None or not pools or kind not in pools:
        return None
    return _shared.pct(peak, pools[kind] - 1)


def pool_blocks(engine: dict, model: dict) -> dict | None:
    """Blocks of each kind's pool, from the configuration file as the
    program derives them (``engine_v2.cache_kinds``): the global layers'
    pool is ``num_blocks``, the window layers' every slot's whole ring."""
    try:
        from deepspeed_tpu.inference.engine_v2 import (
            RaggedInferenceConfig, cache_kinds)
        from deepspeed_tpu.models import get_model_config
    except ImportError:
        return None
    kinds = cache_kinds(get_model_config(model["preset"],
                                         **model["overrides"]),
                        RaggedInferenceConfig(**engine))
    return {k.name: k.num_blocks for k in kinds}


def window_clip_share(ctx):
    s = ctx["stats"]
    if not s.get("attn_pages_unclipped"):
        return None
    return _shared.pct(s["attn_pages_clipped"], s["attn_pages_unclipped"])


def kind_key(op):
    """(scope, kind of layer inside it or None) of one ``op_name`` path."""
    from deepspeed_tpu.profiling.trace import scope_of, sub_scope_of

    return scope_of(op)[0], sub_scope_of(op)


def kind_table(ctx) -> dict | None:
    """``{program: {(scope, kind of layer or None): seconds}}``: the scope
    table's join again, keyed by the kind of layer inside a scope. None
    where the scope table is (a rehearsal, a failed check) and where the
    program names no kinds (a parent commit)."""
    if ctx["trace"].get("host_only") or _scopes.table(ctx) is None:
        return None
    try:
        kind_key("")
        from deepspeed_tpu.profiling.trace import program_scope_maps
    except ImportError:
        return None
    path = ctx.get("trace_dir") or _scopes.trace_dir()
    if path not in _tables:
        maps = ctx.get("scope_maps")
        if maps is None:
            maps = program_scope_maps(set(ctx["trace"]["programs"]))
        planes = reduce_trace.load(reduce_trace.find_xplane(path)) \
            if os.path.isdir(path) else reduce_trace.load(path)
        _tables[path] = _scopes.join(planes, maps, kind_key)
    return _tables[path]


def decode_attn_window_share(ctx):
    """Window layers' attention core over the decode programs' device self
    time."""
    tab = kind_table(ctx)
    if tab is None:
        return None
    rows = [(k, s) for p in _scopes.DECODE_PROGRAMS
            for k, s in tab.get(p, {}).items()]
    total = sum(s for _, s in rows)
    kinds = {sub for (scope, sub), _ in rows if scope == "attn_core" and sub}
    if not total or not kinds:
        return None
    by = {sub: sum(s for (scope, k), s in rows
                   if scope == "attn_core" and k == sub) for sub in kinds}
    say("attention core of the decode programs by kind of layer: "
        + "; ".join(f"{k} {v:.4f} s {100 * v / total:.2f} %"
                    for k, v in sorted(by.items())))
    return 100.0 * by.get("attn_window", 0.0) / total


def hybrid_roofline(ctx):
    """``_shared.paged_roofline``'s decode form with the window counted:
    least time for the attention the decode programs ran in the traced
    window (every sequence's progress from ``a`` to ``b`` tokens past its
    prompt, a token a step: ``work_hybrid.decode_span``), over the paged
    kernel's device time inside those programs."""
    kernel = _shared.paged_kernel(ctx, "decode")
    if kernel is None or not ctx["peaks"]:
        return None
    cfg = ctx["model"]
    if "sliding_window_layout" not in cfg:
        return None
    before, after = ctx["progress"]
    uid_len = {ctx["uid_of"][t]: n for t, n in ctx["done_len"].items()
               if t in ctx["uid_of"]}
    flops = byts = 0.0
    for uid in set(before) | set(after):
        prompt, a = before.get(uid, (None, 0))
        if uid in after:
            prompt, b = after[uid]
        elif uid in uid_len:
            b = uid_len[uid]
        else:
            continue
        if prompt is None or b <= max(a, prompt):
            continue
        span = work_hybrid.decode_span(cfg, max(a, prompt), b)
        flops += span["flops"]
        byts += span["bytes"]
    if not flops:
        return None
    least, bound = work.least_time_s({"flops": flops, "bytes": byts},
                                     ctx["peaks"])
    say(f"paged attention kernel, decode form, window counted: least "
        f"{least:.4f} s ({bound} bound: {flops:.3e} FLOPs, {byts:.3e} "
        f"bytes) over {kernel[0]:.4f} s")
    return 100.0 * least / kernel[0]
