"""Arithmetic shared by the routed-expert readers (``read(ctx)`` as
``_shared``'s). Everything the program must publish for them is new in PR
25 — the ``moe_*`` scopes, the ``grouped_matmul_fwd`` kernel name and the
``moe_*_rows`` counters: where a program lacks one (a parent commit), the
reader finds nothing and returns None."""
from __future__ import annotations

import re

from benchmark import work, work_moe
from benchmark.common import say
from benchmark.layers import _shared

ROUTE_SCOPES = ("moe_router", "moe_dispatch", "moe_combine")
EXPERT_SCOPES = ("moe_experts",)
#: the bf16 grouped GEMM's name on the device's op line (the program names
#: its Pallas kernels: ``ops/pallas/grouped_matmul.py``)
GROUPED_KERNEL = re.compile(r"^grouped_matmul_fwd(\.\d+)?$")
#: grouped GEMMs a routed-expert layer launches (gate, up, down)
GEMMS_PER_LAYER = 3


def tile_fill(ctx):
    s = ctx["stats"]
    if not s.get("moe_padded_rows"):
        return None
    return _shared.pct(s["moe_routed_rows"], s["moe_padded_rows"])


def grouped_kernel(ctx):
    """(device seconds, calls) of the grouped GEMM inside the decode
    programs, or None — LOUDLY — where what the pattern matched cannot be
    that one kernel: a number of calls far from three a layer and decode
    iteration."""
    hit = [(op, secs, calls) for prog in _shared.DECODE_PROGRAMS
           for op, (secs, calls) in ctx["trace"]["ops_by_program"].get(
               prog, {}).items() if GROUPED_KERNEL.search(op)]
    if not hit:
        return None
    secs = sum(s for _, s, _ in hit)
    calls = sum(c for _, _, c in hit)
    want = GEMMS_PER_LAYER * work.shapes(ctx["model"])["L"] \
        * _shared.decode_iters(ctx)
    say(f"grouped GEMM, decode form: {secs:.4f} s in {calls:.0f} calls of "
        f"{sorted({op for op, _, _ in hit})}; 3 x layers x iterations = "
        f"{want}")
    if not want or not 0.5 <= calls / want <= 1.5:
        say("KERNEL NAME AMBIGUOUS: grouped_matmul_roofline left out (three "
            "calls a layer and decode iteration were expected)")
        return None
    return secs, calls


def grouped_roofline(ctx):
    """Least time the chip could take for the grouped GEMMs the decode
    programs ran in the traced window, over their device time. Work by
    ``work_moe.grouped_matmul`` for each (layer, iteration): the routed
    rows are the window's decode tokens x k; the experts touched are
    MODELLED — the engine does not report them — as the expectation under
    uniform routing at the window's mean decode batch (seeded random
    weights route near-uniformly; at 20 rows and more a step that is 60 of
    64 and more, so the model moves the bytes by a few per cent at most)."""
    kernel = grouped_kernel(ctx)
    if kernel is None or not ctx["peaks"]:
        return None
    cfg = ctx["model"]
    iters = _shared.decode_iters(ctx)
    tokens = ctx["tokens_emitted"]          # decode tokens of the window
    if not iters or not tokens:
        return None
    batch = tokens / iters
    touched = work_moe.experts_touched_uniform(cfg, batch)
    one = work_moe.grouped_matmul(cfg, batch * cfg["num_experts_per_tok"],
                                  touched)
    n = work.shapes(cfg)["L"] * iters
    total = {"flops": one["flops"] * n, "bytes": one["bytes"] * n}
    least, bound = work.least_time_s(total, ctx["peaks"])
    say(f"grouped GEMM, decode form: mean batch {batch:.1f} rows a step, "
        f"{touched:.1f} of {cfg['num_experts']} experts touched (modelled); "
        f"least {least:.4f} s ({bound} bound: {total['flops']:.3e} FLOPs, "
        f"{total['bytes']:.3e} bytes)")
    return 100.0 * least / kernel[0]
