"""Kernels: grid steps of the paged attention kernel that read a page, over the steps of the slots x table-width rectangle around them (``attn_steps_live`` / ``attn_steps_rect``, the engine's counters over the traced window). A property of the traffic: how far the list the kernel walks is from the rectangle it used to. New in PR 26: where a program lacks the counters (a parent commit), the reader finds nothing and returns None."""
from benchmark.layers import _shared


def read(ctx):
    s = ctx["stats"]
    if not s.get("attn_steps_rect"):
        return None
    return _shared.pct(s["attn_steps_live"], s["attn_steps_rect"])
