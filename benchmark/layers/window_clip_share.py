"""Cache manager: pool pages a table that grew with the context would have walked in the window layers and the window kind did not (``attn_pages_clipped`` over ``attn_pages_unclipped``, the engine's counters over the traced window): how much of the traffic is past the window at all."""
from benchmark.layers import _hybrid


def read(ctx):
    return _hybrid.window_clip_share(ctx)
