"""Engine programs: routed (token, expert) rows over the rows of the tile-aligned buffers the grouped GEMMs walk (``moe_routed_rows`` / ``moe_padded_rows``, the engine's counters over the traced window)."""
from benchmark.layers import _moe


def read(ctx):
    return _moe.tile_fill(ctx)
