"""Cache manager: peak of the ring blocks live sequences held in the WINDOW layers' pool over that pool's usable blocks (``kv_blocks_peak_window``; the pool is every slot's whole ring, so this is the share of slots' rings in use)."""
from benchmark.layers import _hybrid


def read(ctx):
    return _hybrid.pool_peak_util(ctx, "window")
