"""Cache manager: peak of the blocks live sequences held in the GLOBAL (full-attention) layers' pool over that pool's usable blocks (``kv_blocks_peak_full``; blocks are reserved at admission for prompt + max_new_tokens)."""
from benchmark.layers import _hybrid


def read(ctx):
    return _hybrid.pool_peak_util(ctx, "full")
