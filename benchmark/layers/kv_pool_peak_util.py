"""Cache manager: peak of the KV blocks held by live sequences over the pool's usable blocks, sampled after every step of the traced window (blocks are reserved at admission for prompt + max_new_tokens)."""
from benchmark.layers import _shared


def read(ctx):
    return _shared.pct(ctx['kv_peak_blocks'], ctx['engine']['num_blocks'] - 1)
