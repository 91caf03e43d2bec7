"""Kernels: paged attention and the staging of this step's K/V (``attn_core`` +
``kv_stage``) as a share of the device self time of the decode window and
single-step programs."""
from benchmark.layers import _scopes


def read(ctx):
    return _scopes.share(ctx, _scopes.DECODE_PROGRAMS, ("attn_core", "kv_stage"))
