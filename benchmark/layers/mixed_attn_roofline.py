"""Kernels: least time the chip could take for the paged attention of the decode programs in the traced window — the K/V of the layers that HAVE attention only, over the context each sequence has (``work_mixed.attn_decode_span``) — over the paged kernel's device time inside those programs. Left out, loudly, where the calls are far from attention layers x decode iterations."""
from benchmark.layers import _mixed


def read(ctx):
    return _mixed.attn_roofline(ctx)
