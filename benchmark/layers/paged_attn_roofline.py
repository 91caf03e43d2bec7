"""Kernels: the paged attention kernel in its DECODE form (its calls inside the decode window and single-step programs): least possible time for the K/V it had to read over its device time."""
from benchmark.layers import _shared


def read(ctx):
    return _shared.paged_roofline(ctx, "decode")
