"""Kernels: the grouped (per-expert) GEMM in its DECODE form (its calls inside the decode window and single-step programs): least possible time for the touched experts' weights and the routed rows over its device time."""
from benchmark.layers import _moe


def read(ctx):
    return _moe.grouped_roofline(ctx)
