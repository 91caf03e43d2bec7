"""Kernels: the paged attention kernel in its DECODE form in a model of window and full layers: least possible time for the query-key pairs and K/V bytes with the window counted (a window layer reads min(context, window) keys a step: ``work_hybrid``) over the kernel's device time inside the decode programs."""
from benchmark.layers import _hybrid


def read(ctx):
    return _hybrid.hybrid_roofline(ctx)
