"""Kernels: the paged attention kernel in its PREFILL form (its calls inside ``jit_step_prefill`` runs only): least possible time over its device time."""
from benchmark.layers import _shared


def read(ctx):
    return _shared.paged_roofline(ctx, "prefill")
