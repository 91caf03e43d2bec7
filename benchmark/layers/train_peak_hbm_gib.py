"""As peak_hbm_gib, in the training cell."""
from benchmark.layers import _shared


def read(ctx):
    return _shared.peak_hbm_gib(ctx)
