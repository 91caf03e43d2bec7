"""As device_idle_share, in the training cell (mean over the chips)."""
from benchmark.layers import _shared


def read(ctx):
    return _shared.idle_share(ctx)
