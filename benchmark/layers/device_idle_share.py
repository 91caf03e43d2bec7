"""Device: 1 - union of device-op intervals over the traced window."""
from benchmark.layers import _shared


def read(ctx):
    return _shared.idle_share(ctx)
