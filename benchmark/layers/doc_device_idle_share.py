"""As device_idle_share, in the document cell."""
from benchmark.layers import _shared


def read(ctx):
    return _shared.idle_share(ctx)
