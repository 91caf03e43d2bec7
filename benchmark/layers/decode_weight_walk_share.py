"""Engine programs: the layer-weight walk (``weight_walk``: the per-layer slice
of the stacked weights that rides the layer scan) as a share of the device
self time of the decode window and single-step programs."""
from benchmark.layers import _scopes


def read(ctx):
    return _scopes.share(ctx, _scopes.DECODE_PROGRAMS, ("weight_walk",))
