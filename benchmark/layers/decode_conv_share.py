"""Engine programs: the gated short convolution (scope ``conv_mix``: a conv layer's two projections, its gates and its taps) as a share of the device self time of the decode window and single-step programs. New in PR 50: where a program has no such scope (a parent commit, a model without conv layers), the reader finds nothing and returns None."""
from benchmark.layers import _mixed


def read(ctx):
    return _mixed.conv_share(ctx)
