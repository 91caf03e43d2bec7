"""Engine programs: the WINDOW layers' attention core (the ``attn_window`` scope inside ``attn_core``) as a share of the device self time of the decode window and single-step programs."""
from benchmark.layers import _hybrid


def read(ctx):
    return _hybrid.decode_attn_window_share(ctx)
