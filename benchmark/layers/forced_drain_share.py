"""Dispatches after which the host had to block on the oldest readback, over all dispatches."""
from benchmark.layers import _shared


def read(ctx):
    return _shared.pct(ctx['stats']['forced_drains'], ctx['stats']['dispatches'])
