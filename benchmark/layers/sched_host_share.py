"""Scheduler and engine host loop: host seconds in plan, dispatch (enqueue) and commit (engine.stats) over the traced window; waiting for the device (drain_block) is not in it."""
from benchmark.layers import _shared


def read(ctx):
    return _shared.host_share(ctx)
