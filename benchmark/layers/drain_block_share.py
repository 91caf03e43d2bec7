"""Host seconds blocked on the oldest readback (engine.stats drain_block_s) over the traced window: the host waiting for the device."""
from benchmark.layers import _shared


def read(ctx):
    return _shared.drain_block_share(ctx)
