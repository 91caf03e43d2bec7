"""Cache manager: of the prefill rows dispatched in the traced window, the share that started from the record their sequence's last chunk left and not from zeros (``conv_chunks_carried`` / ``conv_chunks``, the engine's counters, booked on the host at dispatch): how much of the traffic works the hand-over of a conv layer's state between a long prompt's chunks. New in PR 50: where a program lacks the counters, the reader finds nothing and returns None."""
from benchmark.layers import _mixed


def read(ctx):
    return _mixed.carry_share(ctx)
