"""ZeRO and comm: device time with a collective in flight (all-gather,
reduce-scatter, all-reduce, all-to-all, collective-permute; an async pair
from its start to the end of its done), per step, mean over the chips."""


def read(ctx):
    c = ctx["trace"]["collectives"]
    return 1e3 * c["total_s"] / ctx["steps"] if c["total_s"] else None
