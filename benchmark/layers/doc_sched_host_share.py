"""As sched_host_share, in the document cell."""
from benchmark.layers import _shared


def read(ctx):
    return _shared.host_share(ctx)
