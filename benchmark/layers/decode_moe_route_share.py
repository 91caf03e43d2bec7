"""Engine programs: what routing costs around the expert GEMMs — router matmul and top-k, sort and scatter into the tile-aligned buffer, gather and gate-weighted sum (scopes ``moe_router`` + ``moe_dispatch`` + ``moe_combine``) — as a share of the device self time of the decode window and single-step programs."""
from benchmark.layers import _moe, _scopes


def read(ctx):
    return _scopes.share(ctx, _scopes.DECODE_PROGRAMS, _moe.ROUTE_SCOPES)
