"""Engine programs: the routed experts' grouped GEMMs and activation (scope ``moe_experts``) as a share of the device self time of the decode window and single-step programs."""
from benchmark.layers import _moe, _scopes


def read(ctx):
    return _scopes.share(ctx, _scopes.DECODE_PROGRAMS, _moe.EXPERT_SCOPES)
