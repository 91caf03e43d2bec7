"""Engine programs, control: device self time of the decode programs under no
declared scope, or under an instruction name on which the compiled programs
of one module name disagree."""
from benchmark.layers import _scopes


def read(ctx):
    return _scopes.share(ctx, _scopes.DECODE_PROGRAMS, _scopes.REMAINDER)
