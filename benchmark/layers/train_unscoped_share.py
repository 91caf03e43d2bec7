"""Train step, control: device self time of the train step program under no
declared scope, or under an ambiguous instruction name."""
from benchmark.layers import _scopes


def read(ctx):
    return _scopes.share(ctx, _scopes.TRAIN_PROGRAMS, _scopes.REMAINDER)
