"""Train step: everything remat runs again (``rematted_computation`` in the
op's path, any scope) as a share of the device self time of the train step
program. Cuts across the scope shares."""
from benchmark.layers import _scopes


def read(ctx):
    return _scopes.share(ctx, _scopes.TRAIN_PROGRAMS, direction="recompute")
