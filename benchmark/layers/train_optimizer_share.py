"""Train step: the update and the cast back (``optimizer``) plus the finite /
norm / clip checks (``grad_check``) as a share of the device self time of
the train step program."""
from benchmark.layers import _scopes


def read(ctx):
    return _scopes.share(ctx, _scopes.TRAIN_PROGRAMS, ("optimizer", "grad_check"))
