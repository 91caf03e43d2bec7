"""Train step: flax's ``layer_N/attn`` (forward, backward and recomputation) as
a share of the device self time of the train step program."""
from benchmark.layers import _scopes


def read(ctx):
    return _scopes.share(ctx, _scopes.TRAIN_PROGRAMS, ("layer/attn",))
