"""Engine programs: what the latent page costs beside the kernel — the down-projection ``W_dkv``, the latent's norm, the shared rope key, the keys' up-projection folded into the query (the absorb) and ``W_uv`` after the weighted sum: scope ``latent_absorb`` — as a share of the device self time of the decode window and single-step programs."""
from benchmark.layers import _latent


def read(ctx):
    return _latent.absorb_share(ctx)
