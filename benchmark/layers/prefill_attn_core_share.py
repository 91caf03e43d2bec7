"""Kernels: paged attention and the staging of this step's K/V (``attn_core`` +
``kv_stage``) as a share of the device self time of the prefill step programs
(``_shared.PREFILL_PROGRAMS``): ``decode_attn_core_share``'s twin for the
steps a decoder waits through. Where a long prompt's chunks walk tens of
thousands of cached tokens (the latent kind's documents) it is most of a
step, and the step is most of a decoder's p90; where chunks are short the
feed-forward and the experts are. None where no prefill program ran in the
window or the program publishes no scope maps."""
from benchmark.layers import _scopes, _shared


def read(ctx):
    return _scopes.share(ctx, _shared.PREFILL_PROGRAMS,
                         ("attn_core", "kv_stage"))
