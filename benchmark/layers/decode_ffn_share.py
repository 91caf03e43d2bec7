"""Engine programs: the matmuls that must stream the weights once (``ffn`` +
``attn_qkv`` + ``attn_out`` + ``head``) as a share of the device self time
of the decode window and single-step programs."""
from benchmark.layers import _scopes


def read(ctx):
    return _scopes.share(ctx, _scopes.DECODE_PROGRAMS, ("ffn", "attn_qkv", "attn_out", "head"))
