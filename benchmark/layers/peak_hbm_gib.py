"""Device: peak_bytes_in_use of the fullest device, GiB."""
from benchmark.layers import _shared


def read(ctx):
    return _shared.peak_hbm_gib(ctx)
