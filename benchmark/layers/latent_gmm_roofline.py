"""Kernels: least time the chip could take for the grouped GEMMs of the decode programs in the traced window — the routed rows' FLOPs and the touched experts' weights, for the FOUR layers that have experts only (``work_latent``; touched experts modelled from the window's mean LIVE decode batch under uniform routing) — over the grouped GEMM's device time inside those programs. Left out, loudly, where the calls are far from three a layer that HAS experts and decode iteration."""
from benchmark.layers import _latent


def read(ctx):
    return _latent.gmm_roofline(ctx)
