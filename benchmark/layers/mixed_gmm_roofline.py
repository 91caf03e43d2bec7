"""Kernels: least time the chip could take for the grouped GEMMs (``grouped_matmul_fwd``) of the decode programs in the traced window — ``work_mixed.grouped_matmul`` for the layers that HAVE experts — over their device time. Left out, loudly, where the calls are far from 3 x expert layers x decode iterations."""
from benchmark.layers import _mixed


def read(ctx):
    return _mixed.gmm_roofline(ctx)
