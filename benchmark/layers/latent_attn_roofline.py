"""Kernels: least time the chip could take to read the latent rows the decode programs' attention needed in the traced window — every live context token x 1,152 B (``kv_lora_rank + qk_rope_head_dim`` in bf16, as needed, whatever the stored padding) x the layers, from each sequence's own progress (``work_latent.latent_decode_span``) — over the latent kernel's (``paged_latent_decode``) device time inside those programs. Left out, loudly, where the kernel's calls are far from layers x decode iterations."""
from benchmark.layers import _latent


def read(ctx):
    return _latent.attn_roofline(ctx)
