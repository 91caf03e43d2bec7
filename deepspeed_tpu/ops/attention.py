"""Attention op dispatcher.

The role of the reference fused attention kernels
(/root/reference/csrc/transformer/*.cu softmax/attention paths and the
blocked-flash FastGen kernels): one entry point that routes to
- a Pallas flash-attention kernel on TPU (ops/pallas/flash_attention.py), or
- a reference XLA implementation (fp32 softmax, GQA, causal/decode masks)
  that compiles everywhere and is the numerics oracle for kernel tests.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp


def _xla_attention(q, k, v, *, causal, positions, kv_len, mask, bias=None,
                   window=None):
    B, Sq, H, D = q.shape
    Skv, KV = k.shape[1], k.shape[2]
    scale = 1.0 / (D ** 0.5)
    if KV != H:
        rep = H // KV
        k = jnp.repeat(k, rep, axis=2)
        v = jnp.repeat(v, rep, axis=2)
    logits = jnp.einsum("bqhd,bkhd->bhqk", q, k).astype(jnp.float32) * scale

    kv_pos = jnp.arange(Skv)[None, None, None, :]  # [1,1,1,Skv]
    neg = jnp.finfo(jnp.float32).min
    if positions is not None:
        # decode/cached path: query i sits at absolute position positions[b,i]
        q_pos = positions[:, None, :, None]        # [B,1,Sq,1]
        allow = kv_pos <= q_pos
        if kv_len is not None:
            allow &= kv_pos < (kv_len if jnp.ndim(kv_len) == 0
                               else kv_len[:, None, None, None])
        if window:
            allow &= kv_pos > q_pos - window
        logits = jnp.where(allow, logits, neg)
    elif causal:
        q_pos = jnp.arange(Sq)[None, None, :, None]
        allow = kv_pos <= q_pos
        if window:       # mistral sliding window: attend the last W tokens
            allow &= kv_pos > q_pos - window
        logits = jnp.where(allow, logits, neg)
    if mask is not None:
        # mask: [B, Skv] (1 = attend) or broadcastable bool
        m = mask[:, None, None, :] if mask.ndim == 2 else mask
        logits = jnp.where(m.astype(bool), logits, neg)
    if bias is not None:
        # additive position bias (ALiBi etc.), broadcastable to [B,H,Sq,Skv]
        logits = logits + bias.astype(jnp.float32)

    weights = jax.nn.softmax(logits, axis=-1)
    out = jnp.einsum("bhqk,bkhd->bqhd", weights.astype(v.dtype), v)
    return out


def attention_formulation(q, k, v, *, causal: bool = True, positions=None,
                          mask=None, bias=None, impl: str = "auto",
                          window: int | None = None,
                          allow_multi_device: bool = False
                          ) -> tuple[str, str]:
    """``("pallas", "")`` when :func:`dot_product_attention` runs the
    flash kernel for these inputs, else ``("xla", why_not)``. Reads only
    shapes and dtypes, so ``jax.ShapeDtypeStruct``s serve — the training
    engine asks at build time and logs the answer, because ``auto``
    falling through to XLA is otherwise silent."""
    if impl == "xla":
        return "xla", "attn_impl='xla' (config pin)"
    if bias is not None or window:
        return "xla", ("additive bias (alibi) / sliding window have no "
                       "flash kernel path")
    from .pallas.flash_attention import flash_attention_unusable_reason

    why_not = flash_attention_unusable_reason(
        q, k, v, causal=causal, positions=positions, mask=mask,
        allow_multi_device=allow_multi_device)
    return ("xla", why_not) if why_not else ("pallas", "")


def dot_product_attention(q, k, v, *, causal: bool = True, positions=None,
                          kv_len=None, mask=None, bias=None, impl: str = "auto",
                          window: int | None = None,
                          allow_multi_device: bool = False):
    """q: [B,Sq,H,D]; k/v: [B,Skv,KV,D] (KV divides H for GQA).
    ``window``: sliding-window attention — query p attends keys in
    (p - window, p] (mistral; reference inference/v2 mistral impl).

    ``allow_multi_device`` must ONLY be set by callers running per-shard
    inside shard_map (e.g. parallel/sequence.py): pallas_call has no GSPMD
    partitioning rule, so claiming the kernel inside a pjit-sharded model on
    a multi-device mesh would force q/k/v replication. ``impl='pallas'``
    alone does not opt in.
    """
    if window and positions is None and not causal:
        raise ValueError("sliding_window requires causal attention "
                         "(bidirectional windows are not a thing here)")
    chosen, why_not = attention_formulation(
        q, k, v, causal=causal, positions=positions, mask=mask, bias=bias,
        impl=impl, window=window, allow_multi_device=allow_multi_device)
    if chosen == "pallas":
        from .pallas.flash_attention import flash_attention

        return flash_attention(q, k, v, causal=causal)
    if impl == "pallas":
        raise ValueError(f"pallas flash attention not usable for these "
                         f"inputs: {why_not}")
    return _xla_attention(q, k, v, causal=causal, positions=positions,
                          kv_len=kv_len, mask=mask, bias=bias, window=window)
