"""Sequence parallelism: Ulysses all-to-all attention + ring attention.

TPU-native counterpart of reference deepspeed/sequence/layer.py
(``DistributedAttention`` :145, ``_SeqAllToAll`` :90) and
deepspeed/sequence/cross_entropy.py. Two idioms are provided:

1. **GSPMD (default, used by the model zoo):** activations carry logical
   axis annotations; XLA inserts the seq<->head all-to-all pair around local
   attention automatically (models/transformer.py). Nothing to call here.

2. **Explicit (this module):** `shard_map`-based primitives for code that
   wants hand-scheduled communication — the exact algebra of the reference:

   - ``ulysses_attention`` / ``DistributedAttention``: all-to-all converts
     [B, S/n, H, D] (sequence-sharded) → [B, S, H/n, D] (head-sharded), runs
     ANY local attention on the full sequence, and converts back.
   - ``ring_attention``: blockwise online-softmax attention with K/V blocks
     rotating around the `seq` axis via ``ppermute`` — the long-context path
     the reference does NOT have (SURVEY §2.3: no ring/context parallelism
     upstream); comm rides ICI neighbor links and overlaps with compute.
   - ``gang_segment_attention``: the same blockwise algebra for ONE
     contiguous segment of a prompt whose earlier segments' KV was adopted
     from another replica — the engine-level math under serving gang
     prefill (serving/router.py), where the "ring" is the fleet itself.
   - ``vocab_parallel_cross_entropy``: stable CE over vocab-sharded logits.
"""
from __future__ import annotations

import functools
from typing import Callable

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P
from jax import shard_map

from .. import comm

NEG_INF = float(jnp.finfo(jnp.float32).min)


# ---------------------------------------------------------------------------
# Ulysses
# ---------------------------------------------------------------------------

def _ulysses_body(q, k, v, *, axis_name: str, attn_fn: Callable):
    """Per-shard body. q/k/v: [B, S/n, H, D] → out [B, S/n, H, D]."""
    # seq-shard → head-shard (reference _SeqAllToAll scatter_idx=2 :90)
    q = comm.all_to_all(q, axis_name, split_axis=2, concat_axis=1)
    k = comm.all_to_all(k, axis_name, split_axis=2, concat_axis=1)
    v = comm.all_to_all(v, axis_name, split_axis=2, concat_axis=1)
    out = attn_fn(q, k, v)
    # head-shard → seq-shard (gather_idx=1)
    out = comm.all_to_all(out, axis_name, split_axis=1, concat_axis=2)
    return out


def ulysses_attention(q, k, v, mesh, *, axis: str = "seq",
                      attn_fn: Callable | None = None,
                      causal: bool = True):
    """Full Ulysses attention over a mesh axis.

    q: [B, S, H, D]; k/v: [B, S, KV, D] — *global* shapes; the seq dim is
    sharded over `axis`. H and KV must be divisible by the axis size.
    """
    if attn_fn is None:
        from ..ops.attention import dot_product_attention

        # traced inside the shard_map below, where every mesh axis is
        # manual: the dispatcher sees per-shard shapes and may claim the
        # Pallas flash kernel for them
        attn_fn = functools.partial(dot_product_attention, causal=causal)
    n = mesh.shape[axis]
    if q.shape[2] % n or k.shape[2] % n:
        raise ValueError(
            f"num heads {q.shape[2]}/{k.shape[2]} not divisible by "
            f"seq-parallel degree {n}; pad or repeat KV heads first")
    spec = P(None, axis, None, None)
    fn = shard_map(
        functools.partial(_ulysses_body, axis_name=axis, attn_fn=attn_fn),
        mesh=mesh, in_specs=(spec, spec, spec), out_specs=spec,
        check_vma=False)
    return fn(q, k, v)


class DistributedAttention:
    """API-parity shim for reference sequence/layer.py:145.

    Wraps any local attention callable; __call__ takes sequence-sharded
    q/k/v and returns sequence-sharded output.
    """

    def __init__(self, local_attention: Callable, mesh,
                 *, axis: str = "seq"):
        self.local_attn = local_attention
        self.mesh = mesh
        self.axis = axis

    def __call__(self, query, key, value, *args, **kwargs):
        if args or kwargs:
            # extra args go AFTER q/k/v, matching the reference signature
            def attn(q, k, v):
                return self.local_attn(q, k, v, *args, **kwargs)
        else:
            attn = self.local_attn
        return ulysses_attention(query, key, value, self.mesh,
                                 axis=self.axis, attn_fn=attn)


# ---------------------------------------------------------------------------
# Ring attention (context parallelism)
# ---------------------------------------------------------------------------

def _ring_body(q, k, v, *, axis_name: str, causal: bool, scale: float):
    """Per-shard blockwise attention; k/v blocks rotate around the ring.

    q/k/v: [B, S_loc, H|KV, D]. Shard i owns global positions
    [i*S_loc, (i+1)*S_loc). Online softmax in fp32.
    """
    n = jax.lax.axis_size(axis_name)
    idx = comm.axis_index(axis_name)
    B, S, H, D = q.shape
    KV = k.shape[2]
    G = H // KV
    # grouped layout [B, S, KV, G, D]: K/V rotate un-repeated — each
    # ppermute moves [B,S,KV,D], not the G×-expanded tensor.
    qg = q.astype(jnp.float32).reshape(B, S, KV, G, D)
    q_pos = idx * S + jnp.arange(S)                      # [S]

    m = jnp.full((B, KV, G, S, 1), NEG_INF, jnp.float32)
    l = jnp.zeros((B, KV, G, S, 1), jnp.float32)
    acc = jnp.zeros((B, KV, G, S, D), jnp.float32)

    for step in range(n):
        src = (idx - step) % n                           # owner of current k/v
        s = jnp.einsum("bqhgd,bkhd->bhgqk", qg,
                       k.astype(jnp.float32)) * scale    # [B,KV,G,Sq,Sk]
        if causal:
            kv_pos = src * S + jnp.arange(S)             # [S] global
            allow = kv_pos[None, :] <= q_pos[:, None]    # [S_q, S_k]
            s = jnp.where(allow[None, None, None], s, NEG_INF)
        m_cur = jnp.max(s, axis=-1, keepdims=True)
        m_new = jnp.maximum(m, m_cur)
        # guard fully-masked blocks (exp(NEG_INF - NEG_INF) would be 1)
        p = jnp.where(s == NEG_INF, 0.0, jnp.exp(s - m_new))
        alpha = jnp.where(m == NEG_INF, 0.0, jnp.exp(m - m_new))
        l = alpha * l + jnp.sum(p, axis=-1, keepdims=True)
        acc = acc * alpha + jnp.einsum(
            "bhgqk,bkhd->bhgqd", p, v.astype(jnp.float32))
        m = m_new
        if step != n - 1:
            k = comm.send_recv_next(k, axis_name)        # rotate ring rightward
            v = comm.send_recv_next(v, axis_name)

    l_safe = jnp.where(l == 0.0, 1.0, l)
    out = (acc / l_safe).astype(q.dtype)                 # [B,KV,G,S,D]
    return out.transpose(0, 3, 1, 2, 4).reshape(B, S, H, D)


def ring_attention(q, k, v, mesh, *, axis: str = "seq", causal: bool = True,
                   scale: float | None = None):
    """Ring (context-parallel) attention over mesh axis `axis`.

    Global shapes q: [B,S,H,D], k/v: [B,S,KV,D]; S sharded over `axis`.
    Peak activation memory per chip is O(S_local * S_local) per block pair —
    supports sequences n× longer than single-chip attention.
    """
    if scale is None:
        scale = 1.0 / (q.shape[-1] ** 0.5)
    spec = P(None, axis, None, None)
    fn = shard_map(
        functools.partial(_ring_body, axis_name=axis, causal=causal,
                          scale=float(scale)),
        mesh=mesh, in_specs=(spec, spec, spec), out_specs=spec,
        check_vma=False)
    return fn(q, k, v)


# ---------------------------------------------------------------------------
# Gang-prefill segment attention (context parallelism across a FLEET)
# ---------------------------------------------------------------------------

def gang_segment_attention(q, k_prefix, v_prefix, k_own, v_own, *,
                           scale: float | None = None, block: int = 512):
    """Causal attention for ONE gang-prefill segment — context
    parallelism where the "devices" are serving replicas and the
    "rotation" is the staged KV hop between them (serving/router.py
    gang prefill).

    ``q``: [B, S_seg, H, D], the segment's queries. ``k_prefix`` /
    ``v_prefix``: [B, S_pre, KV, D], KV for every EARLIER segment
    (adopted from the upstream hop; S_pre may be 0 — gang member 0).
    ``k_own`` / ``v_own``: [B, S_seg, KV, D], this segment's KV.
    Segments are contiguous, so every prefix key strictly precedes
    every query: the prefix blocks fold in unmasked and only the own
    block carries a causal mask. Blockwise online softmax in fp32 —
    the exact ``_ring_body`` algebra with the ring replaced by a
    prefix walk — so the result equals rows [S_pre, S_pre + S_seg) of
    full causal attention over the concatenated sequence, bit-exactly
    in fp32. GQA folds H into KV groups like the ring path.
    """
    B, S, H, D = q.shape
    KV = k_own.shape[2]
    G = H // KV
    if H % KV:
        raise ValueError(f"heads {H} not divisible by kv heads {KV}")
    if scale is None:
        scale = 1.0 / (D ** 0.5)
    qg = q.astype(jnp.float32).reshape(B, S, KV, G, D)

    m = jnp.full((B, KV, G, S, 1), NEG_INF, jnp.float32)
    l = jnp.zeros((B, KV, G, S, 1), jnp.float32)
    acc = jnp.zeros((B, KV, G, S, D), jnp.float32)

    def fold(carry, k_blk, v_blk, allow):
        m, l, acc = carry
        s = jnp.einsum("bqhgd,bkhd->bhgqk", qg,
                       k_blk.astype(jnp.float32)) * scale
        if allow is not None:
            s = jnp.where(allow[None, None, None], s, NEG_INF)
        m_cur = jnp.max(s, axis=-1, keepdims=True)
        m_new = jnp.maximum(m, m_cur)
        # guard fully-masked rows (exp(NEG_INF - NEG_INF) would be 1)
        p = jnp.where(s == NEG_INF, 0.0, jnp.exp(s - m_new))
        alpha = jnp.where(m == NEG_INF, 0.0, jnp.exp(m - m_new))
        l = alpha * l + jnp.sum(p, axis=-1, keepdims=True)
        acc = acc * alpha + jnp.einsum("bhgqk,bkhd->bhgqd", p,
                                       v_blk.astype(jnp.float32))
        return m_new, l, acc

    S_pre = 0 if k_prefix is None else k_prefix.shape[1]
    carry = (m, l, acc)
    for lo in range(0, S_pre, block):
        hi = min(lo + block, S_pre)
        carry = fold(carry, k_prefix[:, lo:hi], v_prefix[:, lo:hi], None)
    allow = jnp.arange(S)[None, :] <= jnp.arange(S)[:, None]   # [S_q, S_k]
    m, l, acc = fold(carry, k_own, v_own, allow)

    l_safe = jnp.where(l == 0.0, 1.0, l)
    out = (acc / l_safe).astype(q.dtype)                 # [B,KV,G,S,D]
    return out.transpose(0, 3, 1, 2, 4).reshape(B, S, H, D)


# ---------------------------------------------------------------------------
# Vocab-parallel cross entropy (reference sequence/cross_entropy.py)
# ---------------------------------------------------------------------------

def _vp_ce_body(logits, labels, *, axis_name: str, ignore_index: int,
                seq_axis: str | None = None):
    """logits: [B, S/sp, V/n] local shard; labels: [B, S/sp] local ids."""
    idx = comm.axis_index(axis_name)
    V_loc = logits.shape[-1]
    lo = idx * V_loc

    logits = logits.astype(jnp.float32)
    local_max = jnp.max(logits, axis=-1)
    gmax = comm.all_reduce(local_max, axis_name, op="max")       # [B,S]
    sumexp = jnp.sum(jnp.exp(logits - gmax[..., None]), axis=-1)
    gsum = comm.all_reduce(sumexp, axis_name)                    # [B,S]

    in_shard = (labels >= lo) & (labels < lo + V_loc)
    local_label = jnp.clip(labels - lo, 0, V_loc - 1)
    picked = jnp.take_along_axis(logits, local_label[..., None],
                                 axis=-1)[..., 0]
    target_logit = comm.all_reduce(jnp.where(in_shard, picked, 0.0), axis_name)

    nll = jnp.log(gsum) + gmax - target_logit                    # [B,S]
    mask = (labels != ignore_index).astype(jnp.float32)
    num, den = jnp.sum(nll * mask), jnp.sum(mask)
    if seq_axis is not None:
        # sequence-sharded rows: the masked mean spans every seq shard
        # (ignore_index rows may be unevenly distributed across shards)
        num = comm.all_reduce(num, seq_axis)
        den = comm.all_reduce(den, seq_axis)
    return num / jnp.maximum(den, 1.0)


def vocab_parallel_cross_entropy(logits, labels, mesh, *,
                                 axis: str = "tensor",
                                 ignore_index: int = -100,
                                 seq_axis: str | None = None):
    """Cross entropy over vocab-sharded logits without materializing the
    full softmax on any chip. logits: [B,S,V] sharded over `axis` on dim 2;
    ``seq_axis`` additionally shards the sequence dim (seq×tensor training
    layouts) — the per-position algebra is shard-local either way, only the
    final masked mean gains a seq reduction.
    """
    fn = shard_map(
        functools.partial(_vp_ce_body, axis_name=axis,
                          ignore_index=ignore_index, seq_axis=seq_axis),
        mesh=mesh,
        in_specs=(P(None, seq_axis, axis), P(None, seq_axis)),
        out_specs=P(),
        check_vma=False)
    return fn(logits, labels)
