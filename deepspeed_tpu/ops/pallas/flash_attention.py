"""Flash attention as a Pallas TPU kernel (fwd + bwd), with GQA.

TPU-native replacement for the reference's fused attention CUDA kernels
(/root/reference/csrc/transformer/softmax_kernels.cu, attention paths of
csrc/transformer/inference/csrc/, and the flash-attn-2 port under
deepspeed/inference/v2/kernels/ragged_ops/blocked_flash/).

Design (standard TPU flash schedule):
- layout [B, H, S, D]; grid (B, H, num_q_blocks, num_kv_blocks) with the KV
  block index innermost. TPU grids execute sequentially per core, so the
  online-softmax state (m, l, acc) lives in VMEM scratch carried across the
  KV steps of one q block; output is written on the last KV step.
- causal masking is block-aware: fully-masked KV blocks are predicated off
  with @pl.when (no MXU work), the diagonal block applies an elementwise
  mask.
- GQA: the q-head grid index maps onto kv-head q_head // group in the
  BlockSpec index_map — K/V are never materialized per-q-head.
- backward: custom VJP. delta = rowsum(dO*O) precomputed in XLA. When the
  whole KV sequence fits one block (the common S <= 1024 training case) a
  single merged kernel produces dQ + per-q-head dK/dV in one launch with
  s/p computed once (measured +5.6% end-to-end train throughput on v5e vs
  the split pair). Otherwise: one kernel for dQ (grid over q blocks, KV
  innermost), one for per-q-head dK/dV (grid over kv blocks, Q innermost);
  dK/dV are group-summed to the KV heads outside the kernel.

Numerics: logits and softmax state in fp32 (preferred_element_type), inputs
bf16 or fp32.
"""
from __future__ import annotations

import functools
from typing import Any

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from jax.experimental.pallas import tpu as pltpu

NEG_INF = float(jnp.finfo(jnp.float32).min)

# Block policy, measured on v5e (gpt2-350m shapes, B8 H16 S1024 D64):
# per-grid-invocation overhead dominates small tiles — 128x128 blocks ran
# ~1000x slower than 256+, and fewer/fatter invocations kept winning
# (1024 > 512 > 256 in end-to-end bench). Blocks clamp to the sequence for
# short inputs (single-block grid). VMEM bounds the choice from above: the
# bwd kernels keep ~4 [bq,bk] fp32 intermediates plus the q/k/v/do blocks
# live, so the picker shrinks along _FAST_BLOCKS until the estimate fits.
DEFAULT_BLOCK_Q = 1024
DEFAULT_BLOCK_K = 1024
#: below this, the XLA fused attention is both fast and memory-cheap
MIN_SEQ = 128
#: divisor fallbacks, fastest first
_FAST_BLOCKS = (1024, 512, 256)
#: usable VMEM budget per core. 1024x1024 blocks (16 MiB of fp32
#: intermediates) measured to compile and run fastest on v5e — Mosaic
#: spills what doesn't fit — so the budget is a soft bound that still
#: rejects runaway combinations (long-seq x large-D fp32).
VMEM_BUDGET_BYTES = 24 * 1024 * 1024


def _vmem_estimate(bq: int, bk: int, d: int, dtype_bytes: int) -> int:
    """Rough peak VMEM of the bwd kernels: 4 fp32 [bq,bk] intermediates +
    double-buffered q/do [bq,d] and k/v [bk,d] blocks + fp32 scratch."""
    inter = 4 * bq * bk * 4
    blocks = 2 * (2 * bq * d + 2 * bk * d) * dtype_bytes
    scratch = (bq + bk) * d * 4
    return inter + blocks + scratch


def _pick_block(seq: int, requested: int | None = None) -> int | None:
    """Divisibility-only choice for one axis: an explicit request is honored
    when it divides the sequence; otherwise a whole-seq single block
    (seq <= default) or the largest fast divisor. None → unusable."""
    if requested is not None and requested < seq:
        return requested if seq % requested == 0 else None
    if seq <= DEFAULT_BLOCK_Q:
        return seq
    for cand in _FAST_BLOCKS:
        if seq % cand == 0:
            return cand
    return None


def _pick_blocks(Sq: int, Skv: int, d: int, dtype_bytes: int,
                 req_q: int | None = None, req_k: int | None = None
                 ) -> tuple[int, int] | None:
    """(block_q, block_k) satisfying divisibility AND the VMEM budget —
    the single source of truth for the gate and the kernel launcher.
    Explicit requests are honored verbatim (the caller owns the tradeoff)."""
    bq = _pick_block(Sq, req_q)
    bk = _pick_block(Skv, req_k)
    if bq is None or bk is None:
        return None
    if req_q is not None and req_k is not None:
        return bq, bk  # caller owns the whole tradeoff

    def next_down(cur, seq):
        for cand in _FAST_BLOCKS:
            if cand < cur and seq % cand == 0:
                return cand
        return None

    # shrink only axes the caller did NOT pin, larger axis first
    while _vmem_estimate(bq, bk, d, dtype_bytes) > VMEM_BUDGET_BYTES:
        cands = []
        if req_q is None:
            cands.append(("q", bq))
        if req_k is None:
            cands.append(("k", bk))
        cands.sort(key=lambda t: -t[1])
        for axis, _ in cands:
            if axis == "q":
                nxt = next_down(bq, Sq)
                if nxt is not None:
                    bq = nxt
                    break
            else:
                nxt = next_down(bk, Skv)
                if nxt is not None:
                    bk = nxt
                    break
        else:
            return None
    return bq, bk


def _interpret() -> bool:
    from . import interpret_mode
    return interpret_mode()


def flash_attention_unusable_reason(q, k, v, *, causal: bool,
                                    positions=None, mask=None) -> str:
    """Why the kernel cannot run these inputs (arrays or
    ``jax.ShapeDtypeStruct``s — only shapes and dtype are read); ``""``
    when it can. Full-sequence self-attention only (the decode/cached path
    has tiny q and is XLA's job).

    The shapes are the ones ONE kernel call sees: ``pallas_call`` has no
    GSPMD partitioning rule, so on a mesh the dispatcher
    (``ops/attention.py``) asks with the PER-SHARD shapes and runs the
    kernel inside ``shard_map``; whether a call is per shard is its
    question, not this gate's.
    """
    del causal, v
    if positions is not None or mask is not None:
        return "cached/masked attention (positions or mask given)"
    B, Sq, H, D = q.shape
    Skv, KV = k.shape[1], k.shape[2]
    if Sq != Skv:                      # prefill/training only
        return f"q length {Sq} != kv length {Skv}"
    if Sq < MIN_SEQ:                   # tiny: XLA is fast and cheap anyway
        return f"sequence {Sq} < {MIN_SEQ}"
    if _pick_blocks(Sq, Skv, D, jnp.dtype(q.dtype).itemsize) is None:
        return f"sequence {Sq} has no block divisor in {_FAST_BLOCKS}"
    if H % KV != 0:
        return f"{H} query heads not divisible by {KV} kv heads"
    # head_dim should map onto MXU lanes; smaller dims are padded by Mosaic
    # but we only claim the kernel when it is profitable.
    if D not in (64, 128, 256):
        return f"head_dim {D} not in (64, 128, 256)"
    return ""


def flash_attention_usable(q, k, v, **kw) -> bool:
    """Gate for the dispatcher — see
    :func:`flash_attention_unusable_reason`."""
    return not flash_attention_unusable_reason(q, k, v, **kw)


def _block_visible(causal: bool, q_start, k_start, block_q: int):
    """False iff the whole [block_q, block_k] tile is above the diagonal."""
    if not causal:
        return True
    return k_start <= q_start + block_q - 1


def _apply_causal_mask(s, q_start, k_start, block_q: int, block_k: int):
    q_pos = q_start + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 0)
    k_pos = k_start + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 1)
    return jnp.where(k_pos <= q_pos, s, NEG_INF)


# ---------------------------------------------------------------------------
# forward
# ---------------------------------------------------------------------------

def _fwd_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref,
                m_scr, l_scr, acc_scr, *, scale: float, causal: bool,
                block_q: int, block_k: int):
    qi = pl.program_id(2)
    kj = pl.program_id(3)
    nk = pl.num_programs(3)

    @pl.when(kj == 0)
    def _init():
        m_scr[:] = jnp.full_like(m_scr, NEG_INF)
        l_scr[:] = jnp.zeros_like(l_scr)
        acc_scr[:] = jnp.zeros_like(acc_scr)

    q_start = qi * block_q
    k_start = kj * block_k

    @pl.when(_block_visible(causal, q_start, k_start, block_q))
    def _body():
        q = q_ref[0, 0, :, :]
        k = k_ref[0, 0, :, :]
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)
        s = s * scale
        if causal:
            s = _apply_causal_mask(s, q_start, k_start, block_q, block_k)

        m_prev = m_scr[:]                       # [block_q, 1]
        m_cur = jnp.max(s, axis=-1, keepdims=True)
        m_new = jnp.maximum(m_prev, m_cur)
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(s - m_new)                  # [block_q, block_k]
        l_new = alpha * l_scr[:] + jnp.sum(p, axis=-1, keepdims=True)
        acc_scr[:] = acc_scr[:] * alpha + jax.lax.dot_general(
            p.astype(v_ref.dtype), v_ref[0, 0, :, :],
            (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)
        m_scr[:] = m_new
        l_scr[:] = l_new

    @pl.when(kj == nk - 1)
    def _finalize():
        l = l_scr[:]
        l_safe = jnp.where(l == 0.0, 1.0, l)
        o_ref[0, 0, :, :] = (acc_scr[:] / l_safe).astype(o_ref.dtype)
        lse_ref[0, 0, :, :] = m_scr[:] + jnp.log(l_safe)


def _fwd(q, k, v, *, causal: bool, scale: float,
         block_q: int, block_k: int):
    """q: [B,H,Sq,D]; k/v: [B,KV,Skv,D] → (out [B,H,Sq,D], lse [B,H,Sq,1]).

    lse is carried with a trailing singleton dim: TPU block shapes must have
    their last two dims divide (8, 128) or equal the array dims, which a
    (1, 1, block_q) block over [B, H, S] cannot satisfy."""
    B, H, Sq, D = q.shape
    KV, Skv = k.shape[1], k.shape[2]
    group = H // KV
    grid = (B, H, Sq // block_q, Skv // block_k)

    kernel = functools.partial(_fwd_kernel, scale=scale, causal=causal,
                               block_q=block_q, block_k=block_k)
    out, lse = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, block_q, D), lambda b, h, i, j: (b, h, i, 0)),
            pl.BlockSpec((1, 1, block_k, D),
                         lambda b, h, i, j: (b, h // group, j, 0)),
            pl.BlockSpec((1, 1, block_k, D),
                         lambda b, h, i, j: (b, h // group, j, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, block_q, D), lambda b, h, i, j: (b, h, i, 0)),
            pl.BlockSpec((1, 1, block_q, 1), lambda b, h, i, j: (b, h, i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B, H, Sq, D), q.dtype),
            jax.ShapeDtypeStruct((B, H, Sq, 1), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_q, 1), jnp.float32),
            pltpu.VMEM((block_q, 1), jnp.float32),
            pltpu.VMEM((block_q, D), jnp.float32),
        ],
        name="flash_attention_fwd",
        interpret=_interpret(),
    )(q, k, v)
    return out, lse


# ---------------------------------------------------------------------------
# backward
# ---------------------------------------------------------------------------

def _dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dq_ref,
               dq_scr, *, scale: float, causal: bool,
               block_q: int, block_k: int):
    kj = pl.program_id(3)
    nk = pl.num_programs(3)
    qi = pl.program_id(2)

    @pl.when(kj == 0)
    def _init():
        dq_scr[:] = jnp.zeros_like(dq_scr)

    q_start = qi * block_q
    k_start = kj * block_k

    @pl.when(_block_visible(causal, q_start, k_start, block_q))
    def _body():
        q = q_ref[0, 0, :, :]
        k = k_ref[0, 0, :, :]
        v = v_ref[0, 0, :, :]
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        if causal:
            s = _apply_causal_mask(s, q_start, k_start, block_q, block_k)
        p = jnp.exp(s - lse_ref[0, 0, :, :])                # [bq, bk]
        do = do_ref[0, 0, :, :]
        dp = jax.lax.dot_general(do, v, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        ds = p * (dp - delta_ref[0, 0, :, :]) * scale
        dq_scr[:] += jax.lax.dot_general(
            ds.astype(k.dtype), k, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    @pl.when(kj == nk - 1)
    def _finalize():
        dq_ref[0, 0, :, :] = dq_scr[:].astype(dq_ref.dtype)


def _dkv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                dk_ref, dv_ref, dk_scr, dv_scr, *, scale: float,
                causal: bool, block_q: int, block_k: int):
    kj = pl.program_id(2)
    qi = pl.program_id(3)
    nq = pl.num_programs(3)

    @pl.when(qi == 0)
    def _init():
        dk_scr[:] = jnp.zeros_like(dk_scr)
        dv_scr[:] = jnp.zeros_like(dv_scr)

    q_start = qi * block_q
    k_start = kj * block_k

    @pl.when(_block_visible(causal, q_start, k_start, block_q))
    def _body():
        q = q_ref[0, 0, :, :]
        k = k_ref[0, 0, :, :]
        v = v_ref[0, 0, :, :]
        do = do_ref[0, 0, :, :]
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        if causal:
            s = _apply_causal_mask(s, q_start, k_start, block_q, block_k)
        p = jnp.exp(s - lse_ref[0, 0, :, :])                # [bq, bk]
        # dV += P^T @ dO
        dv_scr[:] += jax.lax.dot_general(
            p.astype(do.dtype), do, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        dp = jax.lax.dot_general(do, v, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        ds = p * (dp - delta_ref[0, 0, :, :]) * scale        # [bq, bk]
        # dK += dS^T @ Q
        dk_scr[:] += jax.lax.dot_general(
            ds.astype(q.dtype), q, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    @pl.when(qi == nq - 1)
    def _finalize():
        dk_ref[0, 0, :, :] = dk_scr[:].astype(dk_ref.dtype)
        dv_ref[0, 0, :, :] = dv_scr[:].astype(dv_ref.dtype)


def _dqkv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                 dq_ref, dk_ref, dv_ref, dk_scr, dv_scr, *, scale: float,
                 causal: bool, block_q: int, block_k: int):
    """Merged backward for the single-kv-block case (Skv == block_k): one
    launch produces dQ, per-q-head dK and dV. s/p are computed once and
    shared (the split dq/dkv pair recomputes them), dK/dV accumulate in
    VMEM scratch across the q steps, dQ writes per q step."""
    qi = pl.program_id(2)
    nq = pl.num_programs(2)

    @pl.when(qi == 0)
    def _init():
        dk_scr[:] = jnp.zeros_like(dk_scr)
        dv_scr[:] = jnp.zeros_like(dv_scr)

    q_start = qi * block_q

    # k_start == 0 means every q block sees the diagonal — no fully-masked
    # tiles exist in the single-kv-block schedule, so the body always runs
    q = q_ref[0, 0, :, :]
    k = k_ref[0, 0, :, :]
    v = v_ref[0, 0, :, :]
    do = do_ref[0, 0, :, :]
    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32) * scale
    if causal:
        s = _apply_causal_mask(s, q_start, 0, block_q, block_k)
    p = jnp.exp(s - lse_ref[0, 0, :, :])                 # [bq, bk]
    # dV += P^T @ dO
    dv_scr[:] += jax.lax.dot_general(
        p.astype(do.dtype), do, (((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)
    dp = jax.lax.dot_general(do, v, (((1,), (1,)), ((), ())),
                             preferred_element_type=jnp.float32)
    ds = p * (dp - delta_ref[0, 0, :, :]) * scale        # [bq, bk]
    dq_ref[0, 0, :, :] = jax.lax.dot_general(
        ds.astype(k.dtype), k, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32).astype(dq_ref.dtype)
    # dK += dS^T @ Q
    dk_scr[:] += jax.lax.dot_general(
        ds.astype(q.dtype), q, (((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)

    @pl.when(qi == nq - 1)
    def _finalize():
        dk_ref[0, 0, :, :] = dk_scr[:].astype(dk_ref.dtype)
        dv_ref[0, 0, :, :] = dv_scr[:].astype(dv_ref.dtype)


def _bwd_merged(causal, scale, block_q, block_k, res, do):
    """Single-kv-block backward: one kernel launch instead of two."""
    q, k, v, out, lse = res
    B, H, Sq, D = q.shape
    KV, Skv = k.shape[1], k.shape[2]
    group = H // KV
    delta = jnp.sum(do.astype(jnp.float32) * out.astype(jnp.float32),
                    axis=-1, keepdims=True)                   # [B,H,Sq,1]

    grid = (B, H, Sq // block_q)
    dq, dk_h, dv_h = pl.pallas_call(
        functools.partial(_dqkv_kernel, scale=scale, causal=causal,
                          block_q=block_q, block_k=block_k),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, block_q, D), lambda b, h, i: (b, h, i, 0)),
            pl.BlockSpec((1, 1, block_k, D), lambda b, h, i: (b, h // group, 0, 0)),
            pl.BlockSpec((1, 1, block_k, D), lambda b, h, i: (b, h // group, 0, 0)),
            pl.BlockSpec((1, 1, block_q, D), lambda b, h, i: (b, h, i, 0)),
            pl.BlockSpec((1, 1, block_q, 1), lambda b, h, i: (b, h, i, 0)),
            pl.BlockSpec((1, 1, block_q, 1), lambda b, h, i: (b, h, i, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, block_q, D), lambda b, h, i: (b, h, i, 0)),
            pl.BlockSpec((1, 1, block_k, D), lambda b, h, i: (b, h, 0, 0)),
            pl.BlockSpec((1, 1, block_k, D), lambda b, h, i: (b, h, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B, H, Sq, D), q.dtype),
            jax.ShapeDtypeStruct((B, H, Skv, D), k.dtype),
            jax.ShapeDtypeStruct((B, H, Skv, D), v.dtype),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_k, D), jnp.float32),
            pltpu.VMEM((block_k, D), jnp.float32),
        ],
        name="flash_attention_bwd_dqkv",
        interpret=_interpret(),
    )(q, k, v, do, lse, delta)

    if group > 1:
        dk = dk_h.reshape(B, KV, group, Skv, D).sum(axis=2).astype(k.dtype)
        dv = dv_h.reshape(B, KV, group, Skv, D).sum(axis=2).astype(v.dtype)
    else:
        dk, dv = dk_h, dv_h
    return dq, dk, dv


def _bwd(causal, scale, block_q, block_k, res, do):
    q, k, v, out, lse = res
    B, H, Sq, D = q.shape
    KV, Skv = k.shape[1], k.shape[2]
    group = H // KV

    if Skv == block_k:
        return _bwd_merged(causal, scale, block_q, block_k, res, do)

    delta = jnp.sum(do.astype(jnp.float32) * out.astype(jnp.float32),
                    axis=-1, keepdims=True)                   # [B,H,Sq,1]

    grid_dq = (B, H, Sq // block_q, Skv // block_k)
    dq = pl.pallas_call(
        functools.partial(_dq_kernel, scale=scale, causal=causal,
                          block_q=block_q, block_k=block_k),
        grid=grid_dq,
        in_specs=[
            pl.BlockSpec((1, 1, block_q, D), lambda b, h, i, j: (b, h, i, 0)),
            pl.BlockSpec((1, 1, block_k, D),
                         lambda b, h, i, j: (b, h // group, j, 0)),
            pl.BlockSpec((1, 1, block_k, D),
                         lambda b, h, i, j: (b, h // group, j, 0)),
            pl.BlockSpec((1, 1, block_q, D), lambda b, h, i, j: (b, h, i, 0)),
            pl.BlockSpec((1, 1, block_q, 1), lambda b, h, i, j: (b, h, i, 0)),
            pl.BlockSpec((1, 1, block_q, 1), lambda b, h, i, j: (b, h, i, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, block_q, D),
                               lambda b, h, i, j: (b, h, i, 0)),
        out_shape=jax.ShapeDtypeStruct((B, H, Sq, D), q.dtype),
        scratch_shapes=[pltpu.VMEM((block_q, D), jnp.float32)],
        name="flash_attention_bwd_dq",
        interpret=_interpret(),
    )(q, k, v, do, lse, delta)

    # per-q-head dK/dV, grid over kv blocks with q innermost
    grid_dkv = (B, H, Skv // block_k, Sq // block_q)
    dk_h, dv_h = pl.pallas_call(
        functools.partial(_dkv_kernel, scale=scale, causal=causal,
                          block_q=block_q, block_k=block_k),
        grid=grid_dkv,
        in_specs=[
            pl.BlockSpec((1, 1, block_q, D), lambda b, h, j, i: (b, h, i, 0)),
            pl.BlockSpec((1, 1, block_k, D),
                         lambda b, h, j, i: (b, h // group, j, 0)),
            pl.BlockSpec((1, 1, block_k, D),
                         lambda b, h, j, i: (b, h // group, j, 0)),
            pl.BlockSpec((1, 1, block_q, D), lambda b, h, j, i: (b, h, i, 0)),
            pl.BlockSpec((1, 1, block_q, 1), lambda b, h, j, i: (b, h, i, 0)),
            pl.BlockSpec((1, 1, block_q, 1), lambda b, h, j, i: (b, h, i, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, block_k, D), lambda b, h, j, i: (b, h, j, 0)),
            pl.BlockSpec((1, 1, block_k, D), lambda b, h, j, i: (b, h, j, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B, H, Skv, D), k.dtype),
            jax.ShapeDtypeStruct((B, H, Skv, D), v.dtype),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_k, D), jnp.float32),
            pltpu.VMEM((block_k, D), jnp.float32),
        ],
        name="flash_attention_bwd_dkv",
        interpret=_interpret(),
    )(q, k, v, do, lse, delta)

    if group > 1:  # sum q-head contributions within each GQA group
        dk = dk_h.reshape(B, KV, group, Skv, D).sum(axis=2).astype(k.dtype)
        dv = dv_h.reshape(B, KV, group, Skv, D).sum(axis=2).astype(v.dtype)
    else:
        dk, dv = dk_h, dv_h
    return dq, dk, dv


# ---------------------------------------------------------------------------
# public entry: [B,S,H,D] layout to match ops.attention.dot_product_attention
# ---------------------------------------------------------------------------

@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6))
def _flash(q, k, v, causal, scale, block_q, block_k):
    out, _ = _fwd(q, k, v, causal=causal, scale=scale,
                  block_q=block_q, block_k=block_k)
    return out


def _flash_fwd(q, k, v, causal, scale, block_q, block_k):
    out, lse = _fwd(q, k, v, causal=causal, scale=scale,
                    block_q=block_q, block_k=block_k)
    return out, (q, k, v, out, lse)


_flash.defvjp(_flash_fwd, _bwd)


def flash_attention(q, k, v, *, causal: bool = True,
                    block_q: int | None = None,
                    block_k: int | None = None,
                    scale: float | None = None) -> Any:
    """q: [B,Sq,H,D]; k/v: [B,Skv,KV,D]. Returns [B,Sq,H,D]."""
    B, Sq, H, D = q.shape
    if scale is None:
        scale = 1.0 / (D ** 0.5)
    picked = _pick_blocks(Sq, k.shape[1], D, q.dtype.itemsize,
                          block_q, block_k)
    if picked is None:
        raise ValueError(
            f"flash_attention cannot block Sq={Sq}/Skv={k.shape[1]}: "
            f"sequences <= {DEFAULT_BLOCK_Q} run as one block, longer ones "
            f"need a divisor in {_FAST_BLOCKS} (pad the sequence, e.g. to a "
            f"multiple of {_FAST_BLOCKS[-1]}), and explicit block_q/block_k "
            f"must divide the sequence")
    block_q, block_k = picked
    if q.shape[2] % k.shape[2]:
        raise ValueError(
            f"GQA requires num q heads ({q.shape[2]}) divisible by kv heads "
            f"({k.shape[2]})")
    qt = jnp.swapaxes(q, 1, 2)          # [B,H,S,D]
    kt = jnp.swapaxes(k, 1, 2)
    vt = jnp.swapaxes(v, 1, 2)
    out = _flash(qt, kt, vt, causal, float(scale), block_q, block_k)
    return jnp.swapaxes(out, 1, 2)
