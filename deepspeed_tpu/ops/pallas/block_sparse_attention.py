"""Block-sparse flash attention as Pallas TPU kernels (fwd + bwd).

TPU-native replacement for the reference's Triton block-sparse compute
(/root/reference/deepspeed/ops/sparse_attention/{matmul.py,softmax.py} —
the SDD/softmax/DSD pipeline behind ``SparseSelfAttention``). Rather than
translating the Triton sampled-dense matmuls, the sparsity drives the
GRID: per query block, a scalar-prefetched table lists exactly the visible
key blocks, so masked blocks cost nothing — no DMA, no MXU work — and the
attention itself is the flash online-softmax recurrence from
flash_attention.py.

- fwd: grid (B, H, nq, max_nnz), table index j innermost; k/v BlockSpec
  index_maps read ``tbl[h, qi, j]``; steps with ``j >= cnt[h, qi]`` are
  predicated off (their DMA re-reads the previous block — cache-warm).
- bwd: custom VJP. dQ uses the same (q-major) table; dK/dV use the
  TRANSPOSED table (per key block, the query blocks that see it). delta is
  precomputed in XLA as in the dense flash kernel.
- causal: token-level triangular masking is applied inside diagonal
  blocks; block-level causality is the layout's job (unidirectional
  configs emit lower-triangular layouts).

Efficiency gate: layout blocks map 1:1 onto kernel tiles, so tiny sparsity
blocks (16/32) would drown in per-grid-step overhead — the dispatcher
claims the kernel for block >= 128 and falls back to the masked XLA path
otherwise (ops/sparse_attention.py keeps that as the reference numerics).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl

from jax.experimental.pallas import tpu as pltpu

NEG_INF = float(jnp.finfo(jnp.float32).min)

#: minimum layout block for the kernel to be profitable (per-grid-step
#: overhead; see flash_attention.py block policy notes)
MIN_BLOCK = 128


def layout_tables(layout: np.ndarray) -> tuple[np.ndarray, np.ndarray,
                                               np.ndarray, np.ndarray]:
    """Static per-head visibility tables from a [H, nq, nk] block layout:
    (tbl_q [H,nq,mk], cnt_q [H,nq], tbl_k [H,nk,mq], cnt_k [H,nk]) where
    ``tbl_q[h,i,:cnt_q[h,i]]`` are the key blocks query block i attends
    and ``tbl_k`` is the transpose (query blocks seeing each key block).
    Pad entries repeat index 0 (predicated off in-kernel)."""
    layout = np.asarray(layout, bool)
    H, nq, nk = layout.shape
    cnt_q = layout.sum(2).astype(np.int32)
    cnt_k = layout.sum(1).astype(np.int32)
    mk = max(int(cnt_q.max()), 1)
    mq = max(int(cnt_k.max()), 1)
    tbl_q = np.zeros((H, nq, mk), np.int32)
    tbl_k = np.zeros((H, nk, mq), np.int32)
    for h in range(H):
        for i in range(nq):
            idx = np.nonzero(layout[h, i])[0]
            tbl_q[h, i, :idx.size] = idx
        for j in range(nk):
            idx = np.nonzero(layout[h, :, j])[0]
            tbl_k[h, j, :idx.size] = idx
    return tbl_q, cnt_q, tbl_k, cnt_k


def block_sparse_usable(layout: np.ndarray, block: int, S: int, D: int,
                        H: int, KV: int) -> bool:
    if block < MIN_BLOCK or block % 8 or S % block:
        return False
    if H != KV:                      # GQA head mapping not wired yet
        return False
    return D in (64, 128, 256)


def _apply_masks(s, causal, qi, kb, block):
    """Token-level causal mask inside/above the diagonal block."""
    if not causal:
        return s
    q_pos = qi * block + jax.lax.broadcasted_iota(jnp.int32, s.shape, 0)
    k_pos = kb * block + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
    return jnp.where(k_pos <= q_pos, s, NEG_INF)


# ---------------------------------------------------------------------------
# forward
# ---------------------------------------------------------------------------

def _fwd_kernel(tbl_ref, cnt_ref, q_ref, k_ref, v_ref, o_ref, lse_ref,
                m_scr, l_scr, acc_scr, *, scale: float, causal: bool,
                block: int):
    h = pl.program_id(1)
    qi = pl.program_id(2)
    j = pl.program_id(3)
    nj = pl.num_programs(3)

    @pl.when(j == 0)
    def _init():
        m_scr[:] = jnp.full_like(m_scr, NEG_INF)
        l_scr[:] = jnp.zeros_like(l_scr)
        acc_scr[:] = jnp.zeros_like(acc_scr)

    @pl.when(j < cnt_ref[h, qi])
    def _body():
        kb = tbl_ref[h, qi, j]
        q = q_ref[0, 0]
        k = k_ref[0, 0]
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        s = _apply_masks(s, causal, qi, kb, block)
        m_prev = m_scr[:]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        # all-masked rows keep m == NEG_INF; guard the exp algebra so they
        # contribute 0 instead of nan (possible under sparse+causal)
        m_safe = jnp.where(m_new == NEG_INF, 0.0, m_new)
        alpha = jnp.where(m_prev == NEG_INF, 0.0, jnp.exp(m_prev - m_safe))
        p = jnp.exp(s - m_safe)
        l_scr[:] = alpha * l_scr[:] + jnp.sum(p, axis=-1, keepdims=True)
        acc_scr[:] = acc_scr[:] * alpha + jax.lax.dot_general(
            p.astype(v_ref.dtype), v_ref[0, 0], (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_scr[:] = m_new

    @pl.when(j == nj - 1)
    def _finalize():
        l = l_scr[:]
        l_safe = jnp.where(l == 0.0, 1.0, l)
        o_ref[0, 0] = (acc_scr[:] / l_safe).astype(o_ref.dtype)
        lse_ref[0, 0] = jnp.where(l == 0.0, NEG_INF, m_scr[:] + jnp.log(l_safe))


def _fwd(q, k, v, tbl_q, cnt_q, *, scale, causal, block, interpret):
    B, H, S, D = q.shape
    nq, mk = tbl_q.shape[1], tbl_q.shape[2]
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(B, H, nq, mk),
        in_specs=[
            pl.BlockSpec((1, 1, block, D),
                         lambda b, h, i, j, tbl, cnt: (b, h, i, 0)),
            pl.BlockSpec((1, 1, block, D),
                         lambda b, h, i, j, tbl, cnt: (b, h, tbl[h, i, j], 0)),
            pl.BlockSpec((1, 1, block, D),
                         lambda b, h, i, j, tbl, cnt: (b, h, tbl[h, i, j], 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, block, D),
                         lambda b, h, i, j, tbl, cnt: (b, h, i, 0)),
            pl.BlockSpec((1, 1, block, 1),
                         lambda b, h, i, j, tbl, cnt: (b, h, i, 0)),
        ],
        scratch_shapes=[
            pltpu.VMEM((block, 1), jnp.float32),
            pltpu.VMEM((block, 1), jnp.float32),
            pltpu.VMEM((block, D), jnp.float32),
        ],
    )
    return pl.pallas_call(
        functools.partial(_fwd_kernel, scale=scale, causal=causal,
                          block=block),
        grid_spec=grid_spec,
        out_shape=[
            jax.ShapeDtypeStruct((B, H, S, D), q.dtype),
            jax.ShapeDtypeStruct((B, H, S, 1), jnp.float32),
        ],
        name="block_sparse_fwd",
        interpret=interpret,
    )(tbl_q, cnt_q, q, k, v)


# ---------------------------------------------------------------------------
# backward
# ---------------------------------------------------------------------------

def _dq_kernel(tbl_ref, cnt_ref, q_ref, k_ref, v_ref, do_ref, lse_ref,
               delta_ref, dq_ref, dq_scr, *, scale, causal, block):
    h = pl.program_id(1)
    qi = pl.program_id(2)
    j = pl.program_id(3)
    nj = pl.num_programs(3)

    @pl.when(j == 0)
    def _init():
        dq_scr[:] = jnp.zeros_like(dq_scr)

    @pl.when(j < cnt_ref[h, qi])
    def _body():
        kb = tbl_ref[h, qi, j]
        q = q_ref[0, 0]
        k = k_ref[0, 0]
        v = v_ref[0, 0]
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        s = _apply_masks(s, causal, qi, kb, block)
        lse = lse_ref[0, 0]
        p = jnp.exp(s - jnp.where(lse == NEG_INF, 0.0, lse))
        do = do_ref[0, 0]
        dp = jax.lax.dot_general(do, v, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        ds = p * (dp - delta_ref[0, 0]) * scale
        dq_scr[:] += jax.lax.dot_general(
            ds.astype(k.dtype), k, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    @pl.when(j == nj - 1)
    def _finalize():
        dq_ref[0, 0] = dq_scr[:].astype(dq_ref.dtype)


def _dkv_kernel(tbl_ref, cnt_ref, q_ref, k_ref, v_ref, do_ref, lse_ref,
                delta_ref, dk_ref, dv_ref, dk_scr, dv_scr, *, scale, causal,
                block):
    h = pl.program_id(1)
    ki = pl.program_id(2)
    j = pl.program_id(3)
    nj = pl.num_programs(3)

    @pl.when(j == 0)
    def _init():
        dk_scr[:] = jnp.zeros_like(dk_scr)
        dv_scr[:] = jnp.zeros_like(dv_scr)

    @pl.when(j < cnt_ref[h, ki])
    def _body():
        qb = tbl_ref[h, ki, j]
        q = q_ref[0, 0]
        k = k_ref[0, 0]
        v = v_ref[0, 0]
        do = do_ref[0, 0]
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        s = _apply_masks(s, causal, qb, ki, block)
        lse = lse_ref[0, 0]
        p = jnp.exp(s - jnp.where(lse == NEG_INF, 0.0, lse))
        dv_scr[:] += jax.lax.dot_general(
            p.astype(do.dtype), do, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        dp = jax.lax.dot_general(do, v, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        ds = p * (dp - delta_ref[0, 0]) * scale
        dk_scr[:] += jax.lax.dot_general(
            ds.astype(q.dtype), q, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    @pl.when(j == nj - 1)
    def _finalize():
        dk_ref[0, 0] = dk_scr[:].astype(dk_ref.dtype)
        dv_ref[0, 0] = dv_scr[:].astype(dv_ref.dtype)


def _bwd(causal, scale, block, interpret, res, do):
    q, k, v, out, lse, tbl_q, cnt_q, tbl_k, cnt_k = res
    B, H, S, D = q.shape
    delta = jnp.sum(do.astype(jnp.float32) * out.astype(jnp.float32),
                    axis=-1, keepdims=True)
    nq, mk = tbl_q.shape[1], tbl_q.shape[2]
    nk, mq = tbl_k.shape[1], tbl_k.shape[2]

    qspec = pl.BlockSpec((1, 1, block, D),
                         lambda b, h, i, j, tbl, cnt: (b, h, i, 0))
    kspec = pl.BlockSpec((1, 1, block, D),
                         lambda b, h, i, j, tbl, cnt: (b, h, tbl[h, i, j], 0))
    vec_q = pl.BlockSpec((1, 1, block, 1),
                         lambda b, h, i, j, tbl, cnt: (b, h, i, 0))
    dq = pl.pallas_call(
        functools.partial(_dq_kernel, scale=scale, causal=causal, block=block),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(B, H, nq, mk),
            in_specs=[qspec, kspec, kspec, qspec, vec_q, vec_q],
            out_specs=qspec,
            scratch_shapes=[pltpu.VMEM((block, D), jnp.float32)],
        ),
        out_shape=jax.ShapeDtypeStruct((B, H, S, D), q.dtype),
        name="block_sparse_bwd_dq",
        interpret=interpret,
    )(tbl_q, cnt_q, q, k, v, do, lse, delta)

    # dK/dV: grid over key blocks, q blocks from the transposed table
    qspec_t = pl.BlockSpec((1, 1, block, D),
                           lambda b, h, i, j, tbl, cnt: (b, h, tbl[h, i, j], 0))
    kspec_t = pl.BlockSpec((1, 1, block, D),
                           lambda b, h, i, j, tbl, cnt: (b, h, i, 0))
    vec_t = pl.BlockSpec((1, 1, block, 1),
                         lambda b, h, i, j, tbl, cnt: (b, h, tbl[h, i, j], 0))
    dk, dv = pl.pallas_call(
        functools.partial(_dkv_kernel, scale=scale, causal=causal,
                          block=block),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(B, H, nk, mq),
            in_specs=[qspec_t, kspec_t, kspec_t, qspec_t, vec_t, vec_t],
            out_specs=[kspec_t, kspec_t],
            scratch_shapes=[pltpu.VMEM((block, D), jnp.float32),
                            pltpu.VMEM((block, D), jnp.float32)],
        ),
        out_shape=[jax.ShapeDtypeStruct((B, H, S, D), k.dtype),
                   jax.ShapeDtypeStruct((B, H, S, D), v.dtype)],
        name="block_sparse_bwd_dkv",
        interpret=interpret,
    )(tbl_k, cnt_k, q, k, v, do, lse, delta)
    return dq, dk, dv, None, None, None, None


@functools.partial(jax.custom_vjp, nondiff_argnums=(7, 8, 9, 10))
def _sparse_flash(q, k, v, tbl_q, cnt_q, tbl_k, cnt_k,
                  causal, scale, block, interpret):
    out, _ = _fwd(q, k, v, tbl_q, cnt_q, scale=scale, causal=causal,
                  block=block, interpret=interpret)
    return out


def _sparse_fwd(q, k, v, tbl_q, cnt_q, tbl_k, cnt_k,
                causal, scale, block, interpret):
    out, lse = _fwd(q, k, v, tbl_q, cnt_q, scale=scale,
                    causal=causal, block=block, interpret=interpret)
    return out, (q, k, v, out, lse, tbl_q, cnt_q, tbl_k, cnt_k)


_sparse_flash.defvjp(_sparse_fwd, _bwd)


def block_sparse_flash_attention(q, k, v, layout: np.ndarray, block: int,
                                 *, causal: bool = False,
                                 scale: float | None = None,
                                 interpret: bool | None = None):
    """q/k/v: [B, S, H, D]; ``layout`` [H, S//block, S//block] bool.
    Returns [B, S, H, D]; rows with no visible blocks return zeros
    (matching ops/sparse_attention.block_sparse_attention)."""
    B, S, H, D = q.shape
    if scale is None:
        scale = 1.0 / (D ** 0.5)
    if interpret is None:
        from . import interpret_mode
        interpret = interpret_mode()
    tbl_q, cnt_q, tbl_k, cnt_k = (jnp.asarray(t)
                                  for t in layout_tables(layout))
    qt, kt, vt = (jnp.swapaxes(x, 1, 2) for x in (q, k, v))
    out = _sparse_flash(qt, kt, vt, tbl_q, cnt_q, tbl_k, cnt_k,
                        causal, float(scale), block, interpret)
    return jnp.swapaxes(out, 1, 2)
