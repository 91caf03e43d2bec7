"""Quantized-weight matmul with in-tile dequantization — Pallas TPU kernel.

TPU-native equivalent of the reference's weight-only-quantized GEMMs
(/root/reference/deepspeed/inference/v2/kernels/cutlass_ops/mixed_gemm/ and
kernels/core_ops/cuda_linear/ FP6-LLM): the weight lives in HBM as int8 or
packed int4 codes plus per-(K-group, column) scales, and each grid step
dequantizes ONE [block_k, block_n] tile inside VMEM right before its MXU
contraction — bf16 weights are never materialized in HBM, so weight-read
bandwidth (the decode bottleneck) drops 2x/4x vs bf16.

Layout choices (designed for Mosaic, not translated from CUTLASS):
- codes int8 [K, N]; int4 packs K-row PAIRS into uint8 [K/2, N] (row r =
  rows 2r low nibble | 2r+1 high nibble). The kernel never interleaves
  sublanes: the caller pre-splits x into even/odd K columns and the kernel
  contracts xe @ lo + xo @ hi — two clean MXU dots per tile.
- scales fp32 [K/group, N], symmetric per group x column. Tiles iterate
  the groups with a STATIC python loop (group_size divides block_k), so
  scale broadcast is a plain [1, bn] * [g, bn] multiply.
Serving-only: no VJP (weights are frozen at inference).
"""
from __future__ import annotations

import functools
from typing import Any, NamedTuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from jax.experimental.pallas import tpu as pltpu


class QuantLinear(NamedTuple):
    """A weight-only-quantized [K, N] matrix (pytree node)."""
    data: jax.Array          # int8 [K, N] | uint8 [K/2, N] (int4 pairs)
    scale: jax.Array         # fp32 [K/group, N]
    bits: int
    group_size: int
    shape: tuple[int, int]   # (K, N)
    dtype: Any               # original compute dtype

    @property
    def nbytes(self) -> int:
        return self.data.nbytes + self.scale.nbytes


jax.tree_util.register_pytree_node(
    QuantLinear,
    lambda q: ((q.data, q.scale), (q.bits, q.group_size, q.shape, q.dtype)),
    lambda aux, ch: QuantLinear(*ch, *aux),
)


def _resolve_group(K: int, bits, group_size: int | None) -> int:
    if group_size is None:
        import math

        group_size = 128 if bits == 4 else 512
        if K % group_size:
            group_size = math.gcd(K, group_size) or K
    if K % group_size:
        raise ValueError(f"K={K} not divisible by group_size={group_size}")
    if bits == 4 and group_size % 2:
        raise ValueError("int4 needs an even group_size (K-pairs pack)")
    return group_size


def _quantize_slabs(w3: jax.Array, bits, G: int):
    """Shared quantization core over [n, K, Np] slabs (lane-padded):
    symmetric per-(slab, K-group, column). Returns (codes, scale) —
    int8 [n, K, Np] | uint8 [n, K/2, Np] (int4 K-pair pack) | fp8 codes;
    scale fp32 [n, K/G, Np]. ``quantize_weight`` is the n=1 view."""
    n, K, Np = w3.shape
    w32 = w3.astype(jnp.float32).reshape(n, K // G, G, Np)
    amax = jnp.max(jnp.abs(w32), axis=2, keepdims=True)
    if bits == "fp8":
        scale = jnp.where(amax > 0, amax / 448.0, 1.0)     # e4m3 max
        q = (w32 / scale).reshape(n, K, Np).astype(jnp.float8_e4m3fn)
        return q, scale[:, :, 0, :]
    qmax = float(2 ** (bits - 1) - 1)
    scale = jnp.where(amax > 0, amax / qmax, 1.0)          # [n, K/G, 1, Np]
    q = jnp.clip(jnp.round(w32 / scale), -qmax - 1, qmax)
    q = q.reshape(n, K, Np).astype(jnp.int8)
    if bits == 4:
        lo = (q[:, 0::2] + 8).astype(jnp.uint8)            # [n, K/2, Np]
        hi = (q[:, 1::2] + 8).astype(jnp.uint8)
        q = (lo | (hi << 4)).astype(jnp.uint8)
    return q, scale[:, :, 0, :]


def _dequantize_slabs(codes: jax.Array, scale: jax.Array, bits,
                      K: int, G: int) -> jax.Array:
    """Inverse of :func:`_quantize_slabs` → fp32 [n, K, Np]."""
    n, Np = codes.shape[0], codes.shape[-1]
    if bits in (8, "fp8"):
        c = codes.astype(jnp.float32)
    else:
        u = codes.astype(jnp.int32)
        lo = (u & 15) - 8
        hi = (u >> 4) - 8
        c = jnp.stack([lo, hi], axis=2).reshape(n, K, Np).astype(jnp.float32)
    return (c.reshape(n, K // G, G, Np) * scale[:, :, None, :]
            ).reshape(n, K, Np)


def quantize_weight(w: jax.Array, bits: int | str = 8,
                    group_size: int | None = None) -> QuantLinear:
    """Symmetric per-(K-group, column) quantization of a [K, N] weight.
    ``bits``: 8 | 4 | "fp8" (float8_e4m3 codes — same bytes as int8 with
    per-element dynamic range; the FP6-LLM/fp-quantizer role on a TPU
    whose native float8 dtype makes bit-packing unnecessary)."""
    assert bits in (4, 8, "fp8"), bits
    K, N = w.shape
    # pad N to the TPU lane width so every kernel tile is aligned (GPT-2's
    # 50257 vocab etc.); aux shape keeps the LOGICAL N — dequantize and
    # quant_matmul slice the pad back off
    n_pad = (-N) % 128
    if n_pad:
        w = jnp.pad(w, ((0, 0), (0, n_pad)))
    group_size = _resolve_group(K, bits, group_size)
    q, scale = _quantize_slabs(w[None], bits, group_size)
    return QuantLinear(q[0], scale[0], bits, group_size, (K, N), w.dtype)


def dequantize_weight(qw: QuantLinear) -> jax.Array:
    """Reference inverse (the XLA path the kernel is benchmarked against)."""
    K, N = qw.shape
    w = _dequantize_slabs(qw.data[None], qw.scale[None], qw.bits, K,
                          qw.group_size)[0]
    return w[:, :N].astype(qw.dtype)


def _qmm8_kernel(x_ref, d_ref, s_ref, o_ref, acc, *, G: int, dtype):
    k, nk = pl.program_id(2), pl.num_programs(2)

    @pl.when(k == 0)
    def _init():
        acc[:] = jnp.zeros_like(acc)

    bk = x_ref.shape[1]
    for g in range(bk // G):
        w = (d_ref[g * G:(g + 1) * G, :].astype(jnp.float32)
             * s_ref[0, g:g + 1, :]).astype(dtype)         # [G, bn]
        acc[:] += jax.lax.dot_general(
            x_ref[:, g * G:(g + 1) * G].astype(dtype), w,
            (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    @pl.when(k == nk - 1)
    def _fin():
        o_ref[:] = acc[:].astype(o_ref.dtype)


def _qmm8_kernel_l(li_ref, x_ref, d_ref, s_ref, o_ref, acc, *, G, dtype):
    """Stacked-layer variant: ``d_ref``/``s_ref`` carry a leading size-1
    layer block selected by the scalar-prefetched layer index — the weight
    tile DMAs straight from the [L, ...] stack, so a layer-scanned caller
    never materializes per-layer weight copies (measured r5: the scan's
    dynamic-slice of int8 codes cost ~0.57ms per decode iteration)."""
    del li_ref
    k, nk = pl.program_id(2), pl.num_programs(2)

    @pl.when(k == 0)
    def _init():
        acc[:] = jnp.zeros_like(acc)

    bk = x_ref.shape[1]
    for g in range(bk // G):
        w = (d_ref[0, g * G:(g + 1) * G, :].astype(jnp.float32)
             * s_ref[0, 0, g:g + 1, :]).astype(dtype)      # [G, bn]
        acc[:] += jax.lax.dot_general(
            x_ref[:, g * G:(g + 1) * G].astype(dtype), w,
            (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    @pl.when(k == nk - 1)
    def _fin():
        o_ref[:] = acc[:].astype(o_ref.dtype)


def _qmm4_kernel(xe_ref, xo_ref, d_ref, s_ref, o_ref, acc, *, G: int, dtype):
    k, nk = pl.program_id(2), pl.num_programs(2)

    @pl.when(k == 0)
    def _init():
        acc[:] = jnp.zeros_like(acc)

    h = G // 2                      # packed rows per group
    for g in range(xe_ref.shape[1] // h):
        u = d_ref[g * h:(g + 1) * h, :].astype(jnp.int32)
        s = s_ref[0, g:g + 1, :]
        lo = (((u & 15) - 8).astype(jnp.float32) * s).astype(dtype)
        hi = (((u >> 4) - 8).astype(jnp.float32) * s).astype(dtype)
        acc[:] += jax.lax.dot_general(
            xe_ref[:, g * h:(g + 1) * h].astype(dtype), lo,
            (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        acc[:] += jax.lax.dot_general(
            xo_ref[:, g * h:(g + 1) * h].astype(dtype), hi,
            (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    @pl.when(k == nk - 1)
    def _fin():
        o_ref[:] = acc[:].astype(o_ref.dtype)


def _qmm4_kernel_l(li_ref, xe_ref, xo_ref, d_ref, s_ref, o_ref, acc, *,
                   G: int, dtype):
    """Stacked-layer int4 variant (see ``_qmm8_kernel_l``)."""
    del li_ref
    k, nk = pl.program_id(2), pl.num_programs(2)

    @pl.when(k == 0)
    def _init():
        acc[:] = jnp.zeros_like(acc)

    h = G // 2
    for g in range(xe_ref.shape[1] // h):
        u = d_ref[0, g * h:(g + 1) * h, :].astype(jnp.int32)
        s = s_ref[0, 0, g:g + 1, :]
        lo = (((u & 15) - 8).astype(jnp.float32) * s).astype(dtype)
        hi = (((u >> 4) - 8).astype(jnp.float32) * s).astype(dtype)
        acc[:] += jax.lax.dot_general(
            xe_ref[:, g * h:(g + 1) * h].astype(dtype), lo,
            (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        acc[:] += jax.lax.dot_general(
            xo_ref[:, g * h:(g + 1) * h].astype(dtype), hi,
            (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    @pl.when(k == nk - 1)
    def _fin():
        o_ref[:] = acc[:].astype(o_ref.dtype)


#: row threshold below which int8/fp8 matmuls route through XLA's fused
#: dequant-dot instead of the Pallas tile kernel. At decode-sized M the
#: tile kernel is VPU-bound: every grid step dequantizes a full
#: [block_k, block_n] weight tile element-wise before a tiny MXU dot, so
#: the whole [K, N] weight pays VPU convert+multiply per call. XLA folds
#: the convert+multiply into the dot's operand READ (runs at HBM speed) —
#: measured on v5e, gpt2-350m logits [8,1024]@[1024,50257] int8: 122us
#: XLA fused vs 271us Pallas vs 138us bf16. Large M amortizes the tile
#: dequant over many rows and the Pallas kernel wins again (prefill).
#: int4 always keeps the kernel: XLA cannot fuse the nibble unpack.
SMALL_M_XLA = 16


def _xla_dequant_dot(x: jax.Array, qw, layer_index) -> jax.Array:
    """x @ dequant(codes) with the dequant left for XLA to fold into the
    dot's operand read — the decode-time (small-M) int8/fp8 path. The
    dequant algebra matches the kernel exactly: f32 codes x f32 group
    scales, cast to the compute dtype, then the dot."""
    data, scale = qw.data, qw.scale
    if layer_index is not None:
        data = data[layer_index]
        scale = scale[layer_index]
    K, N_logical = qw.shape
    G = qw.group_size
    w = (data.astype(jnp.float32).reshape(K // G, G, -1)
         * scale[:, None, :]).reshape(K, -1).astype(x.dtype)
    return (x @ w)[:, :N_logical]


def local_matmul(x: jax.Array, w, *,
                 layer_index: jax.Array | None = None) -> jax.Array:
    """Per-shard 2D matmul dispatch by weight type: ``QuantLinear`` routes
    through :func:`quant_matmul` (in-tile dequant Pallas kernel or the
    fused-XLA small-M dispatch — never a whole-shard dequantize), plain
    arrays run a dot with fp32 accumulation. The single local-GEMM entry
    the ring collective-matmul bodies (parallel/tensor.py) use, so
    dtype/quant routing decisions stay next to the kernels."""
    if isinstance(w, QuantLinear):
        return quant_matmul(x, w, layer_index=layer_index)
    wl = w
    if layer_index is not None and w.ndim == 3:
        wl = w[layer_index]
    return jnp.dot(x, wl, preferred_element_type=jnp.float32).astype(x.dtype)


def _pick(dim: int, want: int) -> int:
    if dim <= want:
        return dim
    for cand in (want, 1024, 512, 256, 128):
        if cand <= want and dim % cand == 0:
            return cand
    return dim


def quant_matmul(x: jax.Array, qw: QuantLinear, *,
                 layer_index: jax.Array | None = None,
                 block_m: int = 256, block_n: int = 512,
                 block_k: int = 512,
                 small_m_xla: bool | None = None,
                 interpret: bool | None = None) -> jax.Array:
    """x [M, K] @ dequant(qw) [K, N] -> [M, N] in x.dtype, weights
    dequantized tile-by-tile in VMEM.

    ``layer_index``: when the QuantLinear's arrays carry a leading layer
    dim ([L, K, N] codes from a ``jnp.stack`` over per-layer weights),
    selects the layer INSIDE the kernel via scalar prefetch — a
    layer-scanned caller passes the whole stack plus the loop index and
    never pays a per-layer dynamic-slice copy of the codes.

    ``small_m_xla``: None (auto) routes int8/fp8 calls with
    M <= ``SMALL_M_XLA`` rows through the XLA fused dequant-dot — the
    decode regime where the Pallas tile dequant is VPU-bound (see
    ``SMALL_M_XLA``). True/False forces the choice (tests; profiling).
    """
    M, K = x.shape
    Kw, N_logical = qw.shape
    N = qw.data.shape[-1]            # lane-padded columns
    stacked = layer_index is not None
    if K != Kw:
        raise ValueError(f"contract mismatch: x {x.shape} w {qw.shape}")
    if stacked and qw.data.ndim != 3:
        raise ValueError("layer_index given but codes are not stacked "
                         f"(data {qw.data.shape})")
    if qw.bits in (8, "fp8") and (
            small_m_xla if small_m_xla is not None else M <= SMALL_M_XLA):
        return _xla_dequant_dot(x, qw, layer_index)
    if interpret is None:
        from . import interpret_mode
        interpret = interpret_mode()
    G = qw.group_size
    bk = _pick(K, max(block_k, G))
    if bk % G:
        raise ValueError(f"block_k {bk} must be a multiple of group_size {G}")
    bn = _pick(N, block_n)
    Mp = M + (-M) % 8
    if Mp != M:
        x = jnp.pad(x, ((0, Mp - M), (0, 0)))
    bm = _pick(Mp, block_m)
    grid = (Mp // bm, N // bn, K // bk)
    # operand dtype for the tile dots: interpret mode runs on CPU, whose
    # dot thunk rejects bf16xbf16->f32; the TPU path keeps bf16 for the MXU
    mm_dtype = jnp.float32 if interpret else x.dtype
    out_dtype = x.dtype
    # scale rides as [K/bk, bk/G, N] so the block covers the whole middle
    # dim (Mosaic accepts block == array dim; a (1, bn) tile would not be)
    scale3 = qw.scale.reshape(*qw.scale.shape[:-2], K // bk, bk // G, N)

    int8_like = qw.bits in (8, "fp8")   # the int8 kernel's astype covers fp8
    if not stacked:
        s_spec = pl.BlockSpec((1, bk // G, bn), lambda m, n, k: (k, 0, n))
        x_specs = [pl.BlockSpec((bm, bk), lambda m, n, k: (m, k))] \
            if int8_like else \
            [pl.BlockSpec((bm, bk // 2), lambda m, n, k: (m, k))] * 2
        d_spec = pl.BlockSpec((bk, bn), lambda m, n, k: (k, n)) \
            if int8_like else \
            pl.BlockSpec((bk // 2, bn), lambda m, n, k: (k, n))
        kern = _qmm8_kernel if int8_like else _qmm4_kernel
        out = pl.pallas_call(
            functools.partial(kern, G=G, dtype=mm_dtype),
            grid=grid,
            in_specs=x_specs + [d_spec, s_spec],
            out_specs=pl.BlockSpec((bm, bn), lambda m, n, k: (m, n)),
            scratch_shapes=[pltpu.VMEM((bm, bn), jnp.float32)],
            out_shape=jax.ShapeDtypeStruct((Mp, N), out_dtype),
            name="quant_matmul",
            interpret=interpret,
        )(*((x,) if int8_like else (x[:, 0::2], x[:, 1::2])),
          qw.data, scale3)
    else:
        s_spec = pl.BlockSpec((1, 1, bk // G, bn),
                              lambda m, n, k, li: (li[0], k, 0, n))
        x_specs = [pl.BlockSpec((bm, bk), lambda m, n, k, li: (m, k))] \
            if int8_like else \
            [pl.BlockSpec((bm, bk // 2), lambda m, n, k, li: (m, k))] * 2
        d_spec = pl.BlockSpec((1, bk, bn),
                              lambda m, n, k, li: (li[0], k, n)) \
            if int8_like else \
            pl.BlockSpec((1, bk // 2, bn),
                         lambda m, n, k, li: (li[0], k, n))
        kern = _qmm8_kernel_l if int8_like else _qmm4_kernel_l
        grid_spec = pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=grid,
            in_specs=x_specs + [d_spec, s_spec],
            out_specs=pl.BlockSpec((bm, bn), lambda m, n, k, li: (m, n)),
            scratch_shapes=[pltpu.VMEM((bm, bn), jnp.float32)],
        )
        out = pl.pallas_call(
            functools.partial(kern, G=G, dtype=mm_dtype),
            grid_spec=grid_spec,
            out_shape=jax.ShapeDtypeStruct((Mp, N), out_dtype),
            name="quant_matmul_stacked",
            interpret=interpret,
        )(jnp.asarray(layer_index, jnp.int32).reshape(1),
          *((x,) if int8_like else (x[:, 0::2], x[:, 1::2])),
          qw.data, scale3)
    return out[:M, :N_logical]


# ---------------------------------------------------------------------------
# Grouped (per-expert) quantized GEMM — the reference's quantized MoE GEMM
# (/root/reference/deepspeed/inference/v2/kernels/cutlass_ops/moe_gemm/ with
# mixed_gemm's weight-only quantization applied to the expert weights).
# Same schedule as ops/pallas/grouped_matmul.py (expert-sorted token tiles,
# tile→expert scalar prefetch) with the in-tile dequant of the kernels
# above. Serving-only: no VJP.
# ---------------------------------------------------------------------------

class QuantGrouped(NamedTuple):
    """Weight-only-quantized stacked expert weights [n, K, N] (pytree)."""
    data: jax.Array          # int8 [n, K, N] | uint8 [n, K/2, N] (int4)
    scale: jax.Array         # fp32 [n, K/group, N]
    bits: int
    group_size: int
    shape: tuple[int, int, int]   # (n, K, N) logical
    dtype: Any

    @property
    def nbytes(self) -> int:
        return self.data.nbytes + self.scale.nbytes


jax.tree_util.register_pytree_node(
    QuantGrouped,
    lambda q: ((q.data, q.scale), (q.bits, q.group_size, q.shape, q.dtype)),
    lambda aux, ch: QuantGrouped(*ch, *aux),
)


def quantize_grouped(w: jax.Array, bits: int | str = 8,
                     group_size: int | None = None) -> QuantGrouped:
    """Symmetric per-(expert, K-group, column) quantization of stacked
    expert weights [n, K, N] — :func:`quantize_weight`'s grid applied per
    expert (same ``_quantize_slabs`` core)."""
    assert bits in (4, 8, "fp8"), bits
    n, K, N = w.shape
    n_pad = (-N) % 128
    if n_pad:
        w = jnp.pad(w, ((0, 0), (0, 0), (0, n_pad)))
    group_size = _resolve_group(K, bits, group_size)
    q, scale = _quantize_slabs(w, bits, group_size)
    return QuantGrouped(q, scale, bits, group_size, (n, K, N), w.dtype)


def dequantize_grouped(qw: QuantGrouped) -> jax.Array:
    """XLA reference inverse (tests + no-Pallas fallback)."""
    n, K, N = qw.shape
    w = _dequantize_slabs(qw.data, qw.scale, qw.bits, K, qw.group_size)
    return w[:, :, :N].astype(qw.dtype)


def _qgmm8_kernel(te_ref, x_ref, d_ref, s_ref, o_ref, acc, *, G: int, dtype):
    k, nk = pl.program_id(2), pl.num_programs(2)

    @pl.when(k == 0)
    def _init():
        acc[:] = jnp.zeros_like(acc)

    bk = x_ref.shape[1]
    for g in range(bk // G):
        w = (d_ref[0, g * G:(g + 1) * G, :].astype(jnp.float32)
             * s_ref[0, 0, g:g + 1, :]).astype(dtype)      # [G, bn]
        acc[:] += jax.lax.dot_general(
            x_ref[:, g * G:(g + 1) * G].astype(dtype), w,
            (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    @pl.when(k == nk - 1)
    def _fin():
        o_ref[:] = acc[:].astype(o_ref.dtype)


def _qgmm4_kernel(te_ref, xe_ref, xo_ref, d_ref, s_ref, o_ref, acc, *,
                  G: int, dtype):
    k, nk = pl.program_id(2), pl.num_programs(2)

    @pl.when(k == 0)
    def _init():
        acc[:] = jnp.zeros_like(acc)

    h = G // 2
    for g in range(xe_ref.shape[1] // h):
        u = d_ref[0, g * h:(g + 1) * h, :].astype(jnp.int32)
        s = s_ref[0, 0, g:g + 1, :]
        lo = (((u & 15) - 8).astype(jnp.float32) * s).astype(dtype)
        hi = (((u >> 4) - 8).astype(jnp.float32) * s).astype(dtype)
        acc[:] += jax.lax.dot_general(
            xe_ref[:, g * h:(g + 1) * h].astype(dtype), lo,
            (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        acc[:] += jax.lax.dot_general(
            xo_ref[:, g * h:(g + 1) * h].astype(dtype), hi,
            (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    @pl.when(k == nk - 1)
    def _fin():
        o_ref[:] = acc[:].astype(o_ref.dtype)


def _qgmm8_kernel_l(te_ref, li_ref, x_ref, d_ref, s_ref, o_ref, acc, *,
                    G: int, dtype):
    """Stacked-layer grouped variant (see ``_qmm8_kernel_l``)."""
    del li_ref
    k, nk = pl.program_id(2), pl.num_programs(2)

    @pl.when(k == 0)
    def _init():
        acc[:] = jnp.zeros_like(acc)

    bk = x_ref.shape[1]
    for g in range(bk // G):
        w = (d_ref[0, 0, g * G:(g + 1) * G, :].astype(jnp.float32)
             * s_ref[0, 0, 0, g:g + 1, :]).astype(dtype)   # [G, bn]
        acc[:] += jax.lax.dot_general(
            x_ref[:, g * G:(g + 1) * G].astype(dtype), w,
            (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    @pl.when(k == nk - 1)
    def _fin():
        o_ref[:] = acc[:].astype(o_ref.dtype)


def _qgmm4_kernel_l(te_ref, li_ref, xe_ref, xo_ref, d_ref, s_ref, o_ref,
                    acc, *, G: int, dtype):
    """Stacked-layer grouped int4 variant (see ``_qmm4_kernel_l``)."""
    del li_ref
    k, nk = pl.program_id(2), pl.num_programs(2)

    @pl.when(k == 0)
    def _init():
        acc[:] = jnp.zeros_like(acc)

    h = G // 2
    for g in range(xe_ref.shape[1] // h):
        u = d_ref[0, 0, g * h:(g + 1) * h, :].astype(jnp.int32)
        s = s_ref[0, 0, 0, g:g + 1, :]
        lo = (((u & 15) - 8).astype(jnp.float32) * s).astype(dtype)
        hi = (((u >> 4) - 8).astype(jnp.float32) * s).astype(dtype)
        acc[:] += jax.lax.dot_general(
            xe_ref[:, g * h:(g + 1) * h].astype(dtype), lo,
            (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        acc[:] += jax.lax.dot_general(
            xo_ref[:, g * h:(g + 1) * h].astype(dtype), hi,
            (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    @pl.when(k == nk - 1)
    def _fin():
        o_ref[:] = acc[:].astype(o_ref.dtype)


def quant_grouped_matmul(x: jax.Array, qw: QuantGrouped,
                         tile_expert: jax.Array, *,
                         layer_index: jax.Array | None = None,
                         block_m: int = 128,
                         block_n: int = 512, block_k: int = 512,
                         interpret: bool | None = None) -> jax.Array:
    """x [Tp, K] expert-sorted+aligned tokens (Tp % block_m == 0, every
    block_m tile owned by ONE expert, see ``sort_tokens_by_expert``)
    @ dequant(qw[e]) -> [Tp, N]. The tile→expert map rides as a scalar
    prefetch; each weight tile DMAs from its owner's slab and dequantizes
    in VMEM right before the MXU dot. ``layer_index`` selects a layer of
    a stacked [L, n, K, N] slab inside the kernel (see
    :func:`quant_matmul`)."""
    Tp, K = x.shape
    n_exp, Kw, N_logical = qw.shape
    N = qw.data.shape[-1]            # lane-padded
    stacked = layer_index is not None
    if K != Kw:
        raise ValueError(f"contract mismatch: x {x.shape} w {qw.shape}")
    if Tp % block_m:
        raise ValueError(f"tokens {Tp} not a multiple of block_m {block_m}")
    if stacked and qw.data.ndim != 4:
        raise ValueError("layer_index given but codes are not stacked "
                         f"(data {qw.data.shape})")
    if interpret is None:
        from . import interpret_mode
        interpret = interpret_mode()
    G = qw.group_size
    bk = _pick(K, max(block_k, G))
    if bk % G:
        raise ValueError(f"block_k {bk} must be a multiple of group_size {G}")
    bn = _pick(N, block_n)
    grid = (Tp // block_m, N // bn, K // bk)
    mm_dtype = jnp.float32 if interpret else x.dtype
    int8_like = qw.bits in (8, "fp8")
    half = bk if int8_like else bk // 2
    x_ops = (x,) if int8_like else (x[:, 0::2], x[:, 1::2])

    if not stacked:
        scale4 = qw.scale.reshape(n_exp, K // bk, bk // G, N)
        s_spec = pl.BlockSpec((1, 1, bk // G, bn),
                              lambda t, f, k, te: (te[t], k, 0, f))
        x_specs = [pl.BlockSpec((block_m, half),
                                lambda t, f, k, te: (t, k))] * len(x_ops)
        d_spec = pl.BlockSpec((1, half, bn),
                              lambda t, f, k, te: (te[t], k, f))
        kern = _qgmm8_kernel if int8_like else _qgmm4_kernel
        grid_spec = pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=grid,
            in_specs=x_specs + [d_spec, s_spec],
            out_specs=pl.BlockSpec((block_m, bn), lambda t, f, k, te: (t, f)),
            scratch_shapes=[pltpu.VMEM((block_m, bn), jnp.float32)],
        )
        out = pl.pallas_call(
            functools.partial(kern, G=G, dtype=mm_dtype),
            grid_spec=grid_spec,
            out_shape=jax.ShapeDtypeStruct((Tp, N), x.dtype),
            name="quant_grouped_matmul",
            interpret=interpret,
        )(tile_expert.astype(jnp.int32), *x_ops, qw.data, scale4)
    else:
        L = qw.data.shape[0]
        scale5 = qw.scale.reshape(L, n_exp, K // bk, bk // G, N)
        s_spec = pl.BlockSpec((1, 1, 1, bk // G, bn),
                              lambda t, f, k, te, li: (li[0], te[t], k, 0, f))
        x_specs = [pl.BlockSpec((block_m, half),
                                lambda t, f, k, te, li: (t, k))] * len(x_ops)
        d_spec = pl.BlockSpec((1, 1, half, bn),
                              lambda t, f, k, te, li: (li[0], te[t], k, f))
        kern = _qgmm8_kernel_l if int8_like else _qgmm4_kernel_l
        grid_spec = pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=grid,
            in_specs=x_specs + [d_spec, s_spec],
            out_specs=pl.BlockSpec((block_m, bn),
                                   lambda t, f, k, te, li: (t, f)),
            scratch_shapes=[pltpu.VMEM((block_m, bn), jnp.float32)],
        )
        out = pl.pallas_call(
            functools.partial(kern, G=G, dtype=mm_dtype),
            grid_spec=grid_spec,
            out_shape=jax.ShapeDtypeStruct((Tp, N), x.dtype),
            name="quant_grouped_matmul_stacked",
            interpret=interpret,
        )(tile_expert.astype(jnp.int32),
          jnp.asarray(layer_index, jnp.int32).reshape(1),
          *x_ops, qw.data, scale5)
    return out[:, :N_logical]
