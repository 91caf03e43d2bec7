"""Grouped (per-expert) matmul as a Pallas TPU kernel — dropless MoE GEMM.

TPU-native equivalent of the reference's grouped expert GEMM
(/root/reference/deepspeed/inference/v2/kernels/cutlass_ops/moe_gemm/, the
CUTLASS grouped-GEMM behind FastGen MoE, and the expert GEMMs of
deepspeed/moe/sharded_moe.py). Megablocks-style formulation re-designed for
the TPU pipeline model:

- Tokens are sorted by expert and each expert's segment is padded up to a
  multiple of ``block_m`` (``sort_tokens_by_expert``), so every [block_m]
  token tile belongs to EXACTLY ONE expert. The tile→expert map rides in as
  a scalar-prefetch argument; the weight BlockSpec's index_map reads it to
  DMA that expert's weight tile — the "grouped" part costs one SMEM lookup
  per tile instead of a gather.
- Grid (column_tiles, token_tiles, k_tiles), k innermost. The weight block
  ``[bk, bn]`` comes from ONE plan (:func:`gmm_plan`), a pure function of
  the call's ``(K, N, block_m, dtype)`` and :data:`VMEM_BUDGET_BYTES`, never
  a caller's: K WHOLE wherever a ``[K, bn]`` block fits the budget
  double-buffered beside the x tile, the output tile and the dot's fp32
  result (at a ``bn`` of :data:`MIN_BLOCK_N` or N: no 128-wide strips),
  the column tile the widest multiple of 128 dividing N that still fits
  (N whole included), and K split — into its largest such divisor — only
  where it must be (Mixtral's 14336-deep down projection), never because
  K is not a power of two. What the plan guarantees wherever K is
  whole (every expert shape the benchmark serves: OLMoE 2048 x 1024,
  SmallThinker 2560 x 768, both ways round): ONE dot a step straight into
  the output tile (no accumulator scratch, no partial sums); token tiles
  run INSIDE a column tile, so consecutive tiles of one expert ask for the
  same weight block and Pallas skips the copy — an expert's weights are
  read once however many tiles its tokens fill, the small x tile is what is
  re-read; and a tile of the buffer's empty tail costs ``N / bn`` grid
  steps, one where N is whole. With K split the accumulator is fp32 VMEM
  scratch, written out on the last k step, and a second tile of an expert
  reads its weights again. The serving engine logs the plan of every
  distinct expert shape once, as a ``gmm:`` line, when it builds its
  programs (``inference/forward.py:RaggedForward.gmm``;
  :meth:`GmmPlan.describe`).
- Padding rows are zero → their outputs are zero and are never gathered
  back, so no masking is needed in the kernel. The buffer is FILLED without
  a scatter (``gather_expert_rows``: a one-hot matmul for a step of few
  tokens, a row gather for many; a TPU walks a scatter one update at a
  time), and the sort holds none either.
- The buffer is sized for the worst case (one partial tile an expert), so
  its tail tiles hold no token at all. A caller that passes the sort's
  ``n_tiles`` (the serving forward) has them skipped: no dot, and their
  weight index repeats the last used block, so nothing is copied either.
  At a decode step (a few rows an expert) that tail is a fifth to a half
  of the grid. Their output rows are left unwritten and are never gathered.
- ``layer_index`` selects a layer of a stacked ``[L, n, K, N]`` slab INSIDE
  the kernel (scalar prefetch): a layer sliced out of the stack in XLA is a
  copy of every expert's weights before the kernel reads them.

``grouped_matmul`` is differentiable: dx is the same kernel contracting
the other weight axis (``transpose_rhs``); dw is a second Pallas kernel
that accumulates x_tile^T @ dy_tile into the owning expert's [E, F] block
(token tiles innermost, so each expert's accumulation is a consecutive
grid run) — no [n_tiles, E, F] transient is ever materialized.
"""
from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from jax.experimental.pallas import tpu as pltpu


def _pick(dim: int, want: int) -> int:
    if dim <= want:
        return dim
    for cand in (want, 512, 256, 128, 64, 32, 16, 8):
        if cand <= want and dim % cand == 0:
            return cand
    return dim


#: what :meth:`GmmPlan.vmem_bytes` may come to: the buffers a launch states
#: (weight block, x tile and output tile, each double-buffered), the dot's
#: fp32 result and, where K is split, the fp32 accumulator. Compiled for a
#: described v5e each shape below takes a limit within 1 MiB of its
#: estimate. 12 MiB holds every expert matrix the benchmark serves
#: whole at a 128-row prefill tile (OLMoE 2048 x 1024: 10.0 / 10.5 MiB,
#: SmallThinker 2560 x 768: 9.5 / 10.4) and refuses the next size up.
VMEM_BUDGET_BYTES = 12 * 1024 * 1024
#: the scoped-VMEM limit handed to Mosaic with every launch: the budget and
#: half again for what Mosaic adds itself (as ``flash_attention.py`` does;
#: a v5e core has 128 MiB, the compiler's default is 16)
VMEM_LIMIT_BYTES = 18 * 1024 * 1024
#: the narrowest column tile K is kept whole for: 1 KiB of bf16 contiguous
#: in HBM a weight row. Under it a deeper block is a strip of short rows
MIN_BLOCK_N = 512


class GmmPlan(NamedTuple):
    """The weight block of one grouped-GEMM call — :func:`gmm_plan` makes
    it, ``_gmm_call`` reads it and the serving engine logs it."""
    K: int                  # contracting dim (x's columns)
    N: int                  # output columns
    block_m: int            # token rows a tile (the sort's alignment)
    bk: int                 # the weight block is [bk, bn] ...
    bn: int
    itemsize: int           # ... of elements this wide

    @property
    def nk(self) -> int:
        return self.K // self.bk

    @property
    def steps_per_tile(self) -> int:
        """Grid steps a token tile costs, a tile of the empty tail too."""
        return (self.N // self.bn) * self.nk

    @property
    def vmem_bytes(self) -> int:
        return _vmem_bytes(self.block_m, self.K, self.bk, self.bn,
                           self.itemsize)

    def describe(self) -> str:
        return (f"K {self.K} x N {self.N}: weight block {self.bk} x "
                f"{self.bn} ({self.bk * self.bn * self.itemsize / 2**20:.2f}"
                f" MiB), nk {self.nk}"
                + ("" if self.nk > 1 else
                   " (K whole: one dot a step, an expert's weights read "
                   "once however many tiles)")
                + f", {self.steps_per_tile} grid step"
                f"{'s' * (self.steps_per_tile > 1)} a tile of "
                f"{self.block_m} rows; VMEM {self.vmem_bytes / 2**20:.2f} "
                f"of {VMEM_BUDGET_BYTES / 2**20:.0f} MiB")


def _vmem_bytes(block_m: int, K: int, bk: int, bn: int, itemsize: int) -> int:
    blocks = bk * bn + block_m * bk + block_m * bn
    return 2 * blocks * itemsize + block_m * bn * 4 * (1 + (bk < K))


def _blocks(dim: int) -> list[int]:
    """Block sizes Mosaic takes along ``dim``, widest first: the dim
    itself, then its divisors that are multiples of 128."""
    return [dim] + [d for d in range((dim - 1) // 128 * 128, 0, -128)
                    if dim % d == 0]


def gmm_plan(K: int, N: int, block_m: int, dtype) -> GmmPlan:
    """The weight block for ``[Tp, K] x [n, K, N]`` at ``block_m`` rows a
    tile: the deepest ``bk`` — K whole first — whose block fits
    :data:`VMEM_BUDGET_BYTES` at the widest column tile not over
    :data:`MIN_BLOCK_N`, and then the widest ``bn`` that fits beside it. The
    LOCAL shape decides: under a tensor mesh N (or K) is the shard's. A dim
    with no divisor that is a multiple of 128 is taken whole whatever it
    comes to."""
    itemsize = jnp.dtype(dtype).itemsize
    fits = lambda bk, bn: _vmem_bytes(block_m, K, bk, bn, itemsize) \
        <= VMEM_BUDGET_BYTES
    bks, bns = _blocks(K), _blocks(N)
    floor = next((b for b in bns if b <= MIN_BLOCK_N), bns[-1])
    bk = next((b for b in bks if fits(b, floor)), bks[-1])
    bn = next((b for b in bns if fits(bk, b)), bns[-1])
    return GmmPlan(K=K, N=N, block_m=block_m, bk=bk, bn=bn,
                   itemsize=itemsize)


def _gmm_kernel(te_ref, nu_ref, li_ref, x_ref, w_ref, o_ref, *acc,
                transpose_rhs: bool, upcast: bool):
    t, k = pl.program_id(1), pl.program_id(2)
    nk = pl.num_programs(2)

    @pl.when(t < nu_ref[0])
    def _live():
        x = x_ref[...]                               # [bm, bk]
        w = w_ref[0, 0]                              # [bk, bn] | [bn, bk]
        if upcast:      # the CPU's dot thunk refuses bf16 x bf16 -> f32
            x, w = x.astype(jnp.float32), w.astype(jnp.float32)
        dims = (((1,), (1,)), ((), ())) if transpose_rhs \
            else (((1,), (0,)), ((), ()))
        prod = jax.lax.dot_general(x, w, dims,
                                   preferred_element_type=jnp.float32)
        if not acc:                                  # K whole: one dot
            o_ref[...] = prod.astype(o_ref.dtype)
            return
        acc_ref, = acc

        @pl.when(k == 0)
        def _init():
            acc_ref[:] = jnp.zeros_like(acc_ref)

        acc_ref[:] += prod

        @pl.when(k == nk - 1)
        def _finalize():
            o_ref[...] = acc_ref[:].astype(o_ref.dtype)


def _gmm_call(x, w, tile_expert, *, block_m: int, transpose_rhs: bool,
              interpret: bool | None, n_tiles=None, layer_index=None):
    """One ``grouped_matmul_fwd`` launch; its weight block is
    :func:`gmm_plan`'s for the shapes it is handed (a shard's, under a
    mesh) and nobody else's."""
    Tp, E = x.shape
    if layer_index is None:
        w = w[None]                                  # one "layer": a bitcast
    elif w.ndim != 4:
        raise ValueError(f"layer_index given but w {w.shape} is not stacked")
    if transpose_rhs:
        _, n_exp, N, K = w.shape                     # w [n, F, E], contract E
    else:
        _, n_exp, K, N = w.shape                     # w [n, E, F], contract E
    if K != E:
        raise ValueError(f"contracting dims mismatch: x {x.shape} w {w.shape}")
    if Tp % block_m:
        raise ValueError(f"tokens {Tp} not a multiple of block_m {block_m}")
    plan = gmm_plan(K, N, block_m, x.dtype)
    bk, bn, nk = plan.bk, plan.bn, plan.nk
    if interpret is None:
        from . import interpret_mode
        interpret = interpret_mode()

    n_all = Tp // block_m
    grid = (N // bn, n_all, nk)

    def w_k(t, k, nu):
        # a tile past the used ones repeats the block of the step before it
        return jnp.where(t < nu[0], k, nk - 1)

    if transpose_rhs:
        w_spec = pl.BlockSpec(
            (1, 1, bn, bk),
            lambda f, t, k, te, nu, li: (li[0], te[t], f, w_k(t, k, nu)))
    else:
        w_spec = pl.BlockSpec(
            (1, 1, bk, bn),
            lambda f, t, k, te, nu, li: (li[0], te[t], w_k(t, k, nu), f))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=grid,
        in_specs=[
            pl.BlockSpec((block_m, bk), lambda f, t, k, te, nu, li: (t, k)),
            w_spec,
        ],
        out_specs=pl.BlockSpec((block_m, bn),
                               lambda f, t, k, te, nu, li: (t, f)),
        # K whole: the dot goes straight to the output tile
        scratch_shapes=[pltpu.VMEM((block_m, bn), jnp.float32)] * (nk > 1),
    )
    one = lambda v, default: jnp.asarray(
        default if v is None else v, jnp.int32).reshape(1)
    return pl.pallas_call(
        functools.partial(_gmm_kernel, transpose_rhs=transpose_rhs,
                          upcast=bool(interpret)),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((Tp, N), x.dtype),
        name="grouped_matmul_fwd",
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=VMEM_LIMIT_BYTES),
        interpret=interpret,
    )(tile_expert.astype(jnp.int32), one(n_tiles, n_all),
      one(layer_index, 0), x, w)


def grouped_matmul_layer(x, w, tile_expert, n_tiles, block_m: int,
                         layer_index=None, interpret: bool | None = None):
    """Forward-only form for serving: the sort's ``tile_expert`` and
    ``n_tiles`` (the kernel skips the buffer's empty tail), and ``w`` may
    be the depth-stacked ``[L, n, E, F]`` slab with ``layer_index`` picking
    the layer inside the kernel."""
    return _gmm_call(x, w, tile_expert, block_m=block_m,
                     transpose_rhs=False, interpret=interpret,
                     n_tiles=n_tiles, layer_index=layer_index)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4))
def grouped_matmul(x, w, tile_expert, block_m: int = 128,
                   interpret: bool | None = None):
    """x: [Tp, E] expert-sorted+aligned tokens; w: [n_exp, E, F];
    tile_expert: [Tp // block_m] int32 — expert owning each token tile.
    Returns [Tp, F]."""
    return _gmm_call(x, w, tile_expert, block_m=block_m, transpose_rhs=False,
                     interpret=interpret)


def _gmm_fwd(x, w, tile_expert, block_m, interpret):
    out = _gmm_call(x, w, tile_expert, block_m=block_m, transpose_rhs=False,
                    interpret=interpret)
    return out, (x, w, tile_expert)


def _dw_kernel(te_ref, x_ref, dy_ref, o_ref, acc):
    t = pl.program_id(2)
    nt = pl.num_programs(2)
    te = te_ref[t]

    # first/last tile of this expert's consecutive run (tile_expert is
    # nondecreasing, so each output block's visits are contiguous in t)
    @pl.when((t == 0) | (te != te_ref[jnp.maximum(t - 1, 0)]))
    def _init():
        acc[:] = jnp.zeros_like(acc)

    acc[:] += jax.lax.dot_general(x_ref[...], dy_ref[...],
                                  (((0,), (0,)), ((), ())),
                                  preferred_element_type=jnp.float32)

    @pl.when((t == nt - 1) | (te != te_ref[jnp.minimum(t + 1, nt - 1)]))
    def _finalize():
        o_ref[0] = acc[:].astype(o_ref.dtype)


def _dw_call(x, dy, tile_expert, n_exp: int, *, block_m: int,
             interpret: bool | None):
    """dw[e] = sum_{tiles of e} x_tile^T @ dy_tile, accumulated in VMEM.
    Peak transient is one [block_e, block_f] fp32 block per grid step —
    the [n_tiles, E, F] outer-product tensor of the naive formulation
    (multi-GB at 64k routed rows) never exists."""
    Tp, E = x.shape
    F = dy.shape[1]
    be = _pick(E, 512)
    bf = _pick(F, 512)
    if interpret is None:
        from . import interpret_mode
        interpret = interpret_mode()
    grid = (E // be, F // bf, Tp // block_m)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=grid,
        in_specs=[
            pl.BlockSpec((block_m, be), lambda e, f, t, te: (t, e)),
            pl.BlockSpec((block_m, bf), lambda e, f, t, te: (t, f)),
        ],
        out_specs=pl.BlockSpec((1, be, bf), lambda e, f, t, te: (te[t], e, f)),
        scratch_shapes=[pltpu.VMEM((be, bf), jnp.float32)],
    )
    dw = pl.pallas_call(
        _dw_kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((n_exp, E, F), jnp.float32),
        name="grouped_matmul_bwd_dw",
        interpret=interpret,
    )(tile_expert.astype(jnp.int32), x, dy)
    # experts that own no tiles were never written — mask their garbage
    has = jnp.any(tile_expert[:, None] == jnp.arange(n_exp)[None], axis=0)
    return jnp.where(has[:, None, None], dw, 0.0)


def _gmm_bwd(block_m, interpret, res, dy):
    x, w, tile_expert = res
    n_exp = w.shape[0]
    # dx[t] = dy[t] @ w[e_t]^T — same kernel, contracting w's F axis
    dx = _gmm_call(dy, w, tile_expert, block_m=block_m, transpose_rhs=True,
                   interpret=interpret)
    dw = _dw_call(x, dy, tile_expert, n_exp, block_m=block_m,
                  interpret=interpret).astype(w.dtype)
    return dx.astype(x.dtype), dw, None


grouped_matmul.defvjp(_gmm_fwd, _gmm_bwd)


class ExpertSort(NamedTuple):
    """In-jit dropless dispatch layout (static shapes throughout). The way
    into the buffer is a GATHER, not a scatter: ``src`` names, for every
    buffer row, what it holds; ``dst`` names, for every (token, choice),
    its row — the way back, and what the dense fill compares against."""
    dst: jax.Array          # [T*k] buffer row of each (token, choice);
                            # Tp (no row) for a masked token's
    tile_expert: jax.Array  # [Tp // block_m] expert owning each token tile
    Tp: int                 # static padded buffer length
    n_tiles: jax.Array      # scalar: tiles that hold a token (the rest of
                            # the buffer is its worst-case tail)
    src: jax.Array          # [Tp] the (token, choice) entry ``t * k + c``
                            # each buffer row holds; T*k where it holds none


def _lookup(table: jax.Array, idx: jax.Array) -> jax.Array:
    """``table[idx]`` for a table of ``num_experts`` entries, as a
    comparison and a sum: a gather costs ~8 ns an INDEX on a v5e however
    small the table (``PERF.md`` PR 46), this a few vector ops in all."""
    n = table.shape[0]
    return jnp.sum(jnp.where(idx[..., None] == jnp.arange(n, dtype=idx.dtype),
                             table, 0), axis=-1, dtype=table.dtype)


def sort_tokens_by_expert(expert_idx: jax.Array, num_experts: int,
                          block_m: int = 128,
                          live: jax.Array | None = None) -> ExpertSort:
    """Compute the expert-sorted, block-aligned buffer row of every
    (token, choice) pair, and for every buffer row the entry to gather into
    it. ``expert_idx``: [T, k] int32 from top-k routing.

    ``live`` ([T] bool; None: every token) says which tokens exist. A
    masked token's k entries take the expert id ``num_experts``, which no
    comparison against ``arange(n)`` matches: they count for no expert,
    sort behind every live entry, and ``counts``, the tiles, ``n_tiles``
    and ``src`` are those of the live entries alone. Their ``dst`` is
    ``Tp``, one past the buffer: the fill puts them nowhere, and the way
    back (``dropless_dispatch_combine``) holds the index inside the buffer
    and discards what it fetched — a tile at or past ``n_tiles`` is one
    the kernel never wrote.

    No scatter anywhere (a TPU walks a scatter one update at a time, ~0.17
    us a row: ``PERF.md`` PR 46): the counts are a comparison against
    ``arange(n)`` summed, the order ONE stable sort of the expert ids, its
    inverse permutation (``dst``) a second sort keyed on that order, and
    ``src`` index arithmetic — buffer row ``p`` of a tile of expert ``e``
    has rank ``r = p - starts[e]``, is live iff ``r < counts[e]`` and holds
    entry ``order[cum_counts[e] + r]`` (token ``... // k``).

    Static buffer bound: T*k rounded up to block_m, plus one block_m of
    alignment padding per expert (each expert wastes < block_m rows).
    """
    T, k = expert_idx.shape
    Tk = T * k
    e_flat = expert_idx.reshape(-1).astype(jnp.int32)
    if live is not None:
        e_flat = jnp.where(jnp.repeat(live, k), e_flat, num_experts)
    counts = jnp.sum(
        e_flat[:, None] == jnp.arange(num_experts, dtype=jnp.int32)[None],
        axis=0, dtype=jnp.int32)                                   # [n]
    aligned = ((counts + block_m - 1) // block_m) * block_m
    starts = jnp.concatenate([jnp.zeros((1,), jnp.int32),
                              jnp.cumsum(aligned)[:-1].astype(jnp.int32)])
    cum_counts = jnp.concatenate([jnp.zeros((1,), jnp.int32),
                                  jnp.cumsum(counts)[:-1].astype(jnp.int32)])

    iota = jnp.arange(Tk, dtype=jnp.int32)
    sorted_e, order = jax.lax.sort((e_flat, iota), num_keys=1,
                                   is_stable=True)                 # [Tk]
    Tp = ((Tk + block_m - 1) // block_m) * block_m + num_experts * block_m
    dst_sorted = _lookup(starts - cum_counts, sorted_e) + iota
    if live is not None:
        dst_sorted = jnp.where(sorted_e < num_experts, dst_sorted, Tp)
    _, dst = jax.lax.sort((order, dst_sorted), num_keys=1, is_stable=False)

    tile_starts = jnp.arange(Tp // block_m, dtype=jnp.int32) * block_m
    # tiles past the last used one belong to the last used tile's expert:
    # still nondecreasing (the dw kernel's invariant), and a kernel told
    # ``n_tiles`` finds the weight block it already holds
    n_tiles = (jnp.sum(aligned) // block_m).astype(jnp.int32)
    clamped = jnp.minimum(tile_starts, (n_tiles - 1) * block_m)
    # the last expert whose rows start at or before the tile
    # (``searchsorted(starts, clamped, side="right") - 1``, densely)
    tile_expert = jnp.clip(
        jnp.sum(starts[None] <= clamped[:, None], axis=1, dtype=jnp.int32) - 1,
        0, num_experts - 1)

    # a tail tile lies past its (clamped) expert's aligned rows: it holds
    # no entry
    p = tile_starts[:, None] + jnp.arange(block_m, dtype=jnp.int32)[None]
    held = p < _lookup(starts + counts, tile_expert)[:, None]      # [tiles, bm]
    entry = order[jnp.clip(
        p + _lookup(cum_counts - starts, tile_expert)[:, None], 0, Tk - 1)]
    src = jnp.where(held, entry, Tk).reshape(Tp)
    return ExpertSort(dst=dst, tile_expert=tile_expert, Tp=Tp,
                      n_tiles=n_tiles, src=src)


#: The fill is a one-hot matmul where ``tokens x width`` is at most this,
#: a row gather above it (512 tokens of SmallThinker's 2560): the matmul
#: costs ``2 * Tp * T * E`` operations at the MXU's pace, the gather 6-18 ns
#: a buffer row plus 7 for its index; on a v5e they cross between 600 and
#: 900 tokens at widths 2048-2560 (``PERF.md`` PR 46, call 2). At a 48-row
#: decode step the matmul is 5 us where the gather walks 1,312 rows in 34.
DENSE_FILL_MAX_ELEMS = 512 * 2560


def _rows_or_zero(a: jax.Array, idx: jax.Array) -> jax.Array:
    """``a[idx]``, and a zero row wherever ``idx`` is one past the end (the
    zero row is appended: a select after the gather is one more pass over
    the buffer)."""
    return jnp.concatenate([a, jnp.zeros((1,) + a.shape[1:], a.dtype)])[idx]


def _fill_dense(x2d: jax.Array, dst: jax.Array, Tp: int) -> jax.Array:
    """The buffer as ``hit [Tp, T] @ x2d``, ``hit[p, t]`` set where one of
    token ``t``'s k rows is ``p``: no index is walked at all. A sum of one
    value and zeros is that value, so rows come out as they went in
    (``-0.0`` as ``+0.0``) and padding rows zero. ``0 * inf`` is NaN, so a
    non-finite value is kept out of the matmul — it would reach every row —
    and its token's rows read NaN instead."""
    T = x2d.shape[0]
    hit = jnp.any(dst.reshape(1, T, -1)
                  == jnp.arange(Tp, dtype=dst.dtype)[:, None, None],
                  axis=-1).astype(x2d.dtype)
    pick = functools.partial(jnp.dot, hit, precision=jax.lax.Precision.HIGHEST,
                             preferred_element_type=jnp.float32)
    finite = jnp.isfinite(x2d)
    buf = pick(jnp.where(finite, x2d, jnp.zeros((), x2d.dtype)))
    poisoned = pick(jnp.any(~finite, axis=-1, keepdims=True)
                    .astype(x2d.dtype)) > 0                        # [Tp, 1]
    return jnp.where(poisoned, jnp.nan, buf).astype(x2d.dtype)


@jax.custom_vjp
def gather_expert_rows(x2d: jax.Array, src: jax.Array,
                       dst: jax.Array) -> jax.Array:
    """The expert buffer ``[Tp, E]`` of ``x2d [T, E]`` under an
    :class:`ExpertSort`: row ``p`` is token ``src[p] // k``'s, or zero where
    the row holds none — what ``zeros.at[dst].set(repeat(x2d, k))`` made,
    bit for bit, with no scatter: a one-hot matmul for few tokens
    (:data:`DENSE_FILL_MAX_ELEMS`; the form depends on the shape alone), a
    row gather for many. The backward is a gather as well (the transpose
    of the scatter this replaces, not of the gather, which would be a
    scatter-add of Tp rows): ``d_x2d[t] = sum_k d_buf[dst[t, k]]``."""
    T, E = x2d.shape
    if T * E <= DENSE_FILL_MAX_ELEMS:
        return _fill_dense(x2d, dst, src.shape[0])
    return _rows_or_zero(x2d, src // (dst.shape[0] // T))


def _rows_in_fwd(x2d, src, dst):
    return gather_expert_rows(x2d, src, dst), (dst, x2d.shape[0])


def _rows_in_bwd(res, d_buf):
    dst, T = res
    d_rows = d_buf[dst].reshape(T, dst.shape[0] // T, d_buf.shape[-1])
    return jax.lax.reduce_sum(d_rows, axes=(1,)), None, None


gather_expert_rows.defvjp(_rows_in_fwd, _rows_in_bwd)


@jax.custom_vjp
def gather_token_rows(out_buf: jax.Array, src: jax.Array,
                      dst: jax.Array) -> jax.Array:
    """The way back: ``out_buf[dst]``, the ``[T*k, F]`` rows of every
    (token, choice). Its backward fills a buffer again — a gather by
    ``src``, padding rows zero — where the transpose of ``out_buf[dst]``
    is a scatter-add of T*k rows."""
    return out_buf[dst]


def _rows_out_fwd(out_buf, src, dst):
    return out_buf[dst], src


def _rows_out_bwd(src, d_rows):
    return _rows_or_zero(d_rows, src), None, None


gather_token_rows.defvjp(_rows_out_fwd, _rows_out_bwd)
