"""Paged (block-table) attention as Pallas TPU kernels — decode + prefill.

TPU-native equivalent of the reference's blocked-flash ragged attention
(/root/reference/deepspeed/inference/v2/kernels/ragged_ops/blocked_flash/
blocked_flash.py:64, a flash-attn-2 variant reading K/V through a paged KV
cache). Re-designed for the TPU pipeline model rather than translated.

Two forms live here.

**The ragged form — what the serving engine runs**
(:func:`paged_ragged_attention`, kernels ``paged_attn_decode`` /
``paged_attn_prefill`` / ``paged_attn_tree``):

- The KV pool lives in HBM as ``[L, 2, KV, num_blocks, block_size, D]`` and
  is READ-ONLY inside a program; this step's fresh K/V ride a small staged
  buffer the kernel attends over after a slot's pool pages.
- A grid step DMAs ONE page of ALL kv heads into VMEM; the page index
  comes from a scalar-prefetched block table
  (``pltpu.PrefetchScalarGridSpec``), so the gather happens in the DMA
  engine — no ``[S, ctx, KV, D]`` materialization like the XLA gather
  formulation in inference/engine_v2.py.
- The iteration space is a LIST, not a rectangle: :func:`paged_work_list`
  evaluates the kernel's own predicates (:func:`_live_steps`) over slots x
  (table width + stage pages) in XLA, once a forward, and compacts the
  steps that read a page; the grid is ``(q-tiles, n_items)`` with
  ``n_items`` a dynamic bound and every index map reads its (slot, column)
  from the prefetched list. An empty slot costs one finalize-only step, an
  unused table column nothing.
- Online-softmax state (m, l, acc) is carried in VMEM scratch across the
  steps of one (q-tile, slot): initialised on the slot's first item,
  written out on its last.
- GQA: queries arrive as ``[S, KV, T*G, D]`` (G = H // KV query heads a kv
  head); a step computes all G query heads of every kv head against the
  page, so K/V are never repeated per query head.

**The slice form** (:func:`paged_prefill_attention` /
:func:`paged_decode_attention`, kernels ``paged_attn_slice_*``): one
layer's ``[KV, P, D]`` pool slices, grid ``(seqs, kv_heads, max_pages)``
with pages innermost and pages wholly past ``seq_len`` predicated off with
``@pl.when``. Kept for direct kernel use; no engine path launches it.
Decode there is one new token a sequence whose K/V has already been
scattered into the pool; ``seq_lens`` counts valid context tokens
*including* that token, so position ``p`` attends iff ``p < seq_len``.
"""
from __future__ import annotations

import functools
import math
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl

from jax.experimental.pallas import tpu as pltpu

NEG_INF = float(jnp.finfo(jnp.float32).min)


def paged_attention_usable(num_heads: int, kv_heads: int, head_dim: int,
                           block_size: int) -> bool:
    """Gate: MXU-friendly head_dim (or a row of whole 128-lane registers:
    the latent form's 640), sublane-aligned pages, even GQA groups."""
    if num_heads % kv_heads:
        return False
    if block_size % 8:
        return False
    return head_dim in (64, 128, 256) or head_dim % 128 == 0


def _paged_attn_kernel(tables_ref, lens_ref, starts_ref, q_ref, k_ref, v_ref,
                       o_ref, m_scr, l_scr, acc_scr, *, block_size: int,
                       scale: float, G: int, window: int, ring_tokens: int):
    """One online-softmax kernel serves prefill AND decode: decode is the
    T=1 special case (starts = seq_len - 1 makes the causal mask collapse
    to the plain validity mask ctx < seq_len). ``window`` > 0 adds the
    mistral sliding window (query p attends (p - window, p]) and skips
    pages wholly before any row's window. ``ring_tokens`` > 0 means the
    block table is a ROLLING buffer of ring_tokens/block_size slots:
    table slot j holds the newest block b with b % nwin == j, and offsets
    past seq_len in the newest block still belong to the previous wrap —
    their positions are recovered per-offset and masked by the window."""
    s = pl.program_id(0)
    j = pl.program_id(2)
    nj = pl.num_programs(2)

    @pl.when(j == 0)
    def _init():
        m_scr[:] = jnp.full_like(m_scr, NEG_INF)
        l_scr[:] = jnp.zeros_like(l_scr)
        acc_scr[:] = jnp.zeros_like(acc_scr)

    seq_len = lens_ref[s]
    start = starts_ref[s]
    if ring_tokens:
        nwin = ring_tokens // block_size
        b_latest = jnp.maximum(seq_len - 1, 0) // block_size
        b_j = b_latest - (b_latest - j) % nwin   # jnp %: floor semantics
        page_start = b_j * block_size
        run = (seq_len > 0) & (b_j >= 0)
    else:
        page_start = j * block_size
        run = page_start < seq_len
        if window:
            # earliest key any row of this chunk can see is start-window+1
            run &= page_start + block_size > start - window + 1

    @pl.when(run)
    def _body():
        q = q_ref[0, 0]                                     # [T*G, D]
        k = k_ref[0, 0]                                     # [bs, D]
        v = v_ref[0, 0]
        scores = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale      # [TG, bs]
        # rows are t*G + g; chunk tokens sit at consecutive absolute
        # positions start..start+T-1 (the SplitFuse contract), so the
        # query position is recoverable from the row index — no per-token
        # position input needed
        qpos = start + jax.lax.broadcasted_iota(
            jnp.int32, scores.shape, 0) // G
        ctx = page_start + jax.lax.broadcasted_iota(
            jnp.int32, scores.shape, 1)
        if ring_tokens:
            # offsets past seq_len in the newest block are the PREVIOUS
            # wrap (ring_tokens older); never-written offsets land < 0
            ctx = jnp.where(ctx < seq_len, ctx, ctx - ring_tokens)
            mask = (ctx >= 0) & (ctx <= qpos)
        else:
            mask = (ctx <= qpos) & (ctx < seq_len)
        if window:
            mask &= ctx > qpos - window
        scores = jnp.where(mask, scores, NEG_INF)

        m_prev = m_scr[:]                                    # [TG, 1]
        m_new = jnp.maximum(m_prev, jnp.max(scores, axis=-1, keepdims=True))
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(scores - m_new)                          # [TG, bs]
        l_scr[:] = alpha * l_scr[:] + jnp.sum(p, axis=-1, keepdims=True)
        acc_scr[:] = acc_scr[:] * alpha + jax.lax.dot_general(
            p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_scr[:] = m_new

    @pl.when(j == nj - 1)
    def _finalize():
        l = l_scr[:]
        l_safe = jnp.where(l == 0.0, 1.0, l)                 # empty slot → 0s
        o_ref[0, 0] = (acc_scr[:] / l_safe).astype(o_ref.dtype)


def _live_steps(j, seq_len, qstart, sstart, *, block_size: int, window: int,
                ring_tokens: int, n_pool: int, srows: int, tree: bool,
                xp=jnp):
    """(run_pool, run_stage) of column ``j`` of a slot's walk: whether its
    pool page holds a key some query row of the call can see, and whether
    its stage page does. Columns ``j < n_pool`` are the table's pool pages,
    the rest stage pages. THE rule of the ragged kernel's iteration space:
    the kernel evaluates it on the scalars of one step,
    :func:`paged_work_list` on the whole ``[S, n_pool + nsp]`` rectangle
    (and :func:`paged_step_counts` on the host, ``xp=numpy``)."""
    is_stage = j >= n_pool
    if ring_tokens:
        nwin = ring_tokens // block_size
        b_latest = xp.maximum(sstart - 1, 0) // block_size
        run_pool = (sstart > 0) & (~is_stage) \
            & (b_latest - (b_latest - j) % nwin >= 0)   # jnp %: floor semantics
    else:
        page_start = j * block_size
        run_pool = (page_start < sstart) & (~is_stage)
        if window:
            # earliest key any row of this call can see is qstart-window+1
            run_pool &= page_start + block_size > qstart - window + 1
    sp = xp.maximum(j - n_pool, 0)           # stage page index
    if tree:
        # every stage row is a candidate NODE — a branchy tree packs more
        # nodes than its depth, so seq_len (root+1+max_depth) undercounts
        # the live stage rows; the ancestors mask governs visibility, the
        # gate only skips fully-empty slots
        run_stage = is_stage & (seq_len > 0)
    else:
        run_stage = is_stage & (sstart + sp * srows < seq_len)
    return run_pool, run_stage


def _ragged_geometry(stage_rows: int, block_size: int):
    """(nsp, srows): stage pages and rows a stage page — the rectangle's
    width is the table's ``max_pages`` pool columns + ``nsp``."""
    if stage_rows <= block_size:
        return 1, stage_rows
    if stage_rows % block_size:
        raise ValueError(f"stage rows {stage_rows} must be a multiple of "
                         f"block_size {block_size} (or <= it)")
    return stage_rows // block_size, block_size


#: rows a KV head up to which a call rides ONE query tile whatever its
#: shape: every decode program (T = 1), the tree form (the node positions
#: ride one tile: ``attn_registry.QUERY_TILE_ROWS``) and a short chunk
ONE_TILE_ROWS = 128
#: most bytes of a grid step's f32 score tile ``[KV, TQB, bs]`` (its lanes
#: padded to 128, as VMEM holds them): what a tile's height is cut by
SCORE_TILE_BYTES = 2 ** 21
#: scoped VMEM a call with a tile taller than :data:`ONE_TILE_ROWS` may use
#: (the compiler's default is 16 MiB of a v5e's 128). Beside the score tile
#: a step holds the q and output blocks twice, the f32 accumulator, m and l
#: (a lane each, padded to 128) and ~4 score tiles of temporaries: found by
#: bisection on the compiler, 17 MiB at SmallThinker's 896 rows over 4 KV
#: heads, 19 at its 1,024, 16 at Mistral's 512 over 8, 22 at 256 over 16
VMEM_LIMIT_BYTES = 40 * 1024 * 1024


class PagedPlan(NamedTuple):
    """The query tile of one ragged-kernel call — :func:`paged_plan` makes
    it, :func:`paged_ragged_attention` reads it and the serving engine logs
    it (``paged:``)."""
    TG: int                 # query rows a KV head: T tokens x G query heads
    KV: int
    block_size: int
    tqb: int                # rows a query tile

    @property
    def n_tiles(self) -> int:
        """Query tiles a call: EACH walks every live page of a slot."""
        return self.TG // self.tqb

    @property
    def score_tile_bytes(self) -> int:
        return self.KV * self.tqb * max(self.block_size, 128) * 4

    def describe(self) -> str:
        return (f"{self.TG} query rows a KV head ({self.KV} KV heads, page "
                f"{self.block_size}): {self.n_tiles} query tile"
                f"{'s' * (self.n_tiles > 1)} of {self.tqb} rows a call, "
                f"each walks the slot's pages once; score tile "
                f"{self.score_tile_bytes / 2**20:.2f} of "
                f"{SCORE_TILE_BYTES / 2**20:.0f} MiB")


def paged_plan(TG: int, KV: int, block_size: int, dtype,
               tree: bool = False, lanes: int = 128) -> PagedPlan:
    """The query tile for ``TG`` rows a KV head: the TALLEST divisor of
    ``TG`` that is a multiple of ``dtype``'s sublane tile and keeps the f32
    score tile within :data:`SCORE_TILE_BYTES` — the grid is (query tiles,
    work list), so a call walks its pages once a tile (SmallThinker's
    512-token chunk, 3,584 rows: 4 tiles of 896 where a cap of 128 rows
    made 28). The LOCAL shape decides: under a tensor mesh ``TG`` and
    ``KV`` are the shard's. Where no such divisor is taller than
    :data:`ONE_TILE_ROWS` (always for ``TG`` within it, and for the tree
    form, whose per-row operands tile by 128 lanes) the tile is that many
    rows, halved until it fits — never under 8 — and divides ``TG``.
    ``lanes``: the width of a query row. Beside the score tile a step holds
    the query and output blocks and the accumulator, which grow with it: a
    row wider than 256 lanes (the latent form's 640) cuts the tile in
    proportion, so that the whole stays inside :data:`VMEM_LIMIT_BYTES`
    (1,024 rows at 640 lanes: 27 MiB by the compiler's own count)."""
    plan = lambda t: PagedPlan(TG, KV, block_size, t)
    budget = SCORE_TILE_BYTES * 256 // max(lanes, 256)
    fits = lambda t: plan(t).score_tile_bytes <= budget
    if TG > ONE_TILE_ROWS and not tree:
        sub = 32 // jnp.dtype(dtype).itemsize
        tall = max((t for t in range(sub, TG + 1, sub)
                    if TG % t == 0 and fits(t)), default=0)
        if tall > ONE_TILE_ROWS:
            return plan(tall)
    halves = (min(TG, ONE_TILE_ROWS) >> i for i in range(8))
    return plan(next(t for t in halves
                     if TG % t == 0 and (t <= 8 or fits(t))))


def _item_bits(nj: int) -> int:
    return max(1, (nj - 1).bit_length())


def _unpack_item(code, jbits: int):
    """(slot, column, first-of-slot, last-of-slot) of one work-list item."""
    return (code >> (2 + jbits), (code >> 2) & ((1 << jbits) - 1),
            (code & 1) == 1, (code & 2) == 2)


def _live_rectangle(seq_lens, q_starts, stage_starts, *, block_size: int,
                    max_pages: int, stage_rows: int, window, ring_tokens,
                    tree: bool, xp):
    """:func:`_live_steps` over the whole rectangle: live ``[S, max_pages +
    nsp]`` bool. ``xp`` is ``jnp`` (traced) or ``numpy`` (host)."""
    nsp, srows = _ragged_geometry(stage_rows, block_size)
    col = lambda a: xp.asarray(a, xp.int32)[:, None]
    run_pool, run_stage = _live_steps(
        xp.arange(max_pages + nsp, dtype=xp.int32)[None, :], col(seq_lens),
        col(q_starts), col(stage_starts), block_size=block_size,
        window=int(window or 0), ring_tokens=int(ring_tokens or 0),
        n_pool=max_pages, srows=srows, tree=tree, xp=xp)
    return run_pool | run_stage


def paged_work_list(seq_lens, q_starts, stage_starts, *, block_size: int,
                    max_pages: int, stage_rows: int,
                    window: int | None = None,
                    ring_tokens: int | None = None, tree: bool = False):
    """The ragged kernel's iteration space as a list: the ``(slot, column)``
    steps of the ``[S, max_pages + nsp]`` rectangle for which
    :func:`_live_steps` is true, compacted in ``(slot, column)`` order, each
    marked first-/last-of-slot. A slot with no live step gets ONE item that
    only initialises and finalises (its rows must read zero, not stale
    VMEM). Returns ``(items, n_items)``: int32 ``[S * (max_pages + nsp) + 1]``
    packed ``slot | column | last | first`` (the rectangle is the largest a
    list can get — prefix-shared pages sit in several tables, so no count of
    pool blocks bounds it; one spare entry keeps the pipeline's look-ahead
    past the last item in bounds), and how many of them are items.

    Nothing here depends on the layer: a forward builds it once, outside
    its layer loop, and hands it to every layer's kernel call."""
    live = _live_rectangle(
        seq_lens, q_starts, stage_starts, block_size=block_size,
        max_pages=max_pages, stage_rows=stage_rows, window=window,
        ring_tokens=ring_tokens, tree=tree, xp=jnp)
    S, nj = live.shape
    jbits = _item_bits(nj)
    if 2 + jbits + max(1, (S - 1).bit_length()) > 31:
        raise ValueError(f"{S} slots x {nj} columns do not pack into int32")
    # lax, not jnp, from here on: every serving program traces this once,
    # and each jnp wrapper (cumsum, where, sort, pad) is a nested jit to
    # trace and lower — tens of ms a program, before its cache key exists
    rank = jax.lax.cumsum(live.astype(jnp.int32), axis=1)    # 1-based
    count = rank[:, -1:]
    first = live & (rank == 1)
    last = live & (rank == count)
    # the finalize-only item of an empty slot sits on the first stage
    # column: its pool refs map to the trash block
    j = jax.lax.broadcasted_iota(jnp.int32, (S, nj), 1)
    s = jax.lax.broadcasted_iota(jnp.int32, (S, nj), 0)
    alone = (count == 0) & (j == max_pages)
    code = ((s << jbits | j) << 2 | (last | alone).astype(jnp.int32) << 1
            | (first | alone).astype(jnp.int32))
    item = live | alone
    # codes grow with (slot, column): one sort compacts them in order
    big = jnp.full((S, nj), jnp.iinfo(jnp.int32).max, jnp.int32)
    items = jax.lax.sort(jax.lax.select(item, code, big).reshape(-1),
                         is_stable=False)
    n_items = jnp.sum(item, dtype=jnp.int32)
    keep = jax.lax.iota(jnp.int32, S * nj) < n_items
    items = jax.lax.select(keep, items, jnp.zeros_like(items))
    return jax.lax.pad(items, jnp.int32(0), [(0, 1, 0)]), n_items


def paged_step_counts(seq_lens, q_starts, stage_starts, *, block_size: int,
                      max_pages: int, stage_rows: int,
                      window: int | None = None,
                      ring_tokens: int | None = None, tree: bool = False):
    """(live, rectangle) grid steps of ONE ragged-kernel call a q-tile, on
    the host: the steps :func:`_live_steps` passes (what
    :func:`paged_work_list` lists, less the finalize-only items of empty
    slots) and the ``S x (max_pages + nsp)`` steps of the rectangle a grid
    over slots and table width would walk. numpy in, ints out."""
    live = _live_rectangle(
        seq_lens, q_starts, stage_starts, block_size=block_size,
        max_pages=max_pages, stage_rows=stage_rows, window=window,
        ring_tokens=ring_tokens, tree=tree, xp=np)
    return int(live.sum()), live.size


def _ragged_attn_kernel(tables_ref, lens_ref, qst_ref, sst_ref, layer_ref,
                        work_ref, q_ref, kp_ref, vp_ref, ks_ref, vs_ref,
                        *refs, block_size: int, scale: float, G: int,
                        window: int, ring_tokens: int, srows: int,
                        jbits: int, n_pool: int, p_scale: float = 1.0,
                        tree: bool = False, value_lanes: int = 0):
    """Read-only-pool ragged attention, ALL kv heads per grid step.

    What the measured costs on real hardware made of
    :func:`_paged_attn_kernel`:

    1. Interleaving pool scatters with pallas reads inside the layer scan
       forced XLA to materialize pool-sized buffers (~280ms per decode
       step on a 1.6GB pool). The pool here is READ-ONLY — it holds only
       positions < stage_starts[s]; the current step's (and, in a decode
       window, the window's earlier) tokens arrive in a small staged
       buffer and are merged into the pool ONCE per program by the
       caller.
    2. A (seqs, kv_heads, pages) grid ran ~200k grid steps per decode
       iteration. All KV heads ride one block-DMA and one batched MXU dot
       per step, and a slot's walk is its pool pages, ONE a step, then its
       stage pages (the staged tokens instead of a pool page).
    3. The steps themselves are a LIST, not a rectangle (PR 26). The
       rectangle slots x (table width + stage pages) is sized for every
       slot live at the full table width; an interactive replica holds a
       few short contexts. Measured on a v5e at 48 slots x (128 + 1)
       columns, 32 heads over 8, the kernel alone: a predicated-off step
       of the rectangle cost 0.16us, so a call with NO live slot took
       0.98ms; with 7 slots of ~500 tokens live 1.00ms, of which 38 steps
       read a page. The grid is now (q-tiles, n_items) with ``n_items`` a
       DYNAMIC bound; step ``i`` reads its (slot, column, first-,
       last-of-slot) from the scalar-prefetched work list
       (:func:`paged_work_list`), so a call costs its live steps (~0.8us
       each there: 512 KiB of K+V at ~630 GB/s) plus ~0.4us for each
       empty slot's finalize-only step: the same call 0.054ms, all 48
       slots at the full 16k tokens 5.05ms either way. A slot's pages come
       in the same order as in the rectangle walk: the outputs are
       bitwise the same, on the chip too.

    ``tree`` (the speculative-verify form): each query row is a
    candidate-tree NODE, not a token of a contiguous chunk. Two extra
    VMEM inputs ride along — per-row absolute positions (root + depth;
    siblings share one, so the row-index ramp can't recover them) and
    the ancestors-only visibility mask over the stage columns. Pool
    pages keep the positional-causal walk (every node descends from the
    committed context, with positions read from the input instead of
    the ramp); stage columns take the tree mask VERBATIM, replacing the
    positional mask — exactly the gather formulation's split in
    inference/forward.py (`RaggedForward`).

    ``value_lanes`` > 0 (the LATENT form: one row a token shared by every
    query head, the page ``[1, bs, lanes]`` with no V half): ``vp_ref`` and
    ``vs_ref`` are None and the value is the first ``value_lanes`` lanes of
    the key row — a page is read ONCE for scores and values.

    Grid (q-tiles, n_items).
    ``refs`` = ([tpos, tmask when tree,] o, m_scr, l_scr, acc_scr).
    """
    del layer_ref
    if tree:
        tpos_ref, tmask_ref, *refs = refs
    o_ref, m_scr, l_scr, acc_scr = refs
    tq = pl.program_id(0)          # query-row tile (VMEM-bounds long chunks)
    s, j, first, last = _unpack_item(work_ref[pl.program_id(1)], jbits)

    @pl.when(first)
    def _init():
        m_scr[:] = jnp.full_like(m_scr, NEG_INF)
        l_scr[:] = jnp.zeros_like(l_scr)
        acc_scr[:] = jnp.zeros_like(acc_scr)

    seq_len = lens_ref[s]
    qstart = qst_ref[s]
    sstart = sst_ref[s]            # pool holds positions < sstart
    tqb = m_scr.shape[1]           # query rows per tile

    def online_update(scores, ctx, valid, v, tree_cols=False):
        """Shared online-softmax step. scores [KV, TQB, W]; ctx [KV,TQB,W]
        absolute key positions; valid bool; v [KV, W, D].

        ``p_scale`` != 1 when the pool is fp8: attention weights ~1/n fall
        below e4m3's subnormal granularity (~2^-9) past a few hundred
        context tokens, so the raw p cast would quantize long-context tails
        to zero/coarse steps. Scaling p up to e4m3's full normal range
        (max weight 1.0 → 448) before the cast and accumulating l at the
        SAME scale keeps the final acc/l division exact while every fp8
        code stays normal out to ~200k-token contexts. Constant across all
        grid steps of a program (pool and stage alike) so the online
        alpha-rescaling algebra is unchanged.

        ``tree_cols``: the stage columns of a tree-verify step — ``valid``
        IS the ancestors-only mask and replaces the positional mask
        outright (the tree mask already encodes reachability; window/
        causal checks would wrongly prune sibling-position nodes)."""
        if tree_cols:
            mask = valid
        else:
            if tree:
                # tree nodes sit at root+depth, siblings SHARING a
                # position — unrecoverable from the row ramp, so the
                # positions ride a VMEM input ([1, 1, TQB] rows t*G+g)
                qpos = tpos_ref[0, 0][None, :, None]
            else:
                qpos = qstart + (tq * tqb + jax.lax.broadcasted_iota(
                    jnp.int32, scores.shape, 1)) // G
            mask = valid & (ctx <= qpos)
            if window:
                mask &= ctx > qpos - window
        scores = jnp.where(mask, scores, NEG_INF)
        m_prev = m_scr[:]                                  # [KV, TQB, 1]
        m_new = jnp.maximum(m_prev,
                            jnp.max(scores, axis=-1, keepdims=True))
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(scores - m_new)
        if p_scale != 1.0:
            p = p * p_scale
        l_scr[:] = alpha * l_scr[:] + jnp.sum(p, axis=-1, keepdims=True)
        acc_scr[:] = acc_scr[:] * alpha + jax.lax.dot_general(
            p.astype(v.dtype), v, (((2,), (1,)), ((0,), (0,))),
            preferred_element_type=jnp.float32)            # [KV, TQB, D]
        m_scr[:] = m_new

    # every item of the list is a live step by construction, but for the
    # finalize-only item of an empty slot: there both are false
    run_pool, run_stage = _live_steps(
        j, seq_len, qstart, sstart, block_size=block_size, window=window,
        ring_tokens=ring_tokens, n_pool=n_pool, srows=srows, tree=tree)

    # ---- pool page step --------------------------------------------------
    @pl.when(run_pool)
    def _pool_step():
        q = q_ref[0]                                       # [KV, TQB, D]
        k = kp_ref[0, 0, :, 0]                             # [KV, bs, D]
        v = k[..., :value_lanes] if value_lanes else vp_ref[0, 0, :, 0]
        if k.dtype != q.dtype:
            # fp8 KV pool: converting the PAGE up costs ~10us/page in
            # Mosaic (element-wise + sublane relayout); converting the
            # tiny q tile DOWN is ~free and the MXU contracts fp8 x fp8
            # natively (measured at parity with bf16 dots on v5e).
            # p.astype(v.dtype) in online_update then runs the PV dot in
            # fp8 too — with p pre-scaled into e4m3's normal range
            # (p_scale, see online_update) so long-context weights don't
            # land subnormal. Accuracy is gated by the long-context parity
            # test (tests/test_inference_v2.py::
            # test_v2_fp8_kv_long_context_logits_parity) — if that ever
            # regresses, fall back to v.astype(q.dtype) here (bf16 PV dot,
            # pays the page upconvert).
            q = q.astype(k.dtype)
        scores = jax.lax.dot_general(
            q, k, (((2,), (2,)), ((0,), (0,))),
            preferred_element_type=jnp.float32) * scale    # [KV, TQB, bs]
        off = jax.lax.broadcasted_iota(jnp.int32, scores.shape, 2)
        if ring_tokens:
            nwin = ring_tokens // block_size
            b_latest = jnp.maximum(sstart - 1, 0) // block_size
            # per element, from the broadcast page index: derived on the
            # step's scalars, the remainder sits ahead of every vector op
            # of the step — measured on a v5e over an fp8 pool (20 slots
            # live in a 34-page ring), 425 us a call against 413
            b_j = b_latest - (b_latest - jnp.full_like(off, j)) % nwin
            raw = b_j * block_size + off
            ctx = jnp.where(raw < sstart, raw, raw - ring_tokens)
            valid = (ctx >= 0) & (b_j >= 0)
        else:
            ctx = j * block_size + off
            valid = ctx < sstart
        online_update(scores, ctx, valid, v)

    # ---- stage steps (this program's fresh tokens, page-sized tiles) -----
    sp = jnp.maximum(j - n_pool, 0)          # stage page index

    @pl.when(run_stage)
    def _stage_step():
        q = q_ref[0]                                       # [KV, TQB, D]
        k = ks_ref[0]                                      # [KV, srows, D]
        v = k[..., :value_lanes] if value_lanes else vs_ref[0]
        scores = jax.lax.dot_general(
            q, k, (((2,), (2,)), ((0,), (0,))),
            preferred_element_type=jnp.float32) * scale
        ctx = sstart + sp * srows + jax.lax.broadcasted_iota(
            jnp.int32, scores.shape, 2)
        if tree:
            # stage rows are the candidate nodes themselves: visibility is
            # the prebuilt ancestors-only mask ([1, 1, TQB, srows] tile
            # for this stage page), NOT position order — sibling nodes
            # share a position but must not see each other
            online_update(scores, ctx, tmask_ref[0, 0][None] > 0, v,
                          tree_cols=True)
        else:
            online_update(scores, ctx, ctx < seq_len, v)

    @pl.when(last)
    def _finalize():
        l = l_scr[:]
        l_safe = jnp.where(l == 0.0, 1.0, l)               # empty slot → 0s
        o_ref[0] = (acc_scr[:] / l_safe).astype(o_ref.dtype)


def _latent_attn_kernel(tables_ref, lens_ref, qst_ref, sst_ref, layer_ref,
                        work_ref, q_ref, kp_ref, ks_ref, *refs, **kw):
    """:func:`_ragged_attn_kernel` without the V operands (its latent
    form)."""
    _ragged_attn_kernel(tables_ref, lens_ref, qst_ref, sst_ref, layer_ref,
                        work_ref, q_ref, kp_ref, None, ks_ref, None, *refs,
                        **kw)


def paged_ragged_attention(q, pool, k_stage, v_stage, block_tables,
                           seq_lens, q_starts, stage_starts, *,
                           block_size: int, layer_index,
                           scale: float | None = None,
                           window: int | None = None,
                           ring_tokens: int | None = None,
                           tree_positions=None, tree_mask=None, work=None,
                           value_lanes: int | None = None,
                           interpret: bool | None = None):
    """Ragged attention over a READ-ONLY paged pool plus a staged tail.

    q:            [S, T, H, D] — query rows at positions
                  q_starts[s]..q_starts[s]+T-1 (contiguous per slot)
    pool:         [L, 2, KV, nb, bs, D] — past KV, positions
                  < stage_starts[s] per slot; NEVER written by this
                  kernel (the caller merges the stage in once per
                  program)
    k_stage/v_stage: [S, KV, Ts, D] — fresh tokens at positions
                  stage_starts[s] + r, valid while < seq_lens[s]
    block_tables: [S, max_pages] int32 (pad with the trash block 0)
    seq_lens:     [S] — total valid context incl. staged tokens
    layer_index:  scalar — which pool layer this call reads
    work:         ``(items, n_items)`` of :func:`paged_work_list` for these
                  ``seq_lens``/``q_starts``/``stage_starts`` — the same for
                  every layer, so a forward builds it once outside its
                  layer loop. Built here when not handed one.

    Tree-verify form (speculative decoding): pass ``tree_positions``
    [S, T] int32 (absolute position of each candidate node, root+depth —
    siblings share one) and ``tree_mask`` [S, T, T] (nonzero where node
    row may attend node column: ancestors + self). The T query rows are
    then tree NODES whose K/V sit in the stage at rows 0..T-1; pool
    pages keep the positional-causal walk using the per-node positions,
    stage columns take the mask verbatim. Both args come together.
    Returns [S, T, H, D].

    The LATENT form (``value_lanes``; latent attention absorbed, a page
    kind of its own): ``pool`` ``[L, 1, 1, nb, bs, D]`` — ONE row a token,
    no K/V halves, shared by all ``H`` query heads (``G = H``) — ``k_stage``
    ``[S, 1, Ts, D]``, ``v_stage`` None; the value of a key row is its first
    ``value_lanes`` lanes, so a page is read once for scores and values.
    ``D`` is the row as stored (lane-padded; the query carries zeros in the
    padding) and ``scale`` must be given (the model's, not ``D``'s).
    Returns ``[S, T, H, value_lanes]``. Same plan, same work list.
    """
    S, T, H, D = q.shape
    L, halves, KV, nb, bs, _ = pool.shape
    Dv = int(value_lanes or D)
    if (halves == 1) != bool(value_lanes) or (v_stage is None) != bool(
            value_lanes):
        raise ValueError(
            f"a pool of {halves} half(s) a page with value_lanes "
            f"{value_lanes!r}: the latent form takes a pool without K/V "
            f"halves, no v_stage and the value's width, together")
    if value_lanes and (scale is None or tree_positions is not None
                        or KV != 1):
        raise ValueError("the latent form: one shared row (KV 1), the "
                         "model's own scale, no tree")
    if bs != block_size:
        raise ValueError(f"pool block dim {bs} != block_size {block_size}")
    if H % KV:
        raise ValueError(f"GQA needs H ({H}) divisible by KV ({KV})")
    G = H // KV
    Ts = k_stage.shape[2]
    max_pages = block_tables.shape[1]
    tree = tree_positions is not None
    if tree != (tree_mask is not None):
        raise ValueError("tree_positions and tree_mask come together")
    if tree:
        if tree_positions.shape != (S, T):
            raise ValueError(f"tree_positions {tree_positions.shape} != "
                             f"{(S, T)}")
        if tree_mask.shape != (S, T, T):
            raise ValueError(f"tree_mask {tree_mask.shape} != {(S, T, T)}")
        if Ts < T:
            raise ValueError(f"stage rows {Ts} must cover the {T} tree "
                             f"nodes")
    if ring_tokens and not window:
        raise ValueError("ring buffer requires a sliding window")
    if scale is None:
        scale = 1.0 / (D ** 0.5)
    if interpret is None:
        from . import interpret_mode
        interpret = interpret_mode()

    # [S, T, KV, G, D] -> [S, KV, T*G, D], rows t*G + g
    qg = (q.reshape(S, T, KV, G, D).transpose(0, 2, 1, 3, 4)
          .reshape(S, KV, T * G, D))
    TG = T * G
    # query-row tiles bound VMEM for long prefill chunks; stage pages
    # bound it on the key side (uniform page-sized score tiles)
    TQB = paged_plan(TG, KV, bs, q.dtype, tree, lanes=D).tqb
    n_pool = max_pages
    nsp, srows = _ragged_geometry(Ts, bs)
    jbits = _item_bits(n_pool + nsp)
    if work is None:
        work = paged_work_list(
            seq_lens, q_starts, stage_starts, block_size=bs,
            max_pages=n_pool, stage_rows=Ts, window=window,
            ring_tokens=ring_tokens, tree=tree)
    items, n_items = work
    if items.shape != (S * (n_pool + nsp) + 1,):
        raise ValueError(f"work list {items.shape} was not built for {S} "
                         f"slots x {n_pool + nsp} columns")

    def item(wl, i):
        s, j, _, _ = _unpack_item(wl[i], jbits)
        return s, j

    # index maps see (q-tile, item, *scalar-prefetch refs); the last of
    # those is the work list, which says which (slot, column) item i is
    def pool_spec(half):
        def index(tq, i, t, ln, qs, ss, lr, wl):
            s, j = item(wl, i)
            # a stage step still needs a legal page index: the trash block
            # (0), whose re-fetch is elided when the previous index was 0
            return (lr[0], half, 0,
                    jnp.where(j < n_pool, t[s, jnp.minimum(j, n_pool - 1)], 0),
                    0, 0)
        return pl.BlockSpec((1, 1, KV, 1, bs, D), index)

    def stage_spec():
        def index(tq, i, t, ln, qs, ss, lr, wl):
            s, j = item(wl, i)
            return s, 0, jnp.maximum(j - n_pool, 0), 0
        return pl.BlockSpec((1, KV, srows, D), index)

    def q_index(tq, i, t, ln, qs, ss, lr, wl):
        return item(wl, i)[0], 0, tq, 0

    tree_ops = ()
    tree_specs = []
    if tree:
        # per-ROW node positions: expand [S, T] to the kernel's t*G+g row
        # layout so row r's position is tpos[r // G]; the mask expands the
        # same way on rows and zero-pads columns out to the stage width
        # (padding columns are invisible — ancestor_mask already zeroes
        # past-tree columns, and zero mask == masked out)
        #
        # Mosaic wants each block's last two dims divisible by (8, 128)
        # or equal to the array's, and the slot dim S is neither once a
        # batch holds more than one slot — so both operands keep S (and
        # the stage-page index) on LEADING axes and end in dims the block
        # covers whole or in legal tiles: tpos [S, 1, TG] with block
        # (1, 1, TQB), the mask [S, nsp, TG, srows] with block
        # (1, 1, TQB, srows). Interpret mode never checks this.
        tpos = jnp.repeat(tree_positions.astype(jnp.int32), G, axis=1)
        tpos = tpos.reshape(S, 1, TG)
        tmsk = jnp.repeat(tree_mask.astype(jnp.int32), G, axis=1)
        tmsk = jnp.pad(tmsk, ((0, 0), (0, 0), (0, Ts - T)))
        tmsk = tmsk.reshape(S, TG, nsp, srows).transpose(0, 2, 1, 3)
        tree_ops = (tpos, tmsk)

        def tpos_index(tq, i, t, ln, qs, ss, lr, wl):
            return item(wl, i)[0], 0, tq

        def tmask_index(tq, i, t, ln, qs, ss, lr, wl):
            s, j = item(wl, i)
            return s, jnp.maximum(j - n_pool, 0), tq, 0

        tree_specs = [pl.BlockSpec((1, 1, TQB), tpos_index),
                      pl.BlockSpec((1, 1, TQB, srows), tmask_index)]

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=6,
        grid=(TG // TQB, n_items),
        in_specs=[
            pl.BlockSpec((1, KV, TQB, D), q_index),
            *([pool_spec(0), stage_spec()] if value_lanes else
              [pool_spec(0), pool_spec(1), stage_spec(), stage_spec()]),
            *tree_specs,
        ],
        out_specs=pl.BlockSpec((1, KV, TQB, Dv), q_index),
        scratch_shapes=[
            pltpu.VMEM((KV, TQB, 1), jnp.float32),
            pltpu.VMEM((KV, TQB, 1), jnp.float32),
            pltpu.VMEM((KV, TQB, Dv), jnp.float32),
        ],
    )
    # fp8 pools scale p into e4m3's normal range (the e4m3 max, 448) so
    # long-context attention weights survive the fp8 PV-dot cast; the
    # matching l accumulation cancels the scale exactly at finalize
    p_scale = 448.0 if pool.dtype == jnp.float8_e4m3fn else 1.0
    kw = dict(block_size=block_size, scale=float(scale), G=G,
              window=int(window or 0), ring_tokens=int(ring_tokens or 0),
              srows=srows, jbits=jbits, n_pool=n_pool, p_scale=p_scale,
              tree=tree)
    form = "decode" if T == 1 else "prefill"
    out = pl.pallas_call(
        functools.partial(_latent_attn_kernel, value_lanes=Dv, **kw)
        if value_lanes else functools.partial(_ragged_attn_kernel, **kw),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((S, KV, TG, Dv), q.dtype),
        name=("paged_attn_tree" if tree else f"paged_latent_{form}"
              if value_lanes else f"paged_attn_{form}"),
        # a call of one tile (every decode and tree program) is compiled as
        # it always was; a taller tile states what it may use
        compiler_params=(pltpu.CompilerParams(
            vmem_limit_bytes=VMEM_LIMIT_BYTES)
            if TQB > ONE_TILE_ROWS else None),
        interpret=interpret,
    )(block_tables.astype(jnp.int32), seq_lens.astype(jnp.int32),
      q_starts.astype(jnp.int32), stage_starts.astype(jnp.int32),
      jnp.asarray(layer_index, jnp.int32).reshape(1), items,
      qg, *((pool, k_stage) if value_lanes
            else (pool, pool, k_stage, v_stage)), *tree_ops)
    return (out.reshape(S, KV, T, G, Dv).transpose(0, 2, 1, 3, 4)
            .reshape(S, T, H, Dv))


#: heads one pass of the expanded latent kernel's inner loop takes, side by
#: side in one loop body so that one head's exponentials overlap another's
#: products: 23.5 us a page at one, 21.6 at two, 20.7 at four (v5e, kanana-2's
#: widths, a 512-token chunk over 28k tokens) — and every head of the body
#: is traced and lowered again in each of a replica's prefill programs, at
#: every start: four cost the cell 5 s of set-up (``PERF.md`` section 6,
#: PR 60)
LATENT_HEADS_A_PASS = 2


def _lanes(n: int) -> int:
    """``n`` values as VMEM holds a row of them: whole 128-lane registers."""
    return -(-n // 128) * 128


class LatentPrefillPlan(NamedTuple):
    """The head group (and, where ONE head's chunk does not fit, the token
    tile) of one :func:`paged_latent_prefill` call —
    :func:`latent_prefill_plan` makes it, the kernel's entry reads it and
    the serving engine logs it (``paged:``)."""
    T: int                  # tokens a chunk
    H: int
    block_size: int
    hg: int                 # heads a group: a page is up-projected once each
    tqb: int                # tokens a tile (T but where one head overflows)
    vmem_bytes: int         # what a grid step holds, by :func:`_latent_vmem`
    breakeven: float        # tokens a chunk from which this form is cheaper

    @property
    def n_groups(self) -> int:
        """Head groups a call: EACH walks every live page of a slot."""
        return self.H // self.hg

    def describe(self) -> str:
        tiles = self.T // self.tqb
        return (f"{self.T} tokens x {self.H} heads EXPANDED (cheaper than "
                f"absorbed from {self.breakeven:.0f} tokens a chunk): "
                f"{self.n_groups} group{'s' * (self.n_groups > 1)} of "
                f"{self.hg} heads"
                + (f" x {tiles} tiles of {self.tqb} tokens" * (tiles > 1))
                + f" a call, each walks the slot's pages once (page "
                f"{self.block_size}) and up-projects a page once a head; "
                f"{self.vmem_bytes / 2**20:.1f} of "
                f"{VMEM_LIMIT_BYTES / 2**20:.0f} MiB")


def latent_prefill_breakeven(R: int, dn: int, dr: int, dv: int,
                             lanes: int) -> float:
    """Tokens a chunk at which latent attention EXPANDED costs what it
    costs ABSORBED, a page: absorbed, every head's row contracts the stored
    row (``lanes``) for its score and the latent (``R``) for its value;
    expanded, a page is up-projected once a head (``R (dn + dv)`` a key,
    whatever the chunk) and a row contracts ``dn + dr`` and ``dv``. 158 at
    kanana-2's widths; infinite where expanding never pays."""
    saved = (lanes + R) - (dn + dr + dv)
    return R * (dn + dv) / saved if saved > 0 else float("inf")


def _latent_vmem(hg: int, tqb: int, R: int, dn: int, dv: int, lanes: int,
                 rows: int, itemsize: int) -> int:
    """Bytes of VMEM one grid step of the expanded latent kernel holds:
    the blocks the pipeline keeps twice (the group's two query parts and
    its output, tokens in the lanes; its slices of ``w_uk`` / ``w_uv``; a
    pool page and a stage tile), the f32 accumulator, m and l (a sublane
    each, padded to 8), and a pass's temporaries (~6 score tiles a head
    and the up-projected page)."""
    t = _lanes(tqb)
    blocks = 2 * itemsize * (hg * t * (dn + (lanes - R) + dv)
                             + hg * R * (_lanes(dn) + dv) + 2 * rows * lanes)
    scratch = 4 * hg * t * (dv + 2 * 8)
    temps = math.gcd(hg, LATENT_HEADS_A_PASS) * (
        6 * 4 * rows * t + (4 + itemsize) * rows * (_lanes(dn) + dv))
    return blocks + scratch + temps


def latent_prefill_plan(T: int, H: int, R: int, dn: int, dr: int, dv: int,
                        lanes: int, block_size: int,
                        dtype) -> LatentPrefillPlan | None:
    """The EXPANDED form's plan for a chunk of ``T`` tokens, or None where
    the absorbed form is the cheaper one (``T`` under
    :func:`latent_prefill_breakeven`: every decode program, the rows that
    ride a prefill step). From the call's static shape alone, as
    :func:`paged_plan`: the grid is (head groups, token tiles, work list),
    and a group is the LARGEST divisor of ``H`` whose step stays inside
    :data:`VMEM_LIMIT_BYTES` — tiling by heads, because a page's
    up-projection is per head and a tile of tokens would repeat it. The
    tokens are tiled too (the widest divisor of ``T`` in whole 128-lane
    registers that fits) only where one head's chunk does not fit."""
    even = latent_prefill_breakeven(R, dn, dr, dv, lanes)
    if T < even:
        return None
    isz = jnp.dtype(dtype).itemsize
    # a stage tile is never taller than a page (``_ragged_geometry``)
    vmem = lambda hg, t: _latent_vmem(hg, t, R, dn, dv, lanes, block_size,
                                      isz)
    groups = [g for g in range(H, 0, -1) if H % g == 0]
    hg = next((g for g in groups if vmem(g, T) <= VMEM_LIMIT_BYTES), 1)
    tqb = next((t for t in range(T, 0, -1) if T % t == 0
                and (t == T or t % 128 == 0)
                and vmem(hg, t) <= VMEM_LIMIT_BYTES), None)
    if tqb is None:
        raise ValueError(f"no tile of a {T}-token chunk of one head fits "
                         f"{VMEM_LIMIT_BYTES} bytes of VMEM")
    return LatentPrefillPlan(T, H, block_size, hg, tqb, vmem(hg, tqb), even)


def _latent_prefill_kernel(tables_ref, lens_ref, qst_ref, sst_ref, layer_ref,
                           work_ref, qn_ref, qr_ref, wuk_ref, wuv_ref,
                           kp_ref, ks_ref, o_ref, m_scr, l_scr, acc_scr, *,
                           block_size: int, scale: float, srows: int,
                           jbits: int, n_pool: int, rank: int, hb: int):
    """Latent attention EXPANDED over the read-only latent pool: the form
    of a prefill chunk (:func:`paged_latent_prefill`).

    The absorbed form (:func:`_ragged_attn_kernel`, ``value_lanes``)
    contracts every head's query row with the stored row — ``lanes`` for
    the score, ``rank`` for the value: right at one row a head, twice the
    FLOPs a chunk needs at 512. Here a grid step takes ONE latent page
    ``[bs, lanes]`` (or one stage tile of this chunk's own rows), and for
    each head of its group up-projects it in VMEM — ``k_nope = c W_uk,h``
    (times the score scale) and ``v^T = W_uv,h^T c^T``, in the operands'
    dtype, f32 sums — and runs the online softmax of
    :func:`_ragged_attn_kernel` over ``k_nope · q_nope + k_r · q_rope``
    (the rope lanes of the row, shared by the heads). The group's slices of
    ``W_uk`` / ``W_uv`` stay resident along the work list; the page is read
    once a group.

    TRANSPOSED against the absorbed kernel: a score tile is ``[keys,
    tokens]`` — the page's rows down the sublanes, the chunk's tokens along
    the lanes — so that the max and the sum over keys run DOWN a tile
    (register-wise, then one 8-sublane fold) and m, l and alpha are ``[1,
    tokens]``, lane-dense. With a page's 128 keys along the lanes every
    register of the score tile needs a cross-lane reduction of its own,
    twice, and m / l are a lane wide: measured on a v5e at kanana-2's
    widths that form took 74 us a page and chunk (52 with two heads a
    pass) against the absorbed kernel's 63, this one 21 (``PERF.md``
    section 6, PR 60). The query parts arrive ``[d, tokens]``, the
    accumulator and the output are ``[dv, tokens]``.

    Grid (head groups, token tiles, n_items); the same work list, block
    table, stage, and masks — causal by position, the pool's
    ``stage_starts``, the slot's ``seq_len``, an empty slot's finalize-only
    item — as the absorbed form. ``qr_ref`` carries the query's rope part
    padded with zeros to the row's lanes past ``rank`` (what the pool's
    padding lanes meet). ``hb`` heads a pass of the inner loop.
    """
    del tables_ref, layer_ref
    tq = pl.program_id(1)
    s, j, first, last = _unpack_item(work_ref[pl.program_id(2)], jbits)
    hg, tqb = m_scr.shape[0], m_scr.shape[2]

    @pl.when(first)
    def _init():
        m_scr[:] = jnp.full_like(m_scr, NEG_INF)
        l_scr[:] = jnp.zeros_like(l_scr)
        acc_scr[:] = jnp.zeros_like(acc_scr)

    seq_len = lens_ref[s]
    qstart = qst_ref[s]
    sstart = sst_ref[s]            # pool holds positions < sstart

    def attend(rows, ctx0, limit):
        """The group's heads over latent rows ``[W, lanes]`` at positions
        ``ctx0 + r``, those before ``limit`` and not after the query."""
        W = rows.shape[0]
        c = rows[:, :rank]
        k_r = (rows[:, rank:].astype(jnp.float32) * scale).astype(rows.dtype)
        ctx = ctx0 + jax.lax.broadcasted_iota(jnp.int32, (W, tqb), 0)
        qpos = qstart + tq * tqb + jax.lax.broadcasted_iota(
            jnp.int32, (W, tqb), 1)
        mask = (ctx < limit) & (ctx <= qpos)

        def head(h):
            k_n = (jax.lax.dot_general(
                c, wuk_ref[h], (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32) * scale).astype(
                    rows.dtype)                                # [W, dn]
            v_t = jax.lax.dot_general(
                wuv_ref[h], c, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32).astype(rows.dtype)
            scores = jax.lax.dot_general(
                k_n, qn_ref[0, h], (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)            # [W, tqb]
            scores += jax.lax.dot_general(
                k_r, qr_ref[0, h], (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)
            scores = jnp.where(mask, scores, NEG_INF)
            m_prev = m_scr[h]                                  # [1, tqb]
            m_new = jnp.maximum(m_prev,
                                jnp.max(scores, axis=0, keepdims=True))
            alpha = jnp.exp(m_prev - m_new)
            p = jnp.exp(scores - m_new)
            l_scr[h] = alpha * l_scr[h] + jnp.sum(p, axis=0, keepdims=True)
            acc_scr[h] = acc_scr[h] * alpha + jax.lax.dot_general(
                v_t, p.astype(rows.dtype), (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)            # [dv, tqb]
            m_scr[h] = m_new

        def heads(i, carry):
            for b in range(hb):
                head(i * hb + b)
            return carry

        jax.lax.fori_loop(0, hg // hb, heads, 0)

    # every item of the list is a live step by construction, but for the
    # finalize-only item of an empty slot: there both are false
    run_pool, run_stage = _live_steps(
        j, seq_len, qstart, sstart, block_size=block_size, window=0,
        ring_tokens=0, n_pool=n_pool, srows=srows, tree=False)

    @pl.when(run_pool)
    def _pool_step():
        attend(kp_ref[0, 0, 0, 0], j * block_size, sstart)

    @pl.when(run_stage)
    def _stage_step():
        attend(ks_ref[0, 0], sstart + jnp.maximum(j - n_pool, 0) * srows,
               seq_len)

    @pl.when(last)
    def _finalize():
        l = l_scr[:]
        l_safe = jnp.where(l == 0.0, 1.0, l)               # empty slot → 0s
        o_ref[0] = (acc_scr[:] / l_safe).astype(o_ref.dtype)


def paged_latent_prefill(q, w_uk, w_uv, pool, k_stage, block_tables,
                         seq_lens, q_starts, stage_starts, *,
                         block_size: int, layer_index, scale: float,
                         work=None, interpret: bool | None = None):
    """Latent attention EXPANDED over the paged latent pool plus the staged
    tail: the prefill form of the latent kind (kernel
    ``paged_latent_prefill``; the decode form, and a chunk under
    :func:`latent_prefill_breakeven`, is :func:`paged_ragged_attention`
    with ``value_lanes``). Reads the cache the absorbed form reads — the
    same pool, stage, block table and work list — and writes nothing.

    q:        [S, T, H, dn + dr] — the queries AS PROJECTED (rope applied
              to the last ``dr``; NOT folded through ``w_uk``) at positions
              q_starts[s]..q_starts[s]+T-1
    w_uk:     [R, H, dn], w_uv: [R, H, dv] — the latent's up-projections to
              a head's keys and values, in the dtype of ``q``
    pool:     [L, 1, 1, nb, bs, lanes] — rows ``[c | k_r | padding]``,
              positions < stage_starts[s] a slot
    k_stage:  [S, 1, Ts, lanes] — this step's rows
    scale:    the model's (``(dn + dr) ** -0.5``)
    Returns [S, T, H, dv]: what ``W_o`` takes.
    """
    S, T, H, dq = q.shape
    R, _, dn = w_uk.shape
    dv = w_uv.shape[2]
    L, halves, KV, nb, bs, lanes = pool.shape
    dr = dq - dn
    if (halves, KV) != (1, 1) or bs != block_size or w_uk.shape[1] != H \
            or w_uv.shape[:2] != (R, H) or not 0 < dr <= lanes - R:
        raise ValueError(
            f"the expanded latent form takes q [S, T, H, dn + dr], w_uk "
            f"[R, H, dn], w_uv [R, H, dv] and a pool [L, 1, 1, nb, "
            f"{block_size}, lanes >= R + dr]: got {q.shape}, {w_uk.shape}, "
            f"{w_uv.shape}, {pool.shape}")
    plan = latent_prefill_plan(T, H, R, dn, dr, dv, lanes, bs, q.dtype)
    if plan is None:
        raise ValueError(
            f"a chunk of {T} tokens is cheaper absorbed (the forms cross at "
            f"{latent_prefill_breakeven(R, dn, dr, dv, lanes):.1f}): "
            f"paged_ragged_attention(value_lanes=) serves it")
    if interpret is None:
        from . import interpret_mode
        interpret = interpret_mode()
    hg, tqb = plan.hg, plan.tqb
    Ts = k_stage.shape[2]
    n_pool = block_tables.shape[1]
    nsp, srows = _ragged_geometry(Ts, bs)
    jbits = _item_bits(n_pool + nsp)
    if work is None:
        work = paged_work_list(seq_lens, q_starts, stage_starts,
                               block_size=bs, max_pages=n_pool,
                               stage_rows=Ts)
    items, n_items = work
    if items.shape != (S * (n_pool + nsp) + 1,):
        raise ValueError(f"work list {items.shape} was not built for {S} "
                         f"slots x {n_pool + nsp} columns")

    # heads lead and the tokens lie along the lanes: a group's blocks are
    # [hg, d, tokens], a head's weights [R, dn] and [dv, R] whole
    qt = q.transpose(0, 2, 3, 1)                           # [S, H, dq, T]
    qn = qt[:, :, :dn]
    qr = jnp.pad(qt[:, :, dn:], [(0, 0)] * 2 + [(0, lanes - R - dr), (0, 0)])
    wk, wv = w_uk.transpose(1, 0, 2), w_uv.transpose(1, 2, 0)

    def item(wl, i):
        s, j, _, _ = _unpack_item(wl[i], jbits)
        return s, j

    def pool_index(g, tq, i, t, ln, qs, ss, lr, wl):
        s, j = item(wl, i)
        # a stage step still needs a legal page index: the trash block
        return (lr[0], 0, 0,
                jnp.where(j < n_pool, t[s, jnp.minimum(j, n_pool - 1)], 0),
                0, 0)

    def stage_index(g, tq, i, t, ln, qs, ss, lr, wl):
        s, j = item(wl, i)
        return s, 0, jnp.maximum(j - n_pool, 0), 0

    def q_index(g, tq, i, t, ln, qs, ss, lr, wl):
        return item(wl, i)[0], g, 0, tq

    def w_index(g, tq, i, t, ln, qs, ss, lr, wl):
        return g, 0, 0

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=6,
        grid=(H // hg, T // tqb, n_items),
        in_specs=[
            pl.BlockSpec((1, hg, dn, tqb), q_index),
            pl.BlockSpec((1, hg, lanes - R, tqb), q_index),
            pl.BlockSpec((hg, R, dn), w_index),
            pl.BlockSpec((hg, dv, R), w_index),
            pl.BlockSpec((1, 1, 1, 1, bs, lanes), pool_index),
            pl.BlockSpec((1, 1, srows, lanes), stage_index),
        ],
        out_specs=pl.BlockSpec((1, hg, dv, tqb), q_index),
        scratch_shapes=[
            pltpu.VMEM((hg, 1, tqb), jnp.float32),
            pltpu.VMEM((hg, 1, tqb), jnp.float32),
            pltpu.VMEM((hg, dv, tqb), jnp.float32),
        ],
    )
    out = pl.pallas_call(
        functools.partial(
            _latent_prefill_kernel, block_size=bs, scale=float(scale),
            srows=srows, jbits=jbits, n_pool=n_pool, rank=R,
            hb=math.gcd(hg, LATENT_HEADS_A_PASS)),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((S, H, dv, T), q.dtype),
        name="paged_latent_prefill",
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=VMEM_LIMIT_BYTES),
        interpret=interpret,
    )(block_tables.astype(jnp.int32), seq_lens.astype(jnp.int32),
      q_starts.astype(jnp.int32), stage_starts.astype(jnp.int32),
      jnp.asarray(layer_index, jnp.int32).reshape(1), items,
      qn, qr, wk, wv, pool, k_stage)
    return out.transpose(0, 3, 1, 2)



def paged_prefill_attention(q, k_pool, v_pool, block_tables, seq_lens,
                            chunk_starts, *, block_size: int,
                            scale: float | None = None,
                            window: int | None = None,
                            ring_tokens: int | None = None,
                            interpret: bool | None = None):
    """Chunked-prefill attention against a paged KV pool — the blocked-
    flash half of the reference's ragged attention
    (inference/v2/kernels/ragged_ops/blocked_flash/blocked_flash.py:64).

    q:            [S, T, H, D] — each slot's T-token SplitFuse chunk, whose
                  K/V were already scattered into the pool; positions are
                  chunk_starts[s]..chunk_starts[s]+T-1 (contiguous)
    k_pool/v_pool:[KV, P, D]
    block_tables: [S, max_pages] int32
    seq_lens:     [S] int32 — valid ctx incl. this chunk's tokens
    chunk_starts: [S] int32 — absolute position of each slot's first token
    Returns [S, T, H, D]. Peak memory per grid step is one [T*G, bs]
    score tile + one page — never the [S, ctx, KV, D] gather of the XLA
    formulation. (The serving engine itself uses
    :func:`paged_ragged_attention` — read-only pool + staged fresh
    tokens; this per-layer-slice form remains for direct kernel use.)
    """
    S, T, H, D = q.shape
    KV, P, _ = k_pool.shape
    if P % block_size:
        raise ValueError(f"pool tokens {P} not divisible by block_size "
                         f"{block_size}")
    if H % KV:
        raise ValueError(f"GQA needs H ({H}) divisible by KV ({KV})")
    G = H // KV
    max_pages = block_tables.shape[1]
    if ring_tokens and not window:
        raise ValueError("a rolling KV buffer only retains the last "
                         "ring_tokens positions — it requires a sliding "
                         "window that masks everything older")
    if ring_tokens and ring_tokens % block_size:
        raise ValueError(f"ring_tokens {ring_tokens} must be a multiple of "
                         f"block_size {block_size}")
    if scale is None:
        scale = 1.0 / (D ** 0.5)
    if interpret is None:
        from . import interpret_mode
        interpret = interpret_mode()

    # [S, T, H, D] -> [S, KV, T*G, D], rows t*G + g
    qg = (q.reshape(S, T, KV, G, D).transpose(0, 2, 1, 3, 4)
          .reshape(S, KV, T * G, D))
    scratch = [
        pltpu.VMEM((T * G, 1), jnp.float32),
        pltpu.VMEM((T * G, 1), jnp.float32),
        pltpu.VMEM((T * G, D), jnp.float32),
    ]
    kw = dict(block_size=block_size, scale=float(scale),
              G=G, window=int(window or 0),
              ring_tokens=int(ring_tokens or 0))
    kp = k_pool.reshape(KV, P // block_size, block_size, D)
    vp = v_pool.reshape(KV, P // block_size, block_size, D)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(S, KV, max_pages),
        in_specs=[
            pl.BlockSpec((1, 1, T * G, D),
                         lambda s, h, j, tb, ln, st: (s, h, 0, 0)),
            pl.BlockSpec((1, 1, block_size, D),
                         lambda s, h, j, tb, ln, st: (h, tb[s, j], 0, 0)),
            pl.BlockSpec((1, 1, block_size, D),
                         lambda s, h, j, tb, ln, st: (h, tb[s, j], 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, T * G, D),
                               lambda s, h, j, tb, ln, st: (s, h, 0, 0)),
        scratch_shapes=scratch,
    )
    out = pl.pallas_call(
        functools.partial(_paged_attn_kernel, **kw),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((S, KV, T * G, D), q.dtype),
        name="paged_attn_slice_decode" if T == 1 else "paged_attn_slice_prefill",
        interpret=interpret,
    )(block_tables.astype(jnp.int32), seq_lens.astype(jnp.int32),
      chunk_starts.astype(jnp.int32), qg, kp, vp)
    return (out.reshape(S, KV, T, G, D).transpose(0, 2, 1, 3, 4)
            .reshape(S, T, H, D))


def paged_decode_attention(q, k_pool, v_pool, block_tables, seq_lens, *,
                           block_size: int, scale: float | None = None,
                           window: int | None = None,
                           ring_tokens: int | None = None,
                           interpret: bool | None = None):
    """One-token-per-sequence attention against a paged KV pool: the T=1
    case of :func:`paged_prefill_attention` with the query at position
    seq_len - 1 (so the causal mask reduces to ctx < seq_len).

    q:            [S, H, D] — the new token's query per sequence slot
    k_pool/v_pool:[KV, P, D] with P = num_blocks * block_size
    block_tables: [S, max_pages] int32 (pad entries with the trash block)
    seq_lens:     [S] int32 — valid context incl. the new token (0 = empty)
    Returns [S, H, D].
    """
    starts = jnp.maximum(seq_lens.astype(jnp.int32) - 1, 0)
    out = paged_prefill_attention(
        q[:, None], k_pool, v_pool, block_tables, seq_lens, starts,
        block_size=block_size, scale=scale, window=window,
        ring_tokens=ring_tokens, interpret=interpret)
    return out[:, 0]
