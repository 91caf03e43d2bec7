"""Flash attention as a Pallas TPU kernel (fwd + bwd), with GQA.

TPU-native replacement for the reference's fused attention CUDA kernels
(/root/reference/csrc/transformer/softmax_kernels.cu, attention paths of
csrc/transformer/inference/csrc/, and the flash-attn-2 port under
deepspeed/inference/v2/kernels/ragged_ops/blocked_flash/).

Design:
- layout [B, H, S, D]; a grid step works on one ``[block_q, block_k]`` block
  of the score square, key blocks innermost. TPU grids execute sequentially
  per core, so the online-softmax state (m, l, acc) lives in VMEM scratch
  carried across the key steps of one q block.
- the block a grid step is handed and the tile its scores are computed on
  are two numbers (PR 35). A block wholly under the causal diagonal is ONE
  unmasked tile; a block above it is predicated off; a block the diagonal
  crosses is ONE masked tile in the forward and is walked by the backward
  in ``tile_q`` rows, each ONE masked tile over just the keys those rows can
  see (:func:`_block_tiles`, unrolled — the block's offset from the diagonal
  takes a few static values). Without a mask every block is one unmasked
  tile.
- GQA: the q-head grid index maps onto kv-head q_head // group in the
  BlockSpec index_map — K/V are never materialized per-q-head.
- where the whole K and V of one (row, kv head), the dk/dv blocks and their
  fp32 scratch fit :data:`VMEM_BUDGET_BYTES` (sequence 8192 at head 64,
  4096 at head 128 in bf16) they are brought in once and stay resident over
  the kv head's query heads, q blocks and key steps.
- backward: custom VJP. delta = rowsum(dO*O) precomputed in XLA. K and V
  resident: ONE kernel, ``flash_attention_bwd_dqkv``, makes s, p, dp and ds
  once a tile (5 matmuls, one exp) and sums dK/dV over the kv head's query
  heads in its scratch. Otherwise the split pair, the same tile with two of
  the five matmuls left out each: ``_bwd_dq`` (key blocks innermost) and
  ``_bwd_dkv`` (the kv head's q blocks innermost; the sum over its query
  heads in the scratch too).

Measured on one v5e, 2026-09-29 (PR 35; kernel device time alone, bf16,
causal; before -> after, ms a call): the train cell's shard, B2 S2048 H32/KV8
D128: forward 0.839 -> 0.753, backward 2.366 (dq + dkv) -> 1.367 (dqkv); B8
H16 S1024 D64 (gpt2-350m's rows): 0.443 -> 0.444, 0.985 -> 0.819; B1 H16
S8192 D64: 2.654 -> 2.299, 7.203 -> 4.350; B1 H16 S16384 D64 (split pair
both sides): 9.88 -> 9.57, 28.44 -> 27.29. ``PERF.md`` section 6 has the
sweep behind the tile sizes.

Numerics: logits and softmax state in fp32 (preferred_element_type), inputs
bf16 or fp32.
"""
from __future__ import annotations

import functools
import math
from typing import Any, NamedTuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from jax.experimental.pallas import tpu as pltpu

NEG_INF = float(jnp.finfo(jnp.float32).min)

#: below this, the XLA fused attention is both fast and memory-cheap
MIN_SEQ = 128
#: Block policy, measured on one v5e (PR 35, 2026-09-29; B2 S2048 H32/KV8 D128
#: and B8 H16 S1024 D64, bf16). A block under the diagonal wants to be ONE
#: fat tile: 1024 x 1024 runs the forward at ~70 % and the merged backward at
#: ~84 % of the MXU's pace, and the same block walked in 512 x 512 tiles cost
#: the forward 1.6x (every tile is another pass over the lane-sparse
#: ``[rows, 1]`` softmax state, and a ``fori_loop`` over tiles, tried first,
#: cost 1.5-2.7x more again: the loop serialises what one basic block
#: overlaps). So blocks stay as fat as the sequence divides: a sequence up to
#: the first is one block, a longer one takes the largest divisor.
_FAST_BLOCKS = (1024, 512, 256)
#: ... and only the blocks the diagonal crosses are walked, by the BACKWARD, in
#: ``tile_q`` rows with the visible keys rounded up to ``tile_k``. Cell's
#: shape, ms a call by tile_q: 1024 (no walk) 1.553, 512 1.363, 256 1.360,
#: 128 1.392 — 512: as fast as any with the fewest tiles. The forward does
#: not walk (``fwd_tile_q`` is the block unless the caller pins a tile): its
#: tile carries a pass over the lane-sparse softmax state where the
#: backward's carries three more matmuls, and the walk LOST wherever a
#: sequence is one block (S1024 head 64: 0.444 whole, 0.581 at 512; S1024
#: head 128: 0.424, 0.559) and won 3 % at the cell's (0.753, 0.733).
DEFAULT_TILE_Q = 512
DEFAULT_TILE_K = 512
#: narrower tiles tried where the default does not divide the block
_FAST_TILES = (256, 128)
#: what :func:`_vmem_merged` / :func:`_vmem_split` may come to: the buffers a
#: launch states (blocks double-buffered, lane-padded; scratch) plus two fp32
#: ``[block_q, block_k]`` intermediates (Mosaic streams the rest: the parent's
#: four compiled under its 16 MiB default beside 6 MiB of blocks). It decides
#: whether the whole K and V of a (row, kv head) stay resident (the merged
#: backward) and how far the split pair's blocks shrink.
VMEM_BUDGET_BYTES = 40 * 1024 * 1024
#: the scoped-VMEM limit handed to Mosaic with every launch (a v5e core has
#: 128 MiB; the compiler's default of 16 MiB refuses the resident K and V of
#: a long sequence): the budget and half again for what Mosaic adds itself
VMEM_LIMIT_BYTES = 60 * 1024 * 1024


class FlashPlan(NamedTuple):
    """What one kernel call does with its shapes — :func:`flash_plan` makes
    it, the launcher reads it and the training engine logs it."""
    block_q: int            # query rows a grid step works on
    block_k: int            # keys a grid step works on
    tile_q: int             # rows of a compute tile in a block the diagonal
    tile_k: int             # ... crosses; its visible keys round up to this
    backward: str           # "merged": flash_attention_bwd_dqkv | "split"
    fwd_tile_q: int         # the forward's (block_q: a crossed block whole)
    tiles_computed: int     # tile_q x tile_k tiles the backward runs a
    fwd_tiles_computed: int     # (row, head), and the forward, of ...
    tiles_in_square: int    # ... Sq/tile_q x Skv/tile_k
    causal_need: float      # the share of the square causality asks for

    @property
    def resident(self) -> bool:
        """The whole K and V of a (row, kv head) are brought in once and
        stay in VMEM over its query heads, q blocks and key steps."""
        return self.backward == "merged"

    @property
    def computed_share(self) -> float:
        """Of the score square, what the backward computes (the forward:
        ``fwd_tiles_computed / tiles_in_square``)."""
        return self.tiles_computed / self.tiles_in_square

    def forward(self) -> "FlashPlan":
        """The plan as the forward kernel walks it."""
        return self._replace(tile_q=self.fwd_tile_q)

    def describe(self) -> str:
        fwd = self.fwd_tiles_computed / self.tiles_in_square
        return (f"{self.block_q} query rows x {self.block_k} keys a grid "
                f"step" + (", the whole K and V of a kv head resident"
                           if self.resident else "")
                + f"; blocks the diagonal crosses walked in {self.tile_q} "
                f"rows (forward {self.fwd_tile_q}) x their visible keys to "
                f"the next {self.tile_k}; backward {self.backward}, "
                f"{self.tiles_computed} of {self.tiles_in_square} tiles a "
                f"head = {self.computed_share:.4f} of the square computed "
                f"(forward {fwd:.4f}; the mask needs "
                f"{self.causal_need:.4f})")


def _lanes(d: int) -> int:
    return -(-d // 128) * 128


def _vmem_merged(bq: int, bk: int, skv: int, d: int, nbytes: int) -> int:
    """VMEM of ``flash_attention_bwd_dqkv``, the fattest launch where K and V
    stay whole: K, V and the dk/dv outputs double-buffered, their fp32
    scratch, the q/do/dq blocks, lse and delta (a ``[bq, 1]`` column pads to
    128 lanes), the dq scratch and two ``[bq, bk]`` intermediates."""
    dp = _lanes(d)
    return ((2 * 2 + 2 * 2) * skv * dp * nbytes + 2 * skv * dp * 4
            + 3 * 2 * bq * dp * nbytes + 2 * 2 * bq * 128 * 4
            + bq * dp * 4 + 2 * bq * bk * 4)


def _vmem_split(bq: int, bk: int, d: int, nbytes: int) -> int:
    """VMEM of ``flash_attention_bwd_dkv``, the fatter of the split pair:
    the merged kernel's with one key block for the sequence, less the dq
    block and its scratch."""
    return _vmem_merged(bq, bk, bk, d, nbytes) - bq * _lanes(d) * (
        4 + 2 * nbytes)


def _fast_blocks(seq: int) -> tuple[int, ...]:
    """Blocks a sequence can take, fattest first."""
    if seq <= _FAST_BLOCKS[0]:
        return (seq,) + tuple(b for b in _FAST_BLOCKS if b < seq
                              and seq % b == 0)
    return tuple(b for b in _FAST_BLOCKS if seq % b == 0)


def _pick_tile(seq: int, requested: int | None, default: int
               ) -> tuple[int, tuple[int, ...]] | None:
    """``(tile, blocks)`` for one axis: the compute tile and the blocks it
    divides, fattest first. An explicit request is the TILE, honored
    verbatim where it divides the sequence (a request past the sequence is
    the sequence). None -> unusable."""
    blocks = _fast_blocks(seq)
    if not blocks:
        return None
    if requested is not None:
        tile = min(requested, seq)
        if seq % tile:
            return None
    else:
        tile = next((t for t in (default,) + _FAST_TILES
                     if blocks[0] % t == 0), blocks[0])
    return tile, tuple(b for b in blocks if b % tile == 0) or (tile,)


def _crossed_offsets(plan_or_blocks) -> tuple[int, ...]:
    """The offsets ``q_start - k_start`` at which the diagonal crosses a
    ``[block_q, block_k]`` block (both starts are multiples of their block,
    so of the gcd): a block further up is never computed, one further down
    needs no mask. Equal blocks: ``(0,)``."""
    bq, bk = plan_or_blocks[:2]
    g = math.gcd(bq, bk)
    return tuple(o for o in range(-bq + g, bk - 1, g))


def _block_tiles(plan_or_blocks, offset: int):
    """The compute tiles ``(i, width)`` of a block the diagonal crosses at
    ``offset`` (``q_start - k_start``): for each ``tile_q`` rows ONE score
    tile over the keys ``[0, width)`` of the block that some row of it may
    see, ``width`` rounded up to ``tile_k`` — what lies past it is never
    computed. Static: the kernels unroll this walk, :func:`flash_plan`
    counts with it."""
    bq, bk, tq, tk = plan_or_blocks[:4]
    for i in range(bq // tq):
        seen = min(bk, offset + (i + 1) * tq)       # keys <= the last row
        if seen > 0:
            yield i, min(bk, -(-seen // tk) * tk)


def flash_plan(q_shape, kv_shape, dtype, causal: bool,
               block_q: int | None = None, block_k: int | None = None
               ) -> FlashPlan | None:
    """The blocks, the compute tile, the backward form and the tiles
    computed for one kernel call of ``q_shape`` ``[B, Sq, H, D]`` and
    ``kv_shape`` ``[B, Skv, KV, D]`` — a pure function of shapes, the single
    source of truth for the gate, the launcher and the engine's ``flash:``
    log line. ``block_q``/``block_k`` pin the compute tile (the caller owns
    the tradeoff). None where no blocking divides the sequences or fits.

    Where the whole K and V of one (row, kv head) with their fp32 dk/dv
    scratch fit :data:`VMEM_BUDGET_BYTES` they stay resident and the
    backward is ONE kernel (scores made once); otherwise keys come a block
    a grid step and the backward is the split dq + dk/dv pair."""
    Sq, D = q_shape[1], q_shape[3]
    Skv = kv_shape[1]
    nbytes = jnp.dtype(dtype).itemsize
    picked_q = _pick_tile(Sq, block_q, DEFAULT_TILE_Q)
    picked_k = _pick_tile(Skv, block_k, DEFAULT_TILE_K)
    if picked_q is None or picked_k is None:
        return None
    (tq, q_blocks), (tk, k_blocks) = picked_q, picked_k
    bq, bk = q_blocks[0], k_blocks[0]
    if _vmem_merged(bq, bk, Skv, D, nbytes) <= VMEM_BUDGET_BYTES:
        backward = "merged"
    else:
        backward = "split"
        pinned = block_q is not None and block_k is not None
        while not pinned and _vmem_split(bq, bk, D,
                                         nbytes) > VMEM_BUDGET_BYTES:
            # the fatter axis first
            smaller_q = [b for b in q_blocks if b < bq]
            smaller_k = [b for b in k_blocks if b < bk]
            if smaller_q and (bq >= bk or not smaller_k):
                bq = smaller_q[0]
            elif smaller_k:
                bk = smaller_k[0]
            else:
                return None
    fwd_tq = tq if block_q is not None else bq  # the forward does not walk

    def computed(rows: int) -> int:
        blocks = (bq, bk, rows, tk)
        crossed = {o: sum(w // tk for _, w in _block_tiles(blocks, o))
                   * rows // tq for o in _crossed_offsets(blocks)}
        return sum(
            (bq // tq) * (bk // tk) if not causal or qs - ks >= bk - 1
            else crossed.get(qs - ks, 0)
            for qs in range(0, Sq, bq) for ks in range(0, Skv, bk))

    low = min(Sq, Skv)      # rows whose diagonal still lies inside the keys
    need = (low * (low + 1) // 2 + (Sq - low) * Skv) / (Sq * Skv) \
        if causal else 1.0
    return FlashPlan(bq, bk, tq, tk, backward, fwd_tq, computed(tq),
                     computed(fwd_tq), (Sq // tq) * (Skv // tk), need)


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
    del v
    if positions is not None or mask is not None:
        return "cached/masked attention (positions or mask given)"
    B, Sq, H, D = q.shape
    Skv, KV = k.shape[1], k.shape[2]
    if Sq != Skv:                      # prefill/training only
        return f"q length {Sq} != kv length {Skv}"
    if Sq < MIN_SEQ:                   # tiny: XLA is fast and cheap anyway
        return f"sequence {Sq} < {MIN_SEQ}"
    if flash_plan(q.shape, k.shape, q.dtype, causal) is None:
        return (f"sequence {Sq} has no block divisor in {_FAST_BLOCKS} "
                f"that fits the VMEM budget")
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


def _apply_causal_mask(s, q0: int):
    """Mask a score tile whose first row sits at position ``q0`` (static)
    relative to its first key."""
    q_pos = q0 + jax.lax.broadcasted_iota(jnp.int32, s.shape, 0)
    k_pos = jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
    return jnp.where(k_pos <= q_pos, s, NEG_INF)


def _walk_block(plan: FlashPlan, causal: bool, offset, tile):
    """Run the ``[block_q, block_k]`` block of a grid step as score tiles:
    ``tile(rows, cols, q0)`` — ``rows``/``cols`` static slices of the block;
    ``q0`` the first row's position relative to the block's first key where
    the tile needs the causal mask, else None. ``offset`` (``q_start -
    k_start``, a grid step's scalar) picks ONE of a few unrolled walks: a
    block wholly under the diagonal is one unmasked tile; a block the
    diagonal crosses runs, for each ``tile_q`` rows, one masked tile over
    the keys those rows can see (:func:`_block_tiles`); a block above the
    diagonal runs nothing."""
    def whole():
        tile(slice(None), slice(None), None)

    def crossed(off):
        for i, width in _block_tiles(plan, off):
            tile(slice(i * plan.tile_q, (i + 1) * plan.tile_q),
                 slice(0, width), off + i * plan.tile_q)

    if not causal:
        return whole()
    pl.when(offset >= plan.block_k - 1)(whole)
    for off in _crossed_offsets(plan):
        pl.when(offset == off)(functools.partial(crossed, off))


def _scores(q, k, scale: float):
    return jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                               preferred_element_type=jnp.float32) * scale


def _compiler_params():
    return pltpu.CompilerParams(vmem_limit_bytes=VMEM_LIMIT_BYTES)


def _key_blocks(x, plan: FlashPlan):
    """``[B, KV, Skv, D]`` seen as ``[B, KV, Skv/block_k, block_k, D]`` (a
    bitcast): the kernels pick a key block by its leading index."""
    B, KV, Skv, D = x.shape
    return x.reshape(B, KV, Skv // plan.block_k, plan.block_k, D)


def _key_spec(plan: FlashPlan, k5, key):
    """The BlockSpec of such an operand for a grid whose ids ``key(*ids)``
    maps to ``(b, kv, key block)``: one block a step, or — resident — all
    of a (row, kv head)'s, brought in once."""
    n, bk, D = k5.shape[2:]
    if plan.resident:
        return pl.BlockSpec((1, 1, n, bk, D),
                            lambda *ids: (*key(*ids)[:2], 0, 0, 0))
    return pl.BlockSpec((1, 1, 1, bk, D), lambda *ids: (*key(*ids), 0, 0))


# ---------------------------------------------------------------------------
# forward
# ---------------------------------------------------------------------------

def _fwd_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref,
                m_scr, l_scr, acc_scr, *, scale: float, causal: bool,
                plan: FlashPlan):
    qi = pl.program_id(2)
    kj = pl.program_id(3)
    nk = pl.num_programs(3)
    kb = kj if plan.resident else 0

    @pl.when(kj == 0)
    def _init():
        m_scr[:] = jnp.full_like(m_scr, NEG_INF)
        l_scr[:] = jnp.zeros_like(l_scr)
        acc_scr[:] = jnp.zeros_like(acc_scr)

    def tile(rows, cols, q0):
        s = _scores(q_ref[0, 0, rows, :], k_ref[0, 0, kb, cols, :], scale)
        if q0 is not None:
            s = _apply_causal_mask(s, q0)
        m_prev = m_scr[rows, :]                 # [tile_q, 1]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(s - m_new)                  # [tile_q, tile_k]
        l_scr[rows, :] = alpha * l_scr[rows, :] \
            + jnp.sum(p, axis=-1, keepdims=True)
        acc_scr[rows, :] = acc_scr[rows, :] * alpha + jax.lax.dot_general(
            p.astype(v_ref.dtype), v_ref[0, 0, kb, cols, :],
            (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)
        m_scr[rows, :] = m_new

    _walk_block(plan.forward(), causal,
                qi * plan.block_q - kj * plan.block_k, tile)

    @pl.when(kj == nk - 1)
    def _finalize():
        l = l_scr[:]
        l_safe = jnp.where(l == 0.0, 1.0, l)
        o_ref[0, 0, :, :] = (acc_scr[:] / l_safe).astype(o_ref.dtype)
        lse_ref[0, 0, :, :] = m_scr[:] + jnp.log(l_safe)


def _fwd(q, k, v, *, causal: bool, scale: float, plan: FlashPlan):
    """q: [B,H,Sq,D]; k/v: [B,KV,Skv,D] → (out [B,H,Sq,D], lse [B,H,Sq,1]).

    lse is carried with a trailing singleton dim: TPU block shapes must have
    their last two dims divide (8, 128) or equal the array dims, which a
    (1, 1, block_q) block over [B, H, S] cannot satisfy."""
    B, H, Sq, D = q.shape
    KV, Skv = k.shape[1], k.shape[2]
    group = H // KV
    block_q = plan.block_q
    k, v = _key_blocks(k, plan), _key_blocks(v, plan)
    keys = _key_spec(plan, k, lambda b, h, i, j: (b, h // group, j))

    kernel = functools.partial(_fwd_kernel, scale=scale, causal=causal,
                               plan=plan)
    out, lse = pl.pallas_call(
        kernel,
        grid=(B, H, Sq // block_q, Skv // plan.block_k),
        in_specs=[
            pl.BlockSpec((1, 1, block_q, D), lambda b, h, i, j: (b, h, i, 0)),
            keys, keys,
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
        compiler_params=_compiler_params(),
        name="flash_attention_fwd",
        interpret=_interpret(),
    )(q, k, v)
    return out, lse


# ---------------------------------------------------------------------------
# backward
# ---------------------------------------------------------------------------

#: the grid axes (query head of the kv head's group, q block, key block) of
#: each backward form, after the leading (row, head) pair
_BWD_AXES = {"dqkv": (2, 3, 4), "dq": (None, 2, 3), "dkv": (3, 4, 2)}


def _bwd_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, *refs,
                scale: float, causal: bool, plan: FlashPlan, form: str):
    """The backward of one block, in one of three forms. ``dqkv`` (K and V
    resident, grid ``(B, KV, group, Sq/block_q, Skv/block_k)``): s, p, dp
    and ds once a compute tile, dQ accumulated over a q block's key steps,
    dK/dV accumulated in ``[Skv, D]`` fp32 scratch over the q blocks AND the
    query heads of the kv head. ``dq`` (grid ``(B, H, Sq/block_q,
    Skv/block_k)``) and ``dkv`` (grid ``(B, KV, Skv/block_k, group,
    Sq/block_q)``) are the same tile with two of its five matmuls left out
    each, for keys that come a block a step: both make the scores."""
    want_dq, want_dkv = form != "dkv", form != "dq"
    refs = list(refs)
    dq_ref = refs.pop(0) if want_dq else None
    dk_ref, dv_ref = (refs.pop(0), refs.pop(0)) if want_dkv else (None, None)
    dq_scr = refs.pop(0) if want_dq else None
    dk_scr, dv_scr = refs if want_dkv else (None, None)

    g_ax, q_ax, k_ax = _BWD_AXES[form]
    qi, kj = pl.program_id(q_ax), pl.program_id(k_ax)
    kb = kj if plan.resident else 0

    if want_dq:     # complete once a q block has met its last key block
        @pl.when(kj == 0)
        def _init_dq():
            dq_scr[:] = jnp.zeros_like(dq_scr)

    if want_dkv:    # complete once the keys have met the kv head's last
        first = (pl.program_id(g_ax) == 0) & (qi == 0)      # query block
        last = (pl.program_id(g_ax) == pl.num_programs(g_ax) - 1) \
            & (qi == pl.num_programs(q_ax) - 1)
        if plan.resident:   # the scratch holds every key block
            first &= kj == 0
            last &= kj == pl.num_programs(k_ax) - 1

        @pl.when(first)
        def _init_dkv():
            dk_scr[:] = jnp.zeros_like(dk_scr)
            dv_scr[:] = jnp.zeros_like(dv_scr)

    def tile(rows, cols, q0):
        q = q_ref[0, 0, rows, :]
        k = k_ref[0, 0, kb, cols, :]
        do = do_ref[0, 0, rows, :]
        s = _scores(q, k, scale)
        if q0 is not None:
            s = _apply_causal_mask(s, q0)
        p = jnp.exp(s - lse_ref[0, 0, rows, :])         # [tile_q, tile_k]
        if want_dkv:    # dV += P^T @ dO
            dv_scr[kb, cols, :] += jax.lax.dot_general(
                p.astype(do.dtype), do, (((0,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)
        dp = jax.lax.dot_general(do, v_ref[0, 0, kb, cols, :],
                                 (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        ds = p * (dp - delta_ref[0, 0, rows, :]) * scale
        if want_dq:     # dQ += dS @ K
            dq_scr[rows, :] += jax.lax.dot_general(
                ds.astype(k.dtype), k, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)
        if want_dkv:    # dK += dS^T @ Q
            dk_scr[kb, cols, :] += jax.lax.dot_general(
                ds.astype(q.dtype), q, (((0,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)

    _walk_block(plan, causal, qi * plan.block_q - kj * plan.block_k, tile)

    if want_dq:
        @pl.when(kj == pl.num_programs(k_ax) - 1)
        def _finalize_dq():
            dq_ref[0, 0, :, :] = dq_scr[:].astype(dq_ref.dtype)

    if want_dkv:
        @pl.when(last)
        def _finalize_dkv():
            dk_ref[0, 0, :, :, :] = dk_scr[:].astype(dk_ref.dtype)
            dv_ref[0, 0, :, :, :] = dv_scr[:].astype(dv_ref.dtype)


def _bwd(causal, scale, plan: FlashPlan, res, do):
    q, k, v, out, lse = res
    B, H, Sq, D = q.shape
    KV, Skv = k.shape[1], k.shape[2]
    group = H // KV
    block_q = plan.block_q
    n_q, n_k = Sq // block_q, Skv // plan.block_k
    delta = jnp.sum(do.astype(jnp.float32) * out.astype(jnp.float32),
                    axis=-1, keepdims=True)                   # [B,H,Sq,1]
    k5, v5 = _key_blocks(k, plan), _key_blocks(v, plan)

    def launch(form, grid, q_index, k_index):
        """One backward kernel: ``q_index``/``k_index`` map its grid to the
        (row, head, block) of the q-side and the key-side operands."""
        rows = lambda width: pl.BlockSpec(
            (1, 1, block_q, width), lambda *ids: (*q_index(*ids), 0))
        keys = _key_spec(plan, k5, k_index)
        dq = ([rows(D)], [jax.ShapeDtypeStruct(q.shape, q.dtype)],
              [pltpu.VMEM((block_q, D), jnp.float32)])
        dkv = ([keys, keys], [jax.ShapeDtypeStruct(k5.shape, k.dtype),
                              jax.ShapeDtypeStruct(v5.shape, v.dtype)],
               [pltpu.VMEM(keys.block_shape[2:], jnp.float32)] * 2)
        specs, shapes, scratch = (
            [a + b for a, b in zip(dq, dkv)] if form == "dqkv"
            else dq if form == "dq" else dkv)
        return pl.pallas_call(
            functools.partial(_bwd_kernel, scale=scale, causal=causal,
                              plan=plan, form=form),
            grid=grid,
            in_specs=[rows(D), keys, keys, rows(D), rows(1), rows(1)],
            out_specs=specs, out_shape=shapes, scratch_shapes=scratch,
            compiler_params=_compiler_params(),
            name=f"flash_attention_bwd_{form}",
            interpret=_interpret(),
        )(q, k5, v5, do, lse, delta)

    if plan.backward == "merged":
        dq, dk, dv = launch(
            "dqkv", (B, KV, group, n_q, n_k),
            lambda b, kv, g, i, j: (b, kv * group + g, i),
            lambda b, kv, g, i, j: (b, kv, j))
    else:
        dq, = launch("dq", (B, H, n_q, n_k),
                     lambda b, h, i, j: (b, h, i),
                     lambda b, h, i, j: (b, h // group, j))
        dk, dv = launch("dkv", (B, KV, n_k, group, n_q),
                        lambda b, kv, j, g, i: (b, kv * group + g, i),
                        lambda b, kv, j, g, i: (b, kv, j))
    return dq, dk.reshape(k.shape), dv.reshape(v.shape)


# ---------------------------------------------------------------------------
# public entry: [B,S,H,D] layout to match ops.attention.dot_product_attention
# ---------------------------------------------------------------------------

@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5))
def _flash(q, k, v, causal, scale, plan):
    out, _ = _fwd(q, k, v, causal=causal, scale=scale, plan=plan)
    return out


def _flash_fwd(q, k, v, causal, scale, plan):
    out, lse = _fwd(q, k, v, causal=causal, scale=scale, plan=plan)
    return out, (q, k, v, out, lse)


_flash.defvjp(_flash_fwd, _bwd)


def flash_attention(q, k, v, *, causal: bool = True,
                    block_q: int | None = None,
                    block_k: int | None = None,
                    scale: float | None = None) -> Any:
    """q: [B,Sq,H,D]; k/v: [B,Skv,KV,D]. Returns [B,Sq,H,D].
    ``block_q``/``block_k`` pin the compute tile (:func:`flash_plan`)."""
    B, Sq, H, D = q.shape
    if scale is None:
        scale = 1.0 / (D ** 0.5)
    plan = flash_plan(q.shape, k.shape, q.dtype, causal, block_q, block_k)
    if plan is None:
        raise ValueError(
            f"flash_attention cannot block Sq={Sq}/Skv={k.shape[1]}: "
            f"sequences <= {_FAST_BLOCKS[0]} run as one block, longer ones "
            f"need a divisor in {_FAST_BLOCKS} (pad the sequence, e.g. to a "
            f"multiple of {_FAST_BLOCKS[-1]}), and explicit block_q/block_k "
            f"must divide the sequence")
    if q.shape[2] % k.shape[2]:
        raise ValueError(
            f"GQA requires num q heads ({q.shape[2]}) divisible by kv heads "
            f"({k.shape[2]})")
    qt = jnp.swapaxes(q, 1, 2)          # [B,H,S,D]
    kt = jnp.swapaxes(k, 1, 2)
    vt = jnp.swapaxes(v, 1, 2)
    out = _flash(qt, kt, vt, causal, float(scale), plan)
    return jnp.swapaxes(out, 1, 2)
