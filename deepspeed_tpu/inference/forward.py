"""The serving forward: one ragged step of the model over the paged KV pools.

Split out of ``engine_v2.py`` so that the forward has ONE shape, whatever the
model:

- **A tuple a kind of layer, always** (:func:`cache_kinds`' order): the pools,
  the block tables, the staged buffers and the forward's fresh K/V are tuples
  of ``len(kinds)`` entries, for a model of one kind too. A kind whose state
  is a RECORD a slot and not pages ("conv": the last inputs of a short
  convolution) is one more entry of the same tuples: its "pool" is the
  records ``[layers, max_seqs + 1, rows, width]``, its "table" each row's
  slot, its fresh state each row's new record; a model without such layers
  carries no such entry.
- **One walk over a stacked model** (:func:`scan_layers`: a scan over periods
  of layer kinds; a period of one is the plain scan over depth). Leading
  dense layers before a uniform tail of expert layers are walked from their
  own trees first, then the tail is scanned; layers that cannot be stacked
  at all (MoE on some layers in the middle, a record kind) take the
  unrolled loop.
- **One return form**: :class:`RaggedForward` never writes a pool. It returns
  ``((k_ys, v_ys), logits)`` — this call's fresh K/V a kind — and the
  program that called it merges them inside the same ``jit``
  (:func:`merge_step` for a step plan, :func:`merge_rows` for a decode window
  and the accepted path of a speculative round).

This module is the only one that calls the Pallas kernels of the serving path
(the paged attention, the grouped and the quantised matmuls;
``bin/check_state_invariants.py`` holds the paged kernel to it), and it
imports nothing from ``engine_v2``: a test can build a :class:`RaggedForward`
without an engine.
"""
from __future__ import annotations

import dataclasses
from contextlib import nullcontext
from dataclasses import dataclass
from functools import partial
from typing import Any, Mapping, NamedTuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import shard_map
from jax.sharding import NamedSharding, PartitionSpec as P

from ..models.transformer import (
    _ACTS,
    CONV,
    GLU_ACTS,
    DenseFFN,
    ModelConfig,
    Norm,
    alibi_slopes,
    apply_rope,
    cache_kind,
    conv_mix,
    dense_ffn_config,
    is_moe_layer,
    kind_ropes,
    latent_row,
    qk_norm,
)
from ..moe.layer import dropless_dispatch_combine
from ..moe.sharded_moe import topk_dropless_gating
from ..ops.pallas.grouped_matmul import gmm_plan, grouped_matmul_layer
from ..ops.pallas.paged_attention import (latent_prefill_plan,
                                          paged_latent_prefill,
                                          paged_ragged_attention,
                                          paged_work_list)
from ..ops.pallas.quant_matmul import (QuantGrouped, QuantLinear,
                                       quant_grouped_matmul, quant_matmul)
from ..parallel.tensor import (_ring_rs_core, allgather_matmul,
                               matmul_reduce_scatter, overlap_counters)
from ..parallel.topology import MeshTopology
from ..utils.annotations import device_scope
from ..utils.logging import logger
from .attn_registry import AttnSelection
Pytree = Any

#: TP kind -> weight PartitionSpec, the single source for quantize-time
#: sharding, matmul-time shard_map specs, and stacked-layer shardings.
#: 2D = dense [K, N] QuantLinear; 3D = grouped [n, K, N] QuantGrouped.
KIND_SPEC_2D = {"row": P("tensor", None), "col": P(None, "tensor"),
                "rep": P(None, None)}
KIND_SPEC_3D = {"row": P(None, "tensor", None),
                "col": P(None, None, "tensor"),
                "rep": P(None, None, None)}


#: ``tp_overlap`` in auto mode: the fewest token rows a ring chunk
#: (S*T // tensor) must carry before a program rings
TP_OVERLAP_MIN_ROWS = 64

#: floors of the routed-expert tile height: a bf16 tile is 16 sublanes; the
#: quantised grouped GEMM was validated (and rings its chunks) at 32
MOE_TILE_FLOOR = {False: 16, True: 32}


def moe_tile_rows(tokens: int, top_k: int, num_experts: int,
                  quantised: bool = False) -> int:
    """Tile height of the routed-expert buffer of a step that carries
    ``tokens`` rows (a static shape of the program): TWICE the mean number
    of rows an expert gets, rounded up to a power of two, inside [floor,
    128]. Twice, so that an expert's rows fill one tile with room for the
    spread around the mean: a second tile for the same expert is one more
    pass over its rows' column blocks. Chip timings behind the rule (one
    OLMoE layer alone on a v5e, ``benchmark/tools/time_moe_layer.py``,
    ``PERF.md`` PR 25): at 48 rows every height costs the same (1.30-1.33
    ms; the kernel skips the buffer's empty tail), at 128 rows 32 wins
    (1.37 ms against 1.55 at 16), at 512 and 2048 rows 128 wins (1.77 ms
    against 2.75 at 16; 4.43 against 6.48)."""
    mean2 = -(-2 * tokens * top_k // num_experts)
    return min(128, max(MOE_TILE_FLOOR[bool(quantised)],
                        1 << (mean2 - 1).bit_length()))


def moe_padded_rows(tokens: int, top_k: int, num_experts: int,
                    block_m: int) -> int:
    """Rows of the tile-aligned buffer ``sort_tokens_by_expert`` makes."""
    return -(-tokens * top_k // block_m) * block_m + num_experts * block_m


def scan_layers(stacked: Pytree, x, apply_layer, period: int = 1,
                per_layer=None):
    """THE walk over a depth-stacked model: a ``lax.scan`` over PERIODS of
    ``period`` layers (one kind of layer a place; a period of one is the
    plain scan over depth), so the body knows each layer's place ``j`` in
    the period — its kind — statically. Layer ``li``'s weights are sliced
    out of the ``[L, ...]`` stack INSIDE the body, so each slice is an
    operand of the op that consumes it and XLA fuses it there: the matmuls
    read the stack in place. (Carrying the slice of layer ``li + 1`` through
    the scan makes it a buffer, which is a copy of every layer's weights
    every walk: 38 % of a decode iteration on a v5e, ``PERF.md`` PR 24.)
    ``apply_layer(x, p, li, per_layer[j][pi], j) -> (x, ys)``;
    ``per_layer`` is None or one pytree a place, each with a leading axis
    of ``L // period``. Returns ``(x, ys)`` with ``ys`` a tuple over ``j``
    of that place's outputs stacked over the periods. Module-level so that
    ``tests/test_chip_compile.py`` can compile the walk alone."""
    L = jax.tree.leaves(stacked)[0].shape[0]

    def body(xc, inp):
        pi, extra = inp
        ys = []
        for j in range(period):
            li = pi * period + j
            with device_scope("weight_walk"):
                p = jax.tree.map(
                    lambda s: jax.lax.dynamic_index_in_dim(
                        s, li, 0, keepdims=False), stacked)
            xc, y = apply_layer(xc, p, li,
                                None if extra is None else extra[j], j)
            ys.append(y)
        return xc, tuple(ys)

    return jax.lax.scan(
        body, x, (jnp.arange(L // period, dtype=jnp.int32), per_layer))


@dataclass(frozen=True)
class CacheKind:
    """One kind of layer's cache as the engine holds it: which layers write
    it and its geometry. A PAGED kind ("full" | "window": keys and values):
    the mask its layers attend under and the width of a sequence's block
    table in its pool (``StateManager.kinds`` holds the allocator);
    ``ring_tokens`` > 0: the table is a ring of that many token slots,
    reused in place (a window kind narrower than a whole context). A RECORD
    kind ("conv", ``rows`` > 0): ``rows`` x ``width`` values a layer and
    SLOT, addressed by the slot a live sequence already holds — no
    allocator, no table, nothing to reserve.

    A paged kind's PAGE is ``[halves, heads, block, lanes]`` a layer:
    ``halves`` 2 — keys and values, ``heads`` KV heads (:func:`kv_pack` of
    them side by side in a row of ``lanes``) — or 1: the LATENT kind
    ("latent": latent attention's ``[c | k_r]``, ONE row a token shared by
    every head, no K/V halves, no KV-head dim; ``row_values`` of its
    ``lanes`` are the model's, the rest lane padding). The pool's
    allocation, its merges, the log line and what moves pages read the
    geometry here."""
    name: str                      # "full" | "window" | "latent" | "conv"
    layers: tuple[int, ...]        # the model's layers of this kind
    window: int | None             # sliding-window mask (None: full)
    max_blocks: int                # block-table width of a sequence
    ring_tokens: int               # 0 = a table that grows
    num_blocks: int                # blocks of its pool (records: slots + 1)
    rows: int = 0                  # > 0: a record kind, rows of a record
    width: int = 0                 # values a row of a record
    halves: int = 2                # a page's K and V halves; 1: one row
    heads: int = 0                 # heads a page holds
    lanes: int = 0                 # width of a page row as stored
    row_values: int = 0            # of them the model's (0: all of them)

    @property
    def is_record(self) -> bool:
        return self.rows > 0

    @property
    def is_latent(self) -> bool:
        return self.halves == 1

    def pool_shape(self, block_size: int) -> tuple[int, ...]:
        """The kind's pool: ``[layers, halves, heads, blocks, block,
        lanes]``, or a record kind's ``[layers, slots + 1, rows, width]``."""
        if self.is_record:
            return (len(self.layers), self.num_blocks, self.rows, self.width)
        return (len(self.layers), self.halves, self.heads, self.num_blocks,
                block_size, self.lanes)


#: the latent kind's name, and the lanes a stored row is padded to a
#: multiple of (a v5e vector register's)
LATENT = "latent"
LANES = 128


def cache_kinds(m: ModelConfig, cfg: "RaggedInferenceConfig",
                tp: int = 1) -> tuple[CacheKind, ...]:
    """The caches a model's layers need: the PAGED kinds first, each with
    the layers that HAVE keys and values only, the PRIMARY of them first
    ("full" where the model has full layers), then the record kind where
    the model has "conv" layers. A window kind keeps a ring of
    ceil((W + step) / block) + 1 blocks a sequence where that is narrower
    than a whole context — the mistral rolling buffer: only the last window
    (+ the step being written) stays resident. The primary's pool is
    ``num_blocks``; a further paged kind's is every slot's whole ring
    (``max_seqs`` x ring + the trash block), so it never refuses. The
    record kind "conv" holds ``conv_taps - 1`` rows of ``hidden_size`` a
    layer and slot, ``max_seqs`` records and one more for rows that are not
    live (the trash record, as a pool's trash block).

    A model of latent attention (``kv_lora_rank``) has ONE paged kind,
    "latent", primary, every layer, a table that grows: a linear chain of
    pages like "full" (chunk growth, the prefix trie and rewind work on
    it), whose page row is ``[c | k_r]`` — ``latent_width`` values (576)
    padded to a multiple of 128 lanes (640): 4.5 lanes' worth is neither
    a tile nor, pinned row-major, the device's default layout. ``tp``: the
    tensor axis' size (it decides :func:`kv_pack`, so a page's ``heads`` and
    ``lanes``; the engine hands its topology's)."""
    bs = cfg.block_size
    whole = -(-cfg.max_seq_len // bs)
    if m.kv_lora_rank:
        if set(m.kinds) != {"full"}:
            raise ValueError("latent attention serves full causal rope "
                             "layers only (no window, no conv)")
        return (CacheKind(
            LATENT, tuple(range(m.num_layers)), None, whole, 0,
            cfg.num_blocks, halves=1, heads=1,
            lanes=-(-m.latent_width // LANES) * LANES,
            row_values=m.latent_width),)
    pack = kv_pack(m, tp)
    geom = dict(heads=m.kv_heads // pack, lanes=m.head_dim * pack)
    of = [cache_kind(k) for k in m.kinds]
    names = sorted(set(of) - {CONV})            # "full" < "window"
    if not names:
        raise ValueError("a model needs at least one attention layer: the "
                         "engine's primary cache is a paged one")
    out = []
    for name in names:
        width, ring, W = whole, 0, None
        if name == "window":
            W = m.sliding_window
            step_max = max(cfg.chunk, max(cfg.decode_window, 1))
            nwin = -(-(W + step_max) // bs) + 1
            if nwin < whole or len(names) > 1:
                width = min(nwin, whole)
                ring = width * bs
        out.append(CacheKind(
            name, tuple(i for i, k in enumerate(of) if k == name), W, width,
            ring, cfg.num_blocks if not out else cfg.max_seqs * width + 1,
            **geom))
    if CONV in of:
        out.append(CacheKind(
            CONV, tuple(i for i, k in enumerate(of) if k == CONV), None, 0,
            0, cfg.max_seqs + 1, rows=m.conv_taps - 1, width=m.hidden_size))
    return tuple(out)


def kv_pack(m: ModelConfig, tp: int = 1) -> int:
    """How many KV heads share one page row of the pool: 2 where a head is
    64 wide (and the KV heads pair up, on every tensor shard), else 1.

    A pool ``[L, 2, KV, nb, block, 64]`` pinned row-major is NOT the
    device's default layout on a v5e at a serving size — ``[1, 2, 8, 8192,
    64, 64]`` defaults to ``(0, 1, 2, 4, 5, 3)``, ``[.., 128, 64]`` swaps
    the page and head dims (PR 50, calls 1 and 3) — and an executable read
    back from the persistent compile cache hands its outputs back in the
    DEFAULT layout whatever it pinned (jax 0.9.0 / libtpu 0.0.34, PR 21;
    seen again in both calls), so such an engine had to serve with the
    cache off. Two KV heads side by side make the pool ``[L, 2, KV/2, nb,
    block, 128]``: the same bytes, row-major BY default, no lane padding
    (a 64-wide row is stored in a 128-lane tile: twice its bytes). The
    paged kernel then sees a model of KV/2 heads of 128: a query carries
    zeros in its partner's 64 lanes (its scores are its own head's; the
    scale stays 1/sqrt(64)) and keeps its own half of the output. The
    kernel is untouched and reads the same K and V bytes; the 128-deep
    contraction the MXU does anyway runs over the partner's lanes times
    zero, and the P.V product is twice as wide. Heads of 128 and more are
    left as they were."""
    return 2 if (m.head_dim == 64 and m.kv_heads % (2 * max(tp, 1)) == 0) \
        else 1


def pack_heads(q, k, v, pack: int):
    """``q`` ``[S, T, H, D]`` and ``k``, ``v`` ``[S, T, KV, D]`` as the
    kernel sees them over a pool of ``pack`` KV heads a page row: ``[S, T,
    H, pack * D]`` with zeros outside the lanes of the query's own KV head,
    and ``[S, T, KV / pack, pack * D]`` (a reshape)."""
    S, T, H, D = q.shape
    KV = k.shape[2]
    q6 = q.reshape(S, T, KV // pack, pack, H // KV, D)
    q = jnp.stack([jnp.pad(q6[:, :, :, j], [(0, 0)] * 4
                           + [(j * D, (pack - 1 - j) * D)])
                   for j in range(pack)], axis=3).reshape(S, T, H, pack * D)
    shape = (S, T, KV // pack, pack * D)
    return q, k.reshape(shape), v.reshape(shape)


def unpack_heads(o, kv_heads: int, pack: int):
    """The attention output ``[S, T, H, pack * D]`` over a packed pool back
    to ``[S, T, H, D]``: each head keeps the lanes of its own KV head."""
    S, T, H, Dp = o.shape
    D = Dp // pack
    o7 = o.reshape(S, T, kv_heads // pack, pack, H // kv_heads, pack, D)
    return jnp.stack([o7[:, :, :, j, :, j] for j in range(pack)],
                     axis=3).reshape(S, T, H, D)


def _io_specs(kind: str) -> tuple[P, P]:
    """``shard_map`` specs of a ``[rows, K] @ [K, N]`` matmul's input and
    output under a weight's TP kind: a ``row`` weight contracts a sharded K
    (the partial products are summed over ``tensor``), a ``col`` weight
    leaves the output's columns sharded."""
    return (P(None, "tensor") if kind == "row" else P(None, None),
            P(None, "tensor") if kind == "col" else P(None, None))


def stage_rows(n: int, block_size: int) -> int:
    """Rows of the staged-KV buffer that holds ``n`` fresh tokens a slot:
    sublane-aligned, and page-divisible when it spans pages (the kernel
    tiles the stage in ``block_size`` rows)."""
    rows = max(8, n)
    return rows if rows <= block_size else -(-rows // block_size) * block_size


class DecodeBlock(NamedTuple):
    """The decode-ready rows that ride a prefill step: a second, ``[B, 1]``
    segment of the same forward (``B`` = ``max_seqs``: row ``b`` is slot
    ``b``, whoever is live), one token a row at the row's own position
    against its own tables — what a ``[max_seqs, 1]`` decode plan holds."""
    token_ids: Any                 # [B, 1]
    positions: Any                 # [B, 1]
    block_tables: tuple            # a kind: [B, width] (a record kind: [B])
    seq_lens: Any                  # [B], this token included (0: no
    #                                request in the row: it reaches no
    #                                routed expert, as a plan's empty row)


@dataclass(frozen=True)
class _Segment:
    """What is per SEQUENCE in one ``[S, T]`` rectangle of a forward's
    tokens: attention, a conv layer's record and the staged K/V run a
    segment with these; everything per token runs on the segments' tokens
    together."""
    S: int
    T: int
    Ts: int                        # rows of its staged K/V
    positions: Any                 # [S, T]
    seq_lens: Any                  # [S]
    q_starts: Any                  # [S] each row's first position
    stage_starts: Any              # [S]
    block_tables: tuple
    n_valid: Any                   # [S] tokens of each row that count
    fresh_row: Any                 # [S, 1, 1] the row starts its sequence
    #                                (a record kind's row has no past: zeros)
    sample_idx: Any                # [S]
    attn_works: tuple              # the paged kernel's work list a kind
    empty_stage: tuple


@dataclass(frozen=True, eq=False)
class RaggedForward:
    """The serving forward of one engine (reads the TransformerLM param tree
    directly; reference model_implementations/
    inference_transformer_base.py:48) and the quantised-matmul dispatch it
    calls. The fields are everything it reads of the engine, handed over
    once, at the end of ``InferenceEngineV2.__init__``."""
    mcfg: ModelConfig
    #: the engine's ``RaggedInferenceConfig``: ``block_size``, ``dtype`` and
    #: ``quant_bits`` are read
    config: Any
    kinds: tuple[CacheKind, ...]
    topology: MeshTopology
    #: ring collective-matmul TP: the tensor axis' size where the geometry
    #: rings (0: blocking), and whether ``tp_overlap=True`` forces it
    tp_ring_n: int
    tp_ring_force: bool
    #: the attention registry's static selection a dispatch mode
    #: (``attn_registry.select_attention``): kernel or gather
    attn_decode_sel: AttnSelection
    attn_tree_sel: AttnSelection
    #: a quantised weight's TP kind, by weight name (``_quantize_weights``)
    qkind: Mapping[str, str]
    #: the engine's ``gmm_plans``: ``gmm`` books each distinct block there
    gmm_plans: dict
    #: KV heads held side by side in one page row (:func:`kv_pack`): 1, or
    #: 2 where heads are 64 wide
    kv_pack: int = 1

    def qmm(self, x2d, qw, name: str, li=None):
        """Quantized matmul dispatch: single device runs the Pallas kernel
        directly; on a mesh it runs per-shard through shard_map with specs
        from the weight's TP kind (pallas_call has no GSPMD rule). ``row``
        weights contract a sharded K, so the partial products psum over
        the tensor axis — the same collective GSPMD inserts for the dense
        einsum. ``li`` (a traced layer index) selects a layer of a
        STACKED [L, ...] QuantLinear inside the kernel — the layer-scan
        path passes the whole stack so no per-layer code copies are
        materialized (measured r5: scan slices of int8 codes cost
        ~0.57ms per decode iteration)."""
        mesh = self.topology.mesh
        if mesh.size == 1:
            return quant_matmul(x2d, qw, layer_index=li)
        kind = self.qkind[name]
        ws = KIND_SPEC_2D[kind]
        if li is not None:
            ws = P(None, *ws)       # stacked leaves carry a layer dim
        xs, os_ = _io_specs(kind)

        def fn(xl, ql, lil):
            y = quant_matmul(xl, ql, layer_index=(None if li is None
                                                  else lil))
            return jax.lax.psum(y, "tensor") if kind == "row" else y

        lia = jnp.zeros((), jnp.int32) if li is None else li
        return shard_map(fn, mesh=mesh, in_specs=(xs, ws, P()),
                         out_specs=os_, check_vma=False)(x2d, qw, lia)

    def gmm(self, x2d, w, srt, kind: str, block_m: int, li=None):
        """Grouped (per-expert) bf16 matmul: ``w`` is one layer's
        ``[n, K, N]`` or, with ``li``, the depth-stacked ``[L, n, K, N]``
        (the kernel picks the layer). On a mesh the expert width is the
        tensor-sharded dim, as for a dense FFN: ``kind`` "col" (gate/up)
        keeps the output sharded, "row" (down) sums the partial products.
        The kernel's weight block is ``gmm_plan``'s for the shapes the
        launch sees (a shard's, under a mesh); each distinct one is logged
        once, as a ``gmm:`` line, while the programs are traced, and kept
        in ``self.gmm_plans`` (the engine's)."""
        def launch(xl, wl, te, nt, lil):
            plan = gmm_plan(wl.shape[-2], wl.shape[-1], block_m, xl.dtype)
            seen = self.gmm_plans.setdefault(plan._replace(block_m=0), plan)
            if seen is plan:
                logger.info(f"gmm: {plan.describe()}")
            return grouped_matmul_layer(xl, wl, te, nt, block_m,
                                        layer_index=lil)

        mesh = self.topology.mesh
        ntp = self.topology.size("tensor")
        if mesh.size == 1:
            return launch(x2d, w, srt.tile_expert, srt.n_tiles, li)
        width = w.shape[-1] if kind == "col" else w.shape[-2]
        if ntp <= 1 or width % ntp:
            kind = "rep"
        lead = (None,) * (w.ndim - 3)
        ws = P(*lead, *KIND_SPEC_3D[kind])
        xs, os_ = _io_specs(kind)

        def fn(xl, wl, te, nt, lil):
            y = launch(xl, wl, te, nt, None if li is None else lil)
            return jax.lax.psum(y, "tensor") if kind == "row" else y

        lia = jnp.zeros((), jnp.int32) if li is None else li
        return shard_map(fn, mesh=mesh,
                         in_specs=(xs, ws, P(None), P(), P()),
                         out_specs=os_, check_vma=False)(
            x2d, w, srt.tile_expert, srt.n_tiles, lia)

    def qgmm(self, x2d, qw, tile_expert, name: str, bm: int, li=None):
        """Grouped (per-expert) quantized matmul dispatch — the MoE
        analogue of ``qmm``; the tile→expert map is replicated. ``bm`` is
        the sort's tile height (``moe_tile_rows``): the sort alignment and
        the kernel must use the SAME value for the tile→expert map to mean
        anything."""
        gmm = partial(quant_grouped_matmul, block_m=bm)
        mesh = self.topology.mesh
        if mesh.size == 1:
            return gmm(x2d, qw, tile_expert, layer_index=li)
        kind = self.qkind[name]
        ws = KIND_SPEC_3D[kind]
        if li is not None:
            ws = P(None, *ws)
        xs, os_ = _io_specs(kind)
        # grouped ring steps (tp_overlap): a row-kind expert GEMM's psum
        # becomes a ring accumulation over token-TILE chunks — each step's
        # partial grouped GEMM (chunk rows + matching tile→expert slice)
        # overlaps the traveling accumulator's ppermute; chunks stay
        # tile-aligned so the tile ownership invariant holds
        ntp = self.topology.size("tensor")
        ring = (kind == "row" and self.tp_ring_n and ntp > 1
                and x2d.shape[0] % (ntp * bm) == 0)
        if kind == "row" and self.tp_ring_n and not ring:
            overlap_counters.fallback()

        def fn(xl, ql, te, lil):
            liA = None if li is None else lil
            if not ring:
                y = gmm(xl, ql, te, layer_index=liA)
                return jax.lax.psum(y, "tensor") if kind == "row" else y

            def dot(rows, start):
                # the chunk's tile→expert slice rides the traced row
                # offset; chunks are whole tiles by the ring gate above
                tec = jax.lax.dynamic_slice(te, (start // bm,),
                                            (rows.shape[0] // bm,))
                return gmm(rows, ql, tec, layer_index=liA)

            # unidirectional: the bidirectional half-chunk split need not
            # stay tile-aligned
            y_c = _ring_rs_core(xl, dot, ntp, "tensor", x2d.dtype,
                                bidir=False)
            return jax.lax.all_gather(y_c, "tensor", axis=0, tiled=True)

        if ring:
            n_out = qw.shape[-1]
            overlap_counters.ring(
                steps=ntp - 1,
                bytes_permuted=(ntp - 1) * x2d.shape[0] * n_out * 4)

        lia = jnp.zeros((), jnp.int32) if li is None else li
        return shard_map(fn, mesh=mesh, in_specs=(xs, ws, P(None), P()),
                         out_specs=os_, check_vma=False)(
            x2d, qw, tile_expert, lia)

    def __call__(self, params, kv_pools, token_ids, positions, block_tables,
                 seq_lens, sample_idx, kv_stage=None, stage_fill=None,
                 stage_starts=None, tree_mask=None, live=None, block=None):
        """One ragged forward over READ-ONLY pools; returns ``((k_ys, v_ys),
        logits)`` and never writes a pool.

        ``kv_pools`` and ``block_tables`` are tuples, one entry a kind of
        layer in ``self.kinds``' order, and so are ``k_ys`` / ``v_ys``: this
        call's fresh K/V, ``[layers of the kind, S, KV, Ts, D]``, for the
        CALLER to merge inside its own program (:func:`merge_step`,
        :func:`merge_rows`). A record kind's entries: ``kv_pools[c]`` the
        records ``[layers, slots + 1, rows, width]``, ``block_tables[c]``
        each row's slot ``[S]``, ``k_ys[c]`` each row's NEW record
        ``[layers, S, rows, width]`` (``v_ys[c]`` None) for the caller to
        write for the rows that are live (:func:`merge_records`). A row
        whose first position is 0 starts from zeros, whatever its slot's
        record holds; a row's new record is that of its VALID tokens
        (``seq_lens`` less its first position: a chunk's padding does not
        count). In window mode ``kv_stage[0][c]`` is the running record
        ``[layers, S, rows, width]``, read instead of the records and
        returned advanced by this iteration's token.

        The pools hold only ALREADY-MERGED tokens (positions
        < stage_starts); the fresh K/V ride a small staged buffer that
        attention overlays on the paged context (why: ``engine_v2``'s
        module docstring).

        Default mode (``kv_stage`` None): the stage is this step's tokens,
        ``Ts`` = ``stage_rows(T)``; logits ``[S, V]`` at ``sample_idx``.
        Window mode (``kv_stage`` = (k_bufs, v_bufs), a tuple a kind of
        ``[L, S, KV, Ws, D]``, ``stage_fill`` = this iteration's row):
        writes row ``stage_fill`` and attends over rows < this iteration's
        length; the fresh K/V are the buffers with that row filled, which
        the window loop carries on and merges once, after the loop.
        Tree mode (``tree_mask`` [S, T, T] uint8): the speculative VERIFY
        forward — row t of a sequence is a candidate-tree node whose
        position is root + depth and whose visibility over the staged
        fresh KV is ancestors-only (siblings share a POSITION, which
        positional-causal masking cannot tell apart, hence the explicit
        mask; the paged pool below the root stays position-causal).
        ``live`` ([S, T] bool) says which tokens carry a request;
        the rest reach no routed expert (``routed_experts``). A step plan
        need not pass it: its row's valid tokens are ``seq_lens`` less the
        row's first position, so a chunk's padding and an empty row are not
        live. A window's rows and a tree's nodes look alike to the forward
        (None there: every token live): the program that calls it knows
        (the window's ``active``, the round's node counts).
        Logits are ``[S, T, V]`` — ALL nodes; the caller merges only the
        ACCEPTED path's staged rows, so rejected candidates never reach
        the pool. The Pallas kernel serves tree mode too (per-node stage
        positions + the ancestors mask ride into the kernel) whenever the
        registry's tree selection picks it (attn_registry.select_attention
        — geometry gates on top of the decode gate); the XLA gather
        formulation is the counted fallback. Tree mode never rings
        (all-position logits need the full residual stream).

        ``block`` (a :class:`DecodeBlock`; default mode only): the
        decode-ready rows ride this call as a SECOND segment, ``[B, 1]``
        beside the ``[S, T]`` one, and the layers are walked once over
        both. What is per TOKEN (norms, the q/k/v and output projections,
        the feed-forward, router + dispatch + grouped GEMM + combine, the
        head) runs on the two segments' tokens concatenated, so each weight
        is read once; what is per SEQUENCE (rope's positions aside: the
        staged K/V, the paged attention — the prefill form for one segment,
        the decode form for the other — a conv layer's record) runs a
        segment, with that segment's own positions, lengths, tables and
        work list. The return is then a pair of the form above, one a
        segment: ``(((k_ys, v_ys), logits), ((k_ys, v_ys), logits))`` —
        the block's as a ``[B, 1]`` step's, for the caller to merge by
        :func:`merge_step` with ``T`` 1.
        """
        m, cfg, kinds = self.mcfg, self.config, self.kinds
        S, T = token_ids.shape
        bs = cfg.block_size
        H, KV, D = m.num_heads, m.kv_heads, m.head_dim
        #: the pool's own head geometry (``kv_pack`` heads a page row)
        pk = self.kv_pack
        KVp, Dp = KV // pk, D * pk
        #: latent attention: ONE paged kind whose page is a row a token
        #: (no KV heads to pack, whatever ``kv_pack`` makes of the widths)
        latent = kinds[0].is_latent
        if latent:
            pk, KVp, Dp = 1, kinds[0].heads, kinds[0].lanes
        window_mode = kv_stage is not None
        #: the cache (an index into ``kinds``) of a layer kind
        cache_of = {name: [k.name for k in kinds].index(
            LATENT if latent else cache_kind(name)) for name in set(m.kinds)}
        period = m.kinds_period
        tree_mode = tree_mask is not None
        fused = block is not None
        if fused and (window_mode or tree_mode):
            raise ValueError("a decode block rides a step plan's forward: "
                             "not a window's, not a tree's")
        if window_mode:
            kbufs, vbufs = kv_stage
        # kernel-vs-gather comes from the attention registry's static
        # per-mode selection (attn_registry.py) — the ONLY dispatch
        # decision point, pinned by check_attn_registry in
        # bin/check_state_invariants.py
        sel = self.attn_tree_sel if tree_mode else self.attn_decode_sel

        def segment(positions, block_tables, seq_lens, sample_idx,
                    stage_starts=None):
            S, T = positions.shape
            q_starts = positions[:, 0]
            if stage_starts is None:
                stage_starts = q_starts
            Ts = kbufs[0].shape[3] if window_mode else stage_rows(T, bs)
            # the paged kernel's steps, the same for every layer: built
            # here, outside the layer loop (one list a kind of layer: a
            # window kind's is bounded by its window, over its own table)
            works = [()] * len(kinds)
            if sel.is_pallas:
                with device_scope("attn_core"):
                    works = [() if k.is_record else paged_work_list(
                        seq_lens, q_starts, stage_starts, block_size=bs,
                        max_pages=block_tables[c].shape[1], stage_rows=Ts,
                        window=k.window, ring_tokens=k.ring_tokens,
                        tree=tree_mode) for c, k in enumerate(kinds)]
            empty = jnp.zeros((S, KVp, Ts, Dp), cfg.dtype)
            return _Segment(
                S, T, Ts, positions, seq_lens, q_starts, stage_starts,
                block_tables,
                jnp.ones_like(seq_lens) if window_mode
                else seq_lens - q_starts, (q_starts == 0)[:, None, None],
                sample_idx, tuple(works),
                (empty, None if latent else empty))

        seg0 = segment(positions, block_tables, seq_lens, sample_idx,
                       stage_starts)
        if live is None and not (window_mode or tree_mode):
            live = jnp.arange(T)[None] < seg0.n_valid[:, None]
        segs = [seg0]
        if fused:
            segs.append(segment(block.positions, block.block_tables,
                                block.seq_lens,
                                jnp.zeros_like(block.seq_lens)))
            # the token stream of everything per token: ``[1, N]``, the
            # segments' tokens one after the other
            cat = lambda a, b: jnp.concatenate(
                [a.reshape(1, -1), b.reshape(1, -1)], axis=1)
            token_ids = cat(token_ids, block.token_ids)
            positions = cat(positions, block.positions)
            live = cat(live, segs[1].n_valid[:, None] > 0)
        ends = [0]
        for g in segs:
            ends.append(ends[-1] + g.S * g.T)
        #: tokens in this call, and the rows its stream is split by
        N = ends[-1]

        def to_segs(y):
            """A per-token array as its segments' ``[S, T, ...]``."""
            if not fused:
                return [y]
            return [y[0, a:b].reshape(g.S, g.T, *y.shape[2:])
                    for g, a, b in zip(segs, ends, ends[1:])]

        def from_segs(parts):
            if not fused:
                return parts[0]
            return jnp.concatenate(
                [y.reshape(1, -1, *y.shape[2:]) for y in parts], axis=1)

        # ring collective-matmul TP: static per program — the token-sharded
        # residual stream needs the row dim to divide the tensor axis
        # (exact-k packed prefill plans with odd row counts fall back to
        # the blocking einsum path, counted per compiled program), and the
        # auto mode additionally requires ring chunks of at least
        # TP_OVERLAP_MIN_ROWS rows (decode-sized programs would pay n×
        # weight re-reads for a tiny hidden collective; tp_overlap=True
        # overrides for measurement)
        rn = self.tp_ring_n
        if rn and (tree_mode or (N if fused else S) % rn or not (
                self.tp_ring_force
                or N // rn >= TP_OVERLAP_MIN_ROWS)):
            overlap_counters.fallback()
            rn = 0
        mesh_t = self.topology.mesh

        # Layer-scanned quantized weights do NOT ride the scan xs: a
        # scanned pallas operand forces a dynamic-slice COPY of the codes
        # every iteration (~0.57ms per decode step measured on v5e).
        # Instead the stacked QuantLinear/QuantGrouped leaves are stripped
        # out here, closed over whole, and the kernels select the layer
        # via a scalar-prefetched index (quant_matmul layer_index).
        qstack: dict[str, Any] = {}
        scanned_layers = params.get("layers_stacked")
        if scanned_layers is not None and cfg.quant_bits:
            def _strip(path, leaf):
                if isinstance(leaf, (QuantLinear, QuantGrouped)):
                    key = "/".join(p.key for p in path
                                   if isinstance(p, jax.tree_util.DictKey))
                    qstack[key] = leaf
                    return None
                return leaf

            is_q = lambda l: isinstance(l, (QuantLinear, QuantGrouped))
            scanned_layers = jax.tree_util.tree_map_with_path(
                _strip, scanned_layers, is_leaf=is_q)

        # The same for the bf16 routed-expert slabs of an all-MoE stack: they
        # are nearly all of a layer's bytes, and the grouped GEMM is a
        # Pallas call — a slice of the stack would be copied before it
        # reads a byte. Closed over whole; the kernel picks the layer.
        xstack: dict[str, Any] = {}
        if scanned_layers is not None and "moe" in scanned_layers:
            ml0 = scanned_layers["moe"]["moe_layer"]
            if all(w is not None and not isinstance(w, QuantGrouped)
                   for w in ml0["experts"].values()):
                xstack = dict(ml0["experts"])
                scanned_layers = {
                    **scanned_layers, "moe": {
                        **scanned_layers["moe"], "moe_layer": {
                            **ml0, "experts": {k: None for k in xstack}}}}

        def proj_in(h, w, nh, name, li=None):
            """[S,T,E] @ [E,(nh,D)] -> [S,T,nh,D]; QuantLinear weights run
            the in-tile-dequant Pallas GEMM (per-shard under TP); ``w``
            None means the weight lives in ``qstack`` (stacked quant: ``li``
            picks the layer; it is None wherever nothing is stacked)."""
            if w is None:
                w = qstack[f"attn/{name}"]
            if isinstance(w, QuantLinear):
                y = self.qmm(h.reshape(-1, h.shape[-1]), w, name, li=li)
                return y.reshape(*h.shape[:2], nh, -1).astype(cfg.dtype)
            return jnp.einsum("ste,ehd->sthd", h, w.astype(cfg.dtype))

        def proj_out(o, w, li=None):
            if w is None:
                w = qstack["attn/wo"]
            if isinstance(w, QuantLinear):
                y = self.qmm(o.reshape(N, -1), w, "wo", li=li)
                return y.reshape(*o.shape[:2], -1).astype(cfg.dtype)
            return jnp.einsum("sthd,hde->ste", o, w.astype(cfg.dtype))

        with device_scope("embed"):
            x = params["embed"].astype(cfg.dtype)[token_ids]       # [S,T,E]
            if m.position_embedding == "learned":
                x = x + params["pos_embed"].astype(cfg.dtype)[positions]
            if "ln_embed" in params:                               # bloom
                x = Norm(m).apply({"params": params["ln_embed"]}, x)
        if rn:
            # token-sharded residual stream (Megatron-SP layout): norms and
            # residual adds run 1/tp-sized per chip; the projections put
            # the gather/scatter back via overlapped ring primitives
            x = jax.lax.with_sharding_constraint(
                x, NamedSharding(mesh_t, P(None, "tensor", None) if fused
                                 else P("tensor", None, None)))

        def routed_experts(ml, h, li, h_router=None):
            """THE routed-expert layer of serving, quantised or not: router
            -> dropless top-k (every token reaches its k experts; generation
            must not drop a routed token — the FastGen v2 MoE contract) ->
            sort, and gather the rows into a tile-aligned buffer (no
            scatter: a one-hot matmul at a step's few rows, a row gather
            at many) -> grouped GEMMs -> gather back and gate-weighted
            sum. A token that carries no request (``live``) reaches no
            expert and reads zero. Only the GEMM differs: the bf16 Pallas
            grouped matmul, or
            its in-tile-dequant twin over QuantGrouped slabs (reference
            cutlass_ops/moe_gemm with mixed_gemm). The
            dispatch/combine algebra is shared with the training dropless
            path (moe/layer.py ``dropless_dispatch_combine``). NB this
            diverges from the v1/training forward exactly when eval
            capacity would bind — there v1 drops overflow tokens, v2
            doesn't (tests/test_moe.py::
            test_capacity_divergence_v1_drops_v2_routes_all)."""
            mo = m.moe
            Tt, E = N, h.shape[-1]
            flat = h.reshape(Tt, E).astype(cfg.dtype)
            # what the router reads, where that is not what the experts
            # read (``MoEConfig.router_input``)
            routed = flat if h_router is None \
                else h_router.reshape(Tt, E).astype(cfg.dtype)
            with device_scope("moe_router"):
                logits = jnp.einsum("te,en->tn", routed.astype(jnp.float32),
                                    ml["gate"]["wg"].astype(jnp.float32))
                gate = topk_dropless_gating(
                    logits[None], mo.top_k,
                    normalize_gates=mo.normalize_gates,
                    score=mo.router_score, bias=ml["gate"].get("bias"),
                    scale=mo.routed_scaling_factor)

            def exw(k):      # stripped (stacked) slabs are closed over
                w = ml["experts"].get(k)
                if w is not None:
                    return w, None
                if k in xstack:
                    return xstack[k], li
                return qstack[f"moe/moe_layer/experts/{k}"], li

            quantised = isinstance(exw("w_up")[0], QuantGrouped)
            bm = moe_tile_rows(Tt, mo.top_k, mo.num_experts, quantised)

            def gemm(buf, srt):
                def mm(x, k, kind):
                    w, wli = exw(k)
                    if quantised:
                        return self.qgmm(x, w, srt.tile_expert, f"moe_{k}",
                                         bm, li=wli)
                    return self.gmm(x, w if wli is not None
                                    else w.astype(cfg.dtype), srt, kind, bm,
                                    li=wli)

                if m.activation in GLU_ACTS:
                    z = GLU_ACTS[m.activation](mm(buf, "w_gate", "col")) \
                        * mm(buf, "w_up", "col")
                else:
                    z = _ACTS[m.activation](mm(buf, "w_up", "col"))
                return mm(z.astype(cfg.dtype), "w_down", "row")

            out = dropless_dispatch_combine(
                flat, gate.gates[0], gate.experts[0], mo.num_experts,
                mo.top_k, bm, gemm,
                live=None if live is None else live.reshape(Tt))
            return out.reshape(h.shape).astype(cfg.dtype)

        def ffn(p, h, use_moe: bool, li=None, h_router=None):
            if use_moe and rn:
                # routing needs the full token set (gate + expert sort over
                # all tokens): gather the token-sharded stream once and run
                # the MoE path replicated; the expert GEMMs themselves ring
                # via qgmm's grouped ring steps when the contraction is
                # tensor-sharded
                overlap_counters.fallback()
                h = jax.lax.with_sharding_constraint(
                    h, NamedSharding(mesh_t, P(None, None, None)))
                if h_router is not None:
                    h_router = jax.lax.with_sharding_constraint(
                        h_router, NamedSharding(mesh_t, P(None, None, None)))
            if use_moe:
                out = routed_experts(p["moe"]["moe_layer"], h, li, h_router)
                se = m.moe.shared_expert_intermediate
                if se:   # the always-on shared expert: behind a sigmoid
                    #      gate (qwen2-moe) or as it is (deepseek-v3)
                    with device_scope("ffn"):
                        shared_cfg = dataclasses.replace(
                            m, intermediate_size=se)
                        shared = DenseFFN(shared_cfg).apply(
                            {"params": p["moe"]["shared_expert"]}, h)
                        if not m.moe.shared_expert_gated:
                            return out + shared
                        g = jax.nn.sigmoid(jnp.einsum(
                            "ste,eo->sto", h.astype(jnp.float32),
                            p["moe"]["shared_gate"].astype(jnp.float32)))
                        out = out + g.astype(out.dtype) * shared
                return out
            f = p["ffn"]

            def fw(k):   # stripped (stacked quantised) ones are closed over
                w = f.get(k)
                return qstack[f"ffn/{k}"] \
                    if w is None and f"ffn/{k}" in qstack else w

            if rn:
                # ring FFN pair: gate/up share ONE all-gather⊗matmul ring,
                # down is matmul⊗reduce-scatter back into the token-sharded
                # stream. Mirrors DenseFFN.__call__ / the quant branch below
                # — keep activations/biases in sync across the three.
                def fwr(k):
                    wv = fw(k)
                    return wv if isinstance(wv, QuantLinear) \
                        else wv.astype(cfg.dtype)

                wu = fwr("w_up")
                # dense layers of a mixed MoE stack may carry their own
                # intermediate size — ring only when it divides the axis
                if isinstance(wu, QuantLinear) or wu.shape[1] % rn == 0:
                    h2 = h.reshape(N, -1)
                    if m.activation in GLU_ACTS:
                        g2, u2 = allgather_matmul(
                            h2, (fwr("w_gate"), wu), mesh_t, layer_index=li)
                        z = GLU_ACTS[m.activation](g2) * u2
                    else:
                        u2 = allgather_matmul(h2, wu, mesh_t, layer_index=li)
                        z = _ACTS[m.activation](
                            u2 + f["b_up"].astype(u2.dtype))
                    y2 = matmul_reduce_scatter(
                        z.astype(cfg.dtype), fwr("w_down"), mesh_t,
                        layer_index=li)
                    out = y2.reshape(*h.shape[:2], -1).astype(cfg.dtype)
                    if m.activation not in GLU_ACTS:
                        out = out + f["b_down"].astype(cfg.dtype)
                    return out
                overlap_counters.fallback()
            if isinstance(fw("w_up"), QuantLinear):
                # NB: mirrors DenseFFN.__call__ (models/transformer.py) with
                # the matmuls swapped for quant_matmul — keep the two in
                # sync when touching activations/biases
                h2d = h.reshape(-1, h.shape[-1])
                if m.activation in GLU_ACTS:
                    z = GLU_ACTS[m.activation](self.qmm(
                        h2d, fw("w_gate"), "w_gate", li=li)) \
                        * self.qmm(h2d, fw("w_up"), "w_up", li=li)
                    out = self.qmm(z.astype(cfg.dtype), fw("w_down"),
                                    "w_down", li=li)
                else:
                    z = self.qmm(h2d, fw("w_up"), "w_up", li=li) \
                        + f["b_up"].astype(cfg.dtype)
                    act = _ACTS[m.activation]
                    out = self.qmm(act(z).astype(cfg.dtype),
                                    fw("w_down"), "w_down", li=li) \
                        + f["b_down"].astype(cfg.dtype)
                return out.reshape(h.shape).astype(cfg.dtype)
            return DenseFFN(dense_ffn_config(m)).apply({"params": f}, h)

        def attention(p, qli, h, stage_l, kind, c, lk):
            """QKV → write into the STAGED buffer → ragged attention over
            the read-only pool pages + the stage, a segment. Returns (o,
            stage_l'). ``kind``: the layer's kind (static); ``c`` its cache
            among ``kinds``; ``lk`` the layer's index inside that cache's
            pool."""
            a = p["attn"]
            #: a latent segment's up-projections, where its chunk takes
            #: the EXPANDED form (``latent_expands``); None: absorbed
            ups = [None] * len(segs)
            if latent:
                q, k, v = latent_qkv(a, qli, h)
                ups = [(a["w_uk"].astype(cfg.dtype),
                        a["w_uv"].astype(cfg.dtype))
                       if latent_expands(g.T) else None for g in segs]
            else:
                with device_scope("attn_qkv"):
                    q, k, v = qkv(a, qli, h, kind)
                    if pk > 1:
                        q, k, v = pack_heads(q, k, v, pk)
            outs, stages = [], []
            for g, q_g, k_g, v_g, stage_g, up in zip(
                    segs, to_segs(q), to_segs(k),
                    [None] * len(segs) if latent else to_segs(v), stage_l,
                    ups):
                with device_scope("kv_stage"):
                    stage_g = stage(g, k_g, v_g, stage_g)
                if latent and up is None:
                    with device_scope("latent_absorb"):
                        q_g = latent_absorb_query(a, q_g)
                # window and global layers told apart, inside
                # ``attn_core``, where a model has both (a model of one
                # kind keeps the scope table it always had)
                sub = nullcontext()
                if len(kinds) > 1:
                    sub = device_scope("attn_window") if kinds[c].window \
                        else device_scope("attn_full")
                with device_scope("attn_core"), sub:
                    o_g = core(g, c, lk, q_g, stage_g, up)
                if latent and up is None:
                    # the weighted sum of latents back to a head's value:
                    # ``o_h = W_uv,h o_lat_h``
                    with device_scope("latent_absorb"):
                        o_g = jnp.einsum("sthr,rhd->sthd", o_g,
                                         a["w_uv"].astype(cfg.dtype))
                outs.append(o_g)
                stages.append(stage_g)
            with device_scope("attn_out"):
                o = from_segs(outs)
                if pk > 1:
                    o = unpack_heads(o, KV, pk)
                return out_proj(a, qli, o), tuple(stages)

        def latent_expands(T):
            """Whether a latent segment of ``T`` tokens a row takes the
            kernel's EXPANDED form (:func:`paged_latent_prefill`: the
            prefill chunks) or the absorbed one (the decode programs, the
            rows that ride a prefill step, a chunk too short to pay for a
            page's up-projection) — by its static ``T`` against the
            break-even the model's own widths give."""
            return sel.is_pallas and latent_prefill_plan(
                T, H, m.kv_lora_rank, m.qk_nope_head_dim,
                m.qk_rope_head_dim, m.v_head_dim, Dp, bs,
                cfg.dtype) is not None

        def latent_qkv(a, qli, h):
            """Latent attention's operands: the query AS PROJECTED ``[S,
            T, H, dn + dr]``, rope applied to its last ``dr``; the row ``[c
            | k_r]`` of this call's tokens ``[S, T, 1, lanes]`` (what the
            pool keeps, ONCE: the absorbed kernel takes its first
            ``kv_lora_rank`` lanes as the value, the expanded one
            up-projects them a head); and no V."""
            dn = m.qk_nope_head_dim
            with device_scope("attn_qkv"):
                q = proj_in(h, a["wq"], H, "wq", li=qli)
            with device_scope("latent_absorb"):
                c, k_r = latent_row(m, h, a["w_dkv"], a["kv_norm"])
                q_r, k_r = apply_rope(q[..., dn:], k_r[:, :, None, :],
                                      positions, m.rope_theta)
                q = jnp.concatenate([q[..., :dn], q_r], axis=-1)
                row = jnp.pad(jnp.concatenate([c[:, :, None, :], k_r],
                                              axis=-1),
                              [(0, 0)] * 3 + [(0, Dp - m.latent_width)])
            return q, row.astype(cfg.dtype), None

        def latent_absorb_query(a, q):
            """Latent attention ABSORBED: what attends is the latent row
            itself. The query ``[S, T, H, lanes]`` — ``W_uk,h^T q_nope_h``
            (the up-projection of the keys folded into the query) beside
            ``q_rope_h``, zeros in the row's padding."""
            dn = m.qk_nope_head_dim
            q_abs = jnp.einsum("sthd,rhd->sthr", q[..., :dn],
                               a["w_uk"].astype(cfg.dtype))
            return jnp.pad(jnp.concatenate([q_abs, q[..., dn:]], axis=-1),
                           [(0, 0)] * 3 + [(0, Dp - m.latent_width)])

        def qkv(a, qli, h, kind):
            if rn:
                # ONE bidirectional ring gathers the token-sharded hidden
                # while all three projections consume each arriving shard
                # (fused QKV collective-matmul); quantized weights run
                # quant_matmul per ring step, never a whole-shard dequant
                def aw(name):
                    wv = a[name]
                    if wv is None:
                        return qstack[f"attn/{name}"]
                    if isinstance(wv, QuantLinear):
                        return wv
                    w2 = wv.astype(cfg.dtype)
                    return w2.reshape(w2.shape[0], -1)
                q2, k2, v2 = allgather_matmul(
                    h.reshape(N, -1), (aw("wq"), aw("wk"), aw("wv")),
                    mesh_t, layer_index=qli)
                q = q2.reshape(*h.shape[:2], H, -1).astype(cfg.dtype)
                k = k2.reshape(*h.shape[:2], KV, -1).astype(cfg.dtype)
                v = v2.reshape(*h.shape[:2], KV, -1).astype(cfg.dtype)
            else:
                q = proj_in(h, a["wq"], H, "wq", li=qli)
                k = proj_in(h, a["wk"], KV, "wk", li=qli)
                v = proj_in(h, a["wv"], KV, "wv", li=qli)
            if m.qkv_bias:
                q = q + a["bq"].astype(cfg.dtype)
                k = k + a["bk"].astype(cfg.dtype)
                v = v + a["bv"].astype(cfg.dtype)
            if m.qk_norm:
                q = qk_norm(m, q, a["q_norm"])
                k = qk_norm(m, k, a["k_norm"])
            if kind_ropes(m, kind):
                q, k = apply_rope(q, k, positions, m.rope_theta, m.rotary_pct)
            return q, k, v

        def stage(g, k, v, stage_l):
            """This step's K/V of segment ``g`` into its staged buffers."""
            k_t = k.transpose(0, 2, 1, 3).astype(cfg.dtype)  # [S,KV,T,D]
            v_t = None if v is None \
                else v.transpose(0, 2, 1, 3).astype(cfg.dtype)
            if window_mode:
                k_st, v_st = stage_l
                k_st = jax.lax.dynamic_update_slice(
                    k_st, k_t, (0, 0, stage_fill, 0))
                if v_t is not None:
                    v_st = jax.lax.dynamic_update_slice(
                        v_st, v_t, (0, 0, stage_fill, 0))
            else:
                pad = [(0, 0), (0, 0), (0, g.Ts - g.T), (0, 0)]
                k_st = jnp.pad(k_t, pad)
                v_st = None if v_t is None else jnp.pad(v_t, pad)
            return k_st, v_st

        def core(g, c, lk, q, stage_l, up=None):
            """Ragged attention of segment ``g`` over the pool pages + the
            stage: the Pallas kernel, or the XLA gather fallback — over
            cache ``c``'s pool and block table, at layer ``lk`` of that
            pool. ``up`` (``w_uk``, ``w_uv``): a latent chunk in the
            EXPANDED form — ``q`` as projected in, a head's value out."""
            k_st, v_st = stage_l
            #: the score scale: the model's head width's — a packed or
            #: latent row's lanes are not it
            qk_scale = (m.qk_nope_head_dim + m.qk_rope_head_dim) ** -0.5 \
                if latent else D ** -0.5
            S, T, Ts, positions = g.S, g.T, g.Ts, g.positions
            seq_lens, q_starts = g.seq_lens, g.q_starts
            stage_starts = g.stage_starts
            # Sliding windows mask on every path; a window kind also serves
            # from a ROLLING block table (ring_tokens > 0) so out-of-window
            # KV blocks are reused instead of pinned.
            win = kinds[c].window
            ring = kinds[c].ring_tokens
            ro_pool, table = kv_pools[c], g.block_tables[c]
            attn_work = g.attn_works[c]
            ctx = table.shape[1] * bs
            li_dev = jnp.asarray(lk, jnp.int32)
            if up is not None:
                return paged_latent_prefill(
                    q, *up, ro_pool, k_st, table, seq_lens, q_starts,
                    stage_starts, block_size=bs, layer_index=li_dev,
                    scale=qk_scale, work=attn_work)
            if sel.is_pallas:
                # tree-verify stages ride two extra replicated operands:
                # per-node absolute positions (root+depth) and the
                # ancestors-only mask over the stage columns
                t_ops = (positions, tree_mask) if tree_mode else ()
                t_specs = (P(None, None), P(None, None, None)) \
                    if tree_mode else ()

                def _kernel(qq, pp, ks, vs, bt, sl, qs, ss, lr, wl, nw, *t):
                    return paged_ragged_attention(
                        qq, pp, ks, vs, bt, sl, qs, ss,
                        block_size=bs, layer_index=lr, window=win,
                        ring_tokens=ring, work=(wl, nw),
                        scale=qk_scale if latent or pk > 1 else None,
                        value_lanes=m.kv_lora_rank if latent else None,
                        tree_positions=t[0] if t else None,
                        tree_mask=t[1] if t else None)

                mesh = self.topology.mesh
                if mesh.size > 1:
                    # per-shard over the tensor axis: q on query heads, the
                    # pool/stage on kv heads (the weight TP slicing)
                    o = shard_map(
                        _kernel,
                        mesh=mesh,
                        in_specs=(P(None, None, "tensor", None),
                                  P(None, None, "tensor", None, None, None),
                                  P(None, "tensor", None, None),
                                  P(None, "tensor", None, None),
                                  P(None, None), P(None), P(None), P(None),
                                  P(), P(None), P(), *t_specs),
                        out_specs=P(None, None, "tensor", None),
                        check_vma=False,
                    )(q, ro_pool, k_st, v_st, table, seq_lens,
                      q_starts, stage_starts, li_dev, *attn_work, *t_ops)
                else:
                    o = _kernel(q, ro_pool, k_st, v_st, table, seq_lens,
                                q_starts, stage_starts, li_dev, *attn_work,
                                *t_ops)
            else:
                # fallback (alibi / odd geometries): gather each slot's
                # pool pages (valid < stage_starts) and append the stage.
                blocks = jnp.repeat(table, bs, axis=1)           # [S,ctx]
                offs = jnp.tile(jnp.arange(bs), table.shape[1])
                K = ro_pool[li_dev, 0, :, blocks, offs[None, :]]  # [S,ctx,KV,D]
                K = jnp.concatenate([K.astype(cfg.dtype),
                                     k_st.transpose(0, 2, 1, 3)], axis=1)
                if latent:
                    # the same form as the kernel's: the value is the
                    # first lanes of the one row every head reads
                    V = K[..., :m.kv_lora_rank]
                else:
                    V = ro_pool[li_dev, 1, :, blocks, offs[None, :]]
                    V = jnp.concatenate([V.astype(cfg.dtype),
                                         v_st.transpose(0, 2, 1, 3)], axis=1)
                if KVp != H:
                    K = jnp.repeat(K, H // KVp, axis=2)
                    V = jnp.repeat(V, H // KVp, axis=2)

                scores = jnp.einsum("sthd,schd->shtc", q, K).astype(jnp.float32)
                scores = scores * qk_scale if latent \
                    else scores / (D ** 0.5)
                sstart = stage_starts[:, None]
                if ring:
                    # rolling buffer: recover each gathered offset's
                    # absolute position (same algebra as the kernel);
                    # pool-latest is the token BEFORE the stage
                    nwin = ring // bs
                    b_latest = jnp.maximum(sstart - 1, 0) // bs
                    jidx = (jnp.arange(ctx) // bs)[None, :]
                    b_j = b_latest - (b_latest - jidx) % nwin
                    raw = b_j * bs + (jnp.arange(ctx) % bs)[None, :]
                    cpos_pool = jnp.where(raw < sstart, raw,
                                          raw - ring)           # [S,ctx]
                    valid_pool = cpos_pool >= 0
                else:
                    # pages are position-ordered: context index j IS
                    # absolute position j, valid while before the stage
                    cpos_pool = jnp.broadcast_to(jnp.arange(ctx)[None, :],
                                                 (S, ctx))
                    valid_pool = cpos_pool < sstart
                if tree_mode:
                    # stage entries are tree nodes: their ABSOLUTE
                    # positions come from the positions array (root +
                    # depth; siblings share one), not a contiguous ramp —
                    # alibi's relative bias below reads these; validity/
                    # causality over the stage is the ancestors-only mask
                    cpos_st = jnp.pad(positions, ((0, 0), (0, Ts - T)))
                else:
                    cpos_st = sstart + jnp.arange(Ts)[None, :]   # [S,Ts]
                cpos = jnp.concatenate([cpos_pool, cpos_st], axis=1)
                valid = jnp.concatenate(
                    [valid_pool, cpos_st < seq_lens[:, None]], axis=1)
                valid = valid[:, None, None, :]
                if m.position_embedding == "alibi":
                    slopes = alibi_slopes(H)                       # [H]
                    rel = (cpos.astype(jnp.float32)[:, None, None, :]
                           - positions[:, None, :, None].astype(jnp.float32))
                    scores = scores + slopes[None, :, None, None] * rel
                causal = cpos[:, None, :] <= positions[:, :, None]
                if win:
                    causal &= cpos[:, None, :] > positions[:, :, None] - win
                mask = valid & causal[:, None, :, :]
                if tree_mode:
                    # stage columns: ancestors-only visibility replaces
                    # the positional mask entirely (padding nodes carry
                    # all-zero mask rows except their self-bit, set by
                    # the caller); pool columns keep the causal mask —
                    # every node descends from the committed context
                    tm = jnp.pad(tree_mask.astype(bool),
                                 ((0, 0), (0, 0), (0, Ts - T)))
                    mask = jnp.concatenate(
                        [mask[..., :ctx], tm[:, None, :, :]], axis=-1)
                scores = jnp.where(mask, scores, jnp.finfo(jnp.float32).min)
                w = jax.nn.softmax(scores, axis=-1).astype(V.dtype)
                o = jnp.einsum("shtc,schd->sthd", w, V)
            return o

        def out_proj(a, qli, o):
            if rn:
                # row-parallel out-proj: partial outputs ring-accumulate
                # toward their owner's token chunk instead of blocking on
                # the GSPMD all-reduce; output rejoins the token-sharded
                # residual stream directly
                wo = a["wo"] if a["wo"] is not None else qstack["attn/wo"]
                if not isinstance(wo, QuantLinear):
                    wo = wo.astype(cfg.dtype).reshape(-1, wo.shape[-1])
                o2 = matmul_reduce_scatter(
                    o.reshape(N, -1), wo, mesh_t, layer_index=qli)
                o = o2.reshape(*o.shape[:2], -1).astype(cfg.dtype)
            else:
                o = proj_out(o, a["wo"], li=qli)
            if m.attn_out_bias:
                o = o + a["bo"].astype(cfg.dtype)
            return o

        def norm(p_ln, x):
            with device_scope("norm"):
                return Norm(m).apply({"params": p_ln}, x)

        def layer(x, p, li, use_moe, stage_l, kind, lk, stacked=True):
            """``stage_l``: a segment, the layer's staged K/V — or, for a
            "conv" layer, the record each row starts from (zeros for a row
            that starts its sequence: no past); returned advanced.
            ``stacked``: ``p`` is a slice of the depth-stacked tree (``li``
            then picks the layer of a weight closed over whole), not a
            layer's own tree."""
            qli = li if qstack and stacked else None
            h_attn = norm(p["ln_attn"], x)
            if kind == CONV:
                with device_scope("conv_mix"):
                    mixed = [conv_mix(
                        m, p["conv"], h_g,
                        jnp.where(g.fresh_row, 0, rec).astype(cfg.dtype),
                        g.n_valid)
                        for g, h_g, rec in zip(segs, to_segs(h_attn), stage_l)]
                    o = from_segs([o_g for o_g, _ in mixed])
                    stage_l = tuple(rec for _, rec in mixed)
            else:
                o, stage_l = attention(p, qli, h_attn, stage_l, kind,
                                       cache_of[kind], lk)
            if not m.parallel_block:
                x = x + o
            h_ffn = h_attn if m.parallel_block \
                and m.parallel_block_norms == 1 else norm(p["ln_ffn"], x)
            if use_moe:     # its own scopes: router, dispatch, experts...
                f = ffn(p, h_ffn, True, li,
                        h_attn if m.moe.router_input == "attn" else None)
            else:
                with device_scope("ffn"):
                    f = ffn(p, h_ffn, False, qli)
            return (x + o + f if m.parallel_block else x + f), stage_l

        empty_stages = tuple(g.empty_stage for g in segs)
        P_ = len(period)

        def unrolled(x, layer_ids):
            """Layers ``layer_ids`` one after the other, each from its own
            tree ``layer_<i>``. Returns ``x`` and, a segment and kind, the
            layers' staged K/V (or new records) as lists."""
            lists = [[([], []) for _ in kinds] for _ in segs]
            for i in layer_ids:
                use_moe = is_moe_layer(m, i)
                c = cache_of[m.layer_kind(i)]
                lk = kinds[c].layers.index(i)
                if kinds[c].is_record:
                    # the record each row starts from: the running one of a
                    # window, else its slot's (``block_tables[c]``: slots)
                    recs = (kbufs[c][lk],) if window_mode else tuple(
                        kv_pools[c][lk][g.block_tables[c]] for g in segs)
                    x, recs = layer(x, params[f"layer_{i}"], i, use_moe,
                                    recs, CONV, lk, stacked=False)
                    for of_seg, rec in zip(lists, recs):
                        of_seg[c][0].append(rec)
                    continue
                stage_l = ((kbufs[c][lk], None if latent
                            else vbufs[c][lk]),) if window_mode \
                    else empty_stages
                x, stage_l = layer(x, params[f"layer_{i}"], i, use_moe,
                                   stage_l, m.layer_kind(i), lk,
                                   stacked=False)
                for of_seg, (k_st, v_st) in zip(lists, stage_l):
                    of_seg[c][0].append(k_st)
                    if v_st is not None:
                        of_seg[c][1].append(v_st)
            return x, lists

        if "layers_stacked" in params:
            if CONV in cache_of:
                raise ValueError("a model with 'conv' layers is walked "
                                 "unrolled, not stacked")
            # the LEADING layers that are not in the stack (a model of
            # leading dense layers, then identical expert layers: none for
            # a uniform model) are walked from their own trees first
            lead = m.num_layers - jax.tree.leaves(
                params["layers_stacked"])[0].shape[0]
            x, lead_lists = unrolled(x, range(lead))
            # a scan over PERIODS (of one layer, for a model of one kind):
            # one traced body a place, whatever the depth; the pools never
            # enter the carry — only the small staged KV does. Place j of a
            # period fixes the layer's kind, its cache c and its rank r
            # among that cache's layers of the period — stack layer
            # pi * P + j is layer lead + pi * n_c + r of pool c
            place = []
            for j, kind in enumerate(period):
                c = cache_of[kind]
                place.append((c, sum(cache_of[kk] == c
                                     for kk in period[:j])))
            n_in = [sum(cc == c for cc, _ in place)
                    for c in range(len(kinds))]
            xs = None
            if window_mode:
                split = lambda b, c: b[lead:].reshape(-1, n_in[c],
                                                      *b.shape[1:])
                xs = [(split(kbufs[c], c)[:, r],
                       None if latent else split(vbufs[c], c)[:, r])
                      for c, r in place]

            def body(xc, p, li, stage_l, j):
                c, r = place[j]
                return layer(xc, p, li, is_moe_layer(m, lead),
                             (stage_l,) if window_mode else empty_stages,
                             period[j], lead + (li // P_) * n_in[c] + r)

            x, ys = scan_layers(scanned_layers, x, body, P_, xs)
            # ``fresh[gi]``: segment gi's (k_ys, v_ys), a kind each: the
            # leading layers' (if any), then the stack's
            fresh = []
            for gi in range(len(segs)):
                k_ys, v_ys = [], []
                for c in range(len(kinds)):
                    for out, half in ((k_ys, 0), (v_ys, 1)):
                        if latent and half:      # one row a token: no V
                            out.append(None)
                            continue
                        y = jnp.stack([ys[j][gi][half] for j in range(P_)
                                       if place[j][0] == c], axis=1)
                        y = y.reshape(-1, *y.shape[2:])
                        if lead:
                            y = jnp.concatenate(
                                [jnp.stack(lead_lists[gi][c][half]), y])
                        out.append(y)
                fresh.append((tuple(k_ys), tuple(v_ys)))
        else:
            x, lists = unrolled(x, range(m.num_layers))
            fresh = [(tuple(jnp.stack(ks) for ks, _ in of_seg),
                      tuple(jnp.stack(vs) if vs else None
                            for _, vs in of_seg)) for of_seg in lists]

        def head(x):
            x = Norm(m).apply({"params": params["ln_final"]}, x)
            if tree_mode:
                # the verify step samples at EVERY tree node: all-position
                # logits ([S*T, E] rows through the same projection paths)
                last = x.reshape(S * T, -1)
            else:
                # each segment's sampled rows, one after the other: [rows, E]
                last = [jnp.take_along_axis(
                    x_g, g.sample_idx[:, None, None].astype(jnp.int32),
                    axis=1)[:, 0] for g, x_g in zip(segs, to_segs(x))]
                last = jnp.concatenate(last) if fused else last[0]
            if rn:
                # leave the token-sharded stream: the logits projection reads
                # S rows total — replicating them is noise next to the weight
                last = jax.lax.with_sharding_constraint(
                    last, NamedSharding(mesh_t, P(None, None)))
            if m.tie_embeddings:
                if "logits_q" in params:
                    # tied models keep the embedding gather exact but project
                    # logits through an int8 COPY of the table — the decode
                    # step's single largest weight read (103MB bf16 on
                    # gpt2-350m, ~0.14ms/token). At M<=8 rows quant_matmul's
                    # small-M dispatch routes this through XLA's fused
                    # dequant-dot (convert+mul folded into the operand read:
                    # measured 122us vs 138 bf16 vs 271 for the Pallas tile
                    # kernel, whose whole-table dequant is VPU-bound at few
                    # rows); int4 keeps the Pallas kernel (XLA can't fuse the
                    # nibble unpack). Both single- and multi-device go
                    # through qmm — per-shard, the same dispatch applies.
                    logits = self.qmm(last, params["logits_q"], "logits")
                else:
                    logits = jnp.einsum("se,ve->sv", last,
                                        params["embed"].astype(cfg.dtype))
            elif isinstance(params["unembed"], QuantLinear):
                logits = self.qmm(last, params["unembed"], "unembed")
            else:
                logits = jnp.einsum("se,ev->sv", last, params["unembed"].astype(cfg.dtype))
            if m.unembed_bias:
                logits = logits + params["unembed_b"].astype(cfg.dtype)
            return logits

        with device_scope("head"):
            logits = head(x)
        # NO pool write here: the caller merges, once a program
        if fused:
            return (fresh[0], logits[:S]), (fresh[1], logits[S:])
        return fresh[0], (logits.reshape(S, T, -1) if tree_mode else logits)


def merge_records(records, write_slots, new):
    """THE one write of a program's records (a record kind: ``records``
    ``[layers, slots + 1, rows, width]``): row ``s``'s new record
    (``new`` ``[layers, S, rows, width]``, :class:`RaggedForward`'s
    ``k_ys`` entry of the kind) lands at ``write_slots[s]`` — the row's
    slot where the row is live in this step, the last (trash) record where
    it is not: a sequence half-way through its prompt sits in a slot that
    a decode program also spans, and must find its record as its last
    chunk left it."""
    with device_scope("state_commit"):
        return records.at[:, write_slots].set(new.astype(records.dtype))


def merge_step(kv_pools, slot_maps, k_ys, v_ys, T: int):
    """THE one pool write of a step program: every kind's fresh K/V of a
    ``[S, T]`` plan (:class:`RaggedForward`'s ``k_ys`` / ``v_ys``) lands at
    its (block, offset) slot of that kind's pool (``slot_maps``: a tuple a
    kind of ``[S, T]`` flat slots); padded tokens carry trash-block slots
    (block 0) by construction. One token a row is a DUS a row
    (:func:`merge_rows`), chunks of whole pages a DUS a page
    (:func:`merge_pages`): PURE writes of a static slice at a dynamic
    block, which take the pool in the row-major layout it is pinned to
    (``engine._pool_formats``) and write it in place — no compiled step
    program holds a copy of a pool's size
    (``profiling.trace.pool_sized_copies``). Page-misaligned chunks, which
    no serving configuration plans, keep the scatter
    (:func:`merge_stage`). A record kind (its ``v_ys`` entry is None, its
    ``slot_maps`` entry each row's write slot ``[S]``) is written by
    :func:`merge_records`."""
    merged = []
    for pool, slots, kc, vc in zip(kv_pools, slot_maps, k_ys, v_ys):
        if pool.ndim == 4:
            merged.append(merge_records(pool, slots, kc))
            continue
        L, _, KV, _, bs, D = pool.shape
        if T == 1:
            pool = merge_rows(pool, slots[:, 0], kc[:, :, :, 0, :],
                              None if vc is None else vc[:, :, :, 0, :])
        elif T % bs == 0:
            # (a ring too: the slot a whole page lands in held a page
            # more than a window + a step older, dead to every query
            # from this chunk on, and the rows past the chunk's real
            # tokens read as that older wrap: masked by the window)
            pool = merge_pages(pool, slots, kc, vc, T)
        else:
            with device_scope("kv_commit"):
                ks = (kc[:, :, :, :T, :].transpose(0, 1, 3, 2, 4)
                      .reshape(L, -1, KV, D))
                vs = None if vc is None else (
                    vc[:, :, :, :T, :].transpose(0, 1, 3, 2, 4)
                    .reshape(L, -1, KV, D))
            pool = merge_stage(pool, slots.reshape(-1), ks, vs)
        merged.append(pool)
    return tuple(merged)


def merge_stage(kv_pool, flat_slots, ks, vs):
    """The scatter form of the pool write: staged K/V rows (``[L, N, KV,
    D]``, row n ↔ flat pool slot ``flat_slots[n]``) into the block-granular
    ``[L, 2, KV, nb, bs, D]`` pool.

    NB on layout: an XLA scatter layout-assigns the pool to a
    scatter-friendly permutation while the pallas reads need row-major,
    which costs a copy of the whole pool out and one back in every
    compiled step (a flat [rows, D] scatter is WORSE — column-major
    preference; layout_constraint pins don't override scatter's mandatory
    layout; at SmallThinker's cell the scatter held a copy of the window
    layers' whole pool as a temporary of every prefill step). Callers
    therefore prefer the layout-NEUTRAL dynamic-update-slice merges
    (``merge_rows``, ``merge_pages``) and fall back here only for what
    those can't express: a chunk that is no whole number of pages."""
    with device_scope("kv_commit"):
        bs = kv_pool.shape[4]
        blk, off = flat_slots // bs, flat_slots % bs
        liL = jnp.arange(kv_pool.shape[0])
        kv_pool = kv_pool.at[liL[:, None], 0, :, blk[None, :],
                             off[None, :]].set(ks.astype(kv_pool.dtype))
        if vs is not None:
            kv_pool = kv_pool.at[liL[:, None], 1, :, blk[None, :],
                                 off[None, :]].set(vs.astype(kv_pool.dtype))
        return kv_pool


def merge_rows(kv_pool, flat_slots, k_rows, v_rows):
    """Token-granular pool merge: one dynamic-update-slice per row
    (``k_rows/v_rows`` [L, N, KV, D], row n ↔ flat slot n). DUS is
    layout-neutral and in-place — no scatter layout war — and row
    granularity never clobbers neighbouring rows, so it is safe in
    ring (rolling-buffer) mode too. N is small by construction
    (decode plans: S; windows: W*S)."""
    with device_scope("kv_commit"):
        bs = kv_pool.shape[4]
        # [L,2,KV,N,1,D]: row n's update is ONE static slice of it, and its
        # block and offset one element each of two vectors worked out once.
        # The loop is unrolled and a window or a decode block merges
        # hundreds of rows: with the division and the indexing inside it a
        # row was ~30 equations to trace, lower and hash at every start,
        # warm ones too (``PERF.md`` section 6, PR 52: +19 s of a 97 s
        # set-up until this form)
        kv_rows = jnp.stack([k_rows] if v_rows is None
                            else [k_rows, v_rows], axis=1).astype(
            kv_pool.dtype).transpose(0, 1, 3, 2, 4)[:, :, :, :, None, :]
        blk, off = flat_slots // bs, flat_slots % bs
        z = np.int32(0)
        for n in range(flat_slots.shape[0]):
            # (the primitive itself: ``lax.dynamic_update_slice`` wraps
            # every index round that might be negative, three equations
            # an index; a flat slot is not)
            kv_pool = jax.lax.dynamic_update_slice_p.bind(
                kv_pool, jax.lax.slice_in_dim(kv_rows, n, n + 1, axis=3),
                z, z, z, jax.lax.index_in_dim(blk, n, keepdims=False),
                jax.lax.index_in_dim(off, n, keepdims=False), z)
        return kv_pool


def merge_pages(kv_pool, slot_map, k_ys, v_ys, T):
    """Page-granular pool merge for SplitFuse chunk steps
    (``k_ys/v_ys`` [L, S, KV, Ts, D], token t of row s ↔
    ``slot_map[s, t]``). Chunk starts are page-aligned whenever
    chunk % block_size == 0, so each page of a prefill row is one
    whole-page DUS (rows past the chunk's real tokens land in the
    not-yet-valid region — harmless). A row that carries a single token
    (a 1-token final chunk, inactive padding) or starts off a page
    boundary must NOT page-write (its page holds live earlier rows): ALL
    its pages go to the trash block (block 0, which a degraded row's later
    pages name anyway), and the per-row token DUS that ends the merge
    writes its one real token.

    Every update must stay a PURE write, which is why a degraded row's
    first page is redirected and not read back. A read-modify-write of the
    pool (``dynamic_slice``, ``where``, DUS back) the compiler fuses into
    one loop fusion that reads and writes the pool, lays the pool out for
    THAT (the blocks dimension major-most, a page of every layer
    contiguous), and wraps the whole chain of merges in a copy out of the
    pinned row-major layout and a copy back: two copies of every pool a
    prefill step, 9-10 ms each on a 3.1 GiB latent pool (``PERF.md``
    section 6, PR 55). A plain DUS takes its operand's layout as it is;
    ``profiling.trace.pool_sized_copies`` reads a compiled program for
    such copies."""
    with device_scope("kv_commit"):
        bs = kv_pool.shape[4]
        S, pages = slot_map.shape[0], T // bs
        n_real = (slot_map >= bs).sum(axis=1)          # trash slots < bs
        # page-write only rows that really carry a chunk AND start on
        # a page boundary (the scheduler advances kv_next in whole
        # chunks so this holds today; the traced check pins the
        # invariant rather than assuming it)
        no_page = (n_real <= 1) | (slot_map[:, 0] % bs != 0)
        # every page's block, worked out once (the loop below is unrolled
        # and traced at every start: ``merge_rows``); a real row's pages
        # past its tokens carry trash slots, block 0, by construction
        blks = jnp.where(no_page[:, None], 0,
                         slot_map[:, ::bs] // bs).reshape(-1)
        z = np.int32(0)
        for s in range(S):
            for pg in range(pages):
                sl = pg * bs
                page = jnp.stack(
                    [y[:, s, :, sl:sl + bs, :]
                     for y in (k_ys, v_ys) if y is not None],
                    axis=1)[:, :, :, None].astype(kv_pool.dtype)
                kv_pool = jax.lax.dynamic_update_slice_p.bind(
                    kv_pool, page, z, z, z, jax.lax.index_in_dim(
                        blks, s * pages + pg, keepdims=False), z, z)
        # every row's first token (covers degraded rows; for full chunks
        # this rewrites the value the page already wrote)
        return merge_rows(kv_pool, slot_map[:, 0], k_ys[:, :, :, 0, :],
                          None if v_ys is None else v_ys[:, :, :, 0, :])
