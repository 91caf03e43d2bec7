"""The ragged paged kernel's iteration space as a LIST (PR 26): the grid
walks the ``(slot, column)`` steps that read a page, not the rectangle
slots x (table width + stage pages).

Three things are held here, all in the CPU interpreter:

- ``paged_work_list`` against a brute-force loop over the old rectangle
  with the predicates of the rectangle kernel transcribed into plain
  Python (not shared with the code under test): the same steps, in
  ``(slot, column)`` order, first and last of each slot marked, one
  finalize-only item for a slot with none;
- the kernel's outputs over the list BITWISE equal to the rectangle walk
  (the same kernel handed a list of every ``(slot, column)``: what the
  grid was before), and close to a plain float32 softmax over each
  slot's pages in order under the form's mask (causal, sliding window,
  rolling ring, tree verify);
- the rows of a slot with nothing to read are exactly zero;
- an fp8 pool's probability pre-scaling over a long context.
"""
import jax.numpy as jnp
import numpy as np
import pytest

from deepspeed_tpu.ops.pallas import paged_attention as pa
from deepspeed_tpu.ops.pallas.paged_attention import (paged_ragged_attention,
                                                      paged_step_counts,
                                                      paged_work_list)


# ---- (a) the list against the rectangle, brute force ------------------------

def _rectangle_predicates(j, seq_len, qstart, sstart, *, bs, n_pool, srows,
                          window, ring_tokens, tree):
    """``run_pool`` / ``run_stage`` as the rectangle kernel of PR 25 had
    them (``_ragged_attn_kernel`` before the list), in Python ints."""
    is_stage = j >= n_pool
    if ring_tokens:
        nwin = ring_tokens // bs
        b_latest = max(sstart - 1, 0) // bs
        run_pool = sstart > 0 and not is_stage \
            and b_latest - (b_latest - j) % nwin >= 0
    else:
        page_start = j * bs
        run_pool = page_start < sstart and not is_stage
        if window:
            run_pool = run_pool and page_start + bs > qstart - window + 1
    sp = max(j - n_pool, 0)
    if tree:
        run_stage = is_stage and seq_len > 0
    else:
        run_stage = is_stage and sstart + sp * srows < seq_len
    return run_pool, run_stage


def _brute_force(seq_lens, q_starts, stage_starts, *, bs, max_pages, Ts,
                 window, ring_tokens, tree):
    srows, nsp = (Ts, 1) if Ts <= bs else (bs, Ts // bs)
    want, n_empty = [], 0
    for s, (ln, qs, ss) in enumerate(zip(seq_lens, q_starts, stage_starts)):
        mine = [j for j in range(max_pages + nsp) if any(
            _rectangle_predicates(
                j, ln, qs, ss, bs=bs, n_pool=max_pages, srows=srows,
                window=window, ring_tokens=ring_tokens, tree=tree))]
        if not mine:
            want.append((s, max_pages, True, True))     # finalize-only
            n_empty += 1
        for r, j in enumerate(mine):
            want.append((s, j, r == 0, r == len(mine) - 1))
    return want, max_pages + nsp, n_empty


# (block size, stage rows): the stage is one page, or several
GEOMETRIES = {"page8_stage_one_page": (8, 8),
              "page8_stage_three_pages": (8, 24),
              "page16_stage_two_pages": (16, 32)}

# a slot: (whole pool pages, tokens into the next, fresh tokens in the
# stage: "one", "few" = 3, "half" or "most" of the stage), or None = empty.
# Two slots in the pool at different depths, one with a stage only (a first
# chunk), one empty, one whose context fills the table
LISTS = {
    "linear": dict(window=0, ring=False, tree=False, max_pages=6,
                   slots=[(2, 4, "one"), (1, 1, "most"), (0, 0, "half"),
                          None, (5, -1, "one")]),
    # the window (a page and a half) slides off the first pages
    "window": dict(window=1.5, ring=False, tree=False, max_pages=6,
                   slots=[(3, 2, "most"), (1, -1, "one"), (0, 0, "half"),
                          None, (3, 5, "one")]),
    # rolling ring of 4 table slots; slots 1 and 2 have not wrapped yet
    "ring": dict(window=3, ring=True, tree=False, max_pages=4,
                 slots=[(5, 5, "one"), (1, 1, "most"), (2, 0, "one"),
                        None, (4, 5, "few")]),
    # tree verify: every stage page of a live slot runs, whatever seq_len
    "tree": dict(window=0, ring=False, tree=True, max_pages=6,
                 slots=[(2, 2, "few"), (1, 1, "few"), (0, 0, "few"),
                        None, (5, 1, "few")]),
}


def _lengths(slots, bs, Ts):
    fresh = {"one": 1, "few": 3, "half": Ts // 2 + 1, "most": Ts - 3}
    starts = [0 if sl is None else sl[0] * bs + sl[1] % bs for sl in slots]
    lens = [0 if sl is None else st + fresh[sl[2]]
            for sl, st in zip(slots, starts)]
    return lens, starts


@pytest.mark.parametrize("geometry", sorted(GEOMETRIES))
@pytest.mark.parametrize("form", sorted(LISTS))
def test_work_list_is_the_live_steps_of_the_rectangle(form, geometry):
    c = LISTS[form]
    bs, Ts = GEOMETRIES[geometry]
    seq_lens, starts = _lengths(c["slots"], bs, Ts)
    kw = dict(window=int(c["window"] * bs),
              ring_tokens=c["max_pages"] * bs if c["ring"] else 0,
              tree=c["tree"])
    want, nj, n_empty = _brute_force(seq_lens, starts, starts, bs=bs,
                                     max_pages=c["max_pages"], Ts=Ts, **kw)
    items, n_items = paged_work_list(
        jnp.asarray(seq_lens), jnp.asarray(starts), jnp.asarray(starts),
        block_size=bs, max_pages=c["max_pages"], stage_rows=Ts, **kw)
    S = len(seq_lens)
    assert items.shape == (S * nj + 1,) and items.dtype == jnp.int32
    n = int(n_items)
    got = [tuple(int(x) if i < 2 else bool(x) for i, x in enumerate(
        pa._unpack_item(code, pa._item_bits(nj)))) for code in
        np.asarray(items)[:n]]
    assert got == want
    # some slot walks every stage page the geometry has
    assert max(sum(1 for g in got if g[0] == s and g[1] >= c["max_pages"])
               for s in range(S)) == nj - c["max_pages"]
    # past the items the list is zero (the pipeline may look one ahead)
    assert not np.asarray(items)[n:].any()
    # every slot opens once and closes once, in slot order
    assert [g[0] for g in got if g[2]] == list(range(S))
    assert [g[0] for g in got if g[3]] == list(range(S))
    # the host's count of the same steps: the list less the finalize-only
    # items
    live, rect = paged_step_counts(
        np.asarray(seq_lens), np.asarray(starts), np.asarray(starts),
        block_size=bs, max_pages=c["max_pages"], stage_rows=Ts, **kw)
    assert (live, rect) == (n - n_empty, S * nj)


def test_work_list_refuses_a_rectangle_that_does_not_pack():
    with pytest.raises(ValueError, match="do not pack"):
        paged_work_list(jnp.zeros((1 << 16,), jnp.int32),
                        jnp.zeros((1 << 16,), jnp.int32),
                        jnp.zeros((1 << 16,), jnp.int32), block_size=8,
                        max_pages=1 << 14, stage_rows=8)


def test_kernel_refuses_a_list_built_for_another_geometry():
    rng = np.random.default_rng(0)
    a = _case(rng, S=2, KV=2, G=1, D=64, bs=8, nb=8, max_pages=4,
              ctx=[9, 0], fresh=[1, 0])
    work = paged_work_list(a["seq_lens"], a["q_starts"], a["stage_starts"],
                           block_size=8, max_pages=3, stage_rows=8)
    with pytest.raises(ValueError, match="was not built for"):
        _attend(a, work=work)


# ---- (b), (c) the kernel over the list: bitwise the rectangle walk ----------

def _case(rng, *, S, KV, G, D, bs, nb, max_pages, ctx, T=1, fresh=None,
          window_rows=0, kv_dtype=jnp.float32, shared=None, window=None,
          ring=False, tree=None):
    """Inputs of one call. ``ctx[s]`` tokens of slot ``s`` sit in the pool,
    ``fresh[s]`` (default ``T``) in the stage; neither = an empty slot.
    ``window_rows``: the stage is a decode window's (that many rows, the
    one query row at the slot's last token) — slots that stopped at
    different iterations hold different ``fresh``. ``shared`` = (slots,
    pages): those slots' tables start with the same blocks (a prefix-cache
    hit). ``window``: sliding-window attention; ``ring``: the table is a
    rolling ring of its ``max_pages`` slots (block ``b`` sits in slot ``b %
    max_pages``). ``tree`` = parents of the ``T`` candidate nodes (tree
    verify: the root sits at ``ctx[s]``, a node at root + its depth)."""
    H = KV * G
    Ts = window_rows or max(8, T)
    fresh = [T] * S if fresh is None else fresh
    pool = jnp.asarray(rng.standard_normal((2, 2, KV, nb, bs, D)) * 0.3,
                       kv_dtype)
    q = jnp.asarray(rng.standard_normal((S, T, H, D)) * 0.3, jnp.float32)
    ks = jnp.asarray(rng.standard_normal((S, KV, Ts, D)) * 0.3, jnp.float32)
    vs = jnp.asarray(rng.standard_normal((S, KV, Ts, D)) * 0.3, jnp.float32)
    tables = np.zeros((S, max_pages), np.int32)
    lens, starts = np.zeros(S, np.int32), np.zeros(S, np.int32)
    for s in range(S):
        if ctx[s] + fresh[s] == 0:
            continue
        n = min(max_pages, -(-(ctx[s] + fresh[s]) // bs))
        tables[s, :n] = rng.integers(1, nb, n)
        starts[s], lens[s] = ctx[s], ctx[s] + fresh[s]
    if shared:
        slots, pages = shared
        for s in slots[1:]:
            tables[s, :pages] = tables[slots[0], :pages]
    q_starts = np.maximum(lens - 1, 0) if window_rows else starts
    kw = dict(window=window, ring_tokens=max_pages * bs if ring else None)
    if tree is not None:
        depth = [0] * T
        mask = np.zeros((S, T, T), np.uint8)
        for i, par in enumerate(tree):
            depth[i] = 0 if par < 0 else depth[par] + 1
            j = i
            while j != -1:
                mask[:, i, j] = 1
                j = tree[j]
        live = lens > 0
        pos = (starts[:, None] + np.asarray(depth, np.int32)) * live[:, None]
        lens = np.where(live, starts + 1 + max(depth), 0).astype(np.int32)
        kw.update(tree_positions=jnp.asarray(pos, jnp.int32),
                  tree_mask=jnp.asarray(mask * live[:, None, None]))
    return dict(q=q, pool=pool, ks=ks, vs=vs, tables=jnp.asarray(tables),
                seq_lens=jnp.asarray(lens), q_starts=jnp.asarray(q_starts),
                stage_starts=jnp.asarray(starts), bs=bs, kw=kw)


def _attend(a, **kw):
    return paged_ragged_attention(
        a["q"], a["pool"], a["ks"], a["vs"], a["tables"], a["seq_lens"],
        a["q_starts"], a["stage_starts"], block_size=a["bs"],
        layer_index=jnp.int32(1), interpret=True, **a["kw"], **kw)


def _rectangle_walk(a):
    """The grid as it was: every (slot, column), initialise on a slot's
    first column, finalise on its last, the predicates inside."""
    S, max_pages = a["tables"].shape
    Ts, bs = a["ks"].shape[2], a["bs"]
    nj = max_pages + (1 if Ts <= bs else Ts // bs)
    jb = pa._item_bits(nj)
    s, j = np.divmod(np.arange(S * nj), nj)
    code = ((s << jb | j) << 2) | ((j == nj - 1) << 1) | (j == 0)
    items = jnp.asarray(np.append(code, 0), jnp.int32)
    return _attend(a, work=(items, jnp.int32(S * nj)))


def _plain_softmax(a):
    """float32 softmax over each slot's keys in order — its pool tokens
    below ``stage_starts`` (through the ring's slot arithmetic where the
    table is one), then its staged tokens — under the form's mask: causal,
    inside the sliding window, and for tree verify the ancestors mask on
    the stage with the nodes' own positions on the pool."""
    q, pool = np.asarray(a["q"], np.float32), np.asarray(
        a["pool"].astype(jnp.float32))
    ks, vs = np.asarray(a["ks"]), np.asarray(a["vs"])
    tables, bs = np.asarray(a["tables"]), a["bs"]
    window = a["kw"]["window"]
    tpos, tmask = a["kw"].get("tree_positions"), a["kw"].get("tree_mask")
    S, T, H, D = q.shape
    KV = pool.shape[2]
    out = np.zeros_like(q)
    for s in range(S):
        ln, ss = int(a["seq_lens"][s]), int(a["stage_starts"][s])
        if ln == 0:
            continue
        pos = np.arange(ss)
        page = tables[s, (pos // bs) % tables.shape[1]]   # % : no-op linear
        rows = T if tpos is not None else ln - ss
        k = np.concatenate([pool[1, 0][:, page, pos % bs],
                            ks[s, :, :rows]], axis=1)        # [KV, n, D]
        v = np.concatenate([pool[1, 1][:, page, pos % bs],
                            vs[s, :, :rows]], axis=1)
        kpos = np.arange(ss + rows)
        for t in range(T):
            qpos = int(a["q_starts"][s]) + t if tpos is None \
                else int(tpos[s, t])
            see = kpos <= qpos
            if window:
                see &= kpos > qpos - window
            if tpos is not None:
                see[ss:] = np.asarray(tmask[s, t, :rows]) > 0
            for h in range(H):
                sc = k[h // (H // KV)] @ q[s, t, h] / np.sqrt(D)
                sc = np.where(see, sc, -np.inf)
                w = np.exp(sc - sc.max())
                out[s, t, h] = (w / w.sum()) @ v[h // (H // KV)]
    return out


def _cases():
    g = dict(KV=2, G=2, D=64, bs=8, nb=24, max_pages=8)
    decode = dict(S=4, fresh=[1, 1, 1, 0], **g)
    chunk = dict(S=4, T=16, fresh=[16, 16, 16, 0], **g)
    return {
        "all_slots_empty": dict(S=4, ctx=[0] * 4, fresh=[0] * 4, **g),
        "one_slot_at_the_full_table_width": dict(
            S=4, ctx=[0, 63, 0, 0], fresh=[0, 1, 0, 0], **g),
        "all_48_slots_live": dict(
            S=48, ctx=list(range(1, 49)), **{**g, "max_pages": 7}),
        # a decode window of 8 rows: slots stopped after 3 and 8 iterations,
        # one is on its first, one never ran
        "a_slot_finishing_mid_window": dict(
            S=4, ctx=[20, 9, 33, 0], fresh=[3, 8, 1, 0], window_rows=8, **g),
        # two slots share 6 blocks of prefix: 8 + 8 + 2 table entries over a
        # pool of 12 blocks
        "shared_prefix_pages_outnumber_the_pool": dict(
            S=3, ctx=[55, 53, 12], shared=([0, 1], 6), **{**g, "nb": 12}),
        "gqa_32_over_8_head_128": dict(
            S=3, ctx=[40, 0, 17], fresh=[1, 0, 1], KV=8, G=4, D=128, bs=16,
            nb=12, max_pages=4),
        "mha_16_over_16_head_128": dict(
            S=3, ctx=[40, 0, 17], fresh=[1, 0, 1], KV=16, G=1, D=128, bs=16,
            nb=12, max_pages=4),
        # a prefill chunk of 128 tokens (two query tiles at G = 2; the
        # stage spans 8 pages) behind 0, 2 and 5 pages of context
        "chunk_of_128": dict(
            S=4, T=128, ctx=[0, 32, 80, 0], fresh=[128, 128, 128, 0], KV=2,
            G=2, D=64, bs=16, nb=16, max_pages=16),
        "fp8_pool": dict(S=3, ctx=[40, 0, 17], fresh=[1, 0, 1],
                         kv_dtype=jnp.float8_e4m3fn, **g),
        # a rolling ring of 4 (decode) or 6 (a 16-token chunk) table slots
        # under a window of 3 pages: slot 0 has wrapped, slot 1 has not,
        # slot 2 sits on a page boundary (decode) or is a first chunk
        "ring_decode": dict(ctx=[45, 9, 16, 0], window=24, ring=True,
                            **{**decode, "max_pages": 4}),
        "ring_chunk": dict(ctx=[70, 12, 0, 0], window=24, ring=True,
                           **{**chunk, "max_pages": 6}),
        "ring_over_fp8_pool": dict(
            ctx=[45, 9, 16, 0], window=24, ring=True,
            kv_dtype=jnp.float8_e4m3fn, **{**decode, "max_pages": 4}),
        # the window slides off the first pages of slot 0 only
        "sliding_window_decode": dict(ctx=[46, 15, 8, 0], window=12,
                                      **decode),
        "sliding_window_chunk": dict(ctx=[40, 3, 0, 0], window=12, **chunk),
        "window_longer_than_the_context": dict(ctx=[20, 9, 33, 0],
                                               window=64, **decode),
        # tree verify: a chain, and two siblings that share a position with
        # a chain under each
        "tree_verify_chain": dict(
            S=4, T=5, ctx=[18, 11, 37, 0], fresh=[5, 5, 5, 0],
            tree=[-1, 0, 1, 2, 3], **g),
        "tree_verify_branchy": dict(
            S=4, T=6, ctx=[18, 11, 37, 0], fresh=[6, 6, 6, 0],
            tree=[-1, 0, 0, 1, 2, 3], **g),
    }


CASES = _cases()


@pytest.mark.parametrize("name", sorted(CASES))
def test_list_walk_is_bitwise_the_rectangle_walk(name):
    a = _case(np.random.default_rng(5), **CASES[name])
    if name == "shared_prefix_pages_outnumber_the_pool":
        assert int((np.asarray(a["tables"]) > 0).sum()) > a["pool"].shape[3]
    got = _attend(a)
    assert not np.isnan(np.asarray(got, np.float32)).any()
    np.testing.assert_array_equal(np.asarray(got),
                                  np.asarray(_rectangle_walk(a)))
    # (c) nothing to read: exactly zero, not what the scratch held
    empty = np.asarray(a["seq_lens"]) == 0
    assert not np.asarray(got)[empty].any()
    if name == "all_slots_empty":
        assert empty.all()
    # an fp8 pool casts q and p to e4m3 for its dots: a few per cent
    tol = 8e-2 if a["pool"].dtype == jnp.float8_e4m3fn else 2e-5
    np.testing.assert_allclose(np.asarray(got), _plain_softmax(a),
                               rtol=tol, atol=tol)


def test_fp8_pool_p_scaling_matches_fp32_long_context():
    """fp8 pool vs fp32 pool holding the SAME values over a 217-token
    context (27 pool pages and one staged token): with an fp8 pool the
    kernel scales softmax p into e4m3's normal range before the PV-dot
    cast and cancels the scale in the accumulated denominator, which keeps
    long-tail attention weights (~1/n) out of e4m3's subnormal range — the
    output error stays at fp8 value-quantization scale instead of
    collapsing small weights to zero. Both pools hold e4m3-representable
    values, so the remaining delta isolates the q and p casts."""
    a = _case(np.random.default_rng(11), S=1, KV=2, G=2, D=64, bs=8, nb=32,
              max_pages=28, ctx=[27 * 8])
    pool8 = a["pool"].astype(jnp.float8_e4m3fn)
    out32 = np.asarray(_attend({**a, "pool": pool8.astype(jnp.float32)}))
    out8 = np.asarray(_attend({**a, "pool": pool8}), np.float32)
    assert np.abs(out32 - out8).max() < 0.08
    assert np.abs(out32 - out8).mean() < 0.02


def test_a_handed_list_is_the_list_the_kernel_builds():
    """The engine builds the list once a forward and hands it to every
    layer's call: the same output as a call that builds its own."""
    a = _case(np.random.default_rng(9), S=4, ctx=[20, 0, 33, 7],
              fresh=[1, 0, 1, 1], KV=2, G=2, D=64, bs=8, nb=24, max_pages=8)
    work = paged_work_list(a["seq_lens"], a["q_starts"], a["stage_starts"],
                           block_size=8, max_pages=8, stage_rows=8)
    np.testing.assert_array_equal(np.asarray(_attend(a, work=work)),
                                  np.asarray(_attend(a)))


# ---- (d) the query tile is planned from the shape (PR 47) -------------------

def _capped_tile(TG, KV, bs):
    """The tile as it was before ``paged_plan``: a cap of 128 rows, halved
    to a 2 MiB score tile and to a divisor (transcribed, not shared)."""
    TQB = TG if TG <= 128 else 128
    while TQB > 8 and KV * TQB * bs * 4 > 2 ** 21:
        TQB //= 2
    while TG % TQB:
        TQB //= 2
    return TQB


# (rows a KV head, KV heads, page) -> the tile under the 2 MiB budget
PLANS = {
    # T = 1: one token's query heads
    "decode_smallthinker_28_over_4": ((7, 4, 128), 7),
    "decode_mistral_32_over_8": ((4, 8, 128), 4),
    "decode_olmoe_16_over_16": ((1, 16, 128), 1),
    # OLMoE's whole prefill chunk is one tile already
    "chunk128_olmoe": ((128, 16, 128), 128),
    "chunk16_smallthinker": ((112, 4, 128), 112),
    # 512 and 1,024 tokens x 7 query heads a KV head
    "chunk512_smallthinker": ((3584, 4, 128), 896),
    "chunk1024_smallthinker": ((7168, 4, 128), 1024),
    "chunk256_smallthinker": ((1792, 4, 128), 896),
    # 128 and 256 tokens x 4
    "chunk128_mistral": ((512, 8, 128), 512),
    "chunk256_mistral": ((1024, 8, 128), 512),
    # under tensor: 4 a shard holds 2 of Mistral's KV heads
    "chunk256_mistral_a_tensor_shard": ((1024, 2, 128), 1024),
    "chunk1536_olmoe": ((1536, 16, 128), 256),
    # a narrow page's score tile is lane-padded: no taller than at 128
    "page_of_32": ((4096, 4, 32), 1024),
    # no admissible divisor above 128: the capped tile stays
    "wide_page_many_heads": ((3584, 16, 256), 128),
    "thirty_two_kv_heads": ((512, 32, 128), 128),
    "rows_with_no_aligned_divisor": ((262, 2, 16), 2),
    "rows_whose_aligned_divisors_are_short": ((2 * 3 * 5 * 7 * 11, 2, 16), 2),
}


@pytest.mark.parametrize("name", sorted(PLANS))
def test_query_tile_is_planned_from_the_shape(name):
    (TG, KV, bs), want = PLANS[name]
    plan = pa.paged_plan(TG, KV, bs, jnp.bfloat16)
    assert plan.tqb == want and plan.n_tiles * plan.tqb == TG
    capped = _capped_tile(TG, KV, bs)
    if want <= 128:
        assert want == capped           # the parent's tile, to the row
    else:
        assert want % 16 == 0 and want > capped
        assert plan.score_tile_bytes <= pa.SCORE_TILE_BYTES
    # the tree form rides the capped tile whatever the shape
    assert pa.paged_plan(TG, KV, bs, jnp.bfloat16, tree=True).tqb == capped
    assert f"{plan.n_tiles} query tile" in plan.describe()


def test_query_tile_over_a_sweep_of_shapes():
    """Every call of at most 128 rows a KV head keeps the capped tile (all
    decode and tree programs); a taller tile divides the rows, is sublane
    aligned and never holds a score tile over the budget; the tallest
    admissible divisor is the one taken."""
    for KV in (1, 2, 4, 8, 16, 32):
        for bs in (16, 32, 64, 128, 256):
            for G in (1, 2, 4, 7, 8):
                for T in (1, 2, 8, 16, 24, 64, 128, 256, 512, 1024, 1536):
                    TG = T * G
                    capped = _capped_tile(TG, KV, bs)
                    for dt, sub in ((jnp.float32, 8), (jnp.bfloat16, 16)):
                        tqb = pa.paged_plan(TG, KV, bs, dt).tqb
                        assert TG % tqb == 0
                        if TG <= 128 or tqb <= 128:
                            assert tqb == capped, (TG, KV, bs)
                            continue
                        room = pa.SCORE_TILE_BYTES // (KV * max(bs, 128) * 4)
                        assert tqb % sub == 0 and tqb <= room
                        assert not any(TG % t == 0 for t in range(
                            tqb + sub, min(room, TG) + 1, sub))


def _chunk_cases():
    g = dict(KV=2, G=7, D=64, bs=16, nb=40, max_pages=16, T=64)
    return {
        "full_causal": dict(S=1, ctx=[100], **g),
        "a_first_chunk": dict(S=1, ctx=[0], **g),
        # the window (three pages) slides off the first pages of slot 0
        "sliding_window": dict(S=2, ctx=[150, 20], window=48, **g),
        # a ring of 8 table slots under a window of 48: slot 0 has wrapped
        # twice, slot 1 has not, slot 2 is a first chunk
        "wrapped_ring": dict(S=3, ctx=[300, 40, 0], window=48, ring=True,
                             **{**g, "max_pages": 8}),
        # 37 + 50 fresh tokens: starts and ends inside a page, and the
        # chunk's last 14 rows are padding
        "chunk_ends_mid_page": dict(S=2, ctx=[37, 129], fresh=[50, 64], **g),
        "an_empty_slot": dict(S=3, ctx=[100, 0, 55], fresh=[64, 0, 64], **g),
        "all_slots_empty": dict(S=2, ctx=[0, 0], fresh=[0, 0], **g),
        "fp8_pool": dict(S=2, ctx=[100, 37], kv_dtype=jnp.float8_e4m3fn,
                         **g),
        "ring_over_fp8_pool": dict(
            S=2, ctx=[300, 40], window=48, ring=True,
            kv_dtype=jnp.float8_e4m3fn, **{**g, "max_pages": 8}),
        "four_slots_at_four_depths": dict(S=4, ctx=[0, 16, 100, 191], **g),
        "mistral_groups_of_four": dict(
            S=2, ctx=[100, 37], **{**g, "G": 4, "T": 128}),
    }


CHUNKS = _chunk_cases()


@pytest.mark.parametrize("tiles", ["one_tall_tile", "two_tall_tiles"])
@pytest.mark.parametrize("name", sorted(CHUNKS))
def test_a_tall_query_tile_is_bitwise_the_128_row_tile(name, tiles,
                                                       monkeypatch):
    """A row's online softmax sees its pages in the same order and never
    another row: the planned tile (all 448 rows a KV head, or two tiles of
    224 under a budget pulled down) gives the capped tile's output bit for
    bit (seven tiles of 64; at G = 4, 512 rows: four of 128)."""
    c = CHUNKS[name]
    a = _case(np.random.default_rng(47), **c)
    TG, KV = c["T"] * c["G"], c["KV"]
    if tiles == "two_tall_tiles":
        monkeypatch.setattr(pa, "SCORE_TILE_BYTES", KV * (TG // 2) * 128 * 4)
    plan = pa.paged_plan(TG, KV, c["bs"], jnp.float32)
    assert plan.tqb > 128
    assert plan.n_tiles == (1 if tiles == "one_tall_tile" else 2)
    got = _attend(a)
    capped = _capped_tile(TG, KV, c["bs"])
    assert capped <= 128
    monkeypatch.setattr(pa, "paged_plan", lambda *_a, **_k: pa.PagedPlan(
        TG, KV, c["bs"], capped))
    want = _attend(a)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    assert not np.isnan(np.asarray(got, np.float32)).any()
    empty = np.asarray(a["seq_lens"]) == 0
    assert not np.asarray(got)[empty].any()
    tol = 8e-2 if a["pool"].dtype == jnp.float8_e4m3fn else 2e-5
    np.testing.assert_allclose(np.asarray(got), _plain_softmax(a),
                               rtol=tol, atol=tol)
