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
  slot's pages in order;
- the rows of a slot with nothing to read are exactly zero.
"""
import jax.numpy as jnp
import numpy as np
import pytest

from deepspeed_tpu.ops.pallas import paged_attention as pa
from deepspeed_tpu.ops.pallas.paged_attention import (paged_ragged_attention,
                                                      paged_step_counts,
                                                      paged_work_list)


# ---- (a) the list against the rectangle, brute force ------------------------

def _rectangle_predicates(j, seq_len, qstart, sstart, *, bs, n_pool, n_grp,
                          srows, Gp, window, ring_tokens, tree):
    """``run_pool`` / ``run_stage`` as the rectangle kernel of PR 25 had
    them (``_ragged_attn_kernel`` before the list), in Python ints."""
    is_stage = j >= n_grp
    if ring_tokens:
        nwin = ring_tokens // bs
        b_latest = max(sstart - 1, 0) // bs
        first_jj = j * Gp
        run_pool = sstart > 0 and not is_stage \
            and b_latest - (b_latest - first_jj) % nwin >= 0 \
            and first_jj < n_pool
    else:
        group_start = j * Gp * bs
        run_pool = group_start < sstart and not is_stage
        if window:
            run_pool = run_pool and \
                group_start + Gp * bs > qstart - window + 1
    sp = max(j - n_grp, 0)
    if tree:
        run_stage = is_stage and seq_len > 0
    else:
        run_stage = is_stage and sstart + sp * srows < seq_len
    return run_pool, run_stage


def _brute_force(seq_lens, q_starts, stage_starts, *, bs, max_pages, Ts, Gp,
                 window, ring_tokens, tree):
    n_grp = -(-max_pages // Gp)
    srows, nsp = (Ts, 1) if Ts <= bs else (bs, Ts // bs)
    want, n_empty = [], 0
    for s, (ln, qs, ss) in enumerate(zip(seq_lens, q_starts, stage_starts)):
        mine = [j for j in range(n_grp + nsp) if any(_rectangle_predicates(
            j, ln, qs, ss, bs=bs, n_pool=max_pages, n_grp=n_grp, srows=srows,
            Gp=Gp, window=window, ring_tokens=ring_tokens, tree=tree))]
        if not mine:
            want.append((s, n_grp, True, True))     # finalize-only
            n_empty += 1
        for r, j in enumerate(mine):
            want.append((s, j, r == 0, r == len(mine) - 1))
    return want, n_grp + nsp, n_empty


# slots: two in the pool at different depths, one with a stage only (a first
# chunk), one empty, one whose context fills the table
LISTS = {
    "linear": dict(window=0, ring_tokens=0, tree=False, max_pages=6, Ts=8,
                   seq_lens=[21, 10, 5, 0, 48], q_starts=[20, 9, 0, 0, 47],
                   stage_starts=[20, 9, 0, 0, 47]),
    # the window slides off the first pages; a 20-row stage spans 3 pages
    "window": dict(window=12, ring_tokens=0, tree=False, max_pages=6, Ts=24,
                   seq_lens=[47, 16, 9, 0, 30], q_starts=[26, 15, 0, 0, 29],
                   stage_starts=[26, 15, 0, 0, 29]),
    # rolling ring of 4 table slots; slots 1 and 2 have not wrapped yet
    "ring": dict(window=24, ring_tokens=32, tree=False, max_pages=4, Ts=8,
                 seq_lens=[46, 10, 17, 0, 38], q_starts=[45, 9, 16, 0, 37],
                 stage_starts=[45, 9, 16, 0, 37]),
    # tree verify: every stage page of a live slot runs, whatever seq_len
    "tree": dict(window=0, ring_tokens=0, tree=True, max_pages=6, Ts=16,
                 seq_lens=[22, 12, 3, 0, 44], q_starts=[18, 9, 0, 0, 41],
                 stage_starts=[18, 9, 0, 0, 41]),
}


@pytest.mark.parametrize("page_group", [1, 2, 4])
@pytest.mark.parametrize("form", sorted(LISTS))
def test_work_list_is_the_live_steps_of_the_rectangle(form, page_group):
    c = LISTS[form]
    bs = 8
    want, nj, n_empty = _brute_force(
        c["seq_lens"], c["q_starts"], c["stage_starts"], bs=bs,
        max_pages=c["max_pages"], Ts=c["Ts"], Gp=page_group,
        window=c["window"], ring_tokens=c["ring_tokens"], tree=c["tree"])
    items, n_items = paged_work_list(
        jnp.asarray(c["seq_lens"]), jnp.asarray(c["q_starts"]),
        jnp.asarray(c["stage_starts"]), block_size=bs,
        max_pages=c["max_pages"], stage_rows=c["Ts"], window=c["window"],
        ring_tokens=c["ring_tokens"], page_group=page_group, tree=c["tree"])
    S = len(c["seq_lens"])
    assert items.shape == (S * nj + 1,) and items.dtype == jnp.int32
    n = int(n_items)
    got = [tuple(int(x) if i < 2 else bool(x) for i, x in enumerate(
        pa._unpack_item(code, pa._item_bits(nj)))) for code in
        np.asarray(items)[:n]]
    assert got == want
    # past the items the list is zero (the pipeline may look one ahead)
    assert not np.asarray(items)[n:].any()
    # every slot opens once and closes once, in slot order
    assert [g[0] for g in got if g[2]] == list(range(S))
    assert [g[0] for g in got if g[3]] == list(range(S))
    # the host's count of the same steps (one-page groups only): the list
    # less the finalize-only items
    if page_group == 1:
        live, rect = paged_step_counts(
            np.asarray(c["seq_lens"]), np.asarray(c["q_starts"]),
            np.asarray(c["stage_starts"]), block_size=bs,
            max_pages=c["max_pages"], stage_rows=c["Ts"], window=c["window"],
            ring_tokens=c["ring_tokens"], tree=c["tree"])
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
          window_rows=0, kv_dtype=jnp.float32, shared=None):
    """Inputs of one call. ``ctx[s]`` tokens of slot ``s`` sit in the pool,
    ``fresh[s]`` (default ``T``) in the stage; neither = an empty slot.
    ``window_rows``: the stage is a decode window's (that many rows, the
    one query row at the slot's last token) — slots that stopped at
    different iterations hold different ``fresh``. ``shared`` = (slots,
    pages): those slots' tables start with the same blocks (a prefix-cache
    hit)."""
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
        n = -(-(ctx[s] + fresh[s]) // bs)
        tables[s, :n] = rng.integers(1, nb, n)
        starts[s], lens[s] = ctx[s], ctx[s] + fresh[s]
    if shared:
        slots, pages = shared
        for s in slots[1:]:
            tables[s, :pages] = tables[slots[0], :pages]
    q_starts = np.maximum(lens - 1, 0) if window_rows else starts
    return dict(q=q, pool=pool, ks=ks, vs=vs, tables=jnp.asarray(tables),
                seq_lens=jnp.asarray(lens), q_starts=jnp.asarray(q_starts),
                stage_starts=jnp.asarray(starts), bs=bs)


def _attend(a, **kw):
    return paged_ragged_attention(
        a["q"], a["pool"], a["ks"], a["vs"], a["tables"], a["seq_lens"],
        a["q_starts"], a["stage_starts"], block_size=a["bs"],
        layer_index=jnp.int32(1), interpret=True, **kw)


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
    """float32 softmax over each slot's keys in order: its pool pages'
    tokens below ``stage_starts``, then its staged tokens."""
    q, pool = np.asarray(a["q"], np.float32), np.asarray(
        a["pool"].astype(jnp.float32))
    ks, vs = np.asarray(a["ks"]), np.asarray(a["vs"])
    tables, bs = np.asarray(a["tables"]), a["bs"]
    S, T, H, D = q.shape
    KV = pool.shape[2]
    out = np.zeros_like(q)
    for s in range(S):
        ln, ss = int(a["seq_lens"][s]), int(a["stage_starts"][s])
        if ln == 0:
            continue
        pos = np.arange(ss)
        k = np.concatenate([pool[1, 0][:, tables[s, pos // bs], pos % bs],
                            ks[s, :, :ln - ss]], axis=1)      # [KV, n, D]
        v = np.concatenate([pool[1, 1][:, tables[s, pos // bs], pos % bs],
                            vs[s, :, :ln - ss]], axis=1)
        kpos = np.arange(ln)
        for t in range(T):
            qpos = int(a["q_starts"][s]) + t
            for h in range(H):
                sc = k[h // (H // KV)] @ q[s, t, h] / np.sqrt(D)
                sc = np.where(kpos <= qpos, sc, -np.inf)
                w = np.exp(sc - sc.max())
                out[s, t, h] = (w / w.sum()) @ v[h // (H // KV)]
    return out


def _cases():
    g = dict(KV=2, G=2, D=64, bs=8, nb=24, max_pages=8)
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
    if name != "fp8_pool":          # fp8 dots: held by the groups test
        np.testing.assert_allclose(np.asarray(got), _plain_softmax(a),
                                   rtol=2e-5, atol=2e-5)


def test_a_handed_list_is_the_list_the_kernel_builds():
    """The engine builds the list once a forward and hands it to every
    layer's call: the same output as a call that builds its own."""
    a = _case(np.random.default_rng(9), S=4, ctx=[20, 0, 33, 7],
              fresh=[1, 0, 1, 1], KV=2, G=2, D=64, bs=8, nb=24, max_pages=8)
    work = paged_work_list(a["seq_lens"], a["q_starts"], a["stage_starts"],
                           block_size=8, max_pages=8, stage_rows=8)
    np.testing.assert_array_equal(np.asarray(_attend(a, work=work)),
                                  np.asarray(_attend(a)))
