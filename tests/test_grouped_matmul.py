"""The grouped (per-expert) GEMM's block plan and the kernel under every block
the plan can come to (``ops/pallas/grouped_matmul.py``), in interpret mode.
Tier-1: ``tests/test_moe.py`` holds the layer's own tests and is slow-marked as
a file; the described-v5e compiles are in ``tests/test_chip_compile.py``."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

#: (K, N) -> the plan's (bk, bn, nk) at 16 and at 128 rows a tile: the four
#: expert GEMMs the benchmark serves (OLMoE-1B-7B, SmallThinker-21B-A3B) and
#: Mixtral-8x7B's two; bf16
GMM_SHAPES = {
    "olmoe_gate_up": ((2048, 1024), (2048, 1024, 1)),
    "olmoe_down": ((1024, 2048), (1024, 2048, 1)),
    "smallthinker_gate_up": ((2560, 768), (2560, 768, 1)),
    "smallthinker_down": ((768, 2560), (768, 2560, 1)),
    "mixtral_gate_up": ((4096, 14336), (4096, 512, 1)),
    "mixtral_down": ((14336, 4096), (3584, 512, 4)),
    # a tensor shard of four of SmallThinker's: the LOCAL shape decides
    "smallthinker_gate_up_tp4": ((2560, 192), (2560, 192, 1)),
    "smallthinker_down_tp4": ((192, 2560), (192, 2560, 1)),
}


@pytest.mark.parametrize("block_m", [16, 128])
@pytest.mark.parametrize("name", list(GMM_SHAPES))
def test_gmm_plan(name, block_m):
    """The grouped GEMM's weight block is a function of the shape and a
    VMEM budget: K whole wherever a block fits, the column tile the widest
    multiple of 128 dividing N that fits — never a cap that happens to
    divide powers of two."""
    import deepspeed_tpu.ops.pallas.grouped_matmul as gm

    (K, N), want = GMM_SHAPES[name]
    plan = gm.gmm_plan(K, N, block_m, jnp.bfloat16)
    assert (plan.bk, plan.bn, plan.nk) == want
    assert plan.vmem_bytes <= gm.VMEM_BUDGET_BYTES < gm.VMEM_LIMIT_BYTES
    assert K % plan.bk == 0 and N % plan.bn == 0
    assert plan.bn == N or plan.bn % 128 == 0
    assert plan.bk == K or plan.bk % 128 == 0
    assert plan.steps_per_tile == (N // plan.bn) * plan.nk
    # K is split only where no whole-K block fits at the narrowest column
    # tile the plan keeps K whole for
    floor = max([d for d in range(128, min(N, gm.MIN_BLOCK_N) + 1, 128)
                 if N % d == 0], default=N)
    whole_fits = gm._vmem_bytes(block_m, K, K, floor, 2) \
        <= gm.VMEM_BUDGET_BYTES
    assert (plan.nk == 1) == whole_fits
    for word in (f"K {K} x N {N}", f"{plan.bk} x {plan.bn}",
                 f"nk {plan.nk}", f"{plan.steps_per_tile} grid step"):
        assert word in plan.describe()


@pytest.mark.parametrize("budget_kib, N, want", [
    (None, 384, (640, 384, 1)),     # K and N whole: one step a tile
    (2200, 768, (640, 384, 1)),     # K whole, N in two column tiles
    (300, 384, (128, 128, 5)),      # fifteen blocks a tile: the accumulator
])
def test_grouped_matmul_serving_form_at_a_scaled_smallthinker_shape(
        monkeypatch, budget_kib, N, want):
    """K = 5 x 128 and N = 3 x 128 (SmallThinker's 2560 x 768 an eighth
    and a half: neither a power of two), the stacked slab with the layer
    picked in the kernel, an expert that fills two tiles, experts with no
    row, and the buffer's empty tail skipped — against the per-expert
    loop, under each block the plan can come to."""
    import deepspeed_tpu.ops.pallas.grouped_matmul as gm

    if budget_kib is not None:
        monkeypatch.setattr(gm, "VMEM_BUDGET_BYTES", budget_kib * 1024)
    rng = np.random.default_rng(3)
    T, n, K, bm, L = 40, 8, 640, 16, 2
    # expert 5 gets 20 rows (two tiles), experts 0 and 7 none
    eidx = np.concatenate([np.full(20, 5), rng.choice([1, 2, 3, 4, 6], 20)])
    eidx = jnp.asarray(eidx.astype(np.int32)[:, None])
    x = rng.standard_normal((T, K)).astype(np.float32)
    w = rng.standard_normal((L, n, K, N)).astype(np.float32) / K ** 0.5
    plan = gm.gmm_plan(K, N, bm, jnp.float32)
    assert (plan.bk, plan.bn, plan.nk) == want

    def run(x, w, li):
        srt = gm.sort_tokens_by_expert(eidx, n, bm)
        buf = jnp.zeros((srt.Tp, K), x.dtype).at[srt.dst].set(x)
        out = gm.grouped_matmul_layer(buf, w, srt.tile_expert, srt.n_tiles,
                                      bm, layer_index=li)
        return out[srt.dst], srt.tile_expert, srt.n_tiles

    for li in range(L):
        out, te, nt = jax.jit(run)(jnp.asarray(x), jnp.asarray(w),
                                   jnp.int32(li))
        te, nt = np.asarray(te), int(nt)
        assert nt < len(te)                         # an empty tail
        assert (te[:nt] == 5).sum() == 2            # two tiles of one expert
        assert not set(te.tolist()) & {0, 7}
        want_rows = np.einsum("tk,tkn->tn", x, w[li][np.asarray(eidx[:, 0])])
        np.testing.assert_allclose(np.asarray(out), want_rows, atol=2e-4)


def test_grouped_matmul_grads_where_k_splits(monkeypatch):
    """``transpose_rhs`` (dx) goes through the same plan: the gradients
    under a budget that splits BOTH contractions (forward over E, dx over
    F) equal the whole-block ones."""
    import deepspeed_tpu.ops.pallas.grouped_matmul as gm

    rng = np.random.default_rng(4)
    T, n, E, F, bm = 24, 3, 256, 384, 8
    eidx = jnp.asarray(rng.integers(0, n, (T, 1)).astype(np.int32))
    x = jnp.asarray(rng.standard_normal((T, E)).astype(np.float32))
    w = jnp.asarray(rng.standard_normal((n, E, F)).astype(np.float32)) / 16
    srt = jax.jit(lambda e: gm.sort_tokens_by_expert(e, n, bm))(eidx)

    def loss(x, w):
        buf = jnp.zeros((srt.Tp, E), x.dtype).at[srt.dst].set(x)
        return jnp.sum(jnp.sin(
            gm.grouped_matmul(buf, w, srt.tile_expert, bm)[srt.dst]))

    whole = jax.grad(loss, argnums=(0, 1))(x, w)
    monkeypatch.setattr(gm, "VMEM_BUDGET_BYTES", 160 * 1024)
    assert gm.gmm_plan(E, F, bm, jnp.float32).nk == 2
    assert gm.gmm_plan(F, E, bm, jnp.float32).nk == 3
    split = jax.grad(loss, argnums=(0, 1))(x, w)
    for a, b in zip(whole, split):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=2e-4)


# ---------------------------------------------------------------------------
# the way INTO the expert buffer is a gather (PR 46): the sort's layout equals
# the scatter form's element for element, and no scatter is left in the
# dispatch, forward or backward
# ---------------------------------------------------------------------------

#: (T, k, n, block_m): a decode step and a prefill chunk of SmallThinker
#: (6 of 64) and of OLMoE (8 of 64) at the tile heights ``moe_tile_rows``
#: gives them, Mixtral's 2 of 8, and two where T*k is no multiple of block_m
SORT_SHAPES = {
    "smallthinker_decode": (48, 6, 64, 16),
    "smallthinker_chunk512": (512, 6, 64, 128),
    "olmoe_decode": (48, 8, 64, 16),
    "olmoe_chunk128": (128, 8, 64, 32),
    "olmoe_rows2048": (2048, 8, 64, 128),
    "mixtral_rows256": (256, 2, 8, 128),
    "ragged_37x2": (37, 2, 4, 8),
    "ragged_50x3": (50, 3, 8, 16),
}
ROUTINGS = ("random", "one_expert", "empty_experts")


def _route(routing, T, k, n, seed=0):
    rng = np.random.default_rng(seed)
    if routing == "one_expert":         # every (token, choice) on one expert
        e = np.full((T, k), n - 2)
    elif routing == "empty_experts":    # the first, the last and one inside
        allowed = np.setdiff1d(np.arange(n), [0, n // 2, n - 1])
        e = rng.choice(allowed, (T, k))
    else:                               # k distinct experts a token
        e = np.argsort(rng.random((T, n)), axis=1)[:, :k]
    return jnp.asarray(e.astype(np.int32))


def _parent_sort(expert_idx, num_experts, block_m):
    """``sort_tokens_by_expert`` as it stood before PR 46 (bincount, argsort
    and a scatter of the destinations): the plain reference."""
    T, k = expert_idx.shape
    Tk = T * k
    e_flat = expert_idx.reshape(-1)
    counts = jnp.bincount(e_flat, length=num_experts)
    aligned = ((counts + block_m - 1) // block_m) * block_m
    starts = jnp.concatenate([jnp.zeros((1,), jnp.int32),
                              jnp.cumsum(aligned)[:-1].astype(jnp.int32)])
    cum_counts = jnp.concatenate([jnp.zeros((1,), jnp.int32),
                                  jnp.cumsum(counts)[:-1].astype(jnp.int32)])
    order = jnp.argsort(e_flat, stable=True)
    sorted_e = e_flat[order]
    rank = jnp.arange(Tk, dtype=jnp.int32) - cum_counts[sorted_e]
    dst = jnp.zeros((Tk,), jnp.int32).at[order].set(starts[sorted_e] + rank)
    Tp = ((Tk + block_m - 1) // block_m) * block_m + num_experts * block_m
    tile_starts = jnp.arange(Tp // block_m, dtype=jnp.int32) * block_m
    n_tiles = (jnp.sum(aligned) // block_m).astype(jnp.int32)
    tile_starts = jnp.minimum(tile_starts, (n_tiles - 1) * block_m)
    tile_expert = jnp.clip(
        jnp.searchsorted(starts, tile_starts, side="right") - 1,
        0, num_experts - 1).astype(jnp.int32)
    return dst, tile_expert, Tp, n_tiles


def _parent_dispatch_combine(x2d, gates, experts, num_experts, k, block_m,
                             gemm):
    """``dropless_dispatch_combine`` as it stood before PR 46."""
    import deepspeed_tpu.ops.pallas.grouped_matmul as gm

    T, E = x2d.shape
    dst, te, Tp, nt = _parent_sort(experts.reshape(T, k), num_experts,
                                   block_m)
    buf = jnp.zeros((Tp, E), x2d.dtype).at[dst].set(
        jnp.repeat(x2d, k, axis=0))
    out_buf = gemm(buf, gm.ExpertSort(dst=dst, tile_expert=te, Tp=Tp,
                                      n_tiles=nt, src=None))
    return jnp.einsum("tk,tke->te", gates.reshape(T, k).astype(x2d.dtype),
                      out_buf[dst].reshape(T, k, -1))


@pytest.mark.parametrize("routing", ROUTINGS)
@pytest.mark.parametrize("name", list(SORT_SHAPES))
def test_sort_layout_equals_the_scatter_forms(name, routing):
    """``dst``, ``tile_expert``, ``n_tiles`` and ``Tp`` element for element
    (stable order inside an expert); ``src`` is ``dst``'s inverse on the
    live rows and T * k on every other."""
    import deepspeed_tpu.ops.pallas.grouped_matmul as gm

    T, k, n, bm = SORT_SHAPES[name]
    eidx = _route(routing, T, k, n)
    srt = jax.jit(lambda e: gm.sort_tokens_by_expert(e, n, bm))(eidx)
    dst, te, Tp, nt = jax.jit(lambda e: _parent_sort(e, n, bm))(eidx)
    assert int(srt.Tp) == int(Tp)
    np.testing.assert_array_equal(np.asarray(srt.dst), np.asarray(dst))
    np.testing.assert_array_equal(np.asarray(srt.tile_expert), np.asarray(te))
    assert int(srt.n_tiles) == int(nt)
    want_src = np.full(int(Tp), T * k, np.int32)
    want_src[np.asarray(dst)] = np.arange(T * k)
    np.testing.assert_array_equal(np.asarray(srt.src), want_src)


FORMS = {"dense": 1 << 40, "gather": 0}   # DENSE_FILL_MAX_ELEMS that picks it


def _bits(a):
    return np.asarray(a).view(np.uint16)


@pytest.mark.parametrize("form", list(FORMS))
@pytest.mark.parametrize("routing", ROUTINGS)
@pytest.mark.parametrize("name", list(SORT_SHAPES))
def test_gathered_buffer_equals_the_scattered_one_bit_for_bit(
        monkeypatch, name, routing, form):
    """Both forms of the fill (the one-hot matmul a step of few tokens
    takes, the row gather of many) against ``zeros.at[dst].set(repeat(x,
    k))`` in bf16, padding rows zero; the gather also with a NaN row and an
    inf in it (the matmul keeps those out: next test)."""
    import deepspeed_tpu.ops.pallas.grouped_matmul as gm

    monkeypatch.setattr(gm, "DENSE_FILL_MAX_ELEMS", FORMS[form])
    T, k, n, bm = SORT_SHAPES[name]
    E = 128
    eidx = _route(routing, T, k, n, seed=1)
    x = jnp.asarray(np.random.default_rng(2).standard_normal((T, E)),
                    jnp.bfloat16)
    if form == "gather":
        x = x.at[0].set(jnp.nan).at[T - 1, 3].set(jnp.inf).at[1, 2].set(-0.0)

    def new(x, e):
        srt = gm.sort_tokens_by_expert(e, n, bm)
        return gm.gather_expert_rows(x, srt.src, srt.dst), srt.dst

    def parent(x, e):
        dst, _, Tp, _ = _parent_sort(e, n, bm)
        return jnp.zeros((Tp, E), x.dtype).at[dst].set(
            jnp.repeat(x, k, axis=0))

    buf, dst = jax.jit(new)(x, eidx)
    want = jax.jit(parent)(x, eidx)
    assert buf.dtype == want.dtype and buf.shape == want.shape
    np.testing.assert_array_equal(_bits(buf), _bits(want))
    padding = np.setdiff1d(np.arange(buf.shape[0]), np.asarray(dst))
    assert padding.size == buf.shape[0] - T * k
    assert not _bits(buf)[padding].any()


def test_dense_fill_keeps_a_non_finite_token_to_its_own_rows():
    """``0 * inf`` is NaN: in a one-hot matmul one token's inf or NaN would
    reach every row of the buffer — every sequence of the step. The dense
    fill keeps it out: that token's rows read NaN, every other row is the
    scatter form's bit for bit, padding rows stay zero."""
    import deepspeed_tpu.ops.pallas.grouped_matmul as gm

    T, k, n, bm = SORT_SHAPES["smallthinker_decode"]
    E = 128
    assert T * E <= gm.DENSE_FILL_MAX_ELEMS
    eidx = _route("random", T, k, n, seed=3)
    x = jnp.asarray(np.random.default_rng(4).standard_normal((T, E)),
                    jnp.bfloat16)
    x = x.at[5].set(jnp.nan).at[17, 9].set(jnp.inf).at[30, 0].set(-jnp.inf)
    srt = jax.jit(lambda e: gm.sort_tokens_by_expert(e, n, bm))(eidx)
    buf = np.asarray(jax.jit(gm.gather_expert_rows)(x, srt.src, srt.dst)
                     .astype(jnp.float32))
    want = np.asarray(jnp.zeros((int(srt.Tp), E), x.dtype).at[srt.dst].set(
        jnp.repeat(x, k, axis=0)).astype(jnp.float32))
    tok = np.asarray(srt.src) // k
    bad = np.isin(tok, [5, 17, 30])
    assert bad.sum() == 3 * k and np.isnan(buf[bad]).all()
    np.testing.assert_array_equal(buf[~bad], want[~bad])


def _scatters(jaxpr):
    """Names of the scatter primitives anywhere inside a jaxpr (sub-jaxprs
    of pjit, custom_vjp, scan, cond and pallas_call included)."""
    found = []
    for eqn in jaxpr.eqns:
        if eqn.primitive.name.startswith("scatter"):
            found.append(eqn.primitive.name)
        for v in eqn.params.values():
            for sub in (v if isinstance(v, (list, tuple)) else [v]):
                sub = getattr(sub, "jaxpr", sub)
                if hasattr(sub, "eqns"):
                    found += _scatters(sub)
    return found


def _routed_layer(dispatch_combine, n, k, bm):
    import deepspeed_tpu.ops.pallas.grouped_matmul as gm

    def layer(x, gates, wu, wd, eidx):
        def gemm(buf, srt):
            h = jnp.sin(gm.grouped_matmul(buf, wu, srt.tile_expert, bm))
            return gm.grouped_matmul(h, wd, srt.tile_expert, bm)
        return dispatch_combine(x, gates, eidx, n, k, bm, gemm)
    return layer


@pytest.mark.parametrize("form", list(FORMS))
@pytest.mark.parametrize("name", ["ragged_37x2", "ragged_50x3",
                                  "smallthinker_decode"])
def test_dispatch_holds_no_scatter_forward_or_backward(monkeypatch, name,
                                                       form):
    """The jaxpr of ``dropless_dispatch_combine`` (the sort, the fill, the
    grouped GEMMs, the combine) has no ``scatter*`` primitive, and neither
    has its gradient: the fill's backward is the gather the scatter's
    transpose was, not the gather's own transpose. The parent's form, by
    the same walk, holds them — the walk can see one."""
    import deepspeed_tpu.ops.pallas.grouped_matmul as gm
    from deepspeed_tpu.moe.layer import dropless_dispatch_combine

    monkeypatch.setattr(gm, "DENSE_FILL_MAX_ELEMS", FORMS[form])
    T, k, n, bm = SORT_SHAPES[name]
    E, F = 32, 48
    eidx = _route("random", T, k, n)
    x = jnp.ones((T, E), jnp.float32)
    gates = jnp.ones((T, k), jnp.float32) / k
    wu, wd = jnp.ones((n, E, F), jnp.float32), jnp.ones((n, F, E), jnp.float32)
    new = _routed_layer(dropless_dispatch_combine, n, k, bm)
    old = _routed_layer(_parent_dispatch_combine, n, k, bm)
    loss = lambda f: (lambda *a: jnp.sum(f(*a)))
    assert _scatters(jax.make_jaxpr(new)(x, gates, wu, wd, eidx).jaxpr) == []
    assert _scatters(jax.make_jaxpr(jax.grad(loss(new), argnums=(0, 1, 2, 3)))(
        x, gates, wu, wd, eidx).jaxpr) == []
    assert "scatter" in _scatters(
        jax.make_jaxpr(old)(x, gates, wu, wd, eidx).jaxpr)
    assert "scatter-add" in _scatters(jax.make_jaxpr(
        jax.grad(loss(old)))(x, gates, wu, wd, eidx).jaxpr)


@pytest.mark.parametrize("form", list(FORMS))
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("name, routing", [
    ("ragged_37x2", "random"), ("ragged_50x3", "empty_experts"),
    ("ragged_50x3", "one_expert"), ("smallthinker_decode", "random")])
def test_dispatch_output_and_grads_equal_the_scatter_forms(
        monkeypatch, name, routing, dtype, form):
    """Output and the gradients w.r.t. tokens, gates and both expert
    matrices equal the parent's form's exactly: the buffer is the same, so
    the GEMMs and the combine are, and the fill's backward sums the same k
    rows in the same dtype."""
    import deepspeed_tpu.ops.pallas.grouped_matmul as gm
    from deepspeed_tpu.moe.layer import dropless_dispatch_combine

    monkeypatch.setattr(gm, "DENSE_FILL_MAX_ELEMS", FORMS[form])
    T, k, n, bm = SORT_SHAPES[name]
    E, F = 32, 48
    rng = np.random.default_rng(5)
    eidx = _route(routing, T, k, n, seed=6)
    x = jnp.asarray(rng.standard_normal((T, E)), dtype)
    gates = jnp.asarray(rng.random((T, k)), jnp.float32)
    wu = jnp.asarray(rng.standard_normal((n, E, F)) / E ** 0.5, dtype)
    wd = jnp.asarray(rng.standard_normal((n, F, E)) / F ** 0.5, dtype)
    c = jnp.asarray(rng.standard_normal((T, E)), jnp.float32)
    outs = []
    for form in (dropless_dispatch_combine, _parent_dispatch_combine):
        layer = _routed_layer(form, n, k, bm)
        loss = lambda *a: jnp.sum(layer(*a, eidx).astype(jnp.float32) * c)
        outs.append((jax.jit(layer)(x, gates, wu, wd, eidx),
                     *jax.jit(jax.grad(loss, argnums=(0, 1, 2, 3)))(
                         x, gates, wu, wd)))
    for got, want in zip(*outs):
        assert got.dtype == want.dtype
        np.testing.assert_array_equal(np.asarray(got, np.float32),
                                      np.asarray(want, np.float32))
    assert float(jnp.abs(outs[0][1]).sum()) > 0


# ---------------------------------------------------------------------------
# the sort takes a liveness mask (PR 51): a token with no request counts for
# no expert, its rows reach no buffer row, and nothing of a buffer row the
# kernel never wrote reaches the output
# ---------------------------------------------------------------------------

#: which of the T tokens carry a request
MASKS = {
    "none_live": lambda T: np.zeros(T, bool),
    "one_live": lambda T: np.arange(T) == T // 3,
    "all_but_one": lambda T: np.arange(T) != T // 3,
    "all_live": lambda T: np.ones(T, bool),
}


@pytest.mark.parametrize("form", list(FORMS))
@pytest.mark.parametrize("block_m", [16, 128])
@pytest.mark.parametrize("mask", list(MASKS))
def test_masked_sort_is_the_sort_of_the_live_tokens_alone(monkeypatch, mask,
                                                          block_m, form):
    """A decode step's 48 rows, 6 of 64, dead rows ALL on the same experts
    (token 0's, as an empty slot's are). (a) The tiles, ``n_tiles`` and
    ``src`` are those of the sort of the live tokens alone, a masked
    entry's ``dst`` is ``Tp``, and with every token live the masked sort is
    ``live=None``'s to the element. (b) The buffer's live rows are the
    unmasked buffer's rows of the same entries bit for bit, every other row
    zero. (c) With the rows of ``out_buf`` at and past ``n_tiles * block_m``
    — what the kernel never writes — set to NaN, the layer's output is
    finite, exactly zero for a masked token and bitwise the unmasked
    layer's for a live one."""
    import deepspeed_tpu.ops.pallas.grouped_matmul as gm
    from deepspeed_tpu.moe.layer import dropless_dispatch_combine

    monkeypatch.setattr(gm, "DENSE_FILL_MAX_ELEMS", FORMS[form])
    T, k, n, bm = 48, 6, 64, block_m
    Tk = T * k
    live = MASKS[mask](T)
    idx = np.flatnonzero(live)
    eidx = np.array(_route("random", T, k, n, seed=7))
    eidx[~live] = eidx[0] if not live[0] else eidx[T // 3]
    eidx = jnp.asarray(eidx)
    sort = jax.jit(lambda e, lv=None: gm.sort_tokens_by_expert(e, n, bm, lv))
    got, plain = sort(eidx, jnp.asarray(live)), sort(eidx)
    Tp, nt = int(got.Tp), int(got.n_tiles)
    dst, src = np.asarray(got.dst).reshape(T, k), np.asarray(got.src)

    # (a)
    if mask == "all_live":
        for a, b in zip(got, plain):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    assert (dst[~live] == Tp).all() and (src[nt * bm:] == Tk).all()
    te = np.asarray(got.tile_expert)
    assert (np.diff(te) >= 0).all() and (te[nt:] == te[max(nt - 1, 0)]).all()
    if idx.size:
        sub = sort(eidx[idx])
        assert nt == int(sub.n_tiles) > 0
        np.testing.assert_array_equal(te[:nt],
                                      np.asarray(sub.tile_expert)[:nt])
        np.testing.assert_array_equal(dst[live],
                                      np.asarray(sub.dst).reshape(-1, k))
        s = np.asarray(sub.src)[:nt * bm]       # entries of the SUBSET
        held = s < idx.size * k
        want = np.where(held, idx[np.minimum(s // k, idx.size - 1)] * k
                        + s % k, Tk)
        np.testing.assert_array_equal(src[:nt * bm], want)
    else:
        assert nt == 0 and (te == 0).all()
    counts = np.bincount(te[np.arange(Tp) // bm][src < Tk], minlength=n)
    np.testing.assert_array_equal(
        counts, np.bincount(np.asarray(eidx)[live].reshape(-1), minlength=n))

    # (b)
    E, F = 128, 48
    rng = np.random.default_rng(8)
    x = jnp.asarray(rng.standard_normal((T, E)), jnp.bfloat16)
    fill = jax.jit(gm.gather_expert_rows)
    buf, buf_plain = fill(x, got.src, got.dst), fill(x, plain.src, plain.dst)
    assert buf.shape == buf_plain.shape == (Tp, E)
    np.testing.assert_array_equal(
        _bits(buf)[dst[live]],
        _bits(buf_plain)[np.asarray(plain.dst).reshape(T, k)[live]])
    assert not _bits(buf)[np.setdiff1d(np.arange(Tp), dst[live])].any()

    # (c)
    gates = jnp.asarray(rng.random((T, k)), jnp.float32)
    wu = jnp.asarray(rng.standard_normal((n, E, F)) / E ** 0.5, jnp.bfloat16)
    wd = jnp.asarray(rng.standard_normal((n, F, E)) / F ** 0.5, jnp.bfloat16)

    def layer(lv, poison):
        def gemm(b, srt):
            mm = lambda a, w: gm.grouped_matmul_layer(
                a, w, srt.tile_expert, srt.n_tiles, bm)
            out = mm(jax.nn.silu(mm(b, wu)), wd)
            unwritten = jnp.arange(out.shape[0]) >= srt.n_tiles * bm
            return jnp.where(unwritten[:, None] & poison, jnp.nan, out)
        return dropless_dispatch_combine(x, gates, eidx, n, k, bm, gemm,
                                         live=lv)

    out = np.asarray(jax.jit(lambda: layer(jnp.asarray(live), True))()
                     .astype(jnp.float32))
    ref = np.asarray(jax.jit(lambda: layer(None, False))()
                     .astype(jnp.float32))
    assert np.isfinite(out).all()
    assert not out[~live].any() and (np.signbit(out[~live]) == 0).all()
    np.testing.assert_array_equal(out[live], ref[live])
    if idx.size:
        assert np.abs(out[live]).sum() > 0
