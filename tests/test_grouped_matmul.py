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
