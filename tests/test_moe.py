"""MoE gating + layer semantics (role of reference tests/unit/moe/test_moe.py)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

pytestmark = pytest.mark.slow  # multi-minute: engine jit compiles

from deepspeed_tpu.moe import (
    MoE,
    compute_capacity,
    top1gating,
    top2gating,
    topkgating,
)


def _logits(G=2, S=16, n=4, seed=0):
    return jnp.asarray(np.random.default_rng(seed).standard_normal((G, S, n)),
                       jnp.float32)


def test_topk_dispatch_combine_consistency():
    """dispatch is the support of combine; each (token, slot) used once."""
    out = topkgating(_logits(), k=2, capacity_factor=2.0)
    # combine nonzero only where dispatch is 1
    assert np.all((np.asarray(out.combine) > 0) <= (np.asarray(out.dispatch) > 0))
    # each expert slot holds at most one token
    slot_usage = np.asarray(out.dispatch).sum(axis=1)  # [G, n, cap]
    assert slot_usage.max() <= 1.0 + 1e-6
    # each token uses at most k slots
    tok_usage = np.asarray(out.dispatch).sum(axis=(2, 3))  # [G, S]
    assert tok_usage.max() <= 2 + 1e-6


def test_top1_routes_to_argmax():
    logits = _logits()
    out = top1gating(logits, capacity_factor=4.0)
    want = np.argmax(np.asarray(logits), axis=-1)          # [G,S]
    got_expert = np.asarray(out.dispatch).sum(axis=3).argmax(axis=-1)  # [G,S]
    routed = np.asarray(out.dispatch).sum(axis=(2, 3)) > 0
    assert routed.all()  # capacity 4x: nothing dropped
    np.testing.assert_array_equal(got_expert[routed], want[routed])


def test_capacity_drops_overflow():
    """All tokens prefer one expert; capacity bounds how many get through."""
    G, S, n = 1, 16, 4
    logits = jnp.zeros((G, S, n)).at[..., 0].set(10.0)
    out = top1gating(logits, capacity_factor=0.5, min_capacity=2)
    cap = compute_capacity(S, n, 1, 0.5, 2)
    kept = np.asarray(out.dispatch)[:, :, 0, :].sum()
    assert kept == cap  # exactly capacity tokens kept on expert 0
    # dropped tokens have zero combine weight everywhere
    tok_gate = np.asarray(out.combine).sum(axis=(2, 3))
    assert (tok_gate > 0).sum() == cap


def test_capacity_divergence_v1_drops_v2_routes_all():
    """Pin the documented training/v1 vs serving/v2 boundary: past expert
    capacity, the capacity path (drop_tokens=True — training and the v1
    engine) DROPS overflow tokens while the FastGen v2 forward routes
    every token (drop_tokens=False, inference/engine_v2.py ``ffn``).

    Same params, same input, capacity binding → kept tokens agree exactly,
    overflow tokens get a zero FFN delta under v1 and a real one under v2.
    """
    # adversarial routing: every token prefers expert 0, so a tiny eval
    # capacity is guaranteed to bind
    H, S, n = 8, 16, 4
    drop = MoE(hidden_size=H, num_experts=n, ffn_size=16, k=1,
               eval_capacity_factor=0.5, min_capacity=2, drop_tokens=True,
               aux_loss_weight=0.0, z_loss_weight=0.0)
    nodrop = MoE(hidden_size=H, num_experts=n, ffn_size=16, k=1,
                 eval_capacity_factor=0.5, min_capacity=2, drop_tokens=False,
                 aux_loss_weight=0.0, z_loss_weight=0.0)
    # positive tokens + a wg column of +10 on expert 0 → every token's
    # expert-0 logit is large positive → all S tokens route to expert 0
    x = jnp.asarray(np.abs(np.random.default_rng(0).standard_normal(
        (1, S, H))) + 0.1, jnp.float32)
    params = drop.init(jax.random.PRNGKey(0), x)["params"]
    wg_box = params["gate"]["wg"]
    wg = np.zeros(wg_box.value.shape, np.float32)
    wg[:, 0] = 10.0
    params["gate"]["wg"] = wg_box.replace_boxed(jnp.asarray(wg))

    out_drop, _ = drop.apply({"params": params}, x, True, mutable=["losses"])
    out_nodrop, _ = nodrop.apply({"params": params}, x, True,
                                 mutable=["losses"])
    cap = compute_capacity(S, n, 1, 0.5, 2)
    d, nd = np.asarray(out_drop[0]), np.asarray(out_nodrop[0])
    dropped = np.all(d == 0.0, axis=-1)          # zero FFN delta = dropped
    assert dropped.sum() == S - cap              # capacity bound drops
    # v2 routes the overflow tokens v1 dropped
    assert np.all(np.any(nd[dropped] != 0.0, axis=-1))
    # on kept tokens the two paths agree exactly (same expert, same gate)
    np.testing.assert_allclose(d[~dropped], nd[~dropped], rtol=1e-6)


def test_top2_gates_normalized():
    out = top2gating(_logits(), capacity_factor=4.0)
    tok_gate = np.asarray(out.combine).sum(axis=(2, 3))    # [G,S]
    np.testing.assert_allclose(tok_gate, 1.0, atol=1e-5)


def test_aux_loss_uniform_routing_is_one():
    """Perfectly uniform router → aux loss == 1 (GShard normalization)."""
    G, S, n = 2, 32, 4
    logits = jnp.zeros((G, S, n))  # uniform probs; top-k ties broken by index
    out = topkgating(logits, k=1, capacity_factor=4.0)
    # me = 1/n each; ce concentrates on expert 0 due to ties — use probs term
    me = 1.0 / n
    ce = np.asarray(out.exp_counts) / (G * S)
    np.testing.assert_allclose(float(out.aux_loss), n * np.sum(me * ce), rtol=1e-5)


def test_moe_layer_forward_and_aux_loss():
    m = MoE(hidden_size=16, num_experts=4, ffn_size=32, k=2,
            capacity_factor=2.0, eval_capacity_factor=2.0)
    x = jnp.asarray(np.random.default_rng(0).standard_normal((2, 8, 16)),
                    jnp.float32)
    vars_ = m.init(jax.random.PRNGKey(0), x)
    out, state = m.apply({"params": vars_["params"]}, x, mutable=["losses"])
    assert out.shape == x.shape
    assert np.isfinite(np.asarray(out)).all()
    (loss_leaf,) = jax.tree.leaves(state["losses"])
    assert float(loss_leaf) > 0


def test_moe_layer_grads_flow_to_router():
    m = MoE(hidden_size=8, num_experts=2, ffn_size=16, k=1,
            capacity_factor=2.0, eval_capacity_factor=2.0)
    x = jnp.asarray(np.random.default_rng(1).standard_normal((1, 8, 8)),
                    jnp.float32)
    params = m.init(jax.random.PRNGKey(0), x)["params"]

    def loss(p):
        out, state = m.apply({"params": p}, x, mutable=["losses"])
        return jnp.sum(out ** 2) + sum(jnp.sum(l) for l in
                                       jax.tree.leaves(state["losses"]))

    from deepspeed_tpu.runtime.zero.planner import unbox_params

    g = unbox_params(jax.grad(loss)(params))
    gate_g = np.asarray(g["gate"]["wg"])
    assert np.abs(gate_g).sum() > 0  # router receives gradient


# ---------------------------------------------------------------------------
# dropless (megablocks-style) path: Pallas grouped GEMM
# ---------------------------------------------------------------------------

def test_grouped_matmul_matches_per_expert_loop():
    from deepspeed_tpu.ops.pallas.grouped_matmul import (
        grouped_matmul, sort_tokens_by_expert)

    rng = np.random.default_rng(0)
    T, k, n, E, F, bm = 37, 2, 4, 64, 96, 8
    eidx = jnp.asarray(rng.integers(0, n, (T, k)).astype(np.int32))
    x = rng.standard_normal((T, E)).astype(np.float32)
    w = rng.standard_normal((n, E, F)).astype(np.float32)

    def run(x, w):
        srt = sort_tokens_by_expert(eidx, n, bm)
        buf = jnp.zeros((srt.Tp, E), x.dtype).at[srt.dst].set(
            jnp.repeat(x, k, axis=0))
        return grouped_matmul(buf, w, srt.tile_expert, bm)[srt.dst] \
            .reshape(T, k, F)

    out = np.asarray(jax.jit(run)(jnp.asarray(x), jnp.asarray(w)))
    for t in range(T):
        for c in range(k):
            np.testing.assert_allclose(out[t, c], x[t] @ w[int(eidx[t, c])],
                                       atol=2e-4)


def test_grouped_matmul_grads():
    from deepspeed_tpu.ops.pallas.grouped_matmul import (
        grouped_matmul, sort_tokens_by_expert)

    rng = np.random.default_rng(1)
    T, k, n, E, F, bm = 16, 1, 2, 16, 24, 8
    eidx = jnp.asarray(rng.integers(0, n, (T, k)).astype(np.int32))
    x = jnp.asarray(rng.standard_normal((T, E)).astype(np.float32))
    w = jnp.asarray(rng.standard_normal((n, E, F)).astype(np.float32))
    srt = jax.jit(lambda e: sort_tokens_by_expert(e, n, bm))(eidx)

    def loss(x, w):
        buf = jnp.zeros((srt.Tp, E), x.dtype).at[srt.dst].set(
            jnp.repeat(x, k, axis=0))
        return jnp.sum(jnp.sin(
            grouped_matmul(buf, w, srt.tile_expert, bm)[srt.dst]))

    def loss_ref(x, w):
        rows = jnp.einsum("te,tef->tf", x, w[eidx[:, 0]])
        return jnp.sum(jnp.sin(rows))

    gx, gw = jax.jit(jax.grad(loss, argnums=(0, 1)))(x, w)
    rx, rw = jax.grad(loss_ref, argnums=(0, 1))(x, w)
    np.testing.assert_allclose(np.asarray(gx), np.asarray(rx), atol=2e-4)
    np.testing.assert_allclose(np.asarray(gw), np.asarray(rw), atol=2e-4)


def test_moe_dropless_matches_dense_reference():
    """Dropless MoE forward == explicit gather/loop over each token's
    chosen experts (no capacity, nothing dropped)."""
    m = MoE(hidden_size=16, num_experts=4, ffn_size=32, k=2,
            dropless=True, dropless_block_m=8)
    x = jnp.asarray(np.random.default_rng(2).standard_normal((2, 8, 16)),
                    jnp.float32)
    params = m.init(jax.random.PRNGKey(0), x)["params"]
    out = m.apply({"params": params}, x)

    from deepspeed_tpu.moe.sharded_moe import topk_dropless_gating
    from deepspeed_tpu.runtime.zero.planner import unbox_params

    p = unbox_params(params)
    logits = jnp.einsum("gse,en->gsn", x, p["gate"]["wg"])
    g = topk_dropless_gating(logits, 2)
    wg_, wu_, wd_ = (p["experts"]["w_gate"], p["experts"]["w_up"],
                     p["experts"]["w_down"])
    ref = np.zeros_like(np.asarray(x))
    for b in range(2):
        for s in range(8):
            for c in range(2):
                e = int(g.experts[b, s, c])
                h = jax.nn.silu(x[b, s] @ wg_[e]) * (x[b, s] @ wu_[e])
                ref[b, s] += float(g.gates[b, s, c]) * np.asarray(h @ wd_[e])
    np.testing.assert_allclose(np.asarray(out), ref, atol=2e-4)


def test_moe_dropless_grads_flow():
    m = MoE(hidden_size=16, num_experts=2, ffn_size=16, k=1,
            dropless=True, dropless_block_m=8)
    x = jnp.asarray(np.random.default_rng(3).standard_normal((1, 8, 16)),
                    jnp.float32)
    params = m.init(jax.random.PRNGKey(1), x)["params"]

    def loss(p):
        out, state = m.apply({"params": p}, x, mutable=["losses"])
        return jnp.sum(out ** 2) + sum(jnp.sum(l) for l in
                                       jax.tree.leaves(state["losses"]))

    from deepspeed_tpu.runtime.zero.planner import unbox_params

    g = unbox_params(jax.jit(jax.grad(loss))(params))
    assert np.abs(np.asarray(g["gate"]["wg"])).sum() > 0
    assert np.abs(np.asarray(g["experts"]["w_up"])).sum() > 0


@pytest.mark.parametrize("max_elems", [1 << 40, 0], ids=["dense", "gather"])
def test_moe_dropless_layer_holds_no_scatter_and_keeps_its_grads(
        monkeypatch, max_elems):
    """The dropless training layer in either form of the fill (a one-hot
    matmul for few tokens, a row gather for many): no ``scatter*`` primitive
    in its forward, one in its gradient (the router's), and output and
    gradients those of the per-token formulation."""
    import deepspeed_tpu.ops.pallas.grouped_matmul as gm
    from deepspeed_tpu.moe.sharded_moe import topk_dropless_gating
    from deepspeed_tpu.runtime.zero.planner import unbox_params
    from tests.test_grouped_matmul import _scatters as scatters

    monkeypatch.setattr(gm, "DENSE_FILL_MAX_ELEMS", max_elems)
    m = MoE(hidden_size=16, num_experts=4, ffn_size=32, k=2,
            dropless=True, dropless_block_m=8, aux_loss_weight=0.0,
            z_loss_weight=0.0)
    x = jnp.asarray(np.random.default_rng(7).standard_normal((2, 8, 16)),
                    jnp.float32)
    params = unbox_params(m.init(jax.random.PRNGKey(0), x)["params"])

    def loss(p, x):
        return jnp.sum(jnp.sin(m.apply({"params": p}, x)))

    def loss_ref(p, x):
        g = topk_dropless_gating(
            jnp.einsum("gse,en->gsn", x, p["gate"]["wg"]), 2)
        ex = p["experts"]
        h = jax.nn.silu(jnp.einsum("gse,gskef->gskf", x,
                                   ex["w_gate"][g.experts])) \
            * jnp.einsum("gse,gskef->gskf", x, ex["w_up"][g.experts])
        y = jnp.einsum("gskf,gskfe->gske", h, ex["w_down"][g.experts])
        return jnp.sum(jnp.sin(jnp.einsum("gsk,gske->gse", g.gates, y)))

    # (the gradient of the ROUTER's top-k is a scatter-add onto [tokens, n]
    # and stays: tests/test_grouped_matmul.py holds the dispatch's own
    # gradient to none)
    assert scatters(jax.make_jaxpr(loss)(params, x).jaxpr) == []
    assert scatters(jax.make_jaxpr(jax.grad(loss, argnums=(0, 1)))(
        params, x).jaxpr) == ["scatter-add"]
    np.testing.assert_allclose(float(loss(params, x)),
                               float(loss_ref(params, x)), rtol=1e-5)
    got = jax.jit(jax.grad(loss, argnums=(0, 1)))(params, x)
    want = jax.grad(loss_ref, argnums=(0, 1))(params, x)
    for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=2e-4)
