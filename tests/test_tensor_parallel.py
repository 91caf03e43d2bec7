"""Ring collective-matmul tensor parallelism (parallel/tensor.py).

Parity of the latency-hiding primitives against plain einsum references on
CPU meshes (TP in {1, 2, 4}; bf16 / int8 / fp8 weights), the fallback
guards, the seq x tensor vocab-parallel cross entropy, and the two hot-path
integrations: engine_v2 token parity with ``tp_overlap`` on/off and the
training model's ring row-projections (values AND grads).
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax import shard_map
from jax.flatten_util import ravel_pytree
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from deepspeed_tpu.ops.pallas.quant_matmul import (QuantLinear,
                                                   dequantize_weight,
                                                   quantize_weight)
from deepspeed_tpu.parallel import tensor as ring


def make_mesh(n):
    return Mesh(np.array(jax.devices()[:n]), ("tensor",))


def quantize_sharded(w, mesh, bits, kind):
    """Per-shard quantization (the engine_v2 convention: group boundaries
    live within shards; QuantLinear aux shapes are LOCAL)."""
    if mesh.shape["tensor"] == 1:
        return quantize_weight(w, bits=bits)
    ws = P(None, "tensor") if kind == "col" else P("tensor", None)
    return jax.jit(shard_map(lambda wl: quantize_weight(wl, bits=bits),
                             mesh=mesh, in_specs=(ws,), out_specs=ws,
                             check_vma=False))(w)


def dequant_sharded(qw, mesh, kind):
    if mesh.shape["tensor"] == 1:
        return dequantize_weight(qw)
    ws = P(None, "tensor") if kind == "col" else P("tensor", None)
    return jax.jit(shard_map(dequantize_weight, mesh=mesh, in_specs=(ws,),
                             out_specs=ws, check_vma=False))(qw)


def _xw(M=32, K=64, N=256, dtype=jnp.float32, seed=0):
    k1, k2 = jax.random.split(jax.random.PRNGKey(seed))
    x = jax.random.normal(k1, (M, K), dtype)
    w = (jax.random.normal(k2, (K, N), jnp.float32) / K ** 0.5)
    return x, w


@pytest.mark.parametrize("n", [1, 2, 4])
@pytest.mark.parametrize("wq", ["bf16", "int8", "fp8"])
def test_allgather_matmul_parity(n, wq):
    mesh = make_mesh(n)
    if wq == "bf16":
        x, w = _xw(dtype=jnp.bfloat16)
        wa = w.astype(jnp.bfloat16)
        got = ring.allgather_matmul(x, wa, mesh)
        ref = jnp.dot(x, wa, preferred_element_type=jnp.float32)
        np.testing.assert_allclose(np.asarray(got, np.float32),
                                   np.asarray(ref), rtol=2e-2, atol=2e-2)
    else:
        x, w = _xw(dtype=jnp.float32)
        qw = quantize_sharded(w, mesh, 8 if wq == "int8" else "fp8", "col")
        got = ring.allgather_matmul(x, qw, mesh)
        ref = x @ dequant_sharded(qw, mesh, "col").astype(jnp.float32)
        np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                                   rtol=1e-3, atol=1e-3)


@pytest.mark.parametrize("n", [1, 2, 4])
@pytest.mark.parametrize("wq", ["bf16", "int8", "fp8"])
def test_matmul_reduce_scatter_parity(n, wq):
    mesh = make_mesh(n)
    if wq == "bf16":
        x, w = _xw(dtype=jnp.bfloat16)
        wa = w.astype(jnp.bfloat16)
        got = ring.matmul_reduce_scatter(x, wa, mesh)
        ref = jnp.dot(x, wa, preferred_element_type=jnp.float32)
        np.testing.assert_allclose(np.asarray(got, np.float32),
                                   np.asarray(ref), rtol=2e-2, atol=2e-2)
    else:
        x, w = _xw(dtype=jnp.float32)
        qw = quantize_sharded(w, mesh, 8 if wq == "int8" else "fp8", "row")
        got = ring.matmul_reduce_scatter(x, qw, mesh)
        ref = x @ dequant_sharded(qw, mesh, "row").astype(jnp.float32)
        np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                                   rtol=1e-3, atol=1e-3)


def test_fused_multi_weight_single_ring():
    """One ring feeds several projections (fused QKV): tuple in, tuple
    out, each output matching its own einsum."""
    mesh = make_mesh(4)
    x, w1 = _xw()
    _, w2 = _xw(N=128, seed=3)
    ya, yb = ring.allgather_matmul(x, (w1, w2), mesh)
    np.testing.assert_allclose(np.asarray(ya), np.asarray(x @ w1),
                               rtol=2e-5, atol=2e-5)
    np.testing.assert_allclose(np.asarray(yb), np.asarray(x @ w2),
                               rtol=2e-5, atol=2e-5)


def test_uneven_shapes_raise_clear_valueerror():
    """The satellite contract: a non-dividing dim is a clear ValueError at
    trace time, never an XLA shape error."""
    mesh = make_mesh(2)
    x, w = _xw()
    with pytest.raises(ValueError, match="not divisible"):
        ring.allgather_matmul(jnp.ones((33, 64)), w, mesh)
    with pytest.raises(ValueError, match="not divisible"):
        ring.allgather_matmul(x, jnp.ones((64, 129)), mesh)
    with pytest.raises(ValueError, match="not divisible"):
        ring.matmul_reduce_scatter(jnp.ones((32, 63)), jnp.ones((63, 128)),
                                   mesh)
    with pytest.raises(ValueError, match="contract mismatch"):
        ring.matmul_reduce_scatter(x, jnp.ones((32, 8)), mesh)


def test_ring_row_matmul_fallback_and_counters():
    """The call-site wrapper returns None (einsum fallback) on shapes that
    cannot ring, and the overlap counters record both outcomes."""
    mesh = make_mesh(2)
    ring.overlap_counters.reset()
    # K odd -> fallback
    assert ring.ring_row_matmul(jnp.ones((2, 4, 31)), jnp.ones((31, 8)),
                                mesh, lead_specs=(None, None)) is None
    snap = ring.overlap_counters.snapshot()
    assert snap["tp_fallbacks"] == 1 and snap["tp_ring_matmuls"] == 0
    got = ring.ring_row_matmul(jnp.ones((2, 4, 32), jnp.float32),
                               jnp.ones((32, 8), jnp.float32), mesh,
                               lead_specs=(None, None))
    np.testing.assert_allclose(np.asarray(got), 32.0, rtol=1e-6)
    snap = ring.overlap_counters.snapshot()
    assert snap["tp_ring_matmuls"] == 1 and snap["tp_ring_steps"] == 1
    assert snap["tp_bytes_permuted"] > 0


def test_ring_row_matmul_scope_default_specs_on_bare_mesh():
    """The scope's default token_specs name data/expert/fsdp/seq; on a
    mesh that only carries 'tensor' those axes normalize away (nothing can
    be sharded over an absent axis) and the ring still engages — no
    KeyError, no silent fallback."""
    mesh = make_mesh(2)
    x = jax.random.normal(jax.random.PRNGKey(0), (2, 8, 64), jnp.float32)
    w = jax.random.normal(jax.random.PRNGKey(1), (64, 16), jnp.float32)
    got = ring.ring_row_matmul(
        x, w, mesh, lead_specs=ring.TPOverlapScope(mesh).token_specs)
    assert got is not None
    np.testing.assert_allclose(np.asarray(got), np.asarray(x @ w),
                               rtol=2e-5, atol=2e-5)


def test_ring_row_matmul_grads_match():
    """Training contract: ring mm⊗rs + all-gather differentiates and its
    grads match the plain matmul."""
    mesh = make_mesh(4)
    x = jax.random.normal(jax.random.PRNGKey(0), (2, 16, 64), jnp.float32)
    w = jax.random.normal(jax.random.PRNGKey(1), (64, 32), jnp.float32)

    def loss_ring(a, b):
        return jnp.sum(ring.ring_row_matmul(
            a, b, mesh, lead_specs=(None, None)) ** 2)

    def loss_ref(a, b):
        return jnp.sum((a @ b) ** 2)

    g1 = jax.grad(loss_ring, argnums=(0, 1))(x, w)
    g2 = jax.grad(loss_ref, argnums=(0, 1))(x, w)
    for a, b in zip(g1, g2):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-4, atol=1e-4)


# ---------------------------------------------------------------------------
# vocab-parallel CE under seq x tensor (locks PR 1's roll+where label fix)
# ---------------------------------------------------------------------------

def test_vocab_parallel_ce_seq_tensor_with_ignore_rows():
    """vocab_parallel_cross_entropy under a seq x tensor mesh with labels
    built exactly as models/loss.py builds them (roll+where — the
    GSPMD-safe form; slice+concat on the seq-sharded dim miscompiled on
    this jaxlib) and ignore_index rows spread unevenly across seq shards."""
    from deepspeed_tpu.parallel.sequence import vocab_parallel_cross_entropy

    mesh = Mesh(np.array(jax.devices()[:4]).reshape(2, 2),
                ("seq", "tensor"))
    B, S, V = 2, 16, 64
    ids = jax.random.randint(jax.random.PRNGKey(0), (B, S), 0, V)
    logits = jax.random.normal(jax.random.PRNGKey(1), (B, S, V),
                               jnp.float32)
    # next-token labels the loss.py way: roll+where (the fill column at
    # S-1 becomes ignore_index), plus extra ignored rows on one shard only
    labels = jnp.where(jnp.arange(S)[None, :] < S - 1,
                       jnp.roll(ids, -1, axis=1), -100)
    labels = labels.at[0, :3].set(-100)

    logits_s = jax.device_put(
        logits, NamedSharding(mesh, P(None, "seq", "tensor")))
    labels_s = jax.device_put(labels, NamedSharding(mesh, P(None, "seq")))
    got = jax.jit(lambda lg, lb: vocab_parallel_cross_entropy(
        lg, lb, mesh, axis="tensor", seq_axis="seq"))(logits_s, labels_s)

    mask = np.asarray(labels) != -100
    logp = jax.nn.log_softmax(logits, axis=-1)
    picked = np.asarray(jnp.take_along_axis(
        logp, jnp.clip(labels, 0, V - 1)[..., None], axis=-1))[..., 0]
    ref = -(picked * mask).sum() / mask.sum()
    np.testing.assert_allclose(float(got), float(ref), rtol=1e-5)


# ---------------------------------------------------------------------------
# hot-path integrations (engine compiles: slow tier)
# ---------------------------------------------------------------------------

@pytest.mark.slow
@pytest.mark.parametrize("quant", [None, 8])
def test_engine_v2_identical_tokens_tp_overlap_on_off(quant):
    """engine_v2 on a tensor=2 CPU mesh produces IDENTICAL greedy token
    chains with tp_overlap on vs off (fp32 compute so ring vs blocking
    reduction order cannot flip an argmax), and the on-engine reports ring
    activity through its stats dict."""
    from deepspeed_tpu.inference.engine_v2 import (InferenceEngineV2,
                                                   RaggedInferenceConfig)
    from deepspeed_tpu.models.transformer import ModelConfig, TransformerLM
    from deepspeed_tpu.parallel.topology import MeshConfig, MeshTopology

    mcfg = ModelConfig(vocab_size=128, hidden_size=64, num_layers=2,
                       num_heads=4, max_seq_len=256,
                       position_embedding="rope", norm="rmsnorm",
                       activation="silu_glu", dtype=jnp.float32)
    prompts = [[1, 7, 3, 9, 5, 11, 2, 8], [4, 6, 10, 12, 3]]

    def run(overlap):
        eng = InferenceEngineV2(
            TransformerLM(mcfg), None, RaggedInferenceConfig(
                tensor_parallel=2, max_seqs=4, num_blocks=32, block_size=16,
                chunk=16, max_seq_len=128, decode_window=4, greedy=True,
                dtype=jnp.float32, quant_bits=quant, tp_overlap=overlap,
                use_pallas_decode=False),
            topology=MeshTopology(MeshConfig(tensor=2, data=1)),
            rng=jax.random.PRNGKey(0))
        assert eng._tp_ring_n == (2 if overlap else 0)
        out = eng.generate(prompts, max_new_tokens=8)
        return out, dict(eng.stats)

    # True forces the ring on EVERY divisible program incl. decode-sized
    # M (the auto mode's TP_OVERLAP_MIN_ROWS gate keeps decode blocking
    # by default pending real-slice measurement)
    on, stats_on = run(True)
    off, stats_off = run(False)
    assert on == off
    assert stats_on["tp_ring_matmuls"] > 0
    assert stats_on["tp_ring_steps"] > 0
    assert stats_on["tp_bytes_permuted"] > 0
    assert stats_off["tp_ring_matmuls"] == 0


@pytest.mark.slow
def test_engine_v2_odd_row_packed_prefill_rings_tp2():
    """ROADMAP odd-row item: exact-k packed prefill plans whose row count
    doesn't divide the tensor axis used to fall back to the blocking TP
    path per program. The engine now sets ``scheduler.row_multiple`` to
    the ring degree, padding packed plans up to the next tp multiple
    (masked rows), so with 1 or 3 pending sequences at tp=2 EVERY program
    rings (tp_fallbacks == 0) and tokens stay identical to tp_overlap
    off."""
    from deepspeed_tpu.inference.engine_v2 import (InferenceEngineV2,
                                                   RaggedInferenceConfig)
    from deepspeed_tpu.models.transformer import ModelConfig, TransformerLM
    from deepspeed_tpu.parallel.topology import MeshConfig, MeshTopology

    mcfg = ModelConfig(vocab_size=128, hidden_size=64, num_layers=2,
                       num_heads=4, max_seq_len=256,
                       position_embedding="rope", norm="rmsnorm",
                       activation="silu_glu", dtype=jnp.float32)
    odd3 = [[1, 7, 3, 9, 5, 11, 2, 8], [4, 6, 10, 12, 3],
            [13, 2, 5, 9, 1, 1, 7]]                  # k=3 -> 4 rows
    odd1 = [[9, 4, 2, 7, 7, 3]]                      # k=1 -> 2 rows

    def run(overlap):
        eng = InferenceEngineV2(
            TransformerLM(mcfg), None, RaggedInferenceConfig(
                tensor_parallel=2, max_seqs=4, num_blocks=32, block_size=16,
                chunk=16, max_seq_len=128, decode_window=4, greedy=True,
                dtype=jnp.float32, tp_overlap=overlap,
                use_pallas_decode=False),
            topology=MeshTopology(MeshConfig(tensor=2, data=1)),
            rng=jax.random.PRNGKey(0))
        assert eng.scheduler.row_multiple == (2 if overlap else 1)
        if overlap:
            # the compile menu itself only carries ring-divisible rows
            assert all(rows % 2 == 0 for _, rows
                       in eng.scheduler.program_shape_menu())
        out = [eng.generate(odd3, max_new_tokens=6),
               eng.generate(odd1, max_new_tokens=6)]
        return out, dict(eng.stats)

    on, stats_on = run(True)
    off, stats_off = run(False)
    assert on == off
    assert stats_on["tp_ring_matmuls"] > 0
    assert stats_on["tp_fallbacks"] == 0, stats_on   # every program rang


@pytest.mark.slow
def test_qgmm_grouped_ring_matches_psum():
    """The MoE expert-GEMM grouped ring (``RaggedForward.qgmm``, row kind
    under tp_overlap: per-destination token-tile chunks + tile→expert slices
    ring-accumulating over the tensor axis) matches the blocking
    psum formulation on the same per-shard-quantized expert slabs."""
    import dataclasses

    from deepspeed_tpu.inference.engine_v2 import (InferenceEngineV2,
                                                   RaggedInferenceConfig)
    from deepspeed_tpu.inference.forward import MOE_TILE_FLOOR
    from deepspeed_tpu.models.transformer import (ModelConfig, MoEConfig,
                                                  TransformerLM)
    from deepspeed_tpu.ops.pallas.quant_matmul import QuantGrouped
    from deepspeed_tpu.parallel.topology import MeshConfig, MeshTopology

    mcfg = ModelConfig(vocab_size=128, hidden_size=64, num_layers=1,
                       num_heads=4, max_seq_len=128,
                       position_embedding="rope", norm="rmsnorm",
                       activation="silu_glu", dtype=jnp.float32,
                       moe=MoEConfig(num_experts=4, top_k=2))
    eng = InferenceEngineV2(
        TransformerLM(mcfg), None, RaggedInferenceConfig(
            tensor_parallel=2, max_seqs=2, num_blocks=16, block_size=16,
            chunk=16, max_seq_len=64, dtype=jnp.float32, quant_bits=8,
            use_pallas_decode=False),
        topology=MeshTopology(MeshConfig(tensor=2, data=1)),
        rng=jax.random.PRNGKey(0))
    qw = eng.params["layer_0"]["moe"]["moe_layer"]["experts"]["w_down"]
    assert isinstance(qw, QuantGrouped)
    F = mcfg.ffn_size
    bm = MOE_TILE_FLOOR[True]                 # the quantised GEMM's least tile
    rows = 4 * bm                             # tile-aligned, % (tp*bm) == 0
    x2d = jax.random.normal(jax.random.PRNGKey(2), (rows, F), jnp.float32)
    te = jnp.array([0, 2, 1, 3], jnp.int32)   # one expert per tile

    fwd = eng._forward
    assert fwd.tp_ring_n == 2                 # ring path engages
    y_ring = fwd.qgmm(x2d, qw, te, "moe_w_down", bm)
    y_psum = dataclasses.replace(fwd, tp_ring_n=0).qgmm(   # blocking psum
        x2d, qw, te, "moe_w_down", bm)
    np.testing.assert_allclose(np.asarray(y_ring), np.asarray(y_psum),
                               rtol=1e-3, atol=1e-3)


@pytest.mark.slow
def test_training_model_tp_overlap_loss_and_grad_parity():
    """The GSPMD training model under tp_overlap_scope: same logits-loss
    and same grads as the plain einsum path on a tensor=2 mesh (the
    runtime engine installs the scope in _loss_with_rules; the models
    consult it at trace time)."""
    from deepspeed_tpu.models.transformer import ModelConfig, TransformerLM

    mesh = make_mesh(2)
    cfg = ModelConfig(vocab_size=64, hidden_size=32, num_layers=2,
                      num_heads=4, max_seq_len=32, dtype=jnp.float32)
    model = TransformerLM(cfg)
    ids = jax.random.randint(jax.random.PRNGKey(1), (2, 16), 0, 64)
    params = model.init(jax.random.PRNGKey(0), ids)

    def loss_plain(p):
        return jnp.sum(model.apply(p, ids).astype(jnp.float32) ** 2)

    def loss_ring(p):
        with ring.tp_overlap_scope(mesh, token_specs=(None, None)):
            return jnp.sum(model.apply(p, ids).astype(jnp.float32) ** 2)

    ring.overlap_counters.reset()
    v0, g0 = jax.jit(jax.value_and_grad(loss_plain))(params)
    v1, g1 = jax.jit(jax.value_and_grad(loss_ring))(params)
    np.testing.assert_allclose(float(v0), float(v1), rtol=1e-5)

    def unbox(t):
        return jax.tree.map(lambda x: x.value if hasattr(x, "value") else x,
                            t, is_leaf=lambda x: hasattr(x, "value"))

    f0, _ = ravel_pytree(unbox(g0))
    f1, _ = ravel_pytree(unbox(g1))
    np.testing.assert_allclose(np.asarray(f0), np.asarray(f1),
                               rtol=1e-4, atol=1e-5)
    # wo + w_down rings per layer, forward AND transposed in backward
    assert ring.overlap_counters.snapshot()["tp_ring_matmuls"] >= 4


def test_training_engine_installs_scope_from_config():
    """DeepSpeedConfig plumbing: tensor_parallel.overlap reaches the
    engine's scope switch (pipe>1 or tensor==1 keep it off)."""
    from deepspeed_tpu.config import Config

    cfg = Config.from_dict({"train_batch_size": 4,
                            "tensor_parallel": {"overlap": True}})
    assert cfg.tensor_parallel.overlap is True
    cfg2 = Config.from_dict({"train_batch_size": 4})
    assert cfg2.tensor_parallel.overlap is False


def test_overlap_breakdown_from_totals():
    """profiling/trace.py overlap_breakdown splits ring vs blocking
    collective time and derives the comm-hidden fraction."""
    from deepspeed_tpu.profiling.trace import overlap_breakdown

    rep = overlap_breakdown(totals={
        "fusion.1": 5.0,
        "collective-permute.3": 3.0,
        "all-reduce.2": 1.0,
    })
    assert rep["ring_ms"] == 3.0 and rep["blocking_ms"] == 1.0
    np.testing.assert_allclose(rep["comm_hidden_fraction"], 0.75)
    assert overlap_breakdown(totals={"fusion.1": 2.0})[
        "comm_hidden_fraction"] is None
