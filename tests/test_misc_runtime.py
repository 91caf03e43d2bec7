"""Universal checkpoint tools + eigenvalue + PLD + TiledLinear tests
(reference tests/unit/checkpoint/test_universal_checkpoint.py,
runtime eigenvalue/PLD/tiling unit tests analogues)."""
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import deepspeed_tpu as ds
from deepspeed_tpu.checkpoint import (UniversalCheckpoint, ds_to_universal,
                                      get_fp32_state_dict_from_zero_checkpoint,
                                      zero_to_fp32)
from deepspeed_tpu.models import build_model
from deepspeed_tpu.runtime.eigenvalue import Eigenvalue
from deepspeed_tpu.runtime.progressive_layer_drop import (ProgressiveLayerDrop,
                                                          apply_pld_layer,
                                                          pld_keep_mask)
from deepspeed_tpu.runtime.tiling import TiledLinear


# -- offline checkpoint tools ----------------------------------------------
@pytest.fixture(scope="module")
def saved_ckpt(tmp_path_factory):
    d = tmp_path_factory.mktemp("ckpt")
    engine, *_ = ds.initialize(
        model=build_model("tiny-gpt2"),
        config={"train_micro_batch_size_per_gpu": 2,
                "optimizer": {"type": "AdamW", "params": {"lr": 1e-3}},
                "zero_optimization": {"stage": 2}})
    rng = np.random.default_rng(0)
    gbs = engine.config.train_batch_size
    engine.train_batch({"input_ids": rng.integers(0, 256, (gbs, 32))})
    engine.save_checkpoint(str(d))
    return str(d), engine


def test_zero_to_fp32(saved_ckpt, tmp_path):
    ckpt_dir, engine = saved_ckpt
    out = str(tmp_path / "consolidated.npz")
    zero_to_fp32(ckpt_dir, out)
    loaded = np.load(out)
    names = list(loaded.files)
    assert any("embed" in n for n in names)
    total = sum(loaded[n].size for n in names)
    assert total == engine.num_parameters()
    assert all(loaded[n].dtype == np.float32 for n in names)
    # values match the engine's fp32 master
    sd = get_fp32_state_dict_from_zero_checkpoint(ckpt_dir)
    master_embed = np.asarray(engine.state.master["embed"])
    np.testing.assert_allclose(sd["embed"], master_embed, rtol=1e-6)


def test_ds_to_universal_and_reader(saved_ckpt, tmp_path):
    ckpt_dir, engine = saved_ckpt
    out_dir = str(tmp_path / "universal")
    ds_to_universal(ckpt_dir, out_dir)
    assert os.path.exists(os.path.join(out_dir, "universal_index.json"))
    uc = UniversalCheckpoint(out_dir)
    assert any(k.startswith("master.") for k in uc.keys())
    assert any(k.startswith("opt_mu.") for k in uc.keys())
    tree = uc.load_section("master")
    np.testing.assert_allclose(tree["embed"],
                               np.asarray(engine.state.master["embed"]),
                               rtol=1e-6)
    # index metadata carries the training step
    assert uc.meta.get("global_steps") == 1


def test_universal_cli(saved_ckpt, tmp_path):
    from deepspeed_tpu.checkpoint.universal import main

    ckpt_dir, _ = saved_ckpt
    out = str(tmp_path / "w.npz")
    assert main(["zero_to_fp32", ckpt_dir, out]) == 0
    assert os.path.exists(out)
    assert main(["bogus"]) == 2


# -- eigenvalue -------------------------------------------------------------
def test_power_iteration_quadratic():
    """H of 0.5*x^T A x is A: dominant eigenvalue recovered."""
    A = jnp.diag(jnp.asarray([5.0, 2.0, 1.0]))

    def loss(p):
        x = p["x"]
        return 0.5 * x @ A @ x

    eig, vec = Eigenvalue(max_iter=200, tol=1e-4).power_iteration(
        loss, {"x": jnp.ones(3)})
    assert eig == pytest.approx(5.0, rel=1e-2)
    v = np.abs(np.asarray(vec["x"]))
    assert v[0] == pytest.approx(1.0, abs=0.05)  # aligned with e_0


def test_per_block_eigenvalues():
    def loss(p):
        return 0.5 * (10.0 * jnp.sum(p["layer_0"]["w"] ** 2)
                      + 1.0 * jnp.sum(p["layer_1"]["w"] ** 2))

    params = {"layer_0": {"w": jnp.ones(4)}, "layer_1": {"w": jnp.ones(4)}}
    eigs = Eigenvalue(max_iter=100).compute_eigenvalue(loss, params)
    assert eigs["layer_0"] == pytest.approx(10.0, rel=1e-2)
    assert eigs["layer_1"] == pytest.approx(1.0, rel=1e-2)


# -- progressive layer drop -------------------------------------------------
def test_pld_theta_schedule():
    pld = ProgressiveLayerDrop(theta=0.5, gamma=0.01)
    assert pld.get_theta(0) == pytest.approx(1.0)
    assert pld.get_theta(10_000) == pytest.approx(0.5, abs=1e-3)
    mid = pld.get_theta(100)
    assert 0.5 < mid < 1.0
    pld.update_state(100)
    assert pld.get_state()["pld_theta"] == pytest.approx(mid)


def test_pld_keep_mask_depth_ramp():
    rng = jax.random.PRNGKey(0)
    # theta=1 → everything kept
    assert bool(pld_keep_mask(rng, 8, 1.0).all())
    # low theta → deeper layers dropped more often (statistically)
    keeps = np.stack([np.asarray(pld_keep_mask(jax.random.PRNGKey(i), 8, 0.2))
                      for i in range(400)])
    rates = keeps.mean(axis=0)
    assert rates[0] > 0.95 and rates[-1] < 0.4
    assert rates[0] > rates[-1]
    x = jnp.ones((2, 3))
    out = apply_pld_layer(jnp.asarray(False), x, x * 7)
    np.testing.assert_array_equal(np.asarray(out), 1.0)


# -- tiled linear -----------------------------------------------------------
def test_tiled_linear_matches_dense():
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.standard_normal((4, 30)), jnp.float32)
    kernel = jnp.asarray(rng.standard_normal((30, 17)), jnp.float32)
    bias = jnp.asarray(rng.standard_normal(17), jnp.float32)
    m = TiledLinear(features=17, in_splits=3, out_splits=2)
    params = TiledLinear.params_from_dense(kernel, bias, 3, 2)
    y = m.apply({"params": params}, x)
    np.testing.assert_allclose(np.asarray(y), np.asarray(x @ kernel + bias),
                               rtol=1e-5, atol=1e-5)
    # uneven splits covered: 30/3=10 even, 17/2 → 9+8
    assert params["tile_0_0"].shape == (10, 9)
    assert params["tile_0_1"].shape == (10, 8)


def test_tiled_linear_trains():
    m = TiledLinear(features=8, in_splits=2, out_splits=2)
    x = jnp.ones((2, 10))
    p = m.init(jax.random.PRNGKey(0), x)["params"]
    g = jax.grad(lambda pp: jnp.sum(m.apply({"params": pp}, x) ** 2))(p)
    assert all(np.isfinite(np.asarray(l)).all() for l in jax.tree.leaves(g))
    assert sum(np.abs(np.asarray(l)).sum() for l in jax.tree.leaves(g)) > 0


def test_instrument_w_nvtx_annotation():
    """Range decorator runs inside jit and names the scope in the HLO
    (reference utils/nvtx.py instrument_w_nvtx)."""
    import jax
    import jax.numpy as jnp

    from deepspeed_tpu.utils.annotations import instrument_w_nvtx, range_push

    @instrument_w_nvtx(name="my_marked_op")
    def f(x):
        return x * 2 + 1

    out = jax.jit(f)(jnp.ones((4,)))
    assert float(out[0]) == 3.0
    lowered = jax.jit(f).lower(jnp.ones((4,)))
    try:
        txt = lowered.as_text(debug_info=True)
    except TypeError:   # older jax: no debug_info kwarg; scope names only
        txt = lowered.compile().as_text()   # survive into the compiled HLO
    assert "my_marked_op" in txt
    with range_push("block"):
        assert float(f(jnp.ones(()))) == 3.0


def test_chunked_cross_entropy_matches_dense():
    """DS_TPU_CE_CHUNK path: streamed nll/z-loss and grads are exactly the
    dense computation (opt-in OOM escape hatch for huge-vocab configs)."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    import deepspeed_tpu.models.loss as L

    r = np.random.default_rng(0)
    logits = jnp.asarray(r.standard_normal((2, 8, 257)), jnp.float32)
    labels = r.integers(0, 257, (2, 8)).astype(np.int32)
    labels[0, :3] = L.IGNORE_INDEX
    labels = jnp.asarray(labels)

    def fresh():   # new function object per CE_CHUNK value: JAX caches
        return lambda lg: L.cross_entropy_lm(lg, labels,   # traces per
                                             z_loss_weight=1e-3)  # object

    old = L.CE_CHUNK
    try:
        L.CE_CHUNK = 4
        f = fresh()
        assert "scan" in str(jax.make_jaxpr(f)(logits))   # chunked traced
        c_val, c_grad = float(f(logits)), np.asarray(jax.grad(f)(logits))
        L.CE_CHUNK = 0
        f = fresh()
        assert "scan" not in str(jax.make_jaxpr(f)(logits))
        d_val, d_grad = float(f(logits)), np.asarray(jax.grad(f)(logits))
        assert abs(c_val - d_val) < 1e-5
        np.testing.assert_allclose(c_grad, d_grad, atol=1e-6)
        # non-divisible N (2*8=16 with chunk 5): 3 full chunks via scan plus
        # a 1-row static tail — full chunk size kept, no padded logits copy
        L.CE_CHUNK = 5
        f = fresh()
        assert "scan" in str(jax.make_jaxpr(f)(logits))
        assert abs(float(f(logits)) - d_val) < 1e-5
        np.testing.assert_allclose(np.asarray(jax.grad(f)(logits)),
                                   d_grad, atol=1e-6)
        # chunk=7: a divisor search would have degraded to chunk=1
        L.CE_CHUNK = 7
        f = fresh()
        assert abs(float(f(logits)) - d_val) < 1e-5
        np.testing.assert_allclose(np.asarray(jax.grad(f)(logits)),
                                   d_grad, atol=1e-6)
    finally:
        L.CE_CHUNK = old


@pytest.mark.slow  # full engine bring-up (~35s)
def test_zero_namespace_compat():
    """deepspeed_tpu.zero.Init / GatheredParameters shims: reference-shaped
    call sites run unchanged and training proceeds normally."""
    import numpy as np

    import deepspeed_tpu as ds
    from deepspeed_tpu.models import build_model
    from deepspeed_tpu.parallel.topology import MeshTopology

    with ds.zero.Init(config_dict_or_path={"zero_optimization": {"stage": 3}}):
        model = build_model("tiny-gpt2")
    engine, *_ = ds.initialize(
        model=model,
        config={"train_micro_batch_size_per_gpu": 2,
                "optimizer": {"type": "AdamW", "params": {"lr": 1e-3}},
                "zero_optimization": {"stage": 3}},
        topology=MeshTopology({"fsdp": 4, "data": 2}))
    r = np.random.default_rng(0)
    B = engine.config.train_batch_size
    batch = {"input_ids": r.integers(0, 256, (B, 32)).astype(np.int32)}
    l0 = float(engine.train_batch(batch))
    with ds.zero.GatheredParameters(engine.state.params) as full:
        assert full is engine.state.params
    assert float(engine.train_batch(batch)) < l0


def test_fused_head_loss_matches_dense():
    """Fused vocab-chunked head loss == unembed-matmul + dense CE, values
    and all grads (fp32 exact; odd vocab exercises the clamped tail chunk;
    both head orientations + bias)."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    import deepspeed_tpu.models.loss as L

    r = np.random.default_rng(0)
    E, V = 32, 257
    x = jnp.asarray(r.standard_normal((2, 7, E)), jnp.float32)
    labels = r.integers(0, V, (2, 7)).astype(np.int32)
    labels[0, :2] = L.IGNORE_INDEX
    labels = jnp.asarray(labels)
    for w_is_ve in (True, False):
        w = jnp.asarray(r.standard_normal((V, E) if w_is_ve else (E, V))
                        * 0.05, jnp.float32)
        b = jnp.asarray(r.standard_normal((V,)) * 0.1, jnp.float32)

        def dense(x, w, b):
            lg = (jnp.einsum("bse,ve->bsv", x, w) if w_is_ve
                  else jnp.einsum("bse,ev->bsv", x, w)) + b
            return L.cross_entropy_lm(lg, labels, z_loss_weight=1e-3)

        def fused(x, w, b):
            return L.fused_lm_head_loss(x, w, labels, bias=b,
                                        w_is_ve=w_is_ve, vchunk=64,
                                        z_loss_weight=1e-3)

        dv, dg = jax.value_and_grad(dense, argnums=(0, 1, 2))(x, w, b)
        fv, fg = jax.value_and_grad(fused, argnums=(0, 1, 2))(x, w, b)
        assert abs(float(dv) - float(fv)) < 1e-5
        for a, c in zip(fg, dg):
            np.testing.assert_allclose(np.asarray(a), np.asarray(c),
                                       atol=1e-6)


def test_fused_head_engine_training_matches_dense(monkeypatch):
    """DS_TPU_FUSED_HEAD_CHUNK routes the engine's default LM loss through
    the fused head — training trajectory matches the dense path.

    Both engines also pin that the train step compiles ONCE: the state
    starts with every leaf committed to its steady-state sharding, so
    step 1 (fresh state) and step 2 (the step's own outputs) trace the
    same module. An uncommitted step counter made them two modules and
    compiled the whole step twice — 69 s more at step 2 for gpt2-350m on
    a v5e (found by chip_smoke.py, PR 21)."""
    import jax
    import numpy as np

    import deepspeed_tpu as ds
    from deepspeed_tpu.models import build_model

    def losses():
        engine, *_ = ds.initialize(
            model=build_model("tiny-gpt2"),
            config={"train_micro_batch_size_per_gpu": 2,
                    "optimizer": {"type": "AdamW", "params": {"lr": 1e-2}},
                    "steps_per_print": 10_000})
        assert all(leaf.committed for leaf in jax.tree.leaves(engine.state))
        rng = np.random.default_rng(0)
        batch = {"input_ids": rng.integers(
            0, 256, (engine.config.train_batch_size, 32)).astype(np.int32)}
        out = [float(engine.train_batch(batch)) for _ in range(3)]
        assert engine._train_step._cache_size() == 1
        return out

    dense = losses()
    monkeypatch.setenv("DS_TPU_FUSED_HEAD_CHUNK", "96")
    fused = losses()
    np.testing.assert_allclose(fused, dense, rtol=2e-2)


def test_fused_head_removes_logits_memory():
    """The compiler's own memory analysis shows the fused head's grad
    program never materializes the logits: temp bytes fall far below the
    dense program's (llama-class head at 4k rows: measured ~5x)."""
    import jax
    import jax.numpy as jnp

    import deepspeed_tpu.models.loss as L

    E, V = 512, 32000
    x = jax.ShapeDtypeStruct((4, 1024, E), jnp.bfloat16)
    w = jax.ShapeDtypeStruct((V, E), jnp.bfloat16)
    lab = jax.ShapeDtypeStruct((4, 1024), jnp.int32)

    def dense(x, w, labels):
        return L.cross_entropy_lm(jnp.einsum("bse,ve->bsv", x, w), labels)

    def fused(x, w, labels):
        return L.fused_lm_head_loss(x, w, labels, w_is_ve=True, vchunk=4096)

    def temp(fn):
        return jax.jit(jax.grad(fn, argnums=(0, 1))).lower(
            x, w, lab).compile().memory_analysis().temp_size_in_bytes

    assert temp(fused) < temp(dense) / 2
