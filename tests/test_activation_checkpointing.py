"""Activation checkpointing tests (reference
tests/unit/runtime/activation_checkpointing/test_activation_checkpointing.py —
its core assertion is outputs+grads identical with and without checkpointing)."""
import dataclasses

import numpy as np
import jax
import jax.numpy as jnp
import pytest

slow = pytest.mark.slow  # multi-minute: engine jit compiles

import deepspeed_tpu as ds
from deepspeed_tpu.models import build_model
from deepspeed_tpu.models.loss import lm_loss_fn
from deepspeed_tpu.models.transformer import ModelConfig
from deepspeed_tpu.parallel.topology import MeshTopology
from deepspeed_tpu.runtime import activation_checkpointing as ac
from deepspeed_tpu.runtime.activation_checkpointing import REMAT_LADDER


def test_policy_resolution():
    assert ac.make_policy("none") is None
    assert ac.make_policy("full") is jax.checkpoint_policies.nothing_saveable
    assert ac.make_policy("dots_saveable") is jax.checkpoint_policies.dots_saveable
    assert ac.make_policy("offload") is not None  # falls back if unsupported
    with pytest.raises(ValueError):
        ac.make_policy("bogus")
    # the default: the ladder's first rung where no engine judged
    assert ac.make_policy("auto") is ac.make_policy(REMAT_LADDER[0])
    assert REMAT_LADDER[-1] == "nothing_saveable"


def test_checkpoint_fn_same_value_and_grad():
    w = jnp.asarray(np.random.default_rng(0).normal(size=(16, 16)), jnp.float32)

    def f(w, x):
        h = jnp.tanh(x @ w)
        return jnp.sum(jnp.tanh(h @ w) ** 2)

    x = jnp.asarray(np.random.default_rng(1).normal(size=(4, 16)), jnp.float32)
    base_v, base_g = jax.value_and_grad(f)(w, x)
    for policy in ("full", "dots_saveable", "dots_with_no_batch_dims_saveable"):
        ck = ac.checkpoint_fn(f, policy=policy)
        v, g = jax.value_and_grad(ck)(w, x)
        np.testing.assert_allclose(np.asarray(v), np.asarray(base_v), rtol=1e-6)
        np.testing.assert_allclose(np.asarray(g), np.asarray(base_g), rtol=1e-6)


def test_megatron_style_module_api():
    ac.configure({"policy": "full"})
    assert ac.is_configured()

    def f(x):
        return jnp.sum(jnp.sin(x) ** 2)

    x = jnp.linspace(0, 1, 32)
    g = jax.grad(lambda v: ac.checkpoint(f, v))(x)
    np.testing.assert_allclose(np.asarray(g), np.asarray(jax.grad(f)(x)),
                               rtol=1e-6)
    ac.configure({"policy": "none"})


@slow
def test_engine_remat_config_matches_baseline():
    """Training with activation_checkpointing config gives the same losses
    as without (remat changes memory, not math)."""
    def make(policy):
        cfg = {
            "train_micro_batch_size_per_gpu": 2,
            "gradient_accumulation_steps": 1,
            "optimizer": {"type": "AdamW", "params": {"lr": 1e-3}},
            "zero_optimization": {"stage": 2},
            "steps_per_print": 10_000,
        }
        if policy:
            cfg["activation_checkpointing"] = {"policy": policy}
        engine, *_ = ds.initialize(
            model=build_model("tiny-gpt2"),
            config=cfg,
            topology=MeshTopology({"fsdp": 4, "data": 2}))
        return engine

    rng = np.random.default_rng(0)
    batches = [{"input_ids": rng.integers(0, 256, (16, 32)).astype(np.int32)}
               for _ in range(3)]

    base = make(None)
    losses_base = [float(base.train_batch(b)) for b in batches]
    remat = make("full")
    assert remat.model.config.remat is True
    losses_remat = [float(remat.train_batch(b)) for b in batches]
    np.testing.assert_allclose(losses_remat, losses_base, rtol=2e-4)


# ---- what a rematted block keeps: the ladder and its judge (PR 32) ----------

TOP, MIDDLE, BOTTOM = REMAT_LADDER


def _tiny(**over):
    return build_model("tiny-llama", dtype=jnp.float32, **over)


@pytest.fixture(scope="module")
def references():
    """``value_and_grad(**config)`` of tiny-llama in float32, and what it
    gives with no remat at all and under ``nothing_saveable``."""
    model = _tiny()
    ids = jnp.asarray(np.random.default_rng(0).integers(0, 256, (2, 64)),
                      jnp.int32)
    params = jax.tree.map(
        lambda b: b.value, model.init(jax.random.PRNGKey(0), ids)["params"],
        is_leaf=lambda l: hasattr(l, "names"))

    def value_and_grad(**over):
        m = model.clone(config=dataclasses.replace(model.config, **over))
        return jax.jit(jax.value_and_grad(
            lambda p: lm_loss_fn(m, p, {"input_ids": ids})))(params)

    return value_and_grad, (value_and_grad(), value_and_grad(
        remat=True, remat_policy="nothing_saveable"))


def test_the_default_is_judged_not_pinned():
    assert ModelConfig().remat_policy == "auto" and not ModelConfig().remat


@pytest.mark.parametrize("policy", REMAT_LADDER + ("auto",))
def test_every_rung_has_the_unrematted_loss_and_gradients(references, policy):
    """(a) The same mathematics: what a rung keeps changes what the
    backward pass makes again, not a value."""
    value_and_grad, wanted = references
    loss, grads = value_and_grad(remat=True, remat_policy=policy)
    for want_loss, want in wanted:
        np.testing.assert_allclose(loss, want_loss, rtol=1e-6, atol=1e-6)
        flat, _ = jax.tree_util.tree_flatten_with_path(grads)
        for (path, g), r in zip(flat, jax.tree.leaves(want)):
            np.testing.assert_allclose(g, r, rtol=1e-6, atol=1e-6,
                                       err_msg=jax.tree_util.keystr(path))


def _engine(monkeypatch, limit, model=None, seq=64, **config):
    """A tiny engine on the 8-device mesh whose judge reads ``limit`` from
    the one function that reads the device's (None: the CPU's answer)."""
    monkeypatch.setattr(ac, "device_memory_limit", lambda: limit)
    engine, *_ = ds.initialize(
        model=model if model is not None else _tiny(remat=True), config={
            "train_micro_batch_size_per_gpu": 2, "steps_per_print": 10_000,
            "optimizer": {"type": "SGD", "params": {"lr": 1.0}},
            "zero_optimization": {"stage": 3}, "seed": 0,
            "mesh": {"fsdp": 4, "data": 2}, **config},
        sample_batch={"input_ids": np.zeros((16, seq), np.int32)})
    return engine


@pytest.fixture(scope="module")
def rung_bytes():
    """``step_bytes`` of the two judged rungs, read off a judge that is
    refused everything (limit 0)."""
    with pytest.MonkeyPatch.context() as mp:
        plan = _engine(mp, 0).remat_plan
    assert [t["policy"] for t in plan["tried"]] == [TOP, MIDDLE]
    return {t["policy"]: t["step_bytes"] for t in plan["tried"]}


def test_no_limit_takes_the_first_rung_unjudged(monkeypatch):
    """The CPU reports no limit: the first rung, no compile at build."""
    engine = _engine(monkeypatch, None)
    plan = engine.remat_plan
    assert (plan["chosen"], plan["rung"], plan["tried"]) == (TOP, 0, [])
    assert "no memory limit" in plan["why"]
    assert engine.model.config.remat_policy == TOP


@pytest.mark.parametrize("fits,chosen", [
    ((TOP, MIDDLE), TOP), ((MIDDLE,), MIDDLE), ((), BOTTOM)],
    ids=["room_for_all", "below_the_first_rung", "below_every_rung"])
def test_the_judge_steps_down_by_the_limit_it_reads(monkeypatch, rung_bytes,
                                                    fits, chosen):
    """(b) and (d): the rung is chosen by the compiled step's bytes against
    the limit less the headroom, one rung down a refusal, and the plan
    lists every rung tried with its bytes. The CPU's ``memory_analysis()``
    does not tell the two upper rungs apart at this size, so the case
    between them reads the first compiled step a MiB larger."""
    rung_bytes = dict(rung_bytes)
    if fits == (MIDDLE,):
        real, seen = ac.step_memory, []

        def first_is_larger(compiled):
            mem = real(compiled)
            seen.append(compiled)
            if len(seen) == 1:
                mem["temp_bytes"] += 1 << 20
                mem["step_bytes"] += 1 << 20
            return mem

        monkeypatch.setattr(ac, "step_memory", first_is_larger)
        rung_bytes[TOP] += 1 << 20
    limit = ac.STEP_HEADROOM_BYTES + (
        rung_bytes[fits[0]] if fits else min(rung_bytes.values()) - 1)
    engine = _engine(monkeypatch, limit)
    plan = engine.remat_plan
    assert plan["chosen"] == chosen == engine.model.config.remat_policy
    assert plan["rung"] == REMAT_LADDER.index(chosen)
    assert plan["limit_bytes"] == limit
    want_tried = REMAT_LADDER[:min(plan["rung"] + 1, 2)]
    assert [t["policy"] for t in plan["tried"]] == list(want_tried)
    for t in plan["tried"]:
        assert t["step_bytes"] == rung_bytes[t["policy"]] == (
            t["argument_bytes"] + t["temp_bytes"] + t["code_bytes"]
            + t["unaliased_output_bytes"])
        assert t["fits"] == (t["policy"] in fits)
        assert t["limit_bytes"] == limit
        assert t["fits"] or "over the limit" in t["why"]
    # the step that was judged is the step that runs
    loss = float(engine.train_batch(
        {"input_ids": np.random.default_rng(0).integers(
            0, 256, (16, 64)).astype(np.int32)}))
    assert np.isfinite(loss)


@pytest.mark.parametrize("how", ["remat_policy", "activation_checkpointing"])
def test_a_pinned_policy_is_never_judged(monkeypatch, how):
    """(c) Every name but "auto" pins that policy, for the model and
    through the DeepSpeed section, whatever the limit reads."""
    if how == "remat_policy":
        engine = _engine(monkeypatch, 0, model=_tiny(
            remat=True, remat_policy="dots_saveable"))
        want = "dots_saveable"
    else:
        engine = _engine(monkeypatch, 0, model=_tiny(),
                         activation_checkpointing={"policy": "full"})
        want = "full"
    assert engine.model.config.remat
    assert engine.model.config.remat_policy == want
    plan = engine.remat_plan
    assert (plan["chosen"], plan["rung"], plan["tried"]) == (want, None, [])
    assert "pinned" in plan["why"]


def test_no_remat_no_plan(monkeypatch):
    assert _engine(monkeypatch, 0, model=_tiny()).remat_plan is None


def test_offloaded_optimizer_keeps_nothing(monkeypatch):
    """A path that exists because memory is short has no compiled train
    step to judge: today's behaviour."""
    engine = _engine(monkeypatch, 1 << 40, zero_optimization={
        "stage": 2, "offload_optimizer": {"device": "cpu"}},
        optimizer={"type": "AdamW", "params": {"lr": 1e-3}})
    assert engine._train_step is None
    assert engine.remat_plan["chosen"] == BOTTOM
    assert engine.model.config.remat_policy == BOTTOM


def _count(jaxpr, acc):
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pallas_call":
            acc["pallas_call"] += 1
            continue
        if eqn.primitive.name == "dot_general":
            acc["dot_general"] += 1
        for sub in jax.core.jaxprs_in_params(eqn.params):
            _count(sub, acc)
    return acc


def test_the_first_rung_drops_the_matmuls_and_keeps_the_kernel_calls(
        monkeypatch):
    """(e) The gradient's jaxpr: under the first rung the q, k, v, output,
    gate and up matmuls are not made again (6 a layer; at least the FFN's
    two), and the flash kernel still runs forward twice a layer — its
    ``(out, lse)`` carry no tag."""
    counts = {}
    for policy in (TOP, MIDDLE, BOTTOM):
        engine = _engine(monkeypatch, None, model=build_model(
            "tiny-llama", hidden_size=256, num_heads=4, num_kv_heads=2,
            max_seq_len=128, dtype=jnp.float32, remat=True,
            remat_policy=policy), seq=128)
        assert engine.attention_formulation == ("pallas", "")
        counts[policy] = _count(engine._train_step.trace(
            *engine._abstract_step_args()).jaxpr.jaxpr,
            {"dot_general": 0, "pallas_call": 0})
    layers = engine.model.config.num_layers
    assert counts[BOTTOM]["dot_general"] - counts[TOP]["dot_general"] \
        == 6 * layers
    assert counts[BOTTOM]["dot_general"] - counts[MIDDLE]["dot_general"] \
        == 4 * layers
    # forward, the forward made again, the backward kernel(s)
    assert counts[TOP]["pallas_call"] == counts[BOTTOM]["pallas_call"] \
        == counts[MIDDLE]["pallas_call"] >= 3 * layers
