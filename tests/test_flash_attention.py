"""Pallas flash-attention numerics vs the XLA oracle (role of reference
tests/unit/ops/transformer/ kernel tests). Runs in interpret mode on CPU."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from deepspeed_tpu.ops.attention import _xla_attention
from deepspeed_tpu.ops.pallas.flash_attention import (
    flash_attention, flash_attention_usable)


def _rand(shape, key, dtype=jnp.float32):
    return jax.random.normal(key, shape, dtype=dtype)


@pytest.mark.parametrize("causal", [True, False])
@pytest.mark.parametrize("gqa", [1, 2])
def test_forward_matches_xla(causal, gqa):
    B, S, H, D = 2, 256, 4, 64
    KV = H // gqa
    ks = jax.random.split(jax.random.PRNGKey(0), 3)
    q = _rand((B, S, H, D), ks[0])
    k = _rand((B, S, KV, D), ks[1])
    v = _rand((B, S, KV, D), ks[2])
    assert flash_attention_usable(q, k, v, causal=causal)
    out = flash_attention(q, k, v, causal=causal, block_q=128, block_k=128)
    ref = _xla_attention(q, k, v, causal=causal, positions=None,
                         kv_len=None, mask=None)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=2e-5, rtol=2e-5)


@pytest.mark.slow  # merged-bwd compile (~14s)
def test_grads_match_xla():
    B, S, H, D = 1, 256, 2, 64
    KV = 1  # GQA group of 2
    ks = jax.random.split(jax.random.PRNGKey(1), 3)
    q = _rand((B, S, H, D), ks[0])
    k = _rand((B, S, KV, D), ks[1])
    v = _rand((B, S, KV, D), ks[2])

    def loss_flash(q, k, v):
        o = flash_attention(q, k, v, causal=True, block_q=128, block_k=128)
        return jnp.sum(o * o)

    def loss_ref(q, k, v):
        o = _xla_attention(q, k, v, causal=True, positions=None,
                           kv_len=None, mask=None)
        return jnp.sum(o * o)

    g_flash = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
    g_ref = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    for a, b, name in zip(g_flash, g_ref, "qkv"):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   atol=5e-4, rtol=5e-4,
                                   err_msg=f"d{name} mismatch")


def test_bf16_forward_close():
    B, S, H, D = 1, 128, 2, 64
    ks = jax.random.split(jax.random.PRNGKey(2), 3)
    q = _rand((B, S, H, D), ks[0], jnp.bfloat16)
    k = _rand((B, S, H, D), ks[1], jnp.bfloat16)
    v = _rand((B, S, H, D), ks[2], jnp.bfloat16)
    out = flash_attention(q, k, v, causal=True, block_q=128, block_k=128)
    ref = _xla_attention(q, k, v, causal=True, positions=None,
                        kv_len=None, mask=None)
    assert out.dtype == jnp.bfloat16
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32),
                               atol=3e-2, rtol=3e-2)


def test_usable_gate():
    # tiny seqs go to XLA (fast + cheap there); 128..1024 collapse to one
    # block; longer seqs need a fast divisor (1024/512/256)
    q = jnp.zeros((1, 100, 4, 64))
    k = v = jnp.zeros((1, 100, 4, 64))
    assert not flash_attention_usable(q, k, v, causal=True)
    q1 = jnp.zeros((1, 384, 4, 64))
    k1 = v1 = jnp.zeros((1, 384, 4, 64))
    assert flash_attention_usable(q1, k1, v1, causal=True)
    qm = jnp.zeros((1, 1250, 4, 64))   # >1024, no fast divisor
    km = vm = jnp.zeros((1, 1250, 4, 64))
    assert not flash_attention_usable(qm, km, vm, causal=True)
    # multiple of 512 but not 1024 → fast divisor fallback keeps the kernel
    q2 = jnp.zeros((1, 1536, 4, 64))
    k2 = v2 = jnp.zeros((1, 1536, 4, 64))
    assert flash_attention_usable(q2, k2, v2, causal=True)
    q2 = jnp.zeros((1, 1, 4, 64))    # decode shape
    k2 = v2 = jnp.zeros((1, 256, 4, 64))
    assert not flash_attention_usable(q2, k2, v2, causal=True)
    # the gate reads ONE call's shapes; whether a call in a multi-device
    # process is per shard is the dispatcher's question: with no mesh and
    # no specs it keeps XLA attention (pjit would replicate the inputs)
    from deepspeed_tpu.ops.attention import attention_formulation

    q3 = jnp.zeros((1, 256, 4, 64))
    k3 = v3 = jnp.zeros((1, 256, 4, 64))
    assert flash_attention_usable(q3, k3, v3, causal=True)
    if jax.device_count() > 1:
        assert attention_formulation(q3, k3, v3)[0] == "xla"


def test_shape_validation():
    # blocks clamp to seq, so only long lengths with NO fast divisor fail
    # (1250 > 1024 and not a multiple of 1024/512/256); short seqs like 150
    # collapse to one block
    q = jnp.zeros((1, 1250, 4, 64))
    k = v = jnp.zeros((1, 1250, 4, 64))
    with pytest.raises(ValueError, match="cannot block"):
        flash_attention(q, k, v, causal=True)
    out = flash_attention(jnp.zeros((1, 150, 4, 64)),
                          jnp.zeros((1, 150, 4, 64)),
                          jnp.zeros((1, 150, 4, 64)), causal=True)
    assert out.shape == (1, 150, 4, 64)


def test_grads_merged_single_kv_block():
    """Default blocks with S <= 1024 route the backward through the merged
    single-launch dQ/dK/dV kernel — the path production training takes.
    Check grads vs the XLA oracle, incl. GQA head-group summing."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from deepspeed_tpu.ops.attention import _xla_attention
    from deepspeed_tpu.ops.pallas.flash_attention import flash_attention

    r = np.random.default_rng(4)
    B, S, H, KV, D = 2, 256, 4, 2, 64
    q = jnp.asarray(r.standard_normal((B, S, H, D)), jnp.float32)
    k = jnp.asarray(r.standard_normal((B, S, KV, D)), jnp.float32)
    v = jnp.asarray(r.standard_normal((B, S, KV, D)), jnp.float32)

    def loss_flash(q, k, v):   # default blocks → Skv == block_k → merged
        return jnp.sum(flash_attention(q, k, v, causal=True) ** 2)

    def loss_xla(q, k, v):
        return jnp.sum(_xla_attention(q, k, v, causal=True, positions=None,
                                      kv_len=None, mask=None) ** 2)

    gf = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
    gx = jax.grad(loss_xla, argnums=(0, 1, 2))(q, k, v)
    for name, a, b in zip("qkv", gf, gx):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=2e-3,
                                   err_msg=f"d{name}")


# ---- which formulation, and why: nothing about the device is silent --------

@pytest.mark.parametrize("platform,want", [
    ("cpu", True), ("tpu", False), ("gpu", RuntimeError),
    ("somebody_elses_plugin", RuntimeError)])
def test_interpret_mode_is_decided_in_one_place(platform, want, monkeypatch):
    """``ops.pallas.interpret_mode``: cpu interprets, tpu compiles, and any
    other platform is an ERROR — never a quiet interpreter run on a
    device nobody named."""
    import deepspeed_tpu.ops.pallas as pallas_pkg

    monkeypatch.setattr(jax, "default_backend", lambda: platform)
    if want is RuntimeError:
        with pytest.raises(RuntimeError, match=platform):
            pallas_pkg.interpret_mode()
    else:
        assert pallas_pkg.interpret_mode() is want


def _qkv(B=2, S=256, H=4, KV=4, D=64, Skv=None):
    sds = jax.ShapeDtypeStruct
    return (sds((B, S, H, D), jnp.bfloat16),
            sds((B, Skv or S, KV, D), jnp.bfloat16),
            sds((B, Skv or S, KV, D), jnp.bfloat16))


@pytest.mark.parametrize("qkv,kw,why", [
    (_qkv(), {}, ""),
    (_qkv(), {"positions": object()}, "cached/masked"),
    (_qkv(), {"mask": object()}, "cached/masked"),
    (_qkv(Skv=512), {}, "!= kv length"),
    (_qkv(S=64), {}, "sequence 64 <"),
    (_qkv(S=1280 + 8), {}, "no block divisor"),
    (_qkv(H=6, KV=4), {}, "not divisible by 4 kv heads"),
    (_qkv(D=16), {}, "head_dim 16"),
])
def test_flash_gate_names_its_reason(qkv, kw, why):
    """``flash_attention_unusable_reason`` reads the shapes ONE kernel call
    sees (abstract values serve; the device count is not its business) and
    says WHY the kernel cannot run them; "" = usable."""
    from deepspeed_tpu.ops.pallas.flash_attention import \
        flash_attention_unusable_reason

    got = flash_attention_unusable_reason(*qkv, causal=True, **kw)
    assert (got == "") if why == "" else (why in got), got
    assert flash_attention_usable(*qkv, causal=True, **kw) == (why == "")


def _mesh(**sizes):
    """A mesh of the suite's 8 virtual devices with the engine's axis
    names (``sizes`` must multiply to 8)."""
    from deepspeed_tpu.parallel.topology import MeshTopology

    return MeshTopology(sizes).mesh


def _on(mesh_sizes, q_spec, kv_spec=None):
    from deepspeed_tpu.ops.attention import AttentionSharding

    return AttentionSharding(_mesh(**mesh_sizes), q_spec, kv_spec or q_spec)


DP = ("data", "expert", "fsdp")
HEAD_AXES = ("tensor", "seq")


@pytest.mark.parametrize("qkv,kw,chosen,why", [
    # a GSPMD mesh, specs as the model states them: the kernel per shard
    (_qkv(B=8), lambda: {"sharding": _on(
        {"fsdp": 4, "data": 2}, P(DP, None, HEAD_AXES, None))}, "pallas", ""),
    (_qkv(B=8), lambda: {"impl": "xla", "sharding": _on(
        {"fsdp": 8, "data": 1}, P(DP, None, HEAD_AXES, None))},
     "xla", "config pin"),
    (_qkv(), lambda: {"bias": object()}, "xla", "alibi"),
    (_qkv(), lambda: {"window": 128}, "xla", "sliding window"),
    # nobody said where q, k, v lie: XLA, as on every mesh before PR 29
    (_qkv(), lambda: {}, "xla", "devices in this process"),
    # a sequence dimension still sharded at the call (no Ulysses
    # resharding): the kernel wants whole rows
    (_qkv(B=8), lambda: {"sharding": _on(
        {"seq": 2, "data": 4}, P(DP, "seq", "tensor", None))},
     "xla", "sequence dimension is sharded"),
    # GQA under a tensor axis: 8 kv heads over tensor 4 shard with the
    # query heads; 2 kv heads over tensor 4 would need picking by index
    (_qkv(B=8, H=16, KV=8), lambda: {"sharding": _on(
        {"tensor": 4, "data": 2}, P(DP, None, HEAD_AXES, None),
        P(DP, None, None, None))}, "pallas", ""),
    (_qkv(B=8, H=16, KV=2), lambda: {"sharding": _on(
        {"tensor": 4, "data": 2}, P(DP, None, HEAD_AXES, None),
        P(DP, None, None, None))}, "xla", "2 kv heads do not divide"),
    # one kv head (MQA) serves every shard whole
    (_qkv(B=8, H=16, KV=1), lambda: {"sharding": _on(
        {"tensor": 4, "data": 2}, P(DP, None, HEAD_AXES, None),
        P(DP, None, None, None))}, "pallas", ""),
    (_qkv(B=6), lambda: {"sharding": _on(
        {"fsdp": 4, "data": 2}, P(DP, None, HEAD_AXES, None))},
     "xla", "batch 6 x 4 heads do not divide"),
    # the gate runs on the PER-SHARD shapes and says so
    (_qkv(B=8, H=8, KV=8, D=32), lambda: {"sharding": _on(
        {"fsdp": 4, "tensor": 2}, P(DP, None, HEAD_AXES, None))},
     "xla", "head_dim 32 not in (64, 128, 256) (a shard's, of fsdp = 4 x "
            "tensor = 2)"),
    (_qkv(B=8, H=6, KV=3), lambda: {"sharding": _on(
        {"fsdp": 4, "tensor": 2}, P(DP, None, HEAD_AXES, None),
        P(DP, None, None, None))}, "xla", "3 kv heads do not divide"),
    # axes the step's own shard_map made manual (ZeRO++, 1-bit Adam) have
    # cut the shapes already: one device's worth is left, no second map
    (_qkv(B=2), lambda: {"manual_axes": ("data", "fsdp"), "sharding": _on(
        {"fsdp": 4, "data": 2}, P(None, None, HEAD_AXES, None))},
     "pallas", ""),
])
def test_attention_formulation_is_what_the_dispatcher_runs(qkv, kw, chosen,
                                                           why):
    from deepspeed_tpu.ops.attention import attention_formulation

    got = attention_formulation(*qkv, causal=True, **kw())
    assert got[0] == chosen and why in got[1], got


@pytest.mark.parametrize("preset,over,mesh,chosen,why", [
    # what PR 29 changed: 8 devices no longer refuse the kernel
    ("gpt2-350m", {}, {"fsdp": 8, "data": 1}, "pallas", ""),
    ("gpt2-350m", {}, {"fsdp": 2, "tensor": 2, "seq": 2}, "pallas", ""),
    # the reasons that remain under a mesh
    ("gpt2-350m", {"num_kv_heads": 2}, {"tensor": 4, "data": 2}, "xla",
     "2 kv heads do not divide over mesh axes ('tensor',) = 4"),
    ("gpt2-350m", {}, None, "xla", "no mesh or specs at the call"),
    ("tiny-bloom", {}, {"fsdp": 8, "data": 1}, "xla", "alibi"),
    ("mistral-7b", {}, {"fsdp": 8, "data": 1}, "xla", "sliding window"),
])
def test_training_engine_can_say_why_not_flash(preset, over, mesh, chosen,
                                               why):
    """What the training engine logs at build time
    (``engine.attention_formulation``): asked under the rules and the mesh
    it traces the model under (``mesh`` None: a caller that scoped none,
    as the v1 inference engine and the ZeRO-Infinity streamer)."""
    from contextlib import nullcontext

    import flax.linen as nn

    from deepspeed_tpu.models import get_model_config
    from deepspeed_tpu.models.transformer import (
        default_activation_rules, training_attention_formulation)
    from deepspeed_tpu.parallel.axes import model_mesh_scope

    with nn.logical_axis_rules(default_activation_rules(None)), \
            model_mesh_scope(_mesh(**mesh)) if mesh else nullcontext():
        got = training_attention_formulation(
            get_model_config(preset, **over), 8, 1024)
    assert got[0] == chosen and why in got[1], got


# ---- the kernel under a mesh: one step, per shard, against XLA attention ---

def _pallas_calls(jaxpr, mapped=False):
    """For every ``pallas_call`` in ``jaxpr``, whether a ``shard_map``
    encloses it."""
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pallas_call":
            yield mapped
        for sub in jax.core.jaxprs_in_params(eqn.params):
            yield from _pallas_calls(
                sub, mapped or eqn.primitive.name == "shard_map")


def _one_step(mesh, impl, heads, kv_heads, zero):
    """``(formulation, pallas_calls of the step's jaxpr, loss, grads)`` of
    one ``tiny-llama`` train step in float32 with plain SGD at lr 1, so
    that the change of the master weights IS the gradient."""
    import deepspeed_tpu as ds
    from deepspeed_tpu.models import build_model

    model = build_model("tiny-llama", hidden_size=64 * heads, num_heads=heads,
                        num_kv_heads=kv_heads, max_seq_len=128,
                        attn_impl=impl)
    engine, *_ = ds.initialize(model=model, config={
        "train_micro_batch_size_per_gpu": 2, "steps_per_print": 10_000,
        "optimizer": {"type": "SGD", "params": {"lr": 1.0}},
        "bf16": {"enabled": False}, "seed": 0, "mesh": mesh,
        "zero_optimization": zero})
    rng = np.random.default_rng(0)
    batch = {"input_ids": rng.integers(
        0, 256, (engine.config.train_batch_size, 128)).astype(np.int32)}
    staged = engine._shard_batch(engine._reshape_for_gas(batch),
                                 with_gas_dim=True)
    calls = [] if impl == "xla" else list(_pallas_calls(
        engine._train_step.trace(engine.state, staged).jaxpr.jaxpr))
    before = jax.tree.map(np.asarray, engine.state.params)
    loss = float(engine.train_batch(batch))
    grads = jax.tree.map(lambda a, b: a - np.asarray(b), before,
                         engine.state.params)
    return engine.attention_formulation, calls, loss, grads


ZERO3 = {"stage": 3}
#: qwZ alone: the weights' int8 round trip is the same on both sides and
#: the gradients reduce densely, so the two steps stay comparable at 5e-4
ZEROPP = {"stage": 3, "zero_quantized_weights": True}


@pytest.mark.parametrize("mesh,heads,kv_heads,zero,why_not", [
    ({"fsdp": 4, "data": 2}, 4, 2, ZERO3, ""),
    # GQA under a tensor axis: KV % tp == 0, the kv heads shard with q's
    ({"fsdp": 2, "tensor": 2, "data": 2}, 4, 2, ZERO3, ""),
    # KV % tp != 0: one kv head serves every shard whole; three over two
    # would have to be picked by the shard's index — refused, and said
    ({"fsdp": 2, "tensor": 2, "data": 2}, 4, 1, ZERO3, ""),
    ({"fsdp": 2, "tensor": 2, "data": 2}, 6, 3, ZERO3,
     "3 kv heads do not divide over mesh axes ('tensor',) = 2"),
    # Ulysses: heads over the seq axis at the call, rows whole
    ({"seq": 2, "data": 4}, 4, 2, ZERO3, ""),
    # ZeRO++: the step's own shard_map made the DP axes manual
    ({"fsdp": 4, "data": 2}, 4, 2, ZEROPP, ""),
], ids=["fsdp4", "fsdp2-tensor2-kv2", "fsdp2-tensor2-kv1",
        "fsdp2-tensor2-kv3-refused", "ulysses-seq2", "zeropp-fsdp4"])
def test_mesh_step_runs_the_kernel_per_shard(mesh, heads, kv_heads, zero,
                                             why_not):
    """Under a mesh of more than one device ``attn_impl="auto"`` trains on
    the flash kernel, one call a shard inside a ``shard_map`` — or says
    why not — and the step's loss and gradients are XLA attention's."""
    said, calls, loss, grads = _one_step(mesh, "auto", heads, kv_heads, zero)
    pin, _, ref_loss, ref_grads = _one_step(mesh, "xla", heads, kv_heads,
                                            zero)
    assert pin == ("xla", "attn_impl='xla' (config pin)")
    if why_not:
        assert said[0] == "xla" and why_not in said[1] and not calls
    else:
        assert said == ("pallas", "")
        assert calls and all(calls), "a pallas_call outside any shard_map"
    np.testing.assert_allclose(loss, ref_loss, atol=5e-4, rtol=5e-4)
    flat, _ = jax.tree_util.tree_flatten_with_path(grads)
    for (path, g), r in zip(flat, jax.tree.leaves(ref_grads)):
        np.testing.assert_allclose(g, r, atol=5e-4, rtol=5e-4,
                                   err_msg=jax.tree_util.keystr(path))
