"""Pallas flash-attention numerics vs the XLA oracle (role of reference
tests/unit/ops/transformer/ kernel tests). Runs in interpret mode on CPU."""
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from deepspeed_tpu.ops.attention import _xla_attention
from deepspeed_tpu.ops.pallas.flash_attention import (
    flash_attention, flash_attention_usable, flash_plan)

#: the module itself (the package re-exports the function under its name)
FLASH = sys.modules[flash_attention.__module__]


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
    """Default blocks route the backward through the merged single-launch
    dQ/dK/dV kernel — the path production training takes.
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

    def loss_flash(q, k, v):   # default blocks → K, V resident → merged
        return jnp.sum(flash_attention(q, k, v, causal=True) ** 2)

    def loss_xla(q, k, v):
        return jnp.sum(_xla_attention(q, k, v, causal=True, positions=None,
                                      kv_len=None, mask=None) ** 2)

    gf = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
    gx = jax.grad(loss_xla, argnums=(0, 1, 2))(q, k, v)
    for name, a, b in zip("qkv", gf, gx):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=2e-3,
                                   err_msg=f"d{name}")


# ---- compute tiles inside a block, and the backward's two forms (PR 35) -----

def _loss_and_grads(attn, q, k, v):
    return jax.value_and_grad(
        lambda q, k, v: jnp.sum(attn(q, k, v) ** 2), argnums=(0, 1, 2))(
            q, k, v)


def _xla(causal):
    return lambda q, k, v: _xla_attention(
        q, k, v, causal=causal, positions=None, kv_len=None, mask=None)


def _qkv_arrays(S, H, KV, D, seed=0, B=1):
    ks = jax.random.split(jax.random.PRNGKey(seed), 3)
    return (_rand((B, S, H, D), ks[0]), _rand((B, S, KV, D), ks[1]),
            _rand((B, S, KV, D), ks[2]))


@pytest.mark.parametrize("tiles,H,KV,D,causal", [
    ((128, 128), 4, 1, 64, True),       # MQA
    ((128, 256), 8, 2, 128, True),      # GQA 4
    ((128, 128), 2, 2, 128, True),      # group 1
    ((128, 128), 2, 2, 64, False),
    ((128, 256), 4, 1, 128, False),
    ((256, 128), 8, 2, 64, True),
    ((None, None), 4, 2, 64, True),     # the plan's own tiles
], ids=["mqa-128x128-d64", "gqa4-128x256-d128", "group1-128x128-d128",
        "group1-128x128-d64-full", "mqa-128x256-d128-full",
        "gqa4-256x128-d64", "default-tiles"])
def test_many_tiles_under_one_block_match_xla(tiles, H, KV, D, causal):
    """Sequence 512 is ONE block; pinned tiles make the block the diagonal
    crosses a walk of several compute tiles (widths 128..512). Forward and
    all three gradients against XLA attention."""
    q, k, v = _qkv_arrays(512, H, KV, D)
    plan = flash_plan(q.shape, k.shape, q.dtype, causal, *tiles)
    assert (plan.block_q, plan.block_k, plan.backward) == (512, 512, "merged")
    if tiles[0] and causal:
        assert plan.tiles_computed < plan.tiles_in_square
    got, g_got = _loss_and_grads(
        lambda q, k, v: flash_attention(q, k, v, causal=causal,
                                        block_q=tiles[0], block_k=tiles[1]),
        q, k, v)
    want, g_want = _loss_and_grads(_xla(causal), q, k, v)
    np.testing.assert_allclose(got, want, rtol=2e-5)
    for a, b, name in zip(g_got, g_want, "qkv"):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=5e-4,
                                   rtol=5e-4, err_msg=f"d{name}")


@pytest.mark.parametrize("KV,causal", [(1, True), (2, True), (4, False)],
                         ids=["mqa", "gqa2", "group1-full"])
def test_split_backward_pair_equals_the_merged_kernel(KV, causal,
                                                      monkeypatch):
    """A sequence whose K, V and dk/dv scratch do not fit the budget takes
    the split dq + dk/dv pair over key blocks — forced here by patching the
    budget under what the merged kernel needs (and the block menu down, so
    that sequence 512 is four blocks of the square). Same inputs, same
    gradients as the merged kernel, and as XLA attention."""
    q, k, v = _qkv_arrays(512, 4, KV, 64, seed=3)
    monkeypatch.setattr(FLASH, "_FAST_BLOCKS", (256, 128))
    merged = flash_plan(q.shape, k.shape, q.dtype, causal)
    assert (merged.block_q, merged.block_k, merged.backward) \
        == (256, 256, "merged")
    flash = lambda q, k, v: flash_attention(q, k, v, causal=causal)
    got_m, g_m = _loss_and_grads(flash, q, k, v)

    need = FLASH._vmem_merged(256, 256, 512, 64, 4)
    assert FLASH._vmem_split(256, 256, 64, 4) < need
    monkeypatch.setattr(FLASH, "VMEM_BUDGET_BYTES", need - 1)
    split = flash_plan(q.shape, k.shape, q.dtype, causal)
    assert split.backward == "split" and not split.resident
    assert split[:4] == merged[:4]
    got_s, g_s = _loss_and_grads(flash, q, k, v)

    want, g_want = _loss_and_grads(_xla(causal), q, k, v)
    np.testing.assert_allclose(got_s, got_m, rtol=1e-6)
    for a, b, c, name in zip(g_s, g_m, g_want, "qkv"):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-5,
                                   rtol=1e-5, err_msg=f"d{name} split/merged")
        np.testing.assert_allclose(np.asarray(a), np.asarray(c), atol=5e-4,
                                   rtol=5e-4, err_msg=f"d{name} split/xla")


def test_budget_decides_the_backward_form(monkeypatch):
    """Beyond the budget no block size is refused a shape it fits: the
    split pair's blocks shrink; pinned tiles are the caller's tradeoff."""
    shape = (1, 2048, 8, 256)
    assert flash_plan(shape, shape, jnp.float32, True).backward == "merged"
    monkeypatch.setattr(FLASH, "VMEM_BUDGET_BYTES", 12 * 1024 * 1024)
    plan = flash_plan(shape, shape, jnp.float32, True)
    assert plan.backward == "split" and plan.block_q * plan.block_k \
        < 1024 * 1024
    assert FLASH._vmem_split(plan.block_q, plan.block_k, 256, 4) \
        <= FLASH.VMEM_BUDGET_BYTES
    pinned = flash_plan(shape, shape, jnp.float32, True, 256, 256)
    assert (pinned.block_q, pinned.block_k, pinned.tile_q) \
        == (1024, 1024, 256)
    monkeypatch.setattr(FLASH, "VMEM_BUDGET_BYTES", 1024 * 1024)
    assert flash_plan(shape, shape, jnp.float32, True) is None


@pytest.mark.parametrize("S,D,causal,tiles", [
    (2048, 128, True, (None, None)),    # the train cell's shard
    (1024, 64, True, (None, None)),     # gpt2-350m's rows
    (8192, 64, True, (None, None)),
    (1536, 128, True, (None, None)),    # blocks of 512
    (512, 64, True, (128, 128)),
    (512, 64, True, (128, 256)),
    (1024, 128, True, (256, 128)),
    (384, 64, True, (None, None)),
    (2048, 128, False, (None, None)),
])
def test_plan_counts_the_tiles_the_mask_leaves(S, D, causal, tiles):
    """``tiles_computed`` (backward) and ``fwd_tiles_computed`` against a
    count by brute force over the mask: a ``tile_q x tile_k`` tile is
    computed iff some (query, key) pair of it is unmasked — or, where the
    forward walks whole rows of a block (``fwd_tile_q``), iff some pair of
    its ``fwd_tile_q x tile_k`` stripe is."""
    shape = (1, S, 4, D)
    plan = flash_plan(shape, shape, jnp.bfloat16, causal, *tiles)
    allow = np.tril(np.ones((S, S), bool)) if causal \
        else np.ones((S, S), bool)

    def brute(rows):
        seen = allow.reshape(S // rows, rows, S // plan.tile_k,
                             plan.tile_k).any(axis=(1, 3))
        return int(seen.sum()) * rows // plan.tile_q

    assert plan.tiles_in_square == (S // plan.tile_q) * (S // plan.tile_k)
    assert plan.tiles_computed == brute(plan.tile_q)
    assert plan.fwd_tiles_computed == brute(plan.fwd_tile_q)
    assert abs(plan.causal_need - allow.mean()) < 1e-12
    assert plan.causal_need <= plan.computed_share <= 1.0
    if (S, D, tiles) == (2048, 128, (None, None)) and causal:
        assert plan.backward == "merged" and plan.computed_share <= 0.625 \
            and plan.fwd_tile_q == plan.block_q
        assert "backward merged" in plan.describe()


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
    # long rows: K and V resident at 8192, key blocks and the split pair
    # at 16384 — admitted either way, as before PR 35
    (_qkv(B=1, S=8192, H=16, KV=16), {}, ""),
    (_qkv(B=1, S=16384, H=16, KV=16), {}, ""),
    (_qkv(B=1, S=4096, H=8, KV=8, D=256), {}, ""),
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
        default_activation_rules, training_attention_formulation,
        training_flash_plan)
    from deepspeed_tpu.parallel.axes import model_mesh_scope

    with nn.logical_axis_rules(default_activation_rules(None)), \
            model_mesh_scope(_mesh(**mesh)) if mesh else nullcontext():
        got = training_attention_formulation(
            get_model_config(preset, **over), 8, 1024)
        plan = training_flash_plan(get_model_config(preset, **over), 8, 1024)
    assert got[0] == chosen and why in got[1], got
    # the ``flash:`` line's plan exists exactly where the kernel runs, and
    # is the launcher's: made from a shard's shapes
    assert (plan is not None) == (chosen == "pallas")
    if plan is not None:
        assert plan.backward == "merged" and plan.block_k == 1024


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
    # one ``flash:`` log line, from the launcher's own plan of a shard
    assert (engine.flash_plan is not None) \
        == (engine.attention_formulation[0] == "pallas")
    if engine.flash_plan is not None:
        assert engine.flash_plan[:5] == (128, 128, 128, 128, "merged")
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
