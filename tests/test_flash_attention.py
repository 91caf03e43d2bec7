"""Pallas flash-attention numerics vs the XLA oracle (role of reference
tests/unit/ops/transformer/ kernel tests). Runs in interpret mode on CPU."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

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
    assert flash_attention_usable(q, k, v, causal=causal,
                                  allow_multi_device=True)
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
    assert not flash_attention_usable(q, k, v, causal=True,
                                      allow_multi_device=True)
    q1 = jnp.zeros((1, 384, 4, 64))
    k1 = v1 = jnp.zeros((1, 384, 4, 64))
    assert flash_attention_usable(q1, k1, v1, causal=True,
                                  allow_multi_device=True)
    qm = jnp.zeros((1, 1250, 4, 64))   # >1024, no fast divisor
    km = vm = jnp.zeros((1, 1250, 4, 64))
    assert not flash_attention_usable(qm, km, vm, causal=True,
                                      allow_multi_device=True)
    # multiple of 512 but not 1024 → fast divisor fallback keeps the kernel
    q2 = jnp.zeros((1, 1536, 4, 64))
    k2 = v2 = jnp.zeros((1, 1536, 4, 64))
    assert flash_attention_usable(q2, k2, v2, causal=True,
                                  allow_multi_device=True)
    q2 = jnp.zeros((1, 1, 4, 64))    # decode shape
    k2 = v2 = jnp.zeros((1, 256, 4, 64))
    assert not flash_attention_usable(q2, k2, v2, causal=True,
                                      allow_multi_device=True)
    # multi-device default: kernel not claimed (pjit would replicate inputs)
    q3 = jnp.zeros((1, 256, 4, 64))
    k3 = v3 = jnp.zeros((1, 256, 4, 64))
    if jax.device_count() > 1:
        assert not flash_attention_usable(q3, k3, v3, causal=True)


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
    (_qkv(), {"allow_multi_device": False}, "devices in this process"),
    (_qkv(), {"mask": object()}, "cached/masked"),
    (_qkv(Skv=512), {}, "!= kv length"),
    (_qkv(S=64), {}, "sequence 64 <"),
    (_qkv(S=1280 + 8), {}, "no block divisor"),
    (_qkv(H=6, KV=4), {}, "not divisible by 4 kv heads"),
    (_qkv(D=16), {}, "head_dim 16"),
])
def test_flash_gate_names_its_reason(qkv, kw, why):
    """``flash_attention_unusable_reason`` reads shapes only (abstract
    values serve) and says WHY the kernel is not claimed; "" = usable.
    (The suite runs on 8 virtual devices, hence allow_multi_device.)"""
    from deepspeed_tpu.ops.pallas.flash_attention import \
        flash_attention_unusable_reason

    got = flash_attention_unusable_reason(
        *qkv, causal=True, **{"allow_multi_device": True, **kw})
    assert (got == "") if why == "" else (why in got), got
    assert flash_attention_usable(
        *qkv, causal=True, **{"allow_multi_device": True, **kw}) \
        == (why == "")


@pytest.mark.parametrize("kw,chosen,why", [
    ({"allow_multi_device": True}, "pallas", ""),
    ({"allow_multi_device": True, "impl": "xla"}, "xla", "config pin"),
    ({"allow_multi_device": True, "bias": object()}, "xla", "alibi"),
    ({"allow_multi_device": True, "window": 128}, "xla", "sliding window"),
    ({}, "xla", "devices in this process"),
])
def test_attention_formulation_is_what_the_dispatcher_runs(kw, chosen, why):
    from deepspeed_tpu.ops.attention import attention_formulation

    got = attention_formulation(*_qkv(), causal=True, **kw)
    assert got[0] == chosen and why in got[1]


@pytest.mark.parametrize("preset,why", [
    ("gpt2-350m", "devices in this process"),     # 8 virtual devices here
    ("tiny-bloom", "alibi"),
    ("mistral-7b", "sliding window"),
])
def test_training_engine_can_say_why_not_flash(preset, why):
    """What the training engine logs at build time
    (``engine.attention_formulation``)."""
    from deepspeed_tpu.models import get_model_config
    from deepspeed_tpu.models.transformer import \
        training_attention_formulation

    chosen, reason = training_attention_formulation(
        get_model_config(preset), 8, 1024)
    assert chosen == "xla" and why in reason
