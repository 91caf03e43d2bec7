"""LFM2-MoE's serving forms beside the float32 one of
``tests/test_lfm2_moe.py`` (same harness: the engine's own programs, their
logits tapped, held to the plain reference): bfloat16 compute, int8 weights,
heads 64 wide through the Pallas paged kernel (interpreted) — and every
refusal of what a record a slot cannot do, with its message.

TOLERANCE. The bfloat16 engine reads 0.010-0.018 (three seeded request
sets, the CPU) against the float32 reference on logits of magnitude ~1:
``BF16_BAND`` = 0.06, three times the largest reading, holds it; the
float32 bound ``ATOL`` is 300 times tighter, and the test holds both ends.
int8 weights on float32 compute read 0.011-0.020: held to the same band,
and over ``ATOL`` (the quantisation is seen).
"""
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from tests.test_lfm2_moe import (ATOL, CHUNK, ENGINE, Tap, _prompt, build,
                                 hold_to_the_reference, serve)

BF16_BAND = 0.06


@pytest.fixture(scope="module")
def tiny():
    return build()


def test_bf16_engine_sits_in_its_band(tiny, monkeypatch):
    """The float32 tolerance tells precisions apart: the same engine in
    bfloat16 leaves it by orders, and stays inside the stated bf16 band."""
    model, params, _ = tiny
    tap = Tap(monkeypatch)
    rng = np.random.default_rng(6)
    requests = {1: (_prompt(rng, 2 * CHUNK + 3), 9), 2: (_prompt(rng, 2), 9)}
    low = jax.tree.map(lambda a: a.astype(jnp.bfloat16), params)
    _, out = serve(build(jnp.bfloat16)[0], low, tap, requests,
                   dtype=jnp.bfloat16, decode_window=4)
    worst = hold_to_the_reference(model, low, tap, requests, out,
                                  atol=BF16_BAND)
    assert worst > 10 * ATOL


def test_heads_of_64_serve_through_the_paged_kernel(monkeypatch):
    """Tentpole C on the CPU: heads 64 wide (the published width) through
    the Pallas paged kernel, interpreted, against the reference's 8... here
    2 KV heads of 64 — windows and chunks both."""
    model, params, _ = build(head_size=64)
    tap = Tap(monkeypatch)
    rng = np.random.default_rng(7)
    requests = {1: (_prompt(rng, 2 * CHUNK + 5), 6), 2: (_prompt(rng, 3), 6)}
    eng, out = serve(model, params, tap, requests, decode_window=4)
    assert eng._attn_decode_sel.is_pallas and model.config.head_dim == 64
    hold_to_the_reference(model, params, tap, requests, out)


def test_int8_weights_serve_the_mixed_stack(tiny, monkeypatch):
    """``quant_bits`` 8 on a stack of unlike layers: attention, the dense
    feed-forward (of its own width) and the experts quantise, the conv
    operator stays exact; the logits stay near the reference."""
    model, params, _ = tiny
    tap = Tap(monkeypatch)
    rng = np.random.default_rng(8)
    requests = {1: (_prompt(rng, CHUNK + 3), 5)}
    eng, out = serve(model, params, tap, requests, quant_bits=8,
                     decode_window=4)
    assert "w_in" in eng.params["layer_0"]["conv"]
    worst = hold_to_the_reference(model, params, tap, requests, out,
                                  atol=BF16_BAND)
    assert worst > ATOL


# ---------------------------------------------------------------------------
# what a record cannot do is refused, by name
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def engine(tiny):
    from deepspeed_tpu.inference.engine_v2 import InferenceEngineV2

    model, params, _ = tiny
    eng = InferenceEngineV2(model, params=jax.tree.map(jnp.copy, params),
                            config=ENGINE, rng=jax.random.PRNGKey(0))
    eng.put(1, list(range(1, 30)), max_new_tokens=4)
    eng.step()
    return eng


WHY = "kind 'conv' keeps a record a slot"


def test_the_state_is_not_a_page_chain(engine):
    assert WHY in engine.state.not_a_page_chain
    assert "ring" not in engine.state.not_a_page_chain
    assert engine._prefix_cache is None              # auto: off
    assert engine.scheduler.grow_chunk is False      # rows-only packing
    assert all(T == CHUNK for T, _ in engine.scheduler.program_shape_menu())
    assert engine.can_import(16, 4) is False


@pytest.mark.parametrize("config, match", [
    ({"prefix_cache": True}, "prefix_cache=True needs"),
    ({"spec_decode": "ngram"}, "spec_decode needs"),
])
def test_engine_refuses_at_build(tiny, config, match):
    from deepspeed_tpu.inference.engine_v2 import InferenceEngineV2

    model, params, _ = tiny
    with pytest.raises(ValueError, match=match) as e:
        InferenceEngineV2(model, params=jax.tree.map(jnp.copy, params),
                          config={**ENGINE, **config},
                          rng=jax.random.PRNGKey(0))
    assert WHY in str(e.value)


def test_page_export_import_and_rewind_are_refused(engine):
    from deepspeed_tpu.inference.migration import MigrationError, PageBundle

    with pytest.raises(RuntimeError, match="page migration requires") as e:
        engine.export_migration(1)
    assert WHY in str(e.value)
    meta = PageBundle.prefix("t", [1] * 8, 8, "float32", 64, [b"x" * 64]
                             ).meta()
    with pytest.raises(MigrationError, match="cannot import page chains"):
        engine.import_reserve(9, meta)
    with pytest.raises(MigrationError, match="no shareable prefix cache"):
        engine.export_prefix([1] * 16)
    with pytest.raises(RuntimeError, match="rewind needs page chains"):
        engine.state.rewind(1, list(range(1, 10)))


def test_audit_sees_the_records(engine):
    st = engine.state
    assert st.kinds["conv"].record_rows == 2
    assert st.kinds["conv"].allocator is None
    assert st.sample()["conv"] == 1                  # one record live
    st.audit()
    # admission is "in every kind or none", and this kind never refuses
    assert "conv" not in st._more_need(64)
    seq = st.seqs[1]
    st._free_slots.append(seq.slot)                  # a slot held twice
    with pytest.raises(AssertionError, match="conv records"):
        st.audit()
    st._free_slots.remove(seq.slot)
    st.audit()


def test_the_cache_line_names_the_record_kind(tiny, caplog):
    import logging

    from deepspeed_tpu.inference.engine_v2 import InferenceEngineV2
    from deepspeed_tpu.utils.logging import logger

    model, params, _ = tiny
    logger.addHandler(caplog.handler)
    try:
        with caplog.at_level(logging.INFO, logger=logger.name):
            InferenceEngineV2(model, params=jax.tree.map(jnp.copy, params),
                              config=ENGINE, rng=jax.random.PRNGKey(0))
    finally:
        logger.removeHandler(caplog.handler)
    line = next(r.getMessage() for r in caplog.records
                if r.getMessage().startswith("cache:"))
    assert re.search(r"full: 1 layer\(s\), pool 96 blocks of 8", line)
    assert re.search(r"conv: 4 layer\(s\), a record of 2 x 64 a slot, "
                     r"4 slots, 8192 bytes", line)
