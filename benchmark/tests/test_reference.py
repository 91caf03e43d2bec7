"""reference/dense_decoder.py against the program's TransformerLM in
float32 at a tiny width on the CPU: same weights, same tokens, the same
logits and the same loss to float32 rounding."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.reference import dense_decoder as ref


@pytest.fixture(scope="module")
def tiny():
    from deepspeed_tpu.models import build_model, cross_entropy_lm
    from deepspeed_tpu.runtime.zero.planner import unbox_params

    model = build_model("tiny-llama", dtype=jnp.float32, num_layers=3,
                        rope_theta=1e6, attn_impl="xla")
    tokens = np.random.default_rng(0).integers(0, 256, (1, 48)).astype(np.int32)
    params = unbox_params(model.init(jax.random.PRNGKey(3), tokens)["params"])
    return model, params, tokens, cross_entropy_lm


def reference_logits(model, params, row, rows=None):
    m = model.config
    return ref.forward_logits(
        row, embed=params["embed"], layer=lambda i: ref.program_layer(params, i),
        num_layers=m.num_layers, ln_final=params["ln_final"]["scale"],
        unembed=params["unembed"], theta=float(m.rope_theta),
        eps=float(m.norm_eps), rows=rows)


def test_logits_match_transformer_lm(tiny):
    model, params, tokens, _ = tiny
    with jax.default_matmul_precision("highest"):
        want = np.asarray(model.apply({"params": params}, tokens))[0]
    got = np.asarray(reference_logits(model, params, tokens[0]))
    assert got.shape == want.shape == (48, 256)
    np.testing.assert_allclose(got, want, atol=2e-5, rtol=1e-4)
    # GQA (4 query heads over 2 KV heads) and rotary positions are live:
    # shuffling the tokens' order must change the last row
    shuffled = np.asarray(reference_logits(model, params, tokens[0][::-1]))
    assert np.abs(shuffled[-1] - got[-1]).max() > 1e-3


def test_rows_select_before_the_head(tiny):
    model, params, tokens, _ = tiny
    full = np.asarray(reference_logits(model, params, tokens[0]))
    some = np.asarray(reference_logits(model, params, tokens[0], rows=[5, 47]))
    np.testing.assert_allclose(some, full[[5, 47]], atol=1e-6)


def test_padding_after_the_sequence_changes_nothing(tiny):
    model, params, tokens, _ = tiny
    padded = np.concatenate([tokens[0], np.zeros(16, np.int32)])
    a = np.asarray(reference_logits(model, params, tokens[0], rows=[47]))
    b = np.asarray(reference_logits(model, params, padded, rows=[47]))
    np.testing.assert_allclose(a, b, atol=1e-6)


def test_loss_matches_the_engine_loss(tiny):
    model, params, tokens, cross_entropy_lm = tiny
    logits = model.apply({"params": params}, tokens)
    labels = np.concatenate([tokens[:, 1:], np.full((1, 1), -100)], axis=1)
    want = float(cross_entropy_lm(logits, jnp.asarray(labels)))
    got = float(ref.lm_loss(reference_logits(model, params, tokens[0]),
                            tokens[0]))
    assert got == pytest.approx(want, rel=1e-5)
