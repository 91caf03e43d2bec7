"""HF checkpoint import: converted weights reproduce the transformers
forward numerically (the correctness contract module_inject's policies
carry in the reference — here proven against torch directly)."""
import dataclasses
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

torch = pytest.importorskip("torch")
transformers = pytest.importorskip("transformers")


def _logits_ours(model, params, ids):
    out = model.apply({"params": params}, jnp.asarray(ids))
    return np.asarray(out, np.float32)


def test_gpt2_import_matches_torch_forward():
    from deepspeed_tpu.models.hf import from_hf_model

    hf_cfg = transformers.GPT2Config(
        vocab_size=128, n_positions=64, n_embd=64, n_layer=2, n_head=4)
    hf = transformers.GPT2LMHeadModel(hf_cfg).eval()
    model, params = from_hf_model(hf, dtype=jnp.float32)

    ids = np.random.default_rng(0).integers(0, 128, (2, 16)).astype(np.int32)
    with torch.no_grad():
        ref = hf(torch.from_numpy(ids).long()).logits.numpy()
    got = _logits_ours(model, params, ids)
    np.testing.assert_allclose(got, ref, atol=2e-4)


def test_llama_import_matches_torch_forward():
    from deepspeed_tpu.models.hf import from_hf_model

    hf_cfg = transformers.LlamaConfig(
        vocab_size=128, hidden_size=64, intermediate_size=128,
        num_hidden_layers=2, num_attention_heads=4, num_key_value_heads=2,
        max_position_embeddings=64, rms_norm_eps=1e-5, tie_word_embeddings=False)
    hf = transformers.LlamaForCausalLM(hf_cfg).eval()
    model, params = from_hf_model(hf, dtype=jnp.float32)

    ids = np.random.default_rng(1).integers(0, 128, (2, 16)).astype(np.int32)
    with torch.no_grad():
        ref = hf(torch.from_numpy(ids).long()).logits.numpy()
    got = _logits_ours(model, params, ids)
    np.testing.assert_allclose(got, ref, atol=2e-4)


def test_mistral_gqa_import_matches_torch_forward():
    from deepspeed_tpu.models.hf import from_hf_model

    hf_cfg = transformers.MistralConfig(
        vocab_size=128, hidden_size=64, intermediate_size=128,
        num_hidden_layers=2, num_attention_heads=4, num_key_value_heads=2,
        max_position_embeddings=64, rms_norm_eps=1e-5,
        tie_word_embeddings=False, sliding_window=None)
    hf = transformers.MistralForCausalLM(hf_cfg).eval()
    model, params = from_hf_model(hf, dtype=jnp.float32)

    ids = np.random.default_rng(2).integers(0, 128, (1, 24)).astype(np.int32)
    with torch.no_grad():
        ref = hf(torch.from_numpy(ids).long()).logits.numpy()
    got = _logits_ours(model, params, ids)
    np.testing.assert_allclose(got, ref, atol=2e-4)


def test_tied_llama_import_skips_unembed():
    from deepspeed_tpu.models.hf import from_hf_model

    hf = transformers.LlamaForCausalLM(transformers.LlamaConfig(
        vocab_size=128, hidden_size=64, intermediate_size=128,
        num_hidden_layers=2, num_attention_heads=4, num_key_value_heads=2,
        max_position_embeddings=64, tie_word_embeddings=True)).eval()
    model, params = from_hf_model(hf, dtype=jnp.float32)
    assert "unembed" not in params          # tied: embed serves both ends
    ids = np.random.default_rng(3).integers(0, 128, (1, 12)).astype(np.int32)
    with torch.no_grad():
        ref = hf(torch.from_numpy(ids).long()).logits.numpy()
    got = _logits_ours(model, params, ids)
    np.testing.assert_allclose(got, ref, atol=2e-4)


def test_mistral_sliding_window_matches_torch_forward():
    """A BINDING sliding window (window < sequence length) reproduces the
    torch forward — the real mistral-7b case round-1 rejected (reference
    inference/v2/model_implementations/mistral/)."""
    from deepspeed_tpu.models.hf import from_hf_model

    hf_cfg = transformers.MistralConfig(
        vocab_size=128, hidden_size=64, intermediate_size=128,
        num_hidden_layers=2, num_attention_heads=4, num_key_value_heads=2,
        max_position_embeddings=64, rms_norm_eps=1e-5,
        tie_word_embeddings=False, sliding_window=8,
        attn_implementation="eager")
    hf = transformers.MistralForCausalLM(hf_cfg).eval()
    model, params = from_hf_model(hf, dtype=jnp.float32)
    assert model.config.sliding_window == 8

    # S=24 >> window=8: logits past the window depend on the mask
    ids = np.random.default_rng(4).integers(0, 128, (2, 24)).astype(np.int32)
    with torch.no_grad():
        ref = hf(torch.from_numpy(ids).long()).logits.numpy()
    got = _logits_ours(model, params, ids)
    np.testing.assert_allclose(got, ref, atol=2e-4)

    # sanity: the window actually binds (plain-causal logits differ)
    import dataclasses

    dense = model.clone(config=dataclasses.replace(model.config,
                                                   sliding_window=None))
    got_dense = _logits_ours(dense, params, ids)
    assert np.abs(got_dense - got).max() > 1e-3


def test_non_binding_sliding_window_accepted():
    from deepspeed_tpu.models.hf import config_from_hf

    cfg = transformers.MistralConfig(
        vocab_size=128, hidden_size=64, intermediate_size=128,
        num_hidden_layers=2, num_attention_heads=4, num_key_value_heads=2,
        max_position_embeddings=4096, sliding_window=4096)
    assert config_from_hf(cfg).sliding_window is None


def test_qwen2_import_matches_torch_forward():
    from deepspeed_tpu.models.hf import from_hf_model

    hf_cfg = transformers.Qwen2Config(
        vocab_size=128, hidden_size=64, intermediate_size=128,
        num_hidden_layers=2, num_attention_heads=4, num_key_value_heads=2,
        max_position_embeddings=64, rms_norm_eps=1e-5,
        tie_word_embeddings=False, use_sliding_window=False)
    hf = transformers.Qwen2ForCausalLM(hf_cfg).eval()
    model, params = from_hf_model(hf, dtype=jnp.float32)
    assert model.config.qkv_bias

    ids = np.random.default_rng(5).integers(0, 128, (2, 16)).astype(np.int32)
    with torch.no_grad():
        ref = hf(torch.from_numpy(ids).long()).logits.numpy()
    got = _logits_ours(model, params, ids)
    np.testing.assert_allclose(got, ref, atol=2e-4)


def test_mixtral_import_matches_torch_forward():
    from deepspeed_tpu.models.hf import from_hf_model

    hf_cfg = transformers.MixtralConfig(
        vocab_size=128, hidden_size=64, intermediate_size=128,
        num_hidden_layers=2, num_attention_heads=4, num_key_value_heads=2,
        max_position_embeddings=64, rms_norm_eps=1e-5,
        tie_word_embeddings=False, num_local_experts=4,
        num_experts_per_tok=2, sliding_window=None)
    hf = transformers.MixtralForCausalLM(hf_cfg).eval()
    model, params = from_hf_model(hf, dtype=jnp.float32)

    ids = np.random.default_rng(6).integers(0, 128, (1, 16)).astype(np.int32)
    with torch.no_grad():
        ref = hf(torch.from_numpy(ids).long()).logits.numpy()
    got = _logits_ours(model, params, ids)
    np.testing.assert_allclose(got, ref, atol=3e-4)


def test_falcon_import_matches_torch_forward():
    from deepspeed_tpu.models.hf import from_hf_model

    hf_cfg = transformers.FalconConfig(
        vocab_size=128, hidden_size=64, num_hidden_layers=2,
        num_attention_heads=4, multi_query=True, parallel_attn=True,
        new_decoder_architecture=False, bias=False, alibi=False,
        max_position_embeddings=64, layer_norm_epsilon=1e-5)
    hf = transformers.FalconForCausalLM(hf_cfg).eval()
    model, params = from_hf_model(hf, dtype=jnp.float32)
    assert model.config.kv_heads == 1 and model.config.parallel_block

    ids = np.random.default_rng(7).integers(0, 128, (2, 16)).astype(np.int32)
    with torch.no_grad():
        ref = hf(torch.from_numpy(ids).long()).logits.numpy()
    got = _logits_ours(model, params, ids)
    np.testing.assert_allclose(got, ref, atol=2e-4)


def test_bloom_import_matches_torch_forward():
    from deepspeed_tpu.models.hf import from_hf_model

    hf_cfg = transformers.BloomConfig(
        vocab_size=128, hidden_size=64, n_layer=2, n_head=4,
        layer_norm_epsilon=1e-5)
    hf = transformers.BloomForCausalLM(hf_cfg).eval()
    model, params = from_hf_model(hf, dtype=jnp.float32)
    assert model.config.position_embedding == "alibi"
    assert model.config.embed_norm and "ln_embed" in params

    ids = np.random.default_rng(8).integers(0, 128, (2, 16)).astype(np.int32)
    with torch.no_grad():
        ref = hf(torch.from_numpy(ids).long()).logits.numpy()
    got = _logits_ours(model, params, ids)
    np.testing.assert_allclose(got, ref, atol=2e-4)


def test_opt_import_matches_torch_forward():
    from deepspeed_tpu.models.hf import from_hf_model

    hf_cfg = transformers.OPTConfig(
        vocab_size=128, hidden_size=64, ffn_dim=128, num_hidden_layers=2,
        num_attention_heads=4, max_position_embeddings=64,
        word_embed_proj_dim=64, do_layer_norm_before=True)
    hf = transformers.OPTForCausalLM(hf_cfg).eval()
    model, params = from_hf_model(hf, dtype=jnp.float32)
    assert model.config.activation == "relu"

    ids = np.random.default_rng(9).integers(0, 128, (2, 16)).astype(np.int32)
    with torch.no_grad():
        ref = hf(torch.from_numpy(ids).long()).logits.numpy()
    got = _logits_ours(model, params, ids)
    np.testing.assert_allclose(got, ref, atol=2e-4)


def test_phi_import_matches_torch_forward():
    from deepspeed_tpu.models.hf import from_hf_model

    hf_cfg = transformers.PhiConfig(
        vocab_size=128, hidden_size=64, intermediate_size=128,
        num_hidden_layers=2, num_attention_heads=4,
        max_position_embeddings=64, partial_rotary_factor=0.5,
        layer_norm_eps=1e-5, tie_word_embeddings=False)
    hf = transformers.PhiForCausalLM(hf_cfg).eval()
    model, params = from_hf_model(hf, dtype=jnp.float32)
    assert model.config.unembed_bias and "unembed_b" in params
    assert model.config.rotary_pct == 0.5

    ids = np.random.default_rng(10).integers(0, 128, (2, 16)).astype(np.int32)
    with torch.no_grad():
        ref = hf(torch.from_numpy(ids).long()).logits.numpy()
    got = _logits_ours(model, params, ids)
    np.testing.assert_allclose(got, ref, atol=2e-4)


def test_phi3_import_matches_torch_forward():
    from deepspeed_tpu.models.hf import from_hf_model

    hf_cfg = transformers.Phi3Config(
        vocab_size=128, hidden_size=64, intermediate_size=128,
        num_hidden_layers=2, num_attention_heads=4, num_key_value_heads=2,
        max_position_embeddings=64, rms_norm_eps=1e-5,
        tie_word_embeddings=False, sliding_window=None,
        pad_token_id=0, bos_token_id=1, eos_token_id=2)
    hf = transformers.Phi3ForCausalLM(hf_cfg).eval()
    model, params = from_hf_model(hf, dtype=jnp.float32)

    ids = np.random.default_rng(8).integers(0, 128, (2, 16)).astype(np.int32)
    with torch.no_grad():
        ref = hf(torch.from_numpy(ids).long()).logits.numpy()
    got = _logits_ours(model, params, ids)
    np.testing.assert_allclose(got, ref, atol=2e-4)


def test_qwen2_moe_import_matches_torch_forward():
    """Exercises the shared-expert serving math against real HF weights:
    router with norm_topk_prob=False (raw softmax gates), 4 experts top-2,
    sigmoid-gated shared expert."""
    from deepspeed_tpu.models.hf import from_hf_model

    hf_cfg = transformers.Qwen2MoeConfig(
        vocab_size=128, hidden_size=64, intermediate_size=128,
        moe_intermediate_size=96, shared_expert_intermediate_size=112,
        num_hidden_layers=2, num_attention_heads=4, num_key_value_heads=2,
        max_position_embeddings=64, rms_norm_eps=1e-5,
        tie_word_embeddings=False, num_experts=4, num_experts_per_tok=2,
        decoder_sparse_step=1, mlp_only_layers=[], norm_topk_prob=False,
        use_sliding_window=False)
    hf = transformers.Qwen2MoeForCausalLM(hf_cfg).eval()
    model, params = from_hf_model(hf, dtype=jnp.float32)
    assert model.config.moe.shared_expert_intermediate == 112
    assert model.config.moe.normalize_gates is False

    ids = np.random.default_rng(9).integers(0, 128, (1, 16)).astype(np.int32)
    with torch.no_grad():
        ref = hf(torch.from_numpy(ids).long()).logits.numpy()
    got = _logits_ours(model, params, ids)
    np.testing.assert_allclose(got, ref, atol=3e-4)


OLMOE = dict(vocab_size=128, hidden_size=64, intermediate_size=32,
             num_hidden_layers=2, num_attention_heads=4,
             num_key_value_heads=4, max_position_embeddings=64,
             rms_norm_eps=1e-5, tie_word_embeddings=False, num_experts=8,
             num_experts_per_tok=2, norm_topk_prob=False, rope_theta=10000.0)


def _olmoe_state_dict(rng):
    """A synthetic state dict under the checkpoint's own key names
    (allenai/OLMoE-1B-7B: per-expert ``gate_proj/up_proj/down_proj``,
    router ``mlp.gate.weight`` [n, E], ``q_norm``/``k_norm`` [H*D])."""
    E, F, n, V = 64, 32, 8, 128
    r = lambda *shape: rng.standard_normal(shape).astype(np.float32) * 0.1
    sd = {"model.embed_tokens.weight": r(V, E), "lm_head.weight": r(V, E),
          "model.norm.weight": 1 + r(E)}
    for i in range(2):
        p = f"model.layers.{i}."
        sd.update({p + "input_layernorm.weight": 1 + r(E),
                   p + "post_attention_layernorm.weight": 1 + r(E),
                   p + "self_attn.q_norm.weight": 1 + 3 * r(E),
                   p + "self_attn.k_norm.weight": 1 + 3 * r(E),
                   p + "mlp.gate.weight": r(n, E)})
        for name in ("q_proj", "k_proj", "v_proj", "o_proj"):
            sd[p + f"self_attn.{name}.weight"] = r(E, E)
        for k in range(n):
            sd[p + f"mlp.experts.{k}.gate_proj.weight"] = r(F, E)
            sd[p + f"mlp.experts.{k}.up_proj.weight"] = r(F, E)
            sd[p + f"mlp.experts.{k}.down_proj.weight"] = r(E, F)
    return sd


def test_olmoe_tree_from_the_checkpoints_key_names():
    """Shapes and placement from a synthetic state dict: experts stacked
    to [n, E, F] / [n, F, E], the router transposed, and the q/k norm
    scales permuted inside each head exactly as wq/wk's columns are."""
    from types import SimpleNamespace

    from deepspeed_tpu.models.hf import (_interleave_perm, _olmoe_tree,
                                         config_from_hf)

    cfg = config_from_hf(SimpleNamespace(model_type="olmoe", clip_qkv=None,
                                         rope_scaling=None,
                                         attention_bias=False, **OLMOE))
    assert (cfg.qk_norm, cfg.moe.num_experts, cfg.moe.top_k,
            cfg.moe.normalize_gates, cfg.ffn_size) == ("full", 8, 2, False, 32)
    sd = _olmoe_state_dict(np.random.default_rng(0))
    t = _olmoe_tree(sd, cfg)
    l0 = t["layer_0"]
    ex = l0["moe"]["moe_layer"]["experts"]
    assert ex["w_gate"].shape == ex["w_up"].shape == (8, 64, 32)
    assert ex["w_down"].shape == (8, 32, 64)
    np.testing.assert_array_equal(
        ex["w_up"][3], sd["model.layers.0.mlp.experts.3.up_proj.weight"].T)
    np.testing.assert_array_equal(l0["moe"]["moe_layer"]["gate"]["wg"],
                                  sd["model.layers.0.mlp.gate.weight"].T)
    perm = _interleave_perm(16)
    qn = sd["model.layers.0.self_attn.q_norm.weight"].reshape(4, 16)
    np.testing.assert_array_equal(l0["attn"]["q_norm"], qn[:, perm])
    # the scale of projection column c sits where column c went
    wq = sd["model.layers.0.self_attn.q_proj.weight"].T.reshape(64, 4, 16)
    np.testing.assert_array_equal(l0["attn"]["wq"], wq[:, :, perm])
    with pytest.raises(NotImplementedError, match="clip_qkv"):
        config_from_hf(SimpleNamespace(model_type="olmoe", clip_qkv=8.0,
                                       rope_scaling=None, **OLMOE))


def test_olmoe_import_matches_torch_forward():
    """Against transformers' own OlmoeForCausalLM: whole-vector q/k norm
    (with non-trivial scales), 8 experts top-2, gates NOT renormalised."""
    if not hasattr(transformers, "OlmoeForCausalLM"):
        pytest.skip("this transformers has no OlmoeForCausalLM")
    from deepspeed_tpu.models.hf import from_hf_model

    hf = transformers.OlmoeForCausalLM(transformers.OlmoeConfig(**OLMOE)).eval()
    with torch.no_grad():        # the initialiser's ones would hide a
        for name, p in hf.named_parameters():     # misplaced scale
            if name.endswith(("q_norm.weight", "k_norm.weight")):
                p.uniform_(0.5, 1.5)
    model, params = from_hf_model(hf, dtype=jnp.float32)
    assert model.config.qk_norm == "full"
    assert model.config.moe.normalize_gates is False

    ids = np.random.default_rng(9).integers(0, 128, (1, 16)).astype(np.int32)
    with torch.no_grad():
        ref = hf(torch.from_numpy(ids).long()).logits.numpy()
    got = _logits_ours(model, params, ids)
    np.testing.assert_allclose(got, ref, atol=3e-4)


def test_qwen_v1_import_matches_torch_forward():
    """qwen v1 is a remote-code arch (no transformers class), so the
    oracle is a torch qwen2 model whose weights are RENAMED into the qwen
    v1 state-dict layout (same math: rmsnorm + rope + swiglu; v1 fuses
    c_attn = [q;k;v], halves intermediate_size across w1/w2, and swaps
    the silu branch onto w2 — modeling_qwen.py QWenMLP)."""
    from types import SimpleNamespace

    from deepspeed_tpu.models.hf import from_hf_model

    hf_cfg = transformers.Qwen2Config(
        vocab_size=128, hidden_size=64, intermediate_size=128,
        num_hidden_layers=2, num_attention_heads=4, num_key_value_heads=4,
        max_position_embeddings=64, rms_norm_eps=1e-5,
        tie_word_embeddings=False, use_sliding_window=False)
    hf = transformers.Qwen2ForCausalLM(hf_cfg).eval()
    sd = hf.state_dict()

    v1 = {"transformer.wte.weight": sd["model.embed_tokens.weight"],
          "transformer.ln_f.weight": sd["model.norm.weight"],
          "lm_head.weight": sd["lm_head.weight"]}
    for i in range(2):
        q = f"model.layers.{i}."
        p = f"transformer.h.{i}."
        v1[p + "ln_1.weight"] = sd[q + "input_layernorm.weight"]
        v1[p + "ln_2.weight"] = sd[q + "post_attention_layernorm.weight"]
        v1[p + "attn.c_attn.weight"] = torch.cat(
            [sd[q + "self_attn.q_proj.weight"],
             sd[q + "self_attn.k_proj.weight"],
             sd[q + "self_attn.v_proj.weight"]], dim=0)
        v1[p + "attn.c_attn.bias"] = torch.cat(
            [sd[q + "self_attn.q_proj.bias"],
             sd[q + "self_attn.k_proj.bias"],
             sd[q + "self_attn.v_proj.bias"]], dim=0)
        v1[p + "attn.c_proj.weight"] = sd[q + "self_attn.o_proj.weight"]
        v1[p + "mlp.w2.weight"] = sd[q + "mlp.gate_proj.weight"]  # silu br.
        v1[p + "mlp.w1.weight"] = sd[q + "mlp.up_proj.weight"]
        v1[p + "mlp.c_proj.weight"] = sd[q + "mlp.down_proj.weight"]

    shim = SimpleNamespace(
        config=SimpleNamespace(
            model_type="qwen", vocab_size=128, hidden_size=64,
            num_hidden_layers=2, num_attention_heads=4,
            intermediate_size=256,      # v1 counts both swiglu branches
            seq_length=64, layer_norm_epsilon=1e-5,
            rotary_emb_base=10000.0, tie_word_embeddings=False),
        state_dict=lambda: v1)
    model, params = from_hf_model(shim, dtype=jnp.float32)
    assert model.config.ffn_size == 128

    ids = np.random.default_rng(10).integers(0, 128, (2, 16)).astype(np.int32)
    with torch.no_grad():
        ref = hf(torch.from_numpy(ids).long()).logits.numpy()
    got = _logits_ours(model, params, ids)
    np.testing.assert_allclose(got, ref, atol=2e-4)


def test_generic_import_gpt_neox_matches_torch_forward():
    """The AutoTP-role fallback (reference module_inject/auto_tp.py:189):
    gpt-neox has NO hand-written tree — the generic name/shape converter
    must place every tensor (parallel residual, two norms per layer,
    head-interleaved fused QKV, partial rotary, exact-erf gelu) and match
    torch logits."""
    from deepspeed_tpu.models.hf import from_hf_model

    hf_cfg = transformers.GPTNeoXConfig(
        vocab_size=128, hidden_size=64, intermediate_size=128,
        num_hidden_layers=2, num_attention_heads=4,
        max_position_embeddings=64, rotary_pct=0.25,
        use_parallel_residual=True, tie_word_embeddings=False)
    hf = transformers.GPTNeoXForCausalLM(hf_cfg).eval()
    model, params = from_hf_model(hf, dtype=jnp.float32)
    assert model.config.parallel_block and model.config.parallel_block_norms == 2
    assert model.config.activation == "gelu_exact"

    ids = np.random.default_rng(11).integers(0, 128, (2, 16)).astype(np.int32)
    with torch.no_grad():
        ref = hf(torch.from_numpy(ids).long()).logits.numpy()
    got = _logits_ours(model, params, ids)
    np.testing.assert_allclose(got, ref, atol=2e-4)


def test_generic_import_stablelm_matches_torch_forward():
    """Second no-hand-written-tree family: stablelm (separate q/k/v with
    partial rotary, layernorm + silu-GLU — a llama/neox hybrid the
    generic heuristics must classify from names and bias presence)."""
    from deepspeed_tpu.models.hf import from_hf_model

    hf_cfg = transformers.StableLmConfig(
        vocab_size=128, hidden_size=64, intermediate_size=128,
        num_hidden_layers=2, num_attention_heads=4, num_key_value_heads=2,
        max_position_embeddings=64, partial_rotary_factor=0.5,
        use_qkv_bias=False, tie_word_embeddings=False)
    hf = transformers.StableLmForCausalLM(hf_cfg).eval()
    model, params = from_hf_model(hf, dtype=jnp.float32)
    assert model.config.norm == "layernorm"
    assert model.config.activation == "silu_glu"
    assert model.config.rotary_pct == 0.5

    ids = np.random.default_rng(12).integers(0, 128, (2, 16)).astype(np.int32)
    with torch.no_grad():
        ref = hf(torch.from_numpy(ids).long()).logits.numpy()
    got = _logits_ours(model, params, ids)
    np.testing.assert_allclose(got, ref, atol=2e-4)


def test_generic_import_alien_arch_fails_loudly():
    """A genuinely alien layout (encoder-decoder) must raise the
    listing-style error, not silently convert."""
    from deepspeed_tpu.models.hf import from_hf_model

    hf_cfg = transformers.T5Config(
        vocab_size=128, d_model=64, d_ff=128, num_layers=2, num_heads=4,
        d_kv=16)
    hf = transformers.T5ForConditionalGeneration(hf_cfg).eval()
    with pytest.raises(NotImplementedError, match="generic HF import"):
        from_hf_model(hf, dtype=jnp.float32)


def test_rope_scaling_rejected_loudly():
    """Scaled-rope checkpoints (llama3/yarn/longrope) must raise, not
    import with silently wrong position math."""
    from deepspeed_tpu.models.hf import config_from_hf

    cfg = transformers.LlamaConfig(
        vocab_size=128, hidden_size=64, intermediate_size=128,
        num_hidden_layers=2, num_attention_heads=4, num_key_value_heads=2,
        rope_scaling={"rope_type": "linear", "factor": 2.0})
    with pytest.raises(NotImplementedError, match="rope_scaling"):
        config_from_hf(cfg)


def test_generic_import_gptj_matches_torch_forward():
    """Third generic-fallback family: gpt-j — structurally-parallel block
    with ONE norm and NO config flag (detected from the absence of a
    second per-layer norm), INTERLEAVED rotary via ``rotary_dim`` (no
    head-dim permutation), biased lm_head."""
    from deepspeed_tpu.models.hf import from_hf_model

    hf_cfg = transformers.GPTJConfig(
        vocab_size=128, n_embd=64, n_layer=2, n_head=4, n_positions=64,
        rotary_dim=8, tie_word_embeddings=False)
    hf = transformers.GPTJForCausalLM(hf_cfg).eval()
    model, params = from_hf_model(hf, dtype=jnp.float32)
    assert model.config.parallel_block and model.config.parallel_block_norms == 1
    assert model.config.rotary_pct == 0.5 and model.config.unembed_bias

    ids = np.random.default_rng(13).integers(0, 128, (2, 16)).astype(np.int32)
    with torch.no_grad():
        ref = hf(torch.from_numpy(ids).long()).logits.numpy()
    got = _logits_ours(model, params, ids)
    np.testing.assert_allclose(got, ref, atol=2e-4)


def test_qwen2_moe_mixed_stack_import_matches_torch_forward():
    """Mixed dense/MoE stacks (the layout qwen2-moe checkpoints actually
    ship): decoder_sparse_step=2 puts MoE at odd layers, mlp_only_layers
    forces one of those dense anyway, and the dense layers use the
    checkpoint's DENSE intermediate_size (168), which differs from the
    expert width (96) — the import must produce torch-equal logits
    through both FFN kinds (round-4: moe_layer_pattern +
    dense_ffn_intermediate)."""
    from deepspeed_tpu.models.hf import from_hf_model
    from deepspeed_tpu.models.transformer import is_moe_layer

    hf_cfg = transformers.Qwen2MoeConfig(
        vocab_size=128, hidden_size=64, intermediate_size=168,
        moe_intermediate_size=96, shared_expert_intermediate_size=112,
        num_hidden_layers=4, num_attention_heads=4, num_key_value_heads=2,
        max_position_embeddings=64, rms_norm_eps=1e-5,
        tie_word_embeddings=False, num_experts=4, num_experts_per_tok=2,
        decoder_sparse_step=2, mlp_only_layers=[3], norm_topk_prob=False,
        use_sliding_window=False)
    hf = transformers.Qwen2MoeForCausalLM(hf_cfg).eval()
    model, params = from_hf_model(hf, dtype=jnp.float32)
    # HF: MoE at i where (i+1) % 2 == 0 and i not in mlp_only_layers
    flags = [is_moe_layer(model.config, i) for i in range(4)]
    assert flags == [False, True, False, False], flags
    assert model.config.moe.dense_ffn_intermediate == 168

    ids = np.random.default_rng(11).integers(0, 128, (1, 16)).astype(np.int32)
    with torch.no_grad():
        ref = hf(torch.from_numpy(ids).long()).logits.numpy()
    got = _logits_ours(model, params, ids)
    np.testing.assert_allclose(got, ref, atol=3e-4)


# ---------------------------------------------------------------------------
# lfm2_moe: the name map, on a synthetic state dict at the tiny size
# ---------------------------------------------------------------------------

LFM2 = dict(vocab_size=128, hidden_size=64, intermediate_size=96,
            moe_intermediate_size=32, num_hidden_layers=5,
            num_attention_heads=4, num_key_value_heads=2,
            max_position_embeddings=64, norm_eps=1e-5, num_experts=8,
            num_experts_per_tok=2, norm_topk_prob=True, use_expert_bias=True,
            routed_scaling_factor=1, num_dense_layers=1, conv_L_cache=3,
            conv_bias=False, rope_parameters={"rope_theta": 10000.0},
            layer_types=["conv", "full_attention", "conv", "conv", "conv"])


def _lfm2_state_dict(rng):
    """A synthetic state dict under HF ``Lfm2Moe``'s own key names."""
    E, F, Fe, n, V, D = 64, 96, 32, 8, 128, 16
    r = lambda *shape: rng.standard_normal(shape).astype(np.float32) * 0.1
    sd = {"model.embed_tokens.weight": r(V, E),
          "model.embedding_norm.weight": 1 + r(E)}
    for i, kind in enumerate(LFM2["layer_types"]):
        p = f"model.layers.{i}."
        sd.update({p + "operator_norm.weight": 1 + r(E),
                   p + "ffn_norm.weight": 1 + r(E)})
        if kind == "conv":
            sd.update({p + "conv.in_proj.weight": r(3 * E, E),
                       p + "conv.conv.weight": 5 * r(E, 1, 3),
                       p + "conv.out_proj.weight": r(E, E)})
        else:
            sd.update({p + "self_attn.q_proj.weight": r(E, E),
                       p + "self_attn.k_proj.weight": r(2 * D, E),
                       p + "self_attn.v_proj.weight": r(2 * D, E),
                       p + "self_attn.out_proj.weight": r(E, E),
                       p + "self_attn.q_layernorm.weight": 1 + 3 * r(D),
                       p + "self_attn.k_layernorm.weight": 1 + 3 * r(D)})
        f = p + "feed_forward."
        if i < LFM2["num_dense_layers"]:
            sd.update({f + "w1.weight": r(F, E), f + "w3.weight": r(F, E),
                       f + "w2.weight": r(E, F)})
        else:
            sd.update({f + "gate.weight": r(n, E),
                       f + "expert_bias": 0.2 * r(n)})
            for k in range(n):
                sd.update({f + f"experts.{k}.w1.weight": r(Fe, E),
                           f + f"experts.{k}.w3.weight": r(Fe, E),
                           f + f"experts.{k}.w2.weight": r(E, Fe)})
    return sd


def test_lfm2_moe_tree_matches_the_reference_in_the_checkpoints_rotation():
    """Name map and permutation together: the converted tree through the
    program's forward (rope on interleaved pairs) against the plain
    reference run on the checkpoint's OWN layout — unpermuted q/k
    projections and norm scales, rope rotating the two halves as HF does.
    Every tensor of the state dict is consumed."""
    import importlib.util
    from types import SimpleNamespace

    import jax
    import jax.numpy as jnp

    from deepspeed_tpu.models.hf import (_lfm2_moe_tree, _TrackedSD,
                                         config_from_hf)
    from deepspeed_tpu.models.transformer import TransformerLM

    cfg = dataclasses.replace(
        config_from_hf(SimpleNamespace(model_type="lfm2_moe",
                                       rope_scaling=None, **LFM2)),
        dtype=jnp.float32, attn_impl="xla")
    assert cfg.kinds == ("conv", "full", "conv", "conv", "conv")
    assert (cfg.qk_norm, cfg.moe.router_score, cfg.moe.moe_layer_pattern,
            cfg.ffn_size, cfg.moe.dense_ffn_intermediate, cfg.conv_taps,
            cfg.tie_embeddings) == (
        "head", "sigmoid_bias", (False, True, True, True, True), 32, 96, 3,
        True)
    sd = _TrackedSD(_lfm2_state_dict(np.random.default_rng(0)))
    tree = _lfm2_moe_tree(sd, cfg)
    assert set(sd) == sd.used
    assert tree["layer_0"]["conv"]["w_in"].shape == (64, 3, 64)
    assert tree["layer_0"]["conv"]["w_conv"].shape == (3, 64)
    # rows [E:2E] of in_proj are C: the gate on the convolution's output
    np.testing.assert_array_equal(
        tree["layer_0"]["conv"]["w_in"][:, 1],
        sd["model.layers.0.conv.in_proj.weight"][64:128].T)

    spec = importlib.util.spec_from_file_location(
        "lfm2_reference", os.path.join(os.path.dirname(
            os.path.abspath(__file__)), "reference", "lfm2_moe_decoder.py"))
    ref = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(ref)

    def rotate_halves(x, positions, theta):         # HF's apply_rotary
        d = x.shape[-1]
        inv = 1.0 / (theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d))
        ang = positions.astype(jnp.float32)[:, None] * inv[None, :]
        cos = jnp.concatenate([jnp.cos(ang)] * 2, -1)[:, None, :]
        sin = jnp.concatenate([jnp.sin(ang)] * 2, -1)[:, None, :]
        x1, x2 = x[..., :d // 2], x[..., d // 2:]
        return x * cos + jnp.concatenate([-x2, x1], -1) * sin

    def hf_layer(i):
        """Layer ``i`` straight from the state dict, nothing permuted."""
        p, t = f"model.layers.{i}.", tree[f"layer_{i}"]
        w = ref.program_layer(tree, i)
        if "attn" in t:
            w.update(
                wq=sd[p + "self_attn.q_proj.weight"].T.reshape(64, 4, 16),
                wk=sd[p + "self_attn.k_proj.weight"].T.reshape(64, 2, 16),
                q_norm=sd[p + "self_attn.q_layernorm.weight"],
                k_norm=sd[p + "self_attn.k_layernorm.weight"])
        return w

    tokens = np.random.default_rng(1).integers(0, 128, (1, 32)).astype(
        np.int32)
    ops, experts = ref.program_ops(cfg)
    ref.rotary, interleaved = rotate_halves, ref.rotary
    ref._layer_step = jax.jit(ref.layer_forward, static_argnames=(
        "op", "experts", "theta", "eps", "top_k", "q_block"))
    want = np.asarray(ref.forward_logits(
        tokens[0], embed=tree["embed"], layer=hf_layer, ops=ops,
        experts=experts, ln_final=tree["ln_final"]["scale"],
        theta=cfg.rope_theta, eps=cfg.norm_eps, top_k=2, q_block=16))
    with jax.default_matmul_precision("highest"):
        got = np.asarray(TransformerLM(cfg).apply(
            {"params": jax.tree.map(jnp.asarray, tree)}, tokens))[0]
    np.testing.assert_allclose(got, want, atol=2e-4, rtol=0)
    # ...and the permutation is needed: unpermuted q/k leave the reference
    flat = dict(tree, layer_1=dict(tree["layer_1"], attn=dict(
        tree["layer_1"]["attn"],
        wq=hf_layer(1)["wq"], wk=hf_layer(1)["wk"])))
    with jax.default_matmul_precision("highest"):
        off = np.asarray(TransformerLM(cfg).apply(
            {"params": jax.tree.map(jnp.asarray, flat)}, tokens))[0]
    assert np.abs(off - want).max() > 1e-3
    with pytest.raises(NotImplementedError, match="conv_bias"):
        config_from_hf(SimpleNamespace(model_type="lfm2_moe",
                                       rope_scaling=None,
                                       **dict(LFM2, conv_bias=True)))


# ---------------------------------------------------------------------------
# deepseek_v3 without a low-rank query step (kanana-2): the name map, on a
# synthetic state dict at tiny widths that all differ
# ---------------------------------------------------------------------------

DSV3 = dict(vocab_size=128, hidden_size=64, intermediate_size=96,
            moe_intermediate_size=32, num_hidden_layers=3,
            num_attention_heads=4, num_key_value_heads=4,
            max_position_embeddings=64, rms_norm_eps=1e-6,
            n_routed_experts=8, num_experts_per_tok=2, n_shared_experts=2,
            norm_topk_prob=True, routed_scaling_factor=2.448,
            scoring_func="sigmoid", topk_method="noaux_tc", n_group=1,
            topk_group=1, first_k_dense_replace=1, moe_layer_freq=1,
            kv_lora_rank=24, q_lora_rank=None, qk_nope_head_dim=16,
            qk_rope_head_dim=8, v_head_dim=12, rope_theta=10000.0,
            rope_interleave=True, attention_bias=False,
            tie_word_embeddings=False)


def _dsv3_state_dict(rng):
    """A synthetic state dict under HF ``DeepseekV3``'s own key names."""
    E, F, Fe, n, V, H = 64, 96, 32, 8, 128, 4
    R, dn, dr, dv = 24, 16, 8, 12
    r = lambda *shape: rng.standard_normal(shape).astype(np.float32) * 0.1
    sd = {"model.embed_tokens.weight": r(V, E),
          "model.norm.weight": 1 + r(E), "lm_head.weight": r(V, E)}
    mlp = lambda base, width: {base + "gate_proj.weight": r(width, E),
                               base + "up_proj.weight": r(width, E),
                               base + "down_proj.weight": r(E, width)}
    for i in range(3):
        p = f"model.layers.{i}."
        a = p + "self_attn."
        sd.update({p + "input_layernorm.weight": 1 + r(E),
                   p + "post_attention_layernorm.weight": 1 + r(E),
                   a + "q_proj.weight": r(H * (dn + dr), E),
                   a + "kv_a_proj_with_mqa.weight": r(R + dr, E),
                   a + "kv_a_layernorm.weight": 1 + 3 * r(R),
                   a + "kv_b_proj.weight": r(H * (dn + dv), R),
                   a + "o_proj.weight": r(E, H * dv)})
        f = p + "mlp."
        if i < 1:
            sd.update(mlp(f, F))
            continue
        sd.update({f + "gate.weight": r(n, E),
                   f + "gate.e_score_correction_bias": 0.2 * r(n),
                   **mlp(f + "shared_experts.", 2 * Fe)})
        for k in range(n):
            sd.update(mlp(f + f"experts.{k}.", Fe))
    return sd


def test_deepseek_v3_tree_places_every_tensor_and_matches_the_reference():
    """Every tensor placed; ``kv_a_proj_with_mqa`` split into ``c`` and
    ``k_r`` in the stored order; ``kv_b_proj`` into a head's ``k_nope | v``;
    nothing permuted (``rope_interleave``); the converted tree through the
    program's EXPANDED forward equals the plain reference."""
    import importlib.util
    from types import SimpleNamespace

    import jax
    import jax.numpy as jnp

    from deepspeed_tpu.models.hf import (_deepseek_v3_tree, _TrackedSD,
                                         config_from_hf)
    from deepspeed_tpu.models.transformer import TransformerLM

    hf = lambda **over: SimpleNamespace(
        model_type="deepseek_v3", **{**DSV3, "rope_scaling": None, **over})
    cfg = dataclasses.replace(config_from_hf(hf()), dtype=jnp.float32)
    assert (cfg.kv_lora_rank, cfg.qk_nope_head_dim, cfg.qk_rope_head_dim,
            cfg.v_head_dim) == (24, 16, 8, 12)
    assert (cfg.moe.router_score, cfg.moe.moe_layer_pattern, cfg.ffn_size,
            cfg.moe.dense_ffn_intermediate,
            cfg.moe.shared_expert_intermediate, cfg.moe.shared_expert_gated,
            cfg.moe.routed_scaling_factor, cfg.tie_embeddings) == (
        "sigmoid_bias", (False, True, True), 32, 96, 64, False, 2.448, False)
    sd = _TrackedSD(_dsv3_state_dict(np.random.default_rng(0)))
    tree = _deepseek_v3_tree(sd, cfg)
    assert set(sd) == sd.used
    a0, key = tree["layer_0"]["attn"], "model.layers.0.self_attn."
    assert a0["w_dkv"].shape == (64, 32)
    # columns [:24] are the latent c, [24:] the shared rope key: the order
    # of the checkpoint's rows, and of the cached row
    np.testing.assert_array_equal(
        a0["w_dkv"][:, 24:], sd[key + "kv_a_proj_with_mqa.weight"][24:].T)
    # head 1's rows of kv_b_proj: 16 of k_nope, then 12 of v
    kvb = sd[key + "kv_b_proj.weight"]
    np.testing.assert_array_equal(a0["w_uk"][:, 1], kvb[28:44].T)
    np.testing.assert_array_equal(a0["w_uv"][:, 1], kvb[44:56].T)
    assert "shared_gate" not in tree["layer_1"]["moe"]

    spec = importlib.util.spec_from_file_location(
        "kanana2_reference", os.path.join(os.path.dirname(
            os.path.abspath(__file__)), "reference", "kanana2_decoder.py"))
    ref = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(ref)
    tokens = np.random.default_rng(1).integers(0, 128, (1, 32)).astype(
        np.int32)
    want = np.asarray(ref.forward_logits(
        tokens[0], embed=tree["embed"], unembed=tree["unembed"],
        layer=lambda i: ref.program_layer(tree, i),
        experts=ref.program_experts(cfg),
        ln_final=tree["ln_final"]["scale"], theta=cfg.rope_theta,
        eps=cfg.norm_eps, top_k=2, scaling=2.448, q_block=16))
    with jax.default_matmul_precision("highest"):
        got = np.asarray(TransformerLM(cfg).apply(
            {"params": jax.tree.map(jnp.asarray, tree)}, tokens))[0]
    np.testing.assert_allclose(got, want, atol=2e-4, rtol=0)
    for over, text in (({"q_lora_rank": 16}, "q_lora_rank"),
                       ({"n_group": 4, "topk_group": 2}, "n_group > 1"),
                       ({"rope_scaling": {"type": "yarn", "factor": 4}},
                        "rope_scaling")):
        with pytest.raises(NotImplementedError, match=text):
            config_from_hf(hf(**over))
