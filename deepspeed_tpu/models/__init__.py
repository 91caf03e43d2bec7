"""Model zoo: presets matching the reference's supported model families
(inference/v2/model_implementations + module_inject containers: llama,
mistral, mixtral, opt/gpt…) expressed as configs of one TPU-native
TransformerLM."""
from __future__ import annotations

import jax.numpy as jnp

from .loss import cross_entropy_lm, lm_loss_fn  # noqa: F401
from .transformer import (  # noqa: F401
    ModelConfig,
    MoEConfig,
    TransformerLM,
    default_activation_rules,
)

PRESETS: dict[str, ModelConfig] = {
    # --- GPT-2 family (BASELINE.json config 1) ---------------------------
    "gpt2-125m": ModelConfig(vocab_size=50257, hidden_size=768, num_layers=12,
                             num_heads=12, max_seq_len=1024,
                             position_embedding="learned", norm="layernorm",
                             qkv_bias=True, attn_out_bias=True,
                             activation="gelu", tie_embeddings=True),
    "gpt2-350m": ModelConfig(vocab_size=50257, hidden_size=1024, num_layers=24,
                             num_heads=16, max_seq_len=1024,
                             position_embedding="learned", qkv_bias=True, attn_out_bias=True,
                             activation="gelu"),
    # gpt2-large geometry: the largest preset that stays HBM-resident on a
    # 16GB chip with fp32 master+opt state (16 B/param ~ 12.4GB + remat
    # activations)
    "gpt2-774m": ModelConfig(vocab_size=50257, hidden_size=1280, num_layers=36,
                             num_heads=20, max_seq_len=1024,
                             position_embedding="learned", qkv_bias=True, attn_out_bias=True,
                             activation="gelu"),
    "gpt2-1.3b": ModelConfig(vocab_size=50257, hidden_size=2048, num_layers=24,
                             num_heads=32, max_seq_len=1024,
                             position_embedding="learned", qkv_bias=True, attn_out_bias=True,
                             activation="gelu"),
    # --- LLaMA-2 family (BASELINE.json configs 2/4) ----------------------
    "llama2-7b": ModelConfig(vocab_size=32000, hidden_size=4096, num_layers=32,
                             num_heads=32, num_kv_heads=32, intermediate_size=11008,
                             max_seq_len=4096, position_embedding="rope",
                             norm="rmsnorm", activation="silu_glu",
                             tie_embeddings=False),
    "llama2-13b": ModelConfig(vocab_size=32000, hidden_size=5120, num_layers=40,
                              num_heads=40, num_kv_heads=40, intermediate_size=13824,
                              max_seq_len=4096, position_embedding="rope",
                              norm="rmsnorm", activation="silu_glu",
                              tie_embeddings=False),
    "llama2-70b": ModelConfig(vocab_size=32000, hidden_size=8192, num_layers=80,
                              num_heads=64, num_kv_heads=8, intermediate_size=28672,
                              max_seq_len=4096, position_embedding="rope",
                              norm="rmsnorm", activation="silu_glu",
                              tie_embeddings=False),
    # --- Mistral / Mixtral (BASELINE.json config 3) ----------------------
    "mistral-7b": ModelConfig(vocab_size=32000, hidden_size=4096, num_layers=32,
                              num_heads=32, num_kv_heads=8, intermediate_size=14336,
                              max_seq_len=8192, position_embedding="rope",
                              norm="rmsnorm", activation="silu_glu",
                              sliding_window=4096, tie_embeddings=False),
    "mixtral-8x7b": ModelConfig(vocab_size=32000, hidden_size=4096, num_layers=32,
                                num_heads=32, num_kv_heads=8, intermediate_size=14336,
                                max_seq_len=8192, position_embedding="rope",
                                norm="rmsnorm", activation="silu_glu",
                                tie_embeddings=False,
                                moe=MoEConfig(num_experts=8, top_k=2)),
    # --- Falcon (reference inference/v2/model_implementations/falcon) ----
    "falcon-7b": ModelConfig(vocab_size=65024, hidden_size=4544, num_layers=32,
                             num_heads=71, num_kv_heads=1, max_seq_len=2048,
                             position_embedding="rope", norm="layernorm",
                             activation="gelu", parallel_block=True,
                             tie_embeddings=False),
    "falcon-40b": ModelConfig(vocab_size=65024, hidden_size=8192, num_layers=60,
                              num_heads=128, num_kv_heads=8, max_seq_len=2048,
                              position_embedding="rope", norm="layernorm",
                              activation="gelu", parallel_block=True,
                              parallel_block_norms=2,  # ln_attn + ln_mlp
                              tie_embeddings=False),
    # --- BLOOM (reference module_inject/containers/bloom.py; ALiBi) ------
    "bloom-7b1": ModelConfig(vocab_size=250880, hidden_size=4096, num_layers=30,
                             num_heads=32, max_seq_len=2048,
                             position_embedding="alibi", norm="layernorm",
                             activation="gelu", qkv_bias=True,
                             attn_out_bias=True, embed_norm=True,
                             tie_embeddings=True),
    # --- OPT (reference v2 model_implementations/opt; ReLU + learned) ----
    "opt-125m": ModelConfig(vocab_size=50272, hidden_size=768, num_layers=12,
                            num_heads=12, max_seq_len=2048,
                            position_embedding="learned", activation="relu",
                            qkv_bias=True, attn_out_bias=True),
    "opt-6.7b": ModelConfig(vocab_size=50272, hidden_size=4096, num_layers=32,
                            num_heads=32, max_seq_len=2048,
                            position_embedding="learned", activation="relu",
                            qkv_bias=True, attn_out_bias=True),
    # --- GPT-J / GPT-NeoX (reference containers gptj/gptneox) ------------
    "gptj-6b": ModelConfig(vocab_size=50400, hidden_size=4096, num_layers=28,
                           num_heads=16, max_seq_len=2048,
                           position_embedding="rope", rotary_pct=0.25,
                           activation="gelu", parallel_block=True,
                           tie_embeddings=False),
    "gpt-neox-20b": ModelConfig(vocab_size=50432, hidden_size=6144,
                                num_layers=44, num_heads=64, max_seq_len=2048,
                                position_embedding="rope", rotary_pct=0.25,
                                activation="gelu", parallel_block=True,
                                parallel_block_norms=2,  # input+post_attn ln
                                tie_embeddings=False),
    # --- Phi (reference v2 model_implementations/phi; partial rotary) ----
    "phi-2": ModelConfig(vocab_size=51200, hidden_size=2560, num_layers=32,
                         num_heads=32, max_seq_len=2048,
                         position_embedding="rope", rotary_pct=0.4,
                         activation="gelu", parallel_block=True,
                         qkv_bias=True, attn_out_bias=True,
                         unembed_bias=True, tie_embeddings=False),
    # --- Qwen (reference v2 model_implementations/qwen*; qkv bias) -------
    "qwen-7b": ModelConfig(vocab_size=151936, hidden_size=4096, num_layers=32,
                           num_heads=32, intermediate_size=11008,
                           max_seq_len=8192, position_embedding="rope",
                           norm="rmsnorm", activation="silu_glu",
                           qkv_bias=True, tie_embeddings=False),
    "qwen2-7b": ModelConfig(vocab_size=152064, hidden_size=3584, num_layers=28,
                            num_heads=28, num_kv_heads=4,
                            intermediate_size=18944, max_seq_len=32768,
                            position_embedding="rope", norm="rmsnorm",
                            activation="silu_glu", qkv_bias=True,
                            tie_embeddings=False),
    "phi-3-mini": ModelConfig(vocab_size=32064, hidden_size=3072,
                              num_layers=32, num_heads=32,
                              intermediate_size=8192, max_seq_len=4096,
                              position_embedding="rope", norm="rmsnorm",
                              activation="silu_glu", tie_embeddings=False),
    "internlm-7b": ModelConfig(vocab_size=103168, hidden_size=4096,
                               num_layers=32, num_heads=32,
                               intermediate_size=11008, max_seq_len=2048,
                               position_embedding="rope", norm="rmsnorm",
                               activation="silu_glu", qkv_bias=True,
                               tie_embeddings=False),
    # qwen2-moe (qwen1.5-moe-a2.7b): 60 fine-grained experts top-4 plus a
    # sigmoid-gated shared expert (reference inference/v2 qwen_v2_moe)
    "qwen2-moe-a2.7b": ModelConfig(vocab_size=151936, hidden_size=2048,
                                   num_layers=24, num_heads=16,
                                   intermediate_size=1408, max_seq_len=8192,
                                   position_embedding="rope", norm="rmsnorm",
                                   activation="silu_glu", qkv_bias=True,
                                   tie_embeddings=False,
                                   moe=MoEConfig(
                                       num_experts=60, top_k=4,
                                       shared_expert_intermediate=5632)),
    # OLMoE-1B-7B (allenai/OLMoE-1B-7B-0125-Instruct config.json): every
    # layer sparse, 64 experts of width 1024 (``intermediate_size``), 8 a
    # token with the softmax gates NOT renormalised, RMSNorm of the whole
    # projected q and k before rope, no shared expert
    "olmoe-1b-7b": ModelConfig(vocab_size=50304, hidden_size=2048,
                               num_layers=16, num_heads=16, num_kv_heads=16,
                               intermediate_size=1024, max_seq_len=4096,
                               position_embedding="rope", rope_theta=1e4,
                               norm="rmsnorm", norm_eps=1e-5,
                               activation="silu_glu", qk_norm="full",
                               tie_embeddings=False,
                               moe=MoEConfig(num_experts=64, top_k=8,
                                             normalize_gates=False)),
    # SmallThinker-21B-A3B (PowerInfer/SmallThinker-21BA3B-Instruct
    # config.json): 28 heads x 128 over hidden 2560; of every four layers
    # the first is full causal attention with NO position embedding and
    # the next three a 4096-token window with rope; every layer sparse, 64
    # ReGLU experts of width 768, 6 a token, softmax over the chosen six,
    # routed from the layer's INPUT norm output (before attention)
    "smallthinker-21b-a3b": ModelConfig(
        vocab_size=151936, hidden_size=2560, num_layers=52, num_heads=28,
        num_kv_heads=4, head_size=128, intermediate_size=768,
        max_seq_len=16384, position_embedding="rope", rope_theta=1.5e6,
        norm="rmsnorm", norm_eps=1e-6, activation="relu_glu",
        sliding_window=4096,
        layer_kinds=("full_nope", "window", "window", "window"),
        tie_embeddings=False,
        moe=MoEConfig(num_experts=64, top_k=6, normalize_gates=True,
                      router_input="attn")),
    # --- bert family: bidirectional post-norm encoders (reference
    # module_inject/containers/{bert,distil_bert}.py policies and the
    # csrc/transformer training kernels, whose target workload is BERT) ----
    "bert-base-uncased": ModelConfig(vocab_size=30522, hidden_size=768,
                                     num_layers=12, num_heads=12,
                                     max_seq_len=512,
                                     position_embedding="learned",
                                     activation="gelu", qkv_bias=True, attn_out_bias=True,
                             causal=False,
                                     pre_norm=False, dropout=0.1,
                                     type_vocab_size=2, norm_eps=1e-12),
    "bert-large-uncased": ModelConfig(vocab_size=30522, hidden_size=1024,
                                      num_layers=24, num_heads=16,
                                      max_seq_len=512,
                                      position_embedding="learned",
                                      activation="gelu", qkv_bias=True, attn_out_bias=True,
                             causal=False,
                                      pre_norm=False, dropout=0.1,
                                      type_vocab_size=2, norm_eps=1e-12),
    "distilbert-base": ModelConfig(vocab_size=30522, hidden_size=768,
                                   num_layers=6, num_heads=12,
                                   max_seq_len=512,
                                   position_embedding="learned",
                                   activation="gelu", qkv_bias=True, attn_out_bias=True,
                             causal=False,
                                   pre_norm=False, dropout=0.1,
                                   norm_eps=1e-12),
    # --- tiny variants for tests/debug (reference tests/unit/simple_model.py) --
    "tiny-gpt2": ModelConfig(vocab_size=256, hidden_size=64, num_layers=2,
                             num_heads=4, max_seq_len=128,
                             position_embedding="learned", qkv_bias=True, attn_out_bias=True,
                             activation="gelu"),
    "tiny-llama": ModelConfig(vocab_size=256, hidden_size=64, num_layers=2,
                              num_heads=4, num_kv_heads=2, max_seq_len=128,
                              position_embedding="rope", norm="rmsnorm",
                              activation="silu_glu", tie_embeddings=False),
    "tiny-mixtral": ModelConfig(vocab_size=256, hidden_size=64, num_layers=2,
                                num_heads=4, num_kv_heads=2, max_seq_len=128,
                                position_embedding="rope", norm="rmsnorm",
                                activation="silu_glu", tie_embeddings=False,
                                moe=MoEConfig(num_experts=4, top_k=2,
                                              min_capacity=4)),
    "tiny-falcon": ModelConfig(vocab_size=256, hidden_size=64, num_layers=2,
                               num_heads=4, num_kv_heads=1, max_seq_len=128,
                               position_embedding="rope", activation="gelu",
                               parallel_block=True, tie_embeddings=False),
    "tiny-bloom": ModelConfig(vocab_size=256, hidden_size=64, num_layers=2,
                              num_heads=4, max_seq_len=128,
                              position_embedding="alibi", activation="gelu"),
    "tiny-opt": ModelConfig(vocab_size=256, hidden_size=64, num_layers=2,
                            num_heads=4, max_seq_len=128,
                            position_embedding="learned", activation="relu"),
    "tiny-phi": ModelConfig(vocab_size=256, hidden_size=64, num_layers=2,
                            num_heads=4, max_seq_len=128,
                            position_embedding="rope", rotary_pct=0.5,
                            activation="gelu", parallel_block=True,
                            tie_embeddings=False),
    "tiny-qwen": ModelConfig(vocab_size=256, hidden_size=64, num_layers=2,
                             num_heads=4, num_kv_heads=2, max_seq_len=128,
                             position_embedding="rope", norm="rmsnorm",
                             activation="silu_glu", qkv_bias=True,
                             tie_embeddings=False),
    "tiny-bert": ModelConfig(vocab_size=256, hidden_size=64, num_layers=2,
                             num_heads=4, max_seq_len=128,
                             position_embedding="learned", activation="gelu",
                             qkv_bias=True, attn_out_bias=True,
                             causal=False, pre_norm=False,
                             type_vocab_size=2),
    "tiny-qwen2-moe": ModelConfig(vocab_size=256, hidden_size=64,
                                  num_layers=2, num_heads=4, num_kv_heads=2,
                                  intermediate_size=96, max_seq_len=128,
                                  position_embedding="rope", norm="rmsnorm",
                                  activation="silu_glu", qkv_bias=True,
                                  tie_embeddings=False,
                                  moe=MoEConfig(
                                      num_experts=4, top_k=2, min_capacity=4,
                                      shared_expert_intermediate=128)),
    "tiny-olmoe": ModelConfig(vocab_size=256, hidden_size=64, num_layers=4,
                              num_heads=4, num_kv_heads=4,
                              intermediate_size=32, max_seq_len=128,
                              position_embedding="rope", norm="rmsnorm",
                              activation="silu_glu", qk_norm="full",
                              tie_embeddings=False,
                              moe=MoEConfig(num_experts=8, top_k=2,
                                            min_capacity=4,
                                            normalize_gates=False)),
    # two periods; window 16 so that a ring of 8-token blocks wraps in a
    # CPU test; heads x head_size (128) wider than hidden (64), as published
    "tiny-smallthinker": ModelConfig(
        vocab_size=256, hidden_size=64, num_layers=8, num_heads=4,
        num_kv_heads=2, head_size=32, intermediate_size=32, max_seq_len=256,
        position_embedding="rope", rope_theta=1e4, norm="rmsnorm",
        norm_eps=1e-6, activation="relu_glu", sliding_window=16,
        layer_kinds=("full_nope", "window", "window", "window"),
        tie_embeddings=False,
        moe=MoEConfig(num_experts=8, top_k=2, min_capacity=4,
                      normalize_gates=True, router_input="attn")),
    # LFM2-24B-A2B (LiquidAI/LFM2-24B-A2B config.json, ``lfm2_moe``): 30 of
    # 40 layers mix the sequence by a gated short convolution (3 taps a
    # channel, no keys and values), every fourth (i % 4 == 2) by attention
    # over 8 KV heads of 64 with q and k RMS-normalised per head; the first
    # two layers carry a dense SwiGLU of 11776, the rest 64 routed SwiGLU
    # experts of 1536, 4 a token, by sigmoid scores with a selection bias;
    # tied head (assumed: the family ties it)
    "lfm2-24b-a2b": ModelConfig(
        vocab_size=65536, hidden_size=2048, num_layers=40, num_heads=32,
        num_kv_heads=8, intermediate_size=1536, max_seq_len=128000,
        position_embedding="rope", rope_theta=1e6, norm="rmsnorm",
        norm_eps=1e-5, activation="silu_glu", qk_norm="head",
        layer_kinds=("conv", "conv", "full", "conv"), conv_taps=3,
        tie_embeddings=True,
        moe=MoEConfig(num_experts=64, top_k=4, normalize_gates=True,
                      router_score="sigmoid_bias", dropless=True,
                      moe_layer_pattern=(False,) * 2 + (True,) * 38,
                      dense_ffn_intermediate=11776)),
    # one leading conv layer with the dense feed-forward, then one whole
    # period (attention, conv, conv, conv) of expert layers: published
    # layers 1-5 in small
    "tiny-lfm2-moe": ModelConfig(
        vocab_size=256, hidden_size=64, num_layers=5, num_heads=4,
        num_kv_heads=2, intermediate_size=32, max_seq_len=256,
        position_embedding="rope", rope_theta=1e4, norm="rmsnorm",
        norm_eps=1e-5, activation="silu_glu", qk_norm="head",
        leading_kinds=("conv",),
        layer_kinds=("full", "conv", "conv", "conv"), conv_taps=3,
        tie_embeddings=True,
        moe=MoEConfig(num_experts=8, top_k=2, min_capacity=4,
                      normalize_gates=True, router_score="sigmoid_bias",
                      dropless=True, dropless_block_m=16,
                      moe_layer_pattern=(False, True, True, True, True),
                      dense_ffn_intermediate=96)),
    # kanana-2-30b-a3b-instruct-2601 (kakaocorp, ``deepseek_v3``): latent
    # attention (MLA, no low-rank query step) on every layer — 32 heads of
    # 128 nope + 64 rope over ONE latent of 512 and ONE rope key of 64 a
    # token, values of 128 —; layer 0 a dense SwiGLU of 6144, the other 47
    # 128 routed SwiGLU experts of 768, 6 a token (sigmoid scores, a
    # selection bias, ONE group: group-limited selection is the identity),
    # the weights normalised and times 2.448, beside ONE ungated shared
    # expert of 2 x 768; untied head
    "kanana-2-30b-a3b": ModelConfig(
        vocab_size=128256, hidden_size=2048, num_layers=48, num_heads=32,
        intermediate_size=768, max_seq_len=32768,
        position_embedding="rope", rope_theta=1e6, norm="rmsnorm",
        norm_eps=1e-6, activation="silu_glu", tie_embeddings=False,
        kv_lora_rank=512, qk_nope_head_dim=128, qk_rope_head_dim=64,
        v_head_dim=128,
        moe=MoEConfig(num_experts=128, top_k=6, normalize_gates=True,
                      router_score="sigmoid_bias", dropless=True,
                      moe_layer_pattern=(False,) + (True,) * 47,
                      dense_ffn_intermediate=6144,
                      shared_expert_intermediate=1536,
                      shared_expert_gated=False,
                      routed_scaling_factor=2.448)),
    # one leading dense layer and two expert layers; the four MLA widths
    # all differ (a transposed or mis-sliced axis cannot pass)
    "tiny-kanana2": ModelConfig(
        vocab_size=256, hidden_size=64, num_layers=3, num_heads=4,
        intermediate_size=32, max_seq_len=256,
        position_embedding="rope", rope_theta=1e4, norm="rmsnorm",
        norm_eps=1e-6, activation="silu_glu", tie_embeddings=False,
        kv_lora_rank=24, qk_nope_head_dim=16, qk_rope_head_dim=8,
        v_head_dim=12,
        moe=MoEConfig(num_experts=8, top_k=2, min_capacity=4,
                      normalize_gates=True, router_score="sigmoid_bias",
                      dropless=True, dropless_block_m=16,
                      moe_layer_pattern=(False, True, True),
                      dense_ffn_intermediate=96,
                      shared_expert_intermediate=64,
                      shared_expert_gated=False,
                      routed_scaling_factor=2.448)),
}


def _frozen(v):
    """A JSON value as a dataclass field holds it: lists become tuples."""
    return tuple(_frozen(x) for x in v) if isinstance(v, list) else v


def get_model_config(name: str, **overrides) -> ModelConfig:
    """The preset ``name`` with ``overrides`` laid over it. Overrides may
    come from a JSON file (``benchmark/configs/*.json``): a list becomes a
    tuple, and a dict under ``moe`` is laid over the preset's
    ``MoEConfig``."""
    import dataclasses

    if name not in PRESETS:
        raise ValueError(f"unknown model preset '{name}'; known: {sorted(PRESETS)}")
    cfg = PRESETS[name]
    overrides = {k: _frozen(v) for k, v in overrides.items()}
    if isinstance(overrides.get("moe"), dict):
        overrides["moe"] = dataclasses.replace(
            cfg.moe, **{k: _frozen(v) for k, v in overrides["moe"].items()})
    return dataclasses.replace(cfg, **overrides) if overrides else cfg


def build_model(name: str, **overrides) -> TransformerLM:
    return TransformerLM(get_model_config(name, **overrides))
