"""Decoder-only transformer family (GPT-2 / LLaMA / Mixtral-MoE).

This is the flagship model zoo of the framework — the role the reference
plays through HF-model injection (module_inject/containers: llama, gptj,
bloom, opt… and inference/v2/model_implementations/{llama_v2,mistral,
mixtral,…}). Rather than patching torch modules, models here are built
TPU-first in flax.linen:

- every parameter carries *logical* axis names via ``nn.with_partitioning``;
  the ZeRO planner (runtime/zero/planner.py) maps them onto the device mesh
  (tensor/expert axes) and adds ZeRO fsdp sharding,
- activations carry logical constraints; the engine installs rules that make
  XLA materialize the parallelism algebra:
    * tensor parallelism — heads/mlp dims → ``tensor`` (Megatron slicing, the
      role of module_inject/auto_tp.py:189),
    * Ulysses sequence parallelism — sequence dim sharded over ``seq``
      outside attention; head dim constrained to ``seq`` *inside* attention,
      so XLA inserts the seq↔head all-to-all pair around local attention —
      exactly reference deepspeed/sequence/layer.py:90 ``_SeqAllToAll``,
    * expert parallelism — expert dim → ``expert``; the dispatch/combine
      einsums lower to the MoE all-to-all (reference moe/sharded_moe.py:96).

Attention runs through ops/attention.py which picks the Pallas flash kernel
on TPU and a reference XLA path elsewhere.
"""
from __future__ import annotations

import dataclasses
from dataclasses import dataclass, field
from functools import partial
from typing import Any, Callable

import flax.linen as nn
import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name

from ..ops.attention import (AttentionSharding, attention_flash_plan,
                             attention_formulation,
                             dot_product_attention)

# Logical activation axis names (canonical home: parallel/axes.py);
# re-exported here for back-compat.
from ..parallel.axes import (  # noqa: E402
    BATCH,
    EMBED,
    EXPERT,
    HEADS,
    MLP,
    SEQ,
    constrain,
    mesh_specs,
)
from ..parallel.tensor import current_tp_overlap, ring_row_matmul
from ..utils.annotations import device_scope


def default_activation_rules(topology) -> list[tuple[str, Any]]:
    """Logical→mesh rules installed by the engine around apply()."""
    from ..parallel.axes import BATCH_NOEXP

    return [
        (BATCH, ("data", "expert", "fsdp")),
        (BATCH_NOEXP, ("data", "fsdp")),
        (SEQ, "seq"),
        (EMBED, None),
        # inside attention: heads sharded over tensor AND seq (Ulysses)
        (HEADS, ("tensor", "seq")),
        (MLP, "tensor"),
        (EXPERT, "expert"),
    ]


@dataclass(frozen=True)
class MoEConfig:
    """Mixtral/GShard-style MoE (reference deepspeed/moe/layer.py:17)."""
    num_experts: int = 8
    top_k: int = 2
    capacity_factor: float = 1.25
    eval_capacity_factor: float = 2.0
    min_capacity: int = 4
    aux_loss_weight: float = 0.01
    router_z_loss_weight: float = 0.001
    # layers where MoE replaces dense FFN; every Nth layer (1 = all)
    moe_layer_freq: int = 1
    # explicit per-layer MoE pattern (True = MoE FFN at that layer),
    # overriding moe_layer_freq when set — expresses qwen2-moe's
    # decoder_sparse_step phase ((i+1) % step == 0) and mlp_only_layers
    # dense overrides (arbitrary mixed stacks). Length must equal
    # num_layers.
    moe_layer_pattern: tuple[bool, ...] | None = None
    # FFN width of the DENSE layers in a mixed stack (qwen2-moe's
    # ``intermediate_size`` vs ``moe_intermediate_size`` for experts);
    # None = the model's intermediate_size
    dense_ffn_intermediate: int | None = None
    # dropless (megablocks-style) routing through the Pallas grouped GEMM
    # instead of capacity-dispatch einsums (ops/pallas/grouped_matmul.py)
    dropless: bool = False
    dropless_block_m: int = 128
    # qwen2-moe/deepseek-style always-on shared expert: a dense FFN of this
    # intermediate size added to the routed output through a sigmoid gate
    # (reference inference/v2 qwen_v2_moe shared expert). None = no shared.
    shared_expert_intermediate: int | None = None
    # False: the shared expert is added as it is, with NO gate (deepseek-v3
    # ``n_shared_experts``: ONE always-on FFN of n x the experts' width)
    shared_expert_gated: bool = True
    # the routed experts' summed output times this constant (deepseek-v3
    # ``routed_scaling_factor``), applied to the gate weights in ONE place,
    # ``moe/sharded_moe.py:topk_dropless_gating``; 1.0 = every other preset
    routed_scaling_factor: float = 1.0
    # renormalize the top-k gate values to sum to 1 (mixtral semantics);
    # False = use the raw softmax probabilities (qwen2-moe's
    # norm_topk_prob=False default)
    normalize_gates: bool = True
    # what the router reads: "ffn" — the tensor the experts read (the
    # post-attention norm's output, every preset before SmallThinker) — or
    # "attn": the layer's INPUT norm output, before attention
    # (SmallThinker: "router placed before attention")
    router_input: str = "ffn"
    # how the router scores experts (read in ONE place,
    # ``moe/sharded_moe.py:topk_dropless_gating``): "softmax" — top k of
    # the softmax over all experts, every preset before LFM2 — or
    # "sigmoid_bias": ``s = sigmoid(logits)``, the k experts are the top k
    # of ``s + b`` (``gate/bias``, a learned vector that moves the
    # SELECTION only) and the weights are ``s`` at the chosen k, divided
    # by their sum + 1e-6 where ``normalize_gates`` (LFM2's
    # ``use_expert_bias`` / ``norm_topk_prob``). Dropless routing only.
    router_score: str = "softmax"


#: what a kind of layer fixes: (sliding-window mask, position embedding).
#: "full" and "window" carry the model's ``position_embedding``;
#: "full_nope" is full causal attention with NO position embedding
#: (SmallThinker's global layers); "conv" is no attention at all — a gated
#: short convolution mixes the sequence (LFM2: no mask, no position
#: embedding, no keys and values). The serving engine keeps one cache a
#: kind of STATE ("full" / "window" pages, "conv" records): ``cache_kind``.
LAYER_KINDS = {"full": (False, True), "window": (True, True),
               "full_nope": (False, False), "conv": (False, False)}
#: the operator that is not attention
CONV = "conv"


@dataclass(frozen=True)
class ModelConfig:
    vocab_size: int = 50257
    hidden_size: int = 768
    num_layers: int = 12
    num_heads: int = 12
    num_kv_heads: int | None = None          # GQA; None → num_heads
    intermediate_size: int | None = None     # None → 4*hidden (gpt) / 8/3*hidden (glu)
    max_seq_len: int = 1024
    position_embedding: str = "learned"      # learned | rope | alibi
    rope_theta: float = 10000.0
    rotary_pct: float = 1.0                  # partial rotary (gpt-neox/phi)
    norm: str = "layernorm"                  # layernorm | rmsnorm
    norm_eps: float = 1e-5
    activation: str = "gelu"                 # gelu (tanh approx) |
                                             # gelu_exact (erf) | relu |
                                             # silu_glu (SwiGLU) |
                                             # relu_glu (ReGLU)
    qkv_bias: bool = False                   # qwen-style projection biases
    qk_norm: str | None = None               # None | "full": RMSNorm of the
                                             # WHOLE projected q and k (all
                                             # heads as one vector, OLMoE) |
                                             # "head": RMSNorm of each head
                                             # over its own width, one
                                             # learned scale of head_dim
                                             # shared by the heads (LFM2,
                                             # qwen3); both before rope
    attn_out_bias: bool = False              # gpt2/bert-style out-proj bias
    parallel_block: bool = False             # falcon/gpt-j/phi: attn ∥ ffn
    parallel_block_norms: int = 1            # 2 = separate ln for ffn branch
                                             # (gpt-neox, falcon-40b)
    causal: bool = True                      # False → bidirectional encoder
                                             # (bert family)
    sliding_window: int | None = None        # mistral: attend last W tokens
    head_size: int | None = None             # width of one head; None →
                                             # hidden_size // num_heads
    layer_kinds: tuple[str, ...] | None = None   # LAYER_KINDS names, one
                                             # PERIOD repeated over the
                                             # depth; None → every layer
                                             # "window" where
                                             # sliding_window is set, else
                                             # "full"
    leading_kinds: tuple[str, ...] = ()      # kinds of the layers BEFORE the
                                             # periods: a stack is these
                                             # leading layers, then whole
                                             # periods of ``layer_kinds``
    kv_lora_rank: int | None = None          # latent attention (MLA,
                                             # deepseek-v3): keys and values
                                             # are up-projections of ONE
                                             # normed latent ``c`` of this
                                             # width a token, beside ONE
                                             # rope key of
                                             # ``qk_rope_head_dim`` shared by
                                             # the heads; None → per-head
                                             # keys and values (every other
                                             # preset)
    qk_nope_head_dim: int = 0                # MLA: a head's query/key width
                                             # without position ...
    qk_rope_head_dim: int = 0                # ... and with rope; the score
                                             # scale is (nope + rope)^-0.5
    v_head_dim: int = 0                      # MLA: a head's value width
    conv_taps: int = 3                       # taps a channel of a "conv"
                                             # layer's depthwise causal
                                             # convolution (LFM2
                                             # ``conv_L_cache``)
    pre_norm: bool = True                    # False → post-norm residuals
                                             # (original BERT layout)
    embed_norm: bool = False                 # bloom: LayerNorm right after
                                             # the embedding (pre-norm too)
    unembed_bias: bool = False               # phi: lm_head carries a bias
    dropout: float = 0.0                     # bert-style residual dropout
    type_vocab_size: int = 0                 # >0 → bert segment embeddings
    tie_embeddings: bool = True
    moe: MoEConfig | None = None
    dtype: Any = jnp.bfloat16                # compute dtype
    remat: bool = False                      # rematerialize each block
    remat_policy: str = "auto"               # what a rematted block keeps
                                             # (registry: ops/remat.py).
                                             # "auto": the matmul products
                                             # tagged below, stepped down by
                                             # the engine where the compiled
                                             # step's memory says so
                                             # (engine.remat_plan); any
                                             # other name pins that policy
    attn_impl: str = "auto"                  # auto | pallas | xla

    @property
    def kv_heads(self) -> int:
        return self.num_kv_heads or self.num_heads

    @property
    def head_dim(self) -> int:
        return self.head_size or self.hidden_size // self.num_heads

    @property
    def latent_width(self) -> int:
        """Values a token a layer that latent attention keeps of the past:
        the latent and the shared rope key (0: not a latent model)."""
        return (self.kv_lora_rank + self.qk_rope_head_dim) \
            if self.kv_lora_rank else 0

    @property
    def kinds_period(self) -> tuple[str, ...]:
        """One period of layer kinds (``layer_kinds``, or the one kind
        every layer has)."""
        period = tuple(self.layer_kinds or (
            ("window",) if self.sliding_window else ("full",)))
        lead = tuple(self.leading_kinds)
        bad = [k for k in lead + period if k not in LAYER_KINDS]
        if bad or (self.num_layers - len(lead)) % len(period) \
                or len(lead) > self.num_layers:
            raise ValueError(
                f"layer_kinds {period!r} after {len(lead)} leading "
                f"layer(s): names must be of {sorted(LAYER_KINDS)} and the "
                f"period must divide the layers after the leading ones "
                f"(num_layers {self.num_layers})")
        if "window" in lead + period and not self.sliding_window:
            raise ValueError("a 'window' layer kind needs sliding_window")
        return period

    def layer_kind(self, i: int) -> str:
        period, lead = self.kinds_period, self.leading_kinds
        return lead[i] if i < len(lead) \
            else period[(i - len(lead)) % len(period)]

    @property
    def kinds(self) -> tuple[str, ...]:
        """Every layer's kind, in order."""
        return tuple(self.layer_kind(i) for i in range(self.num_layers))

    @property
    def ffn_size(self) -> int:
        if self.intermediate_size:
            return self.intermediate_size
        if self.activation in GLU_ACTS:
            return int(8 * self.hidden_size / 3 // 128 + 1) * 128
        return 4 * self.hidden_size

    def num_params(self) -> int:
        """Analytic parameter count (used by the flops profiler and bench):
        each layer with the operator (attention or a short convolution) and
        the feed-forward (dense, of its own width in a mixed stack, or
        routed experts) it has."""
        h, v, L = self.hidden_size, self.vocab_size, self.num_layers
        f = self.ffn_size
        attn = h * self.num_heads * self.head_dim + 2 * h * self.kv_heads * self.head_dim \
            + self.num_heads * self.head_dim * h
        if self.kv_lora_rank:
            # W_q, W_dkv and its norm, W_uk | W_uv, W_o
            R, H = self.kv_lora_rank, self.num_heads
            qk = self.qk_nope_head_dim + self.qk_rope_head_dim
            attn = h * H * qk + h * self.latent_width + R \
                + R * H * (self.qk_nope_head_dim + self.v_head_dim) \
                + H * self.v_head_dim * h
        glu = self.activation in GLU_ACTS

        def dense(width):
            return 3 * h * width if glu else 2 * h * width + width + h

        ffn_moe = 0
        if self.moe:
            ffn_moe = self.moe.num_experts * (3 if glu else 2) * h * f \
                + h * self.moe.num_experts
            if self.moe.router_score == "sigmoid_bias":
                ffn_moe += self.moe.num_experts
            if self.moe.shared_expert_intermediate:
                ffn_moe += 3 * h * self.moe.shared_expert_intermediate \
                    + (h if self.moe.shared_expert_gated else 0)
        if self.qkv_bias:
            attn += self.num_heads * self.head_dim \
                + 2 * self.kv_heads * self.head_dim
        if self.attn_out_bias:
            attn += h
        if self.qk_norm == "head":
            attn += 2 * self.head_dim
        elif self.qk_norm:
            attn += (self.num_heads + self.kv_heads) * self.head_dim
        # a conv layer: in-projection to (B, C, u), the taps, out-projection
        conv = 3 * h * h + self.conv_taps * h + h * h
        n_conv = sum(k == CONV for k in self.kinds)
        n_moe = sum(is_moe_layer(self, i) for i in range(L))
        dense_width = (self.moe.dense_ffn_intermediate or f) if self.moe \
            else f
        layers = n_conv * conv + (L - n_conv) * attn \
            + n_moe * ffn_moe + (L - n_moe) * dense(dense_width)
        per_norm = h if self.norm == "rmsnorm" else 2 * h
        # pre-norm: 2 per layer + ln_final; post-norm: 2 per layer + ln_embed
        norms = (2 * L + 1) * per_norm
        if self.embed_norm and self.pre_norm:   # bloom: ln_embed on top
            norms += per_norm
        if self.parallel_block and self.parallel_block_norms == 1:
            norms -= L * per_norm               # one ln per layer, not two
        emb = v * h + (0 if self.tie_embeddings else v * h)
        emb += self.type_vocab_size * h
        if self.unembed_bias:
            emb += v
        pos = self.max_seq_len * h if self.position_embedding == "learned" else 0
        return emb + pos + layers + norms


def _dense_init(scale: float = 1.0):
    return nn.initializers.variance_scaling(scale, "fan_in", "normal")


class Norm(nn.Module):
    config: ModelConfig

    @nn.compact
    def __call__(self, x):
        """Stats (mean/variance) reduce in fp32 and the LayerNorm centering
        (x - mean) * inv stays in fp32 too — a bf16 subtraction cancels
        catastrophically when x ≈ mean, which post-norm BERT hits at every
        residual. Only the affine runs in the input dtype: one downcast of
        the normalized tensor, which XLA fuses into the same elementwise
        fusion (the full-fp32-affine version this replaces showed up as ~8%
        of the train step in convert/copy fusions on v5e; this one is
        throughput-neutral — measured 46.0k vs 46.0k tok/s/chip)."""
        cfg = self.config
        dtype = x.dtype
        x32 = x.astype(jnp.float32)
        if cfg.norm == "rmsnorm":
            scale = self.param("scale", nn.with_partitioning(nn.initializers.ones, ("embed",)),
                               (cfg.hidden_size,), jnp.float32)
            var = jnp.mean(jnp.square(x32), axis=-1, keepdims=True)
            inv = jax.lax.rsqrt(var + cfg.norm_eps)
            return x * inv.astype(dtype) * scale.astype(dtype)
        scale = self.param("scale", nn.with_partitioning(nn.initializers.ones, ("embed",)),
                           (cfg.hidden_size,), jnp.float32)
        bias = self.param("bias", nn.with_partitioning(nn.initializers.zeros, ("embed",)),
                          (cfg.hidden_size,), jnp.float32)
        mean = jnp.mean(x32, axis=-1, keepdims=True)
        var = jnp.mean(jnp.square(x32 - mean), axis=-1, keepdims=True)
        inv = jax.lax.rsqrt(var + cfg.norm_eps)
        normed = ((x32 - mean) * inv).astype(dtype)
        return normed * scale.astype(dtype) + bias.astype(dtype)


def alibi_slopes(num_heads: int) -> jax.Array:
    """ALiBi per-head slopes (Press et al.; reference bloom container /
    inference v2 alibi kernels): geometric sequence from 2^(-8/n)."""
    import math

    def pow2_slopes(n):
        start = 2.0 ** (-(2.0 ** -(math.log2(n) - 3)))
        return [start * (start ** i) for i in range(n)]

    if math.log2(num_heads).is_integer():
        vals = pow2_slopes(num_heads)
    else:
        closest = 2 ** math.floor(math.log2(num_heads))
        vals = pow2_slopes(closest) + pow2_slopes(2 * closest)[0::2][
            :num_heads - closest]
    return jnp.asarray(vals, jnp.float32)


def rope(q: jax.Array, k: jax.Array, positions: jax.Array, theta: float) -> tuple[jax.Array, jax.Array]:
    """Rotary position embedding on [B, S, H, D] q/k."""
    d = q.shape[-1]
    freqs = 1.0 / (theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d))
    angles = positions[..., None].astype(jnp.float32) * freqs  # [B, S, D/2]
    cos, sin = jnp.cos(angles)[:, :, None, :], jnp.sin(angles)[:, :, None, :]

    def rot(x):
        x1, x2 = x[..., ::2], x[..., 1::2]
        xr1 = x1 * cos - x2 * sin
        xr2 = x2 * cos + x1 * sin
        return jnp.stack([xr1, xr2], axis=-1).reshape(x.shape)

    return rot(q.astype(jnp.float32)).astype(q.dtype), rot(k.astype(jnp.float32)).astype(k.dtype)


def apply_rope(q: jax.Array, k: jax.Array, positions: jax.Array,
               theta: float, rotary_pct: float = 1.0) -> tuple[jax.Array, jax.Array]:
    """Full or partial (gpt-neox ``rotary_pct`` / phi) rotary embedding —
    the single implementation shared by training attention and the ragged
    inference forward."""
    if rotary_pct >= 1.0:
        return rope(q, k, positions, theta)
    d_rot = (int(q.shape[-1] * rotary_pct) // 2) * 2
    qr, kr = rope(q[..., :d_rot], k[..., :d_rot], positions, theta)
    return (jnp.concatenate([qr, q[..., d_rot:]], axis=-1),
            jnp.concatenate([kr, k[..., d_rot:]], axis=-1))


QK_NORMS = ("full", "head")


def qk_norm_shape(cfg: "ModelConfig", heads: int) -> tuple[int, ...]:
    """Shape of a ``qk_norm`` scale: one a head and lane ("full"), or one
    vector of ``head_dim`` shared by the heads ("head")."""
    return (cfg.head_dim,) if cfg.qk_norm == "head" else (heads, cfg.head_dim)


def qk_norm(cfg: "ModelConfig", x: jax.Array, scale: jax.Array) -> jax.Array:
    """``cfg.qk_norm`` on a projected q or k ``[..., heads, head_dim]`` with
    its scale (:func:`qk_norm_shape`) — the one implementation shared by
    training attention and the ragged inference forward. "full": RMS over
    heads AND head_dim together (OLMoE normalises the projection before it
    is split into heads); "head": RMS over each head's own width (LFM2).
    Statistics in float32, affine in the input dtype, as :class:`Norm`."""
    if cfg.qk_norm not in QK_NORMS:
        raise ValueError(f"qk_norm {cfg.qk_norm!r} is not one of {QK_NORMS}")
    return _rms_scale(x, scale, cfg.norm_eps,
                      -1 if cfg.qk_norm == "head" else (-2, -1))


def _rms_scale(x: jax.Array, scale: jax.Array, eps: float, axis=-1):
    """``x`` RMS-normalised over ``axis`` times ``scale``: statistics in
    float32, affine in the input dtype, as :class:`Norm`."""
    var = jnp.mean(jnp.square(x.astype(jnp.float32)), axis=axis,
                   keepdims=True)
    inv = jax.lax.rsqrt(var + eps)
    return x * inv.astype(x.dtype) * scale.astype(x.dtype)


def kind_window(cfg: "ModelConfig", kind: str) -> int | None:
    """The sliding window a layer of ``kind`` masks with (None: full)."""
    return cfg.sliding_window if LAYER_KINDS[kind][0] else None


def kind_ropes(cfg: "ModelConfig", kind: str) -> bool:
    """Whether a layer of ``kind`` rotates q and k."""
    return cfg.position_embedding == "rope" and LAYER_KINDS[kind][1]


def cache_kind(kind: str) -> str:
    """The cache a layer of ``kind`` keeps: "window" (a bounded ring of
    pages), "full" (a table of pages that grows with the context) or
    "conv" (no pages: one record of the last ``conv_taps - 1`` inputs of
    the convolution a sequence)."""
    if kind == CONV:
        return CONV
    return "window" if LAYER_KINDS[kind][0] else "full"


def conv_mix(cfg: "ModelConfig", p, h: jax.Array, prev: jax.Array,
             n_valid: jax.Array | None = None
             ) -> tuple[jax.Array, jax.Array]:
    """THE gated short convolution (LFM2's operator), shared by the flax
    block and the ragged serving forward: ``[B, C, u] = split3(h W_in)``,
    ``z = B * u``, ``c_t = sum_j w[j] * z_{t-(taps-1)+j}`` (depthwise,
    causal), ``out = (C * c) W_out``. ``h`` ``[S, T, E]``; ``prev``
    ``[S, taps-1, E]``: the ``z`` of the ``taps - 1`` positions before this
    call's first (zeros at a sequence's start). Returns ``(out, new)``
    with ``new`` the ``z`` of the last ``taps - 1`` of each row's
    ``n_valid`` tokens (None: all ``T``), older ones taken from ``prev``
    where the row has fewer: what the next call needs as its ``prev``."""
    S, T, E = h.shape
    R = cfg.conv_taps - 1
    dt = h.dtype
    bcu = jnp.einsum("ste,ekf->stkf", h, p["w_in"].astype(dt))
    z = bcu[:, :, 0] * bcu[:, :, 2]                        # B * u  [S,T,E]
    zfull = jnp.concatenate([prev.astype(dt), z], axis=1)  # [S, R+T, E]
    w = p["w_conv"].astype(dt)                             # [taps, E]
    c = sum(w[j] * zfull[:, j:j + T] for j in range(cfg.conv_taps))
    out = jnp.einsum("ste,ef->stf", bcu[:, :, 1] * c, p["w_out"].astype(dt))
    if n_valid is None:
        return out, zfull[:, T:]
    # row s keeps zfull[s, n : n + R]: its last R valid inputs
    idx = n_valid[:, None].astype(jnp.int32) + jnp.arange(R)[None, :]
    return out, jnp.take_along_axis(zfull, idx[:, :, None], axis=1)


def _attn_impl(cfg: "ModelConfig", kind: str) -> str:
    """alibi's additive bias and sliding windows run XLA attention (no
    flash kernel path); everything else follows ``cfg.attn_impl``."""
    return "xla" if (cfg.position_embedding == "alibi"
                     or kind_window(cfg, kind)) else cfg.attn_impl


def attention_axis_names(cfg: "ModelConfig") -> tuple[tuple, tuple]:
    """The logical axes :class:`Attention` states for q and for k/v at the
    attention call (GQA leaves the K/V heads whole)."""
    kv_heads = HEADS if cfg.kv_heads == cfg.num_heads else None
    return (BATCH, None, HEADS, None), (BATCH, None, kv_heads, None)


def attention_sharding(cfg: "ModelConfig") -> AttentionSharding | None:
    """Those names on the mesh the engine traces the model under, for the
    dispatcher's per-shard kernel path; None where no engine scoped one."""
    found = mesh_specs(*attention_axis_names(cfg))
    return None if found is None else AttentionSharding(*found)


def _training_attention_call(cfg: "ModelConfig", batch: int, seq: int,
                             manual_axes=None) -> tuple[tuple, dict]:
    """The abstract q, k, v and keywords of the attention call
    :class:`Attention` makes for a full-sequence ``[batch, seq]`` step (no
    KV cache, no padding mask) under the rules and mesh in scope HERE."""
    q = jax.ShapeDtypeStruct((batch, seq, cfg.num_heads, cfg.head_dim),
                             cfg.dtype)
    kv = jax.ShapeDtypeStruct((batch, seq, cfg.kv_heads, cfg.head_dim),
                              cfg.dtype)
    # alibi's bias is built per call; its presence is all the gate reads
    return (q, kv, kv), dict(
        causal=cfg.causal, window=cfg.sliding_window,
        bias=True if cfg.position_embedding == "alibi" else None,
        impl=cfg.attn_impl, sharding=attention_sharding(cfg),
        manual_axes=manual_axes)


def training_attention_formulation(cfg: "ModelConfig", batch: int, seq: int,
                                   manual_axes=None) -> tuple[str, str]:
    """``("pallas", "")`` or ``("xla", why_not)``: what :class:`Attention`
    runs for a full-sequence ``[batch, seq]`` step (no KV cache, no
    padding mask) under the rules and mesh in scope HERE — the training
    engine asks once at build time, inside what it traces the loss under,
    so ``attn_impl="auto"`` never falls through to XLA attention
    unannounced. ``manual_axes``: the mesh axes the step's own
    ``shard_map`` will have made manual (``batch`` is then a shard's)."""
    qkv, kw = _training_attention_call(cfg, batch, seq, manual_axes)
    return attention_formulation(*qkv, **kw)


def training_flash_plan(cfg: "ModelConfig", batch: int, seq: int,
                        manual_axes=None):
    """The flash kernel's plan for that call (``ops/pallas/
    flash_attention.py:FlashPlan``: blocks, compute tile, backward form,
    tiles computed) — None where XLA attention runs."""
    qkv, kw = _training_attention_call(cfg, batch, seq, manual_axes)
    return attention_flash_plan(*qkv, **kw)


class Attention(nn.Module):
    """Causal self-attention with GQA + optional RoPE + KV cache.

    TP: heads dim → 'tensor'; Ulysses: q/k/v constrained head-sharded over
    'seq' for the attention itself (all-to-all inserted by XLA).
    """
    config: ModelConfig
    kind: str = ""        # a LAYER_KINDS name; "" → the model's one kind

    @nn.compact
    def __call__(self, x, positions, kv_cache=None, attn_mask=None):
        cfg = self.config
        if not self.kind and len(set(cfg.kinds_period)) > 1:
            raise ValueError("a model of several layer kinds needs each "
                             "Attention told its own (Block(kind=...))")
        kind = self.kind or cfg.layer_kind(0)
        B, S, _ = x.shape
        H, KV, D = cfg.num_heads, cfg.kv_heads, cfg.head_dim

        wq = self.param("wq", nn.with_partitioning(_dense_init(), ("embed", "heads", "head_dim")),
                        (cfg.hidden_size, H, D), jnp.float32)
        wk = self.param("wk", nn.with_partitioning(_dense_init(), ("embed", "kv_heads", "head_dim")),
                        (cfg.hidden_size, KV, D), jnp.float32)
        wv = self.param("wv", nn.with_partitioning(_dense_init(), ("embed", "kv_heads", "head_dim")),
                        (cfg.hidden_size, KV, D), jnp.float32)
        wo = self.param("wo", nn.with_partitioning(_dense_init(), ("heads", "head_dim", "embed")),
                        (H, D, cfg.hidden_size), jnp.float32)

        bo = None
        if cfg.attn_out_bias:
            bo = self.param("bo", nn.with_partitioning(
                nn.initializers.zeros, ("embed",)),
                (cfg.hidden_size,), jnp.float32)
        q = jnp.einsum("bse,ehd->bshd", x, wq.astype(cfg.dtype))
        k = jnp.einsum("bse,ehd->bshd", x, wk.astype(cfg.dtype))
        v = jnp.einsum("bse,ehd->bshd", x, wv.astype(cfg.dtype))
        if cfg.qkv_bias:
            bq = self.param("bq", nn.with_partitioning(
                nn.initializers.zeros, ("heads", "head_dim")), (H, D), jnp.float32)
            bk = self.param("bk", nn.with_partitioning(
                nn.initializers.zeros, ("kv_heads", "head_dim")), (KV, D), jnp.float32)
            bv = self.param("bv", nn.with_partitioning(
                nn.initializers.zeros, ("kv_heads", "head_dim")), (KV, D), jnp.float32)
            q = q + bq.astype(cfg.dtype)
            k = k + bk.astype(cfg.dtype)
            v = v + bv.astype(cfg.dtype)
        if cfg.qk_norm:
            per_head = cfg.qk_norm == "head"
            q = qk_norm(cfg, q, self.param("q_norm", nn.with_partitioning(
                nn.initializers.ones,
                ("head_dim",) if per_head else ("heads", "head_dim")),
                qk_norm_shape(cfg, H), jnp.float32))
            k = qk_norm(cfg, k, self.param("k_norm", nn.with_partitioning(
                nn.initializers.ones,
                ("head_dim",) if per_head else ("kv_heads", "head_dim")),
                qk_norm_shape(cfg, KV), jnp.float32))

        if kind_ropes(cfg, kind):
            q, k = apply_rope(q, k, positions, cfg.rope_theta, cfg.rotary_pct)

        new_cache = None
        if kv_cache is not None:
            # decode path: append at cache_len
            ck, cv, cache_len = kv_cache
            ck = jax.lax.dynamic_update_slice(ck, k.astype(ck.dtype), (0, cache_len, 0, 0))
            cv = jax.lax.dynamic_update_slice(cv, v.astype(cv.dtype), (0, cache_len, 0, 0))
            k, v = ck, cv
            new_cache = (ck, cv, cache_len + S)

        # Ulysses resharding: seq→full, heads→sharded over ('tensor','seq')
        q_names, kv_names = attention_axis_names(cfg)
        q = constrain(q, *q_names)
        k = constrain(k, *kv_names)
        v = constrain(v, *kv_names)
        # what a rematted block may keep (ops/remat.py ATTN_PRODUCTS): a tag
        # is metadata, read by a names policy alone
        q = checkpoint_name(q, "attn_q")
        k = checkpoint_name(k, "attn_k")
        v = checkpoint_name(v, "attn_v")

        alibi_bias = None
        if cfg.position_embedding == "alibi":
            # ALiBi: logits += slope_h * (k_pos - q_pos) (reference bloom
            # policy / inference v2 alibi); no pallas path yet → xla attn
            slopes = alibi_slopes(H)
            k_pos = jnp.arange(k.shape[1], dtype=jnp.float32)
            q_pos = positions.astype(jnp.float32)      # [B, S]
            rel = k_pos[None, None, None, :] - q_pos[:, None, :, None]
            alibi_bias = slopes[None, :, None, None] * rel  # [B,H,S,K]

        out = dot_product_attention(
            q, k, v,
            causal=cfg.causal,
            positions=positions if kv_cache is not None else None,
            kv_len=(kv_cache[2] + S) if kv_cache is not None else None,
            mask=attn_mask,
            bias=alibi_bias,
            window=kind_window(cfg, kind),
            impl=_attn_impl(cfg, kind),
            sharding=attention_sharding(cfg),
        )
        # back to seq-sharded, heads full
        out = constrain(out, BATCH, SEQ, None, None)
        # row-parallel out-proj: under an active tp_overlap scope the
        # contraction (heads) rides a ring matmul⊗reduce-scatter +
        # all-gather (parallel/tensor.py) — the GEMM hides under the ring
        # transfers instead of finishing before a blocking all-reduce
        scope = current_tp_overlap()
        proj = None
        if scope is not None and scope.attention:
            proj = ring_row_matmul(
                out.reshape(B, S, H * D),
                wo.astype(cfg.dtype).reshape(H * D, cfg.hidden_size),
                scope.mesh, axis=scope.axis, lead_specs=scope.token_specs)
        out = proj if proj is not None else \
            jnp.einsum("bshd,hde->bse", out, wo.astype(cfg.dtype))
        if bo is not None:
            out = out + bo.astype(cfg.dtype)
        out = checkpoint_name(constrain(out, BATCH, SEQ, EMBED), "attn_proj")
        if new_cache is not None:
            return out, new_cache
        return out


class LatentAttention(nn.Module):
    """Multi-head LATENT attention (MLA, deepseek-v3 with ``q_lora_rank``
    null), the EXPANDED form: training, v1 and the reference of the serving
    forward, which runs the ABSORBED form of the same function over the
    cached latent (``inference/forward.py``).

    ``q = x W_q`` (a head's ``nope | rope`` columns); ``[c | k_r] = x
    W_dkv``; ``c = RMSNorm(c)``; ``k_r`` is ONE rope key shared by the
    heads; ``k_nope_h = c W_uk,h``, ``v_h = c W_uv,h`` (``kv_b_proj`` kept
    as its two halves: the absorbed form folds them on different sides of
    the attention); rope on ``q_rope`` and ``k_r`` only, interleaved pairs
    (HF ``rope_interleave``); scores over ``nope + rope`` scaled by
    ``(nope + rope)^-0.5``. No bias. What a later token needs of the past
    is ``c`` and ``k_r``: ``ModelConfig.latent_width`` values a token."""
    config: ModelConfig
    kind: str = ""

    @nn.compact
    def __call__(self, x, positions, kv_cache=None, attn_mask=None):
        cfg = self.config
        if kv_cache is not None:
            raise ValueError(
                "latent attention (kv_lora_rank) has no v1 kv_cache: serve "
                "it through InferenceEngineV2 (a latent page a token)")
        if cfg.qkv_bias or cfg.qk_norm or cfg.attn_out_bias \
                or not kind_ropes(cfg, self.kind or cfg.layer_kind(0)):
            raise ValueError("latent attention runs with rope, without "
                             "biases and without a q/k norm")
        H, R = cfg.num_heads, cfg.kv_lora_rank
        dn, dr, dv = cfg.qk_nope_head_dim, cfg.qk_rope_head_dim, \
            cfg.v_head_dim
        E, dt = cfg.hidden_size, cfg.dtype
        part = lambda init, names: nn.with_partitioning(init, names)
        wq = self.param("wq", part(_dense_init(), ("embed", "heads",
                                                   "head_dim")),
                        (E, H, dn + dr), jnp.float32)
        w_dkv = self.param("w_dkv", part(_dense_init(), ("embed", None)),
                           (E, R + dr), jnp.float32)
        kv_norm = self.param("kv_norm", part(nn.initializers.ones, (None,)),
                             (R,), jnp.float32)
        w_uk = self.param("w_uk", part(_dense_init(), (None, "heads",
                                                       "head_dim")),
                          (R, H, dn), jnp.float32)
        w_uv = self.param("w_uv", part(_dense_init(), (None, "heads",
                                                       "head_dim")),
                          (R, H, dv), jnp.float32)
        wo = self.param("wo", part(_dense_init(), ("heads", "head_dim",
                                                   "embed")),
                        (H, dv, E), jnp.float32)
        q = jnp.einsum("bse,ehd->bshd", x, wq.astype(dt))
        c, k_r = latent_row(cfg, x, w_dkv, kv_norm)
        q_r, k_r = apply_rope(q[..., dn:], k_r[:, :, None, :], positions,
                              cfg.rope_theta)
        q = jnp.concatenate([q[..., :dn], q_r], axis=-1)
        k = jnp.concatenate(
            [jnp.einsum("bsr,rhd->bshd", c, w_uk.astype(dt)),
             jnp.broadcast_to(k_r, (*k_r.shape[:2], H, dr))], axis=-1)
        v = jnp.einsum("bsr,rhd->bshd", c, w_uv.astype(dt))
        q = checkpoint_name(constrain(q, BATCH, None, HEADS, None), "attn_q")
        k = checkpoint_name(constrain(k, BATCH, None, HEADS, None), "attn_k")
        v = checkpoint_name(constrain(v, BATCH, None, HEADS, None), "attn_v")
        # (query and value widths differ: XLA attention, scaled by the
        # query's width, nope + rope)
        out = dot_product_attention(q, k, v, causal=cfg.causal,
                                    mask=attn_mask, impl="xla")
        out = constrain(out, BATCH, SEQ, None, None)
        out = jnp.einsum("bshd,hde->bse", out, wo.astype(dt))
        return checkpoint_name(constrain(out, BATCH, SEQ, EMBED),
                               "attn_proj")


def latent_row(cfg: "ModelConfig", x: jax.Array, w_dkv: jax.Array,
               kv_norm: jax.Array) -> tuple[jax.Array, jax.Array]:
    """``[c | k_r] = x W_dkv`` with ``c`` RMS-normalised over its own width
    (``kv_a_layernorm``; statistics in float32, as :class:`Norm`): the
    latent ``[..., kv_lora_rank]`` and the rope key BEFORE rope ``[...,
    qk_rope_head_dim]`` — shared by the flax block and the serving
    forward."""
    ckr = jnp.einsum("...e,er->...r", x, w_dkv.astype(x.dtype))
    return (_rms_scale(ckr[..., :cfg.kv_lora_rank], kv_norm, cfg.norm_eps),
            ckr[..., cfg.kv_lora_rank:])


def attention_cls(cfg: "ModelConfig"):
    """The flax module of a layer's attention: latent (MLA) where the model
    has ``kv_lora_rank``, else per-head keys and values."""
    return LatentAttention if cfg.kv_lora_rank else Attention


class ShortConv(nn.Module):
    """A "conv" layer's operator over a whole sequence (training, v1
    prefill): :func:`conv_mix` from zeros. No bias anywhere (LFM2
    ``conv_bias`` false)."""
    config: ModelConfig

    @nn.compact
    def __call__(self, x):
        cfg = self.config
        E = cfg.hidden_size
        p = {
            "w_in": self.param("w_in", nn.with_partitioning(
                _dense_init(), ("embed", None, "mlp")), (E, 3, E),
                jnp.float32),
            "w_conv": self.param("w_conv", nn.with_partitioning(
                nn.initializers.normal(cfg.conv_taps ** -0.5),
                (None, "mlp")), (cfg.conv_taps, E), jnp.float32),
            "w_out": self.param("w_out", nn.with_partitioning(
                _dense_init(), ("mlp", "embed")), (E, E), jnp.float32),
        }
        with device_scope("conv_mix"):
            out, _ = conv_mix(
                cfg, p, x,
                jnp.zeros((x.shape[0], cfg.conv_taps - 1, E), x.dtype))
        return constrain(out, BATCH, SEQ, EMBED)


#: two-matrix FFN activations; torch's nn.GELU() is the erf form while
#: jax.nn.gelu defaults to the tanh approximation — archs that use exact
#: gelu (gpt-neox, falcon) map to "gelu_exact" at import
_ACTS = {
    "gelu": jax.nn.gelu,
    "gelu_exact": lambda x: jax.nn.gelu(x, approximate=False),
    "relu": jax.nn.relu,
}
#: three-matrix (gated) FFNs, ``down(act(gate x) * up x)``: SwiGLU, ReGLU
GLU_ACTS = {"silu_glu": jax.nn.silu, "relu_glu": jax.nn.relu}


class DenseFFN(nn.Module):
    config: ModelConfig

    @nn.compact
    def __call__(self, x):
        cfg = self.config
        F = cfg.ffn_size
        if cfg.activation in GLU_ACTS:
            wg = self.param("w_gate", nn.with_partitioning(_dense_init(), ("embed", "mlp")),
                            (cfg.hidden_size, F), jnp.float32)
            wu = self.param("w_up", nn.with_partitioning(_dense_init(), ("embed", "mlp")),
                            (cfg.hidden_size, F), jnp.float32)
            wd = self.param("w_down", nn.with_partitioning(_dense_init(), ("mlp", "embed")),
                            (F, cfg.hidden_size), jnp.float32)
            # ops/remat.py FFN_PRODUCTS: kept by the names policies
            h = GLU_ACTS[cfg.activation](
                checkpoint_name(x @ wg.astype(cfg.dtype), "ffn_gate")) \
                * checkpoint_name(x @ wu.astype(cfg.dtype), "ffn_up")
        else:
            wu = self.param("w_up", nn.with_partitioning(_dense_init(), ("embed", "mlp")),
                            (cfg.hidden_size, F), jnp.float32)
            wd = self.param("w_down", nn.with_partitioning(_dense_init(), ("mlp", "embed")),
                            (F, cfg.hidden_size), jnp.float32)
            bu = self.param("b_up", nn.with_partitioning(nn.initializers.zeros, ("mlp",)),
                            (F,), jnp.float32)
            bd = self.param("b_down", nn.with_partitioning(nn.initializers.zeros, ("embed",)),
                            (cfg.hidden_size,), jnp.float32)
            act = _ACTS[cfg.activation]
            h = act(checkpoint_name(
                x @ wu.astype(cfg.dtype) + bu.astype(cfg.dtype), "ffn_up"))
        h = constrain(h, BATCH, SEQ, MLP)
        # row-parallel down-proj via ring matmul⊗reduce-scatter when a
        # tp_overlap scope is active (see Attention); falls back to the
        # plain matmul when the token/contraction dims can't ring
        scope = current_tp_overlap()
        out = None
        if scope is not None and scope.ffn:
            out = ring_row_matmul(h, wd.astype(cfg.dtype), scope.mesh,
                                  axis=scope.axis,
                                  lead_specs=scope.token_specs)
        if out is None:
            out = h @ wd.astype(cfg.dtype)
        if cfg.activation not in GLU_ACTS:
            out = out + bd.astype(cfg.dtype)
        return constrain(out, BATCH, SEQ, EMBED)


def dense_ffn_config(cfg: ModelConfig) -> ModelConfig:
    """Config for the DENSE FFN of a mixed MoE stack: qwen2-moe's
    mlp-only layers keep their own intermediate size."""
    import dataclasses

    if cfg.moe is not None and cfg.moe.dense_ffn_intermediate:
        return dataclasses.replace(
            cfg, intermediate_size=cfg.moe.dense_ffn_intermediate)
    return cfg


def is_moe_layer(cfg: ModelConfig, i: int) -> bool:
    """Whether layer ``i`` carries the MoE FFN: the explicit per-layer
    pattern when set (qwen2-moe sparse-step phase / mlp_only_layers, LFM2's
    leading dense layers), else the every-Nth ``moe_layer_freq`` rule."""
    if cfg.moe is None:
        return False
    pat = cfg.moe.moe_layer_pattern
    if pat is not None:
        if len(pat) != cfg.num_layers:
            raise ValueError(f"moe_layer_pattern has {len(pat)} entries for "
                             f"{cfg.num_layers} layers")
        return bool(pat[i])
    return i % (cfg.moe.moe_layer_freq or 1) == 0


def moe_layer_kwargs(cfg: ModelConfig, **overrides) -> dict:
    """The single ModelConfig.moe → MoE-layer kwargs mapping, shared by the
    training adapter below and the ragged inference forward
    (inference/engine_v2.py) so new MoEConfig fields can't silently drift
    between the two."""
    moe = cfg.moe
    kw = dict(
        hidden_size=cfg.hidden_size,
        num_experts=moe.num_experts,
        ffn_size=cfg.ffn_size,
        k=moe.top_k,
        capacity_factor=moe.capacity_factor,
        eval_capacity_factor=moe.eval_capacity_factor,
        min_capacity=moe.min_capacity,
        activation=cfg.activation,   # Experts routes non-GLU through _ACTS
        aux_loss_weight=moe.aux_loss_weight,
        z_loss_weight=moe.router_z_loss_weight,
        dropless=moe.dropless,
        dropless_block_m=moe.dropless_block_m,
        normalize_gates=moe.normalize_gates,
        router_score=moe.router_score,
        routed_scaling_factor=moe.routed_scaling_factor,
    )
    kw.update(overrides)
    return kw


class MoEFFN(nn.Module):
    """Routed expert FFN — thin adapter over the first-class MoE layer
    (deepspeed_tpu/moe/layer.py; reference deepspeed/moe/layer.py:17), plus
    the optional qwen2-moe-style sigmoid-gated shared expert."""
    config: ModelConfig

    @nn.compact
    def __call__(self, x, deterministic: bool = True, router_x=None):
        from ..moe.layer import MoE

        cfg = self.config
        out = MoE(**moe_layer_kwargs(cfg), name="moe_layer")(
            x, deterministic, router_x=router_x)
        se = cfg.moe.shared_expert_intermediate
        if se:
            shared_cfg = dataclasses.replace(cfg, intermediate_size=se)
            shared = DenseFFN(shared_cfg, name="shared_expert")(x)
            if not cfg.moe.shared_expert_gated:
                return out + shared
            gate = self.param("shared_gate", nn.with_partitioning(
                _dense_init(), ("embed", None)),
                (cfg.hidden_size, 1), jnp.float32)
            g = jax.nn.sigmoid(
                jnp.einsum("bse,eo->bso", x.astype(jnp.float32), gate))
            out = out + g.astype(out.dtype) * shared
        return out


class Block(nn.Module):
    config: ModelConfig
    use_moe: bool = False
    kind: str = ""        # a LAYER_KINDS name; "" → the model's one kind

    @nn.compact
    def __call__(self, x, positions, kv_cache=None, attn_mask=None, deterministic=True):
        cfg = self.config
        if self.kind == CONV:
            # x = x + conv(norm(x)); x = x + ff(norm(x)): LFM2's block.
            # No v1 decode cache: a conv layer's state is served by
            # InferenceEngineV2 (a record a slot), not by kv_caches
            if kv_cache is not None or cfg.parallel_block \
                    or not cfg.pre_norm:
                raise ValueError(
                    "a 'conv' layer runs pre-norm, sequential, and without "
                    "a v1 kv_cache (serve it through InferenceEngineV2)")
            x = x + ShortConv(cfg, name="conv")(Norm(cfg, name="ln_attn")(x))
            h = Norm(cfg, name="ln_ffn")(x)
            if self.use_moe:
                return x + MoEFFN(cfg, name="moe")(
                    h, deterministic=deterministic)
            return x + DenseFFN(dense_ffn_config(cfg), name="ffn")(h)
        if cfg.parallel_block:
            # falcon-7b/gpt-j/phi: ONE pre-norm feeds attention and ffn;
            # gpt-neox/falcon-40b keep separate norms per branch
            # (parallel_block_norms=2) — reference falcon/gptneox containers
            h = Norm(cfg, name="ln_attn")(x)
            attn_out = attention_cls(cfg)(cfg, self.kind, name="attn")(h, positions,
                                                   kv_cache=kv_cache,
                                                   attn_mask=attn_mask)
            if kv_cache is not None:
                attn_out, new_cache = attn_out
            else:
                new_cache = None
            h_ffn = h if cfg.parallel_block_norms == 1 \
                else Norm(cfg, name="ln_ffn")(x)
            if self.use_moe:
                ffn_out = MoEFFN(cfg, name="moe")(h_ffn, deterministic=deterministic)
            else:
                ffn_out = DenseFFN(dense_ffn_config(cfg), name="ffn")(h_ffn)
            x = x + attn_out + ffn_out
            if kv_cache is not None:
                return x, new_cache
            return x
        drop = (lambda t: nn.Dropout(cfg.dropout, deterministic=deterministic)(t)) \
            if cfg.dropout > 0 else (lambda t: t)

        if not cfg.pre_norm:
            # post-norm residuals (original BERT layout; the reference's
            # DeepSpeedTransformerConfig pre_layer_norm=False mode)
            attn_out = attention_cls(cfg)(cfg, self.kind, name="attn")(x, positions,
                                                   kv_cache=kv_cache,
                                                   attn_mask=attn_mask)
            if kv_cache is not None:
                attn_out, new_cache = attn_out
            else:
                new_cache = None
            x = Norm(cfg, name="ln_attn")(x + drop(attn_out))
            if self.use_moe:
                ffn_out = MoEFFN(cfg, name="moe")(x, deterministic=deterministic)
            else:
                ffn_out = DenseFFN(dense_ffn_config(cfg), name="ffn")(x)
            x = Norm(cfg, name="ln_ffn")(x + drop(ffn_out))
            if kv_cache is not None:
                return x, new_cache
            return x

        h_in = Norm(cfg, name="ln_attn")(x)
        attn_out = attention_cls(cfg)(cfg, self.kind, name="attn")(
            h_in, positions, kv_cache=kv_cache, attn_mask=attn_mask)
        if kv_cache is not None:
            attn_out, new_cache = attn_out
        else:
            new_cache = None
        x = x + drop(attn_out)
        h = Norm(cfg, name="ln_ffn")(x)
        if self.use_moe:
            ffn_out = MoEFFN(cfg, name="moe")(
                h, deterministic=deterministic,
                router_x=h_in if cfg.moe.router_input == "attn" else None)
        else:
            ffn_out = DenseFFN(dense_ffn_config(cfg), name="ffn")(h)
        x = x + drop(ffn_out)
        if kv_cache is not None:
            return x, new_cache
        return x


class TransformerLM(nn.Module):
    """The flagship causal LM."""
    config: ModelConfig

    @nn.compact
    def __call__(self, input_ids, positions=None, kv_caches=None, attn_mask=None,
                 deterministic: bool = True, token_type_ids=None,
                 return_hidden: bool = False):
        cfg = self.config
        B, S = input_ids.shape
        if not cfg.causal and kv_caches is not None:
            raise ValueError("bidirectional encoders have no decode path")
        if positions is None:
            positions = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32), (B, S))

        embed = self.param("embed", nn.with_partitioning(
            nn.initializers.normal(0.02), ("vocab", "embed")),
            (cfg.vocab_size, cfg.hidden_size), jnp.float32)
        with device_scope("embed"):
            x = embed.astype(cfg.dtype)[input_ids]
        if cfg.position_embedding == "learned":
            pos_emb = self.param("pos_embed", nn.with_partitioning(
                nn.initializers.normal(0.02), (None, "embed")),
                (cfg.max_seq_len, cfg.hidden_size), jnp.float32)
            with device_scope("embed"):
                x = x + pos_emb.astype(cfg.dtype)[positions]
        if cfg.type_vocab_size:
            type_emb = self.param("type_embed", nn.with_partitioning(
                nn.initializers.normal(0.02), (None, "embed")),
                (cfg.type_vocab_size, cfg.hidden_size), jnp.float32)
            if token_type_ids is None:
                token_type_ids = jnp.zeros_like(input_ids)
            with device_scope("embed"):
                x = x + type_emb.astype(cfg.dtype)[token_type_ids]
        if cfg.embed_norm or not cfg.pre_norm:
            # bert: layernorm + dropout on the embedding sum; bloom:
            # word_embeddings_layernorm ahead of pre-norm blocks
            x = Norm(cfg, name="ln_embed")(x)
        if cfg.dropout > 0:
            x = nn.Dropout(cfg.dropout, deterministic=deterministic)(x)
        x = constrain(x, BATCH, SEQ, EMBED)

        block_cls = Block
        if cfg.remat:
            from ..ops.remat import remat_module

            # remat=True always checkpoints; 'none' would contradict it.
            # "auto" outside an engine is the ladder's first rung
            policy = cfg.remat_policy if cfg.remat_policy != "none" else "full"
            block_cls = remat_module(Block, policy=policy, static_argnums=(4,))

        new_caches = [] if kv_caches is not None else None
        for i in range(cfg.num_layers):
            use_moe = is_moe_layer(cfg, i)
            cache = kv_caches[i] if kv_caches is not None else None
            out = block_cls(cfg, use_moe=use_moe, kind=cfg.layer_kind(i),
                            name=f"layer_{i}")(
                x, positions, cache, attn_mask, deterministic)
            if kv_caches is not None:
                x, c = out
                new_caches.append(c)
            else:
                x = out

        if cfg.pre_norm:  # post-norm layers already end normalized
            x = Norm(cfg, name="ln_final")(x)
        if return_hidden:
            # pre-head hidden states for the fused vocab-chunked head loss
            # (models/loss.py fused_lm_head_loss) — the [B,S,V] logits are
            # never built
            return x
        if cfg.tie_embeddings:
            with device_scope("head_loss"):
                logits = jnp.einsum("bse,ve->bsv", x, embed.astype(cfg.dtype))
        else:
            unembed = self.param("unembed", nn.with_partitioning(
                nn.initializers.normal(0.02), ("embed", "vocab")),
                (cfg.hidden_size, cfg.vocab_size), jnp.float32)
            with device_scope("head_loss"):
                logits = jnp.einsum("bse,ev->bsv", x,
                                    unembed.astype(cfg.dtype))
        if cfg.unembed_bias:
            ub = self.param("unembed_b", nn.with_partitioning(
                nn.initializers.zeros, ("vocab",)),
                (cfg.vocab_size,), jnp.float32)
            with device_scope("head_loss"):
                logits = logits + ub.astype(cfg.dtype)
        logits = constrain(logits, BATCH, SEQ, None)
        if kv_caches is not None:
            return logits, new_caches
        return logits
