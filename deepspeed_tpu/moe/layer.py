"""First-class MoE layer + experts container.

TPU-native re-design of reference deepspeed/moe/layer.py (``MoE`` :17) and
experts.py (``Experts`` :13). The reference wraps a user expert module,
deep-copies it ``num_local_experts`` times, and moves tokens between
expert-parallel ranks with explicit all-to-alls. Here the experts are ONE
stacked parameter tree with a leading ``expert`` logical axis (grouped-GEMM
layout — the megablocks-style formulation the MXU likes) and the
dispatch/combine einsums lower to the expert all-to-all via GSPMD.

TP↔EP activation remapping (reference moe/mappings.py _gather_tokens /
_drop_tokens) is likewise a sharding change: the dispatch einsum's operands
carry batch-axis sharding in, expert-axis sharding out — no manual gather.
"""
from __future__ import annotations

from typing import Callable

import flax.linen as nn
import jax
import jax.numpy as jnp

from ..parallel.axes import (BATCH, BATCH_NOEXP, EMBED, EXPERT, SEQ,
                             constrain as _constrain)
from ..utils.annotations import device_scope
from .sharded_moe import GateOutput, topk_dropless_gating, topkgating


class TopKGate(nn.Module):
    """Router (reference sharded_moe.py:449 ``TopKGate``): fp32 linear +
    top-k capacity gating. Sows nothing; returns the GateOutput."""
    hidden_size: int
    num_experts: int
    k: int = 2
    capacity_factor: float = 1.0
    eval_capacity_factor: float = 1.0
    min_capacity: int = 4
    noisy_gate_policy: str | None = None     # None | 'RSample'
    drop_tokens: bool = True
    dropless: bool = False
    #: renormalize top-k gates to sum to 1 (False = raw softmax probs,
    #: qwen2-moe norm_topk_prob=False semantics)
    normalize_gates: bool = True
    #: ``MoEConfig.router_score``; "sigmoid_bias" adds the parameter
    #: ``bias`` [n] (seeded small and NON-zero, so that selection by
    #: ``s + b`` and weights from ``s`` differ under seeded weights)
    router_score: str = "softmax"
    #: ``MoEConfig.routed_scaling_factor`` (dropless routing only)
    routed_scaling_factor: float = 1.0

    @nn.compact
    def __call__(self, x: jax.Array, deterministic: bool = True):
        wg = self.param(
            "wg",
            nn.with_partitioning(nn.initializers.variance_scaling(
                1.0, "fan_in", "normal"), ("embed", "expert")),
            (self.hidden_size, self.num_experts), jnp.float32)
        logits = jnp.einsum("gse,en->gsn", x.astype(jnp.float32), wg)
        rng = None
        if self.noisy_gate_policy == "RSample" and not deterministic:
            rng = self.make_rng("gating")
        if self.routed_scaling_factor != 1.0 and not self.dropless:
            raise ValueError("routed_scaling_factor routes dropless only "
                             "(MoEConfig.dropless=True)")
        bias = None
        if self.router_score != "softmax":
            if not self.dropless:
                raise ValueError(
                    f"router_score {self.router_score!r} routes dropless "
                    f"only (MoEConfig.dropless=True): capacity gating is "
                    f"softmax's")
            bias = self.param("bias", nn.with_partitioning(
                nn.initializers.normal(0.02), ("expert",)),
                (self.num_experts,), jnp.float32)
        if self.dropless:
            return topk_dropless_gating(logits, self.k, noise_rng=rng,
                                        normalize_gates=self.normalize_gates,
                                        score=self.router_score, bias=bias,
                                        scale=self.routed_scaling_factor)
        return topkgating(
            logits, self.k,
            self.eval_capacity_factor if deterministic else self.capacity_factor,
            self.min_capacity, noise_rng=rng, drop_tokens=self.drop_tokens,
            normalize_gates=self.normalize_gates)


def dropless_dispatch_combine(x2d: jax.Array, gates: jax.Array,
                              experts: jax.Array, num_experts: int, k: int,
                              block_m: int, gemm: Callable,
                              live: jax.Array | None = None) -> jax.Array:
    """Shared megablocks-style dispatch/combine (used by the dropless
    training path below AND every routed-expert layer of the v2 serving
    forward — inference/engine_v2.py ``routed_experts``, quantised or not
    — so routing fixes reach all of them).

    Sort the [T, k] expert choices into the layout of a block-aligned
    buffer and GATHER each buffer row's token into it (padding rows zero;
    no scatter on the way in, forward or backward: ``ExpertSort``,
    ``gather_expert_rows``), run ``gemm(buf, sort) -> [Tp, F]`` (the only
    part that differs between callers: bf16 grouped GEMM vs quantized
    grouped GEMM), gather each token's k rows back and combine with its
    gates (as the router gave them: renormalised or not).

    ``live`` ([T] bool; None: every token, as training passes) says which
    tokens exist: a serving program's rows that carry no request reach no
    expert (the sort counts them for none, so no tile and no weight block
    is theirs) and their output is exactly zero. A select, not a zero
    gate: a masked entry's index, clamped into the buffer, fetches a row
    the kernel may never have written (a tile at or past ``n_tiles``), and
    NaN times 0 is NaN.
    """
    from ..ops.pallas.grouped_matmul import (gather_expert_rows,
                                             gather_token_rows,
                                             sort_tokens_by_expert)

    T = x2d.shape[0]
    with device_scope("moe_dispatch"):
        srt = sort_tokens_by_expert(experts.reshape(T, k), num_experts,
                                    block_m, live)
        buf = gather_expert_rows(x2d, srt.src, srt.dst)    # [Tp, E]
    with device_scope("moe_experts"):
        out_buf = gemm(buf, srt)
    with device_scope("moe_combine"):
        # (a masked entry's ``dst`` is one past the buffer: held inside it
        # for the gather, and what it fetched thrown away)
        dst = srt.dst if live is None else jnp.minimum(srt.dst, srt.Tp - 1)
        rows_out = gather_token_rows(out_buf, srt.src,
                                     dst).reshape(T, k, -1)
        if live is not None:
            rows_out = jnp.where(live[:, None, None], rows_out, 0)
        return jnp.einsum("tk,tke->te",
                          gates.reshape(T, k).astype(x2d.dtype), rows_out)


class Experts(nn.Module):
    """Stacked expert FFNs (reference experts.py:13) as one grouped GEMM.

    The expert body is a SwiGLU FFN by default; ``activation='gelu'`` picks
    the GPT-style two-matrix variant.
    """
    hidden_size: int
    ffn_size: int
    num_experts: int
    activation: str = "silu_glu"

    @nn.compact
    def __call__(self, x: jax.Array, sort=None,
                 block_m: int = 128) -> jax.Array:
        """Capacity mode (``sort=None``): x [n, g, cap, E] → same shape.
        Dropless mode: x is the expert-sorted padded buffer [Tp, E] and
        ``sort`` an ``ExpertSort``; experts run as Pallas grouped GEMMs
        (reference cutlass_ops/moe_gemm analogue)."""
        E, F, n = self.hidden_size, self.ffn_size, self.num_experts
        init = nn.initializers.variance_scaling(1.0, "fan_in", "normal")
        dtype = x.dtype
        from ..models.transformer import _ACTS, GLU_ACTS

        glu = self.activation in GLU_ACTS
        if glu:
            wg = self.param("w_gate", nn.with_partitioning(
                init, ("expert", "embed", "expert_mlp")), (n, E, F), jnp.float32)
        wu = self.param("w_up", nn.with_partitioning(
            init, ("expert", "embed", "expert_mlp")), (n, E, F), jnp.float32)
        wd = self.param("w_down", nn.with_partitioning(
            init, ("expert", "expert_mlp", "embed")), (n, F, E), jnp.float32)

        act = GLU_ACTS[self.activation] if glu else _ACTS[self.activation]
        if sort is not None:
            from ..ops.pallas.grouped_matmul import grouped_matmul

            te = sort.tile_expert
            if glu:
                h = act(grouped_matmul(x, wg.astype(dtype), te,
                                       block_m)) * \
                    grouped_matmul(x, wu.astype(dtype), te, block_m)
            else:
                h = act(grouped_matmul(x, wu.astype(dtype), te, block_m))
            return grouped_matmul(h, wd.astype(dtype), te, block_m)

        if glu:
            h = act(jnp.einsum("ngce,nef->ngcf", x, wg.astype(dtype))) * \
                jnp.einsum("ngce,nef->ngcf", x, wu.astype(dtype))
        else:
            h = act(jnp.einsum("ngce,nef->ngcf", x, wu.astype(dtype)))
        return jnp.einsum("ngcf,nfe->ngce", h, wd.astype(dtype))


class MoE(nn.Module):
    """The user-facing MoE layer (reference moe/layer.py:17 ``MoE``).

    Input [B, S, E] (batch-sharded) → routed expert FFN → [B, S, E].
    Sows ``losses/moe_aux_loss`` (weighted aux + z loss) for the engine's
    loss function to pick up — the role of the reference's l_aux return.
    """
    hidden_size: int
    num_experts: int = 8
    ffn_size: int | None = None
    k: int = 2
    capacity_factor: float = 1.0
    eval_capacity_factor: float = 1.0
    min_capacity: int = 4
    noisy_gate_policy: str | None = None
    drop_tokens: bool = True
    activation: str = "silu_glu"
    aux_loss_weight: float = 0.01
    z_loss_weight: float = 0.001
    #: megablocks-style dropless routing via the Pallas grouped GEMM.
    #: Single-device / shard_map-local only (pallas_call has no GSPMD
    #: partitioning rule) — the capacity path is the multi-device default.
    dropless: bool = False
    dropless_block_m: int = 128
    normalize_gates: bool = True
    router_score: str = "softmax"
    routed_scaling_factor: float = 1.0

    @nn.compact
    def __call__(self, x: jax.Array, deterministic: bool = True,
                 router_x: jax.Array | None = None) -> jax.Array:
        """``router_x``: what the router reads where that is not what the
        experts read (``MoEConfig.router_input``); None → ``x``."""
        B, S, E = x.shape
        dtype = x.dtype
        gate = TopKGate(
            hidden_size=self.hidden_size, num_experts=self.num_experts,
            k=self.k, capacity_factor=self.capacity_factor,
            eval_capacity_factor=self.eval_capacity_factor,
            min_capacity=self.min_capacity,
            noisy_gate_policy=self.noisy_gate_policy,
            drop_tokens=self.drop_tokens, dropless=self.dropless,
            normalize_gates=self.normalize_gates,
            router_score=self.router_score,
            routed_scaling_factor=self.routed_scaling_factor,
            name="gate")(x if router_x is None else router_x, deterministic)

        self.sow("losses", "moe_aux_loss",
                 gate.aux_loss * self.aux_loss_weight +
                 gate.z_loss * self.z_loss_weight)

        if self.dropless:
            bm = self.dropless_block_m
            experts_mod = Experts(
                hidden_size=self.hidden_size,
                ffn_size=self.ffn_size or 4 * self.hidden_size,
                num_experts=self.num_experts,
                activation=self.activation, name="experts")
            y = dropless_dispatch_combine(
                x.reshape(B * S, E), gate.gates, gate.experts,
                self.num_experts, self.k, bm,
                lambda buf, srt: experts_mod(buf, sort=srt, block_m=bm))
            return _constrain(y.reshape(B, S, E), BATCH, SEQ, EMBED)

        # dispatch: [B,S,E] tokens → [n, B, cap, E] expert inputs. Under
        # GSPMD this einsum IS the expert all-to-all (_AllToAll :96).
        # Pin the token operand first: without it, propagation inside a
        # pipe-stage shard_map invents shardings over size-1 dims that
        # the partitioner can only reach via full rematerialization
        # (measured in the pipe x expert dryrun).
        x = _constrain(x, BATCH, SEQ, EMBED)
        expert_in = jnp.einsum("gsnc,gse->ngce",
                               gate.dispatch.astype(dtype), x)
        expert_in = _constrain(expert_in, EXPERT, BATCH_NOEXP, None, EMBED)

        expert_out = Experts(
            hidden_size=self.hidden_size,
            ffn_size=self.ffn_size or 4 * self.hidden_size,
            num_experts=self.num_experts,
            activation=self.activation, name="experts")(expert_in)
        expert_out = _constrain(expert_out, EXPERT, BATCH_NOEXP, None, EMBED)

        out = jnp.einsum("gsnc,ngce->gse", gate.combine.astype(dtype), expert_out)
        return _constrain(out, BATCH, SEQ, EMBED)
