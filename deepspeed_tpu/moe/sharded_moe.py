"""Gating + dispatch algebra for Mixture-of-Experts.

TPU-native re-design of reference deepspeed/moe/sharded_moe.py
(``top1gating`` :183, ``top2gating`` :290, ``topkgating`` :374,
``TopKGate`` :449, ``MOELayer`` :533, ``_AllToAll`` :96).

The reference dispatches tokens with an explicit ``all_to_all_single`` and
einsum-built combine/dispatch masks. Here the same combine/dispatch masks
are built in pure XLA ops; the all-to-all materializes from GSPMD sharding:
token tensors are sharded over the batch axes while expert tensors are
sharded over ``expert``, so the dispatch einsum lowers to exactly the
reference's a2a, scheduled by the compiler. Everything is static-shaped
(capacity-bounded) — the TPU-friendly formulation.

Gating math follows GShard (top-1/2) and the reference's generalized top-k:
softmax → top-k experts per token → capacity-bounded position assignment →
renormalized gates → load-balance aux loss + router z-loss.
"""
from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp


class GateOutput(NamedTuple):
    """Mirrors the reference gating return (l_aux, combine, dispatch,
    exp_counts)."""
    aux_loss: jax.Array        # scalar load-balance loss (unweighted)
    combine: jax.Array         # [G, S, n, cap] fp — gate * position one-hot
    dispatch: jax.Array        # [G, S, n, cap] bool-ish fp mask
    exp_counts: jax.Array      # [n] tokens routed per expert (pre-capacity)
    z_loss: jax.Array          # router z-loss (unweighted)


def compute_capacity(tokens_per_group: int, num_experts: int, k: int,
                     capacity_factor: float, min_capacity: int) -> int:
    """Static per-group expert capacity (reference _capacity, sharded_moe.py)."""
    cap = int(k * tokens_per_group / num_experts * capacity_factor)
    return max(cap, min_capacity)


def topkgating(logits: jax.Array,
               k: int,
               capacity_factor: float = 1.0,
               min_capacity: int = 4,
               *,
               noise_rng: jax.Array | None = None,
               noise_eps: float = 1e-2,
               drop_tokens: bool = True,
               normalize_gates: bool = True) -> GateOutput:
    """Generalized top-k gating (reference topkgating :374; k=1 ≈ top1gating,
    k=2 ≈ top2gating).

    ``logits``: [G, S, n] router outputs per token group (G groups of S
    tokens — groups bound capacity locally so shapes stay static).
    ``noise_rng``: optional RNG for jittered gating (reference
    ``noisy_gate_policy='RSample'``).
    """
    G, S, n = logits.shape
    logits = logits.astype(jnp.float32)
    if noise_rng is not None:
        logits = logits + jax.random.normal(noise_rng, logits.shape) * noise_eps
    probs = jax.nn.softmax(logits, axis=-1)

    if drop_tokens:
        capacity = compute_capacity(S, n, k, capacity_factor, min_capacity)
    else:
        capacity = S * k  # nothing can overflow

    gate_vals, expert_idx = jax.lax.top_k(probs, k)                # [G,S,k]
    onehot = jax.nn.one_hot(expert_idx, n, dtype=jnp.float32)      # [G,S,k,n]

    # position of each (token, choice) in its expert's queue: earlier tokens
    # first, within a token the higher-ranked choice first
    flat = onehot.reshape(G, S * k, n)
    pos_in_expert = jnp.cumsum(flat, axis=1) * flat - 1.0
    pos_in_expert = pos_in_expert.reshape(G, S, k, n)
    keep = (pos_in_expert < capacity) & (onehot > 0)
    pos = jnp.clip(jnp.sum(pos_in_expert * onehot, axis=-1), 0, capacity - 1)
    kept_gate = gate_vals * jnp.sum(keep, axis=-1)                 # drop → 0

    if normalize_gates:
        denom = jnp.sum(kept_gate, axis=-1, keepdims=True)
        kept_gate = kept_gate / jnp.maximum(denom, 1e-9)

    # load-balance aux loss (GShard eq.; reference top1gating :183)
    me = jnp.mean(probs, axis=(0, 1))                              # [n]
    ce = jnp.mean(jnp.sum(onehot, axis=2), axis=(0, 1))            # [n]
    aux_loss = jnp.sum(me * ce) * n
    z_loss = jnp.mean(jnp.square(jax.nn.logsumexp(logits, axis=-1)))

    pos_oh = jax.nn.one_hot(pos.astype(jnp.int32), capacity,
                            dtype=jnp.float32)                     # [G,S,k,cap]
    keepf = keep.astype(jnp.float32) * onehot                      # [G,S,k,n]
    dispatch = jnp.einsum("gskn,gskc->gsnc", keepf, pos_oh)
    combine = jnp.einsum("gsk,gskn,gskc->gsnc", kept_gate, keepf, pos_oh)

    exp_counts = jnp.sum(onehot, axis=(0, 1, 2))
    return GateOutput(aux_loss=aux_loss, combine=combine, dispatch=dispatch,
                      exp_counts=exp_counts, z_loss=z_loss)


class DroplessGateOutput(NamedTuple):
    """Routing for the dropless (megablocks-style) path: raw top-k choices
    instead of capacity masks."""
    gates: jax.Array           # [G, S, k] normalized gate weights
    experts: jax.Array         # [G, S, k] int32 expert ids
    aux_loss: jax.Array
    z_loss: jax.Array
    exp_counts: jax.Array      # [n]


ROUTER_SCORES = ("softmax", "sigmoid_bias")


def topk_dropless_gating(logits: jax.Array, k: int, *,
                         noise_rng: jax.Array | None = None,
                         noise_eps: float = 1e-2,
                         normalize_gates: bool = True,
                         score: str = "softmax",
                         bias: jax.Array | None = None,
                         scale: float = 1.0) -> DroplessGateOutput:
    """Top-k routing with NO capacity and NO drops — every token reaches
    all k chosen experts (the megablocks contract; tokens are instead
    block-aligned per expert by ``sort_tokens_by_expert``).

    ``score`` (``MoEConfig.router_score``, read HERE and nowhere else, at
    trace time): "softmax" — the k largest of the softmax over all experts,
    renormalised where ``normalize_gates``; "sigmoid_bias" — ``s =
    sigmoid(logits)``, the experts are the k largest of ``s + bias`` (the
    bias ``[n]`` moves the SELECTION only), the weights are ``s`` at the
    chosen k, divided by their sum + 1e-6 where ``normalize_gates``.
    ``scale`` (``MoEConfig.routed_scaling_factor``, applied HERE and nowhere
    else): the gate weights times a constant, after the normalisation —
    the routed experts' summed output scaled (deepseek-v3: 2.448 where six
    normalised weights would sum to 1)."""
    G, S, n = logits.shape
    logits = logits.astype(jnp.float32)
    if noise_rng is not None:
        logits = logits + jax.random.normal(noise_rng, logits.shape) * noise_eps
    if score == "softmax":
        probs = jax.nn.softmax(logits, axis=-1)
        gate_vals, expert_idx = jax.lax.top_k(probs, k)            # [G,S,k]
        if normalize_gates:
            gate_vals = gate_vals / jnp.maximum(
                jnp.sum(gate_vals, axis=-1, keepdims=True), 1e-9)
    elif score == "sigmoid_bias":
        probs = jax.nn.sigmoid(logits)
        _, expert_idx = jax.lax.top_k(
            probs + bias.astype(jnp.float32), k)
        gate_vals = jnp.take_along_axis(probs, expert_idx, axis=-1)
        if normalize_gates:
            gate_vals = gate_vals / (
                jnp.sum(gate_vals, axis=-1, keepdims=True) + 1e-6)
    else:
        raise ValueError(f"router score {score!r} is not one of "
                         f"{ROUTER_SCORES}")
    if scale != 1.0:
        gate_vals = gate_vals * scale

    onehot = jax.nn.one_hot(expert_idx, n, dtype=jnp.float32)      # [G,S,k,n]
    me = jnp.mean(probs, axis=(0, 1))
    ce = jnp.mean(jnp.sum(onehot, axis=2), axis=(0, 1))
    aux_loss = jnp.sum(me * ce) * n
    z_loss = jnp.mean(jnp.square(jax.nn.logsumexp(logits, axis=-1)))
    exp_counts = jnp.sum(onehot, axis=(0, 1, 2))
    return DroplessGateOutput(gates=gate_vals,
                              experts=expert_idx.astype(jnp.int32),
                              aux_loss=aux_loss, z_loss=z_loss,
                              exp_counts=exp_counts)


def top1gating(logits: jax.Array, capacity_factor: float = 1.0,
               min_capacity: int = 4, **kw) -> GateOutput:
    """Switch-style top-1 gating (reference top1gating :183)."""
    return topkgating(logits, 1, capacity_factor, min_capacity,
                      normalize_gates=False, **kw)


def top2gating(logits: jax.Array, capacity_factor: float = 1.0,
               min_capacity: int = 4, **kw) -> GateOutput:
    """GShard top-2 gating (reference top2gating :290)."""
    return topkgating(logits, 2, capacity_factor, min_capacity, **kw)
