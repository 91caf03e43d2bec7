"""Plain reference of the OLMoE decoder block (allenai/OLMoE-1B-7B, HF
``modeling_olmoe.py``): RMSNorm, q and k projections normalised as WHOLE
vectors before they are split into heads, rotary positions, causal
attention, and a sparse feed-forward layer — a float32 softmax router over
all experts, the top k taken with their softmax weights NOT renormalised,
each a SwiGLU FFN — in straightforward float32 ``jax.numpy`` under
``default_matmul_precision("highest")``. No sort, no kernel, no cache, no
batching: the experts are a dense mask (every expert runs on every token
and the router's weights, zero off the top k, select). Independent of the
code under test: it shares no function with ``deepspeed_tpu``; a runner
hands it the WEIGHTS (a seeded tree, its bf16 values held in float32) one
layer at a time, so that a 1.6 GB float32 layer fits beside whatever else
the device holds.

Departure from the published model, stated: rotary embedding rotates
interleaved pairs ``(x[2i], x[2i+1])`` as the program does, where the
checkpoint format rotates the two halves. The two are the same function up
to a fixed permutation of the query/key projection's columns (and of the
q/k norm scales with them), which seeded random weights absorb.
"""
from __future__ import annotations

import math
from typing import Callable

import jax
import jax.numpy as jnp


def rms_norm(x, scale, eps):
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * scale


def rotary(x, positions, theta):
    """x [S, H, D], rotated pairwise at ``positions`` [S]."""
    d = x.shape[-1]
    inv = 1.0 / (theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d))
    ang = positions.astype(jnp.float32)[:, None] * inv[None, :]     # [S, D/2]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., 0::2], x[..., 1::2]
    return jnp.stack([x1 * cos - x2 * sin, x2 * cos + x1 * sin],
                     axis=-1).reshape(x.shape)


def route(h, w_router, top_k: int, renormalise: bool = False):
    """(weights [S, n] — the softmax probability on a token's top k experts,
    zero elsewhere — and the chosen experts [S, k])."""
    p = jax.nn.softmax(h @ w_router, axis=-1)                       # float32
    g, e = jax.lax.top_k(p, top_k)
    if renormalise:
        g = g / jnp.sum(g, axis=-1, keepdims=True)
    rows = jnp.arange(h.shape[0])[:, None]
    return jnp.zeros_like(p).at[rows, e].set(g), e


def layer_forward(x, w: dict, positions, *, theta: float, eps: float,
                  top_k: int, renormalise: bool = False):
    """One block on x [S, E]; returns (x', experts chosen [S, k]). ``w``:
    ln_attn [E], wq [E,H,D], wk/wv [E,KV,D], q_norm [H,D], k_norm [KV,D],
    wo [H,D,E], ln_ffn [E], w_router [E,n], w_gate/w_up [n,E,F], w_down
    [n,F,E]."""
    S = x.shape[0]
    H, D = w["wq"].shape[1:]
    KV = w["wk"].shape[1]
    h = rms_norm(x, w["ln_attn"], eps)
    # the norm runs over the whole projection, all heads at once
    q = rms_norm((h @ w["wq"].reshape(-1, H * D)), w["q_norm"].reshape(-1),
                 eps).reshape(S, H, D)
    k = rms_norm((h @ w["wk"].reshape(-1, KV * D)), w["k_norm"].reshape(-1),
                 eps).reshape(S, KV, D)
    q, k = rotary(q, positions, theta), rotary(k, positions, theta)
    v = jnp.einsum("se,ehd->shd", h, w["wv"])
    q = q.reshape(S, KV, H // KV, D)                 # query heads by KV group
    s = jnp.einsum("sgrd,tgd->grst", q, k) / math.sqrt(D)
    causal = positions[:, None] >= positions[None, :]
    s = jnp.where(causal[None, None], s, -jnp.inf)
    p = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("grst,tgd->sgrd", p, v).reshape(S, H, D)
    x = x + jnp.einsum("shd,hde->se", o, w["wo"])
    h = rms_norm(x, w["ln_ffn"], eps)
    weights, chosen = route(h, w["w_router"], top_k, renormalise)
    f = jax.nn.silu(jnp.einsum("se,nef->nsf", h, w["w_gate"])) \
        * jnp.einsum("se,nef->nsf", h, w["w_up"])
    y = jnp.einsum("nsf,nfe->nse", f, w["w_down"])                  # [n,S,E]
    return x + jnp.einsum("sn,nse->se", weights, y), chosen


_layer_step = jax.jit(layer_forward, static_argnames=(
    "theta", "eps", "top_k", "renormalise"))


def forward_logits(tokens, *, embed, layer: Callable[[int], dict],
                   num_layers: int, ln_final, unembed, theta: float,
                   eps: float, top_k: int, renormalise: bool = False,
                   rows=None, round_hidden=None, routes: list | None = None):
    """Teacher-forced logits [len(rows) or S, V] of one sequence ``tokens``
    [S]. ``layer(i)`` returns layer ``i``'s weights (any float dtype; cast
    to float32 here, one layer at a time). ``rows`` selects positions
    before the vocabulary projection. ``routes``, if a list, receives each
    layer's chosen experts [S, k]. ``round_hidden`` (a dtype) rounds the
    residual stream to that dtype after every block — NOT the reference:
    the stand-in for a lower-precision server with which a runner counts
    how many top-k sets a rounding of the hidden state moves."""
    f32 = lambda t: jax.tree.map(lambda a: jnp.asarray(a, jnp.float32), t)
    with jax.default_matmul_precision("highest"):
        tokens = jnp.asarray(tokens, jnp.int32)
        positions = jnp.arange(tokens.shape[0], dtype=jnp.int32)
        x = jnp.asarray(embed)[tokens].astype(jnp.float32)
        for i in range(num_layers):
            x, chosen = _layer_step(x, f32(layer(i)), positions, theta=theta,
                                    eps=eps, top_k=top_k,
                                    renormalise=renormalise)
            if round_hidden is not None:
                x = x.astype(round_hidden).astype(jnp.float32)
            if routes is not None:
                routes.append(chosen)
        x = rms_norm(x, f32(ln_final), eps)
        if rows is not None:
            x = x[jnp.asarray(rows, jnp.int32)]
        return x @ f32(unembed)


def lm_loss(logits, tokens):
    """Mean next-token cross entropy of one row: position t predicts token
    t+1, the last position predicts nothing."""
    logits = jnp.asarray(logits, jnp.float32)[:-1]
    tgt = jnp.asarray(tokens, jnp.int32)[1:]
    logz = jax.nn.logsumexp(logits, axis=-1)
    return jnp.mean(logz - jnp.take_along_axis(logits, tgt[:, None], 1)[:, 0])


def program_layer(tree: dict, i: int) -> dict:
    """Adapter: layer ``i`` of the program's parameter tree (``layer_<i>``
    sub-trees, or the serving engine's ``layers_stacked``) in this file's
    names. Touches names only, no arithmetic."""
    if "layers_stacked" in tree:
        t = jax.tree.map(lambda a: a[i], tree["layers_stacked"])
    else:
        t = tree[f"layer_{i}"]
    ex = t["moe"]["moe_layer"]["experts"]
    return {"ln_attn": t["ln_attn"]["scale"], "ln_ffn": t["ln_ffn"]["scale"],
            "wq": t["attn"]["wq"], "wk": t["attn"]["wk"],
            "wv": t["attn"]["wv"], "wo": t["attn"]["wo"],
            "q_norm": t["attn"]["q_norm"], "k_norm": t["attn"]["k_norm"],
            "w_router": t["moe"]["moe_layer"]["gate"]["wg"],
            "w_gate": ex["w_gate"], "w_up": ex["w_up"],
            "w_down": ex["w_down"]}
