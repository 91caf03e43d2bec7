"""Plain reference of a dense pre-norm decoder (the Mistral-7B block):
RMSNorm, rotary positions, grouped-query causal attention, SwiGLU — in
straightforward float32 ``jax.numpy`` under
``default_matmul_precision("highest")``, with no kernel, no cache and no
batching. Independent of the code under test: it shares no function with
``deepspeed_tpu``; a runner hands it the WEIGHTS (a seeded tree, its bf16
values held in float32) one layer at a time, so that a 0.9 GB float32
layer fits beside whatever else the device holds.

Departure from the published model, stated: rotary embedding rotates
interleaved pairs ``(x[2i], x[2i+1])`` as the program does, where the
checkpoint format rotates the two halves. The two are the same function up
to a fixed permutation of the query/key projection's columns, which seeded
random weights absorb.
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


def layer_forward(x, w: dict, positions, *, theta: float, eps: float):
    """One block on x [S, E]. ``w``: ln_attn [E], wq [E,H,D], wk/wv
    [E,KV,D], wo [H,D,E], ln_ffn [E], w_gate/w_up [E,F], w_down [F,E]."""
    S = x.shape[0]
    H, D = w["wq"].shape[1:]
    KV = w["wk"].shape[1]
    h = rms_norm(x, w["ln_attn"], eps)
    q = rotary(jnp.einsum("se,ehd->shd", h, w["wq"]), positions, theta)
    k = rotary(jnp.einsum("se,ehd->shd", h, w["wk"]), positions, theta)
    v = jnp.einsum("se,ehd->shd", h, w["wv"])
    q = q.reshape(S, KV, H // KV, D)                 # query heads by KV group
    s = jnp.einsum("sgrd,tgd->grst", q, k) / math.sqrt(D)
    causal = positions[:, None] >= positions[None, :]
    s = jnp.where(causal[None, None], s, -jnp.inf)
    p = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("grst,tgd->sgrd", p, v).reshape(S, H, D)
    x = x + jnp.einsum("shd,hde->se", o, w["wo"])
    h = rms_norm(x, w["ln_ffn"], eps)
    f = jax.nn.silu(h @ w["w_gate"]) * (h @ w["w_up"])
    return x + f @ w["w_down"]


_layer_step = jax.jit(layer_forward, static_argnames=("theta", "eps"))


def forward_logits(tokens, *, embed, layer: Callable[[int], dict],
                   num_layers: int, ln_final, unembed, theta: float,
                   eps: float, rows=None):
    """Teacher-forced logits [len(rows) or S, V] of one sequence ``tokens``
    [S]. ``layer(i)`` returns layer ``i``'s weights (any float dtype; cast
    to float32 here, one layer at a time). ``rows`` selects positions
    before the vocabulary projection."""
    f32 = lambda t: jax.tree.map(lambda a: jnp.asarray(a, jnp.float32), t)
    with jax.default_matmul_precision("highest"):
        tokens = jnp.asarray(tokens, jnp.int32)
        positions = jnp.arange(tokens.shape[0], dtype=jnp.int32)
        x = jnp.asarray(embed)[tokens].astype(jnp.float32)
        for i in range(num_layers):
            x = _layer_step(x, f32(layer(i)), positions, theta=theta, eps=eps)
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
    return {"ln_attn": t["ln_attn"]["scale"], "ln_ffn": t["ln_ffn"]["scale"],
            "wq": t["attn"]["wq"], "wk": t["attn"]["wk"],
            "wv": t["attn"]["wv"], "wo": t["attn"]["wo"],
            "w_gate": t["ffn"]["w_gate"], "w_up": t["ffn"]["w_up"],
            "w_down": t["ffn"]["w_down"]}
