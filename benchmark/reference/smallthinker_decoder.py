"""Plain reference of the SmallThinker decoder (PowerInfer/SmallThinker-
21BA3B-Instruct ``config.json``; the layer as ISSUE 41 writes it down):

1. ``h = RMSNorm(x; g_in)``; router logits ``r = h W_r`` — the router reads
   the INPUT norm's output, before attention.
2. ``q = h W_q``, ``k = h W_k``, ``v = h W_v``, no bias, no q/k norm. A
   WINDOW layer rotates q and k (rope, interleaved pairs); a GLOBAL layer
   carries no position embedding at all.
3. scores ``q k^T / sqrt(D)``, grouped-query; key ``j`` visible to query
   ``i`` iff ``j <= i`` and, in a window layer, ``j > i - W``; softmax in
   float32; ``x1 = x + (softmax v) W_o``.
4. ``u = RMSNorm(x1; g_post)``; the top k of ``r``; gates = softmax over the
   chosen k logits (equal to softmax over all, top k, renormalised).
5. ``y = sum_e g_e W_down,e (relu(W_gate,e u) * (W_up,e u))``;
   ``x2 = x1 + y``. No shared expert, every layer sparse.
6. After the last layer: RMSNorm, untied head.

Straightforward float32 ``jax.numpy`` under ``default_matmul_precision(
"highest")``. No sort, no tiles, no cache, no ring: a full mask per kind of
layer and every expert on every token, masked by the gates — computed a
block of query rows at a time, so that a 16k-token stream fits (the mask of
a block is ``[block, S]``; nothing else about the arithmetic changes).
Independent of the code under test: it shares no function with
``deepspeed_tpu``; a runner hands it the WEIGHTS (a seeded tree, its bf16
values held in float32) one layer at a time.

Assumed, where the published ``config.json`` is silent:

- the router reads the input norm's output (``described_as``: "router
  placed before attention");
- attention projections carry no bias;
- the window counts the query's own position: ``W`` keys are visible.

Not modelled: the "secondary experts" / "sparse ReGLU" of the model card
are PowerInfer's neuron-level activation predictor, an inference shortcut
and no part of the forward pass.

Departure, stated: rope rotates interleaved pairs ``(x[2i], x[2i+1])`` as
the program does, where the checkpoint format rotates the two halves — the
same function up to a fixed permutation of the q/k projections' columns,
which seeded random weights absorb.
"""
from __future__ import annotations

import math
from typing import Callable

import jax
import jax.numpy as jnp

#: a layer's kind: full causal attention without a position embedding, or a
#: sliding window with rope
GLOBAL, WINDOW = "global", "window"


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


def route(r, top_k: int):
    """(gates [S, n] — softmax over a token's top-k router logits, zero
    elsewhere — and the chosen experts [S, k])."""
    top, e = jax.lax.top_k(r, top_k)
    g = jax.nn.softmax(top, axis=-1)                                # float32
    rows = jnp.arange(r.shape[0])[:, None]
    return jnp.zeros_like(r).at[rows, e].set(g), e


def layer_forward(x, w: dict, positions, *, kind: str, window: int,
                  theta: float, eps: float, top_k: int, q_block: int):
    """One block on x [S, E]; returns (x', experts chosen [S, k]). ``w``:
    ln_attn [E], wq [E,H,D], wk/wv [E,KV,D], wo [H,D,E], ln_ffn [E],
    w_router [E,n], w_gate/w_up [n,E,F], w_down [n,F,E]. ``S`` is a
    multiple of ``q_block``."""
    S = x.shape[0]
    H, D = w["wq"].shape[1:]
    KV = w["wk"].shape[1]
    h = rms_norm(x, w["ln_attn"], eps)
    r = h @ w["w_router"]            # assumed: routed from the INPUT norm
    q = jnp.einsum("se,ehd->shd", h, w["wq"])    # assumed: no bias
    k = jnp.einsum("se,ehd->shd", h, w["wk"])
    v = jnp.einsum("se,ehd->shd", h, w["wv"])
    if kind == WINDOW:
        q, k = rotary(q, positions, theta), rotary(k, positions, theta)
    gates, chosen = route(r, top_k)

    def rows_block(args):
        xb, qb, pb, gb = args                    # one block of query rows
        qb = qb.reshape(q_block, KV, H // KV, D)
        s = jnp.einsum("sgrd,tgd->grst", qb, k) / math.sqrt(D)
        seen = pb[:, None] >= positions[None, :]
        if kind == WINDOW:           # assumed: the query's own position counts
            seen &= positions[None, :] > pb[:, None] - window
        p = jax.nn.softmax(jnp.where(seen[None, None], s, -jnp.inf), axis=-1)
        o = jnp.einsum("grst,tgd->sgrd", p, v).reshape(q_block, H, D)
        x1 = xb + jnp.einsum("shd,hde->se", o, w["wo"])
        u = rms_norm(x1, w["ln_ffn"], eps)
        f = jax.nn.relu(jnp.einsum("se,nef->nsf", u, w["w_gate"])) \
            * jnp.einsum("se,nef->nsf", u, w["w_up"])
        y = jnp.einsum("nsf,nfe->nse", f, w["w_down"])              # [n,S,E]
        return x1 + jnp.einsum("sn,nse->se", gb, y)

    nb = S // q_block
    blocks = lambda a: a.reshape(nb, q_block, *a.shape[1:])
    out = jax.lax.map(rows_block, (blocks(x), blocks(q), blocks(positions),
                                   blocks(gates)))
    return out.reshape(S, -1), chosen


_layer_step = jax.jit(layer_forward, static_argnames=(
    "kind", "window", "theta", "eps", "top_k", "q_block"))


def forward_logits(tokens, *, embed, layer: Callable[[int], dict],
                   kinds, window: int, ln_final, unembed, theta: float,
                   eps: float, top_k: int, rows=None, round_hidden=None,
                   routes: list | None = None, q_block: int = 512,
                   positions=None):
    """Teacher-forced logits [len(rows) or S, V] of one sequence ``tokens``
    [S]. ``kinds`` names every layer's kind (GLOBAL / WINDOW), in order;
    ``layer(i)`` returns layer ``i``'s weights (any float dtype; cast to
    float32 here, one layer at a time). ``rows`` selects positions before
    the vocabulary projection. ``routes``, if a list, receives each layer's
    chosen experts [S, k]. ``round_hidden`` (a dtype) rounds the residual
    stream to that dtype after every block — NOT the reference: the
    stand-in for a lower-precision server. ``positions`` default to
    ``0..S-1`` (a test shifts them)."""
    f32 = lambda t: jax.tree.map(lambda a: jnp.asarray(a, jnp.float32), t)
    with jax.default_matmul_precision("highest"):
        tokens = jnp.asarray(tokens, jnp.int32)
        S = tokens.shape[0]
        if S % q_block:
            q_block = S
        if positions is None:
            positions = jnp.arange(S, dtype=jnp.int32)
        positions = jnp.asarray(positions, jnp.int32)
        x = jnp.asarray(embed)[tokens].astype(jnp.float32)
        for i, kind in enumerate(kinds):
            x, chosen = _layer_step(x, f32(layer(i)), positions, kind=kind,
                                    window=int(window), theta=theta, eps=eps,
                                    top_k=top_k, q_block=q_block)
            if round_hidden is not None:
                x = x.astype(round_hidden).astype(jnp.float32)
            if routes is not None:
                routes.append(chosen)
        x = rms_norm(x, f32(ln_final), eps)
        if rows is not None:
            x = x[jnp.asarray(rows, jnp.int32)]
        return x @ f32(unembed)


def program_kinds(model_cfg) -> list[str]:
    """Adapter: every layer's kind in this file's names, from the program's
    configuration (names only)."""
    names = {"full_nope": GLOBAL, "window": WINDOW}
    return [names[model_cfg.layer_kind(i)]
            for i in range(model_cfg.num_layers)]


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
            "w_router": t["moe"]["moe_layer"]["gate"]["wg"],
            "w_gate": ex["w_gate"], "w_up": ex["w_up"],
            "w_down": ex["w_down"]}
