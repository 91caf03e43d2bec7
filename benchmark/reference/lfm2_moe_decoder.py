"""Plain reference of the LFM2-MoE decoder (LiquidAI/LFM2-24B-A2B
``config.json``, ``model_type`` ``lfm2_moe``; HF ``transformers``
``Lfm2Moe*``; the layers as ISSUE 50 writes them down). Hidden ``h``, no
bias anywhere (``conv_bias`` false):

1. Block ``i``: ``x = x + Op_i(RMSNorm(x)); x = x + FF_i(RMSNorm(x))``.
   After the last block one RMSNorm (``embedding_norm``), then the head,
   tied to the embedding.
2. ``Op_i`` of a CONV layer: ``[B, C, u] = split3(n W_in)`` (``h -> 3h``);
   ``z_t = B_t * u_t``; ``c_t = sum_{j=0..L-1} w[j] * z_{t-(L-1)+j}`` —
   a depthwise causal convolution of ``L`` (``conv_L_cache``, 3) taps a
   channel, ``z`` before the sequence is 0 —; ``out_t = (C_t * c_t) W_out``.
3. ``Op_i`` of an ATTENTION layer: grouped-query attention, ``q`` and ``k``
   RMS-normalised PER HEAD over the head's width (one learned scale of
   ``D`` each, shared by the heads) before rope on the whole head; full
   causal mask; softmax in float32; output projection.
4. ``FF_i`` DENSE: SwiGLU, ``W_down (silu(W_gate n) * (W_up n))``.
5. ``FF_i`` of EXPERTS: ``s = sigmoid(n W_g)``; the experts are the top k of
   ``s + b`` (``use_expert_bias``: ``b`` moves the SELECTION only); the
   weights are ``s`` at the chosen k, divided by their sum + 1e-6
   (``norm_topk_prob``), times ``routed_scaling_factor`` (1); the layer is
   the weighted sum of the chosen SwiGLU experts. No shared expert.

Straightforward float32 ``jax.numpy`` under ``default_matmul_precision(
"highest")``. No sort, no tiles, no cache, no record, no batching: the
convolution is three shifted copies of the whole sequence, attention a full
mask, and every expert runs on every token, masked by the weights —
computed a block of query rows at a time, so that a 16k-token stream fits
(the mask of a block is ``[block, S]``; nothing else about the arithmetic
changes). Independent of the code under test: it shares no function with
``deepspeed_tpu``; a runner hands it the WEIGHTS (a seeded tree, its bf16
values held in float32) one layer at a time.

Assumed, where the catalogued ``config.json`` is silent: the head is tied to
the embedding (``tie_word_embeddings`` is not among its keys; the family
ties it).

Departure from HF ``Lfm2Moe``, stated: rope rotates interleaved pairs
``(x[2i], x[2i+1])`` as the program does, where the checkpoint format
rotates the two halves — the same function up to a fixed permutation of the
q/k projections' columns (and of the per-head norm scales), which
``models/hf.py`` applies to a checkpoint and seeded random weights absorb.
"""
from __future__ import annotations

import math
from typing import Callable

import jax
import jax.numpy as jnp

#: a layer's operator
CONV, ATTENTION = "conv", "attention"


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


def route(r, bias, top_k: int):
    """(weights [S, n] — sigmoid scores at a token's chosen experts over
    their sum + 1e-6, zero elsewhere — and the chosen experts [S, k]: the
    top k of score + bias)."""
    s = jax.nn.sigmoid(r)
    _, e = jax.lax.top_k(s + bias[None, :], top_k)
    g = jnp.take_along_axis(s, e, axis=-1)
    g = g / (jnp.sum(g, axis=-1, keepdims=True) + 1e-6)
    rows = jnp.arange(r.shape[0])[:, None]
    return jnp.zeros_like(r).at[rows, e].set(g), e


def short_conv(n, w: dict):
    """The gated short convolution on n [S, E] (the whole sequence, from
    zeros): w_in [E, 3, E] (B, C, u), w_conv [L, E], w_out [E, E]."""
    S = n.shape[0]
    bcu = jnp.einsum("se,ekf->skf", n, w["w_in"])
    z = bcu[:, 0] * bcu[:, 2]
    L = w["w_conv"].shape[0]
    zp = jnp.concatenate([jnp.zeros((L - 1, z.shape[1]), z.dtype), z])
    c = sum(w["w_conv"][j][None, :] * zp[j:j + S] for j in range(L))
    return (bcu[:, 1] * c) @ w["w_out"]


def layer_forward(x, w: dict, positions, *, op: str, experts: bool,
                  theta: float, eps: float, top_k: int, q_block: int):
    """One block on x [S, E]; returns (x', experts chosen [S, k] or None).
    ``w``: ln_op [E], ln_ffn [E]; a conv layer w_in / w_conv / w_out; an
    attention layer wq [E,H,D], wk/wv [E,KV,D], wo [H,D,E], q_norm /
    k_norm [D]; dense w_gate/w_up [E,F], w_down [F,E]; experts w_router
    [E,n], b_router [n], w_gate/w_up [n,E,F], w_down [n,F,E]. ``S`` is a
    multiple of ``q_block``."""
    S = x.shape[0]
    n = rms_norm(x, w["ln_op"], eps)
    nb = S // q_block
    blocks = lambda a: a.reshape(nb, q_block, *a.shape[1:])
    if op == CONV:
        x1 = x + short_conv(n, w)
    else:
        H, D = w["wq"].shape[1:]
        KV = w["wk"].shape[1]
        q = jnp.einsum("se,ehd->shd", n, w["wq"])
        k = jnp.einsum("se,ehd->shd", n, w["wk"])
        v = jnp.einsum("se,ehd->shd", n, w["wv"])
        q = rotary(rms_norm(q, w["q_norm"], eps), positions, theta)
        k = rotary(rms_norm(k, w["k_norm"], eps), positions, theta)

        def attend(args):
            qb, pb = args                        # one block of query rows
            qb = qb.reshape(q_block, KV, H // KV, D)
            s = jnp.einsum("sgrd,tgd->grst", qb, k) / math.sqrt(D)
            seen = pb[:, None] >= positions[None, :]
            p = jax.nn.softmax(jnp.where(seen[None, None], s, -jnp.inf),
                               axis=-1)
            o = jnp.einsum("grst,tgd->sgrd", p, v).reshape(q_block, H, D)
            return jnp.einsum("shd,hde->se", o, w["wo"])

        x1 = x + jax.lax.map(attend, (blocks(q), blocks(positions))
                             ).reshape(S, -1)
    u = rms_norm(x1, w["ln_ffn"], eps)
    if not experts:
        f = jax.nn.silu(u @ w["w_gate"]) * (u @ w["w_up"])
        return x1 + f @ w["w_down"], None
    gates, chosen = route(u @ w["w_router"], w["b_router"], top_k)

    def ffn_block(args):
        ub, gb = args
        f = jax.nn.silu(jnp.einsum("se,nef->nsf", ub, w["w_gate"])) \
            * jnp.einsum("se,nef->nsf", ub, w["w_up"])
        y = jnp.einsum("nsf,nfe->nse", f, w["w_down"])              # [n,S,E]
        return jnp.einsum("sn,nse->se", gb, y)

    y = jax.lax.map(ffn_block, (blocks(u), blocks(gates))).reshape(S, -1)
    return x1 + y, chosen


_layer_step = jax.jit(layer_forward, static_argnames=(
    "op", "experts", "theta", "eps", "top_k", "q_block"))


def forward_logits(tokens, *, embed, layer: Callable[[int], dict],
                   ops, experts, ln_final, theta: float, eps: float,
                   top_k: int, rows=None, round_hidden=None,
                   routes: list | None = None, q_block: int = 512,
                   positions=None):
    """Teacher-forced logits [len(rows) or S, V] of one sequence ``tokens``
    [S]. ``ops`` names every layer's operator (CONV / ATTENTION) and
    ``experts`` says whether its feed-forward is routed experts, in order;
    ``layer(i)`` returns layer ``i``'s weights (any float dtype; cast to
    float32 here, one layer at a time). The head is the embedding. ``rows``
    selects positions before the vocabulary projection. ``routes``, if a
    list, receives each expert layer's chosen experts [S, k].
    ``round_hidden`` (a dtype) rounds the residual stream to that dtype
    after every block — NOT the reference: the stand-in for a
    lower-precision server. ``positions`` default to ``0..S-1``."""
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
        for i, (op, ex) in enumerate(zip(ops, experts)):
            x, chosen = _layer_step(x, f32(layer(i)), positions, op=op,
                                    experts=bool(ex), theta=theta, eps=eps,
                                    top_k=top_k, q_block=q_block)
            if round_hidden is not None:
                x = x.astype(round_hidden).astype(jnp.float32)
            if routes is not None and chosen is not None:
                routes.append(chosen)
        x = rms_norm(x, f32(ln_final), eps)
        if rows is not None:
            x = x[jnp.asarray(rows, jnp.int32)]
        return x @ f32(embed).T


def program_ops(model_cfg) -> tuple[list[str], list[bool]]:
    """Adapter: every layer's operator in this file's names and whether its
    feed-forward is routed experts, from the program's configuration
    (names and flags only)."""
    return ([CONV if model_cfg.layer_kind(i) == "conv" else ATTENTION
             for i in range(model_cfg.num_layers)],
            [bool(e) for e in model_cfg.moe.moe_layer_pattern])


def program_layer(tree: dict, i: int) -> dict:
    """Adapter: layer ``i`` of the program's parameter tree (``layer_<i>``
    sub-trees) in this file's names. Touches names only, no arithmetic."""
    t = tree[f"layer_{i}"]
    w = {"ln_op": t["ln_attn"]["scale"], "ln_ffn": t["ln_ffn"]["scale"]}
    if "conv" in t:
        w.update({k: t["conv"][k] for k in ("w_in", "w_conv", "w_out")})
    else:
        w.update({k: t["attn"][k] for k in ("wq", "wk", "wv", "wo",
                                            "q_norm", "k_norm")})
    if "moe" in t:
        ml = t["moe"]["moe_layer"]
        w.update({"w_router": ml["gate"]["wg"], "b_router": ml["gate"]["bias"],
                  **{k: ml["experts"][k]
                     for k in ("w_gate", "w_up", "w_down")}})
    else:
        w.update({k: t["ffn"][k] for k in ("w_gate", "w_up", "w_down")})
    return w
