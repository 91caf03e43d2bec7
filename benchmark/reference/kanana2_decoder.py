"""Plain reference of the kanana-2-30b-a3b decoder (kakaocorp/
kanana-2-30b-a3b-instruct-2601 ``config.json``, ``model_type``
``deepseek_v3``; HF ``transformers`` ``DeepseekV3*``; the layers as ISSUE 54
writes them down). Hidden ``h``, no bias anywhere (``attention_bias``
false), ``q_lora_rank`` null (the query has no low-rank step):

1. Block ``i``: ``x = x + Attn(RMSNorm(x)); x = x + FF_i(RMSNorm(x))``.
   After the last block one RMSNorm, then an UNTIED head.
2. ``Attn`` (multi-head LATENT attention, the EXPANDED form): ``q = n W_q``,
   a head's columns ``q_nope | q_rope``; ``[c | k_r] = n W_dkv``
   (``kv_a_proj_with_mqa``); ``c = RMSNorm(c)`` over its own width
   (``kv_a_layernorm``); ``k_r`` is ONE rope key shared by every head;
   ``k_nope_h = c W_uk,h``, ``v_h = c W_uv,h`` (``kv_b_proj``'s two halves).
   Rope on ``q_rope_h`` and ``k_r`` only. Score ``s_h(t, j) = (q_nope_h(t) .
   k_nope_h(j) + q_rope_h(t) . k_r(j)) / sqrt(nope + rope)``, causal softmax
   in float32, ``o_h = sum_j p_h(t, j) v_h(j)``, output ``W_o [o_1 .. o_H]``.
   Per-head keys and values are built from ``c`` for the WHOLE sequence: no
   cache, no absorbed query, no kernel — the form the served program does
   NOT run (it attends over the cached ``[c | k_r]`` rows with ``W_uk``
   folded into the query and ``W_uv`` applied after the weighted sum).
3. ``FF_i`` DENSE (the leading ``first_k_dense_replace`` layers): SwiGLU,
   ``W_down (silu(W_gate n) * (W_up n))``.
4. ``FF_i`` of EXPERTS: ``s = sigmoid(n W_g)``; selection by ``s + b``
   (``e_score_correction_bias``, ``topk_method`` ``noaux_tc``): the experts
   are split into ``n_group`` groups, a group scores the sum of its two
   best ``s + b``, the best ``topk_group`` groups stay and the ``k`` experts
   are the top ``k`` of ``s + b`` inside them. This model has ``n_group`` 1
   and ``topk_group`` 1: ONE group holding every expert, always kept — the
   group step is the identity (spelled out in :func:`route`, not built as a
   second mechanism). The weights are ``s`` (NOT ``s + b``) at the chosen
   ``k``, divided by their sum + 1e-20 (``norm_topk_prob``), times
   ``routed_scaling_factor`` (2.448). The layer is the weighted sum of the
   chosen SwiGLU experts PLUS one always-on shared SwiGLU expert
   (``n_shared_experts`` x the experts' width) with NO gate.

Straightforward float32 ``jax.numpy`` under ``default_matmul_precision(
"highest")``. No sort, no tiles, no cache, no batching: attention a full
mask, every expert on every token, masked by the weights — computed a block
of query rows at a time so that a 29k-token stream fits (the mask of a block
is ``[block, S]``; nothing else about the arithmetic changes). Independent
of the code under test: it shares no function with ``deepspeed_tpu``; a
runner hands it the WEIGHTS (a seeded tree, its bf16 values held in
float32) one layer at a time.

Departures from HF ``DeepseekV3``, stated:

- the normalisation epsilon of the gate weights. HF divides by ``sum +
  1e-20``; the served program keeps LFM2's ``1e-6`` (one ``sigmoid_bias``
  routine for both models). This file uses HF's. It cannot matter: the six
  weights are sigmoids, their sum is at least six times the smallest
  (>= 1e-2 for any logit above -6), so the two quotients differ by a
  relative 1e-6 / sum < 1e-4 of a weight — two orders under the float32
  agreement the tests hold (2e-4 on LOGITS), four under bf16's rounding.
- rope rotates interleaved pairs ``(x[2i], x[2i+1])``: HF's
  ``rope_interleave`` true says the checkpoint keeps pairs in that order,
  which is the form the program rotates natively — nothing is permuted.
- ``kv_b_proj`` is held as its two halves ``W_uk`` ``[r, H, nope]`` and
  ``W_uv`` ``[r, H, v]`` (a reshape and a split of the checkpoint's matrix).
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


def route(r, bias, top_k: int, scaling: float, n_group: int = 1,
          topk_group: int = 1):
    """(weights [S, n] — sigmoid scores at a token's chosen experts over
    their sum + 1e-20, times ``scaling``, zero elsewhere — and the chosen
    experts [S, k]: the top k of score + bias among the experts of the
    ``topk_group`` best of ``n_group`` groups)."""
    s = jax.nn.sigmoid(r)
    pick = s + bias[None, :]
    S, n = pick.shape
    # group-limited selection: a group scores the sum of its two best
    # ``s + b``; with ONE group of which ONE is kept the mask is all ones
    grouped = pick.reshape(S, n_group, n // n_group)
    score = jnp.sum(jax.lax.top_k(grouped, 2)[0], axis=-1)      # [S, groups]
    _, kept = jax.lax.top_k(score, topk_group)
    keep = jnp.zeros((S, n_group), bool).at[
        jnp.arange(S)[:, None], kept].set(True)
    pick = jnp.where(jnp.repeat(keep, n // n_group, axis=1), pick, -jnp.inf)
    _, e = jax.lax.top_k(pick, top_k)
    g = jnp.take_along_axis(s, e, axis=-1)
    g = g / (jnp.sum(g, axis=-1, keepdims=True) + 1e-20) * scaling
    rows = jnp.arange(S)[:, None]
    return jnp.zeros_like(r).at[rows, e].set(g), e


def layer_forward(x, w: dict, positions, *, experts: bool, theta: float,
                  eps: float, top_k: int, scaling: float, q_block: int):
    """One block on x [S, E]; returns (x', experts chosen [S, k] or None).
    ``w``: ln_op [E], ln_ffn [E]; wq [E,H,nope+rope], w_dkv [E,r+rope],
    kv_norm [r], w_uk [r,H,nope], w_uv [r,H,v], wo [H,v,E]; dense
    w_gate/w_up [E,F], w_down [F,E]; experts w_router [E,n], b_router [n],
    w_gate/w_up [n,E,F], w_down [n,F,E] and the shared expert's
    s_gate/s_up [E,Fs], s_down [Fs,E]. ``S`` is a multiple of ``q_block``."""
    S = x.shape[0]
    n = rms_norm(x, w["ln_op"], eps)
    nb = S // q_block
    blocks = lambda a: a.reshape(nb, q_block, *a.shape[1:])
    r = w["kv_norm"].shape[0]
    dn = w["w_uk"].shape[2]
    q = jnp.einsum("se,ehd->shd", n, w["wq"])
    ckr = n @ w["w_dkv"]
    c = rms_norm(ckr[:, :r], w["kv_norm"], eps)
    k_rope = rotary(ckr[:, None, r:], positions, theta)[:, 0]      # [S, rope]
    k_nope = jnp.einsum("sr,rhd->shd", c, w["w_uk"])      # a head's own keys
    v = jnp.einsum("sr,rhd->shd", c, w["w_uv"])           # and values
    q_nope, q_rope = q[..., :dn], rotary(q[..., dn:], positions, theta)

    def attend(args):
        qn, qr, pb = args                        # one block of query rows
        s = (jnp.einsum("shd,thd->hst", qn, k_nope)
             + jnp.einsum("shd,td->hst", qr, k_rope)) \
            / math.sqrt(q.shape[-1])
        seen = pb[:, None] >= positions[None, :]
        p = jax.nn.softmax(jnp.where(seen[None], s, -jnp.inf), axis=-1)
        o = jnp.einsum("hst,thd->shd", p, v)
        return jnp.einsum("shd,hde->se", o, w["wo"])

    x1 = x + jax.lax.map(attend, (blocks(q_nope), blocks(q_rope),
                                  blocks(positions))).reshape(S, -1)
    u = rms_norm(x1, w["ln_ffn"], eps)
    if not experts:
        f = jax.nn.silu(u @ w["w_gate"]) * (u @ w["w_up"])
        return x1 + f @ w["w_down"], None
    gates, chosen = route(u @ w["w_router"], w["b_router"], top_k, scaling)

    def ffn_block(args):
        ub, gb = args
        f = jax.nn.silu(jnp.einsum("se,nef->nsf", ub, w["w_gate"])) \
            * jnp.einsum("se,nef->nsf", ub, w["w_up"])
        y = jnp.einsum("nsf,nfe->nse", f, w["w_down"])              # [n,S,E]
        return jnp.einsum("sn,nse->se", gb, y)

    y = jax.lax.map(ffn_block, (blocks(u), blocks(gates))).reshape(S, -1)
    shared = (jax.nn.silu(u @ w["s_gate"]) * (u @ w["s_up"])) @ w["s_down"]
    return x1 + y + shared, chosen


_layer_step = jax.jit(layer_forward, static_argnames=(
    "experts", "theta", "eps", "top_k", "scaling", "q_block"))


def forward_logits(tokens, *, embed, unembed, layer: Callable[[int], dict],
                   experts, ln_final, theta: float, eps: float, top_k: int,
                   scaling: float, rows=None, round_hidden=None,
                   routes: list | None = None, q_block: int = 512,
                   positions=None):
    """Teacher-forced logits [len(rows) or S, V] of one sequence ``tokens``
    [S]. ``experts`` says of every layer whether its feed-forward is routed
    experts, in order; ``layer(i)`` returns layer ``i``'s weights (any float
    dtype; cast to float32 here, one layer at a time). ``rows`` selects
    positions before the vocabulary projection. ``routes``, if a list,
    receives each expert layer's chosen experts [S, k]. ``round_hidden`` (a
    dtype) rounds the residual stream to that dtype after every block — NOT
    the reference: the stand-in for a lower-precision server. ``positions``
    default to ``0..S-1``."""
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
        for i, ex in enumerate(experts):
            x, chosen = _layer_step(x, f32(layer(i)), positions,
                                    experts=bool(ex), theta=theta, eps=eps,
                                    top_k=top_k, scaling=float(scaling),
                                    q_block=q_block)
            if round_hidden is not None:
                x = x.astype(round_hidden).astype(jnp.float32)
            if routes is not None and chosen is not None:
                routes.append(chosen)
        x = rms_norm(x, f32(ln_final), eps)
        if rows is not None:
            x = x[jnp.asarray(rows, jnp.int32)]
        return x @ f32(unembed)


def program_experts(model_cfg) -> list[bool]:
    """Adapter: whether each layer's feed-forward is routed experts, from
    the program's configuration (flags only)."""
    return [bool(e) for e in model_cfg.moe.moe_layer_pattern]


def program_layer(tree: dict, i: int) -> dict:
    """Adapter: layer ``i`` of the program's parameter tree (``layer_<i>``
    sub-trees) in this file's names. Touches names only, no arithmetic."""
    t = tree[f"layer_{i}"]
    w = {"ln_op": t["ln_attn"]["scale"], "ln_ffn": t["ln_ffn"]["scale"],
         **{k: t["attn"][k] for k in ("wq", "w_dkv", "kv_norm", "w_uk",
                                      "w_uv", "wo")}}
    if "moe" in t:
        ml = t["moe"]["moe_layer"]
        w.update({"w_router": ml["gate"]["wg"], "b_router": ml["gate"]["bias"],
                  **{k: ml["experts"][k]
                     for k in ("w_gate", "w_up", "w_down")},
                  **{f"s_{k[2:]}": t["moe"]["shared_expert"][k]
                     for k in ("w_gate", "w_up", "w_down")}})
    else:
        w.update({k: t["ffn"][k] for k in ("w_gate", "w_up", "w_down")})
    return w
