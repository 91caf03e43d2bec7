"""Operations and bytes the ALGORITHM needs for a decoder of LATENT
attention (MLA, ``deepseek_v3`` with ``q_lora_rank`` null: kanana-2) — every
layer attends; what a layer keeps of a token is ONE row ``[c | k_r]`` of
``kv_lora_rank + qk_rope_head_dim`` values (576: 1,152 B in bf16), read once
for scores and values and for every head; the leading
``first_k_dense_replace`` layers carry a dense SwiGLU, the rest
``n_routed_experts`` routed SwiGLU experts (``num_experts_per_tok`` a token)
beside ONE always-on shared expert of ``n_shared_experts`` x their width —
from shapes, in ``work.py``'s conventions (a multiply-add is 2 FLOPs; only
matmul parameters count; a query attends to the keys it can see; bf16).
``work.py`` / ``work_moe.py`` / ``work_mixed.py`` read other keys
(``num_experts``, ``layer_types``, K/V heads) and would miscount this file:
experts here are counted for the layers that HAVE experts, and the cache by
the latent row, whatever lanes the program pads it to and whatever
implements the kernel. Shapes come from the configuration file's published
keys."""
from __future__ import annotations

from benchmark import work


def shapes(cfg: dict) -> dict:
    return {"E": cfg["hidden_size"], "H": cfg["num_attention_heads"],
            "R": cfg["kv_lora_rank"], "nope": cfg["qk_nope_head_dim"],
            "rope": cfg["qk_rope_head_dim"], "v": cfg["v_head_dim"],
            "V": cfg["vocab_size"], "F_dense": cfg["intermediate_size"],
            "F_expert": cfg["moe_intermediate_size"],
            "n": cfg["n_routed_experts"], "k": cfg["num_experts_per_tok"],
            "shared": cfg["n_shared_experts"]}


def layers(cfg: dict) -> dict:
    """How many of the stack's layers have each feed-forward (every one
    attends)."""
    L = cfg["num_hidden_layers"]
    dense = min(int(cfg["first_k_dense_replace"]), L)
    return {"attention": L, "dense": dense, "experts": L - dense}


def attn_params(cfg: dict) -> int:
    """``W_q``, ``W_dkv``, ``W_ukv`` and ``W_o`` of one layer."""
    s = shapes(cfg)
    return (s["E"] * s["H"] * (s["nope"] + s["rope"])
            + s["E"] * (s["R"] + s["rope"])
            + s["R"] * s["H"] * (s["nope"] + s["v"])
            + s["H"] * s["v"] * s["E"])


def expert_params(cfg: dict) -> int:
    """The three SwiGLU matrices of ONE routed expert."""
    s = shapes(cfg)
    return 3 * s["E"] * s["F_expert"]


def shared_params(cfg: dict) -> int:
    """The ONE always-on shared expert: ``n_shared_experts`` x the width."""
    return shapes(cfg)["shared"] * expert_params(cfg)


def dense_params(cfg: dict) -> int:
    s = shapes(cfg)
    return 3 * s["E"] * s["F_dense"]


def expert_layer_params(cfg: dict, experts: float) -> float:
    """One expert layer with ``experts`` routed experts counted: attention,
    router, shared expert and those experts."""
    s = shapes(cfg)
    return attn_params(cfg) + s["E"] * s["n"] + shared_params(cfg) \
        + experts * expert_params(cfg)


def matmul_params(cfg: dict, experts_a_layer: float) -> float:
    """Every parameter that multiplies an activation, with
    ``experts_a_layer`` routed experts counted in each expert layer (``n``:
    at rest; ``k``: what one token's FLOPs follow), plus the untied head."""
    s, n = shapes(cfg), layers(cfg)
    return (n["dense"] * (attn_params(cfg) + dense_params(cfg))
            + n["experts"] * expert_layer_params(cfg, experts_a_layer)
            + s["E"] * s["V"])


def latent_bytes_token(cfg: dict, a_bytes: int = 2) -> int:
    """What ONE layer needs of ONE past token: the latent and the shared
    rope key (1,152 B in bf16), as needed — not as stored."""
    s = shapes(cfg)
    return (s["R"] + s["rope"]) * a_bytes


def latent_flops_pair(cfg: dict) -> float:
    """One (query token, key) pair of ONE layer in the absorbed form, all
    heads: the score over ``R + rope`` and the weighted sum over ``R``."""
    s = shapes(cfg)
    return 2.0 * s["H"] * (s["R"] + s["rope"] + s["R"])


def latent_decode_span(cfg: dict, ctx_lo: int, ctx_hi: int) -> dict:
    """The latent kernel's work for the decode steps that take ONE sequence
    from ``ctx_lo`` to ``ctx_hi`` tokens of context, a token a step, in
    every layer: step j sees (and reads the row of) ``ctx_lo + j + 1``
    tokens."""
    n = ctx_hi - ctx_lo
    if n <= 0:
        return {"flops": 0.0, "bytes": 0.0}
    L = layers(cfg)["attention"]
    pairs = n * ctx_lo + n * (n + 1) / 2
    return {"flops": float(L * latent_flops_pair(cfg) * pairs),
            "bytes": float(L * latent_bytes_token(cfg) * pairs)}


def grouped_matmul(cfg: dict, routed_rows: float, experts_touched: float,
                   w_bytes: int = 2, a_bytes: int = 2) -> dict:
    """The three grouped GEMMs of ONE expert layer over ``routed_rows``
    (token, expert) rows that reach ``experts_touched`` distinct experts:
    FLOPs of the routed rows alone (padding is not work); bytes = the
    touched experts' weights read once, and each routed row in and out of
    each GEMM."""
    s = shapes(cfg)
    rows_io = routed_rows * 3 * (s["E"] + s["F_expert"])
    return {"flops": 2.0 * expert_params(cfg) * routed_rows,
            "bytes": float(experts_touched * expert_params(cfg) * w_bytes
                           + rows_io * a_bytes)}


def experts_touched_uniform(cfg: dict, tokens: float) -> float:
    """Expected number of distinct experts ``tokens`` tokens reach when each
    picks its ``k`` uniformly at random (seeded random weights; a trained
    router is skewed and reaches FEWER: an upper estimate)."""
    s = shapes(cfg)
    return s["n"] * (1.0 - (1.0 - s["k"] / s["n"]) ** tokens)


def decode_step(cfg: dict, contexts, experts_touched: float | None = None,
                w_bytes: int = 2) -> dict:
    """One decode iteration over a batch whose sequences hold ``contexts``
    tokens each (the new token included): every attention's, router's,
    shared expert's, dense feed-forward's and the head's weights read once,
    the touched experts' weights once an expert layer, every sequence's
    latent rows once a layer."""
    s = shapes(cfg)
    b = len(contexts)
    if experts_touched is None:
        experts_touched = experts_touched_uniform(cfg, b)
    flops = 2.0 * matmul_params(cfg, s["k"]) * b \
        + sum(latent_decode_span(cfg, c - 1, c)["flops"] for c in contexts)
    byts = matmul_params(cfg, experts_touched) * w_bytes \
        + layers(cfg)["attention"] * latent_bytes_token(cfg) * sum(contexts)
    return {"flops": float(flops), "bytes": float(byts)}


def least_time_s(w: dict, peak: dict) -> tuple[float, str]:
    return work.least_time_s(w, peak)
