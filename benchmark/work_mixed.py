"""Operations and bytes the ALGORITHM needs for a decoder whose layers are
UNLIKE — the sequence mixed by attention in some layers and by a gated short
convolution (no keys and values) in the others; the feed-forward dense in
the leading layers and routed experts in the rest (LFM2-MoE) — from shapes,
in ``work.py``'s conventions (a multiply-add is 2 FLOPs; only matmul
parameters count; a query attends to the keys it can see; bf16). ``work.py``
and ``work_moe.py`` multiply one layer's work by ``num_hidden_layers``: here
experts are counted for the layers that HAVE experts and K/V for the layers
that HAVE attention. Shapes come from the configuration file's published
keys: ``layer_types`` (one entry a PUBLISHED layer; the stack is the
``num_hidden_layers`` of them from ``first_layer`` on), ``num_dense_layers``
(the stack's leading layers with the dense feed-forward of
``intermediate_size``), ``moe_intermediate_size`` (ONE expert's width),
``num_experts``, ``num_experts_per_tok``, ``conv_L_cache``.
"""
from __future__ import annotations

from benchmark import work

ATTENTION, CONV = "full_attention", "conv"


def layer_types(cfg: dict) -> list[str]:
    """The operator of every layer of the stack the configuration runs."""
    first = int(cfg.get("first_layer", 0))
    types = cfg["layer_types"][first:first + cfg["num_hidden_layers"]]
    if len(types) != cfg["num_hidden_layers"] \
            or set(types) - {ATTENTION, CONV}:
        raise ValueError(f"layer_types gives {types!r} for "
                         f"{cfg['num_hidden_layers']} layers from {first}")
    return types


def layers(cfg: dict) -> dict:
    """How many of the stack's layers have each mechanism."""
    types = layer_types(cfg)
    dense = min(int(cfg["num_dense_layers"]), len(types))
    return {"attention": types.count(ATTENTION), "conv": types.count(CONV),
            "dense": dense, "experts": len(types) - dense}


def shapes(cfg: dict) -> dict:
    E, H = cfg["hidden_size"], cfg["num_attention_heads"]
    return {"E": E, "H": H, "KV": cfg["num_key_value_heads"],
            "D": cfg.get("head_dim") or E // H, "V": cfg["vocab_size"],
            "F_dense": cfg["intermediate_size"],
            "F_expert": cfg["moe_intermediate_size"],
            "n": cfg["num_experts"], "k": cfg["num_experts_per_tok"],
            "taps": cfg["conv_L_cache"]}


def attn_params(cfg: dict) -> int:
    """q, k, v, o of one attention layer."""
    s = shapes(cfg)
    return s["E"] * s["H"] * s["D"] * 2 + s["E"] * s["KV"] * s["D"] * 2


def conv_params(cfg: dict) -> int:
    """One conv layer's two projections (E -> 3E, E -> E); the taps are
    elementwise and not counted."""
    s = shapes(cfg)
    return 4 * s["E"] * s["E"]


def expert_params(cfg: dict) -> int:
    """The three SwiGLU matrices of ONE expert."""
    s = shapes(cfg)
    return 3 * s["E"] * s["F_expert"]


def dense_params(cfg: dict) -> int:
    s = shapes(cfg)
    return 3 * s["E"] * s["F_dense"]


def matmul_params(cfg: dict, experts_a_layer: float) -> float:
    """Every parameter that multiplies an activation, with
    ``experts_a_layer`` experts counted in each expert layer (``n``: at
    rest; ``k``: what one token's FLOPs follow), plus the head."""
    s, n = shapes(cfg), layers(cfg)
    return (n["attention"] * attn_params(cfg) + n["conv"] * conv_params(cfg)
            + n["dense"] * dense_params(cfg)
            + n["experts"] * (s["E"] * s["n"]
                              + experts_a_layer * expert_params(cfg))
            + s["E"] * s["V"])


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


def kv_bytes_token(cfg: dict, kv_bytes: int = 2) -> int:
    """K and V of one token in ONE attention layer."""
    s = shapes(cfg)
    return 2 * s["KV"] * s["D"] * kv_bytes


def attn_decode_span(cfg: dict, ctx_lo: int, ctx_hi: int) -> dict:
    """Attention of the decode steps that take ONE sequence from ``ctx_lo``
    to ``ctx_hi`` tokens of context, a token a step, in the layers that
    HAVE attention: step j sees (and reads the K/V of) ``ctx_lo + j + 1``
    keys."""
    n = ctx_hi - ctx_lo
    if n <= 0:
        return {"flops": 0.0, "bytes": 0.0}
    s, L = shapes(cfg), layers(cfg)["attention"]
    pairs = n * ctx_lo + n * (n + 1) / 2
    return {"flops": float(L * 4.0 * s["H"] * s["D"] * pairs),
            "bytes": float(L * kv_bytes_token(cfg) * pairs)}


def record_bytes(cfg: dict, a_bytes: int = 2) -> int:
    """What ONE sequence keeps of the past for all conv layers: ``taps - 1``
    rows of ``E`` a layer, whatever the context."""
    s = shapes(cfg)
    return layers(cfg)["conv"] * (s["taps"] - 1) * s["E"] * a_bytes


def decode_step(cfg: dict, contexts, experts_touched: float | None = None,
                w_bytes: int = 2) -> dict:
    """One decode iteration over a batch whose sequences hold ``contexts``
    tokens each (the new token included): every operator's, router's,
    dense feed-forward's and the head's weights read once, the touched
    experts' weights once an expert layer, every sequence's K/V once an
    attention layer, every sequence's record read and written once."""
    s, L = shapes(cfg), layers(cfg)
    b = len(contexts)
    if experts_touched is None:
        experts_touched = experts_touched_uniform(cfg, b)
    flops = 2.0 * matmul_params(cfg, s["k"]) * b \
        + sum(attn_decode_span(cfg, c - 1, c)["flops"] for c in contexts)
    byts = matmul_params(cfg, experts_touched) * w_bytes \
        + L["attention"] * kv_bytes_token(cfg) * sum(contexts) \
        + 2 * record_bytes(cfg) * b
    return {"flops": float(flops), "bytes": float(byts)}


def least_time_s(w: dict, peak: dict) -> tuple[float, str]:
    return work.least_time_s(w, peak)
