"""Operations and bytes the ALGORITHM needs for a decoder whose feed-forward
layers are routed experts, from shapes — ``work.py``'s conventions (a
multiply-add is 2 FLOPs; only matmul parameters count; bf16 weights), for
the configurations ``work.py``'s dense FFN count does not fit. Shapes come
from the configuration file's published keys: ``num_experts``,
``num_experts_per_tok``, and ``intermediate_size`` read as ONE expert's
width (OLMoE).
"""
from __future__ import annotations

from benchmark import work


def shapes(cfg: dict) -> dict:
    return {**work.shapes(cfg), "n": cfg["num_experts"],
            "k": cfg["num_experts_per_tok"]}


def attn_params(cfg: dict) -> int:
    """q, k, v, o of one layer."""
    s = shapes(cfg)
    return s["E"] * s["H"] * s["D"] * 2 + s["E"] * s["KV"] * s["D"] * 2


def expert_params(cfg: dict) -> int:
    """The three SwiGLU matrices of ONE expert."""
    s = shapes(cfg)
    return 3 * s["E"] * s["F"]


def matmul_params_total(cfg: dict) -> int:
    """Every parameter that multiplies an activation: per layer q, k, v, o,
    the router and ALL experts, plus the output head (the input embedding
    is a gather)."""
    s = shapes(cfg)
    return s["L"] * (attn_params(cfg) + s["E"] * s["n"]
                     + s["n"] * expert_params(cfg)) + s["E"] * s["V"]


def matmul_params_active(cfg: dict) -> int:
    """The same, counting the ``k`` experts one token reaches: what a
    token's FLOPs follow."""
    s = shapes(cfg)
    return s["L"] * (attn_params(cfg) + s["E"] * s["n"]
                     + s["k"] * expert_params(cfg)) + s["E"] * s["V"]


def grouped_matmul(cfg: dict, routed_rows: float, experts_touched: float,
                   w_bytes: int = 2, a_bytes: int = 2) -> dict:
    """The three grouped GEMMs of ONE routed-expert layer over
    ``routed_rows`` (token, expert) rows that reach ``experts_touched``
    distinct experts: FLOPs of the routed rows alone (padding is not
    work); bytes = the touched experts' weights read once, and each routed
    row in and out of each GEMM (gate and up: E in, F out; down: F in, E
    out)."""
    s = shapes(cfg)
    flops = 2.0 * expert_params(cfg) * routed_rows
    rows_io = routed_rows * 3 * (s["E"] + s["F"])
    return {"flops": flops,
            "bytes": float(experts_touched * expert_params(cfg) * w_bytes
                           + rows_io * a_bytes)}


def experts_touched_uniform(cfg: dict, tokens: float) -> float:
    """Expected number of distinct experts ``tokens`` tokens reach when
    each picks its ``k`` experts uniformly at random (seeded random
    weights; a trained router is skewed and reaches FEWER, so this is an
    upper estimate of a real deployment's)."""
    s = shapes(cfg)
    return s["n"] * (1.0 - (1.0 - s["k"] / s["n"]) ** tokens)


def decode_step(cfg: dict, contexts, experts_touched: float | None = None,
                kv_bytes: int = 2, w_bytes: int = 2) -> dict:
    """One decode iteration over a batch whose sequences hold ``contexts``
    tokens each (the new token included): attention, router and head
    weights read once, the touched experts' weights read once a layer,
    every sequence's K/V read once."""
    s = shapes(cfg)
    n = len(contexts)
    if experts_touched is None:
        experts_touched = experts_touched_uniform(cfg, n)
    flops = 2.0 * matmul_params_active(cfg) * n \
        + sum(work.attn_flops(cfg, 1, c - 1) for c in contexts)
    weights = s["L"] * (attn_params(cfg) + s["E"] * s["n"]
                        + experts_touched * expert_params(cfg)) \
        + s["E"] * s["V"]
    kv_tok = 2 * s["L"] * s["KV"] * s["D"] * kv_bytes
    return {"flops": flops,
            "bytes": float(weights * w_bytes + kv_tok * sum(contexts))}
