"""Operations and bytes the ALGORITHM needs, from shapes — the yardstick's
arithmetic, kept where no later PR can change it.

Conventions (the training sheet's): a multiply-add is 2 FLOPs; only matmul
parameters count (the input embedding is a gather, norms are elementwise);
causal attention is counted HALVED (a query attends to the keys at or
before it); recomputation (remat) is never counted. Shapes come from the
configuration file's published keys.
"""
from __future__ import annotations

import json
import os

_HERE = os.path.dirname(os.path.abspath(__file__))


def peaks(device_kind: str) -> dict:
    """The published peaks of one chip. An unknown device is an error, not
    a default."""
    with open(os.path.join(_HERE, "peaks.json"), encoding="utf-8") as f:
        table = json.load(f)
    if device_kind not in table or device_kind.startswith("_"):
        raise KeyError(f"no published peaks for device kind {device_kind!r} "
                       f"in benchmark/peaks.json")
    return table[device_kind]


def shapes(cfg: dict) -> dict:
    """The sizes the counts below need, from a configuration file."""
    E, H = cfg["hidden_size"], cfg["num_attention_heads"]
    D = cfg.get("head_dim") or E // H
    return {"E": E, "H": H, "KV": cfg["num_key_value_heads"], "D": D,
            "F": cfg["intermediate_size"], "L": cfg["num_hidden_layers"],
            "V": cfg["vocab_size"]}


def matmul_params(cfg: dict) -> int:
    """Parameters that multiply activations: per layer q, k, v, o and the
    three SwiGLU matrices, plus the output head. The input embedding table
    is looked up, not multiplied, and is not counted."""
    s = shapes(cfg)
    attn = s["E"] * s["H"] * s["D"] * 2 + s["E"] * s["KV"] * s["D"] * 2
    return s["L"] * (attn + 3 * s["E"] * s["F"]) + s["E"] * s["V"]


def attn_flops(cfg: dict, q_tokens: int, ctx_before: int) -> float:
    """FLOPs of causal attention (QK^T and PV) for ``q_tokens`` new
    positions that follow ``ctx_before`` cached ones, all layers: query i
    attends to ctx_before + i + 1 keys — the causal half, not the square."""
    s = shapes(cfg)
    pairs = q_tokens * ctx_before + q_tokens * (q_tokens + 1) / 2
    return s["L"] * 4.0 * s["H"] * s["D"] * pairs


def train_flops_per_token(cfg: dict, seq: int) -> float:
    """Forward plus backward of one token in a ``seq``-token causal row:
    6 x matmul parameters, plus 3 x the forward attention FLOPs a token
    (backward is twice forward). Recomputation not counted."""
    return 6.0 * matmul_params(cfg) + 3.0 * attn_flops(cfg, seq, 0) / seq


def prefill_chunk(cfg: dict, q_tokens: int, ctx_before: int,
                  sampled_rows: int = 1, kv_bytes: int = 2,
                  w_bytes: int = 2) -> dict:
    """One prefill chunk of one sequence: ``q_tokens`` prompt tokens after
    ``ctx_before`` cached ones. The head runs on ``sampled_rows`` rows (the
    engine samples only where a prompt ends). Bytes: every weight once, the
    cached K/V read once, the fresh K/V written once."""
    s = shapes(cfg)
    per_tok = 2.0 * (matmul_params(cfg) - s["E"] * s["V"])
    flops = per_tok * q_tokens + 2.0 * s["E"] * s["V"] * sampled_rows \
        + attn_flops(cfg, q_tokens, ctx_before)
    kv_tok = 2 * s["L"] * s["KV"] * s["D"] * kv_bytes
    byts = matmul_params(cfg) * w_bytes + kv_tok * (ctx_before + q_tokens)
    return {"flops": flops, "bytes": float(byts)}


def decode_step(cfg: dict, contexts, kv_bytes: int = 2,
                w_bytes: int = 2) -> dict:
    """One decode iteration over a batch whose sequences hold ``contexts``
    tokens each (the new token included): every weight read once, every
    sequence's K/V read once."""
    s = shapes(cfg)
    n = len(contexts)
    flops = 2.0 * matmul_params(cfg) * n \
        + sum(attn_flops(cfg, 1, c - 1) for c in contexts)
    kv_tok = 2 * s["L"] * s["KV"] * s["D"] * kv_bytes
    return {"flops": flops,
            "bytes": float(matmul_params(cfg) * w_bytes + kv_tok * sum(contexts))}


def paged_attention(cfg: dict, q_tokens: int, ctx_before: int,
                    kv_bytes: int = 2) -> dict:
    """The paged attention kernel alone, all layers, one sequence: the
    causal FLOPs above; bytes = the K/V pages of the context read once plus
    q in and o out (bf16)."""
    s = shapes(cfg)
    kv_tok = 2 * s["L"] * s["KV"] * s["D"] * kv_bytes
    qo = 2 * s["L"] * s["H"] * s["D"] * 2 * q_tokens
    return {"flops": attn_flops(cfg, q_tokens, ctx_before),
            "bytes": float(kv_tok * (ctx_before + q_tokens) + qo)}


def least_time_s(work: dict, peak: dict) -> tuple[float, str]:
    """Roofline: the larger of FLOPs over peak FLOP/s and bytes over peak
    bytes/s, and which of the two bounds."""
    tc = work["flops"] / peak["bf16_flops"]
    tm = work["bytes"] / peak["hbm_bytes_per_s"]
    return (tc, "compute") if tc >= tm else (tm, "memory")
