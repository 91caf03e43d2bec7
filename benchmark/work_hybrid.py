"""Operations and bytes the ALGORITHM needs for paged attention in a decoder
whose layers are of two KINDS — full causal attention, and a sliding window
of ``sliding_window_size`` keys (the query's own position counts) — from
shapes, in ``work.py``'s conventions (a multiply-add is 2 FLOPs; a query
attends to the keys it can see, not to the square; bf16 K/V). ``work.py``
counts every layer over the WHOLE context, which over-counts a window layer
past its window. Shapes come from the configuration file's published keys:
``sliding_window_layout`` (one entry a layer: 1 = window), of which the
first ``num_hidden_layers`` apply, and ``sliding_window_size``.
"""
from __future__ import annotations

from benchmark import work

KINDS = ("global", "window")


def layers_by_kind(cfg: dict) -> dict:
    """How many of the configuration's layers are of each kind."""
    layout = cfg["sliding_window_layout"][:cfg["num_hidden_layers"]]
    return {"global": sum(1 for w in layout if not w),
            "window": sum(1 for w in layout if w)}


def visible_keys(cfg: dict, kind: str, q_tokens: int, ctx_before: int) -> float:
    """Query-key pairs of ONE layer of ``kind`` for ``q_tokens`` new
    positions that follow ``ctx_before`` cached ones: query i (0-based
    among the new ones) sees ``ctx_before + i + 1`` keys, a window layer at
    most ``sliding_window_size`` of them."""
    a, n = ctx_before, q_tokens
    whole = n * a + n * (n + 1) / 2
    if kind == "global":
        return float(whole)
    W = cfg["sliding_window_size"]
    # queries whose context (a + i + 1) is within the window see it all
    m = min(max(W - a, 0), n)
    return float(m * a + m * (m + 1) / 2 + (n - m) * W)


def attn_flops(cfg: dict, q_tokens: int, ctx_before: int) -> dict:
    """FLOPs of attention (QK^T and PV) by kind, all layers of that kind."""
    s = work.shapes(cfg)
    return {k: L * 4.0 * s["H"] * s["D"] * visible_keys(cfg, k, q_tokens,
                                                         ctx_before)
            for k, L in layers_by_kind(cfg).items()}


def kv_bytes_read(cfg: dict, context: float, kv_bytes: int = 2) -> dict:
    """K/V bytes ONE decode step (or one chunk) reads of a sequence that
    holds ``context`` tokens, by kind, all layers of that kind: the whole
    context in a global layer, ``min(context, window)`` in a window
    layer."""
    s = work.shapes(cfg)
    tok = 2 * s["KV"] * s["D"] * kv_bytes           # one layer, K and V
    W = cfg["sliding_window_size"]
    seen = {"global": context, "window": min(context, W)}
    return {k: float(L * tok * seen[k])
            for k, L in layers_by_kind(cfg).items()}


def decode_span(cfg: dict, ctx_lo: int, ctx_hi: int) -> dict:
    """Attention of the decode steps that take ONE sequence from ``ctx_lo``
    to ``ctx_hi`` tokens of context, a token a step: FLOPs and K/V bytes,
    both kinds together. The bytes sum ``kv_bytes_read`` over the steps'
    contexts in closed form (step j reads a context of ``ctx_lo + j + 1``)."""
    n = ctx_hi - ctx_lo
    if n <= 0:
        return {"flops": 0.0, "bytes": 0.0}
    flops = sum(attn_flops(cfg, n, ctx_lo).values())
    s = work.shapes(cfg)
    tok = 2 * s["KV"] * s["D"] * 2
    by_kind = layers_by_kind(cfg)
    # the keys a step READS are the keys its query sees
    byts = sum(L * tok * visible_keys(cfg, k, n, ctx_lo)
               for k, L in by_kind.items())
    return {"flops": float(flops), "bytes": float(byts)}
