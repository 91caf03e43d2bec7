"""Scheduler and engine host loop: of the decode tokens dispatched in the traced window, the share that left a prefill step's decode block (``fused_decode_tokens`` / ``decode_tokens``, the engine's counters: a block's tokens booked on the host at dispatch, a window's at its commit): how much of the decoding the prefill steps carry, at the price of the block's attention and not of a decode iteration. Those tokens are in no ``decode_steps`` / ``window_iters``: the readers that divide the client's ``tokens_emitted`` by decode iterations read high by about this share. New in PR 52: where a program lacks the counters, the reader finds nothing and returns None."""
from benchmark.layers import _shared


def read(ctx):
    s = ctx["stats"]
    if "fused_decode_tokens" not in s or not s.get("decode_tokens"):
        return None
    return _shared.pct(s["fused_decode_tokens"], s["decode_tokens"])
