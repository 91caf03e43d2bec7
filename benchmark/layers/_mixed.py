"""Arithmetic shared by the readers of a stack of UNLIKE layers (attention
in some, a gated short convolution in the others; a dense feed-forward in
the leading ones, routed experts in the rest: ``work_mixed.py``).
``_moe.grouped_roofline`` and ``_shared.paged_roofline`` count every layer
of ``num_hidden_layers``; here the grouped GEMM is held to the layers that
HAVE experts and the paged kernel to the layers that HAVE attention — in
the sanity check of the calls and in the work both — so neither can read
over 100 % by counting a layer that is not there. Everything the program
must publish for them beyond PRs 25/26 is new in PR 50 — the ``conv_mix``
scope and the ``conv_chunks*`` counters: where a program lacks one (a
parent commit), the reader finds nothing and returns None."""
from __future__ import annotations

from benchmark import work_mixed
from benchmark.common import say
from benchmark.layers import _moe, _scopes, _shared

CONV_SCOPES = ("conv_mix",)


def conv_share(ctx):
    """The conv operator (its two projections, the gates and the taps:
    scope ``conv_mix``) over the decode programs' device self time."""
    tab = _scopes.table(ctx)
    if tab is None or not any(scope in CONV_SCOPES for p in tab.values()
                              for scope, _ in p):
        return None
    return _scopes.share(ctx, _scopes.DECODE_PROGRAMS, CONV_SCOPES)


def carry_share(ctx):
    """Of the prefill rows dispatched in the traced window, the share that
    started from a record their sequence's last chunk left, not from zeros
    (``conv_chunks_carried`` / ``conv_chunks``: booked on the host at
    dispatch)."""
    s = ctx["stats"]
    if not s.get("conv_chunks"):
        return None
    return _shared.pct(s["conv_chunks_carried"], s["conv_chunks"])


def _kernel(ctx, pattern, per_iteration: int, what: str):
    """(device seconds, calls) of the ops of the decode programs whose name
    matches ``pattern``, or None — LOUDLY — where the calls are far from
    ``per_iteration`` a decode iteration."""
    hit = [(op, secs, calls) for prog in _shared.DECODE_PROGRAMS
           for op, (secs, calls) in ctx["trace"]["ops_by_program"].get(
               prog, {}).items() if pattern.search(op)]
    if not hit or "layer_types" not in ctx["model"]:
        return None
    secs = sum(s for _, s, _ in hit)
    calls = sum(c for _, _, c in hit)
    want = per_iteration * _shared.decode_iters(ctx)
    say(f"{what}, decode form: {secs:.4f} s in {calls:.0f} calls of "
        f"{sorted({op for op, _, _ in hit})}; {per_iteration} a decode "
        f"iteration x iterations = {want}")
    if not want or not 0.5 <= calls / want <= 1.5:
        say(f"KERNEL NAME AMBIGUOUS: {what}'s roofline left out "
            f"({per_iteration} calls a decode iteration were expected)")
        return None
    return secs, calls


def gmm_roofline(ctx):
    """``_moe.grouped_roofline`` for the layers that HAVE experts: least
    time for the grouped GEMMs the decode programs ran in the traced window
    (routed rows = the window's decode tokens x k; experts touched MODELLED
    as the uniform expectation at the window's mean decode batch), over
    their device time."""
    if "layer_types" not in ctx["model"] or not ctx["peaks"]:
        return None
    cfg = ctx["model"]
    n_exp = work_mixed.layers(cfg)["experts"]
    kernel = _kernel(ctx, _moe.GROUPED_KERNEL,
                     _moe.GEMMS_PER_LAYER * n_exp, "grouped GEMM")
    iters = _shared.decode_iters(ctx)
    tokens = ctx["tokens_emitted"]
    if kernel is None or not iters or not tokens:
        return None
    batch = tokens / iters
    touched = work_mixed.experts_touched_uniform(cfg, batch)
    one = work_mixed.grouped_matmul(cfg, batch * cfg["num_experts_per_tok"],
                                    touched)
    n = n_exp * iters
    total = {"flops": one["flops"] * n, "bytes": one["bytes"] * n}
    least, bound = work_mixed.least_time_s(total, ctx["peaks"])
    say(f"grouped GEMM, decode form, {n_exp} expert layers: mean batch "
        f"{batch:.1f} rows a step, {touched:.1f} of {cfg['num_experts']} "
        f"experts touched (modelled); least {least:.4f} s ({bound} bound: "
        f"{total['flops']:.3e} FLOPs, {total['bytes']:.3e} bytes)")
    return 100.0 * least / kernel[0]


def attn_roofline(ctx):
    """``_shared.paged_roofline``'s decode form for the layers that HAVE
    attention: least time for the attention the decode programs ran in the
    traced window (every sequence's progress past its prompt, a token a
    step: ``work_mixed.attn_decode_span``), over the paged kernel's device
    time inside those programs."""
    if "layer_types" not in ctx["model"] or not ctx["peaks"]:
        return None
    cfg = ctx["model"]
    kernel = _kernel(ctx, _shared.PAGED_KERNEL,
                     work_mixed.layers(cfg)["attention"],
                     "paged attention kernel")
    if kernel is None:
        return None
    before, after = ctx["progress"]
    uid_len = {ctx["uid_of"][t]: n for t, n in ctx["done_len"].items()
               if t in ctx["uid_of"]}
    flops = byts = 0.0
    for uid in set(before) | set(after):
        prompt, a = before.get(uid, (None, 0))
        if uid in after:
            prompt, b = after[uid]
        elif uid in uid_len:
            b = uid_len[uid]
        else:
            continue
        if prompt is None or b <= max(a, prompt):
            continue
        span = work_mixed.attn_decode_span(cfg, max(a, prompt), b)
        flops += span["flops"]
        byts += span["bytes"]
    if not flops:
        return None
    least, bound = work_mixed.least_time_s({"flops": flops, "bytes": byts},
                                           ctx["peaks"])
    say(f"paged attention kernel, decode form, attention layers only: least "
        f"{least:.4f} s ({bound} bound: {flops:.3e} FLOPs, {byts:.3e} "
        f"bytes) over {kernel[0]:.4f} s")
    return 100.0 * least / kernel[0]
