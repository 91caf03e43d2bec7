"""Arithmetic shared by the readers of a decoder of latent attention
(``work_latent.py``: kanana-2). ``_moe.grouped_roofline`` counts experts in
every layer of ``num_hidden_layers`` and ``_shared.paged_roofline`` K and V
a KV head; here the grouped GEMM is held to the layers that HAVE experts
and the attention kernel to the latent row a token — in the sanity check of
the calls and in the work both — so neither can read over 100 % by counting
what is not there. Everything the program must publish for them is new in
PR 54 — the ``paged_latent_*`` kernel names and the ``latent_absorb`` scope:
where a program lacks one (a parent commit), the reader finds nothing and
returns None."""
from __future__ import annotations

import re

from benchmark import work_latent
from benchmark.common import say
from benchmark.layers import _moe, _scopes, _shared

ABSORB_SCOPES = ("latent_absorb",)
#: the latent form of the paged kernel, decode (``ops/pallas/
#: paged_attention.py`` names its calls by form)
LATENT_KERNEL = re.compile(r"^paged_latent_decode(\.\d+)?$")


def is_latent(ctx) -> bool:
    return "kv_lora_rank" in ctx["model"]


def absorb_share(ctx):
    """What the latent page costs beside the kernel — ``W_dkv``, the
    latent's norm, the rope key, the keys' up-projection folded into the
    query and ``W_uv`` after the weighted sum (scope ``latent_absorb``) —
    over the decode programs' device self time."""
    tab = _scopes.table(ctx)
    if tab is None or not any(scope in ABSORB_SCOPES for p in tab.values()
                              for scope, _ in p):
        return None
    return _scopes.share(ctx, _scopes.DECODE_PROGRAMS, ABSORB_SCOPES)


def _kernel(ctx, pattern, per_iteration: int, what: str):
    """(device seconds, calls) of the ops of the decode programs whose name
    matches ``pattern``, or None — LOUDLY — where the calls are not
    ``per_iteration`` a decode iteration (within a half)."""
    hit = [(op, secs, calls) for prog in _shared.DECODE_PROGRAMS
           for op, (secs, calls) in ctx["trace"]["ops_by_program"].get(
               prog, {}).items() if pattern.search(op)]
    if not hit or not is_latent(ctx):
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


def attn_roofline(ctx):
    """Least time for the latent rows the decode programs' kernel had to
    read in the traced window — every sequence's progress past its prompt,
    a token a step, the context each time, 1,152 B a token a layer as
    NEEDED whatever the stored padding (``work_latent.latent_decode_span``)
    — over the latent kernel's device time inside those programs."""
    if not is_latent(ctx) or not ctx["peaks"]:
        return None
    cfg = ctx["model"]
    kernel = _kernel(ctx, LATENT_KERNEL,
                     work_latent.layers(cfg)["attention"],
                     "latent attention kernel")
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
        span = work_latent.latent_decode_span(cfg, max(a, prompt), b)
        flops += span["flops"]
        byts += span["bytes"]
    if not flops:
        return None
    least, bound = work_latent.least_time_s({"flops": flops, "bytes": byts},
                                            ctx["peaks"])
    say(f"latent attention kernel, decode form: least {least:.4f} s "
        f"({bound} bound: {flops:.3e} FLOPs, {byts:.3e} bytes of latent "
        f"rows as needed) over {kernel[0]:.4f} s")
    return 100.0 * least / kernel[0]


def gmm_roofline(ctx):
    """``_moe.grouped_roofline``'s arithmetic held to the layers that HAVE
    experts: least time for the grouped GEMMs the decode programs ran in
    the traced window (routed rows = the window's decode tokens x k;
    experts touched MODELLED as the uniform expectation at the window's
    mean LIVE decode batch — the expectation is concave in the batch, so at
    a batch that varies the model is an upper estimate and the share reads
    a few per cent high), over their device time."""
    if not is_latent(ctx) or not ctx["peaks"]:
        return None
    cfg = ctx["model"]
    n_exp = work_latent.layers(cfg)["experts"]
    kernel = _kernel(ctx, _moe.GROUPED_KERNEL,
                     _moe.GEMMS_PER_LAYER * n_exp, "grouped GEMM")
    iters = _shared.decode_iters(ctx)
    # the rows of a decode ITERATION: the window's decode tokens less those
    # a prefill step's decode block made (``fused_decode_tokens``: in no
    # iteration; counting them reads the batch, the experts touched and so
    # the roofline high by ``fused_decode_share`` — 110 % at the cell's
    # rate, my chip run, PR 54, call C)
    tokens = ctx["tokens_emitted"] - ctx["stats"].get("fused_decode_tokens",
                                                      0)
    if kernel is None or not iters or tokens <= 0:
        return None
    batch = tokens / iters
    touched = work_latent.experts_touched_uniform(cfg, batch)
    one = work_latent.grouped_matmul(
        cfg, batch * cfg["num_experts_per_tok"], touched)
    n = n_exp * iters
    total = {"flops": one["flops"] * n, "bytes": one["bytes"] * n}
    least, bound = work_latent.least_time_s(total, ctx["peaks"])
    say(f"grouped GEMM, decode form, {n_exp} expert layers: mean batch "
        f"{batch:.1f} live rows a step, {touched:.1f} of "
        f"{cfg['n_routed_experts']} experts touched (modelled); least "
        f"{least:.4f} s ({bound} bound: {total['flops']:.3e} FLOPs, "
        f"{total['bytes']:.3e} bytes)")
    return 100.0 * least / kernel[0]
