"""Arithmetic shared by the per-layer readers. A reader is
``read(ctx) -> number | None``; ``ctx`` is what the runner of the traced
run hands over: ``trace`` (``reduce_trace.summarize``), ``stats`` (the
engine's counters over the traced window), ``engine``/``model`` (the
configuration file), ``peaks``, and the runner's own counts."""
from __future__ import annotations

import re

from benchmark import work
from benchmark.common import say

DECODE_PROGRAMS = ("jit_run", "jit_step_decode")
PREFILL_PROGRAMS = ("jit_step_prefill",)
#: how the paged attention kernel shows on the device's op line. The
#: program gives its Pallas kernels no stable name yet (PERF.md, tracing
#: list): in the v5e traces of PR 22 the only Pallas kernel of a bf16
#: serving program, paged attention, is the op ``closed_call`` — a generic
#: name, which is why ``paged_roofline`` checks what it matched.
PAGED_KERNEL = re.compile(r"^closed_call$|paged|ragged", re.I)
#: the kernel's two forms, told apart by the program that launched them
FORM_PROGRAMS = {"decode": DECODE_PROGRAMS, "prefill": PREFILL_PROGRAMS}


def pct(num, den):
    return 100.0 * num / den if den else None


def program_s(ctx, names) -> float | None:
    progs = ctx["trace"]["programs"]
    hit = [v["s"] for k, v in progs.items() if k in names]
    return sum(hit) if hit else None


def decode_iters(ctx) -> int:
    return ctx["stats"]["window_iters"] + ctx["stats"]["decode_steps"]


def host_share(ctx):
    """Host seconds the engine spent planning, enqueuing and committing.
    ``drain_block_s`` is NOT in it (ISSUE 22 had it in): that is the host
    waiting for the device, and has a metric of its own."""
    s = ctx["stats"]
    return pct(s["plan_s"] + s["dispatch_s"] + s["commit_s"], ctx["window_s"])


def drain_block_share(ctx):
    return pct(ctx["stats"]["drain_block_s"], ctx["window_s"])


def idle_share(ctx):
    v = ctx["trace"]["idle_share"]
    return None if v is None else 100.0 * v


def peak_hbm_gib(ctx):
    b = ctx["memory_peak_bytes"]
    return b / 2 ** 30 if b else None


def paged_kernel(ctx, form: str):
    """(device seconds, calls) of the paged attention kernel launched by
    the programs of one form, or None — LOUDLY — where what the pattern
    matched cannot be one kernel: ops of more than one name, or a number
    of calls far from one a layer and iteration (another kernel under the
    same generic name would at least double it)."""
    hit = [(op, secs, calls) for prog in FORM_PROGRAMS[form]
           for op, (secs, calls) in ctx["trace"]["ops_by_program"].get(
               prog, {}).items() if PAGED_KERNEL.search(op)]
    if not hit:
        return None
    names = sorted({op for op, _, _ in hit})
    secs = sum(s for _, s, _ in hit)
    calls = sum(c for _, _, c in hit)
    iters = decode_iters(ctx) if form == "decode" \
        else ctx["stats"]["prefill_steps"]
    want = work.shapes(ctx["model"])["L"] * iters
    say(f"paged attention kernel, {form} form: {secs:.4f} s in {calls:.0f} "
        f"calls of {names}; layers x iterations = {want}")
    if len(names) > 1 or not want or not 0.5 <= calls / want <= 1.5:
        say(f"KERNEL NAME AMBIGUOUS: {form}-form roofline left out (one "
            f"kernel, about one call a layer and iteration, was expected)")
        return None
    return secs, calls


def paged_roofline(ctx, form: str):
    """Least time the chip could take for the attention that ONE form of
    the kernel ran in the traced window, over that form's device time
    (the kernel's time inside ``jit_step_prefill`` runs, or inside the
    decode window and single-step programs). Work from the sequences' own
    progress: a sequence that advanced from ``a`` to ``b`` tokens of
    context attended b(b+1)/2 - a(a+1)/2 query-key pairs, the part below
    its prompt length in the prefill form and the rest in the decode form.
    K/V bytes are read once per decode step (the context each time) and
    once per prefill chunk — a MODELLED mean chunk, prefilled tokens over
    prefill steps: the engine does not report each plan's rows."""
    kernel = paged_kernel(ctx, form)
    if kernel is None or not ctx["peaks"]:
        return None
    cfg, sh = ctx["model"], work.shapes(ctx["model"])
    before, after = ctx["progress"]
    uid_len = {ctx["uid_of"][t]: n for t, n in ctx["done_len"].items()
               if t in ctx["uid_of"]}
    st = ctx["stats"]
    chunk = max(st["prefill_tokens"] / st["prefill_steps"], 1.0) \
        if st["prefill_steps"] else 1.0
    kv_tok = 2 * sh["L"] * sh["KV"] * sh["D"] * 2
    flops = byts = 0.0
    for uid in set(before) | set(after):
        prompt, a = before.get(uid, (None, 0))
        if uid in after:
            prompt, b = after[uid]
        elif uid in uid_len:
            b = uid_len[uid]
        else:
            continue
        if prompt is None or b <= a:
            continue
        p_hi = min(b, prompt)                     # prefill part: a..p_hi
        d_lo = max(a, prompt)                     # decode part: d_lo..b
        if form == "prefill" and p_hi > a:
            flops += work.attn_flops(cfg, p_hi - a, a)
            byts += kv_tok * (a + p_hi) / 2.0 * -(-(p_hi - a) // chunk)
        if form == "decode" and b > d_lo:
            flops += work.attn_flops(cfg, b - d_lo, d_lo)
            byts += kv_tok * (d_lo + b) / 2.0 * (b - d_lo)
    if not flops:
        return None
    least, bound = work.least_time_s({"flops": flops, "bytes": byts},
                                     ctx["peaks"])
    say(f"paged attention kernel, {form} form: least {least:.4f} s "
        f"({bound} bound: {flops:.3e} FLOPs, {byts:.3e} bytes)")
    return 100.0 * least / kernel[0]
