"""Kernels: the flash attention kernels of the train step (forward and the
backward pair) — the attention FLOPs a chip had to do in the traced steps
over the kernels' device time, as a share of the chip's bf16 peak."""
from __future__ import annotations

import re

from benchmark import work
from benchmark.common import say
from benchmark.layers._scopes import TRAIN_PROGRAMS

#: the kernels' names on the device's op line (the program names its Pallas
#: kernels, ``ops/pallas/flash_attention.py``): one forward; backward either
#: merged (``_bwd_dqkv``: the keys fit one block, sequence <= 1024) or the
#: pair ``_bwd_dq`` + ``_bwd_dkv``
FLASH_KERNEL = re.compile(r"^flash_attention_(fwd|bwd_\w+?)(\.\d+)?$")
#: forward passes of attention a layer and step: its own and, under full
#: remat (the cell's configuration), one more in the backward pass
FORWARDS = 2


def read(ctx):
    """FLOPs counted (``work.attn_flops``: QK^T and PV, the causal half):
    the forward once, once more for the recomputation under remat — here,
    unlike ``train_mfu``, recomputed work counts, because the kernel really
    ran it and the share is of the KERNEL's time — and the backward at twice
    the forward: 4 x forward, for the rows a chip holds. None, loudly, where
    the program launched no such kernel (XLA attention: the parent of PR 29,
    or a gate that refused) or the calls are far from (2 forward + the
    backward kernels) a layer and step, so that another kernel under these
    names, or a step without remat, is not read as this one."""
    hit = [(op, secs, calls) for prog in TRAIN_PROGRAMS
           for op, (secs, calls) in ctx["trace"]["ops_by_program"].get(
               prog, {}).items() if FLASH_KERNEL.search(op)]
    if not hit or not ctx["peaks"]:
        say("flash attention kernels: none in the train step (XLA "
            "attention ran): train_flash_attn_mfu left out")
        return None
    names = sorted({FLASH_KERNEL.search(op).group(1) for op, _, _ in hit})
    secs = sum(s for _, s, _ in hit)
    calls = sum(c for _, _, c in hit)
    layers = work.shapes(ctx["model"])["L"]
    want = layers * ctx["steps"] * (FORWARDS + len(names) - 1)
    say(f"flash attention kernels: {secs:.4f} s in {calls:.0f} calls of "
        f"{names} a device; layers x steps x ({FORWARDS} forward + "
        f"{len(names) - 1} backward) = {want}")
    if "fwd" not in names or len(names) < 2 \
            or not 0.5 <= calls / want <= 1.5:
        say("KERNEL CALLS UNEXPECTED: train_flash_attn_mfu left out (two "
            "forward calls and the backward kernels a layer and step were "
            "expected)")
        return None
    rows = ctx["steps"] * ctx["tokens_per_step"] / ctx["seq"] / ctx["chips"]
    flops = (FORWARDS + 2) * work.attn_flops(ctx["model"], ctx["seq"], 0) \
        * rows
    say(f"flash attention kernels: {flops:.3e} FLOPs a device "
        f"({rows:.0f} rows of {ctx['seq']}) = "
        f"{flops / secs / 1e12:.1f} TFLOP/s")
    return 100.0 * flops / secs / ctx["peaks"]["bf16_flops"]
