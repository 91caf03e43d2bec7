"""Serving runner for a ``mode: serve_latent`` configuration: a decoder of
latent attention (MLA: the cache holds ONE row ``[c | k_r]`` a token a
layer) with one leading dense layer, then routed experts beside an ungated
shared expert, and an UNTIED head (kanana-2-30b-a3b, ``deepseek_v3``).
Everything is ``runners/serve_mixed.py`` — the fixed open-loop schedule, the
stratified sample, the TWO limits of the reference check (the worst margin
of the sample: a wrong program; the mean margin over every served token: a
lower precision) — but the one call into the plain reference:
``serve_mixed.stream_margins`` hands a reference the embedding as its head
and a list of operators, and this model's reference
(``benchmark/reference/kanana2_decoder.py``) takes an unembedding of its own,
the routed scaling factor, and no operators (every layer attends).
"""
from __future__ import annotations

import os
import sys

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))

from benchmark.runners import serve, serve_mixed  # noqa: E402


def stream_margins(ref, model_cfg, params, prompt, served,
                   spec: dict) -> np.ndarray:
    """``serve_mixed.stream_margins`` over this model's reference: the
    margin of EVERY served token of one stream (the reference's maximum
    logit less its logit of the served token, teacher-forced)."""
    m = model_cfg
    bucket = int(spec.get("pad_to", 1024))
    toks = list(prompt) + list(served)
    padded = np.zeros(-(-len(toks) // bucket) * bucket, np.int32)
    padded[:len(toks)] = toks
    rows = len(prompt) - 1 + np.arange(len(served))
    fill = -len(rows) % serve_mixed.ROW_BUCKET
    logits = np.asarray(ref.forward_logits(
        padded, embed=params["embed"], unembed=params["unembed"],
        layer=lambda i: ref.program_layer(params, i),
        experts=ref.program_experts(m),
        ln_final=params["ln_final"]["scale"], theta=float(m.rope_theta),
        eps=float(m.norm_eps), top_k=m.moe.top_k,
        scaling=float(m.moe.routed_scaling_factor),
        rows=np.pad(rows, (0, fill), mode="edge"),
        q_block=int(spec.get("q_block", 512))))[:len(rows)]
    tok = np.asarray(toks)[rows + 1]
    return logits.max(axis=1) - logits[np.arange(len(rows)), tok]


# (``serve_mixed.reference_check`` and ``tools/check_precision_mixed.py``
# call the module's name)
serve_mixed.stream_margins = stream_margins

if __name__ == "__main__":
    sys.exit(serve.main())
