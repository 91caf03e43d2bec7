"""Serving runner for a ``mode: serve_mixed`` configuration: a decoder whose
layers are UNLIKE — some mix the sequence by attention over a paged KV
table, the others by a gated short convolution whose state is one record a
slot; the leading layers carry a dense feed-forward, the rest routed experts
(LFM2-MoE). Everything but the reference check is ``runners/serve_hybrid.py``
(and through it ``runners/serve.py``): the Router over one worker for
``--trace 0``, the engine in process for ``--trace 1``, an
``open_loop_fixed`` schedule served as open loop, the same load loop, judge
and result line.

The reference check (this model's plain reference: the module the cell's
``reference`` section names, ``benchmark/reference/lfm2_moe_decoder.py``;
served streams teacher-forced, the served token's reference logit under the
reference's maximum = its MARGIN) holds a run to TWO limits:

- ``logit_tolerance`` on the WORST margin of ``serve_hybrid``'s sample — a
  seeded sample of served streams STRATIFIED by class (at least half of
  ``long_prompt_min`` tokens and more); of each stream the first ``rows``
  served tokens and the last ``rows_tail`` (hundreds of decode-window
  programs after the prompt's last chunk: the record has been carried
  through all of them). What it catches is a WRONG program — a dropped
  record, a wrong router or cache reads margins of whole logits. It cannot
  tell a lower precision: one run's worst row is a near tie of the
  reference's two best tokens, and bf16's worst run reaches int8's typical.
- ``precision.mean_margin_tolerance`` on the MEAN margin over EVERY served
  token of those streams and of ``precision.requests`` more of the short
  class (a short stream's reference forward is 1-3k tokens: thousands of
  rows for seconds). A served token leaves the reference's argmax where
  the server's rounding exceeds the gap of the two best logits, by that
  gap: the mean grows with the SQUARE of the server's rounding, and over
  thousands of rows it is a steady number — the limit that a server
  computing in a lower precision than the configuration states fails.
"""
from __future__ import annotations

import importlib
import os
import sys

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))

from benchmark.runners import serve  # noqa: E402
# (importing it lays the open_loop_fixed schedule over ``serve``'s)
from benchmark.runners.serve_hybrid import (checked_rows,  # noqa: E402
                                            stratified_sample)
from benchmark.runners.serve_moe import seeded_params  # noqa: E402

ROW_BUCKET = 128    # checked rows are padded to a multiple: few head shapes


def stream_margins(ref, model_cfg, params, prompt, served,
                   spec: dict) -> np.ndarray:
    """The margin of EVERY served token of one stream: the reference's
    maximum logit less its logit of the served token, teacher-forced over
    prompt + served tokens (row 0 is the prompt's last position: the
    prefill form's token)."""
    m = model_cfg
    bucket = int(spec.get("pad_to", 1024))
    ops, experts = ref.program_ops(m)
    toks = list(prompt) + list(served)
    padded = np.zeros(-(-len(toks) // bucket) * bucket, np.int32)
    padded[:len(toks)] = toks
    rows = len(prompt) - 1 + np.arange(len(served))
    fill = -len(rows) % ROW_BUCKET
    logits = np.asarray(ref.forward_logits(
        padded, embed=params["embed"],
        layer=lambda i: ref.program_layer(params, i), ops=ops,
        experts=experts, ln_final=params["ln_final"]["scale"],
        theta=float(m.rope_theta), eps=float(m.norm_eps),
        top_k=m.moe.top_k, rows=np.pad(rows, (0, fill), mode="edge"),
        q_block=int(spec.get("q_block", 512))))[:len(rows)]
    tok = np.asarray(toks)[rows + 1]
    return logits.max(axis=1) - logits[np.arange(len(rows)), tok]


def summarize(margins: list[tuple[int, np.ndarray]], judged: int,
              spec: dict) -> dict:
    """``margins``: (prompt length, every served token's margin) a stream.
    The first ``judged`` streams are ``serve_hybrid``'s stratified sample:
    the worst margin of their checked rows (first ``rows``, last
    ``rows_tail``), by form. Every row of every stream counts in the mean
    margin and in the share of rows off the reference's argmax (reported,
    not judged)."""
    worst = {"prefill_form": 0.0, "decode_form": 0.0}
    rows_checked = off_argmax = 0
    for P, mg in margins[:judged]:
        mg = mg[checked_rows(P, len(mg), spec) - (P - 1)]
        worst["prefill_form"] = max(worst["prefill_form"], float(mg[0]))
        if len(mg) > 1:
            worst["decode_form"] = max(worst["decode_form"],
                                       float(mg[1:].max()))
        rows_checked += len(mg)
        off_argmax += int((mg > 0).sum())
    every = np.concatenate([mg for _, mg in margins])
    return {"requests": judged, "rows": rows_checked,
            "longest_stream_tokens": max(P + len(mg) for P, mg in margins),
            "worst_margin": worst,
            "rows_off_the_reference_argmax_share":
                off_argmax / max(rows_checked, 1),
            "precision": {
                "requests": len(margins), "rows": int(every.size),
                "mean_margin": float(every.mean()),
                "rows_off_the_reference_argmax_share":
                    float((every > 0).mean())}}


def precision_sample(ok, taken, spec: dict, seed: int):
    """``precision.requests`` more served streams of the short class (a
    prompt under ``long_prompt_min``: a cheap reference forward), seeded;
    None where the run served too few."""
    skip = {id(r) for r in taken}
    short = [r for r in ok if id(r) not in skip
             and len(r.prompt) < int(spec["long_prompt_min"])]
    np.random.default_rng([seed, 19]).shuffle(short)
    n = int(spec["precision"]["requests"])
    return short[:n] if len(short) >= n else None


def reference_check(ok, conf: dict, cellp: dict, seed: int):
    """``serve.reference_check``'s contract with this cell's two limits.
    Runs in THIS process, on the device the worker has given back."""
    spec = cellp["reference"]
    ref = importlib.import_module(spec["module"])
    sample = stratified_sample(ok, spec, seed)
    more = sample and precision_sample(ok, sample, spec, seed)
    if not sample or more is None:
        return False, {"error": f"fewer than {spec['requests']} served "
                                f"streams, half of them long, and "
                                f"{spec['precision']['requests']} short "
                                f"ones fit the reference's "
                                f"{spec['max_tokens']} tokens"}
    model_cfg, params = seeded_params(conf, seed)
    detail = summarize(
        [(len(r.prompt), stream_margins(ref, model_cfg, params, r.prompt,
                                        r.tokens, spec))
         for r in sample + more], len(sample), spec)
    tol = float(spec["logit_tolerance"])
    mean_tol = float(spec["precision"]["mean_margin_tolerance"])
    detail["tolerance"] = tol
    detail["precision"]["tolerance"] = mean_tol
    return (max(detail["worst_margin"].values()) <= tol
            and detail["precision"]["mean_margin"] <= mean_tol), detail


# (``serve.main`` and ``serve.traced`` call the module's name: the hook
# ``serve_moe`` and ``serve_hybrid`` lay theirs over too)
serve.reference_check = reference_check

if __name__ == "__main__":
    sys.exit(serve.main())
