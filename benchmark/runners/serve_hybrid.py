"""Serving runner for a ``mode: serve_hybrid`` configuration: a decoder
whose layers are of two kinds — full causal attention over a KV table that
grows, and a sliding window over a bounded ring — with routed experts in
every layer (SmallThinker). Everything but the reference check and the
traffic kind's name is ``runners/serve.py``: the Router over one worker for
``--trace 0``, the engine in process for ``--trace 1``, the same load loop,
judge and result line.

- The schedule of an ``open_loop_fixed`` traffic file is ``open_loop``'s in
  form (requests due on a seeded schedule): it is handed to
  ``serve.run_load`` / ``serve.judge`` under that name.
- The reference check keeps ``serve.reference_check``'s contract (a seeded
  sample of served streams, teacher-forced; the served token's reference
  logit within ``logit_tolerance`` of the maximum; the first token is the
  prefill form's, the later ones the decode form's) with the plain
  reference of the module the cell's ``reference`` section names, and a
  sample STRATIFIED by class: at least half the checked streams have a
  prompt of ``long_prompt_min`` tokens and more, so that the checked rows
  lie past the window, where every window layer reads a ring that has
  wrapped; of each stream the first ``rows`` served tokens AND the last
  ``rows_tail`` are checked (hundreds of decode steps later).
"""
from __future__ import annotations

import importlib
import os
import sys

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))

from benchmark.runners import serve  # noqa: E402
# (the seeded weights as the worker built them; importing it lays OLMoE's
# check over ``serve``'s, and this module's own over that, below)
from benchmark.runners.serve_moe import seeded_params  # noqa: E402

_generate = serve.generate


def generate(traffic: dict, seed: int, vocab: int, seconds: float) -> dict:
    """``traffic.generate.generate``; a schedule of requests due at fixed
    times is served and judged as ``open_loop``."""
    out = _generate(traffic, seed, vocab, seconds)
    if "requests" in out:
        out["kind"] = "open_loop"
    return out


def checked_rows(prompt_len: int, served: int, spec: dict) -> np.ndarray:
    """Positions of one stream whose logits are checked: the last prompt
    token's (it predicts the first served token) and those of the first
    ``rows`` - 1 served tokens, then the last ``rows_tail`` served ones."""
    head = np.arange(min(served, int(spec["rows"])))
    tail = np.arange(max(served - int(spec.get("rows_tail", 0)), len(head)),
                     served)
    return prompt_len - 1 + np.concatenate([head, tail])


def reference_margins(ref, model_cfg, params, streams, spec: dict) -> dict:
    """``streams``: [(prompt tokens, served tokens)]. Worst margin (the
    reference's maximum logit less its logit of the served token) of the
    first served token and of the later ones; the share of checked rows
    whose served token is not the reference's argmax at all (reported, not
    judged); and how many rows lie past the window."""
    m = model_cfg
    bucket = int(spec.get("pad_to", 1024))
    worst = {"prefill_form": 0.0, "decode_form": 0.0}
    rows_checked = off_argmax = past_window = 0
    longest = 0
    for prompt, served in streams:
        P = len(prompt)
        toks = list(prompt) + list(served)
        S = -(-len(toks) // bucket) * bucket
        padded = np.zeros(S, np.int32)
        padded[:len(toks)] = toks
        rows = checked_rows(P, len(served), spec)
        logits = np.asarray(ref.forward_logits(
            padded, embed=params["embed"],
            layer=lambda i: ref.program_layer(params, i),
            kinds=ref.program_kinds(m), window=int(m.sliding_window),
            ln_final=params["ln_final"]["scale"], unembed=params["unembed"],
            theta=float(m.rope_theta), eps=float(m.norm_eps),
            top_k=m.moe.top_k, rows=rows,
            q_block=int(spec.get("q_block", 512))))
        tok = np.asarray(toks)[rows + 1]
        margin = logits.max(axis=1) - logits[np.arange(len(rows)), tok]
        worst["prefill_form"] = max(worst["prefill_form"], float(margin[0]))
        if len(rows) > 1:
            worst["decode_form"] = max(worst["decode_form"],
                                       float(margin[1:].max()))
        rows_checked += len(rows)
        off_argmax += int((margin > 0).sum())
        past_window += int((rows >= int(m.sliding_window)).sum())
        longest = max(longest, len(toks))
    return {"requests": len(streams), "rows": rows_checked,
            "rows_past_the_window": past_window,
            "longest_stream_tokens": longest,
            "worst_margin": worst,
            "rows_off_the_reference_argmax_share":
                off_argmax / max(rows_checked, 1)}


def stratified_sample(ok, spec: dict, seed: int):
    """``requests`` served streams that fit the reference, at least half of
    them (rounded up) of the long class; None where the run served too few
    of either."""
    fits = [r for r in ok if len(r.prompt) + r.max_new <= spec["max_tokens"]]
    rng = np.random.default_rng([seed, 11])
    rng.shuffle(fits)
    n = int(spec["requests"])
    long_ = [r for r in fits if len(r.prompt) >= int(spec["long_prompt_min"])]
    short = [r for r in fits if len(r.prompt) < int(spec["long_prompt_min"])]
    sample = long_[:-(-n // 2)] + short[:n // 2]
    return sample if len(sample) == n else None


def reference_check(ok, conf: dict, cellp: dict, seed: int):
    """``serve.reference_check``'s contract, with the cell's own reference
    module and a sample stratified by class. Runs in THIS process, on the
    device the worker has given back."""
    spec = cellp["reference"]
    ref = importlib.import_module(spec["module"])
    sample = stratified_sample(ok, spec, seed)
    if sample is None:
        return False, {"error": f"fewer than {spec['requests']} served "
                                f"streams, half of them long, fit the "
                                f"reference's {spec['max_tokens']} tokens"}
    model_cfg, params = seeded_params(conf, seed)
    detail = reference_margins(ref, model_cfg, params,
                               [(r.prompt, r.tokens) for r in sample], spec)
    tol = float(spec["logit_tolerance"])
    detail["tolerance"] = tol
    return max(detail["worst_margin"].values()) <= tol, detail


serve.generate = generate
serve.reference_check = reference_check

if __name__ == "__main__":
    sys.exit(serve.main())
