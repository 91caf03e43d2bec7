"""Open loop with the work FIXED: independent users as ``open_loop``, but the
seed chooses order, placement and token ids and nothing else. Every seed
offers the same number of requests, the same lengths, and the same sum of
prompt and of output tokens in every block of ``block_slots`` slots — so
the runs of a cell differ by what the system does, not by how much it was
given (PERF_LEDGER, PRs 30 and 33: cells refused for their own seed-to-seed
noise, because ``open_loop`` draws the COUNT and every length from the seed).

- time is cut into slots of ``1 / rate`` seconds, one of whose edges lies
  at ``lead_in_s`` (where the judged window opens); ONE arrival a slot,
  placed uniformly inside it by the seed; only slots that lie wholly inside
  the run are used;
- classes are dealt to the slots in the fixed repeating ``pattern``;
- a class's prompt lengths are the stratified quantiles of its distribution
  over a count that depends only on the file and the run's length (the same
  multiset for every seed), its output lengths likewise; both are dealt to
  the blocks by permutations with a CONSTANT seed, one quantile band a
  member, so that every block holds short and long ones of each;
- the seed permutes a block's requests among the block's slots of their
  class, and draws the token ids.

Parameters (``traffic/<name>.json``): ``arrivals.rate``, ``lead_in_s``,
``pattern`` (class names), ``classes`` (name -> ``prompt_len``,
``output_len``: ``draw_lengths``' distribution specs), ``block_slots``
(default 8; a multiple of the pattern's length). Returns ``requests`` in
``open_loop``'s form, each with its ``class``."""
from __future__ import annotations

import math
from statistics import NormalDist

import numpy as np

from benchmark.traffic.generate import draw_tokens

#: the seed of everything that must NOT vary with the run's seed
FIXED = 0x5EED


def quantiles(spec: dict, n: int) -> np.ndarray:
    """The ``n`` stratified quantiles (at ``(i + 0.5) / n``) of a length
    distribution spec, clipped to [min, max], ascending."""
    u = (np.arange(n) + 0.5) / n
    lo, hi = int(spec["min"]), int(spec["max"])
    d = spec["dist"]
    if d == "lognormal":
        z = np.asarray([NormalDist().inv_cdf(float(x)) for x in u])
        x = np.exp(math.log(spec["median"]) + spec["sigma"] * z)
    elif d == "loguniform":
        x = np.exp(math.log(lo) + u * (math.log(hi) - math.log(lo)))
    elif d == "uniform":
        x = lo + u * (hi + 1 - lo)
    else:
        raise ValueError(f"unknown length distribution {d!r}")
    return np.clip(np.floor(x), lo, hi).astype(np.int64)


def deal(spec: dict, n_blocks: int, members: int, fixed) -> np.ndarray:
    """[n_blocks, members] lengths: member ``k`` of a block takes one value
    of quantile band ``k`` (``n_blocks`` values wide), the band's values
    dealt to the blocks by a constant-seeded permutation."""
    q = quantiles(spec, n_blocks * members).reshape(members, n_blocks)
    return np.stack([q[k][fixed.permutation(n_blocks)]
                     for k in range(members)], axis=1)


def generate(p: dict, rng, vocab: int, seconds: float) -> dict:
    rate, lead = float(p["arrivals"]["rate"]), float(p["lead_in_s"])
    pattern = list(p["pattern"])
    block = int(p.get("block_slots", 8))
    if block % len(pattern):
        raise ValueError("block_slots must be a multiple of the pattern")
    # slot k covers [lead + k / rate, lead + (k + 1) / rate)
    k_lo = -math.floor(lead * rate + 1e-9)
    k_hi = math.floor((seconds - lead) * rate + 1e-9)      # exclusive
    slots = np.arange(k_lo, k_hi)
    # blocks are counted from the window's opening edge, both ways
    block_of = np.floor_divide(slots, block)
    b_lo, n_blocks = int(block_of.min()), int(block_of.max() - block_of.min() + 1)
    cls_of = np.asarray([pattern[k % len(pattern)] for k in slots])
    fixed = np.random.default_rng(FIXED)
    plen = np.zeros(len(slots), np.int64)
    olen = np.zeros(len(slots), np.int64)
    for name in sorted(set(pattern)):
        members = pattern.count(name) * block // len(pattern)
        prompts = deal(p["classes"][name]["prompt_len"], n_blocks, members,
                       fixed)
        outputs = deal(p["classes"][name]["output_len"], n_blocks, members,
                       fixed)
        for b in range(n_blocks):
            # which output band meets which prompt band: fixed too
            outputs[b] = outputs[b][fixed.permutation(members)]
            mine = np.nonzero((block_of == b_lo + b) & (cls_of == name))[0]
            # a block cut by the run's edge keeps its FIRST members (fixed);
            # the seed permutes them among the slots that exist
            order = rng.permutation(len(mine))
            plen[mine] = prompts[b][:len(mine)][order]
            olen[mine] = outputs[b][:len(mine)][order]
    due = lead + (slots + rng.uniform(0.0, 1.0, len(slots))) / rate
    return {"requests": [
        {"due_s": float(t), "prompt": draw_tokens(rng, vocab, n),
         "max_new": int(m), "class": str(c)}
        for t, n, m, c in zip(due, plen, olen, cls_of)]}
