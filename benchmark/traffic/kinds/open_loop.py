"""Open loop: independent users. Requests are due on a seeded schedule
whether or not earlier ones have finished."""
from benchmark.traffic.generate import draw_arrivals, draw_lengths, draw_tokens


def generate(p: dict, rng, vocab: int, seconds: float) -> dict:
    due = draw_arrivals(rng, p["arrivals"], seconds)
    plen = draw_lengths(rng, p["prompt_len"], len(due))
    olen = draw_lengths(rng, p["output_len"], len(due))
    return {"requests": [
        {"due_s": float(t), "prompt": draw_tokens(rng, vocab, n),
         "max_new": int(m)} for t, n, m in zip(due, plen, olen)]}
