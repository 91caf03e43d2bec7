"""Closed loop: ``clients`` callers, each sending its next request when its
last one completes (offline document work: callers wait). The schedule is
one seeded queue per client, long enough that none runs dry."""
from benchmark.traffic.generate import draw_lengths, draw_tokens


def generate(p: dict, rng, vocab: int, seconds: float) -> dict:
    clients = int(p["clients"])
    # never fewer than a client could finish: ``max_rate_per_client`` is an
    # upper bound on completions a second for one caller
    depth = int(seconds * float(p.get("max_rate_per_client", 1.0))) + 4
    queues = []
    for _ in range(clients):
        plen = draw_lengths(rng, p["prompt_len"], depth)
        olen = draw_lengths(rng, p["output_len"], depth)
        queues.append([{"prompt": draw_tokens(rng, vocab, n),
                        "max_new": int(m)} for n, m in zip(plen, olen)])
    return {"clients": queues}
