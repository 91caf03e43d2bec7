"""Training batches of packed documents: seeded documents (heavy-tailed
lengths) joined with an EOS into full ``seq``-token rows, a fresh batch for
every step. Tokens come from a seeded sub-vocabulary of ``support`` ids so
that a few AdamW steps visibly learn it. The engine's loss takes no
per-document segments today, so the lengths shape the data (where the EOS
falls), not the compute."""
import numpy as np

from benchmark.traffic.generate import draw_lengths


def generate(p: dict, rng, vocab: int, seconds: float) -> dict:
    seq, batch = int(p["seq"]), int(p["global_batch"])
    steps = int(seconds * float(p["max_steps_per_s"])) + 4
    support = rng.choice(vocab, size=min(int(p["support"]), vocab),
                         replace=False)
    eos = int(support[0])
    need = steps * batch * seq
    lens = draw_lengths(rng, p["doc_len"], need // int(p["doc_len"]["min"]) + 1)
    lens = lens[:int(np.searchsorted(np.cumsum(lens + 1), need)) + 1]
    flat = support[rng.integers(1, len(support), int(np.sum(lens + 1)))]
    flat[np.cumsum(lens + 1) - 1] = eos            # one EOS closes each document
    rows = flat[:need].reshape(steps, batch, seq).astype(np.int32)
    return {"batches": rows, "docs": int(len(lens)), "eos": eos,
            "tokens_per_step": batch * seq}
