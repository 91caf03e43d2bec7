"""Mean share of the engine's slots that decode in an iteration: tokens the runner saw step() emit in the traced window over (window iterations + single decode steps) x max_seqs."""
from benchmark.layers import _shared


def read(ctx):
    return _shared.pct(ctx['tokens_emitted'], _shared.decode_iters(ctx) * ctx['engine']['max_seqs'])
