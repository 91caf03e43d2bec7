"""Engine programs: device time of the decode window program (``jit_run``)
and the single decode step (``jit_step_decode``) on the "XLA Modules" line,
per decode iteration."""
from benchmark.layers import _shared


def read(ctx):
    s, n = _shared.program_s(ctx, _shared.DECODE_PROGRAMS), _shared.decode_iters(ctx)
    return 1e3 * s / n if s and n else None
