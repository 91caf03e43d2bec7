"""Engine programs: device time of ``jit_step_prefill`` per 1,000 prompt
tokens prefilled in the traced window."""
from benchmark.layers import _shared


def read(ctx):
    s, n = _shared.program_s(ctx, _shared.PREFILL_PROGRAMS), ctx["stats"]["prefill_tokens"]
    return 1e3 * s / (n / 1000.0) if s and n else None
