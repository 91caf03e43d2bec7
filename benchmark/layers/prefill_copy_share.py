"""Engine programs: of the device time inside the prefill step programs (``_shared.PREFILL_PROGRAMS`` on the "XLA Modules" line), the share spent in plain ``copy`` instructions (``reduce_trace.op_key`` ``copy``: self time by enclosing program, ``ctx["trace"]["ops_by_program"]``). A step program reads its donated pool, writes it once in place and hands it back; a ``copy`` of a pool's size round that write (PR 55: a layout copy out and back round the pool merge's read-modify-write, two of the 3.1 GiB latent pool a step) is the pool read and written again for nothing, and the scope table books it ``ambiguous``, so no share by scope shows it. What small copies a program keeps (a transposed projection, a staged row) stay in the share: it reads a few per cent where no pool is copied. Needs nothing of the program; None where no prefill program ran in the window."""
from benchmark.layers import _shared


def read(ctx):
    total = _shared.program_s(ctx, _shared.PREFILL_PROGRAMS)
    if not total:
        return None
    by_program = ctx["trace"]["ops_by_program"]
    copies = sum(by_program.get(prog, {}).get("copy", (0.0, 0))[0]
                 for prog in _shared.PREFILL_PROGRAMS)
    return _shared.pct(copies, total)
