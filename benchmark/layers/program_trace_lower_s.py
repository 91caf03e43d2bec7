"""Engine programs: seconds that JAX's own events book to TRACING and LOWERING the registered programs of this process, ahead of or at their first calls (``trace_s`` + ``lower_s`` of the ``program`` records with ``variant`` <= 1 in the build ledger, ``profiling/trace.py``): Python, paid on every start. The compile cache does NOT move it. New in PR 57: None where a program has no ledger."""
from benchmark.layers import _builds


def read(ctx):
    s = _builds.built()
    return None if s is None else s["trace_s"] + s["lower_s"]
