"""Engine programs: seconds the BACKEND took for the registered programs of this process, ahead of or at their first calls (``backend_s`` of the ``program`` records with ``variant`` <= 1 in the build ledger): compiles on a cold start, reads from the persistent cache on a warm one. The compile cache MOVES it: read it with the run's ``builds: H of N first calls from the cache`` line. New in PR 57: None where a program has no ledger."""
from benchmark.layers import _builds


def read(ctx):
    s = _builds.built()
    return None if s is None else s["backend_s"]
