"""Engine programs: seconds (trace + lower + backend) of the jitted functions of this process that are in NO engine's table and reached the backend (``seconds`` of the ``outside`` records with a backend event in the build ledger; each carries the ``phase`` and the ``site`` that jitted it). The compile cache MOVES it: read it with the run's ``builds:`` line. New in PR 57: None where a program has no ledger."""
from benchmark.layers import _builds


def read(ctx):
    s = _builds.summary()
    return None if s is None else s["outside_s"]
