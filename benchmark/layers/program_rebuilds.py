"""Engine programs: registered programs that were BUILT AGAIN by a later call in this process (``program`` records with ``variant`` >= 2 in the build ledger: an argument that differed from the first call's, named in the record's ``differs``); each is a compile under load, and a sound tree reads 0. The compile cache does not move it. New in PR 57: None where a program has no ledger."""
from benchmark.layers import _builds


def read(ctx):
    s = _builds.summary()
    return None if s is None else float(s["rebuilt"])
