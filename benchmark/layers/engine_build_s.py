"""Engine programs: wall time of the newest engine's constructor in this process LESS what the backend spent in it on jitted functions outside the table (``seconds`` - ``outside_backend_s`` over its ``phase`` records in the build ledger: ``model``, ``weights``, ``stack``, ``pools``, ``probes``, ``rest`` partition it): the part of ``setup_s`` before the first program, as every start pays it. The compile cache does NOT move it (what it moves is ``outside_build_s``). New in PR 57: None where a program has no ledger or built no engine by phase."""
from benchmark.layers import _builds


def read(ctx):
    s = _builds.summary()
    return s["phase_s"] - s["phase_outside_backend_s"] \
        if s and s["phases"] else None
