"""Engine programs: what the programs of the traced run's own process cost
to BUILD, from the program's build ledger (``deepspeed_tpu.profiling.trace.
build_records`` / ``build_summary``, PR 57): a record for every registered
program's build ahead of and at its first call (what JAX's own events
reported of it: trace, lower, backend compile or cache read), for every
later call that built again, for every jitted function outside any engine's
table, and for every phase of an engine's constructor.

The ledger is the process's, from its start: set-up is what it measures,
and the traced window adds to it only what compiles under load. Each reader
says whether the compile cache moves it; the first of them to read says the
cache's state to the run's log, once: ``builds: H of N first calls from the
cache`` (0 of N: a cold start; N of N: a warm one), and after it what the
program says of its ledger when it leaves (``builds_lines()``: the sums,
each rebuild, each build outside the table over 50 ms with its phase and
site). A program without the ledger (a parent commit) gives None and every
reader here returns None.
"""
from __future__ import annotations

from benchmark.common import say

_said = False


def summary() -> dict | None:
    """``build_summary()`` of this process, or None where the program has
    no ledger."""
    global _said
    try:
        from deepspeed_tpu.profiling.trace import (build_summary,
                                                   builds_lines)
    except ImportError:
        return None
    try:
        s = build_summary()
        if not _said:
            say(f"builds: {s['cache_hits']} of {s['backend_builds']} first "
                f"calls from the cache")
            for line in builds_lines():
                say(line)
            _said = True
    except Exception:  # noqa: BLE001 — a reader never breaks its run
        return None
    return s


def built() -> dict | None:
    """The summary where a registered program of this process reached the
    backend: before that there is nothing of a build to read."""
    s = summary()
    return s if s and s["backend_builds"] else None
