"""Scheduler and engine host loop: from an entry's append to the pipeline to the end of its commit, host clock, a mean over the entries committed in the traced window (``inflight_residence_s`` / ``entries_committed``). New in PR 37: None without the counters."""
from benchmark.layers import _entries


def read(ctx):
    return _entries.residence_ms(ctx)
