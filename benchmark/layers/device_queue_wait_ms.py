"""Scheduler and engine host loop: run start on the device less the end of the host's ``dispatch`` span, a mean over the window's joined entries (``_entries``): what an entry waits behind the entries queued before it. New in PR 37: None without the numbered spans, and under ``--rehearse``."""
from benchmark.layers import _entries


def read(ctx):
    return _entries.mean_ms(ctx, "queue_s")
