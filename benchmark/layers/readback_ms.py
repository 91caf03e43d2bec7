"""Scheduler and engine host loop: start of the host's ``commit`` span less the end of the entry's run on the device, a mean over the window's joined entries (``_entries``): the device has the tokens, the host does not yet. New in PR 37: None without the numbered spans, and under ``--rehearse``."""
from benchmark.layers import _entries


def read(ctx):
    return _entries.mean_ms(ctx, "readback_s")
