"""Scheduler and engine host loop: entries already in the engine's pipeline when a new one joins it, a mean over the entries appended in the traced window (``inflight_depth_sum`` / ``entries_dispatched``): how far the host runs ahead of the device. New in PR 37: a program without the counters (a parent commit) gives None."""
from benchmark.layers import _entries


def read(ctx):
    return _entries.depth_mean(ctx)
