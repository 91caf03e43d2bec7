"""Scheduler and engine host loop: ``device_queue_wait_ms`` over the prefill entries alone: what a fresh prompt's chunk waits before the device touches it. New in PR 37: None without the numbered spans, and under ``--rehearse``."""
from benchmark.layers import _entries


def read(ctx):
    return _entries.mean_ms(ctx, "queue_s", prefill_only=True)
