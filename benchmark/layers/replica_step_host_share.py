"""Router, replica loop: host seconds ``EngineBackend.step`` spent outside ``engine.step()`` (``replica_step_s`` - ``engine_step_s``) over the traced window. New in PR 37: None without the counters."""
from benchmark.layers import _entries


def read(ctx):
    return _entries.replica_step_host_share(ctx)
