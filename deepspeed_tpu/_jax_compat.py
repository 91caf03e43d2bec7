"""The one JAX configuration helper the package and its tests share.

Written for the installed jax/jaxlib 0.9 (``pyproject.toml`` states the
versions): ``jax.shard_map``, ``jax.lax.axis_size``,
``jax.experimental.layout.Format`` and the partitionable threefry default
are used directly where they are needed — nothing here shims an older
JAX.
"""
from __future__ import annotations

import jax


def set_cpu_devices(n: int) -> None:
    """Force the CPU platform with ``n`` virtual devices. Must run before
    the first backend touch (``jax.devices()`` and friends) — a lazy
    backend has not read either option yet. conftest, the dryrun entry,
    ``chip_smoke.py --rehearse`` and the subprocess test templates all
    call this."""
    jax.config.update("jax_platforms", "cpu")
    jax.config.update("jax_num_cpu_devices", n)
