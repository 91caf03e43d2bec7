"""Test bootstrap: force an 8-device virtual CPU platform.

The reference tests fork N processes over NCCL (tests/unit/common.py:384
``DistributedTest``). On JAX the same coverage comes from a single process
with a virtual multi-device CPU mesh — every sharding/collective path
compiles and runs exactly as it would across a real slice.

Configured through ``jax.config`` (valid until a backend is initialized):
the tier-1 command also sets ``JAX_PLATFORMS=cpu``, which the worker
processes the serving tests spawn inherit.
"""
import os

os.environ.setdefault("DS_TPU_LOG_LEVEL", "warning")

import jax

if os.environ.get("DS_TPU_TEST_REAL_DEVICES") != "1":
    try:
        from deepspeed_tpu._jax_compat import set_cpu_devices

        set_cpu_devices(8)
    except RuntimeError:
        # backend already initialized (e.g. running a single test from a
        # session that already touched devices) — leave as-is.
        pass

import pytest  # noqa: E402


@pytest.fixture(scope="session")
def devices():
    return jax.devices()


@pytest.fixture(scope="session", autouse=True)
def _assert_multidevice(devices):
    # the sharding tests are meaningless on one device; fail loudly.
    if os.environ.get("DS_TPU_TEST_REAL_DEVICES") != "1":
        assert len(devices) == 8, f"expected 8 virtual CPU devices, got {devices}"
