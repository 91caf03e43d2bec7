"""Platform/device abstraction.

TPU-native analogue of the reference accelerator layer
(/root/reference/accelerator/abstract_accelerator.py:10 and
real_accelerator.py:52). On JAX the runtime already abstracts hardware via
PJRT, so this layer is deliberately thin: it is the single place the rest of
the framework asks "what am I running on, how many devices, how much memory,
which dtypes are fast". The platform is whatever JAX selects
(``JAX_PLATFORMS`` is the one override).
"""
from __future__ import annotations

import functools
from dataclasses import dataclass
from typing import Any

import jax
import jax.numpy as jnp

from .utils.logging import logger


@dataclass(frozen=True)
class DeviceInfo:
    platform: str           # 'tpu' | 'cpu' | 'gpu'
    kind: str               # e.g. 'TPU v5 lite'
    num_devices: int        # global device count
    num_local_devices: int
    num_processes: int
    process_index: int


class Accelerator:
    """Queries about the current platform. All device touches route here."""

    # -- identity ---------------------------------------------------------
    @functools.cached_property
    def devices(self) -> list[Any]:
        return jax.devices()

    @functools.cached_property
    def info(self) -> DeviceInfo:
        devs = self.devices
        return DeviceInfo(
            platform=devs[0].platform,
            kind=getattr(devs[0], "device_kind", devs[0].platform),
            num_devices=len(devs),
            num_local_devices=len([d for d in devs if d.process_index == jax.process_index()]),
            num_processes=jax.process_count(),
            process_index=jax.process_index(),
        )

    def device_name(self, index: int = 0) -> str:
        return str(self.devices[index])

    def is_tpu(self) -> bool:
        return self.info.platform == "tpu"

    def device_count(self) -> int:
        return self.info.num_devices

    def local_device_count(self) -> int:
        return self.info.num_local_devices

    def current_device(self) -> Any:
        return self.devices[0]

    # -- memory (reference abstract_accelerator memory_* methods) ---------
    def memory_stats(self, index: int = 0) -> dict[str, int]:
        try:
            return self.devices[index].memory_stats() or {}
        except Exception:
            return {}

    def memory_allocated(self, index: int = 0) -> int:
        return self.memory_stats(index).get("bytes_in_use", 0)

    def max_memory_allocated(self, index: int = 0) -> int:
        return self.memory_stats(index).get("peak_bytes_in_use", 0)

    def total_memory(self, index: int = 0) -> int:
        return self.memory_stats(index).get("bytes_limit", 0)

    def available_memory(self, index: int = 0) -> int:
        stats = self.memory_stats(index)
        return stats.get("bytes_limit", 0) - stats.get("bytes_in_use", 0)

    # -- dtype support ----------------------------------------------------
    def is_bf16_supported(self) -> bool:
        return True  # all TPU generations; CPU XLA emulates

    def is_fp16_supported(self) -> bool:
        # TPUs compute in bf16/f32; fp16 storage works but is not the fast path.
        return not self.is_tpu()

    def preferred_dtype(self) -> jnp.dtype:
        return jnp.bfloat16

    def supported_dtypes(self) -> list[jnp.dtype]:
        dts = [jnp.float32, jnp.bfloat16]
        if self.is_fp16_supported():
            dts.append(jnp.float16)
        return dts

    # -- comm / misc ------------------------------------------------------
    def communication_backend_name(self) -> str:
        # XLA lowers collectives onto ICI/DCN itself; there is no NCCL analogue
        # to pick. The name is informational (reference
        # cuda_accelerator.py:241 returns 'nccl').
        return "xla"

    def synchronize(self, value: Any | None = None) -> None:
        if value is not None:
            jax.block_until_ready(value)
        else:
            jnp.zeros(()).block_until_ready()

    def random_seed_key(self, seed: int) -> jax.Array:
        return jax.random.PRNGKey(seed)

    def empty_cache(self) -> None:
        # XLA arenas don't expose an explicit cache flush; live-buffer deletion
        # happens via GC. Provided for API parity.
        pass


_accelerator: Accelerator | None = None


def get_accelerator() -> Accelerator:
    """Singleton accessor (reference real_accelerator.py:52)."""
    global _accelerator
    if _accelerator is None:
        _accelerator = Accelerator()
        try:
            info = _accelerator.info
            logger.info(
                f"accelerator: platform={info.platform} kind={info.kind} "
                f"devices={info.num_devices} processes={info.num_processes}")
        except Exception as e:  # backend not up yet — info is best-effort
            logger.debug(f"accelerator info probe failed: {e!r}")
    return _accelerator


def set_accelerator(acc: Accelerator) -> None:
    global _accelerator
    _accelerator = acc
