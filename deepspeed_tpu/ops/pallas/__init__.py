"""Pallas TPU kernels — the role of the reference's hand-written CUDA under
/root/reference/csrc/ (transformer attention/softmax kernels, FastGen blocked
flash) re-designed as Mosaic/Pallas kernels for the MXU/VMEM machine model.

Kernels run compiled on TPU and in interpreter mode on CPU for tests;
:func:`interpret_mode` is the one place that decides which.
"""
import jax


def interpret_mode() -> bool:
    """Whether ``pallas_call`` runs in interpreter mode on the default
    backend: ``cpu`` interprets (tests), ``tpu`` compiles through Mosaic,
    and anything else is an error — these are TPU kernels, and quietly
    interpreting them on a platform nobody named would hide the device.
    Kernels look this up through the package at trace time
    (``from . import interpret_mode`` inside the launcher), so a test can
    steer every kernel by patching this one attribute."""
    platform = jax.default_backend()
    if platform == "tpu":
        return False
    if platform == "cpu":
        return True
    raise RuntimeError(
        f"Pallas TPU kernels run compiled on 'tpu' and interpreted on "
        f"'cpu'; the default jax backend is {platform!r}")


from .flash_attention import flash_attention, flash_attention_usable  # noqa: E402,F401
