"""MFU and goodput accounting.

MFU (model FLOPs utilization, PaLM appendix B): model FLOPs per step — the
XLA cost model's count for the compiled train step, which
profiling/flops_profiler.py reads for free off the cached executable —
divided by (step wall time × hardware peak FLOPs). Goodput (MegaScale §3)
further discounts steps whose work was THROWN AWAY: optimizer updates the
divergence sentinel skipped and steps rewound to a checkpoint — the
difference between "the chips were busy" and "training advanced".

Pure-host arithmetic, no jax imports; peak-FLOPs lookup probes the device
at call time only (import-time probes are lint-banned).
"""
from __future__ import annotations

from ..utils.logging import logger

#: dense bf16 peak TFLOPs per chip, by device_kind substring (public
#: specs); the first fragment found wins, so a v5e (``"TPU v5 lite"``) is
#: told from a v5p, whose device_kind is the bare ``"TPU v5"``
PEAK_TFLOPS_BY_KIND = (
    ("v6e", 918.0), ("v6", 918.0),
    ("v5e", 197.0), ("v5 lite", 197.0), ("v5litepod", 197.0),
    ("v5", 459.0),
    ("v4", 275.0),
    ("v3", 123.0),
    ("v2", 45.0),
)


def device_peak_flops() -> float | None:
    """Per-chip peak FLOPs/s of the current backend, or None when unknown
    (CPU backends: MFU is not meaningful there)."""
    try:
        import jax

        dev = jax.devices()[0]
        kind = getattr(dev, "device_kind", "").lower()
    except Exception as e:
        logger.debug(f"peak-flops probe failed ({e!r})")
        return None
    for frag, tflops in PEAK_TFLOPS_BY_KIND:
        if frag in kind:
            return tflops * 1e12
    return None


def mfu(flops_per_step: float, step_time_s: float,
        peak_flops: float) -> float:
    """Single-step MFU in [0, ~1]."""
    if step_time_s <= 0 or peak_flops <= 0:
        return 0.0
    return flops_per_step / (step_time_s * peak_flops)


def goodput(flops_per_step: float, useful_steps: int, wall_time_s: float,
            peak_flops: float) -> float:
    """Utilization counting only steps whose work survived."""
    if wall_time_s <= 0 or peak_flops <= 0:
        return 0.0
    return flops_per_step * useful_steps / (wall_time_s * peak_flops)


class MFUTracker:
    """Running MFU/goodput over a training run.

    ``on_step(dt)`` records every executed step; ``useful=False`` marks a
    step whose update was skipped (sentinel non-finite). ``discard_steps(n)``
    retroactively un-counts n previously-useful steps — the rewind case:
    work up to the divergence is recomputed from the checkpoint, so it
    contributed wall time but no progress. By construction
    ``goodput() <= mfu()`` with equality iff nothing was wasted.
    """

    def __init__(self, peak_flops: float | None = None,
                 flops_per_step: float | None = None):
        self.peak_flops = peak_flops
        self.flops_per_step = flops_per_step
        self.total_steps = 0
        self.useful_steps = 0
        self.total_time_s = 0.0
        self.last_step_s = 0.0

    @property
    def configured(self) -> bool:
        return bool(self.peak_flops) and bool(self.flops_per_step)

    def on_step(self, step_time_s: float, useful: bool = True) -> None:
        self.total_steps += 1
        self.useful_steps += 1 if useful else 0
        self.total_time_s += max(float(step_time_s), 0.0)
        self.last_step_s = float(step_time_s)

    def discard_steps(self, n: int) -> None:
        self.useful_steps = max(0, self.useful_steps - max(int(n), 0))

    def mfu(self) -> float | None:
        if not self.configured or not self.total_steps:
            return None
        return goodput(self.flops_per_step, self.total_steps,
                       self.total_time_s, self.peak_flops)

    def goodput(self) -> float | None:
        if not self.configured or not self.total_steps:
            return None
        return goodput(self.flops_per_step, self.useful_steps,
                       self.total_time_s, self.peak_flops)
