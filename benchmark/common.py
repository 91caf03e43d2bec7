"""What both runners share: the device check, compile and memory counters,
the result line. Helpers copied from ``chip_smoke.py`` (``require_device``,
``CompileClock``, ``compiled_train_step``) live here because the yardstick
may not import a file later PRs may edit."""
from __future__ import annotations

import argparse
import importlib
import json
import math
import os
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark.run import CACHE_DIR, MARK, load_cell  # noqa: E402,F401  (stdlib only)

#: process start of ``run.py`` on the monotonic clock (system-wide on Linux)
T0 = float(os.environ.get("DS_BENCH_T0") or time.monotonic())
OUT_DIR = os.path.join(ROOT, "chiprun_out", "benchmark")


class BenchFailure(RuntimeError):
    """The run cannot produce a result (wrong device, program failure)."""


def runner_args(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, default=0)
    ap.add_argument("--rehearse", action="store_true")
    return ap.parse_args(argv)


def say(msg: str) -> None:
    print(f"[bench +{time.monotonic() - T0:7.2f}s] {msg}", flush=True)


def pick(section: dict, rehearse: bool) -> dict:
    """A file's values, with its ``rehearse`` section laid over them on the
    CPU path (tiny sizes; never read on the chip)."""
    out = {k: v for k, v in section.items() if k != "rehearse"}
    if rehearse:
        out.update(section.get("rehearse") or {})
    return out


def check_device(dev: dict, chips: int, rehearse: bool) -> dict:
    want = "cpu" if rehearse else "tpu"
    if dev["platform"] != want:
        raise BenchFailure(f"jax runs on {dev['platform']!r}, this run needs "
                           f"{want!r}")
    if dev["count"] != chips:
        raise BenchFailure(f"{dev['count']} device(s) visible, this cell "
                           f"needs {chips}")
    return dev


def require_device(chips: int, rehearse: bool) -> dict:
    """FIRST thing a process on the device does: name the device JAX gives
    it and refuse the wrong one — a CPU run is never a result."""
    import jax

    devs = jax.devices()
    dev = {"platform": str(devs[0].platform),
           "kind": str(devs[0].device_kind), "count": len(devs)}
    say(f"device: {dev}")
    return check_device(dev, chips, rehearse)


def memory_peak_bytes() -> int:
    """Peak bytes in use on the fullest device of this process (0 where the
    backend does not report it: the CPU)."""
    import jax

    return max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
               for d in jax.local_devices())


class CompileClock:
    """Backend compiles (or executables read back from the persistent
    cache) seen by this process, each with the monotonic time it ended:
    ``in_window`` counts the ones inside the measured window — there must
    be none."""

    def __init__(self):
        import jax.monitoring as mon

        self.events: list[tuple[float, float]] = []
        self.cache_hits = 0
        mon.register_event_duration_secs_listener(self._on_duration)
        mon.register_event_listener(self._on_event)

    def _on_duration(self, event: str, secs: float, **_) -> None:
        if event == "/jax/core/compile/backend_compile_duration":
            self.events.append((time.monotonic(), secs))

    def _on_event(self, event: str, **_) -> None:
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1

    def in_window(self, t0: float, t1: float) -> int:
        return sum(1 for t, _ in self.events if t0 <= t <= t1)

    def report(self) -> dict:
        return {"compiles": len(self.events),
                "compile_s": round(sum(s for _, s in self.events), 2),
                "compile_cache_hits": self.cache_hits}


def read_layers(entry: dict, ctx: dict) -> dict:
    """Every per-layer metric of this cell through its own reader
    (``layers/<name>.py``: ``read(ctx) -> number | None``). A reader that
    finds nothing to read returns None and the metric is left out."""
    out = {}
    for m in entry["metrics"]["per_layer"]:
        mod = importlib.import_module(f"benchmark.layers.{m['name']}")
        try:
            v = mod.read(ctx)
        except (KeyError, ZeroDivisionError, TypeError) as e:
            say(f"layer metric {m['name']}: nothing to read ({e!r})")
            v = None
        if v is not None and math.isfinite(v):
            out[m["name"]] = {"value": float(v), "unit": m["unit"]}
    return out


def emit(entry: dict, trace: int, *, correct: bool, attempted: int,
         failed: int, values: dict, device: dict,
         breakdown: dict | None = None) -> None:
    """The result for ``run.py`` to print last. ``values`` holds end-to-end
    numbers by name (``--trace 0``) or finished per-layer entries."""
    if trace:
        metrics = values
    else:
        metrics = {m["name"]: {"value": float(values[m["name"]]),
                               "unit": m["unit"]}
                   for m in entry["metrics"]["end_to_end"]}
        bad = [k for k, v in metrics.items() if not math.isfinite(v["value"])]
        if bad:
            raise BenchFailure(f"no number for {bad}: too little completed "
                               f"inside the window")
    line = {"correct": bool(correct), "attempted": int(attempted),
            "failed": int(failed), "metrics": metrics, "device": device}
    if trace and breakdown:
        line["breakdown"] = breakdown
    print(MARK + json.dumps(line), flush=True)
