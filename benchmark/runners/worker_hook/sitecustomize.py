"""Observer inside the serving worker, loaded by Python's ``site`` because
this directory is on the worker's ``PYTHONPATH`` — and only then, and only
where ``DS_BENCH_WORKER_STATUS`` names a file.

The router's worker (``python -m deepspeed_tpu.serving.replica``) is the
only process on the chip, the program gives it no way to say how much
device memory it used or whether it compiled anything, and the benchmark
may not edit the program. So a daemon thread here WATCHES and never acts:
once the worker itself has initialised its JAX backend it writes, four
times a second, what ``jax`` reports — device platform, kind and count, the
peak bytes in use on the fullest device, and every backend compile (or
read-back from the persistent cache) with the monotonic time it ended.
The benchmark reads that file; the worker's own code runs unchanged.

The one action: a worker whose backend is not the platform or the device
count this run asked for is stopped at once (exit 91), so that a run
without a chip fails in seconds instead of serving from a CPU.
"""
import json
import os
import sys
import threading
import time

_STATUS = os.environ.get("DS_BENCH_WORKER_STATUS")


def _watch() -> None:
    compiles: list = []
    listening = False
    device = None
    while True:
        time.sleep(0.25)
        jax = sys.modules.get("jax")
        if jax is None:
            continue
        try:
            if not listening:
                import jax.monitoring as mon

                def on_duration(event, secs, **_):
                    if event == "/jax/core/compile/backend_compile_duration":
                        compiles.append([time.monotonic(), secs])

                mon.register_event_duration_secs_listener(on_duration)
                listening = True
            from jax._src import xla_bridge

            if not xla_bridge.backends_are_initialized():
                continue                 # never initialise it from here
            devs = jax.local_devices()
            if device is None:
                device = {"platform": str(devs[0].platform),
                          "kind": str(devs[0].device_kind),
                          "count": len(jax.devices())}
            status = {"device": device, "pid": os.getpid(),
                      "t": time.monotonic(),
                      "memory_peak_bytes": max(
                          (d.memory_stats() or {}).get("peak_bytes_in_use", 0)
                          for d in devs),
                      "compiles": list(compiles)}
            want_p = os.environ.get("DS_BENCH_WANT_PLATFORM")
            want_n = os.environ.get("DS_BENCH_WANT_CHIPS")
            if (want_p and device["platform"] != want_p) or \
                    (want_n and device["count"] != int(want_n)):
                status["error"] = (f"worker runs on {device}, this run "
                                   f"needs {want_p} x {want_n}")
            tmp = _STATUS + ".tmp"
            with open(tmp, "w", encoding="utf-8") as f:
                json.dump(status, f)
            os.replace(tmp, _STATUS)
            if "error" in status:
                os._exit(91)
        except Exception as e:  # noqa: BLE001 — an observer never breaks its host
            sys.stderr.write(f"bench worker_hook: {e!r}\n")


if _STATUS:
    threading.Thread(target=_watch, name="bench-worker-hook",
                     daemon=True).start()
