#!/usr/bin/env python3
"""Records the small device trace kept in ``tests/data/`` — run by hand on
the chip (``chiprun -- python3 benchmark/tests/record_trace_fixture.py``),
never by a test. Two small jitted programs, three runs each, with a gap the
host spends in a named span; the expected numbers are written beside the
trace from the host's own clock so the reducer's test has an independent
bound to hold it to."""
import glob
import json
import os
import shutil
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))


def main() -> int:
    import jax
    import jax.numpy as jnp

    from benchmark import reduce_trace

    out = os.path.join("chiprun_out", "benchmark", "fixture")
    shutil.rmtree(out, ignore_errors=True)
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"needs a TPU, found {dev.platform}")
        return 1

    @jax.jit
    def mm_step(x):
        return jnp.tanh(x @ x) * 0.5

    @jax.jit
    def reduce_step(x):
        return jnp.sum(x * x, axis=0)

    x = jnp.ones((1024, 1024), jnp.bfloat16)
    jax.block_until_ready((mm_step(x), reduce_step(x)))
    reduce_trace.start(out)
    t0 = time.monotonic()
    with jax.profiler.TraceAnnotation(reduce_trace.WINDOW_SPAN):
        for _ in range(3):
            with jax.profiler.TraceAnnotation("dispatch"):
                y = mm_step(x)
                z = reduce_step(y)
            jax.block_until_ready(z)
            with jax.profiler.TraceAnnotation("plan"):
                time.sleep(0.004)
    wall = time.monotonic() - t0
    summary = reduce_trace.stop_and_summarize(out)
    src = glob.glob(os.path.join(out, "plugins", "profile", "*", "*.xplane.pb"))[0]
    shutil.copy(src, os.path.join(out, "tpu_v5e_small.xplane.pb"))
    with open(os.path.join(out, "tpu_v5e_small.expected.json"), "w") as f:
        json.dump({"host_wall_s": wall, "runs_each": 3,
                   "programs": ["jit_mm_step", "jit_reduce_step"],
                   "sleep_span": "plan", "summary": summary}, f, indent=1)
    print(json.dumps(summary)[:3000])
    print("bytes:", os.path.getsize(src))
    return 0


if __name__ == "__main__":
    sys.exit(main())
