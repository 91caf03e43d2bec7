#!/usr/bin/env python3
"""benchmark/run.py — one cell, once.

    python benchmark/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

The parent is stdlib only and never touches JAX: a chip belongs to one
process at a time. It looks the cell up in ``BENCHMARK.json``, starts the
runner the cell's configuration names (``runners/<mode>.py``) as a child in
a session of its own, relays the child's lines, and prints as the LAST line
of stdout the one JSON object the contract asks for: ``correct``,
``attempted``, ``failed``, ``metrics``, ``device`` and, when traced,
``breakdown``. No result line and a non-zero exit when the child found no
TPU, the wrong device count, or failed in any other way.

``--rehearse`` is the only CPU path (tiny sizes from each file's
``rehearse`` section, virtual devices); its last line names ``cpu``.
"""
from __future__ import annotations

import argparse
import json
import os
import signal
import subprocess
import sys
import threading
import time

T0 = time.monotonic()
HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
MARK = "BENCH_RESULT "
#: JAX's persistent compilation cache: inside the checkout, at a fixed path
#: (the path is part of the cache's key). The program's own default
#: coincides, and where it reads the variable it takes this one
CACHE_DIR = os.path.join(ROOT, ".jax_cache")
#: the contract: a run ends within 360 s, a cell's first (compiling) run in
#: a checkout within 1200 s — the child is killed before the larger one
BUDGET_S = 1150.0


def load_cell(workload: str) -> tuple[dict, dict, dict, dict]:
    """(manifest entry, cell file, configuration file, traffic file) — every
    file is found by the names ``BENCHMARK.json`` gives."""
    with open(os.path.join(ROOT, "BENCHMARK.json"), encoding="utf-8") as f:
        manifest = json.load(f)
    entry = next((w for w in manifest["workloads"]
                  if w["name"] == workload), None)
    if entry is None:
        raise SystemExit(f"no workload {workload!r} in BENCHMARK.json "
                         f"(have {[w['name'] for w in manifest['workloads']]})")
    conf = next(c for c in manifest["configs"] if c["name"] == entry["config"])

    def read(path):
        with open(os.path.join(ROOT, path), encoding="utf-8") as f:
            return json.load(f)

    cell = read(f"benchmark/workloads/{workload}.json")
    traffic = read(f"benchmark/traffic/{entry['traffic']}.json")
    config = read(conf["file"])
    for k in ("config", "traffic", "chips"):
        if cell.get(k, entry[k]) != entry[k]:
            raise SystemExit(f"{workload}: cell file and BENCHMARK.json "
                             f"disagree on {k!r}")
    names = {"end_to_end": [], "per_layer": []}
    for kind in names:
        for m in manifest[kind]:
            if workload in m.get("workloads", [workload]):
                names[kind].append(m)
    return ({**entry, "metrics": names,
             "run_seconds": manifest["run_seconds"]}, cell, config, traffic)


def heal_cache_dir(path: str) -> None:
    """JAX's cache, where a size limit is set (``jax_compilation_cache_
    max_size``), keeps a ``<key>-atime`` file beside every ``<key>-cache``
    and REFUSES EVERY WRITE when one is missing (``_evict_if_needed`` stats
    them all). A cache directory restored without its atime files — the
    chip machine of PR 22 did that between calls — then never caches again:
    every run compiled everything. Give such entries their atime back."""
    try:
        names = set(os.listdir(path))
    except OSError:
        return
    for n in names:
        if n.endswith("-cache") and n[:-len("cache")] + "atime" not in names:
            with open(os.path.join(path, n[:-len("cache")] + "atime"),
                      "wb") as f:
                f.write(time.time_ns().to_bytes(8, "little"))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--seconds", type=float, default=None)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearse", action="store_true",
                    help="tiny sizes on the CPU; the last line says cpu")
    args = ap.parse_args(argv)
    entry, cell, config, traffic = load_cell(args.workload)
    seconds = args.seconds if args.seconds is not None \
        else float(entry["run_seconds"])
    mode = config["mode"]
    runner = os.path.join(HERE, "runners", f"{mode}.py")
    if not os.path.exists(runner):
        raise SystemExit(f"configuration mode {mode!r} has no runner")

    env = dict(os.environ)
    env["JAX_COMPILATION_CACHE_DIR"] = CACHE_DIR
    heal_cache_dir(CACHE_DIR)
    env["PYTHONPATH"] = ROOT + (os.pathsep + env["PYTHONPATH"]
                                if env.get("PYTHONPATH") else "")
    env["DS_BENCH_T0"] = repr(T0)
    env.setdefault("TPU_LOG_DIR", "disabled")
    if args.rehearse:
        env["JAX_PLATFORMS"] = "cpu"
        env["XLA_FLAGS"] = (env.get("XLA_FLAGS", "") + " --xla_force_host_"
                            f"platform_device_count={entry['chips']}").strip()
    cmd = [sys.executable, "-u", runner, "--workload", args.workload,
           "--seed", str(args.seed), "--seconds", repr(seconds),
           "--trace", str(args.trace)] + (["--rehearse"] if args.rehearse
                                          else [])
    result = None
    proc = subprocess.Popen(cmd, stdout=subprocess.PIPE, text=True, cwd=ROOT,
                            env=env, start_new_session=True)
    timer = threading.Timer(BUDGET_S,
                            lambda: os.killpg(proc.pid, signal.SIGKILL))
    timer.daemon = True
    timer.start()
    try:
        for line in proc.stdout:
            if line.startswith(MARK):
                result = json.loads(line[len(MARK):])
            else:
                print(line.rstrip(), flush=True)
        rc = proc.wait()
    finally:
        timer.cancel()
        try:
            os.killpg(proc.pid, signal.SIGKILL)     # stragglers, if any
        except ProcessLookupError:
            pass
        proc.wait()
    if rc != 0 or result is None:
        print(f"benchmark: runner exited {rc} "
              f"{'without a result' if result is None else ''}",
              file=sys.stderr, flush=True)
        return rc or 1
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
