"""``chip_smoke.py`` among the tests: it must refuse a host without a
chip in seconds (fast tier), its whole flow must run on the CPU at tiny
sizes with the same parent and children (slow tier — it compiles two
engines), and the compile-cache helper it shares with the benchmark and
the replica worker must place the cache where the contract says.
"""
from __future__ import annotations

import json
import os
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SMOKE = os.path.join(ROOT, "chip_smoke.py")


def _run_smoke(*args, timeout):
    env = {k: v for k, v in os.environ.items()
           if k != "JAX_COMPILATION_CACHE_DIR"}
    env["JAX_PLATFORMS"] = "cpu"            # a host without a chip
    p = subprocess.run([sys.executable, SMOKE, *args], env=env, cwd=ROOT,
                       capture_output=True, text=True, timeout=timeout)
    last = p.stdout.strip().splitlines()[-1]
    return p, json.loads(last)


@pytest.mark.multiprocess
def test_chip_smoke_refuses_a_host_without_a_chip():
    """Plain ``python chip_smoke.py`` where jax finds no accelerator:
    non-zero exit and ``"ok": false``, decided by the first child's device
    check — before any engine is built, and never a CPU run reported as a
    pass."""
    p, last = _run_smoke(timeout=120)
    assert p.returncode != 0
    assert last == {"ok": False, "device": None}
    assert "needs 'tpu'" in p.stdout
    assert "engine up" not in p.stdout          # nothing was built
    assert "=== phase serve" not in p.stdout    # later phases never start


@pytest.mark.slow
@pytest.mark.multiprocess
@pytest.mark.parametrize("chips", [1, 4])
def test_chip_smoke_rehearsal_on_cpu(chips):
    """``--rehearse``: the same parent, children, entry points and checks
    at tiny sizes (interpret-mode kernels; four virtual devices for
    ``--chips 4``). The last line names ``cpu`` truthfully."""
    p, last = _run_smoke("--rehearse", "--chips", str(chips), timeout=900)
    assert p.returncode == 0, p.stdout[-4000:] + p.stderr[-2000:]
    assert last == {"ok": True, "device": {"platform": "cpu", "kind": "cpu",
                                           "count": chips}}
    phases = ("train", "serve", "serve_parity") if chips == 1 \
        else ("train_sharded",)
    for ph in phases:
        assert f"=== phase {ph}: ok" in p.stdout
    if chips == 4:                  # and no one-chip phase rode along
        assert "=== phase train ===" not in p.stdout
        assert "=== phase serve" not in p.stdout


# ---- the compile-cache helper ---------------------------------------------

#: loads the helper's file directly: the package import (~3 s) is not
#: what this test is about
_PRINT_DIR = (
    "import importlib.util, sys, jax\n"
    "spec = importlib.util.spec_from_file_location('cc', sys.argv[1])\n"
    "cc = importlib.util.module_from_spec(spec); spec.loader.exec_module(cc)\n"
    "print(repr((cc.enable_compile_cache(),"
    " jax.config.jax_compilation_cache_dir)))\n")


@pytest.mark.multiprocess
def test_compile_cache_dir_is_fixed_or_placed_from_outside(tmp_path,
                                                           monkeypatch):
    """Unset, the helper yields ``<checkout>/.jax_cache`` — the same path
    from two processes started in different working directories (the
    path is part of the cache key; one that moves never hits). With
    ``JAX_COMPILATION_CACHE_DIR`` set, jax reads it itself and the helper
    leaves ``jax_compilation_cache_dir`` alone."""
    import jax

    from deepspeed_tpu.utils.compile_cache import enable_compile_cache

    env = {k: v for k, v in os.environ.items()
           if k != "JAX_COMPILATION_CACHE_DIR"}
    env["JAX_PLATFORMS"] = "cpu"
    helper = os.path.join(ROOT, "deepspeed_tpu", "utils", "compile_cache.py")
    procs = [subprocess.Popen([sys.executable, "-c", _PRINT_DIR, helper],
                              env=env, cwd=cwd, stdout=subprocess.PIPE,
                              text=True)
             for cwd in (str(tmp_path), ROOT)]
    outs = [p.communicate(timeout=120)[0] for p in procs]
    assert all(p.returncode == 0 for p in procs)
    want = os.path.join(ROOT, ".jax_cache")
    assert [o.strip().splitlines()[-1] for o in outs] \
        == [repr((want, want))] * 2

    placed = str(tmp_path / "placed_cache")
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", placed)
    before = jax.config.jax_compilation_cache_dir
    assert enable_compile_cache() == placed
    assert jax.config.jax_compilation_cache_dir == before
