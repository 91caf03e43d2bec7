"""Where JAX's persistent compilation cache lives.

A cold start compiles every program (a gpt2-350m train step takes over a
minute), and a machine that runs one command and is thrown away keeps
nothing unless the cache sits where the next process looks. The cache
directory is part of the cache's key, so it must not move between runs:
it is either the place ``JAX_COMPILATION_CACHE_DIR`` names — JAX reads
that variable itself, and then no code here sets another — or a fixed
path derived from where this package is checked out, never from
``tempfile``, a pid or the clock.

Entry points call :func:`enable_compile_cache` before their first
compile: ``chip_smoke.py``'s children, the runners of ``benchmark/`` and the replica
worker's ``main``. The launcher hands its children the same directory
through their environment (:func:`default_cache_dir`).
"""
from __future__ import annotations

import os

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"


def default_cache_dir() -> str:
    """``<checkout>/.jax_cache``: beside the package, the same path from
    any working directory."""
    checkout = os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    return os.path.join(checkout, ".jax_cache")


def enable_compile_cache() -> str:
    """Turn the persistent compilation cache on and return its directory.
    Touches no device: safe before (and required before) the first
    compile."""
    placed = os.environ.get(ENV_VAR)
    if placed:
        return placed
    import jax

    path = default_cache_dir()
    jax.config.update("jax_compilation_cache_dir", path)
    return path
