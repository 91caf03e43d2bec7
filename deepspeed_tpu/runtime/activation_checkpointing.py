"""Activation checkpointing (rematerialization).

TPU-native re-design of
/root/reference/deepspeed/runtime/activation_checkpointing/checkpointing.py:
- ``CheckpointFunction`` (:486) — a hand-rolled autograd.Function that stashes
  (optionally partitioned/CPU-moved) inputs and replays forward in backward,
  with a CUDA RNG fork tracker (:124) so dropout replays identically.
- partitioned activations (:375) — each model-parallel rank keeps 1/mp of the
  stashed activation, all-gathered back before replay.

Under JAX every piece collapses into ``jax.checkpoint``:
- replay-in-backward is the transform itself; there is no tape to manage.
- RNG forking is unnecessary — dropout keys are explicit function inputs, so
  the recomputation is bit-identical by construction.
- *what* to stash is a checkpoint **policy** (save nothing / save matmul
  outputs / offload residuals to host), strictly more general than the
  reference's all-or-nothing stash. The registry lives in ops/remat.py.
- partitioned activations = sharding the residual stream over the ``seq``
  axis between layers, which the model zoo already does via logical
  constraints; the engine warns if the flag is set without a seq axis.
- CPU checkpointing (:472) = the ``offload`` policy: saved residuals live in
  pinned host memory (``offload_src='device', offload_dst='pinned_host'``)
  and XLA schedules the D2H/H2D copies asynchronously.

What ``remat=True`` keeps by default: ``ModelConfig.remat_policy="auto"``.
The model tags its matmul products (``ops/remat.py:ATTN_PRODUCTS`` /
``FFN_PRODUCTS``) and the training engine walks ``REMAT_LADDER`` —
``save_matmul_products``, ``save_attn_products``, ``nothing_saveable`` —
keeping the first rung whose COMPILED train step fits the device
(:func:`step_memory` against :func:`device_memory_limit` less
:data:`STEP_HEADROOM_BYTES`). Any other policy name, on the model or as
``activation_checkpointing.policy``, pins that policy and is never judged.
The decision is ``engine.remat_plan`` (and one ``remat:`` log line).

API parity: ``configure(config)`` + module-level ``checkpoint(fn, *args)``
mirror the reference's Megatron-style entry points (checkpointing.py:893,
:486); the policy-based API is the native surface.
"""
from __future__ import annotations

from typing import Callable

from ..config import ActivationCheckpointingConfig, Config, _take
from ..ops.remat import (  # noqa: F401  (re-exported native surface)
    POLICIES,
    REMAT_LADDER,
    checkpoint_fn,
    make_policy,
    remat_module,
)

# --------------------------------------------------------------------------
# What the training engine judges a rung of REMAT_LADDER by
# (runtime/engine.py:_build_judged_programs).
# --------------------------------------------------------------------------
#: what a compiled step must leave free under the device's limit: 1 GiB.
#: Measured on a v5e (PERF.md section 6, PR 32, call 1; the 8-layer Mistral
#: step under each rung): while a step runs the device holds its arguments
#: and code in ``bytes_in_use`` and its temporaries in ``bytes_reserved``,
#: together ``step_bytes`` to within 1 MB (11.146 GB read, 11.145 reckoned,
#: at the first rung; 0.17-0.19 GB UNDER it at the other two), so the
#: analysis itself needs no allowance. What the process held beside one
#: step was other executables' code, 0.21-0.25 GB each at that size (an
#: eval step, the imperative triplet's grad and apply programs, a refused
#: rung's executable until it is collected: four of them are 1 GB), and
#: batches of kilobytes.
STEP_HEADROOM_BYTES = 1 << 30


def device_memory_limit() -> int | None:
    """``bytes_limit`` of this process's first device; None where the
    backend reports none (the CPU)."""
    import jax

    stats = getattr(jax.local_devices()[0], "memory_stats", lambda: None)()
    return (stats or {}).get("bytes_limit")


def step_memory(compiled) -> dict:
    """A compiled step's bytes a device by ``memory_analysis()``:
    arguments, temporaries and generated code, and their sum with the
    outputs that alias no argument (a donated state's do)."""
    ma = compiled.memory_analysis()
    out = {"argument_bytes": int(ma.argument_size_in_bytes),
           "temp_bytes": int(ma.temp_size_in_bytes),
           "code_bytes": int(ma.generated_code_size_in_bytes),
           "unaliased_output_bytes": int(ma.output_size_in_bytes
                                         - ma.alias_size_in_bytes)}
    out["step_bytes"] = sum(out.values())
    return out

# --------------------------------------------------------------------------
# Megatron-style module-level API (reference checkpointing.py:893 configure,
# :486 checkpoint) for drop-in porting of reference training scripts.
# --------------------------------------------------------------------------
_configured = ActivationCheckpointingConfig()


def configure(config: Config | ActivationCheckpointingConfig | dict | None = None,
              **kwargs) -> None:
    """Set the module-level checkpointing behavior from a DeepSpeed-style
    config section (accepts the whole Config, the section dict — unknown /
    GPU-specific keys filtered like any config section — or kwargs)."""
    global _configured
    if isinstance(config, Config):
        _configured = config.activation_checkpointing
    elif isinstance(config, ActivationCheckpointingConfig):
        _configured = config
    elif isinstance(config, dict):
        _configured = _take(dict(config), ActivationCheckpointingConfig,
                            "activation_checkpointing")
    if kwargs:
        import dataclasses

        _configured = dataclasses.replace(_configured, **kwargs)


def is_configured() -> bool:
    return _configured.policy != "none"


def checkpoint(function: Callable, *args):
    """Reference-parity call shape: run ``function(*args)`` under the
    configured remat policy (checkpointing.py:486 ``CheckpointFunction``).
    Must be called inside a traced (grad/jit) context to have effect."""
    policy = _configured.policy if _configured.policy != "none" else "full"
    return checkpoint_fn(function, policy=policy)(*args)
