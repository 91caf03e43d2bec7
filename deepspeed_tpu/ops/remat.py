"""Rematerialization policy registry (neutral layer: used by both the model
zoo and the runtime's activation-checkpointing API — see
runtime/activation_checkpointing.py for the DeepSpeed-parity surface and the
mapping to the reference's CheckpointFunction)."""
from __future__ import annotations

import functools
from typing import Any, Callable

import jax

from ..utils.logging import logger

_cp = jax.checkpoint_policies

#: what models/transformer.py tags with ``checkpoint_name`` because it is
#: dear to make again and cheap to hold: a matmul's product, one hidden- or
#: FFN-wide row a token. Attention's q, k, v are tagged as they enter
#: ``dot_product_attention`` (after bias, q/k norm and rope) with the output
#: projection's result; the dense FFN's gate and up products (the up
#: product alone in a two-matrix FFN). The flash kernel's own ``(out, lse)``
#: and a routed expert's products carry no tag: they are made again.
ATTN_PRODUCTS = ("attn_q", "attn_k", "attn_v", "attn_proj")
FFN_PRODUCTS = ("ffn_gate", "ffn_up")

#: name → jax.checkpoint policy ("full" remat saves nothing; "none" disables)
POLICIES: dict[str, Any] = {
    "none": None,
    "full": _cp.nothing_saveable,
    "nothing_saveable": _cp.nothing_saveable,
    "dots_saveable": _cp.dots_saveable,
    "checkpoint_dots": _cp.dots_saveable,
    "dots_with_no_batch_dims_saveable": _cp.dots_with_no_batch_dims_saveable,
    "checkpoint_dots_with_no_batch_dims": _cp.dots_with_no_batch_dims_saveable,
    "everything_saveable": _cp.everything_saveable,
    # names policies, not dots_saveable: that one also keeps the down
    # projection's output (the next block's saved input once more), the
    # router's float32 einsum and an MoE block's capacity einsums
    "save_matmul_products": _cp.save_only_these_names(*ATTN_PRODUCTS,
                                                      *FFN_PRODUCTS),
    "save_attn_products": _cp.save_only_these_names(*ATTN_PRODUCTS),
}

#: ``remat_policy="auto"`` (the default): the rungs a rematted block's
#: saved set steps down, dearest first. The training engine judges each
#: against the compiled step's memory (runtime/engine.py ``remat_plan``); a
#: model traced outside an engine has no step to judge and takes the first.
AUTO = "auto"
REMAT_LADDER = ("save_matmul_products", "save_attn_products",
                "nothing_saveable")


def make_policy(name: str):
    """Resolve a policy name to a ``jax.checkpoint`` policy.

    ``cpu`` / ``offload`` implement the reference's ``cpu_checkpointing``
    (checkpointing.py:472): matmul outputs are kept on device, everything
    else saved is offloaded to pinned host memory instead of recomputed.
    """
    if name == AUTO:
        name = REMAT_LADDER[0]
    if name in POLICIES:
        return POLICIES[name]
    if name in ("cpu", "offload", "offload_dots"):
        return _offload_policy()
    raise ValueError(f"unknown activation checkpointing policy '{name}'; "
                     f"one of {sorted(POLICIES)}, 'auto' or 'offload'")


@functools.cache
def _offload_policy():
    """Constructing the offload policy always succeeds; whether the backend
    supports pinned_host offload only surfaces at compile time. Probe once
    per process with a tiny checkpointed grad so a missing memory space
    degrades to dots_saveable here instead of failing inside the user's
    train step (make_policy is called on every model trace — the cache keeps
    the probe off the hot path)."""
    pol = _cp.offload_dot_with_no_batch_dims("device", "pinned_host")
    try:
        import jax.numpy as jnp

        f = jax.checkpoint(lambda x: jnp.sin(x @ x), policy=pol)
        jax.jit(jax.grad(lambda x: f(x).sum())).lower(
            jax.ShapeDtypeStruct((4, 4), jnp.float32)).compile()
        return pol
    except Exception:  # backend without host-offload support
        logger.warning("activation offload policy unavailable on this "
                       "backend; falling back to dots_saveable")
        return _cp.dots_saveable


def checkpoint_fn(fn: Callable, policy: str = "full",
                  prevent_cse: bool = True, static_argnums=()) -> Callable:
    """Wrap ``fn`` so its intermediates are rematerialized in backward."""
    pol = make_policy(policy)
    if pol is None and policy == "none":
        return fn
    return jax.checkpoint(fn, policy=pol, prevent_cse=prevent_cse,
                          static_argnums=static_argnums)


def remat_module(module_cls, policy: str = "full", static_argnums=()):
    """nn.remat a flax module class with the named policy (the per-block
    wrapping the reference applies per transformer layer)."""
    import flax.linen as nn

    pol = make_policy(policy)
    if pol is None:
        return module_cls
    return nn.remat(module_cls, policy=pol, prevent_cse=True,
                    static_argnums=static_argnums)
