"""Profiler range annotations — the NVTX analogue.

Reference: deepspeed/utils/nvtx.py ``instrument_w_nvtx`` (wraps functions in
``get_accelerator().range_push/pop`` so kernels group under named ranges in
nsight). The TPU equivalent is a ``jax.profiler.TraceAnnotation`` (host
span) + ``jax.named_scope`` (names carried into the compiled HLO, visible
in XProf/xplane traces).
"""
from __future__ import annotations

import functools

import jax

#: Every scope the jitted programs give their own work, by name. A scope is
#: metadata (``op_name`` in the compiled HLO): nothing runs at step time.
#: ``profiling/trace.py`` joins these with a device trace;
#: ``tests/test_device_scopes.py`` holds the tuple to the compiled programs
#: in both directions (every name is used, no other name is).
DEVICE_SCOPES = (
    # serving forward (inference/engine_v2.py)
    "embed", "weight_walk", "norm", "attn_qkv", "kv_stage", "attn_core",
    "attn_out", "ffn", "head", "sample", "kv_commit",
    # inside ``attn_core``, in a model of window AND full layers: which kind
    # of layer the attention core belongs to (one KV cache a kind)
    "attn_full", "attn_window",
    # a routed-expert layer (moe/layer.py dropless_dispatch_combine and the
    # serving forward's router), in place of ``ffn``: the router matmul and
    # top-k; sort and gather into the tile-aligned buffer; the grouped
    # GEMMs and the activation; gather back and the gate-weighted sum
    "moe_router", "moe_dispatch", "moe_experts", "moe_combine",
    # a "conv" layer's operator (models/transformer.py ``conv_mix``: the
    # projections, the gates and the taps) in place of the five attention
    # scopes, and the one write of a program's records (per-slot state that
    # is not pages: inference/forward.py ``merge_records``)
    "conv_mix", "state_commit",
    # latent attention absorbed (inference/forward.py ``latent_qkv``): the
    # down-projection to ``[c | k_r]``, its norm and rope, the keys'
    # up-projection folded into the query, and the values' up-projection of
    # the attended latents — what the latent page costs beside the kernel
    "latent_absorb",
    # training (models/transformer.py, models/loss.py, runtime/engine.py)
    "head_loss", "optimizer", "grad_check", "zero_gather", "zero_reduce",
)
#: flax's own module names in the training step (``layer_N`` folds to
#: ``layer``), which the scope readers take as they are
MODULE_SCOPES = ("layer", "attn", "ffn", "moe", "ln_attn", "ln_ffn",
                 "ln_final", "ln_embed")


def device_scope(name: str):
    """``jax.named_scope`` for one of :data:`DEVICE_SCOPES` — for use INSIDE
    a jitted function, where a host span would only time the tracing."""
    if name not in DEVICE_SCOPES:
        raise ValueError(f"{name!r} is not declared in DEVICE_SCOPES")
    return jax.named_scope(name)


def instrument_w_nvtx(fn=None, *, name: str | None = None):
    """Decorator: run ``fn`` under a named profiler range. Usable bare
    (``@instrument_w_nvtx``) or with a custom name."""
    def wrap(f):
        label = name or getattr(f, "__qualname__", getattr(f, "__name__", "fn"))

        @functools.wraps(f)
        def inner(*args, **kwargs):
            with jax.profiler.TraceAnnotation(label), jax.named_scope(label):
                return f(*args, **kwargs)

        return inner

    return wrap(fn) if fn is not None else wrap


class range_push:
    """Context-manager form (reference range_push/range_pop pairs)."""

    def __init__(self, name: str):
        self._ann = jax.profiler.TraceAnnotation(name)
        self._scope = jax.named_scope(name)

    def __enter__(self):
        self._ann.__enter__()
        self._scope.__enter__()
        return self

    def __exit__(self, *exc):
        self._scope.__exit__(*exc)
        self._ann.__exit__(*exc)
        return False
