"""Attention op dispatcher.

The role of the reference fused attention kernels
(/root/reference/csrc/transformer/*.cu softmax/attention paths and the
blocked-flash FastGen kernels): one entry point that routes to
- a Pallas flash-attention kernel on TPU (ops/pallas/flash_attention.py), or
- a reference XLA implementation (fp32 softmax, GQA, causal/decode masks)
  that compiles everywhere and is the numerics oracle for kernel tests.
"""
from __future__ import annotations

import functools
import math
from typing import Any, NamedTuple

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P


def _xla_attention(q, k, v, *, causal, positions, kv_len, mask, bias=None,
                   window=None):
    B, Sq, H, D = q.shape
    Skv, KV = k.shape[1], k.shape[2]
    scale = 1.0 / (D ** 0.5)
    if KV != H:
        rep = H // KV
        k = jnp.repeat(k, rep, axis=2)
        v = jnp.repeat(v, rep, axis=2)
    logits = jnp.einsum("bqhd,bkhd->bhqk", q, k).astype(jnp.float32) * scale

    kv_pos = jnp.arange(Skv)[None, None, None, :]  # [1,1,1,Skv]
    neg = jnp.finfo(jnp.float32).min
    if positions is not None:
        # decode/cached path: query i sits at absolute position positions[b,i]
        q_pos = positions[:, None, :, None]        # [B,1,Sq,1]
        allow = kv_pos <= q_pos
        if kv_len is not None:
            allow &= kv_pos < (kv_len if jnp.ndim(kv_len) == 0
                               else kv_len[:, None, None, None])
        if window:
            allow &= kv_pos > q_pos - window
        logits = jnp.where(allow, logits, neg)
    elif causal:
        q_pos = jnp.arange(Sq)[None, None, :, None]
        allow = kv_pos <= q_pos
        if window:       # mistral sliding window: attend the last W tokens
            allow &= kv_pos > q_pos - window
        logits = jnp.where(allow, logits, neg)
    if mask is not None:
        # mask: [B, Skv] (1 = attend) or broadcastable bool
        m = mask[:, None, None, :] if mask.ndim == 2 else mask
        logits = jnp.where(m.astype(bool), logits, neg)
    if bias is not None:
        # additive position bias (ALiBi etc.), broadcastable to [B,H,Sq,Skv]
        logits = logits + bias.astype(jnp.float32)

    weights = jax.nn.softmax(logits, axis=-1)
    out = jnp.einsum("bhqk,bkhd->bqhd", weights.astype(v.dtype), v)
    return out


class AttentionSharding(NamedTuple):
    """How the caller says q and k/v lie on a mesh at the call: the specs
    the model states for them (``models/transformer.py`` resolves its
    logical names under the engine's rules and mesh,
    ``parallel/axes.py:mesh_specs``). The output takes ``q_spec``."""
    mesh: Any
    q_spec: P
    kv_spec: P


class _Placement(NamedTuple):
    """Where ONE kernel call would run: the shapes it sees, and the
    ``shard_map`` around it (``specs`` None = call it directly)."""
    q_shape: tuple
    kv_shape: tuple
    specs: tuple | None = None      # (q_spec, kv_spec) of the shard_map
    axes: tuple = ()                # the mesh axes it maps over
    mesh: Any = None                # None: the context's (a nested map)


def _place(q, k, sharding: AttentionSharding | None, manual_axes
           ) -> tuple[str, _Placement | None]:
    """``("", placement)`` or ``(why_not, None)``: the per-shard shapes of
    a full-sequence attention call and the ``shard_map`` that gives them.

    Mesh axes that are already manual where this is traced (ZeRO++, 1-bit
    Adam, the pipeline, ``parallel/sequence.py``'s own ``shard_map``) have
    cut the shapes already and cannot be mapped again: they are dropped
    from the specs, and so is every axis of size one. What is left decides:
    batch and head axes -> one ``shard_map`` (rows and heads are
    independent: no collective inside); a sharded sequence or head width
    -> XLA attention."""
    context = jax.sharding.get_abstract_mesh()
    manual = frozenset(context.manual_axes if manual_axes is None
                       else manual_axes)
    if sharding is None and manual:
        # a caller's own shard_map (parallel/sequence.py): the shapes are
        # per shard already, of the context's mesh
        auto = [a for a in context.axis_names
                if a not in manual and context.shape[a] > 1]
        if auto:
            return (f"inside a manual region over {sorted(manual)} with "
                    f"mesh axes {auto} still automatic and no specs for "
                    f"q, k, v over them"), None
        sharding = AttentionSharding(context, P(), P())
    elif sharding is None:
        if jax.device_count() > 1:
            return (f"{jax.device_count()} devices in this process and no "
                    f"mesh or specs at the call (pallas_call has no GSPMD "
                    f"partitioning rule)"), None
        return "", _Placement(tuple(q.shape), tuple(k.shape))

    mesh, q_spec, kv_spec = sharding
    sizes = dict(mesh.shape)

    def live(spec):
        dims = [tuple(a for a in ((e,) if isinstance(e, str) else e or ())
                      if a not in manual and sizes[a] > 1) for e in spec]
        return dims + [()] * (4 - len(dims))

    (qb, qs, qh, qd), (kb, ks, kh, kd) = live(q_spec), live(kv_spec)
    if qs or ks:
        return (f"the sequence dimension is sharded over mesh axes "
                f"{qs or ks} at the call: the kernel wants whole rows "
                f"(parallel/sequence.py owns that case)"), None
    if qd or kd or qb != kb or kh not in ((), qh):
        return (f"q {q_spec} and k/v {kv_spec} do not split into whole "
                f"per-shard attention problems"), None
    n_b = math.prod(sizes[a] for a in qb)
    n_h = math.prod(sizes[a] for a in qh)
    B, _, H, _ = q.shape
    KV = k.shape[2]
    if B % n_b or H % n_h:
        return (f"batch {B} x {H} heads do not divide over mesh axes "
                f"{qb} = {n_b} x {qh} = {n_h}"), None
    if n_h > 1 and KV % n_h == 0:
        # K/V heads shard WITH the query heads (GQA leaves them whole in
        # the model's spec): shard i's H/n query heads read exactly kv
        # heads [i*KV/n, (i+1)*KV/n)
        kh = qh
    elif n_h > 1 and KV != 1:
        return (f"{KV} kv heads do not divide over mesh axes {qh} = {n_h}: "
                f"a shard's {H // n_h} query heads would need kv heads "
                f"picked by its index"), None
    else:
        kh = ()         # one kv head (MQA) serves every shard whole
    n_kh = math.prod(sizes[a] for a in kh)
    q_shape = (B // n_b, q.shape[1], H // n_h, q.shape[3])
    kv_shape = (B // n_b, k.shape[1], KV // n_kh, k.shape[3])
    # Mosaic lowers a kernel only where EVERY mesh axis is manual (size-one
    # axes too), so inside a caller's partial-manual region the rest of the
    # axes are mapped here, over the context's mesh (it knows which are
    # manual already); one device, or no axis left, needs no map
    axes = tuple(a for a in mesh.axis_names if a not in manual)
    if mesh.size == 1 or not axes:
        return "", _Placement(q_shape, kv_shape)
    return "", _Placement(
        q_shape, kv_shape,
        (P(qb or None, None, qh or None, None),
         P(qb or None, None, kh or None, None)),
        axes, None if manual else mesh)


def _formulate(q, k, v, *, causal, positions, mask, bias, impl, window,
               sharding, manual_axes=None
               ) -> tuple[str, str, _Placement | None]:
    if impl == "xla":
        return "xla", "attn_impl='xla' (config pin)", None
    if bias is not None or window:
        return "xla", ("additive bias (alibi) / sliding window have no "
                       "flash kernel path"), None
    from .pallas.flash_attention import flash_attention_unusable_reason

    why_not, place = _place(q, k, sharding, manual_axes)
    if not why_not:
        sds = jax.ShapeDtypeStruct
        why_not = flash_attention_unusable_reason(
            sds(place.q_shape, q.dtype), sds(place.kv_shape, k.dtype),
            sds(place.kv_shape, v.dtype), causal=causal,
            positions=positions, mask=mask)
        if why_not and place.q_shape != tuple(q.shape):
            why_not += " (a shard's, of " + " x ".join(
                f"{a} = {n}" for a, n in sharding.mesh.shape.items()
                if n > 1 and a in place.axes) + ")"
    return ("xla", why_not, None) if why_not else ("pallas", "", place)


def attention_formulation(q, k, v, *, causal: bool = True, positions=None,
                          mask=None, bias=None, impl: str = "auto",
                          window: int | None = None,
                          sharding: AttentionSharding | None = None,
                          manual_axes=None) -> tuple[str, str]:
    """``("pallas", "")`` when :func:`dot_product_attention` runs the
    flash kernel for these inputs, else ``("xla", why_not)``. Reads only
    shapes and dtypes, so ``jax.ShapeDtypeStruct``s serve — the training
    engine asks at build time and logs the answer, because ``auto``
    falling through to XLA is otherwise silent. ``manual_axes``: the mesh
    axes that will be manual where the call is traced (None = the ones
    that are manual here and now)."""
    return _formulate(q, k, v, causal=causal, positions=positions, mask=mask,
                      bias=bias, impl=impl, window=window, sharding=sharding,
                      manual_axes=manual_axes)[:2]


def attention_flash_plan(q, k, v, *, causal: bool = True, positions=None,
                         mask=None, bias=None, impl: str = "auto",
                         window: int | None = None,
                         sharding: AttentionSharding | None = None,
                         manual_axes=None):
    """The ``FlashPlan`` (``ops/pallas/flash_attention.py``: blocks, compute
    tile, backward form, tiles computed) of the ONE kernel call
    :func:`dot_product_attention` makes for these inputs — a shard's shapes
    under a mesh, and the launcher's own plan, since the launcher makes it
    from the same shapes. None where XLA attention runs. Takes what
    :func:`attention_formulation` takes."""
    chosen, _, place = _formulate(
        q, k, v, causal=causal, positions=positions, mask=mask, bias=bias,
        impl=impl, window=window, sharding=sharding, manual_axes=manual_axes)
    if chosen != "pallas":
        return None
    from .pallas.flash_attention import flash_plan

    return flash_plan(place.q_shape, place.kv_shape, q.dtype, causal)


def dot_product_attention(q, k, v, *, causal: bool = True, positions=None,
                          kv_len=None, mask=None, bias=None, impl: str = "auto",
                          window: int | None = None,
                          sharding: AttentionSharding | None = None):
    """q: [B,Sq,H,D]; k/v: [B,Skv,KV,D] (KV divides H for GQA).
    ``window``: sliding-window attention — query p attends keys in
    (p - window, p] (mistral; reference inference/v2 mistral impl).

    ``sharding``: how q, k, v lie on the mesh where a GSPMD-sharded model
    makes this call. ``pallas_call`` has no GSPMD partitioning rule, so
    with more than one device in play the flash kernel runs PER SHARD,
    inside a ``shard_map`` over the batch and head axes of those specs,
    wherever its gate passes the per-shard shapes (:func:`_place`); a
    caller already inside its own ``shard_map`` over every axis
    (parallel/sequence.py) needs to say nothing. XLA attention runs
    otherwise — ``attention_formulation`` names the reason.
    """
    if window and positions is None and not causal:
        raise ValueError("sliding_window requires causal attention "
                         "(bidirectional windows are not a thing here)")
    chosen, why_not, place = _formulate(
        q, k, v, causal=causal, positions=positions, mask=mask, bias=bias,
        impl=impl, window=window, sharding=sharding)
    if chosen == "pallas":
        from .pallas.flash_attention import flash_attention

        kernel = functools.partial(flash_attention, causal=causal)
        if place.specs is None:
            return kernel(q, k, v)
        q_spec, kv_spec = place.specs
        over = {"axis_names": frozenset(place.axes)} if place.mesh is None \
            else {"mesh": place.mesh}
        return jax.shard_map(
            kernel, in_specs=(q_spec, kv_spec, kv_spec), out_specs=q_spec,
            check_vma=False, **over)(q, k, v)
    if impl == "pallas":
        raise ValueError(f"pallas flash attention not usable for these "
                         f"inputs: {why_not}")
    return _xla_attention(q, k, v, causal=causal, positions=positions,
                          kv_len=kv_len, mask=mask, bias=bias, window=window)
