"""Latency-hiding tensor parallelism: ring collective-matmuls.

At TP>1 every tensor-parallel projection is otherwise a GSPMD-inserted
*blocking* collective on the critical path: the column-parallel in-proj
waits for its activation all-gather, the row-parallel out-proj finishes its
GEMM and then waits for an all-reduce/reduce-scatter. This module provides
the "collective matmul" decomposition (Wang et al., *Overlap Communication
with Dependent Computation via Decomposition*, ASPLOS'23 — the same
comm/compute pipelining idea DeepSpeed-Ulysses applies to attention):
sharded matmuls split into per-peer chunks whose ``ppermute`` transfers ride
the ICI ring while the dependent partial GEMMs run, so the compiler can
schedule step *i*'s transfer under step *i-1*'s compute.

Primitives (global-view, ``shard_map`` inside, bidirectional ring):

- :func:`allgather_matmul` — column-parallel in-proj. ``x`` arrives
  token-sharded over the ``tensor`` axis; each arriving x-shard is consumed
  into a partial dot against the local weight columns while the next shard
  is in flight. Accepts a tuple of weights so one ring feeds several
  projections (fused QKV).
- :func:`matmul_reduce_scatter` — row-parallel out-proj. Partial outputs
  are produced chunk-by-chunk and ring-accumulated toward their owner
  shard; the traveling accumulator overlaps with the next chunk's GEMM.
- :func:`ring_row_matmul` — drop-in for a row-parallel ``x @ w`` whose
  output must stay replicated (the GSPMD training model): ring
  matmul⊗reduce-scatter followed by an all-gather — half the *exposed*
  comm of the blocking all-reduce, with the GEMM hidden under the ring.

Dtype/quant awareness: weights may be plain arrays (bf16/fp32 dot with
fp32 accumulation) or per-shard-quantized ``QuantLinear`` codes — the ring
bodies route through ``quant_matmul`` (in-tile dequant / fused-XLA small-M
dispatch) rather than dequantizing whole shards per ring step.

Fallback contract: the primitives raise a clear ``ValueError`` (never an
XLA shape error) when a dim does not divide by the ``tensor`` axis size;
call sites pre-check with the same arithmetic and fall back to the plain
einsum path, bumping :data:`overlap_counters` so bench/stats can report
ring engagement vs fallback.
"""
from __future__ import annotations

import contextvars
import dataclasses
import math
import threading
from contextlib import contextmanager
from typing import Any, Sequence

import jax
import jax.numpy as jnp
from jax import lax, shard_map
from jax.sharding import PartitionSpec as P

from .. import comm
from ..ops.pallas.quant_matmul import QuantLinear, local_matmul


# ---------------------------------------------------------------------------
# Trace-time overlap accounting (the CommsLogger idiom: under jit the
# compiler owns wall time; ring structure — steps, permuted bytes, fallback
# hits — is recorded when a program traces, once per compiled program).
# ---------------------------------------------------------------------------

class OverlapCounters:
    """Process-wide ring collective-matmul counters, recorded at trace
    time. ``stats_dict`` keys surface in the engine ``stats`` dict and the
    bench artifact."""

    _KEYS = ("tp_ring_matmuls", "tp_ring_steps", "tp_bytes_permuted",
             "tp_fallbacks")

    def __init__(self):
        self._lock = threading.Lock()
        self.reset()

    def reset(self) -> None:
        with getattr(self, "_lock", threading.Lock()):
            self._c = {k: 0 for k in self._KEYS}

    def ring(self, steps: int, bytes_permuted: int) -> None:
        with self._lock:
            self._c["tp_ring_matmuls"] += 1
            self._c["tp_ring_steps"] += int(steps)
            self._c["tp_bytes_permuted"] += int(bytes_permuted)

    def fallback(self) -> None:
        with self._lock:
            self._c["tp_fallbacks"] += 1

    def snapshot(self) -> dict[str, int]:
        with self._lock:
            return dict(self._c)


overlap_counters = OverlapCounters()


# ---------------------------------------------------------------------------
# Scope: how the GSPMD training model finds the mesh (models/transformer.py
# consults this; runtime/engine.py installs it around the loss).
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class TPOverlapScope:
    """Active ring-overlap context for model code traced under GSPMD.

    ``token_specs`` names the mesh axes of the token (batch, seq) dims of
    activations at the projection sites — the engine's activation rules in
    mesh-axis form — so the ring shard_map can declare the full manual
    partitioning."""
    mesh: Any
    axis: str = "tensor"
    token_specs: tuple = (("data", "expert", "fsdp"), "seq")
    attention: bool = True
    ffn: bool = True


_SCOPE: contextvars.ContextVar[TPOverlapScope | None] = \
    contextvars.ContextVar("tp_overlap_scope", default=None)


@contextmanager
def tp_overlap_scope(mesh, *, axis: str = "tensor",
                     token_specs: tuple = (("data", "expert", "fsdp"),
                                           "seq"),
                     attention: bool = True, ffn: bool = True):
    """Enable ring collective-matmuls in model code traced inside the
    context (trace-time switch, like ``nn.logical_axis_rules``)."""
    tok = _SCOPE.set(TPOverlapScope(mesh, axis, tuple(token_specs),
                                    attention, ffn))
    try:
        yield
    finally:
        _SCOPE.reset(tok)


def current_tp_overlap() -> TPOverlapScope | None:
    return _SCOPE.get()


# ---------------------------------------------------------------------------
# Weight handling: plain arrays and per-shard-quantized QuantLinear both
# ride the same ring; only the local dot differs (ops/pallas local_matmul).
# ---------------------------------------------------------------------------

def _axis_n(mesh, axis: str) -> int:
    return int(mesh.shape[axis])


def _wspec(base: P, stacked: bool) -> P:
    return P(None, *base) if stacked else base


def _flatten_w(w, base_spec: P, stacked: bool):
    """(leaves, specs, recipe) for one weight operand. QuantLinear codes
    and scales share the sharded-dim pattern, so one prefix spec covers
    both leaves."""
    spec = _wspec(base_spec, stacked)
    if isinstance(w, QuantLinear):
        return ([w.data, w.scale], [spec, spec],
                ("q", w.bits, w.group_size, w.shape, w.dtype))
    if w.ndim != 2:
        raise ValueError(f"dense ring weights must be 2D, got {w.shape} — "
                         f"reshape the projection to [K, N] first")
    return ([w], [spec], ("d",))


def _rebuild_dots(recipes, leaves, li, stacked):
    """Per-weight local-dot closures from the flattened shard_map args."""
    dots, i = [], 0
    for r in recipes:
        if r[0] == "q":
            qw = QuantLinear(leaves[i], leaves[i + 1], r[1], r[2], r[3],
                             r[4])
            i += 2
            dots.append(lambda c, qw=qw: local_matmul(
                c, qw, layer_index=(li if stacked else None)))
        else:
            wl = leaves[i]
            i += 1
            dots.append(lambda c, wl=wl: local_matmul(c, wl))
    return dots


def _w_contract_out(w, n: int, *, sharded: str) -> tuple[int, int]:
    """(global contraction K, global output N) of one weight operand under
    ``sharded`` ∈ {'col', 'row'} over an axis of size ``n``. QuantLinear
    aux shapes are per-shard (LOCAL) by the engine's quantize-in-shard_map
    convention."""
    if isinstance(w, QuantLinear):
        K_aux, N_aux = w.shape
        return (K_aux, N_aux * n) if sharded == "col" else (K_aux * n, N_aux)
    return int(w.shape[0]), int(w.shape[1])


# ---------------------------------------------------------------------------
# Ring cores (per-shard; run inside shard_map bodies)
# ---------------------------------------------------------------------------

def _ring_ag_core(x_loc, dots, n: int, axis_name: str):
    """Bidirectional all-gather⊗matmul: x_loc [C, K] is this shard's token
    chunk; every dot consumes one arriving chunk while the next is in
    flight (the permute chain has no data dependence on the dots, so XLA
    overlaps transfer s+1 with dot s). Returns [n*C, N_j] per weight."""
    C = x_loc.shape[0]
    idx = lax.axis_index(axis_name)
    outs = [d(x_loc) for d in dots]
    ys = [lax.dynamic_update_slice(
        jnp.zeros((n * C, o.shape[1]), o.dtype), o, (idx * C, 0))
        for o in outs]
    k_up = n // 2                   # ceil((n-1)/2) hops from below …
    k_dn = n - 1 - k_up             # … the rest from above
    up = dn = x_loc
    for s in range(1, k_up + 1):
        up = comm.send_recv_next(up, axis_name)      # now holds shard idx-s
        src = (idx - s) % n
        ys = [lax.dynamic_update_slice(y, d(up), (src * C, 0))
              for y, d in zip(ys, dots)]
        if s <= k_dn:
            dn = comm.send_recv_prev(dn, axis_name)  # holds shard idx+s
            src = (idx + s) % n
            ys = [lax.dynamic_update_slice(y, d(dn), (src * C, 0))
                  for y, d in zip(ys, dots)]
    return ys


def _ring_rs_core(x_loc, dot, n: int, axis_name: str, out_dtype, *,
                  bidir: bool | None = None):
    """Bidirectional matmul⊗reduce-scatter: x_loc [M, K_loc] (every shard
    holds all M rows of its contraction slice); partial outputs for each
    destination's row chunk ring-accumulate toward their owner in fp32.
    Returns this shard's [M/n, N] chunk. The next chunk's GEMM has no
    dependence on the traveling accumulator, so it overlaps the permute.

    ``dot(rows, start)`` receives the (traced) global row offset of the
    chunk so side-table callers (the grouped MoE GEMM's tile→expert map)
    can slice their per-row metadata; plain matmuls ignore it.
    ``bidir=False`` forces the unidirectional schedule (callers whose
    side tables can't split a chunk in half)."""
    M = x_loc.shape[0]
    C = M // n
    idx = lax.axis_index(axis_name)

    def part(dest, lo, sz):
        start = dest * C + lo
        rows = lax.dynamic_slice(x_loc, (start, 0), (sz, x_loc.shape[1]))
        return dot(rows, start).astype(jnp.float32)

    if bidir is None:
        bidir = C % 2 == 0
    if not bidir or n == 1:
        acc = None
        for s in range(n):
            dest = (idx + (n - 1 - s)) % n
            p = part(dest, 0, C)
            acc = p if acc is None else acc + p
            if s != n - 1:
                acc = comm.send_recv_next(acc, axis_name)
        return acc.astype(out_dtype)
    h = C // 2
    acc_u = acc_d = None
    for s in range(n):
        pu = part((idx + (n - 1 - s)) % n, 0, h)
        pd = part((idx - (n - 1 - s)) % n, h, h)
        acc_u = pu if acc_u is None else acc_u + pu
        acc_d = pd if acc_d is None else acc_d + pd
        if s != n - 1:
            acc_u = comm.send_recv_next(acc_u, axis_name)
            acc_d = comm.send_recv_prev(acc_d, axis_name)
    return jnp.concatenate([acc_u, acc_d], axis=0).astype(out_dtype)


# ---------------------------------------------------------------------------
# Public primitives
# ---------------------------------------------------------------------------

def _li_arg(layer_index):
    return jnp.zeros((), jnp.int32) if layer_index is None \
        else jnp.asarray(layer_index, jnp.int32)


def allgather_matmul(x, w, mesh, *, axis: str = "tensor",
                     layer_index=None):
    """``<all-gather x over axis> @ w``, ring-overlapped.

    x: [M, K] with rows (M) sharded over ``axis``; w: [K, N] with output
    columns sharded over ``axis`` — a plain array, a per-shard-quantized
    ``QuantLinear``, or a tuple of those (one ring feeds several
    projections: fused QKV / GLU gate+up). Returns [M, N] column-sharded
    (tuple in → tuple out). ``layer_index`` selects a layer of stacked
    [L, ...] QuantLinear codes inside the kernel (scalar prefetch).

    Raises ``ValueError`` when M or an output dim does not divide by the
    ``axis`` size — pre-check and fall back to einsum at call sites.
    """
    # NB QuantLinear IS a NamedTuple — the multi-weight form is a plain
    # tuple/list of weights, never the pytree itself
    single = isinstance(w, QuantLinear) or not isinstance(w, (tuple, list))
    ws = (w,) if single else tuple(w)
    n = _axis_n(mesh, axis)
    if x.ndim != 2:
        raise ValueError(f"allgather_matmul expects 2D x, got {x.shape}")
    M, K = x.shape
    if n > 1 and M % n:
        raise ValueError(
            f"allgather_matmul: x rows {M} not divisible by '{axis}' axis "
            f"size {n} — pad the token dim or fall back to einsum")
    stacked = layer_index is not None
    for wi in ws:
        wK, wN = _w_contract_out(wi, n, sharded="col")
        if wK != K:
            raise ValueError(f"contract mismatch: x K={K} vs w K={wK}")
        data_cols = wi.data.shape[-1] if isinstance(wi, QuantLinear) \
            else wi.shape[1]
        if n > 1 and data_cols % n:
            raise ValueError(
                f"allgather_matmul: w output dim {data_cols} not divisible "
                f"by '{axis}' axis size {n}")
    if n == 1:
        outs = tuple(local_matmul(x, wi, layer_index=layer_index)
                     for wi in ws)
        return outs[0] if single else outs

    leaves, specs, recipes = [], [], []
    for wi in ws:
        ls, ss, r = _flatten_w(wi, P(None, axis), stacked)
        leaves += ls
        specs += ss
        recipes.append(r)

    def body(x_loc, li_l, *wl):
        dots = _rebuild_dots(recipes, wl, li_l, stacked)
        return tuple(_ring_ag_core(x_loc, dots, n, axis))

    fn = shard_map(body, mesh=mesh,
                   in_specs=(P(axis, None), P(), *specs),
                   out_specs=tuple(P(None, axis) for _ in ws),
                   check_vma=False)
    overlap_counters.ring(steps=n - 1, bytes_permuted=(n - 1) * x.nbytes)
    outs = fn(x, _li_arg(layer_index), *leaves)
    return outs[0] if single else outs


def matmul_reduce_scatter(x, w, mesh, *, axis: str = "tensor",
                          layer_index=None):
    """``reduce-scatter(x @ w) over axis``, ring-overlapped.

    x: [M, K] with the contraction (K) sharded over ``axis``; w: [K, N]
    with rows sharded over ``axis`` (array or per-shard ``QuantLinear``).
    Returns [M, N] with rows (M) sharded over ``axis`` — the row-parallel
    out-proj whose partial products ring-accumulate in fp32 instead of
    blocking on an all-reduce.

    Raises ``ValueError`` on dims that do not divide by the axis size.
    """
    n = _axis_n(mesh, axis)
    if x.ndim != 2:
        raise ValueError(f"matmul_reduce_scatter expects 2D x, got {x.shape}")
    M, K = x.shape
    wK, wN = _w_contract_out(w, n, sharded="row")
    if wK != K:
        raise ValueError(f"contract mismatch: x K={K} vs w K={wK}")
    if n > 1 and K % n:
        raise ValueError(
            f"matmul_reduce_scatter: contraction dim {K} not divisible by "
            f"'{axis}' axis size {n} — fall back to einsum + psum")
    if n > 1 and M % n:
        raise ValueError(
            f"matmul_reduce_scatter: output rows {M} not divisible by "
            f"'{axis}' axis size {n} — pad the token dim or fall back")
    if n == 1:
        return local_matmul(x, w, layer_index=layer_index)
    stacked = layer_index is not None
    leaves, specs, recipe = _flatten_w(w, P(axis, None), stacked)

    def body(x_loc, li_l, *wl):
        dots = _rebuild_dots([recipe], wl, li_l, stacked)
        return _ring_rs_core(x_loc, lambda rows, _s: dots[0](rows), n,
                             axis, x.dtype)

    fn = shard_map(body, mesh=mesh,
                   in_specs=(P(None, axis), P(), *specs),
                   out_specs=P(axis, None),
                   check_vma=False)
    overlap_counters.ring(steps=n - 1,
                          bytes_permuted=(n - 1) * M * wN * 4)  # fp32 acc
    return fn(x, _li_arg(layer_index), *leaves)


def ring_row_matmul(x, w, mesh, *, axis: str = "tensor",
                    lead_specs: Sequence | None = None,
                    layer_index=None):
    """Replicated-output row-parallel matmul for the GSPMD model zoo.

    x: [*lead, K] (K forced ``axis``-sharded at the shard_map boundary —
    a free reslice when the producing projection already shards it, e.g.
    heads/mlp dims under the Megatron rules); w: [K, N] row-sharded.
    Computes ring matmul⊗reduce-scatter then all-gathers the row chunks,
    so the GEMM hides under the ring transfers and only the (n-1)/n
    all-gather stays exposed — vs the 2(n-1)/n blocking all-reduce GSPMD
    would insert. ``lead_specs`` gives the mesh axes of the lead (token)
    dims, mirroring the engine's activation rules.

    Returns ``None`` (with a fallback counter bump) when the shapes cannot
    ring — callers keep the plain matmul as the fallback path. Safe under
    ``jax.grad``: every ring op (ppermute/all_gather/DUS) differentiates.
    """
    n = _axis_n(mesh, axis)
    if n <= 1:
        return None
    lead = x.shape[:-1]
    K = x.shape[-1]
    lead_specs = tuple(lead_specs) if lead_specs is not None \
        else (None,) * len(lead)
    if len(lead_specs) != len(lead):
        raise ValueError(f"lead_specs {lead_specs} does not match x lead "
                         f"dims {lead}")
    wK, wN = _w_contract_out(w, n, sharded="row")
    if wK != K or K % n:
        overlap_counters.fallback()
        return None
    # normalize lead specs against THIS mesh: an axis the mesh doesn't
    # carry cannot shard anything, so dropping it is exact (a bare
    # ('tensor',) mesh with the scope's default data/expert/fsdp/seq
    # token_specs must ring, not KeyError)
    lead_specs = tuple(
        (tuple(a for a in (e if isinstance(e, (tuple, list)) else (e,))
               if a is not None and a in mesh.shape) or None)
        for e in lead_specs)
    loc = []
    for d, e in zip(lead, lead_specs):
        sz = math.prod(_axis_n(mesh, a) for a in e) if e else 1
        if d % sz:
            overlap_counters.fallback()
            return None
        loc.append(d // sz)
    M_l = math.prod(loc) if loc else 1
    if M_l % n:
        overlap_counters.fallback()
        return None
    stacked = layer_index is not None
    leaves, specs, recipe = _flatten_w(w, P(axis, None), stacked)

    def body(x_loc, li_l, *wl):
        dots = _rebuild_dots([recipe], wl, li_l, stacked)
        x2 = x_loc.reshape(-1, x_loc.shape[-1])
        y_c = _ring_rs_core(x2, lambda rows, _s: dots[0](rows), n, axis,
                            x.dtype)
        y = lax.all_gather(y_c, axis, axis=0, tiled=True)
        return y.reshape(*x_loc.shape[:-1], y.shape[-1])

    fn = shard_map(body, mesh=mesh,
                   in_specs=(P(*lead_specs, axis), P(), *specs),
                   out_specs=P(*lead_specs, None),
                   check_vma=False)
    M_g = math.prod(lead) if lead else 1
    overlap_counters.ring(
        steps=n - 1,
        bytes_permuted=(n - 1) * M_g * wN * 4
        + (n - 1) * M_g * wN * jnp.dtype(x.dtype).itemsize // n)
    return fn(x, _li_arg(layer_index), *leaves)
