"""Attention-formulation registry — ONE place that picks kernel vs gather.

The serving engine has two formulations of paged attention: the Pallas
kernel (ops/pallas/paged_attention.py — block-table DMA gather, online
softmax, no [S, ctx, KV, D] materialization) and the XLA gather fallback
inside ``inference/forward.py``. Historically each dispatch site
carried its own ``if self._pallas_decode and ...`` conditional, which is
how the tree-verify path silently pinned the gather formulation for a
year of PRs. This module centralizes the decision:

- :func:`select_attention` is a PURE function of engine geometry/config
  returning an :class:`AttnSelection` — the chosen path plus a
  human-readable reason whenever the gather fallback wins. The engine
  computes one selection per mode at init (the inputs are all static),
  hands both to its forward (``forward.RaggedForward``), which routes
  through them, surfaces it in ``ds_report``,
  and counts every dispatch against it
  (``serving_attn_kernel_total{path,mode}``).
- A repo lint (bin/check_state_invariants.py::check_attn_registry) pins
  that the engine has no ad-hoc second dispatch site: the forward's module
  alone imports the kernel.

Tree mode adds geometry gates on top of :func:`paged_attention_usable`:
the T candidate nodes must fit ONE query-row tile (the kernel's
per-node-position input rides the q tile; splitting nodes across tiles
is unimplemented) and the ancestors mask must fit the VMEM budget next
to the score tile.
"""
from __future__ import annotations

from dataclasses import dataclass

from ..ops.pallas.paged_attention import (ONE_TILE_ROWS,
                                          paged_attention_usable)

#: the TREE form's one-tile limit: tree nodes × GQA group must ride ONE
#: query tile of paged_ragged_attention (the per-node positions and the
#: ancestors mask tile with it), and up to this many rows a KV head every
#: call does (``paged_plan``; a prefill chunk's tile is planned from its
#: shape and may be taller)
QUERY_TILE_ROWS = ONE_TILE_ROWS

#: int32 ancestors-mask bytes the tree q-tile may bind in VMEM. The
#: decode kernel already budgets ~2MB for its f32 score tile; the mask
#: rides beside it, so keep it an order of magnitude smaller.
TREE_MASK_VMEM_BYTES = 1 << 19


@dataclass(frozen=True)
class AttnSelection:
    """Which attention formulation serves a dispatch mode, and why not
    the kernel when it doesn't."""
    path: str      # "pallas" | "gather"
    mode: str      # "decode" | "tree"
    reason: str    # fallback reason; "" when the Pallas kernel serves

    @property
    def is_pallas(self) -> bool:
        return self.path == "pallas"


def select_attention(*, mode: str, num_heads: int, kv_heads: int,
                     head_dim: int, block_size: int, use_pallas: bool,
                     reason_not_usable: str = "",
                     tree_nodes: int = 0,
                     stage_rows: int = 0) -> AttnSelection:
    """Pick the formulation for ``mode`` ("decode" | "tree").

    ``use_pallas`` is the engine's resolved kernel gate (geometry +
    position embedding + tensor-axis divisibility + config pin), with
    ``reason_not_usable`` naming WHY it is off when it is. Tree mode
    applies the additional geometry gates; ``tree_nodes`` is the verify
    width T (spec_max_nodes) and ``stage_rows`` the padded stage width
    Ts the engine will stage the node K/V into.
    """
    if mode not in ("decode", "tree"):
        raise ValueError(f"unknown attention mode {mode!r}")
    if not use_pallas:
        return AttnSelection(
            "gather", mode,
            reason_not_usable or "pallas kernels disabled for this engine")
    if not paged_attention_usable(num_heads, kv_heads, head_dim,
                                  block_size):
        return AttnSelection(
            "gather", mode,
            "kernel-unusable geometry (head_dim/block_size/GQA/pltpu)")
    if mode == "decode":
        return AttnSelection("pallas", "decode", "")
    G = num_heads // kv_heads
    T = tree_nodes
    Ts = stage_rows or T
    if T < 1:
        return AttnSelection("gather", "tree", "no tree nodes configured")
    if T * G > QUERY_TILE_ROWS:
        return AttnSelection(
            "gather", "tree",
            f"{T} nodes x {G} query heads/kv head exceed the "
            f"{QUERY_TILE_ROWS}-row query tile")
    if Ts > block_size and Ts % block_size:
        return AttnSelection(
            "gather", "tree",
            f"stage width {Ts} not page-tileable at block_size "
            f"{block_size}")
    mask_bytes = T * G * Ts * 4
    if mask_bytes > TREE_MASK_VMEM_BYTES:
        return AttnSelection(
            "gather", "tree",
            f"ancestors mask ({mask_bytes}B) exceeds the "
            f"{TREE_MASK_VMEM_BYTES}B VMEM budget")
    return AttnSelection("pallas", "tree", "")
