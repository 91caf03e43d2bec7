"""ZeRO-Infinity parameter offload: host-resident params, layer streaming.

TPU-native re-design of the reference's partitioned-parameter swapper
(/root/reference/deepspeed/runtime/swap_tensor/partitioned_param_swapper.py:37,
runtime/zero/stage3.py:1910,1958 NVMe param path, and the hook-driven
fetch/release of runtime/zero/parameter_offload.py:80). The reference keeps
each rank's param partition in host/NVMe and hooks every submodule to
all-gather it into HBM just in time. Under a single-controller JAX runtime
the same memory state is expressed as a *host-driven layer walk*:

- The fp32 master (+ moments) lives in the host optimizer
  (:class:`~.offload.HostOffloadOptimizer`); a bf16 compute cache of every
  parameter group lives in host RAM (or NVMe when
  ``offload_param.device == "nvme"``).
- The transformer is executed group-by-group (embedding → layer_0..L-1 →
  head) through per-group jitted programs. All layers share ONE compiled
  forward and ONE compiled fused fwd+vjp program (same shapes), so compile
  cost is depth-independent.
- Groups are staged host→device with ``jax.device_put`` (async) and a
  configurable lookahead (``offload_param.buffer_count``), and released
  right after use — peak HBM holds O(lookahead) layers of params, never
  the model (the swapper's available/inflight buffer pool, re-expressed).
- NVMe reads are pipelined one window AHEAD of device staging: while the
  walk computes group i with groups [i, i+lookahead) in HBM, the reads
  for groups [i+lookahead, i+2·lookahead) are in flight on the aio
  thread pool (``_prefetch_host``), so ``_stage`` waits on reads that
  were issued ``lookahead`` iterations earlier — the swapper's
  available/inflight split (partitioned_param_swapper.py:37) on the
  host side. Host read-ahead buffers cost RAM, never HBM.
- The backward walk re-stages each layer and runs the fused program;
  each layer's gradient starts a non-blocking D2H copy immediately
  (``copy_to_host_async``) and is accumulated into the fp32 host buffers
  only once it is ``lookahead`` layers stale — the host thread never
  blocks on a transfer that would stall dispatch of the next layer's
  backward. Full gradients never exist in HBM (≤ lookahead layers of
  grads ride the queue). At the GAS boundary the host SIMD optimizer
  steps group-by-group (composing with the NVMe optimizer-state
  swapper) and the bf16 cache is refreshed.

DP composes: batch dims are sharded over the mesh's DP axes and staged
params are replicated, so XLA emits the gradient all-reduce inside each
layer-bwd program. TP/PP/SP do not compose with this path (the reference's
param swapper is likewise a pure-DP ZeRO-3 feature) — validated loudly.
"""
from __future__ import annotations

from typing import Any

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from ...models.loss import IGNORE_INDEX, cross_entropy_lm
from ...models.transformer import Block, Norm
from ...parallel.topology import BATCH_AXES
from ...utils.logging import logger

Pytree = Any


def _keystr(prefix: str, sub_path) -> str:
    return prefix + jax.tree_util.keystr(sub_path)


class NVMeParamPlaceholder:
    """Stands in for a parameter whose bytes live on NVMe in
    ``engine.state.params``. Carries the true shape/dtype (so shape-driven
    consumers — flops profiler, topology checks — keep working) but any
    VALUE access raises instead of silently reading zeros: the bytes are
    on disk, fetch them via ``engine._param_stream.host_params_tree()``
    (the checkpoint path already does). Mirrors the reference's invariant
    that an NVMe-resident partition has ``param.data`` swapped out
    (partitioned_param_swapper.py:37) rather than zero-filled."""

    __slots__ = ("shape", "dtype", "_key")

    def __init__(self, shape, dtype, key: str):
        self.shape = tuple(shape)
        self.dtype = np.dtype(dtype)
        self._key = key

    @property
    def ndim(self) -> int:
        return len(self.shape)

    @property
    def size(self) -> int:
        return int(np.prod(self.shape)) if self.shape else 1

    @property
    def nbytes(self) -> int:
        return self.size * self.dtype.itemsize

    def _raise(self, *a, **k):
        raise RuntimeError(
            f"parameter '{self._key}' is NVMe-resident (offload_param."
            f"device='nvme'): engine.state.params carries shape/dtype "
            f"placeholders only. Read values through "
            f"engine._param_stream.host_params_tree() — note it loads the "
            f"FULL model into host RAM.")

    __array__ = _raise
    __getitem__ = _raise
    __iter__ = _raise
    __float__ = _raise
    __int__ = _raise
    __bool__ = _raise
    __add__ = __radd__ = __mul__ = __rmul__ = _raise
    __sub__ = __rsub__ = __truediv__ = __rtruediv__ = _raise
    __matmul__ = __rmatmul__ = _raise

    def __repr__(self):
        return (f"NVMeParamPlaceholder(key={self._key!r}, "
                f"shape={self.shape}, dtype={self.dtype})")


class LayerStreamTrainer:
    """Executes TransformerLM training with host-resident parameters."""

    def __init__(self, model, config, topology, host_opt, compute_dtype):
        self.model = model
        self.mcfg = model.config
        self.config = config
        self.topology = topology
        self.host_opt = host_opt
        self.dtype = compute_dtype
        m = self.mcfg
        if getattr(m, "dropout", 0):
            logger.warning("offload_param path runs deterministic=True — "
                           "dropout is disabled on the streamed layer walk")
        if not m.causal:
            raise ValueError("offload_param streaming supports causal LMs "
                             "(TransformerLM) only")

        self.lookahead = max(1, int(getattr(
            config.zero_optimization.offload_param, "buffer_count", 4)))
        self.nvme = config.zero_optimization.offload_param.device == "nvme"
        self.aio = host_opt.aio if self.nvme else None
        self.nvme_dir = host_opt.nvme_dir if self.nvme else None

        mesh = topology.mesh
        self._repl = NamedSharding(mesh, P())
        self._batch_sh = NamedSharding(mesh, P(BATCH_AXES))

        # host state, filled by init_from_master
        self.cache: dict[str, dict] = {}      # group -> subtree of np bf16
        self.shapes: dict[str, dict] = {}     # group -> subtree of shapes
        self.groups: list[str] = []
        self.total_param_bytes = 0
        self.peak_staged_bytes = 0
        self._staged: dict[str, Pytree] = {}
        self._staged_bytes: dict[str, int] = {}
        self._live_bytes = 0
        self._grad_acc: dict[str, np.ndarray] = {}
        self._programs: dict[Any, Any] = {}
        # NVMe read-ahead: group -> ([(buf, req, shape), ...], treedef)
        self._inflight: dict[str, tuple] = {}
        # non-blocking grad D2H: (tree, nbytes) awaiting accumulation
        self._grad_pending: list[tuple] = []
        self._grad_live_bytes = 0
        # peak_staged_bytes counts staged PARAMS; peak_hbm_bytes adds the
        # grad queue (≤ lookahead+1 layer-grad trees) — the honest total
        self.peak_hbm_bytes = 0
        # read-ahead effectiveness: a hit
        # = the group's NVMe reads were already in flight when the walk
        # needed it; a miss = the fetch had to be issued synchronously
        self.nvme_prefetch_hits = 0
        self.nvme_prefetch_misses = 0

    # ------------------------------------------------------------------
    # host state bring-up
    # ------------------------------------------------------------------
    def group_of(self, top_key: str) -> str:
        if top_key.startswith("layer_"):
            return top_key
        if top_key in ("ln_final", "unembed", "unembed_b"):
            return "head"
        return "pre"   # embed / pos_embed / type_embed / ln_embed

    def init_from_master(self, master_np: dict) -> None:
        """Take the fp32 master pytree (numpy, host) and build the grouped
        bf16 compute cache. The master itself is handed to the host
        optimizer by the engine."""
        if self.nvme:
            self._drain_inflight()      # restore rewrites the NVMe files
        m = self.mcfg
        self.groups = (["pre"] + [f"layer_{i}" for i in range(m.num_layers)]
                       + ["head"])
        for g in self.groups:
            self.cache[g] = {}
            self.shapes[g] = {}
        dt = np.dtype(self.dtype)
        for top, sub in master_np.items():
            g = self.group_of(top)
            self.cache[g][top] = jax.tree.map(
                lambda a: np.asarray(a).astype(dt)
                if np.issubdtype(np.asarray(a).dtype, np.floating) else
                np.asarray(a), sub)
            self.shapes[g][top] = jax.tree.map(
                lambda a: jax.ShapeDtypeStruct(np.asarray(a).shape,
                                               self.dtype), sub)
        if m.tie_embeddings:
            # the head reads the embedding table too; reference the SAME
            # host buffer (no copy) so refreshes stay coherent
            self.cache["head"]["embed"] = self.cache["pre"]["embed"]
        self.total_param_bytes = sum(
            a.nbytes for g in self.groups
            for a in jax.tree.leaves(self.cache[g]))
        if self.nvme:
            for g in self.groups:
                self._spill_group(g)
        logger.info(
            f"ZeRO-Infinity param offload: {len(self.groups)} groups, "
            f"{self.total_param_bytes / 1e6:.0f}MB params host-resident "
            f"({'nvme' if self.nvme else 'cpu'}), lookahead={self.lookahead}")

    # -- nvme bf16 cache ------------------------------------------------
    # Disk layout: one file per leaf, named by the FULL keystr path
    # ("['layer_0']['attn']['wq']"); in-RAM self.cache[g] is emptied after
    # spill (self.shapes keeps the tree structure + shapes).
    def _param_path(self, full_key: str) -> str:
        import os

        from ...utils.naming import safe_filename

        return os.path.join(self.nvme_dir,
                            f"param.{safe_filename(full_key)}.bin")

    def _group_items(self, g: str, tree: dict) -> dict:
        if self.mcfg.tie_embeddings and g == "head":
            # 'embed' rides with the pre group on disk
            return {k: v for k, v in tree.items() if k != "embed"}
        return tree

    def _spill_group(self, g: str) -> None:
        items = self._group_items(g, self.cache[g])
        flat, _ = jax.tree_util.tree_flatten_with_path(items)
        reqs, keep = [], []
        for path, arr in flat:
            buf = np.ascontiguousarray(arr).view(np.uint8).reshape(-1)
            keep.append(buf)                 # alive until the waits below
            reqs.append(self.aio.async_pwrite(
                buf, self._param_path(jax.tree_util.keystr(path))))
        for r in reqs:
            self.aio.wait(r)
        self.cache[g] = {}     # disk owns the bytes; shapes keep structure

    def _issue_fetch(self, g: str) -> tuple:
        """Issue async NVMe reads for every leaf of group ``g`` (returns
        without waiting — completion happens in :meth:`_fetch_group`)."""
        shapes = self._group_items(g, self.shapes[g])
        flat, treedef = jax.tree_util.tree_flatten_with_path(
            shapes, is_leaf=lambda x: isinstance(x, jax.ShapeDtypeStruct))
        itemsize = np.dtype(self.dtype).itemsize
        bufs = []
        for path, sds in flat:
            n = int(np.prod(sds.shape)) * itemsize
            buf = np.empty(n, np.uint8)
            req = self.aio.async_pread(
                buf, self._param_path(jax.tree_util.keystr(path)))
            bufs.append((buf, req, sds.shape))
        return bufs, treedef

    def _prefetch_host(self, g: str) -> None:
        """Start the NVMe reads for ``g`` ahead of its ``_stage`` — the
        walk calls this one lookahead-window early so the wait inside
        :meth:`_fetch_group` lands on already-serviced requests. No-op in
        CPU mode (host cache access is free) and when already staged or
        in flight."""
        if not self.nvme or g in self._staged or g in self._inflight:
            return
        if self.mcfg.tie_embeddings and g == "head":
            self._prefetch_host("pre")   # head borrows pre's embed table
        self._inflight[g] = self._issue_fetch(g)

    def _fetch_group(self, g: str) -> dict:
        """Complete (or issue-and-complete) the NVMe read of a group."""
        inflight = self._inflight.pop(g, None)
        if inflight is not None:
            self.nvme_prefetch_hits += 1
        else:
            self.nvme_prefetch_misses += 1
        bufs, treedef = inflight or self._issue_fetch(g)
        leaves = []
        for buf, req, shape in bufs:
            self.aio.wait(req)
            leaves.append(buf.view(np.dtype(self.dtype)).reshape(shape))
        out = dict(jax.tree_util.tree_unflatten(treedef, leaves))
        if self.mcfg.tie_embeddings and g == "head":
            out["embed"] = self._host_group("pre")["embed"]
        return out

    def _drain_inflight(self) -> None:
        """Complete and discard any outstanding prefetch reads. Called
        before anything rewrites the NVMe files (cache refresh at the GAS
        boundary, checkpoint restore) — a pending read racing a rewrite
        of the same file would tear."""
        for g in list(self._inflight):
            bufs, _ = self._inflight.pop(g)
            for _, req, _ in bufs:
                self.aio.wait(req)

    def _host_group(self, g: str) -> dict:
        if self.nvme:
            return self._fetch_group(g)
        return self.cache[g]

    # -- staging --------------------------------------------------------
    def _stage(self, g: str) -> Pytree:
        if g not in self._staged:
            tree = self._host_group(g)
            dev = jax.device_put(tree, self._repl)
            nbytes = sum(a.nbytes for a in jax.tree.leaves(tree))
            self._staged[g] = dev
            self._staged_bytes[g] = nbytes
            self._live_bytes += nbytes
            self.peak_staged_bytes = max(self.peak_staged_bytes,
                                         self._live_bytes)
            self.peak_hbm_bytes = max(
                self.peak_hbm_bytes, self._live_bytes + self._grad_live_bytes)
        return self._staged[g]

    def _release(self, g: str) -> None:
        if g in self._staged:
            self._live_bytes -= self._staged_bytes.pop(g)
            del self._staged[g]

    # ------------------------------------------------------------------
    # jitted per-group programs (compiled once; all layers share)
    # ------------------------------------------------------------------
    def _pre_fwd_fn(self):
        m, dt = self.mcfg, self.dtype

        def pre_fwd(pre, ids, positions):
            x = pre["embed"].astype(dt)[ids]
            if "pos_embed" in pre:
                x = x + pre["pos_embed"].astype(dt)[positions]
            if "type_embed" in pre:
                # token_type_ids default to 0 (transformer.py:515); batches
                # carrying explicit type ids are rejected in _prepare_micro
                x = x + pre["type_embed"].astype(dt)[jnp.zeros_like(ids)]
            if "ln_embed" in pre:
                x = Norm(m).apply({"params": pre["ln_embed"]}, x)
            return x

        return pre_fwd

    def _use_moe(self, i: int) -> bool:
        m = self.mcfg
        return bool(m.moe) and (i % (m.moe.moe_layer_freq or 1) == 0)

    def _block_fn(self, i: int):
        """Takes the LAYER subtree directly (not the group dict), so the
        compiled program is index-free and shared across layers."""
        m = self.mcfg
        use_moe = self._use_moe(i)

        def block(p, x, positions):
            y, var = Block(m, use_moe=use_moe).apply(
                {"params": p}, x, positions, None, None, True,
                mutable=["losses"])
            aux = sum((jnp.sum(l) for l in jax.tree.leaves(
                var.get("losses", {}))), jnp.zeros((), jnp.float32))
            return y, aux

        return block

    def _head_fn(self):
        m, dt = self.mcfg, self.dtype

        def head(hp, x, labels):
            if m.pre_norm:
                x = Norm(m).apply({"params": hp["ln_final"]}, x)
            if m.tie_embeddings:
                logits = jnp.einsum("bse,ve->bsv", x,
                                    hp["embed"].astype(dt))
            else:
                logits = jnp.einsum("bse,ev->bsv", x,
                                    hp["unembed"].astype(dt))
            if m.unembed_bias:
                logits = logits + hp["unembed_b"].astype(dt)
            return cross_entropy_lm(logits, labels)

        return head

    def _program(self, kind: str, i: int = -1):
        """Build-and-cache jitted programs. Layer programs key on the moe
        pattern, not the index, so depth never multiplies compiles."""
        m = self.mcfg
        if kind in ("block_fwd", "block_bwd"):
            use_moe = bool(m.moe) and (i % (m.moe.moe_layer_freq or 1) == 0)
            key = (kind, use_moe)
        else:
            key = kind
        if key in self._programs:
            return self._programs[key]

        if kind == "pre_fwd":
            fn = jax.jit(self._pre_fwd_fn(),
                         out_shardings=self._batch_sh)
        elif kind == "pre_bwd":
            pre_fwd = self._pre_fwd_fn()

            def pre_bwd(pre, ids, positions, dx):
                _, vjp = jax.vjp(lambda p: pre_fwd(p, ids, positions), pre)
                return vjp(dx)[0]

            fn = jax.jit(pre_bwd, out_shardings=self._repl)
        elif kind == "block_fwd":
            fn = jax.jit(self._block_fn(i),
                         out_shardings=(self._batch_sh, self._repl))
        elif kind == "block_bwd":
            block = self._block_fn(i)

            def block_bwd(p, x, positions, dy):
                (y, aux), vjp = jax.vjp(lambda p, x: block(p, x, positions),
                                        p, x)
                # total loss = head_loss + sum(aux): aux cotangent is 1
                dp, dx = vjp((dy, jnp.ones((), jnp.float32)))
                return dp, dx

            fn = jax.jit(block_bwd,
                         out_shardings=(self._repl, self._batch_sh))
        elif kind == "head_bwd":
            head = self._head_fn()

            def head_bwd(hp, x, labels):
                (loss, (dhp, dx)) = jax.value_and_grad(
                    head, argnums=(0, 1))(hp, x, labels)
                return loss, dhp, dx

            fn = jax.jit(head_bwd,
                         out_shardings=(self._repl, self._repl,
                                        self._batch_sh))
        elif kind == "head_loss":
            fn = jax.jit(self._head_fn(), out_shardings=self._repl)
        else:
            raise KeyError(kind)
        self._programs[key] = fn
        return fn

    # ------------------------------------------------------------------
    # gradient plumbing
    # ------------------------------------------------------------------
    def _acc_grads(self, top_prefix_tree: dict) -> None:
        """Accumulate a device grad tree (keyed by top-level param name)
        into the host fp32 buffers. Blocks on the D2H transfer — the walk
        routes through :meth:`_enqueue_grads` so this only runs on trees
        whose async copy started ``lookahead`` layers ago."""
        for top, sub in top_prefix_tree.items():
            flat, _ = jax.tree_util.tree_flatten_with_path(sub)
            for path, leaf in flat:
                key = _keystr(f"['{top}']", path)
                g = np.asarray(leaf, np.float32).reshape(-1)
                if key in self._grad_acc:
                    self._grad_acc[key] += g
                else:
                    self._grad_acc[key] = g

    def _enqueue_grads(self, top_prefix_tree: dict) -> None:
        """Start the non-blocking D2H copy of a layer's gradients and park
        the tree; the device buffers stay alive (≤ lookahead+1 layers of
        grads, counted in ``peak_hbm_bytes``) until :meth:`_drain_grads`
        accumulates them."""
        nbytes = 0
        for leaf in jax.tree.leaves(top_prefix_tree):
            if isinstance(leaf, jax.Array):
                leaf.copy_to_host_async()
                nbytes += leaf.nbytes
        self._grad_pending.append((top_prefix_tree, nbytes))
        self._grad_live_bytes += nbytes
        self.peak_hbm_bytes = max(
            self.peak_hbm_bytes, self._live_bytes + self._grad_live_bytes)

    def _drain_grads(self, keep: int = 0) -> None:
        while len(self._grad_pending) > keep:
            tree, nbytes = self._grad_pending.pop(0)
            self._acc_grads(tree)
            self._grad_live_bytes -= nbytes

    # ------------------------------------------------------------------
    def _prepare_micro(self, mb: dict):
        if "token_type_ids" in mb:
            raise NotImplementedError(
                "offload_param streaming does not plumb token_type_ids "
                "(type_embed trains at index 0, the dense default)")
        ids_np = np.asarray(mb["input_ids"])
        B, S = ids_np.shape
        ids = jax.device_put(ids_np, self._batch_sh)
        labels_np = mb.get("labels")
        if labels_np is None:
            labels_np = np.concatenate(
                [ids_np[:, 1:], np.full_like(ids_np[:, :1], IGNORE_INDEX)],
                axis=1)
        labels = jax.device_put(np.asarray(labels_np), self._batch_sh)
        positions = jax.device_put(
            np.broadcast_to(np.arange(S, dtype=np.int32), (B, S)).copy(),
            self._batch_sh)
        return ids, labels, positions

    def micro_forward(self, mb: dict, keep_activations: bool):
        """Streamed forward. Returns (loss_total, xs, (ids, labels,
        positions)); xs is None unless ``keep_activations``."""
        m = self.mcfg
        L = m.num_layers
        ids, labels, positions = self._prepare_micro(mb)

        k = self.lookahead
        self._prefetch_host("pre")
        for j in range(min(2 * k, L)):       # read-ahead window: 2k deep
            self._prefetch_host(f"layer_{j}")
        self._stage("pre")
        for j in range(min(k, L)):           # device window: k deep
            self._stage(f"layer_{j}")
        x = self._program("pre_fwd")(self._staged["pre"], ids, positions)
        self._release("pre")
        xs = [x] if keep_activations else None
        aux_total = jnp.zeros((), jnp.float32)
        for i in range(L):
            g = f"layer_{i}"
            dev = self._stage(g)
            x, aux = self._program("block_fwd", i)(dev[g], x, positions)
            aux_total = aux_total + aux
            if keep_activations:
                xs.append(x)
            self._release(g)
            nxt = i + k
            pf = i + 2 * k
            if pf < L:
                self._prefetch_host(f"layer_{pf}")
            else:
                self._prefetch_host("head")
            if nxt < L:
                self._stage(f"layer_{nxt}")
        head = self._stage("head")
        if keep_activations:
            return aux_total, xs, (ids, labels, positions)
        loss = self._program("head_loss")(head, x, labels)
        self._release("head")
        return loss + aux_total, None, (ids, labels, positions)

    def micro_fwd_bwd(self, mb: dict) -> jax.Array:
        """One microbatch: streamed forward, then streamed backward with
        immediate host-side gradient accumulation."""
        m = self.mcfg
        L = m.num_layers
        aux_total, xs, (ids, labels, positions) = self.micro_forward(
            mb, keep_activations=True)

        k = self.lookahead
        head = self._staged["head"]
        loss, dhead, dx = self._program("head_bwd")(head, xs[L], labels)
        self._enqueue_grads(dhead)
        self._release("head")

        for j in range(min(2 * k, L)):       # reverse read-ahead window
            self._prefetch_host(f"layer_{L - 1 - j}")
        for i in reversed(range(L)):
            g = f"layer_{i}"
            dev = self._stage(g)
            for j in range(1, k):
                if i - j >= 0:
                    self._stage(f"layer_{i - j}")
            pf = i - 2 * k
            self._prefetch_host(f"layer_{pf}" if pf >= 0 else "pre")
            dp, dx = self._program("block_bwd", i)(dev[g], xs[i],
                                                   positions, dx)
            self._enqueue_grads({g: dp})
            self._release(g)
            xs[i + 1] = None                      # free the activation
            self._drain_grads(keep=k)
        pre = self._stage("pre")
        dpre = self._program("pre_bwd")(pre, ids, positions, dx)
        self._enqueue_grads(dpre)
        self._release("pre")
        self._drain_grads(keep=0)
        return loss + aux_total

    # ------------------------------------------------------------------
    def apply_grads(self, gas: int, lr: float, clip: float | None) -> None:
        """GAS-boundary host optimizer step, group by group, then refresh
        the bf16 compute cache (and NVMe spill)."""
        self._drain_grads(keep=0)       # normally already empty
        self._drain_inflight()          # refresh rewrites the NVMe files
        inv = 1.0 / gas
        for g in self._grad_acc.values():
            g *= inv
        if clip:
            sq = sum(float(np.sum(np.square(g)))
                     for g in self._grad_acc.values())
            norm = float(np.sqrt(sq))
            scale = min(1.0, clip / (norm + 1e-6))
            if scale < 1.0:
                for g in self._grad_acc.values():
                    g *= scale

        first = True
        for grp in self.groups:
            prefix_keys = [k for k in self._grad_acc
                           if self.group_of(k.split("']")[0][2:]) == grp]
            if not prefix_keys:
                continue
            sub = {k: self._grad_acc[k] for k in prefix_keys}
            new_master = self.host_opt.step_keys(sub, lr, bump_step=first)
            first = False
            self._refresh_cache(grp, new_master)
        self._grad_acc.clear()

    def _refresh_cache(self, grp: str, new_master: dict[str, np.ndarray]):
        dt = np.dtype(self.dtype)
        if self.nvme:
            flat, _ = jax.tree_util.tree_flatten_with_path(
                self._group_items(grp, self.shapes[grp]),
                is_leaf=lambda x: isinstance(x, jax.ShapeDtypeStruct))
            by_key = {jax.tree_util.keystr(p): s for p, s in flat}
            reqs, keep = [], []
            for key, master in new_master.items():
                sds = by_key[key]
                buf = np.ascontiguousarray(
                    master.reshape(sds.shape).astype(dt)
                ).view(np.uint8).reshape(-1)
                keep.append(buf)
                reqs.append(self.aio.async_pwrite(buf, self._param_path(key)))
            for r in reqs:
                self.aio.wait(r)
            return
        for key, master in new_master.items():
            top = key.split("']")[0][2:]
            sub_path = key[len(f"['{top}']"):]
            if not sub_path:
                tgt = self.cache[grp][top]
                np.copyto(tgt, master.reshape(tgt.shape).astype(dt))
            else:
                _assign_by_path(self.cache[grp][top], sub_path, master, dt)

    # checkpoint/readback: rebuild a full params pytree (numpy, host)
    def host_params_tree(self, snapshot: bool = False) -> dict:
        """Fresh full params view. NVMe mode reads the whole model from
        disk — call only at checkpoint/readback time (the same transient
        full-RAM caveat as HostOffloadOptimizer.global_trees).
        ``snapshot=True`` copies leaves so async checkpoint serialization
        never races the in-place cache refresh."""
        out: dict = {}
        fix = (lambda a: np.array(a, copy=True)) if snapshot else \
            (lambda a: a)
        for grp in self.groups:
            src = self._host_group(grp)
            for top, sub in src.items():
                if top in out:      # tied embed appears in pre AND head
                    continue
                out[top] = jax.tree.map(fix, sub)
        return out

    def params_view(self) -> dict:
        """The tree exposed as ``engine.state.params``. CPU mode: the LIVE
        cache arrays (in-place refresh keeps them current, no copies).
        NVMe mode: :class:`NVMeParamPlaceholder` leaves carrying true
        shapes/dtypes that RAISE on any value access — checkpoint saves
        substitute :meth:`host_params_tree` output."""
        if not self.nvme:
            return self.host_params_tree()
        out: dict = {}
        for grp in self.groups:
            for top, sub in self.shapes[grp].items():
                if top in out:
                    continue
                flat, treedef = jax.tree_util.tree_flatten_with_path(
                    sub, is_leaf=lambda x: isinstance(x, jax.ShapeDtypeStruct))
                out[top] = jax.tree_util.tree_unflatten(treedef, [
                    NVMeParamPlaceholder(s.shape, s.dtype,
                                         _keystr(f"['{top}']", p))
                    for p, s in flat])
        return out


def _assign_by_path(tree: dict, keystr_path: str, master_flat: np.ndarray,
                    dt: np.dtype):
    """Write a flat fp32 master back into the compute cache leaf at the
    keystr path (e.g. \"['attn']['wq']\") IN PLACE, so every external view
    of the cache (engine.state.params, tied-embed aliases) stays fresh."""
    node = tree
    parts = [p[2:-2] for p in keystr_path.replace("][", "]|[").split("|")
             if p] if keystr_path else []
    if not parts:
        raise KeyError(f"empty leaf path for cache assign: {keystr_path}")
    for p in parts[:-1]:
        node = node[p]
    leaf = node[parts[-1]]
    np.copyto(leaf, master_flat.reshape(leaf.shape).astype(dt))
