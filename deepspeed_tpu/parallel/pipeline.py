"""Pipeline parallelism — SPMD circular pipeline over the ``pipe`` mesh axis.

TPU-native re-design of reference runtime/pipe/ (``PipelineModule``
module.py:86, ``LayerSpec`` :30, ``TiedLayerSpec`` :77, ``PipelineEngine``
engine.py:61 with its ``_exec_*`` instruction interpreter and the 1F1B
``TrainSchedule`` schedule.py:189, p2p send/recv p2p.py).

The reference is MPMD: each stage is a different process running an
instruction schedule, exchanging activations over NCCL p2p. On TPU the
idiomatic equivalent is a *single* SPMD program: every device runs the same
per-stage function; stage identity is the device's index along the ``pipe``
mesh axis; the p2p send/recv pair is one ``ppermute`` ring shift; and the
schedule is a ``lax.scan`` over ``M + P - 1`` ticks (M microbatches through
P stages — a GPipe/circular schedule; its bubble fraction (P-1)/(M+P-1) is
identical to 1F1B, which differs only in activation liveness, a concern the
XLA scheduler + rematerialization own here).

Composition with the other axes: the shard_map is *partial* — only ``pipe``
is manual; data/fsdp/tensor/seq stay GSPMD-auto inside the stage body, so
ZeRO sharding and Megatron TP compose unchanged with pipelining.

Tied weights (``TiedLayerSpec``): under SPMD there is no tied-weight
replica + allreduce protocol (reference pipe/module.py:77, engine.py:275) —
tying is simply reusing one parameter pytree leaf in two places; autodiff
sums the contributions. See ``PipelinedTransformerLM.tie_embeddings``.
"""
from __future__ import annotations

import dataclasses
from functools import partial
from typing import Any, Callable, Sequence

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from .. import comm
from ..utils.logging import logger

Pytree = Any


# ---------------------------------------------------------------------------
# Core primitive
# ---------------------------------------------------------------------------

def spmd_pipeline(stage_fn: Callable[[Pytree, jax.Array, Pytree], jax.Array],
                  stage_params: Pytree,
                  xs: jax.Array,
                  aux: Pytree = None,
                  *,
                  mesh,
                  axis: str = "pipe",
                  remat: bool = True,
                  with_aux_loss: bool = False,
                  shared: Pytree = None):
    """Run microbatches through a P-stage pipeline laid out on mesh ``axis``.

    ``stage_params``: pytree whose leaves have leading dim L (total layers),
    L divisible by P; dim 0 is sharded over ``axis`` so each stage holds
    L/P layers. ``stage_fn(local_params, x, aux_m)`` consumes one
    microbatch activation plus that microbatch's aux inputs and must return
    an array of the same shape/dtype as ``x`` (the inter-stage wire format).

    ``xs``: [M, ...] microbatched activations entering stage 0.
    ``aux``: optional pytree of [M, ...] per-microbatch side inputs
    (positions, masks) that every stage can read.

    ``with_aux_loss``: ``stage_fn`` returns ``(y, scalar)`` — a per-(stage,
    microbatch) side loss (MoE aux/z losses; reference PipelineEngine
    accumulates these across stages via the tied-comm machinery). Each
    stage's contributions are masked to its VALID ticks (the circular
    schedule clamps edge ticks to duplicate microbatches, which must not
    double-count) and summed across stages and microbatches.

    ``shared``: optional pytree of stage-INVARIANT inputs (tied weights
    reused by every stage — the reference's tied-module replica; its
    gradient is the sum over stages, which the broadcast transpose
    produces). Passed to ``stage_fn`` as a 4th argument when given.

    Returns [M, ...] (plus the total aux loss when ``with_aux_loss``) —
    the final stage's outputs, in microbatch order.
    """
    n = mesh.shape[axis]
    M = xs.shape[0]
    base_fn = stage_fn if shared is not None else \
        (lambda p, x, a, _sh: stage_fn(p, x, a))
    fn = jax.checkpoint(base_fn) if remat else base_fn

    if n == 1:
        def seq_step(_, t):
            aux_m = jax.tree.map(lambda a: jax.lax.dynamic_index_in_dim(
                a, t, 0, keepdims=False), aux)
            x = jax.lax.dynamic_index_in_dim(xs, t, 0, keepdims=False)
            return None, fn(stage_params, x, aux_m, shared)

        _, ys = jax.lax.scan(seq_step, None, jnp.arange(M))
        if with_aux_loss:
            ys, aux_losses = ys
            return ys, jnp.sum(aux_losses)
        return ys

    def body(params, si, xs, aux, sh):
        # squeeze the broadcast stage dim (see below)
        xs = xs[0]
        aux = jax.tree.map(lambda a: a[0], aux)
        sh = jax.tree.map(lambda a: a[0], sh)
        # the stage index arrives as a pipe-sharded iota operand rather
        # than lax.axis_index: under a PARTIAL-manual shard_map some XLA
        # versions cannot partition the PartitionId instruction axis_index
        # lowers to ("UNIMPLEMENTED ... ambiguous", jaxlib 0.4.36), while
        # a sharded operand read is just data
        idx = si[0]
        T = M + n - 1
        state0 = jnp.zeros_like(xs[0])

        def step(state, t):
            # stage `idx` works on microbatch m = t - idx at tick t
            m = jnp.clip(t - idx, 0, M - 1)
            inp = jax.lax.dynamic_index_in_dim(xs, m, 0, keepdims=False)
            cur = jnp.where(idx == 0, inp, state)
            aux_m = jax.tree.map(lambda a: jax.lax.dynamic_index_in_dim(
                a, m, 0, keepdims=False), aux)
            out = fn(params, cur, aux_m, sh)
            if with_aux_loss:
                y, aux_l = out
                # edge ticks recompute clamped microbatches — mask them out
                valid = (t >= idx) & (t - idx < M)
                aux_l = jnp.where(valid, aux_l, 0.0)
            else:
                y, aux_l = out, jnp.float32(0)
            nxt = comm.send_recv_next(y, axis)   # the p2p.py send/recv pair
            return nxt, (y, aux_l)

        _, (ys, aux_ls) = jax.lax.scan(step, state0, jnp.arange(T))
        return ys[None], jnp.sum(aux_ls)[None]   # [1, T, ...] per stage

    # Inputs are broadcast over a leading pipe-sharded stage dim rather than
    # passed with a replicated in_spec: the cotangent of a replicated input
    # would need a psum over the manual axis, which the XLA SPMD partitioner
    # miscompiles for partial-manual shard_maps (jaxlib 0.9.0 crashes with
    # "Invalid binary instruction opcode copy"); a broadcast's transpose is a
    # plain GSPMD reduction outside the shard_map, which is also free to
    # schedule better.
    xs_b = jnp.broadcast_to(xs[None], (n, *xs.shape))
    aux_b = jax.tree.map(lambda a: jnp.broadcast_to(a[None], (n, *a.shape)), aux)
    sh_b = jax.tree.map(lambda a: jnp.broadcast_to(a[None], (n, *a.shape)),
                        shared)
    out, aux_total = jax.shard_map(
        body,
        mesh=mesh,
        axis_names={axis},
        in_specs=(jax.tree.map(lambda _: P(axis), stage_params), P(axis),
                  P(axis), P(axis), P(axis)),
        out_specs=(P(axis), P(axis)),
        check_vma=False,
    )(stage_params, jnp.arange(n, dtype=jnp.int32), xs_b, aux_b, sh_b)
    # final stage's outputs appear at ticks n-1 .. n-1+M
    ys = out[n - 1, n - 1:n - 1 + M]
    if with_aux_loss:
        return ys, jnp.sum(aux_total)            # sum over stages
    return ys


def stack_layer_params(module, rng: jax.Array, num_layers: int,
                       *init_args) -> Pytree:
    """Init ``num_layers`` independent copies of ``module``'s params stacked
    on a leading dim carrying the ``pipe_layers`` logical axis (the ZeRO
    planner maps it to the ``pipe`` mesh axis; remaining dims then get
    fsdp/tensor sharding — ZeRO × TP × PP composition for free)."""
    import flax.linen as nn

    from ..runtime.zero.planner import unbox_params

    def init_one(r):
        return module.init(r, *init_args)["params"]

    boxed = jax.eval_shape(init_one, rng)
    rngs = jax.random.split(rng, num_layers)
    stacked = jax.vmap(lambda r: unbox_params(init_one(r)))(rngs)

    def rebox(spec_leaf, value):
        names = spec_leaf.names if isinstance(spec_leaf, nn.Partitioned) else \
            (None,) * (value.ndim - 1)
        return nn.Partitioned(value, names=("pipe_layers", *names))

    return jax.tree.map(rebox, boxed, stacked,
                        is_leaf=lambda l: isinstance(l, nn.Partitioned))


def _pattern_period(sigs: Sequence, pp: int) -> int:
    """Smallest period of a per-layer signature list, validated against
    the pipe split: SPMD stages must be identical programs, so every
    stage must hold whole pattern groups."""
    L = len(sigs)
    if L % pp != 0:
        raise ValueError(f"{L} layers not divisible by pipe={pp} stages")
    period = next(d for d in range(1, L + 1)
                  if L % d == 0
                  and all(sigs[i] == sigs[i % d] for i in range(L)))
    if (L // pp) % period:
        raise ValueError(
            f"heterogeneous stack has pattern period {period}, which does "
            f"not divide the {L // pp} layers per stage — SPMD stages "
            f"must be identical programs. Either choose pipe so that "
            f"(num_layers/pipe) % {period} == 0, or group the aperiodic "
            f"layers into ONE repeating composite block "
            f"(nn.Module applying them in sequence) and pipeline the "
            f"blocks — see MIGRATION.md 'Aperiodic pipeline stacks'")
    return period


# ---------------------------------------------------------------------------
# LayerSpec / PipelineModule (API parity)
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class LayerSpec:
    """Deferred layer construction (reference pipe/module.py:30)."""
    module_cls: type
    args: tuple = ()
    kwargs: dict = dataclasses.field(default_factory=dict)

    def build(self):
        return self.module_cls(*self.args, **self.kwargs)


@dataclasses.dataclass
class TiedLayerSpec(LayerSpec):
    """Reference pipe/module.py:77. Under SPMD, tying is parameter reuse —
    ``key`` identifies the shared parameter group. Tied specs INSIDE the
    staged stack are supported at periodic positions: the tied params are
    replicated across pipe stages (one copy, broadcast) and every
    occurrence applies the same tree — the gradient sums over stages,
    which is exactly the reference's tied-weight allreduce
    (pipe/engine.py:275). The embed/head tie of a full LM stays outside
    the stack (see ``PipelinedTransformerLM.tie_embeddings``)."""
    key: str = "tied"


class PipelineModule:
    """A stack of layers partitioned over the ``pipe`` axis (reference
    runtime/pipe/module.py:86, ``partition_method='uniform'``).

    Homogeneous stacks (every spec builds the same module) pipeline as one
    scanned stage. HETEROGENEOUS stacks are supported when the layer
    pattern is PERIODIC (e.g. dense/MoE alternating) and each stage holds
    whole pattern groups — every pipe rank then traces the identical stage
    program, which is what SPMD requires. Aperiodic stacks raise.
    ``TiedLayerSpec`` occurrences share ONE replicated param tree.

    ``init(rng, x, *apply_args)`` → boxed params with leading logical axis
    ``pipe_layers`` (the ZeRO planner maps it to the ``pipe`` mesh axis and
    then applies fsdp/tensor sharding to the remaining dims — ZeRO × TP × PP
    composition for free). Homogeneous untied stacks return the bare
    stacked tree (back-compat); otherwise a
    ``{"stacks": {slot: tree}, "tied": {key: tree}}`` dict.
    ``apply(params, xs, aux=None)`` → pipelined forward over microbatches.
    """

    def __init__(self, layers: Sequence[LayerSpec], topology,
                 num_microbatches: int, *, remat: bool = True):
        if not layers:
            raise ValueError("PipelineModule needs at least one LayerSpec")

        def sig(s):
            if isinstance(s, TiedLayerSpec):
                return ("tied", s.key)
            return (s.module_cls, s.args, tuple(sorted(s.kwargs.items())))

        sigs = [sig(s) for s in layers]
        L = len(layers)
        pp = topology.size("pipe")
        period = _pattern_period(sigs, pp)
        self.num_layers = L
        self.period = period
        self.slots = list(layers[:period])
        self._mods = [s.build() for s in self.slots]
        self.module = self._mods[0]          # back-compat attribute
        self.topology = topology
        self.num_microbatches = num_microbatches
        self.remat = remat
        self.layers_per_stage = L // pp
        self._plain = period == 1 and \
            not isinstance(self.slots[0], TiedLayerSpec)

    def init(self, rng: jax.Array, x: jax.Array, *apply_args) -> Pytree:
        if self._plain:
            return stack_layer_params(self.module, rng, self.num_layers,
                                      x, *apply_args)
        import flax.linen as nn

        rngs = jax.random.split(rng, self.period)
        stacks: dict[str, Any] = {}
        tied: dict[str, Any] = {}
        for j, spec in enumerate(self.slots):
            if isinstance(spec, TiedLayerSpec):
                if spec.key not in tied:
                    tied[spec.key] = self._mods[j].init(
                        rngs[j], x, *apply_args)["params"]
            else:
                stacks[str(j)] = stack_layer_params(
                    self._mods[j], rngs[j],
                    self.num_layers // self.period, x, *apply_args)
        return {"stacks": stacks, "tied": tied}

    def apply(self, params: Pytree, xs: jax.Array, aux: Pytree = None,
              extra_apply_args: tuple = ()) -> jax.Array:
        if self._plain:
            def stage_fn(local_params, x, aux_m):
                def layer(x, p):
                    args = (aux_m,) if aux is not None else ()
                    return self.module.apply({"params": p}, x,
                                             *args, *extra_apply_args), None

                x, _ = jax.lax.scan(layer, x, local_params)
                return x

            return spmd_pipeline(stage_fn, params, xs, aux,
                                 mesh=self.topology.mesh, remat=self.remat)

        stacks, tied = params["stacks"], params.get("tied", {})
        stack_slots = sorted(stacks, key=int)

        pp = self.topology.size("pipe")
        groups_per_stage = self.num_layers // (self.period * pp)

        def stage_fn(local_stacks, x, aux_m, sh):
            def group(x, slabs):
                for j, spec in enumerate(self.slots):
                    p = sh[spec.key] if isinstance(spec, TiedLayerSpec) \
                        else slabs[str(j)]
                    args = (aux_m,) if aux is not None else ()
                    x = self._mods[j].apply({"params": p}, x,
                                            *args, *extra_apply_args)
                return x, None

            # explicit length: an ALL-tied stack has no scanned stacks to
            # infer it from (every slot reads the shared tree)
            x, _ = jax.lax.scan(
                group, x, {k: local_stacks[k] for k in stack_slots},
                length=groups_per_stage)
            return x

        return spmd_pipeline(stage_fn, stacks, xs, aux,
                             mesh=self.topology.mesh, remat=self.remat,
                             shared=tied)


# ---------------------------------------------------------------------------
# Flagship integration: pipelined causal LM
# ---------------------------------------------------------------------------

class PipelinedTransformerLM:
    """TransformerLM with its block stack run through the SPMD pipeline —
    the role of the reference's GPT2ModelPipe-style models built on
    ``PipelineModule``. Functional (init/apply/loss_fn) rather than flax, so
    the engine drives it through ``initialize(loss_fn=..., params=...)``.

    Embedding, final norm, and the (tied) LM head run under plain GSPMD on
    every pipe rank (they are < 1% of FLOPs; replicating their compute over
    ``pipe`` costs nothing and avoids heterogeneous stages).
    """

    def __init__(self, config, topology, num_microbatches: int,
                 *, remat: bool = True):
        from ..models.transformer import Block, is_moe_layer

        self.config = config
        self.topology = topology
        self.num_microbatches = num_microbatches
        cfg = config
        L = cfg.num_layers
        pp = topology.size("pipe")
        if L % pp != 0:
            raise ValueError(f"{L} layers not divisible by pipe={pp}")
        # Mixed dense/MoE stacks (qwen2-moe's shipped layout) pipeline as
        # PERIODIC heterogeneous stages: find the smallest layer-pattern
        # period p; every stage then runs L/(p*pp) repetitions of the same
        # p-slot group, which keeps the program SPMD (every pipe rank
        # traces the identical stage function). Reference pipe/module.py:86
        # partitions arbitrary layer lists; arbitrary APERIODIC patterns
        # would need per-stage programs and stay unsupported.
        flags = [is_moe_layer(cfg, i) for i in range(L)]
        period = _pattern_period(flags, pp)
        self.period = period
        self._moe = any(flags)
        self._block_mods = tuple(Block(cfg, use_moe=flags[j])
                                 for j in range(period))
        self._block_mod = self._block_mods[0]   # homogeneous fast path
        self.remat = remat

    # -- params ------------------------------------------------------------
    def init(self, rng: jax.Array, sample_ids: jax.Array) -> Pytree:
        import flax.linen as nn

        from ..models.transformer import Norm

        cfg = self.config
        B, S = sample_ids.shape
        x = jnp.zeros((1, S, cfg.hidden_size), cfg.dtype)
        pos = jnp.zeros((1, S), jnp.int32)

        r_embed, r_pos, r_blocks, r_norm, r_head = jax.random.split(rng, 5)

        if self.period == 1:
            blocks = stack_layer_params(self._block_mod, r_blocks,
                                        cfg.num_layers, x, pos)
        else:
            # one stacked tree per pattern slot: slot j holds layers
            # j, j+p, j+2p, ... ([L/p] leading dim, pipe-sharded)
            rs = jax.random.split(r_blocks, self.period)
            blocks = tuple(
                stack_layer_params(self._block_mods[j], rs[j],
                                   cfg.num_layers // self.period, x, pos)
                for j in range(self.period))

        params: dict[str, Any] = {
            "embed": nn.Partitioned(
                jax.random.normal(r_embed, (cfg.vocab_size, cfg.hidden_size),
                                  jnp.float32) * 0.02,
                names=("vocab", "embed")),
            "blocks": blocks,
            "ln_final": Norm(cfg).init(r_norm, x)["params"],
        }
        if cfg.position_embedding == "learned":
            params["pos_embed"] = nn.Partitioned(
                jax.random.normal(r_pos, (cfg.max_seq_len, cfg.hidden_size),
                                  jnp.float32) * 0.02,
                names=(None, "embed"))
        if not cfg.tie_embeddings:
            params["unembed"] = nn.Partitioned(
                jax.random.normal(r_head, (cfg.hidden_size, cfg.vocab_size),
                                  jnp.float32) * 0.02,
                names=("embed", "vocab"))
        return params

    # -- forward -----------------------------------------------------------
    def apply(self, params: Pytree, input_ids: jax.Array) -> jax.Array:
        """Logits only (parity-friendly). MoE aux losses are NOT returned
        here — use :meth:`apply_with_aux` (or :meth:`loss_fn`) for them;
        a mutable side channel would leak tracers out of a jitted apply."""
        return self.apply_with_aux(params, input_ids)[0]

    def apply_with_aux(self, params: Pytree, input_ids: jax.Array):
        from ..models.transformer import BATCH, EMBED, SEQ, Norm, constrain

        cfg = self.config
        M = self.num_microbatches
        B, S = input_ids.shape
        if B % M:
            raise ValueError(f"batch {B} not divisible by {M} microbatches")
        mb = B // M

        positions = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32), (B, S))
        x = params["embed"].astype(cfg.dtype)[input_ids]
        if cfg.position_embedding == "learned":
            x = x + params["pos_embed"].astype(cfg.dtype)[positions]
        x = constrain(x, BATCH, SEQ, EMBED)

        xs = constrain(x.reshape(M, mb, S, cfg.hidden_size),
                       None, BATCH, SEQ, EMBED)
        pos_mb = positions.reshape(M, mb, S)

        if self._moe:
            # MoE-in-pipeline (VERDICT r03 missing #1): each Block sows its
            # weighted aux/z losses into the flax 'losses' collection; the
            # stage accumulates them along the layer scan and the pipeline
            # sums them over (stage, microbatch) with edge-tick masking —
            # the reference composes the same totals across stages in
            # PipelineEngine (runtime/pipe/module.py:86 accepts MoE layers,
            # zero/stage_1_and_2.py:609 handles the param groups).
            # Heterogeneous (periodic) stacks scan over PATTERN GROUPS: a
            # tuple of per-slot param stacks zips through one scan, each
            # group applying the p slot modules in layer order.
            def stage_fn(local_params, x, pos):
                mods = self._block_mods

                def group(carry, slabs):
                    x, acc = carry
                    if self.period == 1:
                        slabs = (slabs,)
                    for j, mod in enumerate(mods):
                        x, var = mod.apply({"params": slabs[j]}, x, pos,
                                           mutable=["losses"])
                        for leaf in jax.tree.leaves(var.get("losses", {})):
                            acc = acc + jnp.sum(leaf)
                    return (x, acc), None

                (x, acc), _ = jax.lax.scan(
                    group, (x, jnp.float32(0)), local_params)
                return x, acc

            ys, aux_total = spmd_pipeline(
                stage_fn, params["blocks"], xs, pos_mb,
                mesh=self.topology.mesh, remat=self.remat,
                with_aux_loss=True)
            # per-microbatch losses average over M in the caller's CE; the
            # sown values are per-microbatch means, so scale to match
            aux_loss = aux_total / M
        else:
            def stage_fn(local_params, x, pos):
                def layer(x, p):
                    return self._block_mod.apply({"params": p}, x, pos), None

                x, _ = jax.lax.scan(layer, x, local_params)
                return x

            ys = spmd_pipeline(stage_fn, params["blocks"], xs, pos_mb,
                               mesh=self.topology.mesh, remat=self.remat)
            aux_loss = None
        x = constrain(ys.reshape(B, S, cfg.hidden_size), BATCH, SEQ, EMBED)

        x = Norm(cfg).apply({"params": params["ln_final"]}, x)
        if cfg.tie_embeddings:
            logits = jnp.einsum("bse,ve->bsv", x, params["embed"].astype(cfg.dtype))
        else:
            logits = jnp.einsum("bse,ev->bsv", x, params["unembed"].astype(cfg.dtype))
        return constrain(logits, BATCH, SEQ, None), aux_loss

    # -- engine plumbing ---------------------------------------------------
    def loss_fn(self, params: Pytree, batch: dict) -> jax.Array:
        from ..models.loss import IGNORE_INDEX, cross_entropy_lm

        ids = batch["input_ids"]
        labels = batch.get("labels")
        if labels is None:
            labels = jnp.concatenate(
                [ids[:, 1:], jnp.full_like(ids[:, :1], IGNORE_INDEX)], axis=1)
        logits, aux_loss = self.apply_with_aux(params, ids)
        loss = cross_entropy_lm(logits, labels)
        if aux_loss is not None:
            loss = loss + aux_loss
        return loss


def initialize_pipelined(model_config, config, topology=None,
                         num_microbatches: int | None = None, **kwargs):
    """Bring-up for the pipelined flagship: builds PipelinedTransformerLM,
    inits params into the planner's sharded layout, and returns the standard
    ``(engine, optimizer, dataloader, lr_scheduler)`` tuple.

    The pipeline consumes ``num_microbatches`` per ``train_batch`` (default:
    gradient_accumulation_steps, matching reference PipelineEngine
    train_batch semantics, pipe/engine.py:337); the engine's own GAS loop is
    set to 1 — the pipeline IS the microbatch loop.
    """
    from ..config import Config
    from ..parallel.topology import MeshTopology
    from ..runtime.engine import DeepSpeedEngine

    cfg = Config.load(config)
    topo = topology or MeshTopology(cfg.mesh)
    gas = cfg.gradient_accumulation_steps
    M = num_microbatches or (gas if isinstance(gas, int) else 1)
    model = PipelinedTransformerLM(model_config, topo, M)

    micro = cfg.train_micro_batch_size_per_gpu
    if not isinstance(micro, int):
        raise ValueError("pipelined initialize needs an explicit "
                         "train_micro_batch_size_per_gpu")
    B = micro * M * (topo.size("data") * topo.size("expert") * topo.size("fsdp"))
    S = model_config.max_seq_len
    sample = jnp.zeros((B, min(S, 128)), jnp.int32)
    params = model.init(jax.random.PRNGKey(cfg.seed), sample)

    # the pipeline IS the microbatch loop: fold GAS into the per-call batch
    cfg.gradient_accumulation_steps = 1
    cfg.train_micro_batch_size_per_gpu = micro * M
    cfg.train_batch_size = B

    engine = DeepSpeedEngine(config=cfg, loss_fn=model.loss_fn, params=params,
                             topology=topo, **kwargs)
    engine.pipeline_model = model
    logger.info(f"pipelined engine: stages={topo.size('pipe')} "
                f"microbatches={M} layers/stage="
                f"{model_config.num_layers // topo.size('pipe')}")
    return engine, engine.optimizer, None, engine.lr_schedule
