"""The training engine.

TPU-native re-design of /root/reference/deepspeed/runtime/engine.py
(``DeepSpeedEngine`` :182). The reference engine is an imperative wrapper
around a torch module: ``forward`` (:1838) runs the module with hooks pulling
ZeRO shards in, ``backward`` (:1977) drives hook-based reduce-scatter,
``step`` (:2176) runs the partitioned optimizer. Here the same contract is a
*compiled program*: the whole microbatch loop — forward, backward,
gradient accumulation, reduction, optimizer — is one jitted SPMD function
whose sharding layout implements the configured ZeRO stage (see
runtime/zero/planner.py), and XLA schedules the collectives the reference
issues by hand.

API parity:
- ``initialize(...)`` → (engine, optimizer, dataloader, lr_scheduler)
  (reference deepspeed/__init__.py:69)
- ``engine.train_batch(batch)`` — full global batch incl. grad accumulation
  (the pipeline engine's contract, runtime/pipe/engine.py:337, which is the
  saner primitive under jit)
- ``engine.forward`` / ``engine.backward`` / ``engine.step`` — the eager
  triplet, expressed as separate jitted grad-accumulate/apply programs
- ``engine.save_checkpoint`` / ``load_checkpoint`` (reference :3109/:2763)
"""
from __future__ import annotations

import dataclasses
from contextlib import contextmanager, nullcontext
from functools import partial
from typing import Any, Callable, NamedTuple

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental.layout import Layout, with_layout_constraint
from jax.sharding import NamedSharding, PartitionSpec as P

from ..config import Config
from ..models.loss import lm_loss_fn
from ..models.transformer import (ModelConfig, default_activation_rules,
                                  training_attention_formulation,
                                  training_flash_plan)
from ..ops.optimizers import OptState, Optimizer, build_optimizer
from ..ops.remat import AUTO as REMAT_AUTO, REMAT_LADDER
from ..parallel.topology import BATCH_AXES, MeshTopology
from ..profiling.trace import (_abstract, books_its_build, engine_build,
                               register_program)
from ..utils.annotations import device_scope
from ..utils.logging import log_dist, logger
from ..utils.timer import (
    BACKWARD_GLOBAL_TIMER,
    BACKWARD_MICRO_TIMER,
    FORWARD_GLOBAL_TIMER,
    FORWARD_MICRO_TIMER,
    STEP_GLOBAL_TIMER,
    STEP_MICRO_TIMER,
    TRAIN_BATCH_TIMER,
    SynchronizedWallClockTimer,
    ThroughputTimer,
)
from . import activation_checkpointing as _ac_mod
from . import fp16 as fp16_mod
from .fp16 import ScalerState
from .lr_schedules import Schedule, build_scheduler, constant_lr
from .zero.planner import ZeroPlan, build_plan, unbox_params

Pytree = Any


class TrainState(NamedTuple):
    """The engine's entire mutable state — one sharded pytree.

    ``params``: compute-precision (bf16/fp16) weights, sharded per ZeRO
    stage. ``master``: fp32 master copy sharded over ``fsdp`` from stage 1
    (None in pure-fp32 mode, where ``params`` is the master). ``opt_state``:
    moments, sharded like master. ``scaler``: fp16 dynamic loss scale.
    """
    params: Pytree
    master: Pytree | None
    opt_state: OptState
    scaler: ScalerState | None
    global_step: jax.Array


def _cast_tree(tree: Pytree, dtype) -> Pytree:
    return jax.tree.map(
        lambda x: x.astype(dtype) if jnp.issubdtype(x.dtype, jnp.floating) else x, tree)


def _global_norm(tree: Pytree) -> jax.Array:
    leaves = [jnp.sum(jnp.square(l.astype(jnp.float32))) for l in jax.tree.leaves(tree)]
    return jnp.sqrt(jnp.sum(jnp.stack(leaves)))


class DeepSpeedEngine:
    @books_its_build
    def __init__(self,
                 config: Config,
                 model: nn.Module | None = None,
                 loss_fn: Callable[[Pytree, dict], jax.Array] | None = None,
                 params: Pytree | None = None,
                 topology: MeshTopology | None = None,
                 sample_batch: dict | None = None,
                 rng: jax.Array | None = None,
                 activation_rules: list | None = None):
        self.config = config
        self.model = model
        if topology is not None and (
                config.zero_optimization.mics_shard_size > 0
                or config.zero_optimization.zero_hpz_partition_size > 1):
            raise ValueError(
                "mics_shard_size / zero_hpz_partition_size require the "
                "engine to build the mesh (both re-spec the fsdp/data "
                "axes) — pass the mesh via config['mesh'] instead of a "
                "prebuilt topology")
        self._hpz_folded = False
        if topology is not None:
            self.topology = topology
        else:
            self.topology, self._hpz_folded = self._build_topology(config)
        config.resolve_batch_terms(self.topology.dp_world_size)

        # activation checkpointing: flip the model zoo's remat switch from the
        # DeepSpeed-style config section (reference checkpointing.py:893)
        ac = config.activation_checkpointing
        if ac.policy != "none" and model is not None and hasattr(model, "config") \
                and hasattr(model.config, "remat"):
            if loss_fn is not None:
                logger.warning(
                    "activation_checkpointing is configured but a custom "
                    "loss_fn was supplied — the engine cannot rewire a loss "
                    "closure; apply ops/remat.py policies (or cfg.remat) in "
                    "your own model for checkpointing to take effect")
            else:
                self.model = model = model.clone(config=dataclasses.replace(
                    model.config, remat=True, remat_policy=ac.policy))
        if ac.partition_activations and self.topology.size("seq") <= 1:
            logger.warning("partition_activations=True but the mesh has no "
                           "'seq' axis — activations stay unpartitioned")
        _ac_mod.configure(ac)

        self._custom_loss_fn = loss_fn is not None
        if loss_fn is None:
            if model is None:
                raise ValueError("need a model or a loss_fn")
            loss_fn = partial(lm_loss_fn, model)
        self._raw_loss_fn = loss_fn
        self._rules = activation_rules or default_activation_rules(self.topology)
        # ring collective-matmul TP (parallel/tensor.py): hide the
        # row-parallel projections' all-reduce under ring-overlapped
        # partial GEMMs. GSPMD-path only — the spmd_pipeline / ZeRO++
        # shard_map paths would nest manual regions (pipe>1 requires
        # tensor==1 there anyway), and the models consult the scope at
        # trace time, so installing it around the loss is the whole wiring.
        self._tp_overlap = bool(
            config.tensor_parallel.overlap
            and self.topology.size("tensor") > 1
            and self.topology.size("pipe") == 1)

        # precision regime (reference engine dtype checks :1101)
        self.fp16_enabled = config.fp16.enabled
        self.bf16_enabled = config.bf16.enabled and not self.fp16_enabled
        self.compute_dtype = config.compute_dtype
        self.mixed_precision = self.fp16_enabled or self.bf16_enabled

        # optimizer + schedule (reference _configure_optimizer :1272)
        self.optimizer: Optimizer = build_optimizer(config.optimizer.type,
                                                    config.optimizer.params)
        base_lr = config.optimizer.params.get("lr", getattr(self.optimizer, "lr", 1e-3))
        if config.scheduler is not None:
            self.lr_schedule: Schedule = build_scheduler(
                config.scheduler.type, config.scheduler.params, base_lr=base_lr)
        else:
            self.lr_schedule = constant_lr(base_lr)

        # timers / throughput (reference EngineTimers :147)
        self.timers = SynchronizedWallClockTimer()
        self.tput_timer = ThroughputTimer(
            batch_size=config.train_batch_size,
            steps_per_output=config.steps_per_print)

        if config.comms_logger.enabled:
            from ..comm import configure_comms_logger

            configure_comms_logger(enabled=True, verbose=config.comms_logger.verbose,
                                   debug=config.comms_logger.debug)

        # flops profiler, fired once at profile_step (reference engine.py:1867)
        self.flops_profiler = None
        if config.flops_profiler.enabled:
            from ..profiling import FlopsProfiler

            self.flops_profiler = FlopsProfiler(config.flops_profiler)

        # data efficiency: curriculum learning + random-LTD (reference
        # runtime/data_pipeline/; engine curriculum hook engine.py:1913)
        self.curriculum_scheduler = None
        self.random_ltd_scheduler = None
        de = config.data_efficiency
        if de.enabled:
            cl = de.curriculum_config()
            if cl is not None:
                from .data_pipeline import CurriculumScheduler

                self.curriculum_scheduler = CurriculumScheduler(cl)
                if self.curriculum_scheduler.curriculum_type != "seqlen":
                    logger.warning(
                        "engine only auto-applies 'seqlen' curricula to "
                        "batches; use CurriculumDataSampler for metric "
                        f"'{self.curriculum_scheduler.curriculum_type}'")
            rl = de.random_ltd_config()
            if rl is not None:
                from .data_pipeline import RandomLTDScheduler

                self.random_ltd_scheduler = RandomLTDScheduler(rl)
                logger.warning(
                    "random_ltd: scheduler active, but the engine does not "
                    "auto-convert model layers — call random_ltd_select/"
                    "random_ltd_merge in your blocks with "
                    "engine.random_ltd_scheduler.get_seq_len(step) "
                    "(the reference likewise requires convert_to_random_ltd)")

        # host-offloaded optimizer (ZeRO-Offload/-Infinity; reference
        # stage_1_and_2.py:1190 CPU path + swap_tensor/)
        self._offload_opt = None
        off = config.zero_optimization.offload_optimizer
        if off.device in ("cpu", "nvme"):
            if self.fp16_enabled:
                raise ValueError("offload_optimizer requires bf16/fp32 "
                                 "(dynamic loss scaling is device-side)")
            from .zero.offload import HostOffloadOptimizer

            self._offload_opt = HostOffloadOptimizer(
                config.optimizer.type, config.optimizer.params, off,
                compute_dtype=self.compute_dtype if self.mixed_precision
                else jnp.float32)
        elif off.device not in ("none",):
            raise ValueError(f"offload_optimizer.device '{off.device}' "
                             f"unsupported (none|cpu|nvme)")

        # ZeRO-Infinity parameter offload: host-resident params streamed
        # layer-by-layer (reference swap_tensor/partitioned_param_swapper.py:37)
        self._param_stream = None
        poff = config.zero_optimization.offload_param
        if poff.device in ("cpu", "nvme"):
            if self._offload_opt is None:
                raise ValueError(
                    "offload_param requires offload_optimizer (cpu|nvme): "
                    "streamed params update on the host master")
            if self._offload_opt.ratio != 1.0:
                raise ValueError(
                    "offload_param requires offload_optimizer.ratio == 1.0 "
                    "(a Twin-Flow device share would keep streamed params "
                    "resident)")
            if poff.device == "nvme" and self._offload_opt.device != "nvme":
                raise ValueError("offload_param.device='nvme' requires "
                                 "offload_optimizer.device='nvme' (shared "
                                 "async-I/O engine)")
            if self._custom_loss_fn or model is None:
                raise ValueError(
                    "offload_param drives the model layer-by-layer — pass "
                    "model= (a TransformerLM) without a custom loss_fn")
            bad = [a for a in ("tensor", "seq", "pipe", "expert")
                   if self.topology.size(a) > 1]
            if bad:
                raise ValueError(f"offload_param streaming needs a pure DP "
                                 f"mesh (fsdp x data); axes {bad} have "
                                 f"size > 1")
            from .zero.infinity import LayerStreamTrainer

            self._param_stream = LayerStreamTrainer(
                model, config, self.topology, self._offload_opt,
                self.compute_dtype if self.mixed_precision else jnp.float32)
        elif poff.device not in ("none",):
            raise ValueError(f"offload_param.device '{poff.device}' "
                             f"unsupported (none|cpu|nvme)")

        self._validate_zeropp()

        # fault tolerance (runtime/resilience.py): divergence sentinel,
        # preemption-safe saves, hang watchdog, fault injection. Built
        # before the programs — the sentinel decides whether train steps
        # carry the fused non-finite skip.
        from .resilience import ResilienceManager

        self.resilience = ResilienceManager(self, config.resilience)
        self._monitor_master = None   # lazy MonitorMaster (monitor/)

        # telemetry (telemetry/): spans + SLO/health metrics + MFU/goodput
        # + flight recorder. The process-wide instance is shared with
        # engine_v2 / checkpointing / resilience so /metrics is one pane;
        # configure() mutates it in place when this engine enables it.
        from .. import telemetry as _telemetry

        if config.telemetry.enabled:
            _telemetry.configure(config.telemetry)
        self._telem = _telemetry.get_telemetry()
        self._mfu_tracker: _telemetry.MFUTracker | None = None
        self._step_flops: float | None = None  # lazy XLA cost-model read
        if self._telem.enabled:
            peak = (config.telemetry.peak_tflops * 1e12
                    if config.telemetry.peak_tflops
                    else _telemetry.device_peak_flops())
            self._mfu_tracker = _telemetry.MFUTracker(peak_flops=peak)
            self._telem.set_health(job="train",
                                   zero_stage=config.zero_optimization.stage)
        self._resume_tag: str | None = None
        self._ckpt_commit_error = None

        # ---- state bring-up (reference _configure_distributed_model :1137)
        self._init_state(params, sample_batch, rng)
        engine_build(type(self).__name__).phase("programs")
        self.remat_plan = self._build_judged_programs()
        self.attention_formulation = self._announce_attention()

        # imperative-API grad buffer (forward/backward/step triplet)
        self._accum_grads: Pytree | None = None
        self._accum_count = 0
        self._last_loss: jax.Array | None = None
        self.global_steps = int(self.state.global_step)

        logger.info(
            f"engine up: zero_stage={config.zero_optimization.stage} "
            f"dtype={'fp16' if self.fp16_enabled else 'bf16' if self.bf16_enabled else 'fp32'} "
            f"micro_bs={config.train_micro_batch_size_per_gpu} "
            f"gas={config.gradient_accumulation_steps} "
            f"global_bs={config.train_batch_size} mesh={self.topology.axis_sizes}")

    def _build_judged_programs(self) -> dict | None:
        """Build the step programs and decide what a rematted block SAVES
        for its backward pass. ``remat=True`` with ``remat_policy="auto"``
        (the default) walks ``ops/remat.py:REMAT_LADDER`` from the top: the
        step is built with a rung, lowered and compiled from abstract
        arguments — the executable the first ``train_batch`` then finds in
        jit's cache, so judging adds no lowering — and kept if its
        arguments, temporaries and code fit the device's limit less
        ``activation_checkpointing.STEP_HEADROOM_BYTES``; otherwise one
        rung down. Returns the plan (``engine.remat_plan``): the policy
        chosen and why, and each rung tried with the compiled step's bytes
        and the limit it was held to. None where nothing is rematted (or
        the model is not the zoo's); a pinned policy is never judged."""
        mcfg = getattr(self.model, "config", None)
        if self._custom_loss_fn or not isinstance(mcfg, ModelConfig) \
                or not mcfg.remat:
            self._build_programs()
            return None
        plan = {"chosen": mcfg.remat_policy, "rung": None, "why": "",
                "limit_bytes": None, "tried": []}
        # the rungs to walk; the last of them is taken without a judgement
        limit = None
        if mcfg.remat_policy != REMAT_AUTO:
            ladder = (mcfg.remat_policy,)
            unjudged = "pinned by remat_policy: not judged"
        elif self._param_stream is not None or self._offload_opt is not None:
            # paths that exist because memory is short, with no one compiled
            # train step to judge: what remat=True kept before the ladder
            ladder = REMAT_LADDER[-1:]
            unjudged = "host-offload path (no compiled train step): not judged"
        else:
            limit = plan["limit_bytes"] = _ac_mod.device_memory_limit()
            ladder = REMAT_LADDER if limit is not None else REMAT_LADDER[:1]
            unjudged = ("last rung (what remat=True kept before the ladder): "
                        "not judged" if limit is not None else
                        "the backend reports no memory limit: first rung, "
                        "not judged")
        headroom = _ac_mod.STEP_HEADROOM_BYTES
        for policy in ladder:
            self._pin_remat(policy)
            self._build_programs()
            if policy == ladder[-1]:
                plan.update(chosen=policy, why=unjudged)
                break
            tried = {"policy": policy, "limit_bytes": limit,
                     "headroom_bytes": headroom}
            plan["tried"].append(tried)
            try:
                compiled = self._train_step.lower(
                    *self._abstract_step_args()).compile()
            except jax.errors.JaxRuntimeError as e:
                if "RESOURCE_EXHAUSTED" not in str(e):
                    raise
                # XLA refuses to compile a step that cannot fit
                tried.update(fits=False, why="the compiler ran out of "
                             "memory: " + str(e).splitlines()[0][:200])
                continue
            tried.update(_ac_mod.step_memory(compiled))
            tried["fits"] = tried["step_bytes"] <= limit - headroom
            verdict = (f"step {tried['step_bytes']} B (arguments "
                       f"{tried['argument_bytes']}, temporaries "
                       f"{tried['temp_bytes']}) %s the limit {limit} B less "
                       f"{headroom}")
            if tried["fits"]:
                plan.update(chosen=policy, why=verdict % "fits")
                break
            tried["why"] = verdict % "over"
        if mcfg.remat_policy == REMAT_AUTO:
            plan["rung"] = REMAT_LADDER.index(plan["chosen"])
        return self._announce_remat(plan)

    def _pin_remat(self, policy: str) -> None:
        """Trace the model with ``policy`` from here on (the engine's own
        clone: the caller's module is left as it was)."""
        self.model = self.model.clone(config=dataclasses.replace(
            self.model.config, remat_policy=policy))
        self._raw_loss_fn = partial(lm_loss_fn, self.model)

    def _abstract_step_args(self) -> tuple:
        """``(state, batch)`` as ``train_batch`` hands them to the jitted
        step, abstract: committed leaves with their sharding, the batch
        with the sample batch's trailing dims under the gas dim."""
        cfg = self.config
        gas = cfg.gradient_accumulation_steps
        lead = (gas, cfg.train_batch_size // gas)
        batch = jax.tree.map(lambda x: jax.ShapeDtypeStruct(
            lead + tuple(x.shape[1:]), jax.dtypes.canonicalize_dtype(x.dtype),
            sharding=self._batch_sharding(1 + x.ndim, with_gas_dim=True)),
            self._sample_batch)
        return jax.tree.map(_abstract, self.state), batch

    def _announce_remat(self, plan: dict) -> dict:
        """One log line and, under telemetry, two gauges: the rung's index
        in the ladder (−1: pinned) and the chosen step's temporary bytes
        (0 where it was not compiled to be judged)."""
        judged = [t for t in plan["tried"] if t["policy"] == plan["chosen"]]
        logger.info(
            f"remat: every block checkpointed, keeping "
            f"{plan['chosen']!r} — {plan['why']}" + "".join(
                f"; {t['policy']!r} refused: {t['why']}"
                for t in plan["tried"] if not t["fits"]))
        if self._telem.enabled:
            reg = self._telem.registry
            reg.gauge("train_remat_rung").set(
                -1 if plan["rung"] is None else plan["rung"])
            reg.gauge("train_step_temp_bytes").set(
                judged[0]["temp_bytes"] if judged else 0)
        return plan

    def _announce_attention(self) -> tuple[str, str] | None:
        """Log ONCE which attention formulation the train step will trace
        and, when it is not the flash kernel, why — ``attn_impl="auto"``
        otherwise falls through to XLA attention silently. Asked inside
        what the step traces the model under (:meth:`_model_scope`; the
        ZeRO++ and 1-bit steps make the DP axes manual and hand the model
        a shard's rows). Where it is the flash kernel, one ``flash:`` line
        more says what the kernel does with a shard's shapes
        (``engine.flash_plan``, the launcher's own ``FlashPlan``: blocks,
        compute tile, backward form, the share of the score square
        computed). Returns ``(formulation, reason)``; None for models
        that are not the zoo's TransformerLM (custom loss_fn / foreign
        modules)."""
        mcfg = getattr(self.model, "config", None)
        self.flash_plan = None
        if self._custom_loss_fn or not isinstance(mcfg, ModelConfig):
            return None
        topo = self.topology
        rows = self.config.train_micro_batch_size_per_gpu
        manual = tuple(a for a in BATCH_AXES if topo.size(a) > 1) \
            if self._use_zeropp_comm() or self._use_onebit_comm() else ()
        if not manual:
            rows *= topo.dp_world_size
        seq = self._sample_batch["input_ids"].shape[1]
        # the ZeRO-Infinity layer streamer traces its blocks under neither
        # rules nor mesh (runtime/zero/infinity.py)
        with nullcontext() if self._param_stream is not None \
                else self._model_scope(manual):
            chosen, why_not = training_attention_formulation(
                mcfg, rows, seq, manual_axes=manual)
            self.flash_plan = training_flash_plan(
                mcfg, rows, seq, manual_axes=manual)
        logger.info(f"attention: attn_impl={mcfg.attn_impl!r} runs " + (
            "the Pallas flash kernel" + (
                " per shard" if topo.mesh.size > 1 else "")
            if chosen == "pallas" else f"XLA attention — {why_not}"))
        if self.flash_plan is not None:
            logger.info(f"flash: {self.flash_plan.describe()}")
        return chosen, why_not

    # ------------------------------------------------------------------
    def _validate_zeropp(self):
        """ZeRO++ flag validation — unsupported combinations raise instead
        of silently running dense (reference stage3.py:155-157 enables the
        same features only on its stage-3 path)."""
        z = self.config.zero_optimization
        if not (z.zero_quantized_gradients or z.zero_quantized_weights):
            return
        from .onebit import OneBitAdam

        if z.zero_quantized_gradients and z.stage < 2:
            raise ValueError("zero_quantized_gradients (qgZ) needs ZeRO "
                             "stage >= 2 (gradients must be partitioned)")
        if z.zero_quantized_weights and z.stage < 3:
            raise ValueError("zero_quantized_weights (qwZ) needs ZeRO "
                             "stage 3 (weights must be partitioned)")
        if self.fp16_enabled:
            raise ValueError("ZeRO++ quantized comm requires bf16/fp32 "
                             "(loss-scaled fp16 grads don't survive int8 "
                             "transport)")
        if self._offload_opt is not None:
            raise ValueError("ZeRO++ quantized comm does not compose with "
                             "offload_optimizer yet")
        if isinstance(self.optimizer, OneBitAdam):
            raise ValueError("ZeRO++ quantized comm and 1-bit optimizers "
                             "are mutually exclusive compression schemes")
        bad = [a for a in ("tensor", "seq", "pipe", "expert")
               if self.topology.size(a) > 1]
        if bad:
            raise ValueError(f"ZeRO++ quantized comm needs a pure DP mesh "
                             f"(fsdp x data); axes {bad} have size > 1")
        if self.topology.size("fsdp") <= 1:
            logger.warning("ZeRO++ flags set but the fsdp axis is 1 — "
                           "quantized comm is a no-op, running dense")

    @staticmethod
    def _build_topology(config: Config) -> tuple[MeshTopology, bool]:
        """Mesh construction with the MiCS/hpZ transforms; returns
        ``(topology, hpz_folded)`` — the second element is the single
        source of truth for whether hpZ master re-sharding applies (the
        planner must not re-derive it from config alone).

        MiCS (reference
        runtime/zero/mics.py:64 `MiCS_Init`): ``mics_shard_size=p`` shards
        ZeRO state over sub-groups of p devices and replicates across the
        groups. Under GSPMD that IS a mesh re-spec — the fsdp axis shrinks
        to p (it sits innermost of the DP axes in AXIS_ORDER, i.e. on
        ICI-adjacent devices) and the group count multiplies the data axis,
        so gathers ride ICI within a group while gradient reduction spans
        groups hierarchically. The reference needs bespoke hierarchical
        allgather code for this; XLA derives it from the sharding."""
        topo = MeshTopology(config.mesh)
        mics = config.zero_optimization.mics_shard_size
        hpz = config.zero_optimization.zero_hpz_partition_size
        if mics and mics > 0 and hpz and hpz > 1:
            raise ValueError(
                "mics_shard_size and zero_hpz_partition_size both re-spec "
                "the fsdp axis — pick one (MiCS replicates the whole ZeRO "
                "state per group; hpZ only the compute param copy)")

        def fold_fsdp(group: int, feature: str) -> MeshTopology:
            """Shrink fsdp to ``group`` (innermost of the DP axes in
            AXIS_ORDER = ICI-adjacent) and fold the group count into data.
            Shared by MiCS and hpZ so both validate identically."""
            fs = topo.size("fsdp")
            if fs % group:
                raise ValueError(f"{feature} {group} must divide the fsdp "
                                 f"axis ({fs})")
            sizes = dict(topo.axis_sizes)
            sizes["fsdp"] = group
            sizes["data"] = sizes.get("data", 1) * (fs // group)
            return MeshTopology(sizes)

        if hpz and hpz > 1:
            # hpZ (ZeRO++ secondary tensor partition, reference
            # stage3.py:155,495): the COMPUTE param copy shards over an
            # ICI-adjacent subgroup of hpz devices so forward/backward
            # all-gathers never leave the fast domain, while master/opt
            # keep the full primary partition (the planner shards them
            # over data x fsdp jointly — see build_plan).
            if config.zero_optimization.stage != 3:
                raise ValueError("zero_hpz_partition_size needs ZeRO "
                                 "stage 3 (it re-partitions stage-3 param "
                                 "gathers)")
            fs = topo.size("fsdp")
            if fs == hpz:
                logger.info("hpZ: partition size equals the fsdp axis — "
                            "secondary == primary, nothing to re-spec")
                return topo, False
            new = fold_fsdp(hpz, "zero_hpz_partition_size")
            logger.info(f"hpZ: param gathers now span {hpz}-device ICI "
                        f"groups; primary partition stays {fs}-wide over "
                        f"data x fsdp (mesh now {new.axis_sizes})")
            return new, True
        if mics is None or mics <= 0:
            return topo, False
        if config.zero_optimization.stage < 1:
            raise ValueError("mics_shard_size needs ZeRO stage >= 1")
        fs = topo.size("fsdp")
        if fs == mics:
            return topo, False
        new = fold_fsdp(mics, "mics_shard_size")
        logger.info(f"MiCS: fsdp {fs} -> shard groups of {mics}, "
                    f"{fs // mics}x replication folded into data "
                    f"(mesh now {new.axis_sizes})")
        return new, False

    def _init_state(self, params, sample_batch, rng):
        cfg = self.config
        topo = self.topology
        if rng is None:
            rng = jax.random.PRNGKey(cfg.seed)
        # independent stream for train-time stochastic layers (dropout /
        # noisy gating) — never touches the init stream
        self._train_rng_base = jax.random.fold_in(rng, 0x5eed)

        init_input = None
        if self.model is not None:
            if sample_batch is None:
                sample_batch = {"input_ids": jnp.zeros(
                    (cfg.train_micro_batch_size_per_gpu * topo.dp_world_size,
                     getattr(self.model.config, "max_seq_len", 128)), jnp.int32)}
            init_input = sample_batch["input_ids"]
            abstract = jax.eval_shape(
                lambda r: self.model.init(r, init_input), rng)["params"]
        elif params is not None:
            abstract = params
        else:
            raise ValueError("need a model or initial params")

        self.plan: ZeroPlan = build_plan(topo, cfg.zero_optimization, abstract,
                                         hpz_active=self._hpz_folded)
        self._sample_batch = sample_batch
        self._abstract_master = jax.eval_shape(
            lambda t: _cast_tree(unbox_params(t), jnp.float32), abstract)

        master_shardings = self.plan.master_shardings
        param_shardings = self.plan.param_shardings

        if self._param_stream is not None:
            # ZeRO-Infinity: init on the HOST CPU backend — the full master
            # never touches HBM (the zero.Init analogue for a model that
            # doesn't fit it)
            self._init_state_param_stream(params, init_input, rng)
            return

        if params is None:
            # init directly into the sharded layout — no full replica ever
            # materializes (the role of zero.Init, partition_parameters.py:808)
            def init_fn(r):
                p = unbox_params(self.model.init(r, init_input)["params"])
                return _cast_tree(p, jnp.float32)

            with jax.transfer_guard("allow"):
                master0 = jax.jit(init_fn, out_shardings=master_shardings)(rng)
        else:
            params = unbox_params(params)
            master0 = jax.device_put(_cast_tree(params, jnp.float32), master_shardings)

        if self._offload_opt is not None:
            # master + moments move to the host; the device keeps only the
            # compute-dtype params (ZeRO-Offload memory model)
            if self.mixed_precision:
                params0 = jax.jit(lambda m: _cast_tree(m, self.compute_dtype),
                                  out_shardings=param_shardings)(master0)
            else:
                params0 = jax.jit(lambda m: m, out_shardings=param_shardings)(master0)
            self._offload_opt.init_from_master(master0)
            del master0
            self.state = TrainState(
                params=params0, master=None,
                opt_state=OptState(step=jnp.zeros((), jnp.int32), mu=None, nu=None),
                scaler=None, global_step=jnp.zeros((), jnp.int32))
            self._state_shardings = TrainState(
                params=param_shardings, master=None,
                opt_state=OptState(step=NamedSharding(topo.mesh, P()),
                                   mu=None, nu=None),
                scaler=None,
                global_step=NamedSharding(topo.mesh, P()),
            )
            return

        opt_sh = self._opt_shardings_for(master_shardings)
        opt_init_fn, opt_sh = self._wrap_opt_init(opt_sh)
        opt0 = jax.jit(opt_init_fn, out_shardings=opt_sh)(master0)

        if self.mixed_precision:
            params0 = jax.jit(lambda m: _cast_tree(m, self.compute_dtype),
                              out_shardings=param_shardings)(master0)
            master = master0
        else:
            params0 = jax.jit(lambda m: m, out_shardings=param_shardings)(master0)
            master = None

        scaler = fp16_mod.init_scaler(cfg.fp16) if self.fp16_enabled else None
        self.state = TrainState(params=params0, master=master, opt_state=opt0,
                                scaler=scaler, global_step=jnp.zeros((), jnp.int32))
        self._state_shardings = TrainState(
            params=param_shardings,
            master=master_shardings if master is not None else None,
            opt_state=opt_sh,
            scaler=None if scaler is None else jax.tree.map(
                lambda _: NamedSharding(topo.mesh, P()), scaler),
            global_step=NamedSharding(topo.mesh, P()),
        )
        # every leaf COMMITTED to its steady-state sharding before the
        # first step. An uncommitted counter lowers without a sharding
        # annotation, so step 1 (this fresh state) and step 2 (the step's
        # own outputs) were two different modules, and the whole train
        # step compiled twice (gpt2-350m on a v5e: 69 s at step 2).
        self.state = jax.device_put(self.state, self._state_shardings)

    def _init_state_param_stream(self, params, init_input, rng):
        """ZeRO-Infinity state bring-up: master initializes on the host CPU
        backend, moves into the host optimizer + bf16 stream cache, and
        ``state.params`` becomes the host-resident numpy tree (checkpoints
        serialize it like any pytree; no jitted program ever receives it)."""
        topo = self.topology
        if params is None:
            try:
                cpu0 = jax.devices("cpu")[0]
            except RuntimeError:
                cpu0 = None
            ctx = jax.default_device(cpu0) if cpu0 is not None else \
                jax.transfer_guard("allow")
            with ctx:
                master0 = jax.jit(lambda r: _cast_tree(
                    unbox_params(self.model.init(r, init_input)["params"]),
                    jnp.float32))(rng)
        else:
            master0 = _cast_tree(unbox_params(params), jnp.float32)
        master_np = jax.tree.map(lambda a: np.asarray(a), master0)
        del master0
        self._offload_opt.init_from_master(master_np)
        self._param_stream.init_from_master(master_np)
        del master_np
        self.state = TrainState(
            params=self._param_stream.params_view(), master=None,
            opt_state=OptState(step=jnp.zeros((), jnp.int32), mu=None,
                               nu=None),
            scaler=None, global_step=jnp.zeros((), jnp.int32))
        self._state_shardings = TrainState(
            params=None, master=None,
            opt_state=OptState(step=NamedSharding(topo.mesh, P()), mu=None,
                               nu=None),
            scaler=None, global_step=NamedSharding(topo.mesh, P()))

    def _wrap_opt_init(self, opt_shardings):
        """1-bit error feedback is per-DP-member state. When the compressed
        path is active, the init stacks it with a leading DP dim sharded
        over the DP axes (so checkpoints carry every member's error); in
        the dense fallback the buffer is dropped INSIDE the jitted init, so
        XLA dead-code-eliminates it and no transient params-sized zeros
        ever materialize."""
        from .onebit import OneBitAdam

        if not isinstance(self.optimizer, OneBitAdam) \
                or opt_shardings.error is None:
            return self.optimizer.init, opt_shardings
        topo = self.topology
        if not self._use_onebit_comm():
            def init_dense(m):
                return self.optimizer.init(m)._replace(error=None)

            return init_dense, opt_shardings._replace(error=None)

        dp_axes = tuple(a for a in BATCH_AXES if topo.size(a) > 1)
        dp = topo.dp_world_size

        def init_stacked(m):
            o = self.optimizer.init(m)
            err = jax.tree.map(
                lambda e: jnp.zeros((dp,) + e.shape, jnp.float32), o.error)
            return o._replace(error=err)

        is_sh = lambda x: isinstance(x, NamedSharding)
        err_sh = jax.tree.map(lambda _: NamedSharding(topo.mesh, P(dp_axes)),
                              opt_shardings.error, is_leaf=is_sh)
        return init_stacked, opt_shardings._replace(error=err_sh)

    def _opt_shardings_for(self, master_shardings):
        # OptState moments mirror master shardings; absent moments stay None.
        repl = NamedSharding(self.topology.mesh, P())
        probe = jax.eval_shape(self.optimizer.init, self._abstract_master)
        return OptState(
            step=repl,
            mu=None if probe.mu is None else master_shardings,
            nu=None if probe.nu is None else master_shardings,
            error=None if probe.error is None else master_shardings,
        )

    # ------------------------------------------------------------------
    @contextmanager
    def _model_scope(self, manual_axes: tuple[str, ...] = ()):
        """Everything the model is traced under, in one place: the
        activation rules (less those on axes the step's own ``shard_map``
        has made manual — a constraint on a manual axis is illegal), the
        mesh they resolve onto (the attention dispatcher maps its kernel
        over it) and, on the GSPMD path, the TP ring-overlap scope."""
        from ..parallel.axes import model_mesh_scope
        from ..parallel.tensor import tp_overlap_scope

        rules = self._safe_manual_rules(manual_axes) if manual_axes \
            else self._rules
        with nn.logical_axis_rules(rules), \
                model_mesh_scope(self.topology.mesh), \
                (tp_overlap_scope(self.topology.mesh)
                 if self._tp_overlap and not manual_axes else nullcontext()):
            yield

    def _loss_with_rules(self, params, batch, step=None):
        """``step`` present → training call: a per-step PRNG key rides into
        the batch under '_train_rng' so stochastic layers (bert dropout,
        RSample noisy gating) can draw masks; loss fns that don't use it
        ignore the key. One key per optimizer step — microbatches within a
        GAS step share masks (they already share the step's params)."""
        fault_scale = None
        if isinstance(batch, dict) and "_fault_scale" in batch:
            batch = dict(batch)
            fault_scale = batch.pop("_fault_scale")
        if step is not None:
            batch = dict(batch)
            batch["_train_rng"] = jax.random.fold_in(self._train_rng_base,
                                                     step)
        with self._model_scope():
            loss = self._raw_loss_fn(params, batch)
        if fault_scale is not None:
            # fault-injection rail (resilience.FaultInjector.nan_scale):
            # 1.0 except at the armed step, where NaN poisons the grads
            loss = loss * jnp.mean(fault_scale)
        return loss

    def _compute_grads(self, state: TrainState, batch: dict) -> tuple[jax.Array, Pytree]:
        """One microbatch forward+backward; grads constrained per plan
        (stage ≥2 → reduce-scatter; else all-reduce) in the dtype the
        backward made them, and only then read as float32 (unscaled, under
        fp16). The cast is no pass of its own: whoever consumes the
        gradients inside the same program — the GAS scan's accumulator, or
        with nothing to accumulate the update itself — does it in registers.
        Only a program that RETURNS them (``grad_step``, the imperative
        API) writes float32 gradients to memory, as it must."""
        mgr = getattr(self, "compression_manager", None)

        def scaled_loss(p):
            if mgr is not None:
                # QAT/pruning transform inside the grad so STE gradients
                # reach the raw weights; step traced → schedule stays live
                p = mgr.transform_params(p, state.opt_state.step)
            loss = self._loss_with_rules(p, batch,
                                         step=state.opt_state.step)
            if state.scaler is not None:
                loss = loss * state.scaler.scale
            return loss

        loss, grads = jax.value_and_grad(scaled_loss)(state.params)
        grads = _cast_tree(self._constrain_grads(grads), jnp.float32)
        if state.scaler is not None:
            loss = loss / state.scaler.scale
            grads = jax.tree.map(lambda g: g / state.scaler.scale, grads)
        return loss, grads

    def _constrain_grads(self, grads: Pytree) -> Pytree:
        """The backward's gradients as the rest of the step takes them:
        sharded per plan, and laid out as the state is (row-major). A weight
        gradient leaves its matmul in whatever layout suits the matmul
        (``wq``'s ``[E, H, D]`` comes out D-major); left so, the compiler
        gives the fused update's OUTPUTS the gradient's layout and copies
        params, master and both moments back — four copies a leaf where
        re-laying the one bf16 gradient is one."""
        grads = jax.lax.with_sharding_constraint(grads, self.plan.grad_shardings)
        return jax.tree.map(
            lambda g: with_layout_constraint(
                g, Layout(major_to_minor=tuple(range(g.ndim)))), grads)

    def _apply_grads(self, state: TrainState, grads: Pytree,
                     loss_finite: jax.Array | None = None
                     ) -> tuple[TrainState, jax.Array]:
        """The tail of the step: optimizer update and the cast back to the
        compute dtype; returns ``(new_state, finite_flag)``.

        Written so that it compiles to ONE fused pass over each leaf of the
        shard. First a pre-pass (scope ``grad_check``) reads each gradient
        once for the global scalars the update waits on: the finite flag
        and, with ``gradient_clipping``, the norm. The clip factor is a
        scalar multiplied inside the update — no clipped tree is written —
        and so is a gradient's float32 cast where it arrives in the
        backward's dtype. Then (scope ``optimizer``) ``optimizer.update``,
        the skip and the cast back are one fusion a leaf with the new
        params, master and moments as its outputs.

        Under the fp16 scaler OR the resilience sentinel (bf16/fp32
        included) a non-finite step changes nothing: the update is computed
        unconditionally and each leaf of master, moments and the optimizer's
        step SELECTS old or new by the flag (a ``lax.cond`` round the update
        would be a fusion barrier: its operands and results are buffers, so
        the gradient cast, the check, the update and the cast back were four
        passes). A skipped step therefore costs a normal step's bytes
        instead of none; skips are rare — an fp16 overflow every
        ``loss_scale_window`` steps, a bf16 divergence never in a sound
        run. ``global_step`` still advances, so ``skipped_steps`` counts the
        skips host-side with no extra sync."""
        cfg = self.config
        lr = self.lr_schedule(state.opt_state.step)
        guarded = state.scaler is not None or cfg.resilience.sentinel
        with device_scope("grad_check"):
            if guarded:
                finite = fp16_mod.grads_finite(grads)
                if loss_finite is not None:
                    finite = finite & loss_finite
            else:
                finite = jnp.asarray(True)
            if cfg.gradient_clipping:
                # a non-finite gradient stays non-finite clipped (inf x 0 is
                # NaN), so checking the unclipped tree decides the same and
                # lets the check and the norm share one read
                norm = _global_norm(grads)
                clip = jnp.minimum(1.0,
                                   cfg.gradient_clipping / (norm + 1e-6))
                grads = jax.tree.map(lambda g: g * clip, grads)

        master_in = state.master if state.master is not None else state.params
        with device_scope("optimizer"):
            new_master, new_opt = self.optimizer.update(
                grads, state.opt_state, master_in, lr=lr)
            if guarded:
                def keep(new, old):
                    return jnp.where(finite, new, old)
                new_master = jax.tree.map(keep, new_master, master_in)
                new_opt = jax.tree.map(keep, new_opt, state.opt_state)
            new_master = jax.lax.with_sharding_constraint(
                new_master, self.plan.master_shardings)
            if self.mixed_precision:
                new_params = _cast_tree(new_master, self.compute_dtype)
                master_out = new_master
            else:
                new_params = new_master
                master_out = None
            new_params = jax.lax.with_sharding_constraint(
                new_params, self.plan.param_shardings)
        new_scaler = None if state.scaler is None else \
            fp16_mod.update_scaler(state.scaler, finite, cfg.fp16)
        return TrainState(params=new_params, master=master_out, opt_state=new_opt,
                          scaler=new_scaler, global_step=state.global_step + 1), finite

    # ------------------------------------------------------------------
    def _build_programs(self):
        cfg = self.config
        topo = self.topology
        if self._param_stream is not None:
            # ZeRO-Infinity: the layer streamer owns all device programs;
            # no whole-model jitted step may exist (it would pull the full
            # params into HBM)
            self._train_step = self._apply_step = self._eval_step = None
            self._grad_step = self._accum_fn = None
            return
        gas = cfg.gradient_accumulation_steps
        ss = self._state_shardings
        repl = NamedSharding(topo.mesh, P())

        def make_gas_grads(compute, constrain: bool):
            """GAS factory: fp32 grad accumulation over microbatches
            (reference engine.py:1838/:1977 forward/backward loop).
            ``compute(state, mb) -> (loss, grads)``; constrain=False inside
            shard_map regions where sharding constraints are illegal.
            With ``gradient_accumulation_steps`` 1 there is nothing to
            accumulate: no float32 zero tree, no scan, no ``/ gas`` — the
            one microbatch's gradients go to the update as ``compute``
            hands them. Otherwise the float32 accumulator is the scan's
            carry, and feeds the same update."""
            def gas_grads(state: TrainState, batch: dict):
                if gas == 1:
                    loss, grads = compute(
                        state, jax.tree.map(lambda x: x[0], batch))
                    return loss.astype(jnp.float32), grads

                def micro(carry, mb):
                    loss_sum, grad_acc = carry
                    loss, grads = compute(state, mb)
                    grad_acc = jax.tree.map(jnp.add, grad_acc, grads)
                    return (loss_sum + loss, grad_acc), None

                zero_grads = jax.tree.map(
                    lambda p: jnp.zeros(p.shape, jnp.float32), state.params)
                if constrain:
                    zero_grads = jax.lax.with_sharding_constraint(
                        zero_grads, self.plan.grad_shardings)
                (loss_sum, grads), _ = jax.lax.scan(
                    micro, (jnp.zeros((), jnp.float32), zero_grads), batch)
                grads = jax.tree.map(lambda g: g / gas, grads)
                return loss_sum / gas, grads

            return gas_grads

        gas_grads = make_gas_grads(self._compute_grads, constrain=True)

        def eval_step(state: TrainState, batch: dict):
            p = state.params
            mgr = getattr(self, "compression_manager", None)
            if mgr is not None:  # eval must see the model that will deploy
                p = mgr.transform_params(p, state.opt_state.step)
            return self._loss_with_rules(p, batch)

        self._eval_step = jax.jit(eval_step, out_shardings=repl)

        def grad_step(state: TrainState, batch: dict):
            loss, grads = self._compute_grads(state, batch)
            return loss, grads

        self._grad_step = jax.jit(
            grad_step, out_shardings=(repl, self.plan.grad_shardings))

        def accum(acc: Pytree, grads: Pytree):
            return jax.tree.map(jnp.add, acc, grads)

        self._accum_fn = jax.jit(accum, out_shardings=self.plan.grad_shardings,
                                 donate_argnums=(0,))

        if self._offload_opt is not None:
            # host-optimizer path: GAS scan / clipping stay on device, the
            # parameter update runs in the host SIMD optimizer
            self._offload_gas_grads = jax.jit(
                gas_grads, out_shardings=(repl, self.plan.grad_shardings))

            def finalize(grads: Pytree, scale: jax.Array):
                grads = jax.tree.map(lambda g: g * scale, grads)
                if cfg.gradient_clipping:
                    norm = _global_norm(grads)
                    clip = jnp.minimum(1.0, cfg.gradient_clipping / (norm + 1e-6))
                    grads = jax.tree.map(lambda g: g * clip, grads)
                return grads

            self._offload_finalize = jax.jit(
                finalize, out_shardings=self.plan.grad_shardings,
                donate_argnums=(0,))
            # sentinel flag for the host-optimizer path: the skip decision
            # is host-side (the host walk syncs every step anyway)
            self._offload_finite = jax.jit(
                lambda loss, grads: jnp.isfinite(loss)
                & fp16_mod.grads_finite(grads), out_shardings=repl)
            self._train_step = None
            self._apply_step = None
            return

        def apply_step(state: TrainState, grads: Pytree, scale: jax.Array):
            grads = jax.tree.map(lambda g: g * scale, grads)
            return self._apply_grads(state, grads)

        self._apply_step = jax.jit(apply_step, out_shardings=(ss, repl),
                                   donate_argnums=(0,))

        if self._use_zeropp_comm():
            self._build_zeropp_programs(repl, ss)
            return

        if self._use_onebit_comm():
            self._build_onebit_programs(repl, make_gas_grads)
            return

        def train_step(state: TrainState, batch: dict):
            """Full global-batch step: GAS scan then one update — the
            compiled analogue of forward/backward/step (reference
            engine.py:1838/:1977/:2176). Returns ``(state, (loss, finite))``
            — the fused non-finite flag rides out with the loss so the
            divergence sentinel reads it without a second program."""
            loss, grads = gas_grads(state, batch)
            new_state, finite = self._apply_grads(state, grads,
                                                  jnp.isfinite(loss))
            return new_state, (loss, finite)

        self._train_step = register_program(jax.jit(
            train_step,
            out_shardings=(ss, (repl, repl)),
            donate_argnums=(0,),
        ), key=("train_step", "gspmd"))

    def _safe_manual_rules(self, manual_axes: tuple[str, ...]):
        """Logical-axis constraints on manual (shard_map) axes are illegal —
        drop rules that map onto them."""
        return [(name, ax) for name, ax in self._rules
                if not (isinstance(ax, str) and ax in manual_axes)
                and not (isinstance(ax, (tuple, list))
                         and any(a in manual_axes for a in ax))]

    def _use_zeropp_comm(self) -> bool:
        """The explicit quantized-comm train step applies when a ZeRO++
        flag is on and the layout supports it (validated at init; the only
        soft fallback is fsdp=1, where quantized transport is pointless)."""
        z = self.config.zero_optimization
        return ((z.zero_quantized_gradients or z.zero_quantized_weights)
                and self.topology.size("fsdp") > 1)

    def _build_zeropp_programs(self, repl, ss):
        """ZeRO++ train step: shard_map over the DP axes with quantized
        collectives in place of XLA's dense ones (reference
        coalesced_collectives.py:31 qgZ, stage3.py:156 qwZ).

        - qwZ (``zero_quantized_weights``): stage-3 param shards all-gather
          with int8 transport before the GAS scan — one gather per boundary,
          forward AND backward run on the quantize-roundtripped weights
          (the reference's tradeoff exactly: stage3.py:227 quantizes the
          allgather payload, not the master copy).
        - qgZ (``zero_quantized_gradients``): every microbatch's gradient
          reduces immediately as a blockwise-int8 all-to-all reduce-scatter
          along each leaf's fsdp-sharded dim (the reference likewise
          reduces per bucket per backward), so the accumulator only ever
          holds each member's 1/k slab — never a full fp32 gradient copy;
          any remaining ``data`` axis reduces with an fp32 pmean of the
          slab.
        The optimizer update stays the GSPMD ``_apply_grads`` — masters are
        fp32 and untouched by transport quantization. Memory note: gathered
        params stay resident for the whole step (one gather per boundary,
        the hpZ-style speed/memory tradeoff) — stage-3 param sharding's
        per-layer gather/free does not apply on this explicit path."""
        from jax import shard_map

        from .comm.compressed import (quant_reduce_scatter_dim,
                                      quantized_all_gather_dim)

        cfg = self.config
        z = cfg.zero_optimization
        topo = self.topology
        gas = cfg.gradient_accumulation_steps
        qg = z.zero_quantized_gradients
        qw = z.zero_quantized_weights
        dp_axes = tuple(a for a in BATCH_AXES if topo.size(a) > 1)
        data_axes = tuple(a for a in dp_axes if a != "fsdp")
        is_p = lambda x: isinstance(x, P)

        def fsdp_dim(spec):
            for i, e in enumerate(spec):
                if e == "fsdp" or (isinstance(e, (tuple, list)) and "fsdp" in e):
                    return i
            return -1

        def dp_only(spec):  # restrict a planner spec to the manual axes
            return P(*["fsdp" if fsdp_dim(spec) == i else None
                       for i in range(len(spec))])

        param_dims = jax.tree.map(fsdp_dim, self.plan.param_specs, is_leaf=is_p)
        grad_dims = jax.tree.map(fsdp_dim, self.plan.grad_specs, is_leaf=is_p)
        param_in = jax.tree.map(dp_only, self.plan.param_specs, is_leaf=is_p)
        grad_out = jax.tree.map(dp_only, self.plan.grad_specs, is_leaf=is_p)

        def local_loss(p, mb, step):
            mb = dict(mb)
            fault_scale = mb.pop("_fault_scale", None)
            mb["_train_rng"] = jax.random.fold_in(self._train_rng_base, step)
            with self._model_scope(dp_axes):
                loss = self._raw_loss_fn(p, mb)
            if fault_scale is not None:
                loss = loss * jnp.mean(fault_scale)
            return loss

        def zpp_grads(params, step, batch):
            def gather(p, d):
                if d < 0:
                    return p        # replicated (small / stage-2) leaf
                if qw:
                    return quantized_all_gather_dim(p, "fsdp", d)
                return jnp.moveaxis(jax.lax.all_gather(
                    jnp.moveaxis(p, d, 0), "fsdp", tiled=True), 0, d)

            with device_scope("zero_gather"):
                full = jax.tree.map(gather, params, param_dims)

            def reduce(g, d):
                if d >= 0:
                    if qg:
                        g = quant_reduce_scatter_dim(g, "fsdp", d, op="mean")
                    else:
                        moved = jnp.moveaxis(g, d, 0)
                        red = jax.lax.psum_scatter(moved, "fsdp",
                                                   scatter_dimension=0,
                                                   tiled=True)
                        g = jnp.moveaxis(red, 0, d) / topo.size("fsdp")
                else:
                    g = jax.lax.pmean(g, "fsdp")
                if data_axes:
                    g = jax.lax.pmean(g, data_axes)
                return g

            def slab_zero(p, d):
                shape = list(p.shape)
                if d >= 0:
                    shape[d] //= topo.size("fsdp")
                return jnp.zeros(shape, jnp.float32)

            def micro(carry, mb):
                loss_sum, acc = carry
                loss, g = jax.value_and_grad(
                    lambda p: local_loss(p, mb, step))(full)
                with device_scope("zero_reduce"):
                    slabs = jax.tree.map(reduce, _cast_tree(g, jnp.float32),
                                         grad_dims)
                acc = jax.tree.map(jnp.add, acc, slabs)
                return (loss_sum + loss, acc), None

            zero = jax.tree.map(slab_zero, full, grad_dims)
            (loss_sum, acc), _ = jax.lax.scan(
                micro, (jnp.zeros((), jnp.float32), zero), batch)
            grads = jax.tree.map(lambda a: a / gas, acc)
            loss = jax.lax.pmean(loss_sum / gas, dp_axes)
            return loss, grads

        def train_step(state: TrainState, batch: dict):
            bspec = jax.tree.map(lambda _: P(None, dp_axes), batch)
            loss, grads = shard_map(
                zpp_grads, mesh=topo.mesh,
                in_specs=(param_in, P(), bspec),
                out_specs=(P(), grad_out),
                axis_names=set(dp_axes), check_vma=False,
            )(state.params, state.opt_state.step, batch)
            new_state, finite = self._apply_grads(state, grads,
                                                  jnp.isfinite(loss))
            return new_state, (loss, finite)

        self._train_step = register_program(jax.jit(
            train_step, out_shardings=(ss, (repl, repl)),
            donate_argnums=(0,)), key=("train_step", "zeropp"))

    def _use_onebit_comm(self) -> bool:
        """1-bit compressed gradient comm applies when the optimizer is a
        1-bit variant AND the layout allows per-device local grads: pure
        data parallelism (replicated params = ZeRO stage 0), >1 DP member,
        no host offload, no fp16 scaler (reference onebit optimizers are
        likewise DP-comm features; runtime/fp16/onebit/adam.py:14)."""
        from .onebit import OneBitAdam

        if not isinstance(self.optimizer, OneBitAdam):
            return False
        # 'expert' excluded too: MoE params shard over the expert axis, which
        # breaks the replicated-params assumption of the compressed step
        ok = (self.topology.dp_world_size > 1
              and self.config.zero_optimization.stage == 0
              and self._offload_opt is None
              and not self.fp16_enabled
              and all(self.topology.size(a) <= 1
                      for a in ("tensor", "seq", "pipe", "expert")))
        if not ok and not getattr(self, "_onebit_warned", False):
            self._onebit_warned = True
            logger.warning(
                "1-bit optimizer configured but the layout doesn't support "
                "compressed comm (needs ZeRO stage 0, dp>1, bf16/fp32, no "
                "offload, no tp/sp/pp/ep) — running its exact dense update")
        return ok

    def _build_onebit_programs(self, repl, make_gas_grads):
        """Train step with per-device local grads (shard_map over the DP
        axes) feeding the 1-bit optimizer's compressed momentum averaging
        (runtime/onebit.py). Warmup steps inside are exact dense Adam via
        psum, so the program is one compile for both phases. The error-
        feedback buffers are genuinely per-device state: they carry a
        leading DP dimension sharded over the DP axes, so checkpoints
        save/restore every member's compensation error (the imperative
        forward/backward/step path stays dense, like the reference's
        warmup regime)."""
        from jax import shard_map

        cfg = self.config
        topo = self.topology
        dp_axes = tuple(a for a in BATCH_AXES if topo.size(a) > 1)
        if cfg.gradient_clipping:
            logger.warning("gradient_clipping is ignored on the 1-bit "
                           "compressed path (error feedback and clipping "
                           "don't compose; the reference behaves the same)")

        def local_loss(p, mb, step):
            mb = dict(mb)
            fault_scale = mb.pop("_fault_scale", None)
            mb["_train_rng"] = jax.random.fold_in(self._train_rng_base, step)
            with self._model_scope(dp_axes):
                loss = self._raw_loss_fn(p, mb)
            if fault_scale is not None:
                loss = loss * jnp.mean(fault_scale)
            return loss

        def local_compute(state, mb):
            loss, grads = jax.value_and_grad(
                lambda p: local_loss(p, mb, state.opt_state.step))(state.params)
            return loss, _cast_tree(grads, jnp.float32)

        gas_local = make_gas_grads(local_compute, constrain=False)

        def inner(state: TrainState, batch: dict):
            master = state.master if state.master is not None else state.params
            loss_local, local_grads = gas_local(state, batch)
            # fused non-finite flag (sentinel contract): reported, NOT
            # gated — error-feedback state and a skipped update don't
            # compose (the member's compensation error would double-count),
            # so recovery on this path is rewind-only
            finite_local = (jnp.isfinite(loss_local)
                            & fp16_mod.grads_finite(local_grads))
            finite = jax.lax.pmin(finite_local.astype(jnp.int32),
                                  dp_axes).astype(jnp.bool_)
            lr = self.lr_schedule(state.opt_state.step)
            # error arrives [1, ...] (this member's slice of the stacked
            # per-device buffer)
            opt_in = state.opt_state._replace(
                error=jax.tree.map(lambda e: e[0], state.opt_state.error))
            new_master, new_opt = self.optimizer.local_update(
                local_grads, opt_in, master, dp_axes, lr=lr)
            new_opt = new_opt._replace(
                error=jax.tree.map(lambda e: e[None], new_opt.error))
            if self.mixed_precision:
                new_params = _cast_tree(new_master, self.compute_dtype)
                master_out = new_master
            else:
                new_params, master_out = new_master, None
            loss = jax.lax.pmean(loss_local, dp_axes)
            new_state = TrainState(params=new_params, master=master_out,
                                   opt_state=new_opt, scaler=None,
                                   global_step=state.global_step + 1)
            return new_state, (loss, finite)

        state_spec = jax.tree.map(lambda _: P(), self.state)
        err_spec = jax.tree.map(lambda _: P(dp_axes), self.state.opt_state.error)
        state_spec = state_spec._replace(
            opt_state=state_spec.opt_state._replace(error=err_spec))

        def train_step(state, batch):
            bspec = jax.tree.map(lambda _: P(None, dp_axes), batch)
            # only the DP axes go manual; the rest stay auto so the model's
            # internal sharding constraints (seq/tensor rules) remain legal
            return shard_map(inner, mesh=topo.mesh,
                             in_specs=(state_spec, bspec),
                             out_specs=(state_spec, (P(), P())),
                             axis_names=set(dp_axes),
                             check_vma=False)(state, batch)

        self._train_step = register_program(jax.jit(
            train_step, out_shardings=(self._state_shardings, (repl, repl)),
            donate_argnums=(0,)), key=("train_step", "onebit"))

    def _offload_apply(self, grads: Pytree) -> None:
        """Host optimizer step + device param refresh."""
        step_scalar = self.state.opt_state.step
        lr = float(self.lr_schedule(step_scalar))
        new_params = self._offload_opt.step_tree(
            grads, self.plan.param_shardings, lr)
        self.state = self.state._replace(
            params=new_params,
            opt_state=self.state.opt_state._replace(step=step_scalar + 1),
            global_step=self.state.global_step + 1)

    # ------------------------------------------------------------------
    # batch plumbing
    def _shard_batch(self, batch: dict, with_gas_dim: bool) -> dict:
        """Device_put the host batch with [*(gas), global_batch, seq] dims
        sharded over the DP axes (+ seq axis)."""
        def put(x):
            x = jnp.asarray(x) if not isinstance(x, jax.Array) else x
            return jax.device_put(x, self._batch_sharding(x.ndim,
                                                          with_gas_dim))

        return jax.tree.map(put, batch)

    def _batch_sharding(self, ndim: int, with_gas_dim: bool) -> NamedSharding:
        topo = self.topology
        entries: list[Any] = [None] * ndim
        b = 1 if with_gas_dim else 0
        if ndim > b:
            entries[b] = BATCH_AXES
        if ndim > b + 1 and topo.size("seq") > 1:
            entries[b + 1] = "seq"
        return NamedSharding(topo.mesh, P(*entries))

    def _apply_curriculum(self, batch: dict) -> dict:
        """Seqlen curriculum: truncate [B, S] leaves to the current
        difficulty (reference engine.py:1913 curriculum seqlen path). The
        scheduler quantizes difficulties, so recompiles stay bounded."""
        cs = self.curriculum_scheduler
        if cs is None or cs.curriculum_type != "seqlen":
            return batch
        seqlen = cs.update_difficulty(self.global_steps)
        # the sequence length is input_ids' second dim; only axes of exactly
        # that size are sequence axes (leaves like [B, S, S] masks truncate
        # on both, label-score leaves [B, K] stay intact)
        leaves = batch.get("input_ids") if isinstance(batch, dict) else None
        full_len = leaves.shape[1] if hasattr(leaves, "shape") else max(
            (x.shape[1] for x in jax.tree.leaves(batch)
             if hasattr(x, "ndim") and x.ndim >= 2), default=0)
        if full_len <= seqlen:
            return batch

        def trunc(x):
            if not hasattr(x, "ndim") or x.ndim < 2:
                return x
            sl = tuple(slice(None) if d == 0 or x.shape[d] != full_len
                       else slice(seqlen) for d in range(x.ndim))
            return x[sl]

        return jax.tree.map(trunc, batch)

    def _reshape_for_gas(self, batch: dict) -> dict:
        gas = self.config.gradient_accumulation_steps

        def reshape(x):
            x = jnp.asarray(x)
            assert x.shape[0] == self.config.train_batch_size, (
                f"train_batch expects global batch dim {self.config.train_batch_size}, "
                f"got {x.shape[0]}")
            return x.reshape((gas, x.shape[0] // gas) + x.shape[1:])

        return jax.tree.map(reshape, batch)

    # ------------------------------------------------------------------
    # ZeRO-Infinity streamed step
    def _train_batch_streamed(self, batch: dict) -> jax.Array:
        ps = self._param_stream
        gas = self.config.gradient_accumulation_steps
        B = self.config.train_batch_size

        def resh(x):
            x = np.asarray(x)
            assert x.shape[0] == B, (
                f"train_batch expects global batch dim {B}, got {x.shape[0]}")
            return x.reshape((gas, x.shape[0] // gas) + x.shape[1:])

        hb = jax.tree.map(resh, batch)
        losses = [ps.micro_fwd_bwd(jax.tree.map(lambda x: x[g], hb))
                  for g in range(gas)]
        lr = float(self.lr_schedule(self.state.opt_state.step))
        ps.apply_grads(gas, lr, self.config.gradient_clipping or None)
        # state.params is a LIVE view of the cpu cache (refreshed in place)
        # or an NVMe placeholder — never rebuilt per step
        self.state = self.state._replace(
            opt_state=self.state.opt_state._replace(
                step=self.state.opt_state.step + 1),
            global_step=self.state.global_step + 1)
        return jnp.mean(jnp.stack(losses))

    # ------------------------------------------------------------------
    # public API
    def train_batch(self, batch: dict) -> jax.Array:
        """Run one full training step over a global batch
        (shape [train_batch_size, ...] per leaf).

        Resilience hooks (runtime/resilience.py): a pending preemption
        triggers a priority save + ``Preempted`` exit BEFORE the step; the
        divergence sentinel observes the fused non-finite flag AFTER it and
        may rewind (``engine.last_step_rewound`` — re-derive data order
        from the restored ``engine.global_steps``) or raise
        ``DivergenceError`` once the rewind budget is spent.

        Telemetry (telemetry/): when enabled, the step runs under a
        ``StepTraceAnnotation``-mirrored span (host timeline overlays the
        xplane device trace) and feeds the training-health instruments —
        step-time histogram, tokens/s, MFU, and goodput that discounts
        sentinel-skipped and rewound steps."""
        telem = self._telem
        if not telem.enabled:
            return self._train_batch_inner(batch)
        step_before = self.global_steps
        skipped_before = self.skipped_steps
        with telem.step_span("train_batch", self.global_steps):
            loss = self._train_batch_inner(batch)
        self._record_train_telemetry(batch, step_before, skipped_before)
        return loss

    def _train_batch_inner(self, batch: dict) -> jax.Array:
        res = self.resilience
        res.check_preemption()
        self.tput_timer.start()
        self.timers(TRAIN_BATCH_TIMER).start()
        if self._param_stream is not None:
            batch = self._apply_curriculum(batch)
            with res.guard("train_step"):
                loss = self._train_batch_streamed(batch)
            self.global_steps += 1
            self.timers(TRAIN_BATCH_TIMER).stop(sync_val=loss)
            self.tput_timer.stop(sync_val=loss)
            if self.global_steps % self.config.steps_per_print == 0:
                log_dist(f"step={self.global_steps} loss={float(loss):.4f}")
                if self.config.wall_clock_breakdown:
                    self._emit_timer_means()
            self._last_loss = loss
            res.observe_step(loss, None)
            return loss
        batch = self._apply_curriculum(batch)
        batch = res.arm_batch(batch, self.config.train_batch_size)
        batch = self._shard_batch(self._reshape_for_gas(batch), with_gas_dim=True)
        profile_target = self._train_step if self._offload_opt is None \
            else self._offload_gas_grads
        if self.flops_profiler is not None and not self.flops_profiler.profiled:
            # last_step_s is device-synced only under wall_clock_breakdown;
            # otherwise it measures async dispatch and would inflate TFLOPS
            self.flops_profiler.maybe_profile_step(
                profile_target, (self.state, batch), self.global_steps,
                params=self.num_parameters(),
                latency_s=self.tput_timer.last_step_s
                if self.config.wall_clock_breakdown else None)
        if self._step_flops is None and self._mfu_tracker is not None:
            # MFU numerator: the compiled step's XLA cost-model FLOPs —
            # probed HERE because only this scope holds the batch in its
            # final (sharded, gas-dim) shape; the executable cache makes
            # the read free after the first step's compile
            self._step_flops = self._cost_model_flops(
                profile_target, (self.state, batch))
            if self._step_flops:
                self._mfu_tracker.flops_per_step = self._step_flops
        finite = None
        if self._offload_opt is not None:
            with res.guard("train_step"):
                res.injector.maybe_stall("stall_train_step_s")
                loss, grads = self._offload_gas_grads(self.state, batch)
                if self.config.resilience.sentinel:
                    finite = self._offload_finite(loss, grads)
            if finite is not None and not bool(finite):
                # skip-step on the host-optimizer path: the update never
                # runs, global_step still advances (skipped_steps counts it)
                self.state = self.state._replace(
                    global_step=self.state.global_step + 1)
            else:
                if self.config.gradient_clipping:  # scale=1: only clip matters
                    grads = self._offload_finalize(grads,
                                                   jnp.ones((), jnp.float32))
                self._offload_apply(grads)
        else:
            with res.guard("train_step"):
                res.injector.maybe_stall("stall_train_step_s")
                self.state, (loss, finite) = self._train_step(self.state, batch)
                if res.watchdog.timeout_s > 0:
                    # surface a device hang INSIDE the guarded region —
                    # async dispatch would otherwise return instantly and
                    # stall later, outside any watchdog
                    jax.block_until_ready(loss)
        self.global_steps += 1
        if self.config.wall_clock_breakdown:
            self.timers(TRAIN_BATCH_TIMER).stop(sync_val=loss)
        else:
            self.timers(TRAIN_BATCH_TIMER).stop()
        self.tput_timer.stop(sync_val=loss if self.config.wall_clock_breakdown else None)
        if self.global_steps % self.config.steps_per_print == 0:
            log_dist(f"step={self.global_steps} loss={float(loss):.4f} "
                     f"lr={float(self.lr_schedule(self.state.opt_state.step)):.3e}")
            if self.config.wall_clock_breakdown:
                self._emit_timer_means()
        self._last_loss = loss
        res.observe_step(loss, finite)
        return loss

    def eval_batch(self, batch: dict) -> jax.Array:
        if self._param_stream is not None:
            loss, _, _ = self._param_stream.micro_forward(
                batch, keep_activations=False)
            return loss
        batch = self._shard_batch(batch, with_gas_dim=False)
        return self._eval_step(self.state, batch)

    # --- imperative triplet (reference forward/backward/step) ----------
    def forward(self, batch: dict) -> jax.Array:
        """Forward-only loss on a microbatch (for parity with reference
        ``engine(batch)``; the grad pass happens in ``backward``)."""
        if self._param_stream is not None:
            raise NotImplementedError(
                "offload_param streaming exposes train_batch/eval_batch "
                "only; the imperative forward/backward/step triplet needs "
                "device-resident params")
        self.timers(FORWARD_GLOBAL_TIMER).start()
        batch = self._shard_batch(batch, with_gas_dim=False)
        loss = self._eval_step(self.state, batch)
        self.timers(FORWARD_GLOBAL_TIMER).stop()
        self._last_forward_batch = batch
        return loss

    def backward(self, batch: dict | None = None, loss=None) -> jax.Array:
        if self._param_stream is not None:
            raise NotImplementedError(
                "offload_param streaming exposes train_batch/eval_batch "
                "only; the imperative forward/backward/step triplet needs "
                "device-resident params")
        """Compute grads for a microbatch and accumulate (reference
        engine.backward :1977 + ZeRO IPG accumulation). Accepts the
        DeepSpeed-canonical ``backward(loss)`` call shape: a scalar loss (or
        ``loss=`` kwarg) means "differentiate the batch from the last
        forward()" — JAX recomputes the forward inside the grad program.
        A *transformed* loss (e.g. ``backward(loss * alpha)``) cannot be
        differentiated here (no tape); pass a custom ``loss_fn`` to
        ``initialize`` instead — a mismatch triggers a warning."""
        self.timers(BACKWARD_GLOBAL_TIMER).start()
        if batch is not None and not isinstance(batch, dict):
            # engine.backward(loss) — reference call shape
            loss, batch = batch, None
        if loss is not None and self._last_loss is not None:
            try:
                if abs(float(loss) - float(self._last_loss)) > 1e-4 * (
                        abs(float(self._last_loss)) + 1e-8):
                    logger.warning(
                        "backward(loss) received a value different from the last "
                        "forward loss; transformations of the loss are NOT "
                        "differentiated — use a custom loss_fn in initialize()")
            except TypeError:
                pass
        if batch is None:
            batch = getattr(self, "_last_forward_batch", None)
            if batch is None:
                raise ValueError("backward() needs a batch (or a prior forward())")
        else:
            batch = self._shard_batch(batch, with_gas_dim=False)
        loss, grads = self._grad_step(self.state, batch)
        if self._accum_grads is None:
            self._accum_grads = grads
        else:
            self._accum_grads = self._accum_fn(self._accum_grads, grads)
        self._accum_count += 1
        self.timers(BACKWARD_GLOBAL_TIMER).stop()
        self._last_loss = loss
        return loss

    def is_gradient_accumulation_boundary(self) -> bool:
        return self._accum_count >= self.config.gradient_accumulation_steps

    def step(self) -> None:
        """Apply accumulated grads (reference engine.step :2176). No-op—with
        warning—if backward hasn't run. The divergence sentinel observes
        this path too: the fused finite flag comes from the apply program
        (or a host check on the offload path), so a bf16 NaN streak rewinds
        or aborts exactly as under ``train_batch``."""
        if self._accum_grads is None:
            logger.warning("step() called with no accumulated gradients")
            return
        self.timers(STEP_GLOBAL_TIMER).start()
        scale = jnp.asarray(1.0 / max(self._accum_count, 1), jnp.float32)
        if self._offload_opt is not None:
            finite = self._offload_finite(self._last_loss, self._accum_grads) \
                if self.config.resilience.sentinel \
                and self._last_loss is not None else None
            grads = self._offload_finalize(self._accum_grads, scale)
            if finite is not None and not bool(finite):
                # skip-step (host decision, like train_batch's offload path)
                self.state = self.state._replace(
                    global_step=self.state.global_step + 1)
            else:
                self._offload_apply(grads)
        else:
            self.state, finite = self._apply_step(
                self.state, self._accum_grads, scale)
        self._last_step_finite = finite
        self._accum_grads = None
        self._accum_count = 0
        self.global_steps += 1
        self.timers(STEP_GLOBAL_TIMER).stop()
        if self.config.wall_clock_breakdown \
                and self.global_steps % self.config.steps_per_print == 0:
            self._emit_timer_means()   # fwd/bwd/step means → dashboards
        if self._last_loss is not None:
            self.resilience.observe_step(self._last_loss, finite)

    def zero_grad(self) -> None:
        self._accum_grads = None
        self._accum_count = 0

    # ------------------------------------------------------------------
    @property
    def params(self) -> Pytree:
        return self.state.params

    @property
    def skipped_steps(self) -> int:
        """Steps whose optimizer update was skipped by the fp16 overflow
        check (reference ``engine.skipped_steps``). The optimizer step
        counter only advances on applied updates, so the difference from
        ``global_step`` is exactly the skip count."""
        return int(self.state.global_step) - int(self.state.opt_state.step)

    def get_lr(self) -> float:
        return float(self.lr_schedule(self.state.opt_state.step))

    def get_loss_scale(self) -> float:
        return float(self.state.scaler.scale) if self.state.scaler is not None else 1.0

    def num_parameters(self) -> int:
        return sum(l.size for l in jax.tree.leaves(self.state.params))

    def close(self) -> None:
        """Release the engine's device buffers immediately.

        A failed or finished engine must not pin HBM while references to it
        (e.g. a traceback in a caller's except block, or a bench harness
        moving to its next entry) are still alive — jax frees buffers by
        refcount, so an explicit delete is the only prompt path. The engine
        is unusable afterwards.
        """
        if self.state is None:
            return
        for leaf in jax.tree.leaves(self.state):
            if isinstance(leaf, jax.Array):
                try:
                    leaf.delete()
                except RuntimeError:
                    pass  # already deleted (donated into a later step)
        self.state = None
        self._param_stream = None

    # --- resilience surface (runtime/resilience.py) ---------------------
    @property
    def last_step_rewound(self) -> bool:
        """True when the immediately preceding ``train_batch`` ended in a
        sentinel rewind — the driver should re-derive its data position
        from the restored ``global_steps``."""
        return self.resilience.last_step_rewound

    @property
    def resilience_counters(self) -> dict:
        """Host-side resilience counters (bad/skipped steps, rewinds,
        preemptions, aborts) — also emitted through monitor/ backends."""
        return dict(self.resilience.counters)

    def _emit_counters(self, counters: dict, prefix: str) -> None:
        """Fan resilience/checkpoint counters out to the configured
        monitor/ backends (lazy MonitorMaster; no-op when none enabled)."""
        if self._monitor_master is None:
            from ..monitor import MonitorMaster

            self._monitor_master = MonitorMaster(self.config)
        self._monitor_master.write_counters(counters, self.global_steps,
                                            prefix=prefix)

    #: wall_clock_breakdown timers exported to dashboards (means, ms)
    _BREAKDOWN_TIMERS = (TRAIN_BATCH_TIMER, FORWARD_GLOBAL_TIMER,
                         BACKWARD_GLOBAL_TIMER, STEP_GLOBAL_TIMER,
                         FORWARD_MICRO_TIMER, BACKWARD_MICRO_TIMER,
                         STEP_MICRO_TIMER)

    def _emit_timer_means(self) -> None:
        """Fan the wall_clock_breakdown timer MEANS out through
        ``MonitorMaster.write_counters`` (and telemetry gauges) every
        ``steps_per_print`` — previously the breakdown only reached the
        log, invisible to dashboards. Emitted timers reset, so each point
        is the mean over the last print window."""
        means: dict[str, float] = {}
        for name in self._BREAKDOWN_TIMERS:
            if self.timers.has(name):
                t = self.timers.timers[name]
                if t.count:
                    means[f"{name}_ms"] = t.mean() * 1000.0
                    t.reset()
        if not means:
            return
        self._emit_counters(means, "Train/")
        if self._telem.enabled:
            for k, v in means.items():
                self._telem.registry.gauge(f"train_{k}").set(v)

    def _cost_model_flops(self, jitted_step, args: tuple) -> float:
        """FLOPs of one compiled step from XLA's cost analysis (free: the
        executable is cached). 0.0 marks 'unavailable' so the probe never
        retries every step."""
        try:
            from ..profiling.flops_profiler import _normalize_costs

            cost = _normalize_costs(
                jitted_step.lower(*args).compile().cost_analysis())
            return float(cost.get("flops", 0.0))
        except Exception as e:  # telemetry must never kill training
            logger.debug(f"step-flops probe failed ({e!r}); MFU disabled")
            return 0.0

    def _record_train_telemetry(self, batch: dict, step_before: int,
                                skipped_before: int) -> None:
        """Post-step training-health instruments (train_batch wrapper)."""
        reg = self._telem.registry
        dt = self.tput_timer.last_step_s
        # without wall_clock_breakdown the timer stops unsynced and dt is
        # ASYNC DISPATCH time (~ms for a ~100ms device step) — rate/MFU
        # gauges computed from it would render as confident nonsense
        # (same reason flops_profiler passes latency_s=None there); the
        # raw histogram stays, labeled, for the breakdown-off case
        synced = self.config.wall_clock_breakdown
        if dt:
            reg.histogram(
                "train_step_time_s",
                help="train_batch wall time per step (device-synced only "
                     "under wall_clock_breakdown)").observe(dt)
        tokens = 0
        for leaf in jax.tree.leaves(batch):
            shape = getattr(leaf, "shape", ())
            if len(shape) >= 2:
                tokens = int(shape[0]) * int(shape[1])
                break
        reg.counter("train_steps_total").inc()
        if tokens:
            reg.counter("train_tokens_total").inc(tokens)
            if dt and synced:
                reg.gauge("train_tokens_per_s").set(tokens / dt)
        tracker = self._mfu_tracker
        if tracker is not None and dt and synced:
            rewound = self.resilience.last_step_rewound
            skipped = self.skipped_steps > skipped_before
            tracker.on_step(dt, useful=not (rewound or skipped))
            if rewound:
                # the rewind rolled global_steps back: everything between
                # the restored step and the divergence was wasted work
                tracker.discard_steps(max(0, step_before - self.global_steps))
            m, g = tracker.mfu(), tracker.goodput()
            if m is not None:
                reg.gauge("train_mfu", help="model FLOPs utilization "
                          "(XLA cost model / peak)").set(m)
                reg.gauge("train_goodput", help="MFU counting only steps "
                          "whose update survived (skips/rewinds discounted)"
                          ).set(g)
        self._telem.set_health(global_step=self.global_steps)

    # --- checkpointing (reference engine.py:3109/:2763) -----------------
    def save_checkpoint(self, save_dir: str, tag: str | None = None,
                        client_state: dict | None = None) -> str:
        from .checkpointing import save_checkpoint as _save

        return _save(self, save_dir, tag=tag, client_state=client_state)

    def deepspeed_io(self, dataset, batch_size: int | None = None, *,
                     shuffle: bool = True, drop_last: bool = True,
                     collate_fn=None):
        """Build a global-batch DataLoader for this engine (reference
        ``deepspeed_io``, engine.py:1743). ``batch_size`` defaults to the
        engine's global train batch; the jitted step shards it per plan."""
        from .data import DataLoader

        return DataLoader(dataset,
                          batch_size if batch_size is not None
                          else self.config.train_batch_size,
                          shuffle=shuffle, seed=self.config.seed,
                          drop_last=drop_last, collate_fn=collate_fn)

    def load_checkpoint(self, load_dir: str, tag: str | None = None) -> dict:
        from .checkpointing import load_checkpoint as _load

        with self.resilience.guard("checkpoint_restore"):
            return _load(self, load_dir, tag=tag)

    def wait_for_checkpoint(self, timeout_s: float | None = None) -> None:
        """Block until an async checkpoint save has committed. Bounded by
        ``timeout_s`` (default ``checkpoint.wait_timeout_s``); a wedged
        save thread raises ``CheckpointWaitTimeout`` instead of hanging."""
        from .checkpointing import wait_for_checkpoint as _wait

        _wait(self, timeout_s=timeout_s)


# --------------------------------------------------------------------------
def initialize(model: nn.Module | None = None,
               config: Config | dict | str | None = None,
               loss_fn: Callable | None = None,
               params: Pytree | None = None,
               topology: MeshTopology | None = None,
               sample_batch: dict | None = None,
               rng: jax.Array | None = None,
               training_data=None,
               **kwargs):
    """Training bring-up (reference deepspeed/__init__.py:69). Returns
    ``(engine, optimizer, dataloader, lr_scheduler)``; the dataloader is
    built from ``training_data`` (reference ``training_data`` arg →
    ``deepspeed_io``) or None."""
    cfg = Config.load(config)
    engine_cls = DeepSpeedEngine
    if cfg.hybrid_engine.enabled:
        from .hybrid_engine import DeepSpeedHybridEngine

        engine_cls = DeepSpeedHybridEngine
    engine = engine_cls(config=cfg, model=model, loss_fn=loss_fn, params=params,
                        topology=topology, sample_batch=sample_batch, rng=rng,
                        **kwargs)
    loader = engine.deepspeed_io(training_data) if training_data is not None \
        else None
    return engine, engine.optimizer, loader, engine.lr_schedule
