"""Inference engine v2: continuous batching over a paged KV pool (FastGen).

TPU-native re-design of reference inference/v2 (``InferenceEngineV2``
engine_v2.py:30 with ``put`` :107 / ``query`` :158 / ``can_schedule`` :184 /
``flush`` :242, ``engine_factory.build_hf_engine`` :69, paged
``BlockedKVCache`` ragged/kv_cache.py, blocked-flash ragged attention
kernels kernels/ragged_ops/).

Architecture (TPU-first, round-4 async design):
- KV lives in ONE block-granular pool per KIND of layer (``kv_pool``: a
  tuple, ``forward.cache_kinds``' order, for every model):
  [L, 2, KV, num_blocks, block_size, D], sharded over ``tensor`` on the
  KV-head dim. Sequences own block lists (host-side allocator,
  inference/ragged.py). The pools are READ-ONLY inside the forward
  (inference/forward.py — the model's ragged step, the only caller of the
  serving path's Pallas kernels): fresh K/V rides a small staged buffer
  through ``paged_ragged_attention`` (ops/pallas/paged_attention.py — pool
  pages + stage in one online softmax, all KV heads per grid step), the
  forward returns it, and ONE merge per program writes it, after the
  forward and inside the same ``jit``. Interleaving pool writes with the
  attention custom call makes XLA materialize pool-sized copies — the
  measured difference is ~280ms vs ~8.5ms per decode token-step.
- Steps are cached jitted programs — a SplitFuse plan ([rows, chunk]
  prompt chunks, and beside them the DECODE BLOCK: the decode-ready
  sequences as a [max_seqs, 1] segment of the same forward, one token each
  for the price of their attention, the weights read once for both), a
  pure [max_seqs, 1] decode plan, or a multi-iteration decode window
  (a fixed-trip ``lax.scan``) — built by inference/scheduler.py
  from a SPECULATIVE view of each sequence (dispatched-but-uncommitted).
- Dispatch never waits: decode chains through a device-resident
  last-sampled-token array, sampled-token readbacks ride d2h in the
  background, and host commits lag up to ``max_inflight`` dispatches
  (a readback's latency never gates throughput).
- The model is the SAME TransformerLM parameter tree the trainer produces —
  no weight surgery; the ragged forward reads the tree directly.
"""
from __future__ import annotations

import os
import time
from dataclasses import dataclass
from typing import Any

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from ..models.transformer import (ModelConfig, TransformerLM,
                                  default_activation_rules, is_moe_layer)
from ..parallel.tensor import overlap_counters
from ..parallel.topology import MeshConfig, MeshTopology
from ..profiling.trace import (books_its_build, engine_build,
                               register_program)
from ..utils.annotations import device_scope
from ..utils.logging import logger
from ..ops.pallas.paged_attention import (latent_prefill_plan,
                                          paged_attention_usable, paged_plan,
                                          paged_step_counts)
# (``cache_kinds``, ``moe_tile_rows`` and ``moe_padded_rows`` are imported
# from here by the benchmark's tools too)
from .forward import (KIND_SPEC_2D, KIND_SPEC_3D, DecodeBlock, RaggedForward,
                      cache_kinds, kv_pack, merge_records, merge_rows,
                      merge_step, moe_padded_rows, moe_tile_rows, stage_rows)
from .ragged import StateManager, StepPlan
from .sampling import sample_logits, sample_tree_logits
from .scheduler import SpecAcceptTracker, SplitFuseScheduler
from .speculative import (SPEC_BRANCHES, SPEC_DEPTH_MIXED_CAP, SPEC_NGRAM_MAX,
                          SPEC_NGRAM_MIN, DraftModelProposer, NGramProposer,
                          accept_walk)
from .weights import load_tp_params

Pytree = Any


class WeightSwapError(RuntimeError):
    """A live weight swap was refused or failed verification. ``reason``
    is machine-readable (``integrity`` | ``shape_mismatch`` |
    ``probe_failed`` | ``no_checkpoint``) — the serving replica ships it
    verbatim in its ``swap_fail`` reply and the deploy orchestrator keys
    rollback decisions on it. Raising here NEVER leaves the engine on
    partial weights: the old params keep serving."""

    def __init__(self, reason: str, detail: str = ""):
        super().__init__(f"weight swap refused: {reason}"
                         + (f" ({detail})" if detail else ""))
        self.reason = reason


@dataclass
class RaggedInferenceConfig:
    """Reference inference/v2/config_v2.py ``RaggedInferenceEngineConfig``."""
    #: KV page width. Wide pages feed the attention kernel full-lane MXU
    #: tiles and shrink the page grid — measured on v5e (gpt2-350m long
    #: mix): 6032/7459/9800 prompt tok/s at 32/64/128. 64 balances that
    #: against per-sequence memory granularity; the benchmark's
    #: configurations (``benchmark/configs/``) set 128.
    block_size: int = 64
    num_blocks: int = 64
    max_seqs: int = 8                 # state_manager max_tracked_sequences
    chunk: int = 64                   # SplitFuse token budget per prefill step
    max_seq_len: int = 2048
    dtype: Any = jnp.bfloat16
    tensor_parallel: int = 1
    greedy: bool = True
    temperature: float = 1.0
    top_k: int = 0
    top_p: float = 1.0
    #: use the Pallas paged-attention kernels (decode AND chunked-prefill
    #: steps); None = auto (on whenever the kernel supports the model's
    #: head geometry). False forces the XLA gather formulation for both.
    use_pallas_decode: bool | None = None
    #: when every live sequence is decoding, run up to this many decode
    #: iterations inside ONE jitted program — one host→device dispatch per
    #: window instead of per token. Slots finish independently (per-slot
    #: remaining masks): a finished slot's later iterations emit -1 and
    #: write the trash block, so a near-done sequence never shrinks
    #: everyone's window. 1 disables windowing.
    decode_window: int = 8
    #: cap on the decode window while prefill chunks are PENDING (advisor
    #: r05: a new request's first chunk could wait out a full
    #: decode_window, inflating TTFT). The engine alternates prefill steps
    #: (each carries the decode block: one token for every decoder) with
    #: decode windows; this bounds how long a pending chunk waits behind
    #: the decode side of the alternation without giving up windowing
    #: entirely — a capped cycle hands a decoder cap + 1 tokens. Pow2-
    #: floored like the window itself, so the compiled-program menu stays
    #: bounded. 0 disables the cap.
    decode_window_mixed_cap: int = 4
    #: async pipeline depth: how many dispatched steps may await host
    #: readback before the engine blocks on the oldest. Dispatch never
    #: waits for sampled tokens (decode chains through a device-resident
    #: last-token array); readbacks ride d2h in the background and commit
    #: lazily. 0 restores fully synchronous stepping. The queue must
    #: cover the host's plan+dispatch+commit round trip; the cost of
    #: depth is only more speculative tokens discarded at an eos. (The
    #: default of 8 was chosen on an earlier, high-latency link to the
    #: chip and has not been re-measured on an attached one.)
    max_inflight: int = 8
    #: weight-only quantization (8 | 4 | "fp8"): matmul weights live in HBM
    #: as codes + group scales and dequantize TILE-BY-TILE inside the
    #: Pallas quant matmul (ops/pallas/quant_matmul.py — the reference
    #: mixed_gemm / FP6-LLM cuda_linear role); norms/biases/embeddings
    #: stay exact.
    quant_bits: int | str | None = None
    #: token-budget prefill packing (Dynamic SplitFuse constant-work under
    #: XLA static shapes): when fewer than max_seqs sequences have pending
    #: chunks, the plan carries EXACTLY the rows that have work (exact-k —
    #: pow2 row buckets measured worse: 5-7 pending rows round up to 8 and
    #: miss the pool-throttled steady state entirely) and each row's chunk
    #: grows along the scheduler's page-aligned chunk chain toward the
    #: constant rows x tokens budget — a near-full useful-token step
    #: instead of idle padded rows. Costs one compiled program per
    #: (rows, chunk) pair on the chain (see
    #: ``SplitFuseScheduler.program_shape_menu``); off in rolling-window
    #: mode.
    prefill_pack: bool = True
    #: the most sequences ONE packed prefill plan carries (0 = as many as
    #: have work, up to ``max_seqs``). A model whose plans pack ROWS only
    #: (a ring or a record: the chunk cannot grow) has one program a row
    #: count, ``max_seqs`` of them; a cap bounds that menu — and a step's
    #: tokens, rows x ``chunk`` — at what a deployment warms. Sequences
    #: past the cap keep their place and ride the next prefill plan.
    prefill_max_rows: int = 0
    #: False: a packed plan packs ROWS only and its chunk stays ``chunk``,
    #: also where the state is a chain of pages (where it is not, the chunk
    #: never grows). For a model whose prefill step costs grow with the
    #: CONTEXT — latent attention over a 28k-token table: a lone prompt's
    #: grown chunk of ``chunk`` x ``max_seqs`` tokens is one step of most of
    #: a second, in which every decoding row gets one token — and whose
    #: programs compile slowly (the menu is then one program a row count).
    prefill_grow_chunk: bool = True
    #: content-addressed shared-prefix KV cache over the paged pool
    #: (vLLM PagedAttention block sharing + SGLang RadixAttention, TPU
    #: formulation — inference/prefix_cache.py): full KV pages are keyed
    #: by their token-id chain from the root in a radix index held by
    #: StateManager. Admit walks the trie and points the new sequence's
    #: block table at the longest cached page-aligned prefix (refcount++,
    #: zero copy — pages are position-ordered, so the attention kernels
    #: need no change) and prefill chunking starts at the cached
    #: boundary; released sequences publish their full computed pages
    #: into the trie instead of freeing them; unreferenced pages form an
    #: LRU reclaimed only under allocation pressure (referenced or
    #: in-flight pages never are). None = auto: ON for pack-mode linear
    #: serving; OFF under fp8-KV pages (cross-request reuse parity
    #: unproven at e4m3 granularity — see tests) and always off in
    #: rolling-window ring mode, where page slots are reused in place and
    #: a published page's content would change under a reader. True
    #: forces it on (still refuses ring mode; allowed with fp8-KV for
    #: parity work); False disables.
    prefix_cache: bool | None = None
    #: KV tiering (inference/kvtier.py — Mooncake-style HBM → host RAM →
    #: NVMe): prefix-cache eviction DEMOTES chains through the
    #: kind="prefix" PageBundle path into a bounded host-RAM ring with
    #: an optional NVMe spill behind it, indexed by the same blake2b
    #: chain hashes placement matches on; an admission miss whose chain
    #: is tier-resident PROMOTES (adopt_prefix + the page scatter)
    #: instead of recomputing — recompute stays the always-safe fallback
    #: on any crc/version-skew/capacity failure. Requires the prefix
    #: cache (refused otherwise). False (default) = no tier.
    kv_tier: bool = False
    #: host-RAM ring payload budget for demoted pages
    kv_tier_ram_bytes: int = 64 << 20
    #: NVMe spill directory (None = RAM-only tier, overflow drops)
    kv_tier_nvme_dir: str | None = None
    #: total NVMe spill budget (oldest segment dropped past it)
    kv_tier_nvme_bytes: int = 256 << 20
    #: shortest tier-resident chain worth promoting (pages). None = auto:
    #: sized at startup from the measured tier byte rates
    #: (kvtier.measure_tier_rates micro-probe) against the prefill
    #: recompute rate — the smallest chain where promoting beats
    #: recomputing (kvtier.auto_min_pages). An explicit int always wins.
    kv_tier_min_pages: int | None = None
    #: KV-cache dtype: None = compute dtype (bf16); "fp8" stores the pool
    #: as float8_e4m3 — the TPU-native form of FastGen's quantized KV
    #: (scale-free: e4m3's dynamic range covers K/V activations, so pages
    #: need no side-car scale arrays and the kernel pays one convert per
    #: page). Halves the decode attention's page DMA, the measured
    #: dominant cost of a decode iteration (60% of device time on v5e).
    #: Fresh tokens compute/stage in bf16 and quantize at the pool merge.
    kv_cache_dtype: str | None = None
    #: ring collective-matmul tensor parallelism (latency hiding): the
    #: residual stream runs token-sharded over the ``tensor`` axis and
    #: every projection is an overlapped ring primitive — in-projs consume
    #: arriving activation shards into partial dots while the next shard
    #: is in flight (all-gather⊗matmul, QKV fused into ONE ring),
    #: out-projs ring-accumulate partial outputs toward their owner shard
    #: (matmul⊗reduce-scatter) instead of blocking on the GSPMD
    #: all-reduce (parallel/tensor.py). None = auto: on whenever tensor>1,
    #: the model's head/ffn dims divide by the axis, AND the program
    #: carries at least ``TP_OVERLAP_MIN_ROWS`` token rows per ring chunk
    #: — prefill/training-shaped M; decode windows (M = max_seqs) stay on
    #: the blocking path by default because each ring step re-reads the
    #: weight shard, and at HBM-roofline decode sizes n× weight traffic
    #: outweighs the tiny hidden collective until measured otherwise
    #: (ROADMAP open item). Programs whose row count doesn't divide fall
    #: back per-program (counted in stats["tp_fallbacks"]). False = off;
    #: True = require: ring EVERY divisible program including decode, and
    #: raise when the geometry can't ring.
    tp_overlap: bool | None = None
    #: speculative decoding (inference/speculative.py): None = off;
    #: "ngram" = self-speculative prompt-lookup proposer (no extra
    #: weights — candidates come from the sequence's own history);
    #: "draft" = a small draft model running in-process against its own
    #: paged KV pool (pass ``draft_model``/``draft_params`` to the engine
    #: constructor). Decode dispatches become verify rounds: one batched
    #: forward checks a k-token candidate tree per sequence against the
    #: paged pool under a tree-attention mask, exact accept/reject
    #: sampling commits every accepted token in one step (greedy mode is
    #: bit-identical to baseline decode), and rejected provisional tokens
    #: roll back through StateManager so audits stay clean. Refused in
    #: rolling-window ring mode (provisional slots would alias live ring
    #: pages) and under forced-ring tp_overlap (the verify forward runs
    #: all-position logits, which the token-sharded stream doesn't carry).
    spec_decode: str | None = None
    #: max candidate chain depth per proposal round (adapted per tenant
    #: from the acceptance-rate EMA, scheduler.SpecAcceptTracker); also
    #: bounds the draft mirror's decode budget. The n-gram proposer's
    #: branches and n-gram lengths and the depth cap while prefill is
    #: pending are constants of inference/speculative.py.
    spec_depth: int = 4
    #: candidate-tree node budget per sequence (root included); branchy
    #: n-gram proposals are truncated here so the verify width is bounded
    spec_max_nodes: int = 8
    #: speculative VERIFY attention formulation. None = auto (the kernel
    #: registry picks Pallas whenever the geometry allows — see
    #: attn_registry.select_attention). False pins the XLA gather
    #: formulation: under bf16 compute the two formulations round greedy
    #: near-ties differently (sub-ulp logit gaps), so streams calibrated
    #: bit-exact against a gather-verified baseline should pin False.
    #: True requires the kernel and refuses construction when the
    #: geometry can't serve it.
    spec_verify_pallas: bool | None = None
    #: serving-SLO telemetry (telemetry/): TTFT / time-between-tokens /
    #: queue-wait histograms, per-step occupancy, KV-page utilization,
    #: host spans around dispatch/drain. True enables the PROCESS-WIDE
    #: telemetry instance (shared /metrics with training + monitor
    #: backends); None follows its current state (DS_TPU_TELEMETRY /
    #: a training engine's config section); False pins this engine to a
    #: private disabled instance regardless.
    telemetry: bool | None = None
    #: per-request lifecycle tracing (telemetry/reqtrace.py): every
    #: admitted sequence gets a trace ID and a sampled event timeline
    #: (enqueue/admit with prefix-hit extent/prefill chunks/decode
    #: windows/spec rounds/rollbacks/commits/release), per-tenant
    #: attribution series (``put(..., tenant=)``), SLO histogram
    #: exemplars, and TTFT/TBT breach auto-capture. True implies
    #: telemetry; None follows the process-wide reqtrace state; False
    #: pins this engine's emissions off.
    reqtrace: bool | None = None
    #: fraction of requests whose full timeline is retained (sampling is
    #: deterministic in the trace ID; unsampled requests still count in
    #: the per-tenant series but carry no timeline/exemplar). None keeps
    #: the process tracer's current rate (default 1.0) — only an explicit
    #: value is forwarded, so one engine cannot stomp a lower rate
    #: another engine or the telemetry config already set.
    reqtrace_sample: float | None = None
    #: SLO-breach thresholds: a TTFT / per-token TBT observation past
    #: these dumps the offending request's full timeline plus an
    #: engine/pool state snapshot to the flight recorder (rate-limited —
    #: telemetry breach_interval_s). None = no auto-capture.
    slo_ttft_s: float | None = None
    slo_tbt_s: float | None = None


class InferenceEngineV2:
    @books_its_build
    def __init__(self, model: TransformerLM, params: Pytree | None = None,
                 config: RaggedInferenceConfig | dict | None = None,
                 topology: MeshTopology | None = None,
                 rng: jax.Array | None = None,
                 draft_model: TransformerLM | None = None,
                 draft_params: Pytree | None = None,
                 draft_rng: jax.Array | None = None):
        # the constructor by phase, into the build ledger: the phases
        # partition its wall time (``books_its_build`` opened the block,
        # in phase ``rest``, and ends it with one ``build:`` line)
        build = engine_build(type(self).__name__)
        if isinstance(config, dict):
            config = RaggedInferenceConfig(**config)
        self.config = config or RaggedInferenceConfig()
        cfg = self.config
        self.model = model
        self.mcfg: ModelConfig = model.config
        if topology is None:
            topology = MeshTopology(MeshConfig(tensor=cfg.tensor_parallel, data=1))
        self.topology = topology
        self._rules = default_activation_rules(topology)

        # one KV cache (allocator, pool, block table a sequence) for each
        # KIND of layer the model has: a table that grows for full layers,
        # a bounded ring for window layers (``cache_kinds``)
        # — and one RECORD a slot for layers whose state is no pages at all
        # (a "conv" kind: no allocator, no table)
        self._kinds = cache_kinds(model.config, cfg,
                                  max(topology.size("tensor"), 1))
        k0 = self._kinds[0]
        if k0.is_latent:
            # what the latent page does not do yet, refused by its name
            for on, what in ((topology.mesh.size > 1, "a device mesh"),
                             (cfg.kv_cache_dtype, "kv_cache_dtype"),
                             (cfg.spec_decode, "spec_decode"),
                             (cfg.kv_tier, "kv_tier")):
                if on:
                    raise ValueError(
                        f"kind {k0.name!r} (latent attention: one row a "
                        f"token shared by every head, no K/V halves) does "
                        f"not serve under {what} yet")
        self.state = StateManager(
            k0.num_blocks, cfg.block_size, cfg.max_seqs, k0.max_blocks,
            kind=k0.name, ring=bool(k0.ring_tokens),
            more_kinds={k.name: (k.num_blocks, k.max_blocks,
                                 bool(k.ring_tokens))
                        for k in self._kinds[1:] if not k.is_record},
            records={k.name: k.rows for k in self._kinds if k.is_record})
        # what only a linear chain of pages can do is refused below where
        # a kind keeps a ring or a record, with this reason
        not_pages = self.state.not_a_page_chain
        # there plans pack ROWS only: a rolling table is sized for
        # chunk-at-most steps and a grown chunk would overrun it; a record
        # hands over at chunk boundaries the warmed programs know
        self.scheduler = SplitFuseScheduler(
            self.state, cfg.chunk, pack=cfg.prefill_pack,
            grow_chunk=cfg.prefill_grow_chunk and not not_pages,
            max_rows=cfg.prefill_max_rows)

        # --- shared-prefix KV cache (radix reuse over the pool) ----------
        use_pc = cfg.prefix_cache
        if use_pc is None:
            # auto: ON for pack-mode linear serving, fp8-KV pages
            # included — published pages are served bit-for-bit (zero
            # copy, no requantization), and the cross-request
            # suffix-divergence parity test (tests/test_inference_v2.py::
            # test_v2_fp8_kv_prefix_cache_cross_request_parity) pins warm
            # == cold greedy streams at e4m3 granularity
            use_pc = self.scheduler.pack and not not_pages
        if use_pc and not_pages:
            raise ValueError(
                f"prefix_cache=True needs a sequence's state to be a "
                f"linear chain of pages: {not_pages} (a rolling KV ring's "
                f"published page would change under a reader; a record "
                f"has no page to publish). Set prefix_cache=False")
        self._prefix_cache = None
        if use_pc:
            from .prefix_cache import PrefixCache
            self._prefix_cache = PrefixCache(cfg.block_size)
            self.state.attach_prefix_cache(self._prefix_cache)

        # --- KV tiering: HBM → host RAM → NVMe (inference/kvtier.py) -----
        self._kv_tier = None
        if cfg.kv_tier:
            if self._prefix_cache is None:
                raise ValueError(
                    "kv_tier requires the shared-prefix cache: the tier "
                    "is an eviction sink under the radix trie (enable "
                    "prefix_cache, or serve pack-mode linear where auto "
                    "turns it on)")
            from .kvtier import (KVTier, KVTierConfig, auto_min_pages,
                                 measure_tier_rates)
            min_pages = cfg.kv_tier_min_pages
            if min_pages is None:
                # size the promote threshold from MEASURED tier rates
                # instead of a guessed constant: one page's demoted
                # payload is its full cross-layer K/V slab
                m0 = self.mcfg
                kv_bytes = 1 if cfg.kv_cache_dtype == "fp8" \
                    else jnp.dtype(cfg.dtype).itemsize
                page_bytes = int(np.prod(
                    k0.pool_shape(cfg.block_size)) // k0.num_blocks
                    * kv_bytes)
                min_pages = auto_min_pages(
                    measure_tier_rates(nvme_dir=cfg.kv_tier_nvme_dir),
                    page_bytes=page_bytes, block_size=cfg.block_size,
                    nvme=cfg.kv_tier_nvme_dir is not None)
            self._kv_tier = KVTier(KVTierConfig(
                ram_bytes=cfg.kv_tier_ram_bytes,
                nvme_dir=cfg.kv_tier_nvme_dir,
                nvme_bytes=cfg.kv_tier_nvme_bytes,
                min_pages=min_pages))
            # eviction becomes demotion: the sink gathers reclaimed
            # chains to host and absorbs them into the tier (best-effort
            # — a sink failure is counted and eviction proceeds)
            self._prefix_cache.evict_sink = self._demote_evicted
        # DS_TPU_STATE_AUDIT=1: full-pool ownership/refcount audit after
        # every release (debug mode — O(pool) per flush)
        import os as _os
        self._audit_state = _os.environ.get("DS_TPU_STATE_AUDIT") == "1"

        # --- versioned weights (live hot-swap, serving/deploy.py) --------
        # monotonic id + content digest of the params this engine serves;
        # "init" = the constructor's (model, params|rng) weights, before
        # any swap. Rides every exported PageBundle and the serving
        # heartbeat so cross-replica KV transfer can refuse version skew.
        # Mutation is pinned to swap_weights (check_state_invariants.py).
        self._weight_version: dict = {"id": 0, "digest": "init"}

        # --- weights: same tree as the trainer, TP-sharded ---------------
        build.phase("weights")
        self.params, plan = load_tp_params(model, params, rng, topology,
                                           cfg.dtype)
        #: a quantised weight's TP kind, by weight name
        self._qkind: dict[str, str] = {}
        if cfg.quant_bits:
            if cfg.quant_bits not in (4, 8, "fp8"):
                raise ValueError(f"quant_bits must be 4, 8 or 'fp8', got "
                                 f"{cfg.quant_bits}")
            self._quantize_weights(cfg.quant_bits, plan)
        # stack homogeneous layers [L, ...] so the ragged forward can
        # lax.scan over depth — compile time stays flat vs num_layers
        # (reference inference_transformer_base.py:535's per-layer loop is
        # kernel dispatch; under jit an unrolled loop is per-layer
        # RECOMPILATION). Heterogeneous moe patterns (freq > 1) keep the
        # unrolled loop.
        build.phase("stack")
        m = self.mcfg
        moe_flags = [is_moe_layer(m, i) for i in range(m.num_layers)]
        # ... and so does a stack whose layers differ in OPERATOR (a
        # "conv" kind among attention layers)
        uniform = all(moe_flags) or not any(moe_flags)
        #: ... or whose LEADING layers carry a dense feed-forward and the
        #: rest, all alike, routed experts (one kind of layer, no record:
        #: kanana-2): the leading layers keep their own trees and are
        #: walked unrolled, the tail is stacked and scanned
        lead = moe_flags.index(True) if any(moe_flags) else 0
        self._scan_lead = lead if (
            not uniform and all(moe_flags[lead:])
            and m.num_layers - lead > 1 and len(self._kinds) == 1
            and len(m.kinds_period) == 1) else 0
        self._scan_layers = (m.num_layers > 1
                             and (uniform or bool(self._scan_lead))
                             and not any(k.is_record for k in self._kinds))
        if self._scan_layers:
            layers = [self.params.pop(f"layer_{i}")
                      for i in range(self._scan_lead, m.num_layers)]
            stack_kw = {}
            if not cfg.quant_bits:
                is_p = lambda x: isinstance(x, P)
                stack_kw["out_shardings"] = jax.tree.map(
                    lambda p: NamedSharding(topology.mesh, P(None, *p)),
                    plan.param_specs[f"layer_{self._scan_lead}"],
                    is_leaf=is_p)
                # donate: each per-layer buffer frees as it is copied, so
                # init never holds 2x the layer weights in HBM
                stack_kw["donate_argnums"] = (0,)
            else:
                # quantized trees changed structure vs the plan's specs:
                # QuantLinear leaves take their 2D spec from the recorded
                # TP kind, everything else walks the original plan by dict
                # path. (No donation — int8/uint8 buffers can't alias the
                # stack.)
                from jax.tree_util import DictKey, tree_map_with_path

                spec0 = plan.param_specs[f"layer_{self._scan_lead}"]

                def stacked_sharding(path, leaf):
                    names = [p.key for p in path if isinstance(p, DictKey)]
                    last = names[-1] if names else ""
                    # routed-expert slabs live at moe/moe_layer/experts/*;
                    # the qwen2-moe shared expert (moe/shared_expert/*)
                    # stays bf16 and must fall through to the plan walk
                    if "experts" in names and f"moe_{last}" in self._qkind:
                        spec = KIND_SPEC_3D[self._qkind[f"moe_{last}"]]
                    elif "moe" not in names and last in self._qkind:
                        spec = KIND_SPEC_2D[self._qkind[last]]
                    else:
                        node = spec0
                        for n in names:
                            node = node[n]
                        spec = node
                    return NamedSharding(topology.mesh, P(None, *spec))

                stack_kw["out_shardings"] = tree_map_with_path(
                    stacked_sharding, layers[0])
            self.params["layers_stacked"] = jax.jit(
                lambda ls: jax.tree.map(lambda *xs: jnp.stack(xs), *ls),
                **stack_kw)(layers)
            # the per-layer buffers the stack could not take over (the
            # donation is refused for most of them) live as long as this
            # list: without this a stack and its layers are BOTH there
            # when the pool below is allocated (kanana-2's 1 + 4 layers:
            # 13.6 GiB in use and no room for a 3.1 GiB pool; my chip run,
            # PR 54, call D)
            del layers

        # --- the paged KV pool -------------------------------------------
        # [L, 2, KV, num_blocks, block_size, D], block-granular so the
        # kernel's per-page DMA ([KV, block_size, D] with the layer/half
        # offset folded into the index map) needs no reshape, and the
        # once-per-program stage merge scatters at (block, offset). The
        # pool is READ-ONLY inside the forward (inference/forward.py) —
        # fresh KV rides a small staged buffer and is merged exactly once
        # per dispatch.
        build.phase("pools")
        tp = max(topology.size("tensor"), 1)
        #: the pool's own head geometry: ``kv_pack`` KV heads side by side
        #: in a page row (2 where heads are 64 wide: ``forward.kv_pack``),
        #: so ``[.., KV / pack, nb, block, pack * head_dim]``
        self._kv_pack = kv_pack(m, tp)
        #: (heads, lanes) of a page row: the paged kinds' own (one geometry
        #: for them all: ``CacheKind.heads`` / ``lanes``)
        self._kv_geom = (k0.heads, k0.lanes)
        kv_spec = P(None, None, "tensor", None, None, None) \
            if self._kv_geom[0] % tp == 0 else \
            P(None, None, None, None, None, None)
        self._pool_sharding = NamedSharding(topology.mesh, kv_spec)
        # pin the pool's jit entry/exit layout to row-major: with the
        # layout-neutral DUS merges (pure writes: ``forward.merge_pages``)
        # the whole program then runs in one layout, and no step program
        # copies a pool (``profiling.trace.pool_sized_copies``)
        from jax.experimental.layout import Format, Layout
        self._pool_format = Format(
            Layout(major_to_minor=(0, 1, 2, 3, 4, 5)), self._pool_sharding)
        if cfg.kv_cache_dtype not in (None, "fp8"):
            raise ValueError(f"kv_cache_dtype must be None or 'fp8', got "
                             f"{cfg.kv_cache_dtype!r}")
        self._kv_dtype = jnp.float8_e4m3fn \
            if cfg.kv_cache_dtype == "fp8" else cfg.dtype
        build.phase("probes")
        self._guard_pinned_layout_against_cache()
        build.phase("pools")
        #: one pool a kind of layer: a tuple in ``self._kinds``' order, for
        #: every model
        # every model; a record kind's entry is its records ``[layers,
        # max_seqs + 1, rows, width]`` (the last one the trash record),
        # replicated, in the compute dtype: donated and returned like a pool
        repl = NamedSharding(topology.mesh, P())
        self.kv_pool = tuple(
            jax.device_put(jnp.zeros(k.pool_shape(cfg.block_size),
                                     cfg.dtype), repl)
            if k.is_record else
            jax.device_put(jnp.zeros(k.pool_shape(cfg.block_size),
                                     self._kv_dtype), self._pool_format)
            for k in self._kinds)
        #: a jitted program's sharding of its ``kv_pool`` argument
        self._pool_formats = tuple(repl if k.is_record else self._pool_format
                                   for k in self._kinds)
        logger.info("cache: " + "; ".join(
            f"{k.name}: {len(k.layers)} layer(s), a record of {k.rows} x "
            f"{k.width} a slot, {k.num_blocks} slots, "
            f"{self.kv_pool[c].nbytes} bytes (no pages: addressed by the "
            f"sequence's slot)" if k.is_record else
            f"{k.name}: {len(k.layers)} layer(s), pool {k.num_blocks} "
            f"blocks of {cfg.block_size}, table {k.max_blocks} a sequence"
            + (f" (a ring of {k.ring_tokens} tokens, window {k.window})"
               if k.ring_tokens else " (grows with the context)")
            + (f", ONE row of {k.row_values} values a token in {k.lanes} "
               f"lanes (no K/V halves), {self.kv_pool[c].nbytes} bytes"
               if k.is_latent else "")
            for c, k in enumerate(self._kinds)))

        build.phase("probes")
        # alibi needs a positional bias inside the kernel — XLA path only.
        # pallas_call has no GSPMD rule, so multi-device meshes run the
        # kernel per-shard through shard_map over ALL live axes: q sharded
        # on query heads over 'tensor', the pool on kv heads (the TP
        # slicing the weights already use), and every other axis manual
        # with replicated specs — legal because this engine replicates all
        # serving state across non-tensor axes (each data member computes
        # the same thing, which is the multi-replica serving layout).
        tp_ok = (m.num_heads % tp == 0 and m.kv_heads % tp == 0)
        pallas_ok = (paged_attention_usable(m.num_heads, *self._kv_geom,
                                            cfg.block_size)
                     and m.position_embedding != "alibi"
                     and (topology.mesh.size == 1 or tp_ok))
        if cfg.use_pallas_decode and not pallas_ok:
            raise ValueError(
                "use_pallas_decode=True but the paged attention kernels "
                "(decode + prefill) do not "
                "support this setup (needs head_dim in {64,128,256}, "
                "block_size % 8 == 0, heads % kv_heads == 0, no alibi, and "
                "head counts divisible by the tensor axis)")
        self._pallas_decode = pallas_ok if cfg.use_pallas_decode is None \
            else cfg.use_pallas_decode

        # ---- attention-formulation registry (attn_registry.py) ----------
        # ONE static selection per dispatch mode: every hot-path dispatch
        # consults these (and counts against them — see _emit_attn_kernel)
        # instead of carrying its own kernel-vs-gather conditional. The
        # reason string names WHY the gather fallback serves, for
        # ds_report and debugging silent perf regressions.
        from .attn_registry import select_attention
        if self._pallas_decode:
            no_pallas = ""
        elif cfg.use_pallas_decode is False:
            no_pallas = "use_pallas_decode=False (config pin)"
        elif m.position_embedding == "alibi":
            no_pallas = "alibi positional bias runs in the XLA path only"
        elif not (topology.mesh.size == 1 or tp_ok):
            no_pallas = (f"head counts ({m.num_heads}q/{m.kv_heads}kv) do "
                         f"not divide the tensor axis ({tp})")
        else:
            no_pallas = ("kernel-unusable geometry (needs head_dim in "
                         "{64,128,256}, block_size % 8 == 0 and even "
                         "GQA groups)")
        # tree-verify stage width: the forward pads T nodes to max(8, T)
        # rows, rounded up to a page multiple past one page
        T_tree = max(cfg.spec_max_nodes, 1)
        Ts_tree = stage_rows(T_tree, cfg.block_size)
        sel_kw = dict(num_heads=m.num_heads, kv_heads=self._kv_geom[0],
                      head_dim=self._kv_geom[1], block_size=cfg.block_size,
                      use_pallas=self._pallas_decode,
                      reason_not_usable=no_pallas)
        self._attn_decode_sel = select_attention(mode="decode", **sel_kw)
        self._attn_paged = self._attn_decode_sel.is_pallas
        #: the paged kernel's query tile for a whole prefill chunk, one
        #: entry a kind of layer: ``paged_plan`` — what the kernel itself
        #: calls — at the head counts a launch sees (a shard's, under a
        #: mesh); the ``paged:`` log lines. A decode program's rows (one
        #: token's query heads) ride one tile
        self.paged_plans: dict[str, Any] = {}
        if self._attn_paged:
            plan = paged_plan(cfg.chunk * (m.num_heads // self._kv_geom[0]),
                              self._kv_geom[0] // tp, cfg.block_size,
                              cfg.dtype, lanes=self._kv_geom[1])
            if k0.is_latent:
                # a chunk past the forms' break-even runs the EXPANDED
                # kernel, planned by heads (None: it stays absorbed)
                plan = latent_prefill_plan(
                    cfg.chunk, m.num_heads, m.kv_lora_rank,
                    m.qk_nope_head_dim, m.qk_rope_head_dim, m.v_head_dim,
                    k0.lanes, cfg.block_size, cfg.dtype) or plan
            self.paged_plans = {k.name: plan for k in self._kinds
                                if not k.is_record}
            for k in self.paged_plans:
                logger.info(f"paged: {k}: a chunk of {cfg.chunk} "
                            f"tokens is {plan.describe()}")
        self._attn_tree_sel = select_attention(
            mode="tree", tree_nodes=T_tree, stage_rows=Ts_tree, **sel_kw)
        if cfg.spec_verify_pallas is False:
            # formulation pin for gather-calibrated greedy streams: bf16
            # verify rounds sub-ulp near-ties differently per formulation
            from .attn_registry import AttnSelection
            self._attn_tree_sel = AttnSelection(
                "gather", "tree", "spec_verify_pallas=False (config pin)")
        elif cfg.spec_verify_pallas and not self._attn_tree_sel.is_pallas:
            raise ValueError(
                "spec_verify_pallas=True but the tree-verify kernel can't "
                f"serve this setup: {self._attn_tree_sel.reason}")

        # ---- ring collective-matmul TP (latency-hiding overlap) ----------
        # static geometry gate; programs whose row count doesn't divide the
        # axis additionally fall back per-program inside the forward
        ring_geom = (tp > 1 and m.num_heads % tp == 0
                     and m.kv_heads % tp == 0 and m.ffn_size % tp == 0)
        if cfg.tp_overlap and not ring_geom:
            raise ValueError(
                f"tp_overlap=True but the geometry can't ring: heads "
                f"{m.num_heads}, kv_heads {m.kv_heads}, ffn {m.ffn_size} "
                f"must all divide by the tensor axis size {tp}")
        self._tp_ring_n = tp if (ring_geom and cfg.tp_overlap is not False) \
            else 0
        self._tp_ring_force = cfg.tp_overlap is True
        self._tp_counter_base = overlap_counters.snapshot()
        if self._tp_ring_n:
            # ROADMAP odd-row item: pad packed prefill plans to the ring
            # multiple so exact-k programs with rows % tp != 0 ring
            # (masked empty rows) instead of falling back to the blocking
            # path; no-op when packing is off
            self.scheduler.row_multiple = self._tp_ring_n

        build.phase("rest")
        self._programs: dict[int, Any] = {}
        #: the decode block of a prefill plan that carries none
        #: (``_plan_args``): a ``[max_seqs, 1]`` plan of no live row
        self._empty_block: StepPlan | None = None
        #: the grouped GEMM's weight blocks, one entry a distinct expert
        #: shape and block (``RaggedForward.gmm``; the ``gmm:`` log lines)
        self.gmm_plans: dict[tuple, Any] = {}
        #: THE serving forward (inference/forward.py): everything it reads
        #: of this engine, handed over once
        self._forward = RaggedForward(
            mcfg=m, config=cfg, kinds=self._kinds, topology=topology,
            tp_ring_n=self._tp_ring_n, tp_ring_force=self._tp_ring_force,
            attn_decode_sel=self._attn_decode_sel,
            attn_tree_sel=self._attn_tree_sel, qkind=self._qkind,
            gmm_plans=self.gmm_plans, kv_pack=self._kv_pack)
        self._rng = jax.random.PRNGKey(17)
        self._results: dict[int, list[int]] = {}
        # device-resident last sampled token per slot: decode steps read it
        # on device (use_last), so the next dispatch never waits for a host
        # readback of the previous step's samples. COMMITTED with the
        # replicated sharding program outputs carry: an uncommitted array
        # keys a different jit cache entry, so every program warmed before
        # the first real step would silently recompile inside the first
        # SLA-scored serve (measured: 3-4s per shape).
        self._last_tok = jax.device_put(
            jnp.zeros((cfg.max_seqs,), jnp.int32),
            NamedSharding(topology.mesh, P()))
        # async pipeline: dispatched steps whose sampled tokens are still
        # riding d2h; committed lazily (see _drain)
        from collections import deque
        self._inflight: deque = deque()
        # the number the next entry appended to ``_inflight`` takes; the
        # spans of its dispatch, blocked drain and commit carry it
        self._entry_seq = 0
        # serving SLO instruments (telemetry/) — all no-ops when disabled
        from .. import telemetry as _telemetry
        if cfg.reqtrace and cfg.telemetry is False:
            raise ValueError(
                "reqtrace=True cannot combine with telemetry=False: "
                "request timelines ride the telemetry bundle (drop the "
                "telemetry=False pin or disable reqtrace)")
        if cfg.telemetry or cfg.reqtrace:
            rt_kw: dict[str, Any] = {}
            if cfg.reqtrace:
                # reqtrace implies the base substrate: timelines without
                # the registry/recorder would answer nothing
                rt_kw = {"reqtrace": True}
                if cfg.reqtrace_sample is not None:
                    rt_kw["reqtrace_sample"] = cfg.reqtrace_sample
                if cfg.slo_ttft_s is not None:
                    rt_kw["slo_ttft_s"] = cfg.slo_ttft_s
                if cfg.slo_tbt_s is not None:
                    rt_kw["slo_tbt_s"] = cfg.slo_tbt_s
            _telemetry.configure(enabled=True, **rt_kw)
        self._telem = _telemetry.get_telemetry() if cfg.telemetry is not False \
            else _telemetry.Telemetry(enabled=False)
        self.scheduler._telem = self._telem   # cfg.telemetry=False pins both
        # per-request lifecycle tracing: cfg.reqtrace=False pins THIS
        # engine's emissions to a private disabled tracer even when the
        # process-wide one is on (mirrors the telemetry=False pin); the
        # StateManager / scheduler / prefix cache emit through the same
        # handle, so one pin silences the whole serving stack
        self._rt = self._telem.reqtrace if cfg.reqtrace is not False \
            else _telemetry.ReqTracer(enabled=False)
        self.scheduler._reqtrace = self._rt
        self.state.reqtrace = self._rt
        if self._prefix_cache is not None:
            self._prefix_cache.reqtrace = self._rt
        if self._rt.enabled:
            # breach dumps attach an engine/pool state snapshot; weakref
            # so the process-wide tracer never keeps a dead engine (and
            # its device pool) alive. Two engines in one process: last
            # one wins, like the shared registry.
            import weakref
            ref = weakref.ref(self)
            self._rt.state_probe = lambda: (
                lambda e: None if e is None
                else e._reqtrace_state_snapshot())(ref())
        self._admit_t: dict[int, float] = {}      # uid → put() time
        self._first_sched: set[int] = set()       # uids past their 1st chunk
        self._last_commit_t: dict[int, float] = {}
        if self._telem.enabled:
            self._telem.set_health(serving=True, max_seqs=cfg.max_seqs,
                                   num_blocks=cfg.num_blocks)
        # mixed-load alternation: True → the next dispatch prefers the
        # decode window/plan over another prefill step
        self._serve_toggle = False
        #: wall-time split + counters for the serving artifact (VERDICT r03:
        #: "nothing in the artifact says where the time goes")
        self.stats = {"plan_s": 0.0, "dispatch_s": 0.0, "drain_block_s": 0.0,
                      "commit_s": 0.0, "dispatches": 0, "prefill_steps": 0,
                      "decode_steps": 0, "windows": 0, "window_iters": 0,
                      "forced_drains": 0,
                      "prefill_tokens": 0, "decode_tokens": 0,
                      # the decode block of a prefill step: steps whose
                      # block carried a decode token, the tokens that left
                      # that way (booked in ``decode_tokens`` too, in no
                      # ``decode_steps`` / ``window_iters``), and steps
                      # whose block had no live row
                      "fused_steps": 0, "fused_decode_tokens": 0,
                      "fused_empty_steps": 0,
                      # the pipeline, entry by entry (``_enqueue`` /
                      # ``_drain``): entries appended and the depth each
                      # joined; entries committed and the time from their
                      # append to the end of their commit; the same two
                      # for prefill plans alone
                      "entries_dispatched": 0, "inflight_depth_sum": 0,
                      "entries_committed": 0, "inflight_residence_s": 0.0,
                      "prefill_entries_committed": 0,
                      "prefill_residence_s": 0.0,
                      # shared-prefix KV cache (prefix_cache.py): prompt
                      # tokens served from the trie vs looked up, per-run
                      # (bench zeroes these with the rest of the dict)
                      "prefix_hit_tokens": 0, "prefix_lookup_tokens": 0,
                      "prefix_hit_rate": 0.0,
                      # KV tiering (kvtier.py): pages demoted on
                      # eviction, chains promoted on admission misses
                      # (a promote that fell back to recompute is the
                      # tier's own count, by reason: ``KVTier.stats``)
                      "kv_tier_demoted_pages": 0, "kv_tier_promotes": 0,
                      # ring collective-matmul overlap (trace-time deltas
                      # from parallel/tensor.py — see _refresh_tp_stats)
                      "tp_ring_matmuls": 0, "tp_ring_steps": 0,
                      "tp_bytes_permuted": 0, "tp_fallbacks": 0,
                      # speculative decoding (inference/speculative.py):
                      # rounds = batched verify dispatches, verifies =
                      # per-sequence verify commits, proposed/accepted =
                      # candidate (non-root) tree tokens, steps_saved =
                      # committed tokens beyond the one a baseline decode
                      # step would have produced
                      "spec_rounds": 0, "spec_verifies": 0,
                      "spec_proposed": 0, "spec_accepted": 0,
                      "spec_steps_saved": 0, "spec_accept_rate": 0.0,
                      # attention-formulation split (attn_registry.py):
                      # every decode/tree-verify dispatch counts against
                      # the registry's selected path — a nonzero gather
                      # count IS the visible fallback signal
                      "attn_pallas_decode": 0, "attn_gather_decode": 0,
                      "attn_pallas_tree": 0, "attn_gather_tree": 0,
                      # KV-page migration (inference/migration.py):
                      # disaggregated prefill/decode handoffs through
                      # this engine's pool, both directions, and the
                      # payload taken in
                      "migrations_out": 0, "migrations_in": 0,
                      "migration_bytes_in": 0,
                      # routed-expert layers (``routed_experts``): rows the
                      # live tokens of a step route (tokens x top_k x MoE
                      # layers) against the rows of the tile-aligned
                      # buffers the grouped GEMMs walk — host arithmetic
                      # from each dispatched plan's shape (``_count_moe``);
                      # and the rows of those programs that carried no
                      # request, which the sort's liveness mask left out
                      "moe_routed_rows": 0, "moe_padded_rows": 0,
                      "moe_masked_rows": 0,
                      # the paged kernel's grid steps that read a page
                      # against the slots x table-width rectangle around
                      # them (``_count_attn_steps``, host arithmetic too)
                      "attn_steps_live": 0, "attn_steps_rect": 0,
                      # a ring slot overwritten in place (window kinds)
                      "ring_blocks_reused": 0,
                      # pages a full table would have walked in window
                      # layers, and those of them the window kind did not
                      "attn_pages_unclipped": 0, "attn_pages_clipped": 0}
        if any(k.is_record for k in self._kinds):
            # a record kind: records live (one a sequence in a slot) and
            # the run's peak; prefill rows dispatched, and those of them
            # that started from a record their last chunk left (not zeros)
            self.stats.update({"state_records_live": 0,
                               "state_records_peak": 0,
                               "conv_chunks": 0, "conv_chunks_carried": 0})
        if k0.is_latent:
            # rows ``[c | k_r]`` the dispatched programs were to write, a
            # token a row whatever the layers (host arithmetic, at
            # dispatch: a plan's live tokens and its decode block's, a
            # window's scheduled iterations a slot)
            self.stats["latent_rows_written"] = 0
        for k in self._kinds:
            if k.is_record:
                continue
            # by kind of layer: blocks live sequences hold (sampled after
            # every dispatch, and the run's peak), and the paged kernel's
            # steps as above
            self.stats.update({f"kv_blocks_live_{k.name}": 0,
                               f"kv_blocks_peak_{k.name}": 0,
                               f"attn_steps_live_{k.name}": 0,
                               f"attn_steps_rect_{k.name}": 0})
        self._moe_layers = sum(is_moe_layer(m, i)
                               for i in range(m.num_layers))
        # measure the host<->device readback latency ONCE instead of
        # guessing it (VERDICT r04 weak #4: a fixed 0.15s age gate meant
        # the opportunistic commit path never fired — every drain
        # blocked): opportunistic drains trust is_ready() only after a
        # d2h copy has had ~2x the probed latency to land
        build.phase("probes")
        probe = jnp.arange(max(cfg.decode_window, 1) * cfg.max_seqs,
                           dtype=jnp.int32)
        lat = []
        for i in range(3):
            a = probe + i          # fresh buffer, no cached host copy
            # poll is_ready (compute done) WITHOUT block_until_ready, so
            # the timed np.asarray below is the d2h copy alone
            deadline = time.perf_counter() + 5.0
            while not a.is_ready() and time.perf_counter() < deadline:
                time.sleep(0.0005)
            t0 = time.perf_counter()
            np.asarray(a)
            lat.append(time.perf_counter() - t0)
        self._d2h_latency = float(np.median(lat))
        self._drain_age = min(2.0 * self._d2h_latency, 0.5)
        self.stats["d2h_latency_s"] = round(self._d2h_latency, 4)
        build.phase("rest")

        # --- speculative decoding (inference/speculative.py) -------------
        self._spec = None
        self._spec_tracker = None
        self._draft_engine = None
        # tokens committed by spec rounds inside _dispatch_next, folded
        # into step()'s emitted dict before it returns
        self._spec_emit: dict[int, list[int]] = {}
        if cfg.spec_decode:
            self._init_speculative(draft_model, draft_params, draft_rng)
            # the decode side belongs to the verify rounds: prefill steps
            # stay pure
            self.scheduler.decode_rides = False
            # draft-mirror rewinds show up on the TARGET request's
            # timeline (the mirror engine runs with telemetry off)
            self._spec.reqtrace = self._rt
        logger.info(
            f"engine_v2 up: blocks={cfg.num_blocks}x{cfg.block_size} "
            f"pool={sum(p.nbytes for p in self.kv_pool) / 1e6:.0f}MB "
            f"max_seqs={cfg.max_seqs} "
            f"chunk={cfg.chunk} tp={topology.size('tensor')}")
        # the chosen attention formulation and, for the gather fallback,
        # the reason — said once here so it is never a silent choice
        for sel in (self._attn_decode_sel, self._attn_tree_sel):
            if sel.mode == "decode" or cfg.spec_decode:
                logger.info(f"engine_v2 attention[{sel.mode}]: " + (
                    "Pallas paged kernel" if sel.is_pallas
                    else f"XLA gather — {sel.reason}"))

    def _guard_pinned_layout_against_cache(self) -> None:
        """jax 0.9.0 / libtpu 0.0.34 (measured on v5e, PR 21): an
        executable READ BACK from the persistent compilation cache returns
        its outputs in the device's DEFAULT layout, whatever layout the
        program pinned — while still reporting the pinned one. Where the
        two differ (head width 64: the default swaps the page and head
        dims) a warm start hands the second program a pool it refuses
        ("Layout passed to jit does not match the layout on the
        respective arg") and the worker dies on its first decode. Freshly
        compiled programs are right, so on such a device this engine's
        programs must never come from the cache — and jax's cache switch
        is process-wide, so the process serves without it (its programs
        compile in seconds: the layer stack is scanned). Where the pin IS
        the default (CPU; page rows 128 lanes wide: heads of 128, and heads
        of 64 since PR 50 holds two of them side by side in a page row,
        ``forward.kv_pack``) nothing changes. Presets that can still reach
        it: ``falcon-7b`` (ONE KV head of 64: nothing to pair it with),
        ``phi-2`` (heads of 80), ``phi-3-mini`` and ``gpt-neox-20b`` (96);
        no cell of the benchmark does. The fault still stands (PR
        50, calls 1 and 3: a head-64 engine with the guard off died on its
        warm start with the message above). The default is asked of the
        pool's REAL shape — it depends on all of it: ``[2, 2, 8, 16, 64,
        64]`` defaults to row-major, ``[1, 2, 8, 8192, 64, 64]`` to ``(0,
        1, 2, 4, 5, 3)`` (same calls) — by compiling an identity for that
        shape: nothing of its size is allocated."""
        cfg = self.config
        if not jax.config.jax_enable_compilation_cache:
            return
        shape = self._kinds[0].pool_shape(cfg.block_size)
        default = jax.jit(lambda x: x).lower(jax.ShapeDtypeStruct(
            shape, self._kv_dtype, sharding=self._pool_sharding)).compile(
            ).input_formats[0][0].layout.major_to_minor
        if tuple(default) == (0, 1, 2, 3, 4, 5):
            return
        from jax.experimental.compilation_cache import \
            compilation_cache as cc

        jax.config.update("jax_enable_compilation_cache", False)
        cc.reset_cache()
        logger.warning(
            f"engine_v2: persistent compilation cache turned OFF for this "
            f"process — the KV pool {shape} is pinned row-major but this "
            f"device's default layout for it is {tuple(default)}, and "
            f"executables read back from the cache lose pinned output "
            f"layouts (jax {jax.__version__})")

    def _init_speculative(self, draft_model, draft_params, draft_rng) -> None:
        """Bring up the configured proposer backend + the per-tenant
        accept-rate tracker (see ``RaggedInferenceConfig.spec_decode``).
        ``spec_decode="draft"`` builds a SECOND engine for the draft model
        in the same process — its own paged pool, allocator, and
        scheduler, stepping synchronously (no async pipeline, no windows:
        the proposer decodes exactly ``depth`` tokens per round and a
        window would run past them into the mirror's budget)."""
        cfg = self.config
        if cfg.spec_decode not in ("ngram", "draft"):
            raise ValueError(f"spec_decode must be None, 'ngram' or "
                             f"'draft', got {cfg.spec_decode!r}")
        if self.state.not_a_page_chain:
            raise ValueError(
                f"spec_decode needs a sequence's state to be a linear "
                f"chain of pages: {self.state.not_a_page_chain} "
                f"(provisional verify slots past the committed tail would "
                f"alias live pages of a rolling KV ring; a record cannot "
                f"take back a rejected token). Disable spec_decode")
        if self._tp_ring_force:
            raise ValueError(
                "spec_decode cannot combine with tp_overlap=True: the "
                "verify forward samples all-position logits, which the "
                "forced token-sharded ring stream does not carry (auto "
                "mode is fine — verify programs fall back per-program)")
        if cfg.spec_depth < 1:
            raise ValueError(f"spec_depth must be >= 1, got {cfg.spec_depth}")
        if cfg.spec_max_nodes < 2:
            raise ValueError(f"spec_max_nodes must be >= 2 (root + one "
                             f"candidate), got {cfg.spec_max_nodes}")
        # depth may never exceed the tree width budget (a chain of depth d
        # is d+1 nodes) — clamp here so every later depth request is valid
        base_depth = min(cfg.spec_depth, cfg.spec_max_nodes - 1)
        self._spec_tracker = SpecAcceptTracker(base_depth)
        if cfg.spec_decode == "ngram":
            self._spec = NGramProposer(
                base_depth, ngram_max=SPEC_NGRAM_MAX,
                ngram_min=SPEC_NGRAM_MIN, branches=SPEC_BRANCHES,
                max_nodes=cfg.spec_max_nodes)
            return
        if draft_model is None:
            raise ValueError("spec_decode='draft' needs a draft_model= "
                             "(and usually draft_params=) at engine "
                             "construction")
        self._draft_engine = InferenceEngineV2(
            draft_model, params=draft_params,
            config={
                "block_size": cfg.block_size,
                "num_blocks": cfg.num_blocks,
                "max_seqs": cfg.max_seqs,
                "chunk": cfg.chunk,
                # mirrors may overrun their own depth while a slower
                # mirror catches up (the proposal loop runs the WHOLE
                # draft engine up to 2*depth+4 steps per round); the
                # rewind next round discards the surplus, and rewind
                # caps the restarted budget to the admit-time block
                # reservation so the overrun KV always fits the pages
                "max_seq_len": cfg.max_seq_len + 2 * base_depth + 4,
                "dtype": cfg.dtype,
                "greedy": True,          # proposals are the draft argmax
                "decode_window": 1,
                "max_inflight": 0,       # synchronous mirror stepping
                "prefix_cache": False,
                "telemetry": False,
                "use_pallas_decode": cfg.use_pallas_decode,
            },
            rng=draft_rng)
        self._spec = DraftModelProposer(self._draft_engine)

    # ------------------------------------------------------------------
    @staticmethod
    def _tp_kind(spec) -> str:
        """Classify a weight's TP sharding for its 2D [K, N] matmul view:
        ``col`` = output columns sharded (gather-free, per-shard GEMM),
        ``row`` = contraction dim sharded (per-shard GEMM + psum),
        ``rep`` = replicated."""
        def has_t(e):
            return e == "tensor" or (isinstance(e, (tuple, list))
                                     and "tensor" in e)

        entries = tuple(spec) if spec is not None else ()
        if entries and has_t(entries[0]):
            return "row"
        if any(has_t(e) for e in entries[1:]):
            return "col"
        return "rep"

    def _quantize_weights(self, bits: int, plan) -> None:
        """ZeRO-Inference for the ragged engine: matmul weights become
        QuantLinear codes+scales consumed by the in-tile-dequant Pallas
        GEMM (reference inference/v2/kernels/cutlass_ops/mixed_gemm/).

        TP-composable (reference model_implementations/sharding/): on a
        multi-device mesh each tensor shard quantizes ITS slice inside a
        shard_map, so group boundaries live within shards and the codes/
        scales carry the same tensor-axis sharding as the bf16 weights
        they replace. The matmuls then run per-shard via
        ``RaggedForward.qmm``.
        MoE routed-expert weights quantize into QuantGrouped slabs served
        by the grouped in-tile-dequant GEMM (reference cutlass_ops/
        moe_gemm/) — the gate and the qwen2-moe shared expert stay exact
        (tiny, and the router is precision-critical). The untied
        unembedding quantizes too; the embedding table stays exact (it is
        gathered, not matmul'd)."""
        from jax import shard_map

        from ..ops.pallas.quant_matmul import (quantize_grouped,
                                               quantize_weight)

        m = self.mcfg
        mesh = self.topology.mesh
        tp = self.topology.size("tensor")
        spec0 = plan.param_specs.get("layer_0", {})

        # one jitted per-shard quantize program per (kind, grouped): the
        # same 7-ish weight shapes repeat every layer, and the jit cache
        # keys on function identity — a fresh lambda per weight would
        # compile O(layers x weights) programs
        quant_fns: dict[tuple, Any] = {}

        def quant_fn(kind: str, grouped: bool):
            key = (kind, grouped)
            if key not in quant_fns:
                ws = (KIND_SPEC_3D if grouped else KIND_SPEC_2D)[kind]
                qf = quantize_grouped if grouped else quantize_weight
                quant_fns[key] = jax.jit(shard_map(
                    lambda wl: qf(wl, bits=bits),
                    mesh=mesh, in_specs=(ws,), out_specs=ws,
                    check_vma=False))
            return quant_fns[key]

        def record_kind(name: str, kind: str) -> None:
            # _qkind keys by weight NAME (shared across the layer stack):
            # sound only while every layer shards a given weight the same
            # way — fail loudly the moment a heterogeneous stack breaks
            # that (advisor r03: a silent overwrite would mis-shard)
            prev = self._qkind.setdefault(name, kind)
            if prev != kind:
                raise ValueError(
                    f"TP kind for weight '{name}' differs across layers "
                    f"({prev} vs {kind}); per-name quantized sharding "
                    f"requires homogeneous layer shardings")

        def q2d(w, K: int, name: str, spec) -> Any:
            kind = self._tp_kind(spec) if tp > 1 else "rep"
            record_kind(name, kind)
            w2 = jnp.asarray(w, jnp.float32).reshape(K, -1)
            if mesh.size == 1:
                return quantize_weight(w2, bits=bits)
            return quant_fn(kind, grouped=False)(w2)

        def qg3(w, name: str, spec) -> Any:
            """Stacked expert weights [n, K, N]: kind reads dims 1/2 (dim 0
            is the expert slab index, never tensor-sharded on a serving
            mesh)."""
            kind = self._tp_kind(tuple(spec)[1:]) \
                if tp > 1 and spec is not None else "rep"
            record_kind(name, kind)
            w3 = jnp.asarray(w, jnp.float32)
            if mesh.size == 1:
                return quantize_grouped(w3, bits=bits)
            return quant_fn(kind, grouped=True)(w3)

        before = sum(l.nbytes for l in jax.tree.leaves(self.params))
        E = m.hidden_size
        for i in range(m.num_layers):
            layer = self.params[f"layer_{i}"]
            # (a "conv" layer's operator stays exact: 2.7 % of an LFM2
            # expert layer's bytes, as the router and a shared expert do)
            if "attn" in layer:
                a = layer["attn"]
                sa = spec0.get("attn", {})
                # (latent attention has no wk / wv: its small down- and
                # up-projections ``w_dkv`` / ``w_uk`` / ``w_uv`` — 20 % of
                # the attention's bytes — stay exact, as the router does)
                for k in ("wq", "wk", "wv"):
                    if k in a:
                        a[k] = q2d(a[k], E, k, sa.get(k))  # [E, (H|KV)*D]
                a["wo"] = q2d(a["wo"], a["wo"].shape[0] * a["wo"].shape[1],
                              "wo", sa.get("wo"))
            if "ffn" in layer:
                f = layer["ffn"]
                sf = spec0.get("ffn", {})
                for k in ("w_gate", "w_up"):
                    if k in f:
                        f[k] = q2d(f[k], E, k, sf.get(k))
                # (its own width: a mixed stack's dense layers are not
                # the experts' ``ffn_size``)
                f["w_down"] = q2d(f["w_down"], f["w_down"].shape[0],
                                  "w_down", sf.get("w_down"))
            if "moe" in layer:
                ex = layer["moe"]["moe_layer"]["experts"]
                se = (spec0.get("moe", {}).get("moe_layer", {})
                      .get("experts", {}))
                for k in ("w_gate", "w_up", "w_down"):
                    if k in ex:
                        ex[k] = qg3(ex[k], f"moe_{k}", se.get(k))
        if not m.tie_embeddings:
            self.params["unembed"] = q2d(
                self.params["unembed"], E, "unembed",
                plan.param_specs.get("unembed"))
        else:
            # tied models: the embedding GATHER stays exact; the logits
            # projection reads an int8/int4 copy of the table ([E, V]
            # transposed view) — it is the decode step's single largest
            # weight read and sits squarely on the HBM roofline
            se = plan.param_specs.get("embed")
            spec_t = tuple(reversed(tuple(se))) if se is not None else None
            self.params["logits_q"] = q2d(
                jnp.asarray(self.params["embed"], jnp.float32).T, E,
                "logits", spec_t)
        after = sum(l.nbytes for l in jax.tree.leaves(self.params))
        logger.info(f"engine_v2 int{bits} weights: "
                    f"{before / 1e6:.0f}MB -> {after / 1e6:.0f}MB")

    def _program(self, T: int, S_rows: int | None = None):
        """Step program for a [S_rows, T] plan. Packed prefill plans
        (S_rows < max_seqs) carry fewer, wider rows — the token-budget
        menu VERDICT r04 weak #2 asked for — and map each row to its
        physical slot through ``row_slots`` (all-distinct, so the
        last-token scatter is race-free).

        A PREFILL program (T > 1) always takes one more argument, the
        DECODE BLOCK (``_plan_args``: a ``[max_seqs, 1]`` decode plan's
        arrays, full static width, whoever is live): the forward walks the
        layers once over both segments, the block's one token a row is
        merged, sampled and chained through ``last_tok`` as a ``[S, 1]``
        step's, and ``toks`` holds the plan's rows then the block's. The
        key, and so the compiled menu, does not know the block."""
        key = (T, S_rows)
        if key not in self._programs:
            cfg = self.config

            def from_last(last_tok, token_ids, use_last, row_slots):
                # decode rows whose previous token is still in flight read
                # the device-resident last sample instead of the host
                # placeholder (only col 0 can be such a row: 1-token rows)
                row_last = last_tok[row_slots]
                return row_last, token_ids.at[:, 0].set(
                    jnp.where(use_last.astype(bool), row_last,
                              token_ids[:, 0]))

            def sample(logits, rng):
                return sample_logits(logits.astype(jnp.float32), rng,
                                     temperature=cfg.temperature,
                                     top_k=cfg.top_k, top_p=cfg.top_p,
                                     greedy=cfg.greedy)

            def step_plain(params, kv_pool, last_tok, token_ids, positions,
                           slot_map, block_tables, seq_lens, sample_idx,
                           do_sample, use_last, row_slots, rng):
                row_last, token_ids = from_last(last_tok, token_ids,
                                                use_last, row_slots)
                with nn.logical_axis_rules(self._rules):
                    (k_ys, v_ys), logits = self._forward(
                        params, kv_pool, token_ids, positions,
                        block_tables, seq_lens, sample_idx)
                    # the ONE pool write of this program
                    kv_pool = merge_step(kv_pool, slot_map, k_ys, v_ys, T)
                with device_scope("sample"):
                    toks = sample(logits, rng)
                    last_tok = last_tok.at[row_slots].set(
                        jnp.where(do_sample.astype(bool), toks, row_last))
                return kv_pool, last_tok, toks

            def step_fused(params, kv_pool, last_tok, token_ids, positions,
                           slot_map, block_tables, seq_lens, sample_idx,
                           do_sample, use_last, row_slots, block, rng):
                (b_tok, b_pos, b_slot_map, b_tables, b_lens, b_do_sample,
                 b_use_last, b_slots) = block
                row_last, token_ids = from_last(last_tok, token_ids,
                                                use_last, row_slots)
                _, b_tok = from_last(last_tok, b_tok, b_use_last, b_slots)
                with nn.logical_axis_rules(self._rules):
                    ((k_ys, v_ys), logits), ((bk_ys, bv_ys), b_logits) = \
                        self._forward(
                            params, kv_pool, token_ids, positions,
                            block_tables, seq_lens, sample_idx,
                            block=DecodeBlock(b_tok, b_pos, b_tables, b_lens))
                    # the ONE pool write of this program, a segment: the
                    # chunks by pages, the block's one token a row by rows
                    # (a sequence is in one segment or the other; a row
                    # with no request writes the trash block / record)
                    kv_pool = merge_step(kv_pool, slot_map, k_ys, v_ys, T)
                    kv_pool = merge_step(kv_pool, b_slot_map, bk_ys, bv_ys,
                                         1)
                rows = logits.shape[0]
                with device_scope("sample"):
                    toks = sample(jnp.concatenate([logits, b_logits]), rng)
                    # (the plan's empty rows hold unused slots — a block
                    # row's among them — and write back what they read: the
                    # block's rows write after them, and only those that
                    # sampled, for a block row with no request may sit in
                    # the slot of a sequence the plan has just sampled)
                    last_tok = last_tok.at[row_slots].set(
                        jnp.where(do_sample.astype(bool), toks[:rows],
                                  row_last))
                    last_tok = last_tok.at[jnp.where(
                        b_do_sample.astype(bool), b_slots,
                        last_tok.shape[0])].set(toks[rows:], mode="drop")
                return kv_pool, last_tok, toks

            step = step_fused if T > 1 else step_plain
            # distinct module names per kind: device traces attribute
            # jit_step_prefill vs jit_step_decode busy time separately
            # (a T=1 decode plan in "prefill" seconds would corrupt the
            # trace-derived prefill MFU)
            step.__name__ = "step_prefill" if T > 1 else "step_decode"
            # non-pool outputs PINNED replicated: with tp_overlap's sharded
            # intermediates, letting XLA choose (None) can shard last_tok's
            # output and break its donation alias (replicated input)
            repl = NamedSharding(self.topology.mesh, P())
            self._programs[key] = register_program(jax.jit(
                step, donate_argnums=(1, 2),
                in_shardings=(None, self._pool_formats)
                + (None,) * (12 if T > 1 else 11),
                out_shardings=(self._pool_formats, repl, repl)),
                key=key, cause=("dispatch", self._entry_seq))
        return self._programs[key]

    def _plan_args(self, plan: StepPlan) -> tuple:
        """THE site that turns a plan into a step program's arguments
        (between ``last_tok`` and ``rng``), a tuple a kind of layer
        (``self._kinds``' order) where the program takes one: the plan
        holds the primary's slot map and table itself and every further
        kind's in ``more``. A prefill plan's last argument is its decode
        block's arrays — those of a block with no live row where the plan
        carries none."""
        of_kind = {self._kinds[0].name: (plan.slot_map, plan.block_tables),
                   **plan.more}
        slot_maps, tables = zip(*(of_kind[k.name] for k in self._kinds))
        args = (plan.token_ids, plan.positions, slot_maps, tables,
                plan.seq_lens, plan.sample_idx, plan.do_sample,
                plan.use_last, plan.row_slots)
        if plan.token_ids.shape[1] == 1:
            return args
        block = plan.block
        if block is None:
            if self._empty_block is None:
                self._empty_block = self.scheduler._desc("decode", 1, [])
            block = self._empty_block
        (tok, pos, slot_maps, tables, lens, _, do_sample, use_last,
         slots) = self._plan_args(block)    # (it samples at column 0)
        return args + ((tok, pos, slot_maps, tables, lens, do_sample,
                        use_last, slots),)

    def _window_program(self, W: int, cause: tuple | None = None):
        """Up to W chained decode steps in one jitted program: per step,
        each slot's write slot comes from its block table at the current
        position, the forward runs with T=1, and the sampled token feeds
        the next step — one dispatch per window instead of per token.
        The per-iteration TAIL — logits projection, sampling, write-slot
        bookkeeping, activity masking — is traced into the same program
        (``_iter``), so nothing inside the window ever returns to the
        host or dispatches separately.

        Round-4 semantics (VERDICT r03 weak #4 "decode windows commit
        blind"): slots run independently — a slot goes inactive when it
        samples its eos or exhausts its per-slot remaining budget
        (``rem``), its later KV writes land in the trash block, and
        inactive lanes emit -1 so the host commit sees exactly the
        accepted prefix. The first token per slot comes from the
        device-resident last-sample array when the host value is still
        in flight (``use_last``).

        The window is a FIXED-trip ``lax.scan``: a known trip count lets
        XLA software-pipeline across iterations (iteration i+1's first
        weight reads overlap iteration i's tail), which a data-dependent
        exit test forbids. Work is wasted only when EVERY slot exits early
        (eos): the scheduler already sizes W to the largest remaining
        budget.

        ``cause``: who asks, for the build ledger's record of a program
        made here (the dispatch of the entry under way, unless told)."""
        key = ("win", W)
        if key not in self._programs:
            cfg = self.config
            bs = cfg.block_size
            m = self.mcfg
            Ws = stage_rows(W, bs)

            def run(params, kv_pool, last_tok, tok_host, use_last, pos0,
                    lens0, block_tables, rem, eos_ids, rng):
                S = tok_host.shape[0]
                KV, D = self._kv_geom
                kinds = self._kinds
                tok0 = jnp.where(use_last.astype(bool), last_tok, tok_host)
                active0 = rem > 0
                # (a tuple a kind of layer, as the forward takes them; a
                # record kind's running record starts as its slots' records
                # — a window's row IS its slot — and has no V half)
                stage0 = tuple(
                    (pool[:, :S], None) if k.is_record else
                    (jnp.zeros((len(k.layers), S, KV, Ws, D), cfg.dtype),
                     None) if k.is_latent else
                    (jnp.zeros((len(k.layers), S, KV, Ws, D), cfg.dtype),) * 2
                    for k, pool in zip(kinds, kv_pool))
                stage0 = tuple(zip(*stage0))     # (k halves, v halves)
                base = pos0          # stage base position, fixed per window

                def _iter(i, tok, pos, lens, rng, active, kbuf, vbuf):
                    """One fully-fused decode iteration; returns this
                    iteration's emitted tokens/slots plus the advanced
                    state."""
                    slots = []
                    for k, table in zip(kinds, block_tables):
                        if k.is_record:      # written once, by slot, below
                            slots.append(None)
                            continue
                        blk = jnp.take_along_axis(
                            table, ((pos // bs) % k.max_blocks)[:, None],
                            axis=1)[:, 0]  # ring slot (mod no-op linear)
                        # inactive slots' staged rows merge into the trash
                        # block
                        slots.append(jnp.where(active,
                                               blk * bs + pos % bs, 0))
                    slot = tuple(slots)
                    with nn.logical_axis_rules(self._rules):
                        (kb_new, vbuf), logits = self._forward(
                            params, kv_pool, tok[:, None], pos[:, None],
                            block_tables, lens, jnp.zeros_like(pos),
                            kv_stage=(kbuf, vbuf), stage_fill=i,
                            stage_starts=base, live=active[:, None])
                    # a row that is not live in this iteration keeps the
                    # record it has
                    kbuf = tuple(
                        jnp.where(active[None, :, None, None],
                                  new.astype(old.dtype), old)
                        if k.is_record else new
                        for k, new, old in zip(kinds, kb_new, kbuf))
                    with device_scope("sample"):
                        rng, sub = jax.random.split(rng)
                        nxt = sample_logits(logits.astype(jnp.float32), sub,
                                            temperature=cfg.temperature,
                                            top_k=cfg.top_k, top_p=cfg.top_p,
                                            greedy=cfg.greedy)
                        out_tok = jnp.where(active, nxt, -1)
                        # slots stop at their eos or when their budget is
                        # spent
                        nxt_active = active & (nxt != eos_ids) \
                            & (i + 1 < rem)
                        tok = jnp.where(active, nxt, tok)
                        pos = jnp.where(active, pos + 1, pos)
                        lens = jnp.where(active, lens + 1, lens)
                    return (out_tok, slot, tok, pos, lens, rng, nxt_active,
                            kbuf, vbuf)

                def body(carry, i):
                    tok, pos, lens, rng, active, kbuf, vbuf = carry
                    (out_tok, slot, tok, pos, lens, rng, active, kbuf,
                     vbuf) = _iter(i, tok, pos, lens, rng, active, kbuf,
                                   vbuf)
                    return ((tok, pos, lens, rng, active, kbuf, vbuf),
                            (out_tok, slot))

                ((tok, _, _, _, _, kbuf, vbuf),
                 (buf, slots)) = jax.lax.scan(
                    body, (tok0, pos0, lens0, rng, active0, *stage0),
                    jnp.arange(W, dtype=jnp.int32))
                # useful-iteration count: iterations past the last active
                # slot emit all -1
                i = jnp.sum(jnp.any(buf >= 0, axis=1), dtype=jnp.int32)
                # only window PARTICIPANTS may update the device-resident
                # last token: slots outside the window (empty/sched_done)
                # carry tok0 = 0, and clobbering their last_tok would make
                # a later use_last dispatch decode from token 0 (advisor
                # r04) — safe under today's all-decode window invariant,
                # load-bearing the moment window eligibility goes partial
                tok = jnp.where(active0, tok, last_tok)

                # merge the WHOLE window's staged KV into the pool — the
                # one pool write of this program (the pool stayed
                # read-only through every iteration above)
                merged = []
                for k, pool, kb, vb, sl in zip(kinds, kv_pool, kbuf, vbuf,
                                               slots):
                    if k.is_record:
                        # the window's last records, for the rows that
                        # took part in it; the rest land in the trash one
                        merged.append(merge_records(
                            pool, jnp.where(active0, jnp.arange(S), S), kb))
                        continue
                    L = len(k.layers)
                    with device_scope("kv_commit"):
                        ks = (kb[:, :, :, :W, :].transpose(0, 3, 1, 2, 4)
                              .reshape(L, W * S, KV, D))
                        vs = None if vb is None else (
                            vb[:, :, :, :W, :].transpose(0, 3, 1, 2, 4)
                            .reshape(L, W * S, KV, D))
                        merged.append(merge_rows(
                            pool, sl.reshape(-1), ks, vs))
                # toks [W, S], iters run
                return tuple(merged), tok, buf, i

            # non-pool outputs pinned replicated (see _program)
            repl = NamedSharding(self.topology.mesh, P())
            self._programs[key] = register_program(jax.jit(
                run, donate_argnums=(1, 2),
                in_shardings=(None, self._pool_formats) + (None,) * 9,
                out_shardings=(self._pool_formats, repl, repl, repl)),
                key=key, cause=cause or ("dispatch", self._entry_seq))
        return self._programs[key]

    def warm_decode_windows(self, sizes: list[int] | None = None,
                            skip_existing: bool = True) -> None:
        """Compile AND execute decode-window programs ahead of serving —
        THE warm path for every pow2 window size the dispatcher can emit
        (full windows, budget-shrunk tails, and the mixed-load cap): a
        first compile inside an SLA-scored serve costs seconds. Lives
        here so the zero-state call stays next to ``_window_program``'s
        signature. The call is harmless by construction: ``rem`` = 0
        keeps every slot inactive, staged KV lands in the trash block,
        and the masked last-token update leaves ``_last_tok`` untouched.
        ``sizes`` defaults to every pow2 in [2, decode_window];
        ``skip_existing`` skips sizes whose program was already built
        (e.g. timed by a bench probe)."""
        if sizes is None:
            W = self.config.decode_window
            W = 1 << (W.bit_length() - 1) if W > 1 else 0
            sizes = []
            while W > 1:
                sizes.append(W)
                W //= 2
        S = self.state.max_seqs
        z = lambda *s: np.zeros(s, np.int32)
        tables = tuple(z(S) if k.is_record else z(S, k.max_blocks)
                       for k in self._kinds)
        for W in sizes:
            if W <= 1 or (skip_existing and ("win", W) in self._programs):
                continue
            fn = self._window_program(W, cause=("warm", None))
            self._rng, sub = jax.random.split(self._rng)
            self.kv_pool, self._last_tok, _, _ = fn(
                self.params, self.kv_pool, self._last_tok, z(S),
                np.zeros(S, np.uint8), z(S), z(S), tables, z(S),
                np.full(S, -1, np.int32), sub)
        jax.block_until_ready(self.kv_pool)

    def _try_dispatch_window(self, prefill_pending: bool = False) -> bool:
        """Decode fast path: dispatch up to ``decode_window`` decode steps
        in ONE program (per-slot budgets) without waiting for any
        readback. Runs over the decode-READY subset — slots still
        prefilling (or empty) ride along inactive (rem=0, masked last-
        token update), so mixed states window too; the caller alternates
        windows with prefill steps (which carry the decoders one token
        each, as their decode block). With ``prefill_pending`` the
        window is capped at ``decode_window_mixed_cap`` so a waiting
        chunk (TTFT) is never stuck behind a full-length window — the
        alternation still hands decoders a window every other dispatch,
        just a shorter one while prefill drains."""
        W_max = self.config.decode_window
        if prefill_pending and self.config.decode_window_mixed_cap:
            W_max = min(W_max, self.config.decode_window_mixed_cap)
        if W_max <= 1:
            return False
        live = [s for s in self.state.seqs.values()
                if not s.sched_done and s.slot >= 0
                and s.pending_sched == 1]
        if not live:
            return False
        W = min(max(s.gen_remaining_sched for s in live), W_max)
        if W <= 1:
            return False
        W = 1 << (W.bit_length() - 1)   # pow2 → bounded set of programs

        t0 = time.perf_counter()
        S = self.state.max_seqs
        with self._telem.span("plan", kind="window", seq=self._entry_seq):
            tok0 = np.zeros((S,), np.int32)
            use_last = np.zeros((S,), np.uint8)
            pos0 = np.zeros((S,), np.int32)
            lens0 = np.zeros((S,), np.int32)
            # (a record kind has no table: a window's row is its slot)
            tables = tuple(np.zeros((S,) if k.is_record
                                    else (S, k.max_blocks), np.int32)
                           for k in self._kinds)
            rem = np.zeros((S,), np.int32)
            eos = np.full((S,), -1, np.int32)
            sched: dict[int, tuple[int, int]] = {}   # uid -> (slot, n sched)
            for s in live:
                sl = s.slot
                if s.n_inflight:
                    use_last[sl] = 1             # value only on device
                else:
                    tok0[sl] = s.tokens[-1]
                pos0[sl] = s.len_sched - 1
                lens0[sl] = s.len_sched
                for k, table in zip(self._kinds, tables):
                    if not k.is_record:
                        blocks = self.state.blocks_of(s, k.name)
                        table[sl, :len(blocks)] = blocks
                n = min(s.gen_remaining_sched, W)
                rem[sl] = n
                if s.eos_id is not None:
                    eos[sl] = s.eos_id
                sched[s.uid] = (sl, n)
        self.stats["plan_s"] += time.perf_counter() - t0

        t0 = time.perf_counter()
        self._emit_attn_kernel("decode")
        with self._telem.span("dispatch", kind="window", W=W,
                              seq=self._entry_seq):
            fn = self._window_program(W)
            self._rng, sub = jax.random.split(self._rng)
            self.kv_pool, self._last_tok, toks, iters = fn(
                self.params, self.kv_pool, self._last_tok, tok0, use_last,
                pos0, lens0, tables, rem, eos, sub)
        # dispatch-time speculative advance: KV for positions up to
        # len_sched-1+n-1 is now scheduled, n new samples are in flight
        for s in live:
            _, n = sched[s.uid]
            self.state.note_written(s, s.len_sched - 1, s.len_sched - 1 + n)
            s.n_sched = s.len_sched - 1 + n
            s.n_inflight += n
        toks.copy_to_host_async()
        iters.copy_to_host_async()
        self._enqueue({"kind": "window", "sched": sched, "toks": toks,
                       "iters": iters, "t": time.perf_counter()})
        self.stats["dispatch_s"] += time.perf_counter() - t0
        self.stats["dispatches"] += 1
        self.stats["windows"] += 1
        self._count_moe(int(rem.sum()), S, iters=W)
        if "latent_rows_written" in self.stats:
            self.stats["latent_rows_written"] += int(rem.sum())
        self._count_attn_steps(lens0, pos0,
                               stage_rows(W, self.config.block_size),
                               iters=W)
        if self._rt.enabled:
            for s in live:
                self._rt.event(s.uid, "decode_window", W=W,
                               tokens=sched[s.uid][1])
        if self._telem.enabled:
            # window occupancy is row-based: live decoders / max slots
            self._record_dispatch_telemetry("decode_window", len(live),
                                            self.state.max_seqs, ())
        return True

    def _spec_program(self, T: int):
        """The speculative VERIFY forward: one batched tree-masked step
        over the read-only pool ([S, T] candidate-tree nodes per row,
        ancestors-only stage visibility) sampling the TARGET distribution
        at EVERY node. Returns the staged fresh KV (k_ys, v_ys) and the
        per-node samples — the pool is NOT written here; the caller
        merges only the accepted path (:meth:`_spec_merge_program`), so
        rejected candidates never reach the pool."""
        key = ("spec", T)
        if key not in self._programs:
            cfg = self.config

            def run(params, kv_pool, token_ids, positions, block_tables,
                    seq_lens, tree_mask, n_nodes, rng):
                # a row's nodes past its tree's, and a row with no tree,
                # are padding: they route to no expert
                live = jnp.arange(T)[None] < n_nodes[:, None]
                with nn.logical_axis_rules(self._rules):
                    (k_ys, v_ys), logits = self._forward(
                        params, kv_pool, token_ids, positions,
                        block_tables, seq_lens,
                        jnp.zeros(token_ids.shape[0], jnp.int32),
                        tree_mask=tree_mask, live=live)
                with device_scope("sample"):
                    toks = sample_tree_logits(
                        logits.astype(jnp.float32), rng,
                        temperature=cfg.temperature, top_k=cfg.top_k,
                        top_p=cfg.top_p, greedy=cfg.greedy)
                return k_ys, v_ys, toks

            run.__name__ = "step_spec_verify"
            repl = NamedSharding(self.topology.mesh, P())
            # pool NOT donated: it stays live (unchanged) for the merge
            # program that runs after the host-side acceptance walk
            self._programs[key] = register_program(jax.jit(
                run, in_shardings=(None, self._pool_formats) + (None,) * 7,
                out_shardings=(repl, repl, repl)),
                key=key, cause=("dispatch", self._entry_seq))
        return self._programs[key]

    def _spec_merge_program(self, T: int):
        """THE pool write of a spec round: fold the verify step's staged
        KV rows into the paged pool (the ONE pool of a ring-free model:
        ``_init_speculative`` refuses the rest), row n ↔ ``flat_slots[n]``
        (host-built
        AFTER the acceptance walk — accepted-path nodes get their
        sequence's tail-page slots, every rejected/padding node points at
        the trash block, so unaccepted KV never lands in a real page)."""
        key = ("spec_merge", T)
        if key not in self._programs:
            m = self.mcfg

            def run(kv_pool, k_ys, v_ys, flat_slots):
                L, S = k_ys.shape[0], k_ys.shape[1]
                with device_scope("kv_commit"):
                    ks = (k_ys[:, :, :, :T, :].transpose(0, 1, 3, 2, 4)
                          .reshape(L, S * T, *self._kv_geom))
                    vs = (v_ys[:, :, :, :T, :].transpose(0, 1, 3, 2, 4)
                          .reshape(L, S * T, *self._kv_geom))
                    return merge_rows(kv_pool, flat_slots, ks, vs)

            run.__name__ = "spec_merge"
            self._programs[key] = register_program(jax.jit(
                run, donate_argnums=(0,),
                in_shardings=(self._pool_format, None, None, None),
                out_shardings=self._pool_format),
                key=key, cause=("dispatch", self._entry_seq))
        return self._programs[key]

    def _try_dispatch_spec(self, prefill_pending: bool = False) -> bool:
        """One speculative round over every decode-ready sequence: propose
        candidate trees (n-gram lookup or draft-model mirrors), run ONE
        batched tree-masked verify forward, walk exact acceptance on the
        host, merge only the accepted path's KV, and commit — several
        tokens per target forward when candidates hit, a plain decode's
        worth when they don't. Returns False (nothing dispatched) when no
        sequence is decode-ready or no proposer produced a candidate —
        the window/plain decode path then serves as before.

        Spec rounds are SYNCHRONOUS: the async pipeline is drained first
        (``provision`` verifies from committed state) and the round's
        verify → accept → merge → commit runs to completion inside this
        call, so no provisional state ever outlives it. The drain is paid
        only when the proposer's ``probe`` says candidates plausibly
        exist — a lookup miss on non-repetitive text stays a plain
        pipelined decode step."""
        cfg = self.config
        if not any(not s.sched_done and s.slot >= 0 and s.pending_sched == 1
                   for s in self.state.seqs.values()):
            return False
        if self._inflight:
            # probe on the committed token view BEFORE the blocking
            # drain, over the sequences a round could actually use:
            # decode-ready in the SCHEDULED view (mid-prefill rows would
            # make a repetitive prompt drain the pipeline for nothing)
            # and with the same depth caps the request loop applies (a
            # budget-exhausted row proposes depth 0). Advisory only:
            # in-flight tokens may shift the history tail, so a false
            # negative is just a plain decode step and a false positive
            # costs one drain — same as before
            probe: dict[int, tuple[list[int], int]] = {}
            for s in self.state.seqs.values():
                if s.sched_done or s.slot < 0 or s.pending_sched != 1:
                    continue
                d = self._spec_tracker.depth(
                    s.uid, prefill_pending=prefill_pending,
                    mixed_cap=SPEC_DEPTH_MIXED_CAP)
                d = min(d, s.gen_remaining_sched - 1)
                if d >= 1:
                    probe[s.uid] = (s.tokens, d)
            if not probe or not self._spec.probe(probe):
                return False
            for uid, new in self._drain(drain_all=True).items():
                self._spec_emit.setdefault(uid, []).extend(new)
        live = [s for s in self.state.seqs.values()
                if not s.done and not s.frozen and s.slot >= 0
                and s.pending_tokens == 1
                and s.n_generated < s.max_new_tokens]
        if not live:
            return False

        t0 = time.perf_counter()
        T = cfg.spec_max_nodes
        requests: dict[int, tuple[list[int], int]] = {}
        for s in live:
            d = self._spec_tracker.depth(
                s.uid, prefill_pending=prefill_pending,
                mixed_cap=SPEC_DEPTH_MIXED_CAP)
            # the commit may emit depth+1 tokens (accepted chain + bonus):
            # cap one short of the remaining budget so provision() and the
            # block reservation are honoured by construction
            d = min(d, s.max_new_tokens - s.n_generated - 1)
            requests[s.uid] = (list(s.tokens), max(d, 0))
        trees = self._spec.propose(requests)
        if all(t.n_candidates == 0 for t in trees.values()):
            self.stats["plan_s"] += time.perf_counter() - t0
            return False     # nothing to verify — plain decode is cheaper

        S = self.state.max_seqs
        mb = self.state.max_blocks_per_seq
        bs = cfg.block_size
        tok = np.zeros((S, T), np.int32)
        pos = np.zeros((S, T), np.int32)
        tables = np.zeros((S, mb), np.int32)
        lens = np.zeros(S, np.int32)
        n_nodes = np.zeros(S, np.int32)
        mask = np.zeros((S, T, T), np.uint8)
        # every row starts as self-bits only: empty slots and padding
        # nodes must never see an all-masked softmax row (NaN)
        mask[:, np.arange(T), np.arange(T)] = 1
        meta: dict[int, tuple[int, Any]] = {}    # uid -> (slot, tree)
        try:
            for s in live:
                tree = trees[s.uid]
                depths = tree.depths()
                self.state.provision(s.uid, max(depths))
                sl = s.slot
                n = tree.n_nodes
                tok[sl, :n] = tree.tokens
                root = len(s.tokens) - 1
                pos[sl, :n] = [root + d for d in depths]
                tables[sl, :len(s.blocks)] = s.blocks
                lens[sl] = root + 1 + max(depths)
                n_nodes[sl] = n
                mask[sl] = tree.ancestor_mask(T)
                mask[sl, np.arange(n, T), np.arange(n, T)] = 1
                meta[s.uid] = (sl, tree)
            self.stats["plan_s"] += time.perf_counter() - t0

            t0 = time.perf_counter()
            # no-silent-fallback contract: EVERY verify dispatch counts
            # against the registry's tree selection (pallas or gather)
            self._emit_attn_kernel("tree")
            with self._telem.span("dispatch", kind="spec_verify", T=T):
                fn = self._spec_program(T)
                self._rng, sub = jax.random.split(self._rng)
                k_ys, v_ys, toks = fn(self.params, self.kv_pool, tok, pos,
                                      (tables,), lens, mask, n_nodes, sub)
                toks_h = np.asarray(toks)

            # exact acceptance on the host, then ONE merge of exactly the
            # accepted path's staged rows (everything else → trash block)
            flat = np.zeros(S * T, np.int32)
            accepts: dict[int, list[int]] = {}
            for uid, (sl, tree) in meta.items():
                seq = self.state.seqs[uid]
                accepted, visited = accept_walk(tree,
                                                toks_h[sl, :tree.n_nodes])
                root = len(seq.tokens) - 1
                for i, node in enumerate(visited):
                    p = root + i
                    flat[sl * T + node] = \
                        seq.blocks[(p // bs) % mb] * bs + p % bs
                accepts[uid] = accepted
            self.kv_pool = (self._spec_merge_program(T)(
                self.kv_pool[0], k_ys[0], v_ys[0], flat),)
        except Exception:
            # failed dispatch: no provisional marker may outlive the round
            for uid in meta:
                self.state.rollback_provisional(uid)
            raise

        st = self.stats
        emitted: dict[int, list[int]] = {}
        for uid, accepted in accepts.items():
            tree = meta[uid][1]
            out = self.state.commit_speculative(uid, accepted)
            n_acc = len(accepted) - 1        # matched candidates
            if self._rt.enabled:
                self._rt.event(uid, "spec_round",
                               proposed=tree.n_candidates, accepted=n_acc,
                               committed=len(out))
            st["spec_verifies"] += 1
            st["spec_proposed"] += tree.n_candidates
            st["spec_accepted"] += n_acc
            st["spec_steps_saved"] += max(len(out) - 1, 0)
            if out:
                self._results[uid].extend(out)
                self._spec_emit.setdefault(uid, []).extend(out)
                emitted[uid] = out
            if tree.n_candidates:
                ev = self._spec_tracker.observe(uid, tree.n_candidates,
                                                n_acc)
                if ev is not None:
                    # draft-depth adaptation is a postmortem-grade event:
                    # the flight recorder notes it even when metrics are
                    # off (note() is cheap and only read on dumps)
                    self._telem.note(
                        "spec_depth_adapt", uid=uid, old=ev[0], new=ev[1],
                        rate=round(self._spec_tracker.rate(uid), 4))
                    if self._rt.enabled:
                        self._rt.event(uid, "spec_depth_adapt",
                                       old=ev[0], new=ev[1])
        st["spec_rounds"] += 1
        st["spec_accept_rate"] = round(
            st["spec_accepted"] / max(st["spec_proposed"], 1), 4)
        st["dispatches"] += 1
        st["decode_steps"] += 1
        st["decode_tokens"] += sum(len(v) for v in emitted.values())
        st["dispatch_s"] += time.perf_counter() - t0
        if self._telem.enabled:
            reg = self._telem.registry
            reg.counter("serving_spec_proposed_total",
                        help="candidate tree tokens proposed for "
                             "verification").inc(
                sum(meta[u][1].n_candidates for u in meta))
            reg.counter("serving_spec_accepted_total",
                        help="proposed candidates accepted by the exact "
                             "verify walk").inc(
                sum(len(a) - 1 for a in accepts.values()))
            for accepted in accepts.values():
                reg.histogram(
                    "serving_spec_tokens_per_verify",
                    buckets=tuple(float(b) for b in range(1, T + 2)),
                    help="tokens committed per sequence per verify "
                         "forward (1 = no candidate survived)"
                ).observe(float(len(accepted)))
            self._record_dispatch_telemetry("spec_verify", len(live),
                                            self.state.max_seqs, ())
            if emitted:
                self._record_commit_telemetry(emitted)
        return True

    def _count_moe(self, live_tokens: int, rows: int, iters: int = 1):
        """Book one dispatch of a program whose forward runs ``iters``
        times over ``rows`` token rows, ``live_tokens`` of them real (step
        plans and decode windows; speculative verify rounds are not
        booked). The rest are the (token, choice) entries the liveness mask
        keeps out of the expert sort — as the host knows them at dispatch:
        a slot that meets its EOS inside a window is masked from there on
        and still counted as routed."""
        if not self._moe_layers:
            return
        mo = self.mcfg.moe
        bm = moe_tile_rows(rows, mo.top_k, mo.num_experts,
                           bool(self.config.quant_bits))
        self.stats["moe_routed_rows"] += \
            live_tokens * mo.top_k * self._moe_layers
        self.stats["moe_masked_rows"] += \
            (rows * iters - live_tokens) * mo.top_k * self._moe_layers
        self.stats["moe_padded_rows"] += iters * self._moe_layers * \
            moe_padded_rows(rows, mo.top_k, mo.num_experts, bm)

    def _count_attn_steps(self, seq_lens, starts, stage_rows: int,
                          iters: int = 1):
        """Book the paged kernel's steps for one dispatch of a program
        whose forward runs ``iters`` times (as ``_count_moe``: step plans
        and decode windows). A window is counted as its first iteration
        ``iters`` times over: its stage base is fixed, so the steps only
        move where a sliding window slides or the stage outgrows a page."""
        if not self._attn_paged:
            return
        bs = self.config.block_size
        st = self.stats
        for k in self._kinds:
            if k.is_record:
                continue
            live, rect = paged_step_counts(
                seq_lens, starts, starts, block_size=bs,
                max_pages=k.max_blocks, stage_rows=stage_rows,
                window=k.window, ring_tokens=k.ring_tokens)
            n = iters * len(k.layers)
            st["attn_steps_live"] += n * live
            st["attn_steps_rect"] += n * rect
            st[f"attn_steps_live_{k.name}"] += n * live
            st[f"attn_steps_rect_{k.name}"] += n * rect
            if k.window:
                # pool pages a table that grew with the context would have
                # walked for the same rows, against what the window left
                whole = -(-self.config.max_seq_len // bs)
                full, _ = paged_step_counts(
                    seq_lens, starts, starts, block_size=bs,
                    max_pages=whole, stage_rows=stage_rows)
                st["attn_pages_unclipped"] += n * full
                st["attn_pages_clipped"] += n * (full - live)

    def _dispatch_next(self) -> bool:
        """Dispatch the next scheduled step without blocking. Returns True
        if something was dispatched. Mixed prefill/decode load alternates
        prefill steps with decode windows (or [S,1] decode plans when
        windowing is off); a prefill step's program also runs the
        decode-ready rows, one token each, as its decode block
        (``StepPlan.block``: what the scheduler saw in its queue — the
        program is the same whoever is live), so the decoders do not wait
        out the weights a chunk streams anyway.
        With ``spec_decode`` configured (prefill steps then stay pure), the decode side of the
        alternation first offers the step to the speculative path — a
        verify round replaces up to depth+1 serial decode steps; when no
        proposer finds candidates the window/plain path runs as before."""
        has_prefill, has_decode = self.scheduler.pending_kinds()
        want_decode = has_decode and (not has_prefill or self._serve_toggle)
        if self._spec is not None and want_decode and \
                self._try_dispatch_spec(prefill_pending=has_prefill):
            self._serve_toggle = False
            return True
        if want_decode and self._try_dispatch_window(
                prefill_pending=has_prefill):
            self._serve_toggle = False
            return True
        t0 = time.perf_counter()
        with self._telem.span("plan", kind="step", seq=self._entry_seq):
            plan = self.scheduler.next_step(
                prefer="decode" if want_decode else None)
        self.stats["plan_s"] += time.perf_counter() - t0
        if plan is None:
            return False
        self._serve_toggle = plan.kind == "prefill"
        T, bs = plan.token_ids.shape[1], self.config.block_size
        if T > 1 and T % bs == 0:
            # page-merge invariant (advisor r04): the compiled program
            # whole-page-writes any row carrying >1 real token, assuming
            # its chunk starts page-aligned. The scheduler advances
            # kv_next in whole chunks so this holds; a future scheduler
            # change that broke it would silently drop KV for tokens
            # 1..n-1 — fail HERE, loudly, instead.
            n_real = (plan.slot_map >= bs).sum(axis=1)
            bad = (n_real > 1) & (plan.slot_map[:, 0] % bs != 0)
            if bad.any():
                raise RuntimeError(
                    f"page-merge invariant violated: rows "
                    f"{np.nonzero(bad)[0].tolist()} carry multi-token "
                    f"chunks starting page-misaligned (slot_map col 0 = "
                    f"{plan.slot_map[bad, 0].tolist()}, block_size {bs})")
        t0 = time.perf_counter()
        with self._telem.span("dispatch", kind=plan.kind, T=T,
                              seq=self._entry_seq):
            fn = self._program(T, plan.token_ids.shape[0])
            self._rng, sub = jax.random.split(self._rng)
            self.kv_pool, self._last_tok, toks = fn(
                self.params, self.kv_pool, self._last_tok,
                *self._plan_args(plan), sub)
        self.scheduler.mark_dispatched(plan)
        toks.copy_to_host_async()
        self._enqueue({"kind": "plan", "plan": plan, "toks": toks,
                       "t": time.perf_counter()})
        self.stats["dispatch_s"] += time.perf_counter() - t0
        self.stats["dispatches"] += 1
        n_tok = int(plan.active.sum())
        # the decode block: its tokens are decode tokens that no decode
        # step or window iteration made (their device time is the prefill
        # program's), and its rows are rows of the program's routed layers
        # and steps of its paged kernel's decode form
        n_block = 0 if plan.block is None else int(plan.block.active.sum())
        self._count_moe(n_tok + n_block, plan.token_ids.size
                        + (self.state.max_seqs if T > 1 else 0))
        if "latent_rows_written" in self.stats:
            self.stats["latent_rows_written"] += n_tok + n_block
        self._count_attn_steps(plan.seq_lens, plan.positions[:, 0],
                               stage_rows(T, bs))
        if plan.block is not None:
            self._count_attn_steps(plan.block.seq_lens,
                                   plan.block.positions[:, 0],
                                   stage_rows(1, bs))
        if plan.kind == "prefill":
            self.stats["prefill_steps"] += 1
            self.stats["prefill_tokens"] += n_tok
            self.stats["fused_steps" if n_block
                       else "fused_empty_steps"] += 1
            self.stats["fused_decode_tokens"] += n_block
            self.stats["decode_tokens"] += n_block
            if "conv_chunks" in self.stats:
                live = np.asarray(plan.uids) >= 0
                self.stats["conv_chunks"] += int(live.sum())
                self.stats["conv_chunks_carried"] += int(
                    (live & (plan.positions[:, 0] > 0)).sum())
        else:
            self.stats["decode_steps"] += 1
            self.stats["decode_tokens"] += n_tok
            self._emit_attn_kernel("decode")
        if self._telem.enabled:
            self._record_dispatch_telemetry(
                plan.kind, n_tok, int(np.prod(plan.token_ids.shape)),
                plan.uids)
        return True

    def _enqueue(self, entry: dict) -> None:
        """Append a dispatched entry to the pipeline under a number of its
        own (the one its ``dispatch`` span already carries) and book the
        depth of the pipeline it joins: how far the host runs ahead of the
        device when it dispatches; and, by kind of layer, the KV blocks
        live sequences hold now."""
        for name, n in self.state.sample().items():
            k = self.state.kinds[name]
            if k.record_rows:
                self.stats["state_records_live"] = n
                self.stats["state_records_peak"] = k.blocks_peak
                continue
            self.stats[f"kv_blocks_live_{name}"] = n
            self.stats[f"kv_blocks_peak_{name}"] = k.blocks_peak
        self.stats["ring_blocks_reused"] = sum(
            k.blocks_reused for k in self.state.kinds.values())
        depth = len(self._inflight)
        entry["seq"], entry["depth"] = self._entry_seq, depth
        self._entry_seq += 1
        self.stats["entries_dispatched"] += 1
        self.stats["inflight_depth_sum"] += depth
        self._inflight.append(entry)

    def _drain(self, force: bool = False, drain_all: bool = False) -> dict:
        """Commit completed in-flight steps. Non-forced drains only take
        entries whose readback should already be resident (is_ready()
        covers compute; the probed ``_drain_age`` covers the d2h copy);
        ``force`` takes (at least) the oldest, blocking if needed;
        ``drain_all`` empties the pipeline. Returns {uid: accepted tokens}
        merged across the drained entries."""
        emitted: dict[int, list[int]] = {}
        while self._inflight:
            entry = self._inflight[0]
            # >=: the pipeline holds AT MOST max_inflight awaiting entries,
            # matching the config contract (advisor r04: > ran one deeper)
            over = len(self._inflight) >= max(self.config.max_inflight, 1)
            aged = (time.perf_counter() - entry["t"]) >= self._drain_age
            ready = entry["toks"].is_ready() and aged
            if not (ready or force or drain_all or over):
                break
            if not ready:
                self.stats["forced_drains"] += 1
                t0 = time.perf_counter()
                with self._telem.span("drain_block", kind=entry["kind"],
                                      seq=entry["seq"]):
                    toks_h = np.asarray(entry["toks"])
                self.stats["drain_block_s"] += time.perf_counter() - t0
            else:
                toks_h = np.asarray(entry["toks"])
            self._inflight.popleft()
            force = False
            t0 = time.perf_counter()
            with self._telem.span("commit", seq=entry["seq"],
                                  depth=entry["depth"]):
                self._commit_entry(entry, toks_h, emitted)
            t1 = time.perf_counter()
            st = self.stats
            st["commit_s"] += t1 - t0
            # residence: from the append (``entry["t"]``) to the end of
            # the commit — what a dispatched step spends in the pipeline
            residence = t1 - entry["t"]
            st["entries_committed"] += 1
            st["inflight_residence_s"] += residence
            if entry["kind"] == "plan" and entry["plan"].kind == "prefill":
                st["prefill_entries_committed"] += 1
                st["prefill_residence_s"] += residence
        if emitted and self._telem.enabled:
            self._record_commit_telemetry(emitted)
        return emitted

    def _commit_entry(self, entry: dict, toks_h: np.ndarray,
                      emitted: dict) -> None:
        if entry["kind"] == "window":
            self.stats["window_iters"] += int(np.asarray(entry["iters"]))
            for uid, (sl, n) in entry["sched"].items():
                seq = self.state.seqs.get(uid)
                if seq is None:
                    continue
                seq.n_inflight -= n
                col = toks_h[:, sl]
                vals = [int(t) for t in col[col >= 0]]  # active prefix
                new = seq.commit_generated(vals, len(vals))
                if new:
                    # a decode step books its token at dispatch; a window
                    # knows how many it made only here
                    self.stats["decode_tokens"] += len(new)
                    self._results[uid].extend(new)
                    emitted.setdefault(uid, []).extend(new)
                    if self._rt.enabled:
                        self._rt.event(uid, "commit", tokens=len(new),
                                       window=True)
            return
        plan = entry["plan"]
        sampled = {uid: int(toks_h[r]) for r, uid in plan.sampled_rows()}
        accepted = self.scheduler.commit(plan, sampled)
        for uid, new in accepted.items():   # stop criteria may drop tokens
            if new:
                self._results[uid].extend(new)
                emitted.setdefault(uid, []).extend(new)

    # ------------------------------------------------------------------
    # public API (reference engine_v2.py put/query/flush)
    # ------------------------------------------------------------------
    def can_schedule(self, prompt_len: int, max_new_tokens: int = 32) -> bool:
        """Admission check (reference ``can_schedule`` :184) against the
        worst-case block budget (blocks are reserved at admit)."""
        return self.state.can_admit(prompt_len, max_new_tokens)

    def put(self, uid: int, prompt_tokens, max_new_tokens: int = 32,
            eos_token_id: int | None = None, tenant: str | None = None,
            trace_id: str | None = None) -> None:
        """Admit a request (reference ``put`` :107). Raises if the pool or
        slot budget is exhausted — callers gate on ``can_schedule``.
        ``eos_token_id`` stops the sequence early (truncated at the eos).
        ``tenant`` attributes the request's tokens / KV residency / SLO
        observations to a bounded-cardinality tenant label (reqtrace;
        ignored when tracing is off). ``trace_id`` adopts an externally
        minted canonical trace ID for the reqtrace timeline (a serving
        replica passes the router's — fleet trace assembly keys on it)
        instead of minting a process-local one."""
        toks = [int(t) for t in prompt_tokens]
        if not toks:
            raise ValueError("empty prompt")
        if len(toks) + max_new_tokens > self.config.max_seq_len:
            raise ValueError("prompt + max_new_tokens exceeds max_seq_len")
        if self._kv_tier is not None:
            # KV tiering: an admission miss whose chain the tier holds
            # promotes it into the trie FIRST, so the admit below hits
            # it through the normal match path (recompute on any
            # failure — promoted pages are unreferenced trie entries,
            # so can_admit still counts them evictable)
            self._tier_promote(toks)
        if not self.state.can_admit(len(toks), max_new_tokens):
            raise RuntimeError("cannot schedule: pool/slots exhausted")
        if self._rt.enabled:
            # trace opens BEFORE admit so the admit event (prefix-hit
            # extent, pages pinned — emitted inside StateManager.admit)
            # lands on an existing timeline
            self._rt.begin(uid, tenant=tenant, prompt=len(toks),
                           trace_id=trace_id)
        try:
            with self._telem.span("admit", prompt=len(toks)):
                seq = self.state.admit(uid, toks, max_new_tokens,
                                       eos_id=eos_token_id)
        except Exception:
            self._rt.drop(uid)     # the request never existed
            raise
        self._results[uid] = []
        if self._spec is not None:
            # draft mirrors reserve once, at admit, for the target's FULL
            # budget plus the deepest proposal overhang (rewind never
            # reallocates); a refused mirror admit just means root-only
            # trees for this uid — plain decode, never an error
            self._spec.admit(uid, toks,
                             max_new_tokens + self._spec_tracker.base_depth
                             + 1)
        if self._prefix_cache is not None:
            st = self.stats
            st["prefix_hit_tokens"] += seq.prefix_hit_tokens
            st["prefix_lookup_tokens"] += len(toks)
            st["prefix_hit_rate"] = round(
                st["prefix_hit_tokens"] / max(st["prefix_lookup_tokens"], 1),
                4)
        if self._telem.enabled:
            self._admit_t[uid] = time.perf_counter()
            self._telem.registry.counter(
                "serving_requests_total",
                help="requests admitted (put)").inc()
            if self._prefix_cache is not None:
                self._telem.registry.counter(
                    "serving_prefix_hit_tokens_total",
                    help="prompt tokens served from the shared-prefix KV "
                         "cache").inc(seq.prefix_hit_tokens)
                self._telem.registry.counter(
                    "serving_prefix_lookup_tokens_total",
                    help="prompt tokens looked up against the shared-"
                         "prefix KV cache").inc(len(toks))

    def query(self, uid: int) -> dict:
        """Request status (reference ``query`` :158)."""
        seq = self.state.seqs.get(uid)
        if seq is None:
            return {"live": False, "generated": self._results.get(uid, [])}
        return {"live": True, "done": seq.done,
                "generated": list(self._results[uid]),
                "n_computed": seq.n_computed}

    def _uid_inflight(self, uid: int) -> bool:
        for entry in self._inflight:
            uids = entry["sched"] if entry["kind"] == "window" \
                else entry["plan"].all_uids
            if uid in uids:
                return True
        return False

    def flush(self, uid: int) -> list[int]:
        """Release a request's KV + slot, returning generated tokens
        (reference ``flush`` :242). Drains the async pipeline ONLY up to
        the last in-flight step referencing this uid (FIFO) — a lingering
        device step could otherwise write into blocks about to be reused,
        but steps that only reference other uids keep riding. The common
        case (sequence committed done, nothing in flight for it) releases
        without stalling the pipeline at all."""
        while self._inflight and self._uid_inflight(uid):
            self._drain(force=True)         # pops (at least) the oldest
        seq = self.state.seqs.get(uid)
        if seq is not None and seq.migrating == "out":
            # flushing a pinned export = the abort path: unfreeze first,
            # then the normal release below publishes/frees as usual
            self.state.export_abort(uid)
        elif seq is not None and seq.migrating == "in":
            # a half-imported sequence has no committed content: hand the
            # whole reservation back instead of releasing/publishing
            self.state.abort_import(uid)
        if self._spec is not None:
            # spec rounds are atomic within a step() call, but a failed
            # verify dispatch may have been caught by a driver that then
            # flushes — clear any provisional marker before the audit
            self.state.rollback_provisional(uid)
            self._spec.release(uid)
            self._spec_tracker.forget(uid)
            self._spec_emit.pop(uid, None)
        if uid in self.state.seqs:
            self.state.release(uid)
            if self._audit_state:
                # DS_TPU_STATE_AUDIT=1: every block owned by exactly one
                # of {free list, trie, a live sequence's owned tail}, and
                # trie refcounts equal live sharers — fails loudly on any
                # leak the release/publish path could have introduced
                self.state.audit()
        self._admit_t.pop(uid, None)
        self._first_sched.discard(uid)
        self._last_commit_t.pop(uid, None)
        # release normally finalized the timeline (StateManager.release
        # emits it); this is the safety net for uids that never admitted
        self._rt.forget(uid)
        return self._results.pop(uid, [])

    def prefix_cache_stats(self) -> dict | None:
        """Lifetime shared-prefix cache counters — cached/referenced page
        counts, hit/lookup tokens, insert/dedup/evict totals (None when
        the cache is disabled). The per-run view lives in ``stats``
        (``prefix_hit_tokens`` / ``prefix_hit_rate``), which a measuring
        caller zeroes per phase."""
        return None if self._prefix_cache is None \
            else self._prefix_cache.stats()

    def residency_digest(self, max_entries: int = 4096) -> list[int] | None:
        """Chain hashes of every page the shared-prefix cache holds
        (``prefix_cache.chain_hashes`` scheme), newest-first — the
        serving replica's heartbeat payload for the router's prefix-aware
        placement. None when the cache is disabled (the router then falls
        back to least-loaded placement for this replica)."""
        return None if self._prefix_cache is None \
            else self._prefix_cache.residency_digest(max_entries)

    def prefix_cache_version(self) -> int:
        """Digest version (moves on trie insert/evict): the replica
        heartbeat re-ships its residency digest only when this did."""
        return 0 if self._prefix_cache is None \
            else self._prefix_cache.version

    def load_summary(self) -> dict:
        """Scheduler backlog + pool headroom for the replica heartbeat:
        the router's least-loaded placement signal and shed estimator."""
        out = self.scheduler.load_summary()
        out["free_blocks"] = self.state.allocator.free_blocks
        out["max_seqs"] = self.config.max_seqs
        out["inflight"] = len(self._inflight)
        return out

    def drain(self, deadline_s: float | None = None) -> bool:
        """Graceful-drain hook (the serving tier's replica shutdown path):
        step until every admitted sequence is done and the async pipeline
        is empty — callers stop admitting first. Returns False if
        ``deadline_s`` elapses with work still pending (the caller then
        escalates — in the router's case, by failing the stragglers with
        a structured reason instead of hanging a fleet shutdown on one
        wedged sequence). The engine stays usable either way."""
        t0 = time.perf_counter()
        # frozen (mid-migration) sequences are excluded: they schedule
        # nothing by design, and their fate — export ack or abort — is
        # the serving tier's call, not this loop's
        while any(not s.done and not s.frozen
                  for s in self.state.seqs.values()) \
                or self._inflight:
            if deadline_s is not None \
                    and time.perf_counter() - t0 > deadline_s:
                return False
            self.step()
        return True

    # ------------------------------------------------------------------
    # KV-page migration (inference/migration.py): disaggregated
    # prefill/decode serving moves a sequence's computed KV between
    # engine pools — host-bounce today (device pages -> host bytes ->
    # peer pool), device-to-device later. Ownership/rollback rides
    # StateManager's refcounted migration API; these wrappers add the
    # device half: reading the page extents out and scattering them in.
    # ------------------------------------------------------------------
    def can_import(self, n_tokens: int, remaining_gen: int) -> bool:
        """Would ``import_reserve`` succeed right now? (The serving
        replica's admission check before it acks a migration begin.)"""
        if self.state.not_a_page_chain:
            return False
        return self.state.can_admit(n_tokens, remaining_gen)

    def export_migration(self, uid: int, trace_id: str = "",
                         tenant: str = "default") -> "PageBundle":
        """Snapshot a live sequence into a :class:`PageBundle`: drain the
        async pipeline up to the last step referencing this uid (the
        committed view then IS the pool content), pin it via
        ``StateManager.migrate_out``, and read its page extents to host.
        The sequence stays frozen — pages bit-stable — until
        ``export_commit`` (importer acked) or ``export_abort``."""
        from .migration import PageBundle
        from .prefix_cache import chain_hashes

        if self.state.not_a_page_chain:
            raise RuntimeError(
                f"page migration requires linear block tables: "
                f"{self.state.not_a_page_chain}")
        while self._inflight and self._uid_inflight(uid):
            self._drain(force=True)
        snap = self.state.migrate_out(uid, trace=trace_id or None)
        bs = self.config.block_size
        n_full = len(snap["page_blocks"])
        with self._telem.span("migrate_out", pages=n_full):
            page_blobs = self._read_pages(snap["page_blocks"])
            tail = None
            if snap["tail_rows"]:
                tail = np.asarray(
                    self.kv_pool[0][:, :, :, snap["tail_block"],
                                    :snap["tail_rows"]]).tobytes()
        bundle = PageBundle(
            trace_id=trace_id,
            tokens=snap["tokens"],
            prompt_len=len(snap["tokens"]) - snap["n_generated"],
            n_computed=snap["n_computed"],
            n_generated=snap["n_generated"],
            max_new_tokens=snap["max_new_tokens"],
            eos_id=snap["eos_id"], tenant=tenant,
            block_size=bs,
            kv_dtype=np.dtype(self._kv_dtype).name,
            page_bytes=self._page_bytes,
            tail_rows=snap["tail_rows"],
            tail_bytes=len(tail or b""),
            # the engine's fp8-KV pool is scale-free e4m3 (no side-car
            # scale arrays); pools that carry them ship them here
            weight_version=dict(self._weight_version),
            chain=chain_hashes(snap["tokens"][:n_full * bs], bs),
            scales=None,
            pages=page_blobs, tail=tail)
        bundle.validate()
        self.stats["migrations_out"] += 1
        return bundle

    def export_commit(self, uid: int) -> list[int]:
        """The importer acked: the stream lives there now. Unpin, mark
        done, and flush — release publishes the computed pages into the
        LOCAL trie, so this replica keeps serving the prefix from cache.
        Returns the tokens generated here (the committed stream prefix)."""
        self.state.export_ack(uid)
        return self.flush(uid)

    def export_abort(self, uid: int) -> None:
        """Transfer failed/refused: unpin. The sequence is decode-ready
        again and resumes locally exactly where it stopped."""
        self.state.export_abort(uid)

    # migration, prefix pulls and the tier move pages of THE pool of a
    # ring-free model — one kind of layer: every caller refuses a ring
    @property
    def _page_shape(self) -> tuple[int, ...]:
        k0 = self._kinds[0]
        return (len(k0.layers), k0.halves, k0.heads,
                self.config.block_size, k0.lanes)

    @property
    def _page_bytes(self) -> int:
        return int(np.prod(self._page_shape)) \
            * np.dtype(self._kv_dtype).itemsize

    def _read_pages(self, blocks) -> list[bytes]:
        """Pages ``blocks`` as host bytes, a ``_page_shape`` each: one
        device gather + one transfer for all of them."""
        if not len(blocks):
            return []
        pages_h = np.asarray(
            self.kv_pool[0][:, :, :, np.asarray(blocks, np.int32)])
        return [pages_h[:, :, :, j].tobytes() for j in range(len(blocks))]

    def _write_page(self, block: int, page: np.ndarray) -> None:
        """One-page pool scatter, compiled once: that block replaced.
        Donated + layout-pinned like the step programs, so an import
        never copies the pool."""
        if getattr(self, "_import_page_jit", None) is None:
            self._import_page_jit = jax.jit(
                lambda pool, idx, page: pool.at[:, :, :, idx].set(page),
                donate_argnums=(0,),
                in_shardings=(self._pool_format, None, None),
                out_shardings=self._pool_format)
        self.kv_pool = (self._import_page_jit(
            self.kv_pool[0], np.int32(block), page),)

    def import_reserve(self, uid: int, meta: dict) -> None:
        """Claim capacity for an arriving bundle BEFORE its first payload
        byte: slot + full remaining block budget, sequence frozen until
        ``import_complete``. Raises (refusing the migration) on any
        geometry/dtype mismatch — a host-bounce between pools of
        different page layouts would corrupt KV silently."""
        from .migration import MigrationError, PageBundle

        shell = PageBundle.from_meta(meta)
        if self.state.not_a_page_chain:
            raise MigrationError(f"this pool cannot import page chains: "
                                 f"{self.state.not_a_page_chain}")
        if shell.block_size != self.config.block_size:
            raise MigrationError(
                f"block_size mismatch: bundle {shell.block_size}, "
                f"pool {self.config.block_size}")
        if shell.kv_dtype != np.dtype(self._kv_dtype).name:
            raise MigrationError(
                f"kv dtype mismatch: bundle {shell.kv_dtype}, pool "
                f"{np.dtype(self._kv_dtype).name}")
        want = self._page_bytes
        if shell.page_bytes != want:
            raise MigrationError(
                f"page geometry mismatch: bundle pages are "
                f"{shell.page_bytes}B, this pool's are {want}B")
        if self._rt.enabled:
            # adopt the exporter's canonical (router-minted) trace ID so
            # both halves of the migrated request share one timeline key
            self._rt.begin(uid, tenant=shell.tenant,
                           prompt=shell.prompt_len,
                           trace_id=shell.trace_id or None)
        try:
            self.state.migrate_in_begin(
                uid, shell.tokens, shell.n_computed, shell.n_generated,
                shell.max_new_tokens, eos_id=shell.eos_id,
                trace=shell.trace_id or None)
        except Exception:
            self._rt.drop(uid)
            raise
        # the stream prefix generated on the exporter: flush() returns
        # prior + locally-generated, the full authoritative stream
        self._results[uid] = list(
            shell.tokens[shell.prompt_len:])

    def import_complete(self, uid: int, bundle: "PageBundle") -> None:
        """Payload landed: scatter the page extents into the pool and
        commit — the full pages seed the local prefix trie (the
        cross-replica radix cache leg) and the sequence unfreezes
        decode-ready. The resume step is a plain decode of the last
        token: nothing is recomputed, so a greedy stream continues
        bit-identically."""
        from .migration import MigrationError, version_skew

        bundle.validate()
        if version_skew(bundle.weight_version, self._weight_version):
            # KV computed under other weights must never resume against
            # this pool — the importer aborts and the router falls back
            # (resume-on-source / replay), never a silent mixed forward
            raise MigrationError(
                f"version_skew: bundle weights "
                f"{bundle.weight_version} vs pool {self._weight_version}")
        seq = self.state.seqs[uid]
        page_shape = self._page_shape
        dt = np.dtype(self._kv_dtype)
        with self._telem.span("migrate_in", pages=bundle.n_full):
            for j in range(bundle.n_full):
                self._write_page(seq.blocks[j], np.frombuffer(
                    bundle.pages[j], dtype=dt).reshape(page_shape))
            if bundle.tail_rows:
                rows = np.frombuffer(bundle.tail, dtype=dt).reshape(
                    (*page_shape[:3], bundle.tail_rows, page_shape[4]))
                page = np.zeros(page_shape, dt)
                page[:, :, :, :bundle.tail_rows] = rows
                self._write_page(seq.blocks[bundle.n_full], page)
        self.state.import_commit(uid)
        if self._spec is not None:
            # the proposer sees the full imported history as its
            # "prompt"; a refused mirror admit just means root-only trees
            self._spec.admit(uid, list(seq.tokens),
                             seq.max_new_tokens - seq.n_generated
                             + self._spec_tracker.base_depth + 1)
        self.stats["migrations_in"] += 1
        self.stats["migration_bytes_in"] += bundle.payload_bytes
        if self._telem.enabled:
            self._admit_t[uid] = time.perf_counter()

    def import_abort(self, uid: int) -> None:
        """Transfer died before commit: free the reservation; the trie
        was never touched, nothing leaks."""
        self.state.abort_import(uid)
        self._results.pop(uid, None)
        self._rt.drop(uid)

    # ------------------------------------------------------------------
    # placement-time radix pulls (cross-replica distributed cache): a
    # request placed on a replica without its prefix can pull the page
    # chain from the peer that holds it instead of recomputing it. Same
    # host-bounce wire form as migration (kind="prefix" PageBundle), no
    # sequence involved: the export pin is gather-scoped and the import
    # adopts unreferenced trie pages the arriving admit then hits.
    # ------------------------------------------------------------------
    def export_prefix(self, tokens, trace_id: str = "") -> "PageBundle":
        """Bundle the longest cached chain prefixing ``tokens`` — or
        raise if nothing is cached (the router counts it a pull
        fallback and the puller recomputes)."""
        from .migration import MigrationError, PageBundle

        if self._prefix_cache is None:
            raise MigrationError(
                "no shareable prefix cache on this pool"
                + (f": {self.state.not_a_page_chain}"
                   if self.state.not_a_page_chain else ""))
        snap = self.state.snapshot_prefix(tokens, trace=trace_id or None)
        if snap is None:
            raise MigrationError("prefix chain not cached")
        try:
            bs = self.config.block_size
            with self._telem.span("kv_pull_export",
                                  pages=len(snap["blocks"])):
                blobs = self._read_pages(snap["blocks"])
        finally:
            self.state.release_prefix(snap["handle"])
        bundle = PageBundle.prefix(
            trace_id, [int(t) for t in tokens[:snap["n_tokens"]]], bs,
            np.dtype(self._kv_dtype).name, self._page_bytes, blobs,
            weight_version=dict(self._weight_version))
        bundle.validate()
        return bundle

    def import_prefix(self, bundle: "PageBundle",
                      source: str = "pull") -> int:
        """Adopt a pulled chain into the local trie: allocate-and-adopt
        through the refcounted API, then scatter the pulled payload into
        exactly the freshly-inserted blocks (dedup'd pages keep the
        cached copy — their device content is already correct). Returns
        the pages now cache-resident; raises (and adopts nothing) on a
        geometry/dtype mismatch or a pool too full to hold the chain.
        ``source`` labels the byte counter: "pull" = a cross-replica
        radix pull, "tier" = a local KV-tier promote (kvtier.py) riding
        the same adopt + scatter path."""
        from .migration import MigrationError, version_skew

        bundle.validate()
        if bundle.kind != "prefix":
            raise MigrationError(f"not a prefix bundle ({bundle.kind})")
        if version_skew(bundle.weight_version, self._weight_version):
            raise MigrationError(
                f"version_skew: chain computed under "
                f"{bundle.weight_version}, pool serves "
                f"{self._weight_version}")
        if self._prefix_cache is None:
            raise MigrationError(
                "no shareable prefix cache on this pool"
                + (f": {self.state.not_a_page_chain}"
                   if self.state.not_a_page_chain else ""))
        if bundle.block_size != self.config.block_size:
            raise MigrationError(
                f"block_size mismatch: bundle {bundle.block_size}, "
                f"pool {self.config.block_size}")
        if bundle.kv_dtype != np.dtype(self._kv_dtype).name:
            raise MigrationError(
                f"kv dtype mismatch: bundle {bundle.kv_dtype}, pool "
                f"{np.dtype(self._kv_dtype).name}")
        want = self._page_bytes
        if bundle.page_bytes != want:
            raise MigrationError(
                f"page geometry mismatch: bundle pages are "
                f"{bundle.page_bytes}B, this pool's are {want}B")
        fresh = self.state.adopt_prefix(bundle.tokens, bundle.n_computed,
                                        trace=bundle.trace_id or None)
        dt = np.dtype(self._kv_dtype)
        with self._telem.span("kv_pull_import", pages=len(fresh)):
            for j, block in fresh:
                self._write_page(block, np.frombuffer(
                    bundle.pages[j], dtype=dt).reshape(self._page_shape))
        key = f"kv_{source}_bytes_in"
        self.stats[key] = self.stats.get(key, 0) + bundle.payload_bytes
        return bundle.n_full

    def gang_prefill_segment(self, uid: int, tokens,
                             prefix_bundle: "PageBundle | None" = None,
                             max_new_tokens: int = 1,
                             trace_id: str | None = None) -> int:
        """One gang-prefill member's leg (serving/router.py gang_seg):
        adopt the merged chain from the upstream hop FIRST — the same
        refcounted ``import_prefix`` path cross-replica pulls ride —
        then admit ``tokens``. Admission's radix match skips every
        adopted page, so this engine computes exactly its own segment
        of the prompt (the math of parallel.sequence.
        gang_segment_attention, realized here as prefix-hit + ragged
        prefill over the tail). Member 0 passes no bundle; the FINAL
        member passes the full prompt with ``max_new_tokens=1`` to
        sample the first token on the fully-merged chain, after which
        decode handoff uses the ordinary export_prefix machinery.
        Returns pages adopted from upstream (0 for member 0); raises
        MigrationError on skew/geometry mismatch without admitting."""
        pages = 0
        if prefix_bundle is not None:
            pages = self.import_prefix(prefix_bundle, source="pull")
        self.put(uid, list(tokens), max_new_tokens=max_new_tokens,
                 trace_id=trace_id)
        return pages

    # ------------------------------------------------------------------
    # KV tiering (inference/kvtier.py): HBM → host RAM → NVMe under the
    # radix. _demote_evicted is the PrefixCache eviction sink (installed
    # at construction when cfg.kv_tier); _tier_promote runs at admission
    # — via the two-phase tier_promote_begin/tier_promote_finish form,
    # so the serving layer can start the extract ahead of admission —
    # and adopts the tier's chain through the SAME refcounted
    # adopt_prefix + page-scatter path cross-replica pulls use.
    # bin/check_state_invariants.py pins the tier's absorb/extract
    # (and extract_begin/extract_finish) mutators to exactly these
    # wrappers.
    # ------------------------------------------------------------------
    def _demote_evicted(self, chains) -> None:
        """Serialize each reclaimed chain through the kind="prefix"
        PageBundle path into the tier. Runs synchronously inside
        ``PrefixCache.evict`` BEFORE the freed blocks return to the
        allocator, so one device gather per chain reads the still-intact
        payloads. A chain whose deepest page is already tier-resident
        skips entirely (tier residency is contiguous-from-root, so a
        leaf-first eviction cascade gathers each page once)."""
        from .migration import PageBundle
        from .prefix_cache import chain_hashes

        tier = self._kv_tier
        if tier is None:
            return
        bs = self.config.block_size
        demoted = 0
        for tokens, blocks in chains:
            chain = chain_hashes(tokens, bs)
            if not chain or tier.has(chain[-1]):
                continue
            with self._telem.span("kv_tier_demote", pages=len(blocks)):
                blobs = self._read_pages(blocks)
            bundle = PageBundle.prefix(
                "", [int(t) for t in tokens], bs,
                np.dtype(self._kv_dtype).name, self._page_bytes, blobs,
                weight_version=dict(self._weight_version))
            demoted += tier.absorb(bundle)
        if demoted:
            self.stats["kv_tier_demoted_pages"] += demoted
            if self._rt.enabled:
                self._rt.event(-1, "kv_tier", dir="demote", pages=demoted)

    def tier_promote_begin(self, tokens):
        """Promote-ahead, phase one: plan the admission-path tier
        extract WITHOUT touching tier state (``KVTier.extract_begin``
        is a pure membership walk — no reads, no ring moves, no stat
        counts), so the NVMe read + crc verify in
        :meth:`tier_promote_finish` can start before or concurrently
        with admission. Returns an opaque handle, or None when the
        tier holds nothing deeper than the HBM trie."""
        tier = self._kv_tier
        bs = self.config.block_size
        cap = min(len(tokens) - 1, self.state.max_blocks_per_seq * bs)
        n_full = cap // bs
        if tier is None or n_full < 1:
            return None
        aligned = [int(t) for t in tokens[:n_full * bs]]
        from .prefix_cache import chain_hashes

        chain = chain_hashes(aligned, bs)
        have = self._prefix_cache.cached_depth(aligned)
        deep = tier.probe(chain)
        if deep <= have:
            return None              # HBM already covers the tier's chain
        h = tier.extract_begin(aligned[:deep * bs], bs)
        if h is not None:
            h["have"] = have
        return h

    def tier_promote_finish(self, handle) -> int:
        """Promote-ahead, phase two: the payload reads + crc verify the
        plan named, then the refcounted adopt (``import_prefix`` →
        ``StateManager.adopt_prefix`` + the page scatter) so the admit
        that follows hits the chain through the normal match path.
        Returns pages promoted; 0 — with recompute covering the prompt
        — on ANY miss, corruption, version skew, or pool-capacity
        refusal."""
        tier = self._kv_tier
        if tier is None or handle is None:
            return 0
        bs = self.config.block_size
        t0 = time.perf_counter()
        bundle = tier.extract_finish(handle)
        if bundle is None:
            return 0
        try:
            pages = self.import_prefix(bundle, source="tier")
        except (RuntimeError, ValueError) as e:
            # capacity / skew / geometry: structured refusal — the
            # admission below recomputes, always safe
            tier._fallback("adopt")
            logger.warning(f"engine_v2: tier promote refused ({e}); "
                           f"recomputing")
            return 0
        tier.note_promote_latency(time.perf_counter() - t0, pages=pages)
        if self.config.kv_tier_min_pages is None:
            # auto-sized threshold: once enough promotes were observed
            # end-to-end, the LIVE latency record re-sizes the break-even
            # (an explicit config value is never second-guessed)
            tier.refine_min_pages(block_size=bs)
        gained = max((len(handle["tok"]) // bs
                      - int(handle.get("have", 0))) * bs, 0)
        self.stats["kv_tier_promotes"] += 1
        if self._rt.enabled:
            self._rt.event(-1, "kv_tier", dir="promote", pages=pages,
                           tokens=gained)
        # the serving_kv_tier_* counter family is emitted in ONE place
        # (the replica loop's delta sync) so engine-backed and toy
        # replicas can never double-count; standalone engine users read
        # stats / kv_tier_stats() directly
        return pages

    def _tier_promote(self, tokens) -> int:
        """Admission-path promote, one-shot composition of the
        two-phase form above: when the tier holds a DEEPER chain than
        the HBM trie for this prompt, extract and adopt it so the
        admit that follows hits it."""
        return self.tier_promote_finish(self.tier_promote_begin(tokens))

    def kv_tier_stats(self) -> dict | None:
        """Lifetime tier counters (residency bytes/pages per sub-tier,
        demotes/promotes/fallbacks, torn spill records skipped); None
        when tiering is off."""
        return None if self._kv_tier is None else self._kv_tier.stats()

    def kv_tier_digest(self, max_entries: int = 4096) -> list[int] | None:
        """Chain hashes of tier-resident pages (RAM first) — shipped
        next to the HBM residency digest in the serving heartbeat so
        placement sees tier residency."""
        return None if self._kv_tier is None \
            else self._kv_tier.residency_digest(max_entries)

    def kv_tier_version(self) -> int:
        """Tier membership version (heartbeat re-ships the tier digest
        only when this moved)."""
        return 0 if self._kv_tier is None else self._kv_tier.version

    # ------------------------------------------------------------------
    # Versioned weight hot-swap (the hybrid-engine republish primitive,
    # DeepSpeed-Chat's in-place weight update for colocated train+serve,
    # reference deepspeed/runtime/hybrid_engine.py — here doubling as the
    # serving tier's zero-downtime rolling deploy, serving/deploy.py).
    # Contract: quiesce at a window boundary (drain the async pipeline;
    # live sequences PAUSE, their KV stays valid — same-shape update),
    # load through the PR-3 verified-manifest path, verify the new tree,
    # and only then commit. ANY failure leaves the old weights serving.
    # ------------------------------------------------------------------
    def weight_version(self) -> dict:
        """The serving weight version: ``{"id": monotonic int, "digest":
        manifest digest}`` ("init" digest = constructor weights)."""
        return dict(self._weight_version)

    def save_weights(self, save_dir: str, tag: str | None = None,
                     wid: int | None = None) -> str:
        """Publish this engine's live params as a verified swap
        checkpoint: ``<save_dir>/<tag>/state`` (orbax, the engine's own
        param tree — quantized/stacked form included, so a swap restore
        needs no re-transform), ``meta.json`` (geometry guard),
        ``manifest.json`` (size+crc32 commit proof), then the atomic
        ``latest`` advance — the exact PR-3 ordering, so a crash mid-save
        can never publish a torn deploy target."""
        from ..checkpoint.manifest import (manifest_digest,
                                           write_file_atomic,
                                           write_manifest)

        wid = int(wid if wid is not None
                  else self._weight_version["id"] + 1)
        tag = tag or f"weights_v{wid}"
        root = os.path.abspath(save_dir)
        path = os.path.join(root, tag)
        os.makedirs(path, exist_ok=True)
        import orbax.checkpoint as ocp

        ocp.PyTreeCheckpointer().save(os.path.join(path, "state"),
                                      {"params": self.params}, force=True)
        m = self.mcfg
        meta = {"tag": tag, "global_steps": wid,
                "format": "engine_weights",
                "model_dims": {"num_layers": m.num_layers,
                               "hidden": m.hidden_size,
                               "heads": m.num_heads,
                               "vocab": m.vocab_size},
                "quant_bits": self.config.quant_bits,
                "dtype": str(self.config.dtype)}
        with open(os.path.join(path, "meta.json"), "w") as f:
            import json as _json
            _json.dump(meta, f, indent=2, default=str)
        write_manifest(path, tag, wid)
        write_file_atomic(os.path.join(root, "latest"), tag)
        logger.info(f"engine_v2: published weights {path} "
                    f"(digest {manifest_digest(path)})")
        return path

    def swap_weights(self, ckpt_dir: str, tag: str | None = None,
                     wid: int | None = None) -> dict:
        """In-place live weight swap from a verified checkpoint.

        Sequence: (1) **quiesce** — drain every in-flight dispatch to a
        window boundary (live sequences pause; their KV stays valid for
        a same-shape update, nothing is flushed or replayed); (2)
        **verify** — resolve the tag and check its size+crc32 manifest
        (:mod:`~..checkpoint.manifest`): a torn or tampered checkpoint is
        a structured ``integrity`` refusal before a single byte loads;
        (3) **load** — restore the ``params`` entry INTO the current
        tree's structure and shardings (same-shape contract: a tree,
        shape, or dtype mismatch — including a checkpoint saved for a
        different quantization/stacking config — refuses
        ``shape_mismatch``; the restore target carries this engine's
        shardings, so a checkpoint written on a different mesh resharded
        here is fine, the universal-checkpoint property); (4) **probe**
        — a finiteness sweep over the restored float leaves gates the
        commit (``probe_failed``; the serving deploy adds an end-to-end
        probe REQUEST through the full forward on top); (5) **commit** —
        repoint ``self.params``, release the old buffers, stamp the new
        ``weight_version``. The old params object is retained until the
        probe passes: any raise leaves it serving untouched."""
        from ..checkpoint.manifest import (manifest_digest, resolve_tag,
                                           tag_status)

        t0 = time.perf_counter()
        # (1) quiesce: every in-flight device step commits; the pipeline
        # is empty at return, so nothing concurrently reads self.params
        self._drain(drain_all=True)
        quiesce_s = time.perf_counter() - t0
        # (2) verify the tag through the PR-3 manifest contract: an
        # explicit tag never silently falls back (missing is structured
        # no_checkpoint, torn/tampered is the crc gate's integrity
        # refusal); no tag resolves 'latest' then newest-verified
        if tag is not None:
            status, reason = tag_status(os.path.join(ckpt_dir, tag))
            if status == "missing":
                raise WeightSwapError("no_checkpoint",
                                      f"tag '{tag}' missing")
            if status != "verified":
                raise WeightSwapError(
                    "integrity", f"tag '{tag}' {status}: {reason}")
        else:
            tag, why = resolve_tag(ckpt_dir, None)
            if not tag:
                raise WeightSwapError("no_checkpoint", why)
        path = os.path.join(ckpt_dir, tag)
        try:
            digest = manifest_digest(path)
        except OSError as e:
            raise WeightSwapError("integrity", f"manifest unreadable: {e}")
        wid = int(wid if wid is not None
                  else self._weight_version["id"] + 1)
        t1 = time.perf_counter()
        # (3) same-shape restore into the live tree's structure/shardings
        import orbax.checkpoint as ocp

        target = {"params": self.params}
        restore_args = jax.tree.map(
            lambda x: ocp.ArrayRestoreArgs(
                sharding=x.sharding, global_shape=x.shape, dtype=x.dtype),
            target)
        try:
            restored = ocp.PyTreeCheckpointer().restore(
                os.path.join(path, "state"), item=target,
                restore_args=restore_args)
        except Exception as e:  # orbax raises various concrete types
            raise WeightSwapError("shape_mismatch", str(e))
        new_params = restored["params"]
        # (4) probe: a non-finite leaf would poison every stream served
        # after the swap — refuse and keep the old weights. The sweep
        # accumulates per-leaf flags ON DEVICE and syncs exactly once:
        # this runs inside the quiesce window every paused request pays,
        # so per-leaf host round-trips would inflate the quiesce stall
        # by hundreds of d2h latencies on a real model
        flags = [jnp.all(jnp.isfinite(leaf))
                 for leaf in jax.tree.leaves(new_params)
                 if hasattr(leaf, "dtype")
                 and jnp.issubdtype(leaf.dtype, jnp.floating)]
        if flags and not bool(jnp.all(jnp.stack(flags))):
            raise WeightSwapError(
                "probe_failed", "restored weights hold non-finite values")
        # (5) commit: in-flight sequences resume against the new weights
        # at the next dispatch, keeping their own KV (same-shape ⇒ valid
        # — the hybrid-engine small-update contract). The SHARED prefix
        # cache flushes, though: a NEW request must never prefill from
        # pages the old weights computed (and StateManager.release skips
        # publishing pages from sequences that lived across the swap, by
        # admit-time version — so the post-swap trie only ever holds
        # post-swap KV).
        self.params = new_params
        self._weight_version = {"id": wid, "digest": digest}
        flushed = self.state.flush_prefix_cache()
        if self._prefix_cache is not None:
            self._prefix_cache.set_weight_version(wid)
        if self._kv_tier is not None:
            # the tier's records are stale under the new weights too:
            # invalidate so a post-swap promote can never serve them
            self._kv_tier.set_weight_version(self._weight_version)
        swap_s = time.perf_counter() - t1
        if self._rt.enabled:
            self._rt.event(-1, "weight_swap", wid=wid, flushed=flushed,
                           quiesce_s=round(quiesce_s, 6),
                           swap_s=round(swap_s, 6))
        self._telem.note("weight_swap", wid=wid, digest=digest,
                         quiesce_s=round(quiesce_s, 4),
                         swap_s=round(swap_s, 4))
        logger.info(f"engine_v2: weight swap to v{wid} (digest {digest}) "
                    f"quiesce {quiesce_s * 1e3:.1f}ms "
                    f"swap {swap_s * 1e3:.1f}ms")
        return {"wv": self.weight_version(),
                "quiesce_s": quiesce_s, "swap_s": swap_s}

    def _emit_attn_kernel(self, mode: str) -> None:
        """Count one decode/tree-verify dispatch against the attention
        formulation the registry selected (attn_registry.py). The stats
        split is unconditional — no silent fallback: a spec-verify round
        served by the gather path ALWAYS shows as attn_gather_tree — and
        the labeled counter rides telemetry when enabled."""
        sel = self._attn_tree_sel if mode == "tree" else self._attn_decode_sel
        self.stats[f"attn_{sel.path}_{mode}"] += 1
        if self._telem.enabled:
            self._telem.registry.counter(
                "serving_attn_kernel_total",
                labels={"path": sel.path, "mode": mode},
                help="decode/tree-verify dispatches by the attention "
                     "formulation the registry selected (pallas kernel "
                     "vs XLA gather fallback)").inc()

    def _record_dispatch_telemetry(self, kind: str, useful: int,
                                   budget: int, uids) -> None:
        """Dispatch-side SLO instruments: queue wait (admission → first
        scheduled prefill chunk), per-step occupancy (useful/budget — the
        honest prefill-MFU accounting as a live histogram), KV-page
        utilization. Caller gates on ``self._telem.enabled``."""
        from ..telemetry import RATIO_BUCKETS

        now = time.perf_counter()
        reg = self._telem.registry
        rt = self._rt
        for uid in uids:
            if uid >= 0 and uid not in self._first_sched:
                self._first_sched.add(uid)
                t_admit = self._admit_t.get(uid)
                if t_admit is not None:
                    reg.histogram(
                        "serving_queue_wait_s",
                        help="admission (put) → first scheduled prefill "
                             "chunk").observe(now - t_admit,
                                              exemplar=rt.exemplar(uid))
                    if rt.enabled:
                        rt.observe_queue_wait(uid, now - t_admit)
        if budget > 0:
            reg.histogram(
                f"serving_{kind}_occupancy", buckets=RATIO_BUCKETS,
                help="useful fraction of the step's paid token/row budget"
            ).observe(useful / budget)
        if kind in ("prefill", "decode"):
            # the prefill-vs-decode token split (window tokens land on the
            # commit side as serving_tokens_total — speculative here)
            reg.counter(f"serving_{kind}_tokens_total",
                        help="useful tokens dispatched in pure "
                             f"{kind} plans").inc(useful)
        alloc = self.state.allocator
        cap = max(alloc.num_blocks - 1, 1)      # block 0 is the trash slot
        reg.gauge("serving_kv_page_utilization",
                  help="allocated fraction of the paged KV pool").set(
            1.0 - alloc.free_blocks / cap)
        if self._prefix_cache is not None:
            # ownership split behind the utilization number: cached pages
            # (trie LRU, reclaimable) vs referenced (shared with live
            # sequences) vs plainly owned tails vs free
            pc = self._prefix_cache
            cached, referenced = pc.cached_blocks, pc.referenced_blocks
            for kind, val in (("free", alloc.free_blocks),
                              ("prefix_cached", cached - referenced),
                              ("prefix_referenced", referenced),
                              ("seq_owned",
                               cap - alloc.free_blocks - cached)):
                reg.gauge("serving_kv_pages", labels={"kind": kind},
                          help="paged-pool block ownership split"
                          ).set(val)

    def _record_commit_telemetry(self, emitted: dict) -> None:
        """Commit-side SLOs: TTFT (admission → first committed token) and
        observed per-token time-between-tokens — a window committing n
        tokens dt after the previous commit contributes n samples of dt/n
        (the amortized-burst convention, live)."""
        now = time.perf_counter()
        reg = self._telem.registry
        rt = self._rt
        total = 0
        for uid, toks in emitted.items():
            n = len(toks)
            if not n:
                continue
            total += n
            last = self._last_commit_t.get(uid)
            if last is None:
                t_admit = self._admit_t.get(uid)
                if t_admit is not None:
                    reg.histogram(
                        "serving_ttft_s",
                        help="admission (put) → first committed token"
                    ).observe(now - t_admit, exemplar=rt.exemplar(uid))
                    if rt.enabled:
                        # per-tenant TTFT + the SLO-breach auto-capture
                        # threshold check live behind this call
                        rt.observe_ttft(uid, now - t_admit)
            else:
                reg.histogram(
                    "serving_tbt_s",
                    help="observed per-token time between committed tokens"
                ).observe((now - last) / n, n=n, exemplar=rt.exemplar(uid))
                if rt.enabled:
                    rt.observe_tbt(uid, (now - last) / n, n)
            self._last_commit_t[uid] = now
        if total:
            reg.counter("serving_tokens_total",
                        help="committed (accepted) generated tokens"
                        ).inc(total)

    def _reqtrace_state_snapshot(self) -> dict:
        """Engine/pool state attached to SLO-breach flight dumps: the
        scheduler backlog, pool occupancy, async pipeline depth, and a
        per-sequence summary — "what else was the engine juggling when
        this request blew its SLO"."""
        alloc = self.state.allocator
        has_prefill, has_decode = self.scheduler.pending_kinds()
        out = {
            "queue_depth": self.scheduler.queue_depth(),
            "pending_prefill": has_prefill,
            "pending_decode": has_decode,
            "inflight_steps": len(self._inflight),
            "free_blocks": alloc.free_blocks,
            "num_blocks": alloc.num_blocks,
            "seqs": {
                uid: {"slot": s.slot, "len": len(s.tokens),
                      "n_computed": s.n_computed,
                      "pending_sched": s.pending_sched,
                      "blocks": len(s.blocks),
                      "shared_blocks": s.n_shared_blocks,
                      "done": s.done}
                for uid, s in self.state.seqs.items()},
        }
        if self._prefix_cache is not None:
            out["prefix_cache"] = self._prefix_cache.stats()
        return out

    def _refresh_tp_stats(self) -> None:
        """Accumulate the ring collective-matmul counters (trace-time,
        process-wide in parallel/tensor.py) into this engine's stats.

        INCREMENTAL (+= new-since-last-refresh, base rebased each call)
        rather than since-init values: a measuring caller zeroes the
        stats dict per run, and an absolute-delta overwrite
        would silently clobber that reset with cumulative numbers. A
        snapshot BELOW the base means someone reset the process-wide
        counters — rebase to zero instead of emitting negative deltas.
        (Attribution caveat: two ring-enabled engines stepping in one
        process share the global counters; each engine's stats then count
        the union of both engines' new compiles.)"""
        snap = overlap_counters.snapshot()
        for k, v in snap.items():
            base = self._tp_counter_base.get(k, 0)
            self.stats[k] += v - (base if v >= base else 0)
        self._tp_counter_base = snap

    def step(self) -> dict[int, list[int]]:
        """Dispatch the next scheduled step WITHOUT waiting for it, and
        commit any earlier steps whose readbacks completed. Returns
        {uid: accepted_tokens} for everything committed this call —
        possibly from dispatches several calls ago (the async pipeline
        runs up to ``max_inflight`` steps ahead; decode chains through
        device-resident state, so throughput never waits on a
        readback). Empty dict = nothing committed this call; the
        engine is idle only when it also has nothing in flight."""
        emitted = self._drain()
        dispatched = self._dispatch_next()
        if self._tp_ring_n:
            self._refresh_tp_stats()
        if dispatched and self.config.max_inflight <= 0:
            # max_inflight=0 restores the synchronous contract: the step
            # dispatched THIS call commits before we return
            for uid, new in self._drain(drain_all=True).items():
                emitted.setdefault(uid, []).extend(new)
        elif not dispatched and self._inflight:
            # nothing left to dispatch (all budget in flight) → make
            # progress by blocking on the oldest readback
            for uid, new in self._drain(force=True).items():
                emitted.setdefault(uid, []).extend(new)
        if self._spec_emit:
            # tokens committed synchronously inside a spec round (plus
            # any pipeline drain the round forced) surface with the rest
            for uid, new in self._spec_emit.items():
                emitted.setdefault(uid, []).extend(new)
            self._spec_emit = {}
        return emitted

    def generate(self, prompts: list[list[int]], max_new_tokens: int = 32,
                 eos_token_id: int | None = None) -> list[list[int]]:
        """Convenience driver: continuous-batch a set of prompts to
        completion (the MII serving loop, compressed)."""
        pending = list(enumerate(prompts))
        out: dict[int, list[int]] = {}
        live: set[int] = set()
        while pending or live:
            while pending and self.can_schedule(len(pending[0][1]),
                                                max_new_tokens):
                uid, toks = pending.pop(0)
                self.put(uid, toks, max_new_tokens, eos_token_id=eos_token_id)
                live.add(uid)
            if not live:
                raise RuntimeError(
                    f"prompt of {len(pending[0][1])} tokens can never be "
                    f"scheduled with num_blocks={self.config.num_blocks}")
            self.step()
            for uid in list(live):
                seq = self.state.seqs.get(uid)
                if seq is not None and seq.done:
                    out[uid] = self.flush(uid)
                    live.remove(uid)
        return [out[i] for i in range(len(prompts))]


def build_engine(model: TransformerLM, params: Pytree | None = None,
                 config: RaggedInferenceConfig | dict | None = None,
                 **kwargs) -> InferenceEngineV2:
    """Factory (reference inference/v2/engine_factory.py:69 build_hf_engine)."""
    return InferenceEngineV2(model=model, params=params, config=config, **kwargs)
