"""Host-side ragged batching state: blocked KV allocator + sequence manager.

TPU-native re-design of reference inference/v2/ragged/
(``BlockedAllocator`` blocked_allocator.py:11, ``DSSequenceDescriptor``
sequence_descriptor.py, ``DSStateManager`` ragged_manager.py:19,
``RaggedBatchWrapper`` ragged_wrapper.py:31). This logic is device-agnostic
bookkeeping in both frameworks — the allocator hands out fixed-size KV
blocks from a device-resident pool; sequences own block lists; the batch
wrapper packs per-step descriptors (block tables, positions, lengths) that
the jitted forward consumes as plain int32 arrays.
"""
from __future__ import annotations

from dataclasses import dataclass, field

import numpy as np


class BlockedAllocator:
    """Free-list allocator over ``num_blocks`` KV blocks (reference
    blocked_allocator.py:11). Block 0 is reserved as the trash block —
    padded tokens scatter their (masked) KV there."""

    TRASH = 0

    def __init__(self, num_blocks: int):
        if num_blocks < 2:
            raise ValueError("need at least 2 blocks (one is reserved)")
        self.num_blocks = num_blocks
        self._free: list[int] = list(range(1, num_blocks))

    @property
    def free_blocks(self) -> int:
        return len(self._free)

    def allocate(self, n: int) -> list[int]:
        if n > len(self._free):
            raise RuntimeError(f"KV pool exhausted: want {n}, "
                               f"free {len(self._free)}")
        out, self._free = self._free[:n], self._free[n:]
        return out

    def free(self, blocks: list[int]) -> None:
        for b in blocks:
            if b == self.TRASH or b < 0 or b >= self.num_blocks:
                raise ValueError(f"bad block id {b}")
        self._free.extend(blocks)


@dataclass
class KindCache:
    """The cache of ONE kind of layer. A PAGED kind: its own block
    allocator (its own pool on the device) and the width of a sequence's
    block table there. "full": the table grows with the context. "window":
    a ring of ``max_blocks_per_seq`` slots — absolute position ``p`` lives
    in slot ``(p // block_size) % max_blocks_per_seq``, so a sequence never
    holds more than its ring (``ring`` says whether the table is narrower
    than a whole context, i.e. whether slots are ever reused in place). A
    RECORD kind ("conv", ``record_rows`` > 0): a fixed record a layer and
    SLOT on the device, addressed by the slot a live sequence already
    holds — no allocator (None), no table, nothing to reserve: it never
    refuses an admission. ``blocks_peak`` counts its records live."""
    name: str
    allocator: BlockedAllocator | None
    max_blocks_per_seq: int
    ring: bool = False
    #: > 0: a record kind, rows of one record
    record_rows: int = 0
    #: ring slots overwritten in place (a page past the window reused)
    blocks_reused: int = 0
    #: most blocks live sequences held at once (``StateManager.sample``)
    blocks_peak: int = 0


@dataclass
class SequenceDescriptor:
    """Per-uid state (reference sequence_descriptor.py DSSequenceDescriptor).

    Two views coexist so the engine can run ahead of host readbacks
    (the async serving pipeline, round-4):

    - committed: ``tokens`` / ``n_computed`` / ``n_generated`` advance when
      sampled tokens actually reach the host (``commit_generated``).
    - scheduled: ``n_sched`` (KV scheduled into the pool) and
      ``n_inflight`` (sampled tokens that exist only on device) advance at
      DISPATCH time. The scheduler plans exclusively from this view, so
      step N+1 can be built and dispatched while step N still runs on
      device. Synchronous drivers that never touch the dispatch-time
      accessors see identical numbers (``max`` below).

    Shared-prefix serving (prefix_cache.py): the FIRST ``n_shared_blocks``
    entries of ``blocks`` are READ-ONLY pages owned by the prefix trie
    (refcounted, released at :meth:`StateManager.release`); ``n_computed``
    starts at the cached token boundary so the scheduler never recomputes
    — or writes — a shared page (chunk starts are page-aligned there).
    """
    uid: int
    tokens: list[int]                 # full token history (prompt + generated)
    slot: int = -1                    # batch slot while scheduled
    n_computed: int = 0               # tokens whose KV is already in the pool
    blocks: list[int] = field(default_factory=list)   # the PRIMARY kind's
    #: block tables of the model's further kinds of layer (kind -> blocks):
    #: a model of window AND full layers holds its growing table in
    #: ``blocks`` and its ring here, under "window"
    kind_blocks: dict = field(default_factory=dict)
    max_new_tokens: int = 0
    n_generated: int = 0
    done: bool = False
    eos_id: int | None = None         # stop criterion besides max_new_tokens
    n_sched: int = 0                  # KV tokens scheduled (dispatch-time)
    n_inflight: int = 0               # sampled tokens not yet read back
    n_shared_blocks: int = 0          # leading trie-owned (read-only) pages
    prefix_hit_tokens: int = 0        # prompt tokens served from the trie
    #: prefix-cache weight version at admit (weight hot-swap skew guard):
    #: a sequence that lived across a swap computed its KV (at least
    #: partly) under the OLD weights — release frees its pages instead of
    #: publishing them into the post-swap trie
    admit_wv: int = 0
    #: speculative decoding (speculative.py): candidate tokens whose KV may
    #: land in this sequence's OWNED tail pages ahead of acceptance. Only
    #: the rollback-aware StateManager methods (``provision`` /
    #: ``commit_speculative`` / ``rollback_provisional`` / ``rewind``) may
    #: mutate this — bin/check_state_invariants.py enforces it.
    n_provisional: int = 0
    #: KV-page migration (migration.py): None = not migrating; "out" = an
    #: exported page bundle is in flight to another pool (pages PINNED —
    #: the scheduler must not write them and release is refused until the
    #: importer acks or the export aborts); "in" = the sequence was
    #: created by ``migrate_in_begin`` and its pages are still being
    #: filled (not schedulable until ``import_commit``). Only the
    #: refcounted migration API (``migrate_out`` / ``export_ack`` /
    #: ``export_abort`` / ``migrate_in_begin`` / ``import_commit`` /
    #: ``abort_import``) may mutate this — bin/check_state_invariants.py
    #: enforces it.
    migrating: str | None = None

    @property
    def frozen(self) -> bool:
        """True while a migration pins this sequence: its pages must stay
        bit-stable (out) or are still arriving (in) — never schedulable."""
        return self.migrating is not None

    @property
    def pending_tokens(self) -> int:
        """Tokens not yet run through the model. > 1 → still prefilling the
        prompt (chunked); == 1 → the next step is a decode of the last
        (sampled or final-prompt) token."""
        return len(self.tokens) - self.n_computed

    # --- scheduled (speculative) view -------------------------------------
    @property
    def kv_next(self) -> int:
        """First token index whose KV is not yet scheduled."""
        return max(self.n_computed, self.n_sched)

    @property
    def len_sched(self) -> int:
        """Sequence length including in-flight (device-only) tokens."""
        return len(self.tokens) + self.n_inflight

    @property
    def pending_sched(self) -> int:
        """Tokens not yet scheduled through the model (speculative analogue
        of ``pending_tokens``). > 1 → prefilling; == 1 → decode-ready."""
        return self.len_sched - self.kv_next

    @property
    def gen_remaining_sched(self) -> int:
        """Generation budget not yet scheduled."""
        return self.max_new_tokens - self.n_generated - self.n_inflight

    @property
    def sched_done(self) -> bool:
        """Nothing left to dispatch (committed-done, budget fully in
        flight, OR frozen by an in-flight page migration — every plan
        builder gates on this, so freezing here freezes the sequence out
        of prefill steps, decode plans, windows and spec rounds alike)."""
        return self.done or self.frozen or self.gen_remaining_sched <= 0

    def commit_generated(self, new_tokens: list[int],
                         n_computed: int) -> list[int]:
        """THE generation-accounting step, shared by the per-step scheduler
        commit and the multi-step decode window: append sampled tokens,
        advance the computed-KV counter, apply the stop criteria
        (max_new_tokens, and eos when configured — a window may sample past
        the eos; the surplus is truncated here, never surfaced)."""
        if self.done:
            # a lagged async commit can land after eos already finished the
            # sequence — its tokens were computed past the stop and are
            # discarded, never surfaced
            return []
        if self.eos_id is not None and new_tokens:
            for i, t in enumerate(new_tokens):
                if t == self.eos_id:
                    new_tokens = new_tokens[:i + 1]
                    self.done = True
                    break
        self.tokens.extend(new_tokens)
        # clamp: a truncated window computed KV for tokens we discarded;
        # pending_tokens must never go negative for a finished sequence
        self.n_computed = min(self.n_computed + n_computed, len(self.tokens))
        self.n_generated += len(new_tokens)
        if self.n_generated >= self.max_new_tokens:
            self.done = True
        return new_tokens


class StateManager:
    """Tracks live sequences + owns the allocator (reference
    ragged_manager.py:19 ``DSStateManager``).

    THE refcounted alloc/free API: every block-list mutation in the
    serving stack goes through :meth:`admit` / :meth:`release` here (the
    AST lint ``bin/check_state_invariants.py`` enforces it). With a
    :class:`~.prefix_cache.PrefixCache` attached, admit points new
    sequences at cached read-only pages (refcount++), release publishes
    computed full pages into the trie instead of freeing them, and
    allocation under pressure reclaims LRU unreferenced cached pages —
    never referenced or in-flight ones (the engine's flush drains
    dispatched-but-uncommitted steps before release runs)."""

    def __init__(self, num_blocks: int, block_size: int, max_seqs: int,
                 max_blocks_per_seq: int, kind: str = "full",
                 ring: bool = False, more_kinds: dict | None = None,
                 records: dict | None = None):
        """One allocator and one block table a sequence for each kind of
        layer the model has. The PRIMARY kind is the positional arguments'
        (``state.allocator``, ``state.max_blocks_per_seq``, ``seq.blocks``
        — all a one-kind model has); ``more_kinds`` maps each further kind
        to ``(num_blocks, max_blocks_per_seq, ring)`` and its tables live
        in ``seq.kind_blocks``. Static table widths → step programs never
        recompile. A window kind's width is the engine's ROLLING buffer
        (ceil((window + step) / bs) + 1 slots): the physical slot of
        absolute position p is (p // bs) % width, so a sequence never pins
        more than one window of KV there (the mistral rolling cache). A
        full kind's is the same formula — the mod never fires. ``records``
        maps each RECORD kind to the rows of its record: listed in
        ``kinds`` (``sample``, ``audit``) with nothing to reserve."""
        self.block_size = block_size
        self.max_seqs = max_seqs
        self.kinds: dict[str, KindCache] = {kind: KindCache(
            kind, BlockedAllocator(num_blocks), max_blocks_per_seq, ring)}
        for name, (nb, width, is_ring) in (more_kinds or {}).items():
            self.kinds[name] = KindCache(name, BlockedAllocator(nb), width,
                                         is_ring)
        for name, rows in (records or {}).items():
            self.kinds[name] = KindCache(name, None, 0, record_rows=rows)
        self.primary = kind
        self.seqs: dict[int, SequenceDescriptor] = {}
        self._free_slots = list(range(max_seqs))
        #: shared-prefix trie (attach_prefix_cache); None = no sharing
        self.prefix_cache = None
        # node chains live sequences hold refs on (uid → list[PageNode])
        self._shared_nodes: dict[int, list] = {}
        #: per-request lifecycle tracer (telemetry/reqtrace.py, duck-typed:
        #: ``.enabled`` + ``.event(uid, kind, **fields)``) — engine_v2
        #: attaches it; None = no tracing (bare StateManager users)
        self.reqtrace = None
        # pages the last _alloc call reclaimed from the prefix LRU (admit
        # folds this into its lifecycle event for attribution)
        self._last_evicted = 0
        # serving-tier trace IDs of in-flight imports (uid -> trace),
        # emitted on the migrate_in lifecycle event at import_commit
        self._mig_trace: dict[int, str | None] = {}
        # cross-replica radix pulls: node chains pinned by an in-flight
        # prefix export (handle -> list[PageNode]; snapshot_prefix /
        # release_prefix), counted by audit() alongside sequence shares
        self._pull_pins: dict[int, list] = {}
        self._pull_ctr = 0

    @property
    def allocator(self) -> BlockedAllocator:
        return self.kinds[self.primary].allocator

    @property
    def max_blocks_per_seq(self) -> int:
        return self.kinds[self.primary].max_blocks_per_seq

    @property
    def not_a_page_chain(self) -> str:
        """Why a sequence's state here is NOT a linear chain of pages
        ("" where it is): some kind reuses page slots in place (a ring) or
        keeps a record that is no page at all. What only a page chain can
        do — grow a chunk past the ring, share pages through the prefix
        trie, speculate past the tail, export, import or rewind pages — is
        refused for the whole sequence, with this reason."""
        for k in self.kinds.values():
            if k.ring:
                return (f"kind {k.name!r} keeps a rolling ring: page slots "
                        f"are reused in place")
            if k.record_rows:
                return (f"kind {k.name!r} keeps a record a slot "
                        f"({k.record_rows} rows), not pages: it cannot be "
                        f"shared, exported or rolled back")
        return ""

    def blocks_of(self, seq: SequenceDescriptor, kind: str) -> list[int]:
        return seq.blocks if kind == self.primary else seq.kind_blocks[kind]

    def _more_need(self, n_tokens: int) -> dict[str, int]:
        """Blocks ``n_tokens`` of context need in each kind but the
        primary."""
        return {n: min(-(-n_tokens // self.block_size), k.max_blocks_per_seq)
                for n, k in self.kinds.items()
                if n != self.primary and not k.record_rows}

    def _reserve_more(self, seq: SequenceDescriptor, n_tokens: int,
                      fresh: list[int]) -> None:
        """Reserve the further kinds' tables for ``seq`` — in every kind or
        in none: a kind that cannot give its blocks hands back what the
        kinds before it gave AND ``fresh``, the blocks the primary kind
        just gave, so that a refused admission leaves no kind
        half-reserved."""
        got: dict[str, list[int]] = {}
        try:
            for name, n in self._more_need(n_tokens).items():
                got[name] = self.kinds[name].allocator.allocate(n)
        except RuntimeError:
            for name, blocks in got.items():
                self.kinds[name].allocator.free(blocks)
            if fresh:
                self.allocator.free(fresh)
            raise
        seq.kind_blocks = got

    def _free_more(self, seq: SequenceDescriptor) -> None:
        for name, blocks in seq.kind_blocks.items():
            if blocks:
                self.kinds[name].allocator.free(blocks)
        seq.kind_blocks = {}

    def sample(self) -> dict[str, int]:
        """Blocks live sequences hold, by kind (a record kind: records
        live, one a sequence in a slot), and each kind's running peak (the
        engine samples after every dispatch)."""
        live = {n: 0 for n in self.kinds}
        for seq in self.seqs.values():
            live[self.primary] += len(seq.blocks)
            for n, blocks in seq.kind_blocks.items():
                live[n] += len(blocks)
            for n, k in self.kinds.items():
                if k.record_rows and seq.slot >= 0:
                    live[n] += 1
        for n, k in self.kinds.items():
            k.blocks_peak = max(k.blocks_peak, live[n])
        return live

    def note_written(self, seq: SequenceDescriptor, start: int,
                     end: int) -> None:
        """Book the ring slots that writing positions ``[start, end)``
        overwrites in place (a page whose slot held an earlier page)."""
        bs = self.block_size
        for k in self.kinds.values():
            if k.ring and end > start:
                w = k.max_blocks_per_seq
                first = max(-(-start // bs), w)     # pages starting in range
                k.blocks_reused += max(0, (end - 1) // bs - first + 1)

    def attach_prefix_cache(self, cache) -> None:
        """Enable shared-prefix serving (engine init, linear tables only —
        rolling-ring tables reuse page slots in place and can never share)."""
        if self.seqs:
            raise RuntimeError("attach_prefix_cache before admitting")
        self.prefix_cache = cache

    def flush_prefix_cache(self) -> int:
        """Evict EVERY unreferenced cached page back to the free list
        (the weight hot-swap's skew guard, engine_v2.swap_weights): a
        page computed under the old weights must not seed a NEW
        request's prefill after the swap. Pages pinned by live
        sequences stay — an in-flight sequence keeps its own KV across
        a same-shape update (the hybrid-engine contract) — and fall to
        the ordinary LRU once released. Returns pages reclaimed.

        ``demote=False``: these pages were computed under the OLD
        weights — serializing them into the KV tier (the eviction sink,
        inference/kvtier.py) would only store chains the version-skew
        gate refuses to promote; they drop, the tier invalidates its own
        stale records via ``KVTier.set_weight_version``."""
        if self.prefix_cache is None:
            return 0
        reclaimed = self.prefix_cache.evict(len(self.prefix_cache),
                                            demote=False)
        if reclaimed:
            self.allocator.free(reclaimed)
        return len(reclaimed)

    def _blocks_for(self, n_tokens: int) -> int:
        # a sequence can never OWN more slots than the table has — the
        # rolling buffer reuses them past that point
        return min(-(-n_tokens // self.block_size), self.max_blocks_per_seq)

    def _alloc(self, n: int) -> list[int]:
        """Refcounted-API allocation: top the free list up from the prefix
        LRU under pressure (evicts only unreferenced cached pages — a
        referenced page is pinned by a live sequence's refcount, and
        in-flight steps only reference pages of live sequences)."""
        self._last_evicted = 0
        short = n - self.allocator.free_blocks
        if short > 0 and self.prefix_cache is not None:
            reclaimed = self.prefix_cache.evict(short)
            if reclaimed:
                self.allocator.free(reclaimed)
                self._last_evicted = len(reclaimed)
        return self.allocator.allocate(n)

    def can_admit(self, prompt_len: int, max_new_tokens: int = 0) -> bool:
        """Admission requires the WORST-CASE block budget (prompt + all
        generated tokens) to be free right now — blocks are reserved at
        admit time, so a scheduled step can never exhaust the pool mid-run
        (the failure mode lazy allocation would have). Unreferenced cached
        prefix pages count as free: allocation evicts them on demand.
        With a prefix cache attached, sequences that could WRAP the block
        table (worst case spans more slots than the table holds — the
        rolling-reuse regime) are refused outright: a wrap would rewrite
        blocks the trie may share with other readers."""
        need = self._blocks_for(prompt_len + max_new_tokens)
        avail = self.allocator.free_blocks
        if self.prefix_cache is not None:
            if -(-(prompt_len + max_new_tokens) // self.block_size) \
                    > self.max_blocks_per_seq:
                return False
            avail += self.prefix_cache.evictable_blocks
        if any(self.kinds[n].allocator.free_blocks < k for n, k in
               self._more_need(prompt_len + max_new_tokens).items()):
            return False
        return bool(self._free_slots) and avail >= need

    def admit(self, uid: int, tokens: list[int], max_new_tokens: int,
              eos_id: int | None = None) -> SequenceDescriptor:
        if uid in self.seqs:
            raise ValueError(f"uid {uid} already live")
        if not tokens:
            raise ValueError("empty prompt")
        if not self._free_slots:
            raise RuntimeError("no free sequence slots")
        if self.prefix_cache is not None and \
                -(-(len(tokens) + max_new_tokens) // self.block_size) \
                > self.max_blocks_per_seq:
            # shared pages sit at the table FRONT; a wrapped write (the
            # rolling (pos // bs) % width slot formula firing) would
            # rewrite a trie-owned block under every other reader —
            # refuse rather than corrupt (can_admit mirrors this)
            raise ValueError(
                f"prefix cache requires non-wrapping tables: "
                f"{len(tokens)} + {max_new_tokens} tokens exceed "
                f"{self.max_blocks_per_seq} x {self.block_size}")
        seq = SequenceDescriptor(uid=uid, tokens=list(tokens),
                                 max_new_tokens=max_new_tokens,
                                 eos_id=eos_id,
                                 slot=self._free_slots.pop(0))
        bs = self.block_size
        shared_nodes: list = []
        if self.prefix_cache is not None:
            # longest cached page-aligned prefix; the LAST prompt token is
            # always recomputed (its forward produces the first sample's
            # logits), so the hit is capped one token short of the prompt
            # (and at the block-table width for direct small-table users)
            shared_nodes = self.prefix_cache.match(
                tokens, max_tokens=min(len(tokens) - 1,
                                       self.max_blocks_per_seq * bs))
            # pin BEFORE allocating: _alloc under pressure evicts refs==0
            # LRU pages, and an unpinned matched chain is exactly that —
            # acquire first so the eviction scan can never reclaim a page
            # this admit is about to serve from
            if shared_nodes:
                self.prefix_cache.acquire(shared_nodes)
        n_need = self._blocks_for(len(tokens) + max_new_tokens)
        try:
            fresh = self._alloc(n_need - len(shared_nodes))
            self._reserve_more(seq, len(tokens) + max_new_tokens, fresh)
        except RuntimeError:
            # refused WHOLE: no kind is left half-reserved
            if shared_nodes:
                self.prefix_cache.release(shared_nodes)
            self._free_slots.insert(0, seq.slot)
            raise
        if shared_nodes:
            # adopt the cached chain: read-only pages at the table front,
            # prefill (and the scheduler's chunk chain) starts at the
            # page-aligned cached boundary
            self._shared_nodes[uid] = shared_nodes
            seq.n_shared_blocks = len(shared_nodes)
            seq.n_computed = len(shared_nodes) * bs
            seq.prefix_hit_tokens = seq.n_computed
        seq.blocks = [n.block for n in shared_nodes] + fresh
        if self.prefix_cache is not None:
            seq.admit_wv = self.prefix_cache.weight_version
        self.seqs[uid] = seq
        rt = self.reqtrace
        if rt is not None and rt.enabled:
            # the admit transition carries the prefix-cache hit extent and
            # the reservation — the timeline's "where did this request
            # start from" ground truth
            rt.event(uid, "admit", prompt=len(tokens),
                     max_new=max_new_tokens, blocks=len(seq.blocks),
                     prefix_hit=seq.prefix_hit_tokens,
                     shared_blocks=seq.n_shared_blocks,
                     evicted=self._last_evicted, slot=seq.slot)
        return seq

    def release(self, uid: int) -> None:
        """Free a sequence's slot + pages. With a prefix cache attached,
        full pages whose KV is COMPUTED are published into the trie
        (blocks donated, dedup'd against concurrent publishers) instead of
        freed; shared pages drop their refcount. Callers (engine flush)
        must have drained in-flight steps referencing this uid first.

        Refused while a migration pins the sequence: an exported bundle's
        pages must stay bit-stable until the importer acks
        (``export_ack`` / ``export_abort`` first), and a half-imported
        sequence owns pages with no committed content
        (``abort_import``)."""
        if self.seqs[uid].frozen:
            raise RuntimeError(
                f"uid {uid} is pinned by an in-flight migration "
                f"({self.seqs[uid].migrating!r}): settle it via "
                f"export_ack/export_abort/abort_import before release")
        seq = self.seqs.pop(uid)
        published = 0
        if self.prefix_cache is not None and seq.slot >= 0:
            shared = self._shared_nodes.pop(uid, None)
            if seq.admit_wv != self.prefix_cache.weight_version:
                # the weights swapped while this sequence was live
                # (engine_v2.swap_weights): its KV was computed at least
                # partly under the OLD weights, so publishing it would
                # re-seed the post-swap trie with stale pages — drop the
                # shared pins and free the owned tail instead
                if shared:
                    self.prefix_cache.release(shared)
                owned = seq.blocks[seq.n_shared_blocks:]
                if owned:
                    self.allocator.free(owned)
            else:
                to_free = self.prefix_cache.publish(
                    seq.tokens, seq.blocks, seq.n_shared_blocks,
                    min(seq.n_computed, len(seq.tokens)))
                published = len(seq.blocks) - len(to_free)
                if to_free:
                    self.allocator.free(to_free)
        elif seq.blocks:
            self.allocator.free(seq.blocks)
        self._free_more(seq)
        if seq.slot >= 0:
            self._free_slots.append(seq.slot)
            self._free_slots.sort()
        rt = self.reqtrace
        if rt is not None and rt.enabled:
            # release closes the timeline (and settles the tenant's
            # KV page-seconds integral inside the tracer)
            rt.event(uid, "release", pages=len(seq.blocks),
                     published=published, generated=seq.n_generated)

    # --- speculative decoding: the rollback-aware provisional API --------
    # A verify step runs candidate tokens through the model ahead of
    # acceptance. Candidate KV only ever lands in the sequence's OWNED
    # tail pages (positions >= len(tokens) - 1 >= the shared-page
    # boundary) and inside the block budget RESERVED at admit, so
    # provisioning never allocates, never touches refcounts, and a
    # rejected candidate is erased by bookkeeping alone — the stale KV
    # beyond ``n_computed`` is overwritten by the next accepted token and
    # ``release``/``publish`` never reads past ``n_computed``. These four
    # methods are the ONLY legal mutators of ``n_provisional``
    # (bin/check_state_invariants.py rejects any other site).

    def provision(self, uid: int, n: int) -> None:
        """Mark ``n`` candidate tokens as provisionally scheduled for a
        decode-ready sequence. Bounds: candidates beyond the generation
        budget would write past the block reservation — refused."""
        seq = self.seqs[uid]
        if n < 0:
            raise ValueError(f"negative provisional count {n}")
        if seq.pending_tokens != 1:
            raise RuntimeError(
                f"uid {uid} is not decode-ready (pending "
                f"{seq.pending_tokens}); speculative steps verify from "
                f"the committed last token")
        rem = seq.max_new_tokens - seq.n_generated
        if n > max(rem - 1, 0):
            # a verify step emits up to n+1 tokens (matched candidates +
            # the bonus sample) — cap one short of the remaining budget so
            # the commit can never overshoot max_new_tokens or the block
            # reservation
            raise RuntimeError(
                f"uid {uid}: {n} provisional tokens + bonus exceed the "
                f"remaining generation budget {rem}")
        seq.n_provisional = n

    def commit_speculative(self, uid: int, accepted: list[int]) -> list[int]:
        """Fold a verify step's ACCEPTED tokens into the committed view
        and clear the provisional marker (the rejected remainder rolls
        back here — bookkeeping only, see the class note above). KV is in
        the pool for the verified root + each accepted-but-last token, so
        ``n_computed`` advances by ``len(accepted)`` exactly like a chain
        of plain decode commits. Returns the tokens surviving the stop
        criteria (eos/max_new truncation, like ``commit_generated``)."""
        seq = self.seqs[uid]
        n = len(accepted)
        if n < 1:
            raise ValueError("a verify step always accepts >= 1 token "
                             "(the target sample at the deepest node)")
        if n > seq.n_provisional + 1:
            raise RuntimeError(
                f"uid {uid}: accepting {n} tokens but only "
                f"{seq.n_provisional} were provisioned (+1 bonus)")
        seq.n_provisional = 0
        out = seq.commit_generated(list(accepted), n)
        # spec steps run on a drained pipeline: reconcile the scheduled
        # view so the next plan (spec or plain) sees committed state
        seq.n_sched = seq.n_computed
        seq.n_inflight = 0
        rt = self.reqtrace
        if rt is not None and rt.enabled and out:
            rt.event(uid, "commit", tokens=len(out), spec=True)
        return out

    def rollback_provisional(self, uid: int) -> None:
        """Discard a provisioned-but-unverified tree (flush mid-spec,
        failed dispatch): clear the marker; owned-tail KV beyond
        ``n_computed`` is dead by construction."""
        seq = self.seqs.get(uid)
        if seq is not None:
            had = seq.n_provisional
            seq.n_provisional = 0
            rt = self.reqtrace
            if rt is not None and rt.enabled and had:
                rt.event(uid, "rollback", provisional=had)

    def rewind(self, uid: int, tokens: list[int]) -> None:
        """Reset a sequence's token history to ``tokens`` (the draft-model
        proposer's mirror sync: the target's accept/reject decision is
        ground truth, the draft rewinds to it every proposal round).
        Computed KV for the surviving prefix stays valid — same tokens,
        same positions, same pages; KV past the cut is overwritten as the
        draft re-decodes. Blocks never change hands (the admit-time
        reservation must cover the new history — callers size
        ``max_new_tokens`` for the full target budget)."""
        seq = self.seqs[uid]
        if not tokens:
            raise ValueError("cannot rewind to an empty history")
        if self.not_a_page_chain:
            raise RuntimeError(
                f"uid {uid}: rewind needs page chains: "
                f"{self.not_a_page_chain}")
        if seq.n_shared_blocks:
            shared = seq.n_shared_blocks * self.block_size
            if (len(tokens) <= shared
                    or tokens[:shared] != seq.tokens[:shared]):
                raise RuntimeError(
                    f"uid {uid}: rewind would rewrite shared prefix pages")
        if self._blocks_for(len(tokens)) > len(seq.blocks):
            raise RuntimeError(
                f"uid {uid}: rewind target of {len(tokens)} tokens "
                f"exceeds the {len(seq.blocks)}-block reservation")
        # longest common prefix: KV is only valid where histories agree
        keep = 0
        for a, b in zip(seq.tokens, tokens):
            if a != b:
                break
            keep += 1
        seq.tokens = list(tokens)
        # the last token is always re-run (its forward produces the next
        # logits), so computed KV is capped one short of the history —
        # and FLOORED to a page boundary: the resume prefill chunk starts
        # at kv_next, and the engine's page-merge program whole-page-
        # writes multi-token chunks only from page-aligned starts (the
        # partial page is recomputed; its KV is identical by construction)
        keep = min(seq.n_computed, keep, len(tokens) - 1)
        seq.n_computed = keep - keep % self.block_size
        seq.n_sched = seq.n_computed
        seq.n_inflight = 0
        seq.n_provisional = 0
        # the generation budget restarts from the rewound history, CAPPED
        # so it can never outrun the admit-time block reservation: a
        # mirror rewound to a LONGER history (the target committed G
        # tokens since admit) granted the full budget again could decode
        # G tokens past its pages (e.g. an un-rewound mirror whose target
        # finished but whose flush is delayed) and index off the block
        # list
        cap = len(seq.blocks) * self.block_size
        seq.n_generated = max(0, seq.max_new_tokens - (cap - len(tokens)))
        seq.done = False
        rt = self.reqtrace
        if rt is not None and rt.enabled:
            rt.event(uid, "rewind", to_len=len(tokens),
                     kept_kv=seq.n_computed)

    # --- KV-page migration: the refcounted export/import/abort API -------
    # Disaggregated prefill/decode serving (inference/migration.py,
    # serving/disagg.py) moves a sequence's computed KV pages between
    # pools. Ownership never changes hands mid-transfer: the exporter's
    # pages stay owned by the (frozen) source sequence until the importer
    # ACKS — ``sched_done`` freezes the sequence out of every plan
    # builder, so page content is bit-stable for the whole transfer — and
    # the importer's pages are ordinary owned blocks until
    # ``import_commit`` seeds the prefix trie from them. An abort on
    # either side is pure bookkeeping: unfreeze (source) or free the
    # reservation (importer); no block is ever double-owned or leaked.
    # These six methods are the ONLY legal mutators of ``migrating``
    # (bin/check_state_invariants.py rejects any other site).

    def migrate_out(self, uid: int, trace: str | None = None) -> dict:
        """Pin a live sequence for export and return its page-chain
        snapshot: token history, committed-KV extent, and the pool blocks
        holding it (full pages + the partial tail extent). Callers
        (engine) must have drained in-flight steps referencing this uid
        first — the committed view IS the pool content then. The
        sequence stays live and owns its pages; it is merely frozen until
        ``export_ack`` (importer took over → release) or
        ``export_abort`` (resume decoding locally). ``trace`` is the
        serving-tier trace ID: both replicas' lifecycle events carry it,
        so one request's export and import line up under one key."""
        seq = self.seqs[uid]
        if seq.frozen:
            raise RuntimeError(f"uid {uid} is already migrating "
                               f"({seq.migrating!r})")
        if seq.done:
            raise RuntimeError(f"uid {uid} is done: nothing to migrate")
        if seq.n_provisional:
            raise RuntimeError(
                f"uid {uid} has a provisional speculative tree in flight "
                f"— commit or roll it back before migrating")
        if seq.n_inflight:
            raise RuntimeError(
                f"uid {uid} has {seq.n_inflight} sampled tokens in "
                f"flight — drain the pipeline before migrating")
        bs = self.block_size
        if -(-(len(seq.tokens) + seq.max_new_tokens - seq.n_generated)
             // bs) > self.max_blocks_per_seq:
            # a wrap-capable sequence's rolling table reuses page slots in
            # place — the linear page chain the bundle format commits to
            # does not exist for it
            raise RuntimeError(
                f"uid {uid} can wrap its block table "
                f"(rolling-ring regime): page migration requires linear "
                f"tables")
        n_full = seq.n_computed // bs
        tail_rows = seq.n_computed - n_full * bs
        seq.migrating = "out"
        rt = self.reqtrace
        if rt is not None and rt.enabled:
            rt.event(uid, "migrate_out", pages=n_full, tail=tail_rows,
                     tokens=len(seq.tokens), trace=trace)
        return {
            "uid": uid, "tokens": list(seq.tokens),
            "n_computed": seq.n_computed,
            "n_generated": seq.n_generated,
            "max_new_tokens": seq.max_new_tokens,
            "eos_id": seq.eos_id, "block_size": bs,
            "page_blocks": list(seq.blocks[:n_full]),
            "tail_block": seq.blocks[n_full] if tail_rows else None,
            "tail_rows": tail_rows,
        }

    def export_ack(self, uid: int) -> None:
        """The importer owns the stream now: unfreeze and mark the source
        sequence done so the caller's normal flush path releases it
        (publishing its computed pages into the LOCAL trie — the source
        replica keeps serving the prefix from cache)."""
        seq = self.seqs[uid]
        if seq.migrating != "out":
            raise RuntimeError(f"uid {uid} has no export in flight")
        seq.migrating = None
        seq.done = True

    def export_abort(self, uid: int) -> None:
        """Transfer failed or was refused: unfreeze. The sequence is
        decode-ready again and resumes exactly where it stopped — no
        block changed hands, nothing to roll back."""
        seq = self.seqs[uid]
        if seq.migrating != "out":
            raise RuntimeError(f"uid {uid} has no export in flight")
        seq.migrating = None

    def migrate_in_begin(self, uid: int, tokens: list[int],
                         n_computed: int, n_generated: int,
                         max_new_tokens: int, eos_id: int | None = None,
                         trace: str | None = None) -> SequenceDescriptor:
        """Reserve a slot + the FULL remaining block budget for an
        arriving sequence (capacity is claimed before the first payload
        byte lands, so a concurrent admit can never strand a
        half-transferred bundle). The sequence is created frozen
        (``migrating="in"``): the caller writes the bundle's KV payload
        into the returned descriptor's blocks, then ``import_commit``
        seeds the prefix trie and unfreezes — or ``abort_import`` hands
        every block back."""
        if uid in self.seqs:
            raise ValueError(f"uid {uid} already live")
        if not tokens:
            raise ValueError("empty token chain")
        if not 0 <= n_computed <= len(tokens) - 1:
            raise ValueError(
                f"n_computed {n_computed} outside [0, {len(tokens) - 1}] "
                f"(the last token is always recomputed)")
        if n_generated > max_new_tokens:
            raise ValueError(f"n_generated {n_generated} exceeds the "
                             f"budget {max_new_tokens}")
        if not self._free_slots:
            raise RuntimeError("no free sequence slots")
        bs = self.block_size
        remaining = max_new_tokens - n_generated
        if -(-(len(tokens) + remaining) // bs) > self.max_blocks_per_seq:
            # mirrors admit: the imported chain must stay linear (and,
            # with a prefix cache attached, must never wrap trie pages)
            raise RuntimeError(
                f"import of {len(tokens)} + {remaining} tokens would wrap "
                f"the {self.max_blocks_per_seq} x {bs} block table")
        seq = SequenceDescriptor(uid=uid, tokens=list(tokens),
                                 max_new_tokens=max_new_tokens,
                                 eos_id=eos_id,
                                 slot=self._free_slots.pop(0))
        try:
            fresh = self._alloc(self._blocks_for(len(tokens) + remaining))
            self._reserve_more(seq, len(tokens) + remaining, fresh)
        except RuntimeError:
            self._free_slots.insert(0, seq.slot)
            raise
        seq.blocks = fresh
        seq.n_computed = n_computed
        seq.n_sched = n_computed
        seq.n_generated = n_generated
        seq.migrating = "in"
        self._mig_trace[uid] = trace
        self.seqs[uid] = seq
        return seq

    def import_commit(self, uid: int) -> None:
        """Payload landed: seed the local prefix trie from the imported
        full pages (the first leg of the distributed radix cache — the
        pages become shared trie nodes this sequence references, and
        every later same-prefix admit on this pool hits them) and
        unfreeze. Duplicate pages another sequence already published
        dedup: the freshly-written copy goes back to the allocator and
        the table points at the cached block (identical content by
        construction — same token chain, same weights)."""
        seq = self.seqs[uid]
        if seq.migrating != "in":
            raise RuntimeError(f"uid {uid} has no import in flight")
        bs = self.block_size
        n_full = seq.n_computed // bs
        if self.prefix_cache is not None and n_full > 0:
            nodes, dups = self.prefix_cache.adopt(
                seq.tokens, seq.blocks[:n_full], n_full * bs)
            if len(nodes) != n_full:    # pragma: no cover — adopt contract
                raise RuntimeError(
                    f"uid {uid}: adopted {len(nodes)} trie pages, "
                    f"expected {n_full}")
            self._shared_nodes[uid] = nodes
            seq.n_shared_blocks = n_full
            seq.blocks = [n.block for n in nodes] + seq.blocks[n_full:]
            seq.prefix_hit_tokens = 0     # imported, not served from cache
            if dups:
                self.allocator.free(dups)
        if self.prefix_cache is not None:
            # skew-gated imports only land same-version bundles, so the
            # imported pages are current-by-construction
            seq.admit_wv = self.prefix_cache.weight_version
        seq.migrating = None
        rt = self.reqtrace
        if rt is not None and rt.enabled:
            rt.event(uid, "migrate_in", pages=n_full,
                     tokens=len(seq.tokens), shared=seq.n_shared_blocks,
                     trace=self._mig_trace.pop(uid, None))
        else:
            self._mig_trace.pop(uid, None)

    def abort_import(self, uid: int) -> None:
        """Transfer died before commit: free the whole reservation and
        the slot. The trie was never touched (seeding happens at commit),
        so this cannot leak or double-own a block."""
        seq = self.seqs.get(uid)
        if seq is None:
            return
        if seq.migrating != "in":
            raise RuntimeError(f"uid {uid} has no import in flight")
        self.seqs.pop(uid)
        self._mig_trace.pop(uid, None)
        if seq.blocks:
            self.allocator.free(seq.blocks)
        seq.blocks = []
        self._free_more(seq)
        if seq.slot >= 0:
            self._free_slots.append(seq.slot)
            self._free_slots.sort()

    # --- cross-replica radix pulls (placement-time distributed cache) ----
    # A request placed on a replica WITHOUT its prefix can pull the page
    # chain from the peer that holds it instead of recomputing it
    # (serving/router.py decides pull-vs-recompute; the wire form is a
    # kind="prefix" PageBundle). Gang prefill reuses both legs verbatim:
    # each member exports its merged chain (snapshot_prefix), the next
    # member adopts it (adopt_prefix) and prefills only its own segment
    # on top — the prompt's KV grows member-to-member with no new state
    # machinery here. These three methods are the refcounted surface for
    # both legs — bin/check_state_invariants.py pins every
    # trie/allocator mutation they need to exactly these sites.

    def snapshot_prefix(self, tokens, trace: str | None = None) -> dict | None:
        """Export leg: match + PIN the longest cached chain prefixing
        ``tokens`` so the caller can read the page payloads while nothing
        evicts them. Returns ``{"handle", "blocks", "n_tokens"}`` or None
        on a miss; the caller MUST ``release_prefix(handle)`` once the
        payload is copied out (the pin is gather-scoped, not
        pinned-until-ack: the importer adopts a COPY — the source keeps
        and keeps serving its own pages)."""
        if self.prefix_cache is None:
            return None
        nodes = self.prefix_cache.match(tokens)
        if not nodes:
            return None
        self.prefix_cache.acquire(nodes)
        self._pull_ctr += 1
        handle = self._pull_ctr
        self._pull_pins[handle] = nodes
        rt = self.reqtrace
        if rt is not None and rt.enabled:
            rt.event(-1, "kv_pull", dir="out", pages=len(nodes),
                     trace=trace)
        return {"handle": handle, "blocks": [n.block for n in nodes],
                "n_tokens": len(nodes) * self.block_size}

    def release_prefix(self, handle: int) -> None:
        """Drop a prefix export's pins (pages stay cached, LRU-able)."""
        nodes = self._pull_pins.pop(handle, None)
        if nodes:
            self.prefix_cache.release(nodes)

    def adopt_prefix(self, tokens, n_tokens: int,
                     trace: str | None = None) -> list[tuple[int, int]]:
        """Import leg: allocate a block per full page of
        ``tokens[:n_tokens]`` and insert the chain into the trie
        UNREFERENCED (no sequence owns a pull — the pages are ordinary
        LRU-evictable cache entries the arriving request's admit will
        pin through the normal match path). Pages another sequence
        already published dedup: their fresh blocks go straight back to
        the allocator and the cached copy serves. Returns ``(page index,
        block)`` for the freshly-inserted pages — the engine scatters the
        pulled payload into exactly those blocks before anything else can
        schedule against them (same host operation). Raises RuntimeError
        when the pool cannot fit the chain (caller falls back to
        recompute)."""
        bs = self.block_size
        n_full = min(n_tokens, len(tokens)) // bs
        if self.prefix_cache is None or n_full == 0:
            return []
        blocks = self._alloc(n_full)
        nodes, dups = self.prefix_cache.adopt(tokens, blocks,
                                              n_full * bs)
        self.prefix_cache.release(nodes)
        if dups:
            self.allocator.free(dups)
        fresh = [(j, nodes[j].block) for j in range(n_full)
                 if nodes[j].block == blocks[j]]
        rt = self.reqtrace
        if rt is not None and rt.enabled:
            rt.event(-1, "kv_pull", dir="in", pages=n_full,
                     fresh=len(fresh), trace=trace)
        return fresh

    def audit(self) -> None:
        """Debug-mode FULL-POOL audit: every non-trash block is owned by
        exactly one of {free list, prefix trie, one sequence's owned
        tail}; shared table entries point at live trie nodes; per-node
        refcounts equal the number of live sequences sharing the block.
        Raises AssertionError on any leak, double-own, or refcount drift
        (DS_TPU_STATE_AUDIT=1 runs this from the engine's flush path)."""
        free = list(self.allocator._free)
        if len(set(free)) != len(free):
            raise AssertionError("free list holds duplicate blocks")
        owners: dict[int, str] = {b: "free" for b in free}
        trie_blocks: set[int] = set()
        if self.prefix_cache is not None:
            self.prefix_cache.check()
            trie_blocks = self.prefix_cache.blocks()
            for b in trie_blocks:
                if b in owners:
                    raise AssertionError(f"block {b} in free list AND trie")
                owners[b] = "trie"
        ref_counts: dict[int, int] = {}
        for uid, seq in self.seqs.items():
            if seq.migrating not in (None, "out", "in"):
                raise AssertionError(
                    f"uid {uid}: bad migration state {seq.migrating!r}")
            if seq.migrating == "in" and seq.n_shared_blocks:
                raise AssertionError(
                    f"uid {uid}: importing sequence already shares "
                    f"{seq.n_shared_blocks} trie pages (seeding must "
                    f"happen at import_commit)")
            if seq.migrating == "out" and (seq.n_inflight
                                           or seq.n_provisional):
                raise AssertionError(
                    f"uid {uid}: exported sequence has in-flight work "
                    f"(inflight {seq.n_inflight}, provisional "
                    f"{seq.n_provisional}) — pages are not bit-stable")
            if seq.n_provisional < 0:
                raise AssertionError(
                    f"uid {uid}: negative provisional count "
                    f"{seq.n_provisional}")
            if seq.n_provisional:
                # provisional KV spans positions [len-1, len-1+n]: it must
                # start past the shared-page boundary (never pollutes a
                # published/trie page) and end inside the reservation
                first = len(seq.tokens) - 1
                if first < seq.n_shared_blocks * self.block_size:
                    raise AssertionError(
                        f"uid {uid}: provisional slot {first} falls inside "
                        f"a shared prefix page")
                last = first + seq.n_provisional
                if last >= len(seq.blocks) * self.block_size:
                    raise AssertionError(
                        f"uid {uid}: provisional tokens reach slot {last} "
                        f"past the {len(seq.blocks)}-block reservation")
            for j, b in enumerate(seq.blocks):
                if j < seq.n_shared_blocks:
                    if b not in trie_blocks:
                        raise AssertionError(
                            f"uid {uid} shares block {b} not owned by the "
                            f"trie (stale page)")
                    ref_counts[b] = ref_counts.get(b, 0) + 1
                elif b in owners:
                    raise AssertionError(
                        f"block {b} owned by uid {uid} AND {owners[b]}")
                else:
                    owners[b] = f"uid {uid}"
        # an in-flight prefix export (snapshot_prefix) pins its chain like
        # a sequence does — gather-scoped, but the refcounts must balance
        # at any instant the caller audits
        for nodes in self._pull_pins.values():
            for node in nodes:
                if node.block not in trie_blocks:
                    raise AssertionError(
                        f"pull pin on block {node.block} the trie no "
                        f"longer owns")
                ref_counts[node.block] = ref_counts.get(node.block, 0) + 1
        if self.prefix_cache is not None:
            for node in self.prefix_cache._nodes():
                expect = ref_counts.get(node.block, 0)
                if node.refs != expect:
                    raise AssertionError(
                        f"refcount drift on block {node.block}: trie says "
                        f"{node.refs}, {expect} live sequence(s) share it")
        n_all = self.allocator.num_blocks - 1     # block 0 is the trash slot
        if len(owners) != n_all:
            missing = set(range(1, self.allocator.num_blocks)) - set(owners)
            raise AssertionError(f"leaked blocks (owned by nobody): "
                                 f"{sorted(missing)}")
        # the further kinds share nothing: free list + owned tables
        for name, k in self.kinds.items():
            if name == self.primary:
                continue
            if k.record_rows:
                # a record is addressed by the slot: every live sequence
                # in a slot of its own, the rest free, none out of range
                slots = [s.slot for s in self.seqs.values() if s.slot >= 0]
                if sorted(slots + self._free_slots) \
                        != list(range(self.max_seqs)):
                    raise AssertionError(
                        f"{name} records: slots held twice, leaked or out "
                        f"of range (live {sorted(slots)}, free "
                        f"{sorted(self._free_slots)})")
                if seq_blocks := [u for u, s in self.seqs.items()
                                  if name in s.kind_blocks]:
                    raise AssertionError(
                        f"{name} is a record kind but uid(s) {seq_blocks} "
                        f"hold blocks of it")
                continue
            held = list(k.allocator._free)
            for uid, seq in self.seqs.items():
                mine = seq.kind_blocks.get(name, [])
                if len(mine) > k.max_blocks_per_seq:
                    raise AssertionError(
                        f"uid {uid} holds {len(mine)} {name} blocks, more "
                        f"than its table of {k.max_blocks_per_seq}")
                held.extend(mine)
            if sorted(held) != list(range(1, k.allocator.num_blocks)):
                raise AssertionError(
                    f"{name} pool: blocks leaked or owned twice")


@dataclass
class StepPlan:
    """One scheduled forward step (the RaggedBatchWrapper analogue): plain
    arrays the jitted program consumes. All shapes static:
    [max_seqs, chunk]."""
    kind: str                         # 'prefill' | 'decode'
    token_ids: np.ndarray             # [S, T] int32
    positions: np.ndarray             # [S, T] int32 (pad → 0)
    slot_map: np.ndarray              # [S, T] int32 → pool token slot (block*bs+off)
    active: np.ndarray                # [S, T] uint8 — real tokens
    block_tables: np.ndarray          # [S, max_blocks] int32
    seq_lens: np.ndarray              # [S] int32, length incl. this step's tokens
    sample_idx: np.ndarray            # [S] int32 index into T of last real token
    do_sample: np.ndarray             # [S] uint8 — emit a token for this slot
    use_last: np.ndarray = None       # [S] uint8 — col-0 token comes from the
    #                                   device-resident last-sampled array
    #                                   (its host value is still in flight)
    row_slots: np.ndarray = None      # [S] int32 — physical slot per plan row
    #                                   (packed prefill plans carry fewer rows
    #                                   than max_seqs; row==slot when full)
    uids: list[int] = field(default_factory=list)   # uid per row (-1 = empty)
    dispatched: bool = False          # mark_dispatched ran (async pipeline)
    #: the same two arrays for each further kind of layer (kind ->
    #: (slot_map [S, T], block_tables [S, that kind's width])); empty for
    #: a model of one kind
    more: dict = field(default_factory=dict)
    #: a PREFILL plan's decode block: the decode-ready sequences as a
    #: ``[max_seqs, 1]`` decode plan that rides the same program as a
    #: second segment (None: the program's block has no live row)
    block: "StepPlan | None" = None

    @property
    def all_uids(self) -> list[int]:
        """The uids of the plan's rows and of its block's."""
        return self.uids if self.block is None \
            else self.uids + self.block.uids

    def sampled_rows(self) -> list[tuple[int, int]]:
        """``(row, uid)`` of every row that samples, ``row`` its place in
        the program's ``toks``: the plan's rows, then its block's."""
        rows = [(r, uid) for r, uid in enumerate(self.uids)
                if uid >= 0 and self.do_sample[r]]
        if self.block is not None:
            rows += [(len(self.uids) + r, uid)
                     for r, uid in self.block.sampled_rows()]
        return rows
