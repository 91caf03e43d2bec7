"""Continuous-batching scheduler (Dynamic SplitFuse, TPU formulation).

Reference: inference/v2 engine scheduling (``InferenceEngineV2.put``
engine_v2.py:107, ``can_schedule`` :184) and the Dynamic SplitFuse policy
from the FastGen blog — long prompts are decomposed into fixed-size chunks
so every forward step has near-constant token count.

TPU formulation: FastGen packs prompt chunks and decode tokens into ONE
ragged batch; under XLA's static shapes a decode token in a ``[rows,
chunk]`` rectangle would occupy a whole padded row. So a step is two
fixed-shape SEGMENTS of one forward: the prompt chunks (``[rows, chunk]``)
and, riding every prefill step, the DECODE BLOCK — the decode-ready
sequences as a ``[max_seqs, 1]`` plan of its own (``StepPlan.block``), at
its full static width whoever is live, so the compiled menu does not know
it. The weights are read once for both (``inference/forward.py``). With no
chunk pending the engine runs pure decode steps and windows; with chunks
pending it alternates prefill steps (each worth one token to every
decoder) with capped decode windows.
"""
from __future__ import annotations

import numpy as np

from ..telemetry import get_telemetry
from .ragged import SequenceDescriptor, StateManager, StepPlan


class SplitFuseScheduler:
    def __init__(self, state: StateManager, chunk: int, pack: bool = False,
                 grow_chunk: bool = True, max_rows: int = 0):
        self.state = state
        self.chunk = chunk
        #: the most sequences one packed prefill plan carries (0: all that
        #: have work); the rest keep their place for the next plan
        self.max_rows = max_rows if 0 < max_rows < state.max_seqs else 0
        #: packed plans may carry a chunk LONGER than ``chunk`` (see
        #: ``pack``). False where a kind of layer keeps a ring: the ring is
        #: sized for chunk-at-most steps and a grown chunk would overrun
        #: it — such a model packs ROWS only (exactly the rows that have
        #: work, ``chunk`` tokens each)
        self.grow_chunk = grow_chunk
        # process-wide telemetry (telemetry/); configure() mutates the
        # instance in place, so caching the reference here stays live
        self._telem = get_telemetry()
        # per-request lifecycle tracing (telemetry/reqtrace.py): the
        # scheduler emits the per-row dispatch/commit transitions —
        # engine_v2 overrides this with its (possibly pinned-off) handle
        self._reqtrace = self._telem.reqtrace
        #: token-budget prefill packing (VERDICT r04 weak #2: prefill
        #: steps ran 44% useful tokens): when fewer than max_seqs rows
        #: have work, the plan carries EXACTLY the rows that have work
        #: (exact-k — pow2 row buckets measured worse, see next_step) and
        #: each active row's chunk GROWS along the page-aligned chunk
        #: chain to keep S*T — the per-step compute — near-constant. The
        #: Dynamic SplitFuse constant-work idea applied to XLA's static
        #: shapes: a bounded menu of (rows, chunk) programs instead of
        #: one padded rectangle.
        self.pack = pack
        #: pad packed prefill plans' row count UP to a multiple of this
        #: (engine_v2 sets it to the tensor-axis size under tp_overlap so
        #: every prefill program rings — the ROADMAP odd-row item: an
        #: exact-k plan with rows % tp != 0 used to fall back to the
        #: blocking TP path). Padded rows are empty (masked: uid -1,
        #: distinct unused slots, trash-block writes, do_sample 0) — the
        #: same convention full-width plans already use for idle rows.
        #: 1 = exact-k, no padding.
        self.row_multiple = 1
        #: whether a prefill plan carries the decode-ready sequences as its
        #: ``block``. The engine clears it where the decode side belongs
        #: to speculative verify rounds; a block-less prefill plan runs
        #: the same program with a block of no live row.
        self.decode_rides = True

    def _desc(self, kind: str, T: int, entries,
              use_last_slots=(), n_rows: int | None = None) -> StepPlan:
        S = n_rows if n_rows is not None else self.state.max_seqs
        bs = self.state.block_size
        max_blocks = self.state.max_blocks_per_seq
        packed = S != self.state.max_seqs
        plan = StepPlan(
            kind=kind,
            token_ids=np.zeros((S, T), np.int32),
            positions=np.zeros((S, T), np.int32),
            slot_map=np.zeros((S, T), np.int32),     # trash block slot 0
            active=np.zeros((S, T), np.uint8),
            block_tables=np.zeros((S, max_blocks), np.int32),
            seq_lens=np.zeros(S, np.int32),
            sample_idx=np.zeros(S, np.int32),
            do_sample=np.zeros(S, np.uint8),
            use_last=np.zeros(S, np.uint8),
            row_slots=np.zeros(S, np.int32),
            uids=[-1] * S,
        )
        # row r of a packed plan serves entries[r] (its physical slot in
        # row_slots); full-width plans keep row == slot
        row_of = {seq.slot: (r if packed else seq.slot)
                  for r, (seq, *_) in enumerate(entries)}
        for s in use_last_slots:
            plan.use_last[row_of[s]] = 1
        if not (entries and self._native_build(plan, T, entries, row_of)):
            for r, (seq, toks, start_pos, sample) in enumerate(entries):
                s = r if packed else seq.slot
                n = len(toks)
                plan.token_ids[s, :n] = toks
                plan.positions[s, :n] = np.arange(start_pos, start_pos + n)
                for j in range(n):
                    pos = start_pos + j
                    # rolling-buffer slot (mod is a no-op in linear mode)
                    blk = seq.blocks[(pos // bs) % max_blocks]
                    plan.slot_map[s, j] = blk * bs + pos % bs
                plan.active[s, :n] = True
                plan.block_tables[s, :len(seq.blocks)] = seq.blocks
                plan.seq_lens[s] = start_pos + n
                plan.sample_idx[s] = n - 1
                plan.do_sample[s] = sample
        # the model's further kinds of layer: the same two arrays from
        # each kind's own table (a window kind's ring: slot (pos // bs) %
        # width of ITS width)
        for name, k in self.state.kinds.items():
            if name == self.state.primary or k.record_rows:
                continue
            w = k.max_blocks_per_seq
            slots = np.zeros((S, T), np.int32)
            tables = np.zeros((S, w), np.int32)
            for seq, toks, start_pos, _ in entries:
                r = row_of[seq.slot]
                blocks = np.asarray(seq.kind_blocks[name], np.int32)
                pos = np.arange(start_pos, start_pos + len(toks))
                slots[r, :len(toks)] = blocks[(pos // bs) % w] * bs + pos % bs
                tables[r, :len(blocks)] = blocks
            plan.more[name] = (slots, tables)
        for seq, *_ in entries:
            r = row_of[seq.slot]
            plan.uids[r] = seq.uid
            plan.row_slots[r] = seq.slot
        # empty rows get DISTINCT unused slots: the program's last_tok
        # scatter (last_tok.at[row_slots].set) must never carry duplicate
        # indices, or an empty row's stale value could race a real row's
        # fresh sample at the same slot
        if packed or len(entries) < S:
            used = {seq.slot for seq, *_ in entries}
            free = (s for s in range(self.state.max_seqs) if s not in used)
            for r in range(S):
                if plan.uids[r] < 0:
                    plan.row_slots[r] = next(free)
        # a record kind is addressed by the row's SLOT: read there; written
        # there for the rows live in this step, at the trash record (the
        # last) for the rest (in ``slot_map``'s and ``block_tables``' place)
        for name, k in self.state.kinds.items():
            if k.record_rows:
                live = np.asarray(plan.uids) >= 0
                plan.more[name] = (
                    np.where(live, plan.row_slots,
                             self.state.max_seqs).astype(np.int32),
                    plan.row_slots.copy())
        return plan

    def _native_build(self, plan: StepPlan, T: int, entries,
                      row_of=None) -> bool:
        """Pack the plan arrays in C++ (csrc/atoms.cpp, the reference
        ragged/csrc host-buffer role); False → Python fallback. The
        builder indexes rows by the first meta field — packed plans pass
        the plan ROW there (row != slot), full plans the slot."""
        import ctypes

        from ..ops.native import load_library

        lib = load_library()
        if lib is None:
            return False
        tokens, blocks, meta = [], [], []
        for seq, toks, start_pos, sample in entries:
            row = row_of[seq.slot] if row_of is not None else seq.slot
            meta.extend((row, len(toks), start_pos, int(sample),
                         len(seq.blocks), len(tokens), len(blocks)))
            tokens.extend(toks)
            blocks.extend(seq.blocks)
        tok = np.asarray(tokens, np.int32)
        blk = np.asarray(blocks, np.int32)
        met = np.asarray(meta, np.int32)
        pp = lambda a: a.ctypes.data_as(ctypes.c_void_p)
        rc = lib.dstpu_build_atoms(
            len(entries), pp(tok), pp(met), pp(blk),
            plan.token_ids.shape[0], T, self.state.max_blocks_per_seq,
            self.state.block_size,
            pp(plan.token_ids), pp(plan.positions), pp(plan.slot_map),
            pp(plan.active), pp(plan.block_tables), pp(plan.seq_lens),
            pp(plan.sample_idx), pp(plan.do_sample))
        if rc != 0:
            raise ValueError(
                f"atom builder: entry {rc - 1} violates plan-shape "
                f"invariants (meta {meta[(rc - 1) * 7:rc * 7]})")
        return True

    def pending_kinds(self) -> tuple[bool, bool]:
        """(has_prefill, has_decode) over the SCHEDULED view — the
        engine's alternation + mixed-load window-cap inputs (a pending
        prefill chunk caps the next decode window so TTFT is bounded by
        ``decode_window_mixed_cap`` iterations, not a full window)."""
        has_prefill = has_decode = False
        for seq in self.state.seqs.values():
            if seq.sched_done or seq.slot < 0:
                continue
            if seq.pending_sched > 1:
                has_prefill = True
            else:
                has_decode = True
            if has_prefill and has_decode:
                break
        return has_prefill, has_decode

    def program_shape_menu(self) -> list[tuple[int, int]]:
        """Every (T, n_rows) prefill-plan shape :meth:`next_step` can emit
        under the current packing config — THE warm list for anything that
        must never compile mid-serve (a hand-kept copy drifted once and
        cost a 4.5s recompile inside an SLA-scored run). Mirrors the
        packing math below by construction."""
        S_max = self.state.max_seqs
        shapes = {(self.chunk, S_max)}
        if not self.pack:
            return sorted(shapes)
        if self.max_rows:
            # a capped plan is never the full-width one
            shapes.clear()
        for k in range(1, (self.max_rows or S_max - 1) + 1):
            n_rows = self._pad_rows(k)
            for T in self._chunk_chain(n_rows):
                shapes.add((T, n_rows))
        return sorted(shapes)

    def _pad_rows(self, k: int) -> int:
        """Packed-plan row count for ``k`` pending sequences: ``k`` rounded
        up to ``row_multiple`` (capped at the table width — when max_seqs
        itself doesn't divide, the full-width plan keeps today's per-
        program ring fallback)."""
        m = self.row_multiple
        if m <= 1:
            return k
        return min(-(-k // m) * m, self.state.max_seqs)

    def _chunk_chain(self, n_rows: int) -> list[int]:
        """The T values a packed ``n_rows``-row prefill plan may carry:
        the budget chunk halved toward the configured chunk, stopping
        before any value that is not page-aligned (a non-multiple of
        block_size would advance kv_next off a page boundary and a later
        page-merge program would fail the alignment invariant)."""
        bs = self.state.block_size
        out = [self.chunk]
        if self.grow_chunk and self.chunk % bs == 0:
            T = self.chunk * (self.state.max_seqs // n_rows)
            while T >= self.chunk and T % bs == 0:
                out.append(T)
                T //= 2
        return out

    def queue_depth(self) -> int:
        """Sequences with unscheduled work — the serving backlog gauge."""
        return sum(1 for seq in self.state.seqs.values()
                   if not seq.sched_done)

    def load_summary(self) -> dict:
        """Compact load view for a serving replica's heartbeat (the
        router's least-loaded placement signal and shed estimator):
        live sequences, backlog (prompt tokens not yet scheduled + decode
        budget remaining), and the prefill/decode pending split. Host-only
        dict ops — cheap enough for a sub-second heartbeat cadence."""
        live = queued = pending_tokens = migrating = 0
        for seq in self.state.seqs.values():
            live += 1
            if seq.frozen:
                # a migration pins this sequence (pages bit-stable or
                # still arriving): it holds capacity but schedules
                # nothing — the router's disagg placement reads this
                migrating += 1
                continue
            if seq.sched_done:
                continue
            queued += 1
            pending_tokens += max(seq.pending_sched - 1, 0) \
                + max(seq.max_new_tokens - seq.n_generated
                      - seq.n_inflight, 0)
        has_prefill, has_decode = self.pending_kinds()
        return {"live": live, "queued": queued,
                "pending_tokens": pending_tokens,
                "migrating": migrating,
                "pending_prefill": has_prefill,
                "pending_decode": has_decode}

    def next_step(self, prefer: str | None = None) -> StepPlan | None:
        """Plan-building entry point; see :meth:`_next_step_inner` for the
        policy. Telemetry wrapper: plan construction runs under a
        ``sched_plan`` span and the queue-depth gauge updates per call —
        host plan-build time showing up here is the signal that the C++
        atom builder (csrc) stopped engaging."""
        telem = self._telem
        if not telem.enabled:
            return self._next_step_inner(prefer)
        telem.registry.gauge(
            "serving_queue_depth",
            help="sequences with unscheduled work").set(self.queue_depth())
        with telem.span("sched_plan") as sp:
            plan = self._next_step_inner(prefer)
            if plan is not None:
                sp.set(kind=plan.kind, rows=plan.token_ids.shape[0],
                       T=plan.token_ids.shape[1])
        return plan

    def _next_step_inner(self, prefer: str | None = None) -> StepPlan | None:
        """Build the next step plan, or None if nothing to run.

        Plans from the SCHEDULED (speculative) view so the engine can
        dispatch ahead of readbacks. A decode row whose last token is
        still in flight carries a placeholder with ``use_last`` set — the
        program substitutes the device-resident last sampled token.

        A PREFILL plan carries the decode-ready sequences as its
        ``block``: the ``[max_seqs, 1]`` decode plan the branch below
        would have built, run by the same program as a second segment
        (round 5 had fused a decode row INTO the ``[S, T]`` rectangle,
        where it occupied a full T-token row, and took that out; a
        segment of its own costs no padded row). The engine still
        interleaves capped decode windows between prefill steps.
        ``prefer="decode"`` emits the pure decode plan when both kinds of
        work exist (the engine's alternation hint when the
        multi-iteration window path is unavailable)."""
        st = self.state
        prefill: list[SequenceDescriptor] = []
        decode: list[SequenceDescriptor] = []
        for seq in st.seqs.values():
            if seq.sched_done:
                continue
            (prefill if seq.pending_sched > 1 else decode).append(seq)

        def decode_plan():
            # a row whose last token is still in flight: the value lives
            # only on device → placeholder + use_last
            rows = decode[:st.max_seqs]
            return self._desc(
                "decode", 1,
                [(seq, [0] if seq.n_inflight else seq.tokens[-1:],
                  seq.kv_next, True) for seq in rows],
                [seq.slot for seq in rows if seq.n_inflight])

        # blocks were reserved for prompt + max_new_tokens at admit time,
        # so neither branch can exhaust the pool here
        if prefill and not (decode and prefer == "decode"):
            # token-budget packing: the plan carries exactly the rows that
            # have work (pow2 buckets round 5-7 rows up to 8 and miss the
            # pool-throttled steady state entirely — measured 54%
            # occupancy on the long mix), and each row's chunk grows by
            # the pow2 budget multiplier. One compiled program per
            # (rows, chunk) pair, ~4s each: warm ``program_shape_menu()``.
            k = min(len(prefill), st.max_seqs)
            if self.pack and self.max_rows:
                k = min(k, self.max_rows)
            n_rows = st.max_seqs
            T = self.chunk
            if self.pack and k < st.max_seqs:
                n_rows = self._pad_rows(max(1, k))
                chain = self._chunk_chain(n_rows)
                if len(chain) > 1:
                    # don't pad a row wider than the largest pending
                    # prompt; stay on the chain (page-aligned, >= chunk)
                    maxpend = max(s.pending_sched for s in prefill)
                    T = next((t for t in sorted(chain)
                              if t >= maxpend), max(chain))
                # chunk % block_size != 0 packs ROWS only: growing T could
                # make a later chunk hit the page-merge program with a
                # page-misaligned start (kv_next advanced by non-page
                # multiples) — the engine's invariant check would fire
            entries = []
            for seq in prefill[:n_rows]:
                n = min(T, seq.pending_sched)
                toks = seq.tokens[seq.kv_next:seq.kv_next + n]
                # sample only when this chunk consumes the last pending token
                finishes = n == seq.pending_sched
                entries.append((seq, toks, seq.kv_next, finishes))
            plan = self._desc("prefill", T, entries, (), n_rows=n_rows)
            if self.decode_rides:
                plan.block = decode_plan()
            return plan

        if decode:
            return decode_plan()
        return None

    def mark_dispatched(self, plan: StepPlan) -> None:
        """Advance the SCHEDULED view for every row of a dispatched plan
        (the async pipeline's dispatch-time half; ``commit`` remains the
        readback-time half). Each real row lands one lifecycle event on
        its request timeline (reqtrace): the prefill chunk's token count
        and plan width, or the decode step."""
        if plan.block is not None:
            self.mark_dispatched(plan.block)
        rt = self._reqtrace
        trace = rt.enabled
        T = plan.token_ids.shape[1]
        for s, uid in enumerate(plan.uids):
            if uid < 0:
                continue
            seq = self.state.seqs[uid]
            n = int(plan.active[s].sum())
            self.state.note_written(seq, seq.kv_next, seq.kv_next + n)
            seq.n_sched = seq.kv_next + n
            if plan.do_sample[s]:
                seq.n_inflight += 1
            if trace:
                if plan.kind == "prefill":
                    rt.event(uid, "prefill_chunk", tokens=n, T=T,
                             rows=len(plan.uids))
                else:
                    rt.event(uid, "decode_step", tokens=n)
        plan.dispatched = True

    def commit(self, plan: StepPlan,
               sampled: dict[int, int]) -> dict[int, list[int]]:
        """Advance sequence state after a step ran. ``sampled``: uid → token
        for every slot that had do_sample. Returns uid → tokens actually
        ACCEPTED by each sequence's stop criteria (callers surface these,
        never the raw samples). A plan's ``block`` commits with it (a
        sequence is in one of the two or the other)."""
        st = self.state
        rt = self._reqtrace
        accepted: dict[int, list[int]] = {} if plan.block is None \
            else self.commit(plan.block, sampled)
        for s, uid in enumerate(plan.uids):
            if uid < 0:
                continue
            seq = st.seqs.get(uid)
            if seq is None:         # flushed while the commit was in flight
                continue
            n = int(plan.active[s].sum())
            if plan.dispatched:     # reconcile the speculative view
                if plan.do_sample[s]:
                    seq.n_inflight -= 1
            accepted[uid] = seq.commit_generated(
                [sampled[uid]] if plan.do_sample[s] and uid in sampled
                else [], n)
            if rt.enabled and accepted[uid]:
                rt.event(uid, "commit", tokens=len(accepted[uid]))
        return accepted


class SpecAcceptTracker:
    """Per-tenant accept-rate tracking that adapts speculative draft
    depth (the scheduler-side half of speculative decoding; the verify
    machinery lives in engine_v2 + speculative.py).

    Each uid keeps an EMA of its draft-token acceptance rate. Depth
    shrinks one step when the EMA falls below ``shrink_below`` (a
    low-acceptance tenant pays verify-width compute for tokens that
    mostly reject — at the floor of 1 a verify step degenerates to an
    ordinary decode) and grows back toward ``base_depth`` above
    ``grow_above``. While prefill chunks are PENDING the returned depth
    is additionally capped at ``mixed_cap`` — the decode_window_mixed_cap
    idea: a waiting first chunk (TTFT) must never sit behind a max-depth
    verify round."""

    def __init__(self, base_depth: int, min_depth: int = 1,
                 alpha: float = 0.5, shrink_below: float = 0.35,
                 grow_above: float = 0.75):
        self.base_depth = max(1, base_depth)
        self.min_depth = max(1, min_depth)
        self.alpha = alpha
        self.shrink_below = shrink_below
        self.grow_above = grow_above
        self._rate: dict[int, float] = {}
        self._depth: dict[int, int] = {}

    def rate(self, uid: int) -> float:
        return self._rate.get(uid, 1.0)

    def depth(self, uid: int, prefill_pending: bool = False,
              mixed_cap: int = 0) -> int:
        d = self._depth.get(uid, self.base_depth)
        if prefill_pending and mixed_cap:
            d = min(d, mixed_cap)
        return max(self.min_depth, d)

    def observe(self, uid: int, proposed: int,
                accepted: int) -> tuple[int, int] | None:
        """Record one verify round (``proposed`` candidate tokens,
        ``accepted`` of them matched). Returns ``(old, new)`` when the
        uid's depth adapted, else None (callers note adaptation events to
        the flight recorder). Rounds with nothing proposed (root-only
        trees) carry no acceptance signal and are skipped."""
        if proposed <= 0:
            return None
        r = accepted / proposed
        ema = self._rate.get(uid)
        ema = r if ema is None else self.alpha * r + (1 - self.alpha) * ema
        self._rate[uid] = ema
        old = self._depth.get(uid, self.base_depth)
        new = old
        if ema < self.shrink_below:
            new = max(self.min_depth, old - 1)
        elif ema > self.grow_above:
            new = min(self.base_depth, old + 1)
        if new != old:
            self._depth[uid] = new
            return (old, new)
        self._depth.setdefault(uid, old)
        return None

    def forget(self, uid: int) -> None:
        self._rate.pop(uid, None)
        self._depth.pop(uid, None)
