"""Shared-prefix KV cache (inference/prefix_cache.py): radix index unit
tests, StateManager ownership/refcount integration, a seeded property test
over randomized admit/dispatch/commit/flush/evict/spec interleavings (the
spec op drives speculative provision → accept-or-rollback rounds through
the rollback-aware StateManager API; shrinks to a minimal trace on
failure), and slow-tier engine_v2 warm-path parity
(same prompt twice == cold run, prefill tokens computed drop, eviction
under pressure stays correct)."""
import numpy as np
import pytest

from deepspeed_tpu.inference import PrefixCache, StateManager
from deepspeed_tpu.inference.scheduler import SplitFuseScheduler


# ---------------------------------------------------------------------------
# radix index units (host-only, tier 1)
# ---------------------------------------------------------------------------

def test_match_returns_longest_page_aligned_chain():
    pc = PrefixCache(4)
    toks = list(range(12))
    free = pc.publish(toks, [1, 2, 3], n_shared=0, n_tokens=12)
    assert free == [] and len(pc) == 3
    assert [n.block for n in pc.match(toks)] == [1, 2, 3]
    assert [n.block for n in pc.match(toks[:11])] == [1, 2]   # partial page
    assert [n.block for n in pc.match(toks, max_tokens=9)] == [1, 2]
    assert pc.match([9, 9, 9, 9]) == []
    # divergence mid-chain stops the walk at the shared part
    assert [n.block for n in pc.match(toks[:4] + [99] * 8)] == [1]


def test_publish_dedups_and_returns_partial_tail():
    pc = PrefixCache(4)
    toks = list(range(10))                      # 2 full pages + 2 tokens
    free = pc.publish(toks, [1, 2, 3], n_shared=0, n_tokens=10)
    assert free == [3] and len(pc) == 2         # partial page 3 surrendered
    # an identical chain from another sequence dedups block-by-block
    free = pc.publish(toks, [4, 5, 6], n_shared=0, n_tokens=10)
    assert free == [4, 5, 6] and len(pc) == 2
    assert pc.stats()["deduped_pages"] == 2
    # a diverging second page inserts under the shared first page
    free = pc.publish(toks[:4] + [77, 77, 77, 77], [7, 8], 0, 8)
    assert free == [7] and len(pc) == 3


def test_refcounts_pin_and_evict_is_lru_leaf_first():
    pc = PrefixCache(2)
    pc.publish([1, 2, 3, 4], [1, 2], 0, 4)      # chain 1 -> 2
    pc.publish([1, 2, 9, 9], [3, 4], 0, 4)      # chain 1 -> 4 (3 deduped)
    assert len(pc) == 3
    chain = pc.match([1, 2, 3, 4])
    pc.acquire(chain)
    # the referenced chain (1, 2) is pinned; only leaf 4 may fall
    assert pc.evictable_blocks == 1
    assert pc.evict(10) == [4]
    assert pc.evict(10) == []                   # nothing else evictable
    pc.release(chain)
    # leaf-first: block 2 must fall before its parent 1
    assert pc.evict(1) == [2]
    assert pc.evict(1) == [1]
    assert len(pc) == 0
    with pytest.raises(RuntimeError):
        pc.release(chain)                       # refcount underflow guard


def test_check_catches_corruption():
    pc = PrefixCache(4)
    pc.publish(list(range(8)), [1, 2], 0, 8)
    pc.check()
    node = next(iter(pc.root.children.values()))
    node.refs = -1
    with pytest.raises(AssertionError):
        pc.check()


# ---------------------------------------------------------------------------
# StateManager integration (host-only, tier 1)
# ---------------------------------------------------------------------------

def _state(num_blocks=32, bs=4, max_seqs=4, mb=8):
    st = StateManager(num_blocks=num_blocks, block_size=bs,
                      max_seqs=max_seqs, max_blocks_per_seq=mb)
    st.attach_prefix_cache(PrefixCache(bs))
    return st


def _finish(st, sched, uid, toks=()):
    """Drive a sequence through the scheduler to done (deterministic
    sampled tokens) without touching a device."""
    toks = list(toks) or [7]
    while not st.seqs[uid].done:
        p = sched.next_step()
        assert p is not None, f"uid {uid} stuck (nothing schedulable)"
        sampled = {u: toks[min(st.seqs[u].n_generated, len(toks) - 1)]
                   for _, u in p.sampled_rows()}
        sched.commit(p, sampled)


def test_admit_adopts_cached_chain_and_release_publishes():
    st = _state()
    sched = SplitFuseScheduler(st, chunk=8)
    s1 = st.admit(1, list(range(13)), max_new_tokens=2)
    assert s1.n_shared_blocks == 0 and s1.prefix_hit_tokens == 0
    _finish(st, sched, 1)
    st.release(1)
    st.audit()
    assert len(st.prefix_cache) == 3            # 12 prompt tokens cached

    s2 = st.admit(2, list(range(13)), max_new_tokens=2)
    assert s2.n_shared_blocks == 3
    assert s2.n_computed == 12 and s2.prefix_hit_tokens == 12
    assert s2.blocks[:3] == [n.block
                             for n in st._shared_nodes[2]]
    st.audit()
    # the warm sequence is decode-ready immediately (pending == 1)
    assert s2.pending_tokens == 1
    _finish(st, sched, 2)
    st.release(2)
    st.audit()


def test_last_prompt_token_is_never_served_from_cache():
    """The hit is capped one token short of the prompt: the final token's
    forward produces the first sample's logits, so a fully page-aligned
    prompt still recomputes its last token."""
    st = _state()
    sched = SplitFuseScheduler(st, chunk=8)
    st.admit(1, list(range(16)), max_new_tokens=1)
    _finish(st, sched, 1)
    st.release(1)
    s2 = st.admit(2, list(range(16)), max_new_tokens=1)
    # 16 tokens, bs 4: pages 0..2 cached (12 tokens), NOT page 3 — its
    # last token must run through the model
    assert s2.n_shared_blocks == 3 and s2.pending_tokens == 4


def test_alloc_pressure_evicts_only_unreferenced_pages():
    st = _state(num_blocks=9, bs=4, max_seqs=3, mb=8)   # 8 usable blocks
    sched = SplitFuseScheduler(st, chunk=8)
    st.admit(1, list(range(8)), max_new_tokens=1)       # 3 blocks
    _finish(st, sched, 1)
    st.release(1)                                       # 2 pages cached
    assert st.prefix_cache.cached_blocks == 2
    # a sharer pins the first page of the chain
    s2 = st.admit(2, list(range(8)), max_new_tokens=1)  # 1 shared + 2 fresh
    assert s2.n_shared_blocks == 1
    st.audit()
    # pool: 4 free + 2 owned by seq 2 + 1 referenced + 1 LRU page. The
    # unreferenced page counts as free for admission; the pinned one
    # never does.
    assert st.prefix_cache.evictable_blocks == 1
    assert st.allocator.free_blocks == 4
    assert st.can_admit(20, 0)                          # 5 blocks: uses LRU
    assert not st.can_admit(24, 0)                      # 6: would need pin
    # allocation under pressure reclaims the LRU page, never the pinned one
    st.admit(3, list(range(100, 120)), 0)
    st.audit()
    assert st.prefix_cache.cached_blocks == 1           # pinned survivor
    assert st.prefix_cache.referenced_blocks == 1
    st.release(3), st.release(2)
    st.audit()


def test_admit_rollback_on_pool_exhaustion_releases_pins():
    st = _state(num_blocks=7, bs=4, max_seqs=3, mb=6)    # 6 usable
    sched = SplitFuseScheduler(st, chunk=8)
    st.admit(1, list(range(8)), max_new_tokens=1)
    _finish(st, sched, 1)
    st.release(1)                                        # 2 pages cached
    st.admit(2, list(range(50, 66)), max_new_tokens=4)   # takes 5 blocks,
    st.audit()                                           # evicting the LRU
    assert st.allocator.free_blocks == 0
    assert st.prefix_cache.cached_blocks == 1
    with pytest.raises(RuntimeError):
        # matches the surviving cached page (acquire pins it) but the
        # fresh tail can't be allocated — the match pin must roll back
        st.admit(3, list(range(12)), max_new_tokens=8)
    st.audit()
    assert st.prefix_cache.referenced_blocks == 0
    assert 3 not in st.seqs and st.can_admit(4, 0)


def test_audit_detects_seeded_corruption():
    st = _state()
    sched = SplitFuseScheduler(st, chunk=8)
    st.admit(1, list(range(13)), max_new_tokens=1)
    _finish(st, sched, 1)
    st.release(1)
    st.admit(2, list(range(13)), max_new_tokens=1)
    st.audit()
    # refcount drift
    node = st._shared_nodes[2][0]
    node.refs += 1
    with pytest.raises(AssertionError, match="refcount drift"):
        st.audit()
    node.refs -= 1
    # a leaked block (owned by nobody)
    st.allocator._free.pop()
    with pytest.raises(AssertionError, match="leaked"):
        st.audit()


# ---------------------------------------------------------------------------
# property test: randomized interleavings never free a referenced or
# in-flight page and never serve a stale page (seeded; shrinks on failure)
# ---------------------------------------------------------------------------

_TEMPLATES = [tuple(range(0, 40)), tuple(range(100, 140)),
              tuple(range(0, 20)) + tuple(range(200, 220))]


def _gen_ops(rng, n_ops):
    """Replayable op list; ops no-op gracefully when state doesn't allow
    them, so removing any subset still yields a valid trace (shrinking).
    The trace drives TWO pools: plain ops hit pool A, ``("b", op)``
    wraps one for pool B, and the migrate ops move a decode-ready
    sequence A -> B through the refcounted export/import/abort API
    (``migrate_out`` / ``migrate_in`` / ``abort_migration`` at either
    stage), with the pinned-until-ack contract asserted inline."""
    ops = []
    for _ in range(n_ops):
        r = rng.random()
        if r < 0.28:
            base = _TEMPLATES[int(rng.integers(len(_TEMPLATES)))]
            cut = int(rng.integers(1, len(base) + 1))
            extra = [int(t) for t in
                     rng.integers(300, 310, int(rng.integers(0, 6)))]
            op = ("admit", list(base[:cut]) + extra,
                  int(rng.integers(0, 4)))
        elif r < 0.50:
            op = ("dispatch", "decode" if rng.random() < 0.4 else None)
        elif r < 0.65:
            op = ("commit", int(rng.integers(0, 50)))
        elif r < 0.77:
            op = ("flush", int(rng.integers(0, 8)))
        elif r < 0.84:
            # speculative verify round (rejection-rollback interleavings):
            # provision n candidates on some decode-ready uid, then either
            # accept j of them (j <= n → a mid-tree rejection rolled back
            # by the commit) or roll the whole tree back
            op = ("spec", int(rng.integers(0, 4)),
                  int(rng.integers(1, 4)), int(rng.integers(0, 5)))
        elif r < 0.89:
            # allocation-pressure eviction; with the KV-tier sink
            # attached (kvtier.py) every evicted current-version chain
            # DEMOTES into the shared tier — the demote half of the
            # demote/promote op pair
            op = ("evict", int(rng.integers(1, 5)))
        elif r < 0.935:
            # KV-page migration A -> B: full handoff (export, import,
            # trie seed, ack, release-publish on the source)
            ops.append(("migrate", int(rng.integers(0, 6))))
            continue
        elif r < 0.96:
            # aborted migration: stage 0 = after export (export_abort),
            # stage 1 = after the importer reserved (abort_import too)
            ops.append(("migrate_abort", int(rng.integers(0, 6)),
                        int(rng.integers(0, 2))))
            continue
        elif r < 0.98:
            # placement-time radix pull B <- A: snapshot_prefix pins A's
            # cached chain (audited mid-pin), adopt_prefix inserts it
            # unreferenced into B (dedup'd against B's own trie)
            ops.append(("peer_pull", int(rng.integers(len(_TEMPLATES))),
                        int(rng.integers(1, 11))))
            continue
        else:
            # KV-tier promote: extract the longest tier-resident chain
            # (demoted by earlier evict ops), toy-verify the payloads,
            # and adopt it into either pool through the refcounted
            # adopt_prefix — full audit after, pool-full degrades clean
            if rng.random() < 0.5:
                ops.append(("tier_promote", int(rng.integers(0, 2)),
                            int(rng.integers(len(_TEMPLATES))),
                            int(rng.integers(1, 11))))
            else:
                # two-phase variant (PR-20 promote-ahead): begin plans,
                # finish adopts — or the owner crashes between phases
                ops.append(("tier_promote2", int(rng.integers(0, 2)),
                            int(rng.integers(len(_TEMPLATES))),
                            int(rng.integers(1, 11)),
                            int(rng.integers(0, 2))))
            continue
        if rng.random() < 0.30:
            op = ("b", op)            # same op against the importer pool
        ops.append(op)
    return ops


def _check_no_stale(st):
    """Every live sequence's shared pages must still be the trie nodes for
    ITS token chain — eviction/publish must never leave a block table
    pointing at a page whose content diverged (the stale-serve hazard)."""
    bs = st.block_size
    for uid, seq in st.seqs.items():
        node = st.prefix_cache.root
        for j in range(seq.n_shared_blocks):
            key = tuple(seq.tokens[j * bs:(j + 1) * bs])
            node = node.children.get(key)
            assert node is not None, \
                f"uid {uid} page {j}: chain {key} gone from the trie"
            assert node.block == seq.blocks[j], \
                f"uid {uid} page {j}: table has {seq.blocks[j]}, trie " \
                f"chain holds {node.block} (stale page)"


def _run_trace(ops):
    """Interpret a trace over TWO pools (A = exporter, B = importer);
    returns None or the failure message. Mirrors the engine contract:
    flush commits every outstanding plan referencing the uid (FIFO)
    before release — dispatched-but-uncommitted steps pin their pages by
    keeping their uids live — and migrations drain the uid's in-flight
    plans before ``migrate_out`` (the committed view IS the pool
    content). Both pools run a FULL ``audit()`` + stale-page walk after
    EVERY op, migration stages included."""
    from deepspeed_tpu.inference.kvtier import KVTier, KVTierConfig
    from deepspeed_tpu.inference.migration import toy_prefix_bundle

    # one SHARED host tier behind both pools (the fleet shape): every
    # evict op's reclaimed chains demote into it via the sink, and the
    # tier_promote op adopts them back into either pool
    tier = KVTier(KVTierConfig(ram_bytes=1 << 16))

    def _sink(chains):
        for tokens, _blocks in chains:
            b = toy_prefix_bundle("", tokens, 4)
            if b is not None:
                tier.absorb(b)

    pools = []
    for _ in range(2):
        st = StateManager(num_blocks=24, block_size=4, max_seqs=4,
                          max_blocks_per_seq=8)
        st.attach_prefix_cache(PrefixCache(4))
        st.prefix_cache.evict_sink = _sink
        pools.append({"st": st,
                      "sched": SplitFuseScheduler(st, chunk=8, pack=True),
                      "inflight": []})
    next_uid = [1]

    def commit_oldest(P, tok):
        plan = P["inflight"].pop(0)
        sampled = {u: tok for _, u in plan.sampled_rows()
                   if u in P["st"].seqs}
        P["sched"].commit(plan, sampled)

    def apply(P, op):
        st, sched, inflight = P["st"], P["sched"], P["inflight"]
        kind = op[0]
        if kind == "admit":
            _, toks, gen = op
            if st.can_admit(len(toks), gen):
                st.admit(next_uid[0], toks, gen)
                next_uid[0] += 1
        elif kind == "dispatch":
            plan = sched.next_step(prefer=op[1])
            if plan is not None:
                sched.mark_dispatched(plan)
                inflight.append(plan)
        elif kind == "commit":
            if inflight:
                commit_oldest(P, op[1])
        elif kind == "flush":
            live = sorted(st.seqs)
            if live:
                uid = live[op[1] % len(live)]
                while any(uid in p.all_uids for p in inflight):
                    commit_oldest(P, 0)
                st.release(uid)
        elif kind == "spec":
            # mirrors the engine contract: spec rounds run on a drained
            # pipeline (no in-flight plan references the uid) and are
            # atomic — provision, audit mid-round, then commit or roll
            # back before anything else runs
            _, pick, n, accept = op
            cands = [u for u, s in sorted(st.seqs.items())
                     if not s.done and not s.frozen
                     and s.pending_tokens == 1
                     and s.max_new_tokens - s.n_generated > 1
                     and not any(u in p.all_uids for p in inflight)]
            if cands:
                uid = cands[pick % len(cands)]
                seq = st.seqs[uid]
                k = min(n, seq.max_new_tokens - seq.n_generated - 1)
                if k >= 1:
                    st.provision(uid, k)
                    st.audit()          # the marker itself is audit-clean
                    if accept == 0:
                        st.rollback_provisional(uid)
                    else:
                        j = 1 + (accept - 1) % (k + 1)
                        st.commit_speculative(
                            uid, [700 + i for i in range(j)])
        elif kind == "evict":
            # allocation pressure without a sequence: take blocks through
            # the refcounted API (evicts LRU pages), hand them straight
            # back — pure churn on the eviction path
            n = min(op[1], st.allocator.free_blocks
                    + st.prefix_cache.evictable_blocks)
            if n > 0:
                st.allocator.free(st._alloc(n))

    def migrate(op):
        """A -> B handoff through the refcounted migration API, audited
        at every stage, pinned-until-ack asserted inline. ``op[2]``
        (abort variant) picks the rollback point."""
        A, B = pools
        stA, stB = A["st"], B["st"]
        abort_stage = op[2] if op[0] == "migrate_abort" else None
        cands = [u for u, s in sorted(stA.seqs.items())
                 if not s.done and not s.frozen and s.pending_tokens == 1]
        if not cands:
            return
        uid = cands[op[1] % len(cands)]
        # the engine contract: drain in-flight plans referencing the uid
        while any(uid in p.all_uids for p in A["inflight"]):
            commit_oldest(A, 0)
        seq = stA.seqs.get(uid)
        if seq is None or seq.done or seq.frozen \
                or seq.pending_tokens != 1:
            return                      # the drain finished/changed it
        snap = stA.migrate_out(uid)
        stA.audit()
        # pinned-until-ack: release must refuse, the scheduler must not
        # see the frozen sequence as work
        try:
            stA.release(uid)
            raise AssertionError(
                f"release of pinned export uid {uid} succeeded")
        except RuntimeError:
            pass
        assert stA.seqs[uid].sched_done, "frozen sequence still plans"
        if abort_stage == 0:
            stA.export_abort(uid)
            return
        try:
            nseq = stB.migrate_in_begin(
                next_uid[0], snap["tokens"], snap["n_computed"],
                snap["n_generated"], snap["max_new_tokens"],
                eos_id=snap["eos_id"])
        except RuntimeError:
            stA.export_abort(uid)       # importer full: graceful no-op
            return
        next_uid[0] += 1
        stB.audit()
        if abort_stage is not None:
            stB.abort_import(nseq.uid)
            stB.audit()
            stA.export_abort(uid)
            return
        stB.import_commit(nseq.uid)
        stB.audit()
        stA.export_ack(uid)
        stA.release(uid)                # publishes the prefix locally

    def peer_pull(op):
        """B pulls a cached chain from A through the refcounted pull API
        (the placement-time distributed-cache leg): the export pin is
        audited while held, the adopt is audited after, and a full pool
        on B degrades to a clean no-op (the recompute fallback)."""
        A, B = pools
        stA, stB = A["st"], B["st"]
        _, tmpl, pages = op
        tokens = list(_TEMPLATES[tmpl][:pages * 4])
        snap = stA.snapshot_prefix(tokens)
        if snap is None:
            return
        stA.audit()                     # pinned-chain refcounts balance
        try:
            stB.adopt_prefix(tokens, snap["n_tokens"])
            stB.audit()
        except RuntimeError:
            pass                        # importer pool full: recompute
        finally:
            stA.release_prefix(snap["handle"])
        stA.audit()

    def tier_promote(op):
        """The promote half of the KV-tier op pair: extract the longest
        tier-resident chain for a template prompt (the demote ops'
        output), verify the toy payload oracle, and adopt it into the
        chosen pool through the refcounted pull surface — audited after;
        a full pool degrades to a clean no-op (recompute fallback)."""
        from deepspeed_tpu.inference.migration import toy_verify
        from deepspeed_tpu.inference.prefix_cache import chain_hashes

        _, pick, tmpl, pages = op
        st = pools[pick % 2]["st"]
        tokens = list(_TEMPLATES[tmpl][:pages * 4])
        aligned = tokens[:(len(tokens) // 4) * 4]
        if not aligned:
            return
        deep = tier.probe(chain_hashes(aligned, 4))
        if deep == 0:
            return
        bundle = tier.extract(aligned[:deep * 4], 4)
        if bundle is None:
            return
        toy_verify(bundle)              # payload integrity through the tier
        try:
            st.adopt_prefix(bundle.tokens, bundle.n_computed)
            st.audit()
        except RuntimeError:
            pass                        # pool full: recompute fallback

    def tier_promote2(op):
        """Two-phase promote (PR-20 promote-ahead pipelining):
        ``extract_begin`` plans against current residency without
        mutating anything — a crash before ``extract_finish`` must
        leave the tier byte-identical (recompute owes it nothing) —
        and a finished handle adopts exactly like the one-shot op."""
        from deepspeed_tpu.inference.migration import toy_verify
        from deepspeed_tpu.inference.prefix_cache import chain_hashes

        _, pick, tmpl, pages, crash = op
        st = pools[pick % 2]["st"]
        tokens = list(_TEMPLATES[tmpl][:pages * 4])
        aligned = tokens[:(len(tokens) // 4) * 4]
        if not aligned:
            return
        deep = tier.probe(chain_hashes(aligned, 4))
        if deep == 0:
            return
        before = tier.stats()
        handle = tier.extract_begin(aligned[:deep * 4], 4)
        if crash or handle is None:
            # owner died between the phases: the pure plan left no
            # trace — residency and counters byte-identical
            after = tier.stats()
            for k in ("ram_pages", "nvme_pages", "promotes",
                      "promoted_pages", "demoted_pages"):
                assert after[k] == before[k], \
                    f"extract_begin mutated {k}: {before[k]} -> {after[k]}"
            return
        bundle = tier.extract_finish(handle)
        if bundle is None:
            return                      # residency shrank: recompute
        toy_verify(bundle)              # payload integrity through the tier
        try:
            st.adopt_prefix(bundle.tokens, bundle.n_computed)
            st.audit()
        except RuntimeError:
            pass                        # pool full: recompute fallback

    for i, op in enumerate(ops):
        try:
            if op[0] == "b":
                apply(pools[1], op[1])
            elif op[0] == "peer_pull":
                peer_pull(op)
            elif op[0] == "tier_promote":
                tier_promote(op)
            elif op[0] == "tier_promote2":
                tier_promote2(op)
            elif op[0] in ("migrate", "migrate_abort"):
                migrate(op)
            else:
                apply(pools[0], op)
            for P in pools:
                P["st"].audit()
                _check_no_stale(P["st"])
        except AssertionError as e:
            return f"op {i} {op!r}: {e}"
    # drain + release everything; BOTH pools must reconcile exactly
    try:
        for P in pools:
            while P["inflight"]:
                commit_oldest(P, 0)
            for uid in sorted(P["st"].seqs):
                P["st"].release(uid)
            P["st"].audit()
            assert P["st"].allocator.free_blocks \
                + P["st"].prefix_cache.cached_blocks \
                == P["st"].allocator.num_blocks - 1, \
                "pool failed to reconcile"
            _check_no_stale(P["st"])
    except AssertionError as e:
        return f"final drain: {e}"
    return None


def _shrink(ops, run=None):
    """Greedy delta-debug: drop ops while the trace still fails."""
    run = run or _run_trace
    changed = True
    while changed:
        changed = False
        i = 0
        while i < len(ops):
            cand = ops[:i] + ops[i + 1:]
            if cand and run(cand) is not None:
                ops = cand
                changed = True
            else:
                i += 1
    return ops


def _property(n_traces, ops_per_trace=60, seed0=0):
    for i in range(n_traces):
        seed = seed0 + i
        ops = _gen_ops(np.random.default_rng(seed), ops_per_trace)
        err = _run_trace(ops)
        if err is not None:
            minimal = _shrink(list(ops))
            trace = "\n".join(f"  {op!r}" for op in minimal)
            pytest.fail(
                f"seed {seed}: {err}\nminimal failing trace "
                f"({len(minimal)} ops, replay with _run_trace):\n{trace}")


def test_interleaving_property_fast():
    """Tier-1 smoke: 80 random interleavings, audited after every op."""
    _property(80)


@pytest.mark.slow
def test_interleaving_property_500_plus():
    """The acceptance-criteria run: 600 seeded interleavings x 90 ops of
    admit/dispatch/commit/flush/evict(=tier demote)/spec/migrate/
    peer_pull/tier_promote over TWO
    pools
    (speculative provision → accept-or-rollback rounds, mid-tree
    rejections included; migrate_out/migrate_in/abort_migration at both
    rollback stages, pinned-until-ack asserted inline); every op is
    followed by a full-pool ownership audit and a stale-page walk on
    BOTH pools, dispatched-but-uncommitted plans pin their pages (flush
    drains FIFO first, migrate_out drains its uid first), and each trace
    must reconcile both pools exactly at the end — no leaked or
    double-owned block anywhere."""
    _property(600, ops_per_trace=90, seed0=10_000)


def test_shrinker_finds_minimal_trace():
    """The shrinker itself: seed a genuine invariant break (an op that
    frees a trie-owned block behind the manager's back) and check the
    reported minimal trace collapses to the poisoned op."""
    poison = ("_poison_free_cached_block",)

    def run_with_poison(ops):
        clean = [op for op in ops if op[0] != "_poison_free_cached_block"]
        has_poison = len(clean) != len(ops)
        if not has_poison:
            return _run_trace(clean)
        # replay: publish a page, then double-own it
        st = StateManager(num_blocks=8, block_size=4, max_seqs=2,
                          max_blocks_per_seq=4)
        st.attach_prefix_cache(PrefixCache(4))
        sched = SplitFuseScheduler(st, chunk=8)
        st.admit(1, list(range(8)), 1)
        _finish(st, sched, 1, toks=[3])
        st.release(1)
        blk = next(iter(st.prefix_cache.blocks()))
        st.allocator.free([blk])                 # the bug under test
        try:
            st.audit()
        except AssertionError as e:
            return f"poison: {e}"
        return "poison: audit MISSED the double-own"

    ops = _gen_ops(np.random.default_rng(3), 20) + [poison] \
        + _gen_ops(np.random.default_rng(4), 20)
    err = run_with_poison(ops)
    assert err is not None and "free list AND trie" in err

    # shrink against the poisoned runner: only the poison op survives
    minimal = _shrink(list(ops), run=run_with_poison)
    assert minimal == [poison]


# ---------------------------------------------------------------------------
# engine_v2 warm-path parity (slow tier: engine jit compiles)
# ---------------------------------------------------------------------------

def _build_engine(**over):
    import jax

    from deepspeed_tpu.inference import InferenceEngineV2
    from deepspeed_tpu.models import build_model
    from deepspeed_tpu.parallel.topology import MeshTopology

    model = build_model("tiny-gpt2", hidden_size=256, num_heads=4)
    cfg = {"block_size": 8, "num_blocks": 64, "max_seqs": 4, "chunk": 8,
           "max_seq_len": 128, "prefix_cache": True, **over}
    return InferenceEngineV2(model, config=cfg, rng=jax.random.PRNGKey(5),
                             topology=MeshTopology({"tensor": 1, "data": 1}))


@pytest.mark.slow
@pytest.mark.parametrize("quant", [None, 8])
def test_v2_warm_path_token_identical_and_prefill_drop(quant):
    """Acceptance criterion: serving the same prompt twice with
    prefix_cache=True yields token-identical output to a cold run (bf16
    and int8 weights), stats shows prefix_hit_tokens > 0, and prefill
    tokens computed on the warm run drop >= 80% for a fully-shared
    prompt."""
    eng = _build_engine(quant_bits=quant)
    off = _build_engine(quant_bits=quant, prefix_cache=False)
    assert eng._prefix_cache is not None and off._prefix_cache is None
    off.params = eng.params

    rng = np.random.default_rng(7)
    # len % block_size == 1: everything but the final token is cacheable
    prompt = list(map(int, rng.integers(0, 256, (33,))))

    cold_pf0 = eng.stats["prefill_tokens"]
    cold = eng.generate([prompt], max_new_tokens=6)[0]
    cold_pf = eng.stats["prefill_tokens"] - cold_pf0
    assert eng.stats["prefix_hit_tokens"] == 0       # nothing cached yet

    ref = off.generate([prompt], max_new_tokens=6)[0]
    assert cold == ref                               # cache off == cache on

    warm_pf0 = eng.stats["prefill_tokens"]
    warm = eng.generate([prompt], max_new_tokens=6)[0]
    warm_pf = eng.stats["prefill_tokens"] - warm_pf0
    assert warm == cold                              # token-identical
    assert eng.stats["prefix_hit_tokens"] >= 32
    assert eng.stats["prefix_hit_rate"] > 0
    assert warm_pf <= 0.2 * cold_pf, (warm_pf, cold_pf)
    eng.state.audit()


@pytest.mark.slow
def test_v2_shared_system_prompt_across_requests():
    """Distinct requests sharing a system prefix: later requests hit the
    published pages and still generate exactly what a cache-off engine
    generates."""
    eng = _build_engine()
    off = _build_engine(prefix_cache=False)
    off.params = eng.params
    rng = np.random.default_rng(11)
    system = list(map(int, rng.integers(0, 256, (24,))))
    prompts = [system + list(map(int, rng.integers(0, 256, (n,))))
               for n in (5, 9, 3)]
    # sequential so each flush publishes before the next admit matches
    outs, refs = [], []
    for uid, p in enumerate(prompts):
        eng.put(uid, p, max_new_tokens=5)
        while not eng.query(uid).get("done", False):
            eng.step()
        outs.append(eng.flush(uid))
        eng.state.audit()
    for uid, p in enumerate(prompts):
        off.put(uid, p, max_new_tokens=5)
        while not off.query(uid).get("done", False):
            off.step()
        refs.append(off.flush(uid))
    assert outs == refs
    assert eng.stats["prefix_hit_tokens"] >= 2 * 24 - 16  # requests 2, 3
    pcs = eng.prefix_cache_stats()
    assert pcs["inserted_pages"] > 0


@pytest.mark.slow
def test_v2_eviction_pressure_stays_correct():
    """A pool too small to cache every served prompt: the LRU evicts under
    allocation pressure, admission control counts evictable pages as
    free, and every generation still matches the cache-off engine."""
    eng = _build_engine(num_blocks=14, max_seqs=2)
    off = _build_engine(num_blocks=14, max_seqs=2, prefix_cache=False)
    off.params = eng.params
    rng = np.random.default_rng(13)
    prompts = [list(map(int, rng.integers(0, 256, (int(n),))))
               for n in rng.integers(10, 40, 6)]
    for uid, p in enumerate(prompts):
        for e in (eng, off):
            e.put(uid, p, max_new_tokens=4)
            while not e.query(uid).get("done", False):
                e.step()
        got, ref = eng.flush(uid), off.flush(uid)
        assert got == ref, (uid, got, ref)
        eng.state.audit()
    assert eng.prefix_cache_stats()["evicted_pages"] > 0


@pytest.mark.slow
def test_v2_flush_mid_prefill_keeps_trie_consistent():
    """Releasing a sequence whose prompt is only partially computed (the
    serving-side rewind shape) publishes only full computed pages; the
    pool audits clean and later requests serve normally."""
    eng = _build_engine()
    rng = np.random.default_rng(17)
    # longer than the largest single-row chunk (the chain tops out at
    # chunk * max_seqs = 32), so one step CANNOT finish the prefill
    prompt = list(map(int, rng.integers(0, 256, (40,))))
    eng.put(1, prompt, max_new_tokens=4)
    eng.step()                       # first chunk dispatched (in flight)
    assert eng.state.seqs[1].n_sched < len(prompt)   # genuinely mid-prefill
    got = eng.flush(1)               # drains, releases mid-prefill
    assert got == []
    eng.state.audit()
    # the engine keeps serving; the partially-published prefix may be hit
    eng.put(2, prompt, max_new_tokens=4)
    while not eng.query(2).get("done", False):
        eng.step()
    assert len(eng.flush(2)) == 4
    eng.state.audit()


@pytest.mark.slow
def test_v2_prefix_cache_config_gates():
    """None = auto: on for pack-mode linear serving (fp8-KV pages
    included — published pages serve bit-for-bit, parity pinned by
    test_v2_fp8_kv_prefix_cache_cross_request_parity), off in
    rolling-window ring mode; True refuses ring mode."""
    import jax

    from deepspeed_tpu.inference import InferenceEngineV2
    from deepspeed_tpu.models import build_model
    from deepspeed_tpu.parallel.topology import MeshTopology

    topo = MeshTopology({"tensor": 1, "data": 1})
    base = {"block_size": 8, "num_blocks": 64, "max_seqs": 2, "chunk": 8,
            "max_seq_len": 128}
    model = build_model("tiny-gpt2", hidden_size=256, num_heads=4)
    rng = jax.random.PRNGKey(3)

    auto = InferenceEngineV2(model, config=base, rng=rng, topology=topo)
    assert auto._prefix_cache is not None        # pack-mode default: on

    fp8 = InferenceEngineV2(model, config={**base, "kv_cache_dtype": "fp8"},
                            rng=rng, topology=topo)
    assert fp8._prefix_cache is not None         # parity proven: auto-on

    nopack = InferenceEngineV2(model, config={**base, "prefill_pack": False},
                               rng=rng, topology=topo)
    assert nopack._prefix_cache is None          # auto follows pack mode
    forced = InferenceEngineV2(
        model, config={**base, "prefill_pack": False, "prefix_cache": True},
        rng=rng, topology=topo)
    assert forced._prefix_cache is not None      # explicit True wins

    windowed = build_model("tiny-gpt2", hidden_size=256, num_heads=4,
                           sliding_window=24)
    ring = InferenceEngineV2(windowed, config=base, rng=rng, topology=topo)
    assert ring._kinds[0].ring_tokens and ring._prefix_cache is None
    with pytest.raises(ValueError, match="rolling"):
        InferenceEngineV2(windowed, config={**base, "prefix_cache": True},
                          rng=rng, topology=topo)
