"""KV tiering: HBM → host RAM → NVMe under the fleet radix
(inference/kvtier.py + the serving-side wiring).

Four layers under test:

- **ring/spill units**: the bounded host-RAM ring (oldest-out, deepest
  pages spill first so residency stays contiguous-from-root), the
  segmented NVMe spill (crc'd records, rotation, total-byte cap), and
  the tier-open torn-spill gate — a truncated tail or a mid-file torn
  record (crash mid-demote) is counted and skipped, never fatal, never
  served.
- **demote → promote roundtrip**: prefix-cache eviction with the sink
  attached serializes chains through the kind="prefix" PageBundle path
  into the tier; extract rebuilds them bit-identically (toy payload
  oracle + byte equality), version skew after a weight swap refuses the
  chain, and a capacity-bounded ring degrades to shorter promotes.
- **pool integration**: eviction-under-pressure demotes through
  StateManager's refcounted paths and a later adopt_prefix promotes —
  full audit() after every step; the engine runs the same cycle on a
  real pool (device gather at demote, scatter at promote) with the warm
  stream bit-identical to cold.
- **serving tier (multiprocess)**: a placement miss on a tier-warm toy
  replica promotes instead of recomputing (streams bit-identical to the
  LCG oracle, promote counters in the telemetry snapshot), tier
  residency rides the heartbeat digest into placement, and every
  injected tier failure — torn spill, crash mid-demote — degrades to
  recompute with 0 double-commits.
"""
import json
import os
import time
import types

import pytest

from deepspeed_tpu.inference.kvtier import (GUESS_NVME_BYTES_S,
                                            GUESS_RAM_BYTES_S, HostRing,
                                            KVTier, KVTierConfig,
                                            NVMeSpill, measure_tier_rates)
from deepspeed_tpu.inference.migration import (toy_page_payload,
                                               toy_prefix_bundle,
                                               toy_verify)
from deepspeed_tpu.inference.prefix_cache import PrefixCache, chain_hashes
from deepspeed_tpu.runtime.resilience import FaultInjector
from tests.test_disagg import toy_stream

BS = 16
VOCAB = 1024


def _bundle(tokens, wv=None):
    return toy_prefix_bundle("", list(tokens), BS, weight_version=wv)


# ---------------------------------------------------------------------------
# ring / spill units (host-only, tier 1)
# ---------------------------------------------------------------------------

def test_host_ring_bounds_bytes_oldest_out():
    ring = HostRing(100)
    spilled = ring.put(1, {}, b"a" * 48)
    assert spilled == [] and ring.bytes == 48
    spilled = ring.put(2, {}, b"b" * 48)
    assert spilled == [] and len(ring) == 2
    spilled = ring.put(3, {}, b"c" * 48)     # over budget: oldest out
    assert [h for h, _, _ in spilled] == [1]
    assert 1 not in ring and 2 in ring and 3 in ring
    # replacement never double-counts bytes
    ring.put(3, {}, b"d" * 48)
    assert ring.bytes == 96
    # get() refreshes recency
    assert ring.get(2) is not None
    spilled = ring.put(4, {}, b"e" * 48)
    assert [h for h, _, _ in spilled] == [3]     # 2 was refreshed


def test_spill_roundtrip_rotation_and_total_cap(tmp_path):
    sp = NVMeSpill(str(tmp_path), cap_bytes=4096, segment_bytes=256)
    for i in range(20):
        sp.append(i, {"pb": 48}, bytes([i]) * 48)
    # rotation happened (small segments), every surviving record reads
    # back crc-clean
    assert len(sp._segments()) > 1
    for h in list(sp.keys()):
        meta, payload = sp.read(h)
        assert payload == bytes([h]) * 48 and meta["pb"] == 48
    # cap: push far past it — oldest segments (and their records) drop
    for i in range(100, 160):
        sp.append(i, {}, bytes([i % 251]) * 48)
    assert sp.bytes <= 4096 + 256          # bounded (cap + one segment)
    assert sp.evicted_pages > 0
    assert sp.read(0) is None or 0 in sp   # early records may be gone
    sp.close()


def test_spill_torn_tail_and_midfile_detected_on_open(tmp_path):
    sp = NVMeSpill(str(tmp_path), cap_bytes=1 << 20,
                   segment_bytes=1 << 20)
    for i in range(4):
        sp.append(i, {}, bytes([i]) * 48)
    # a torn record mid-file (the tier_torn_spill shape: half the bytes,
    # never indexed) followed by a GOOD record — the scan must skip the
    # tear and resync to the survivor
    sp.append(99, {}, b"T" * 48, tear=True)
    sp.append(5, {}, bytes([5]) * 48)
    sp.close()
    re1 = NVMeSpill(str(tmp_path), cap_bytes=1 << 20,
                    segment_bytes=1 << 20)
    assert re1.torn_skipped >= 1
    assert 99 not in re1                      # torn: never served
    for i in (0, 1, 2, 3, 5):
        assert re1.read(i)[1] == bytes([i]) * 48
    re1.close()
    # truncated TAIL (crash mid-append): length gate catches it
    seg = sorted(f for f in os.listdir(tmp_path) if f.endswith(".seg"))[-1]
    path = os.path.join(tmp_path, seg)
    with open(path, "r+b") as f:
        f.truncate(os.path.getsize(path) - 7)
    re2 = NVMeSpill(str(tmp_path), cap_bytes=1 << 20,
                    segment_bytes=1 << 20)
    assert re2.torn_skipped >= re1.torn_skipped
    assert len(re2) < 6                       # the torn tail record fell
    re2.close()
    # corrupt payload bytes in place: the read-side crc gate drops it
    sp3 = NVMeSpill(str(tmp_path), cap_bytes=1 << 20,
                    segment_bytes=1 << 20)
    victim = next(iter(sp3.keys()))
    seg_id, off, _, plen, _ = sp3._idx[victim]
    with open(sp3._seg_path(seg_id), "r+b") as f:
        f.seek(off)
        f.write(b"\xff" * plen)
    assert sp3.read(victim) is None
    assert victim not in sp3                  # dropped, counted
    sp3.close()


# ---------------------------------------------------------------------------
# tier semantics (host-only, tier 1)
# ---------------------------------------------------------------------------

def test_tier_demote_promote_roundtrip_bit_identity(tmp_path):
    t = KVTier(KVTierConfig(ram_bytes=1 << 20, nvme_dir=str(tmp_path)))
    b = _bundle(range(4 * BS))
    assert t.absorb(b) == 4
    assert t.absorb(b) == 0                   # dedup: already resident
    assert t.probe(b.chain) == 4
    out = t.extract(list(range(4 * BS)) + [7, 8], BS)
    assert out is not None and out.n_full == 4
    toy_verify(out)                           # payload integrity oracle
    assert out.pages == b.pages               # bit-identical through tiers
    assert out.chain == b.chain
    t.close()


def test_tier_ram_overflow_spills_deep_end_first(tmp_path):
    # ring fits 2 of 4 pages: the DEEPEST pages spill, so RAM keeps the
    # root-contiguous prefix and the full chain stays promotable
    t = KVTier(KVTierConfig(ram_bytes=100, nvme_dir=str(tmp_path)))
    b = _bundle(range(4 * BS))
    t.absorb(b)
    assert len(t.ring) == 2 and len(t.spill) == 2
    assert b.chain[0] in t.ring and b.chain[1] in t.ring
    assert b.chain[2] in t.spill and b.chain[3] in t.spill
    assert t.probe(b.chain) == 4
    out = t.extract(list(range(4 * BS)), BS)
    assert out.n_full == 4 and out.pages == b.pages
    st = t.stats()
    assert st["ram_pages"] + st["nvme_pages"] >= 4
    t.close()


def test_tier_capacity_bounded_wraparound_without_spill():
    # RAM-only tier: overflow DROPS (counted); a later promote serves
    # the surviving root-contiguous prefix, shorter but valid
    t = KVTier(KVTierConfig(ram_bytes=100, nvme_dir=None))
    b = _bundle(range(4 * BS))
    t.absorb(b)
    assert t.stats()["dropped_pages"] == 2
    assert t.probe(b.chain) == 2
    out = t.extract(list(range(4 * BS)), BS)
    assert out is not None and out.n_full == 2
    toy_verify(out)
    # a second chain churns the ring; the tier never exceeds its budget
    t.absorb(_bundle(range(500, 500 + 4 * BS)))
    assert t.ring.bytes <= 100


def test_tier_version_skew_refused_after_weight_swap(tmp_path):
    t = KVTier(KVTierConfig(ram_bytes=1 << 20, nvme_dir=str(tmp_path)))
    t.absorb(_bundle(range(3 * BS), wv={"id": 1, "digest": "aa"}))
    chain = chain_hashes(list(range(3 * BS)), BS)
    t.set_weight_version({"id": 1, "digest": "aa"})
    assert t.probe(chain) == 3                # same version: serves
    t.set_weight_version({"id": 2, "digest": "bb"})
    assert t.probe(chain) == 0                # post-swap: invisible
    assert t.extract(list(range(3 * BS)), BS) is None
    assert len(t.ring) == 0                   # ring dropped them eagerly
    t.close()


def test_tier_nvme_promote_rehydrates_ram_ring(tmp_path):
    t = KVTier(KVTierConfig(ram_bytes=200, nvme_dir=str(tmp_path)))
    t.absorb(_bundle(range(4 * BS)))
    t.absorb(_bundle(range(700, 700 + 4 * BS)))   # pushes chain 1 to NVMe
    chain1 = chain_hashes(list(range(4 * BS)), BS)
    assert any(h in t.spill for h in chain1)
    before = len(t.ring._m)
    out = t.extract(list(range(4 * BS)), BS)
    assert out.n_full == 4
    # promoted records are hot again: they re-entered the RAM ring
    assert all(h in t.ring for h in chain1[:2])
    assert len(t.ring._m) <= max(before, 5)       # still bounded
    t.close()


def test_probe_and_extract_keep_root_newest_in_ring():
    """Review regression: a root-first probe/extract walk must not make
    the ROOT the chain's LRU-oldest record — eviction has to keep
    trimming from the DEEP end or promoted chains lose their root and
    become phantom residency."""
    t = KVTier(KVTierConfig(ram_bytes=4 * 48, nvme_dir=None))
    b = _bundle(range(4 * BS))
    t.absorb(b)
    t.probe(b.chain)                      # recency-neutral
    out = t.extract(list(range(4 * BS)), BS)
    assert out is not None and out.n_full == 4   # touches deepest-first
    # a second chain overflows the ring: the first chain's DEEP pages
    # must fall before its root
    t.absorb(_bundle(range(700, 700 + 2 * BS)))
    assert b.chain[0] in t.ring           # root survives
    assert b.chain[3] not in t.ring       # deepest fell first
    assert t.probe(b.chain) >= 1          # still promotable from root


def test_version_bumps_when_records_are_lost(tmp_path):
    """Review regression: ANY record loss must bump the tier version so
    the heartbeat re-ships the shrunk digest — a stale digest would
    advertise phantom residency the router plans around."""
    t = KVTier(KVTierConfig(ram_bytes=100, nvme_dir=None))
    v0 = t.version
    t.absorb(_bundle(range(4 * BS)))      # overflow DROPS 2 pages
    assert t.stats()["dropped_pages"] == 2 and t.version > v0
    # spill-only invalidation after a swap (the flushed-then-reopened
    # shape: everything lives in the spill, the ring is empty)
    cfg = KVTierConfig(ram_bytes=1 << 20, nvme_dir=str(tmp_path))
    t2 = KVTier(cfg)
    t2.absorb(_bundle(range(3 * BS), wv={"id": 1, "digest": "a"}))
    t2.close(flush=True)
    re = KVTier(cfg)
    assert len(re.ring) == 0 and len(re.spill) == 3
    v = re.version
    re.set_weight_version({"id": 2, "digest": "b"})
    assert re.version > v                 # spill-side pops bump too
    assert re.residency_digest() == []
    re.close()


def test_extract_from_nvme_moves_record_not_copies(tmp_path):
    """Review regression: an NVMe promote MOVES the index entry into the
    RAM ring (the old on-disk bytes go dead until rotation) — hot
    records cycling RAM↔NVMe must never hold duplicate index entries."""
    t = KVTier(KVTierConfig(ram_bytes=100, nvme_dir=str(tmp_path)))
    b = _bundle(range(4 * BS))
    t.absorb(b)
    assert b.chain[2] in t.spill and b.chain[3] in t.spill
    # hot churn: promote (NVMe records move up, colder ones respill)
    for _ in range(3):
        out = t.extract(list(range(4 * BS)), BS)
        assert out is not None and out.n_full == 4
        toy_verify(out)
        # every hash lives in EXACTLY one tier — never both
        for h in b.chain:
            assert (h in t.ring) != (h in t.spill), h
    t.close()


def test_tier_close_flush_reopens_warm(tmp_path):
    cfg = KVTierConfig(ram_bytes=1 << 20, nvme_dir=str(tmp_path))
    t = KVTier(cfg)
    b = _bundle(range(4 * BS))
    t.absorb(b)
    t.close(flush=True)                       # graceful: RAM spills
    re = KVTier(cfg)
    assert re.probe(b.chain) == 4
    out = re.extract(list(range(4 * BS)), BS)
    assert out.pages == b.pages
    re.close()


def test_prefetch_stages_nvme_records_into_ram(tmp_path):
    """Promote-ahead (PR 16): prefetch MOVES the chain's NVMe records
    up into the RAM ring — single-copy, recency root-newest — so the
    later extract pays zero spill reads."""
    cfg = KVTierConfig(ram_bytes=1 << 20, nvme_dir=str(tmp_path))
    t = KVTier(cfg)
    b = _bundle(range(8 * BS))
    t.absorb(b)
    t.close(flush=True)                      # everything on NVMe
    t = KVTier(cfg)
    assert len(t.ring) == 0
    assert t.prefetch(b.chain) == 8
    assert t.stats()["promote_ahead_pages"] == 8
    for h in b.chain:                        # moved, never copied
        assert h in t.ring and h not in t.spill
    # recency: the ROOT ends newest (deep pages must evict first)
    reads = []
    orig = t.spill.read
    t.spill.read = lambda h: reads.append(h) or orig(h)
    out = t.extract(list(range(8 * BS)), BS)
    assert out is not None and out.n_full == 8
    toy_verify(out)
    assert out.pages == b.pages
    assert reads == []                       # extract stayed in RAM
    # a second prefetch of a now-hot chain stages nothing new
    assert t.prefetch(b.chain) == 0
    assert t.stats()["promote_ahead_pages"] == 8
    t.close()


def test_prefetch_latency_delta_vs_cold_nvme_extract(tmp_path):
    """The satellite's point: an extract after promote-ahead is
    strictly faster than one paying per-page NVMe reads (min-of-3 on
    both sides to keep the CPU-box comparison honest)."""
    chain_toks = list(range(64 * BS))
    b = _bundle(chain_toks)

    def spill_only_tier(sub):
        cfg = KVTierConfig(ram_bytes=8 << 20,
                           nvme_dir=str(tmp_path / sub))
        t = KVTier(cfg)
        t.absorb(b)
        t.close(flush=True)
        return KVTier(cfg)

    cold = []
    for i in range(3):                       # fresh tier: all 64 on NVMe
        t = spill_only_tier(f"cold{i}")
        t0 = time.perf_counter()
        out = t.extract(chain_toks, BS)
        cold.append(time.perf_counter() - t0)
        assert out is not None and out.n_full == 64
        t.close()
    t = spill_only_tier("warm")
    assert t.prefetch(b.chain) == 64
    warm = []
    for _ in range(3):
        t0 = time.perf_counter()
        out = t.extract(chain_toks, BS)
        warm.append(time.perf_counter() - t0)
        assert out is not None and out.n_full == 64
    t.close()
    assert min(warm) < min(cold), (warm, cold)


def test_prefetch_respects_version_skew_and_gaps(tmp_path):
    cfg = KVTierConfig(ram_bytes=1 << 20, nvme_dir=str(tmp_path))
    t = KVTier(cfg)
    b = _bundle(range(4 * BS), wv={"id": 1, "digest": "a"})
    t.absorb(b)
    t.close(flush=True)
    t = KVTier(cfg)
    t.set_weight_version({"id": 2, "digest": "b"})
    assert t.prefetch(b.chain) == 0          # stale records never stage
    t.close()
    # RAM-only tier: nothing below to stage from
    t2 = KVTier(KVTierConfig(ram_bytes=1 << 20, nvme_dir=None))
    t2.absorb(_bundle(range(2 * BS)))
    assert t2.prefetch(chain_hashes(list(range(2 * BS)), BS)) == 0
    # an unknown chain is a clean miss
    assert t2.prefetch(chain_hashes(list(range(500, 500 + 2 * BS)),
                                    BS)) == 0


def test_sync_tier_metrics_emits_promote_ahead_counter(tmp_path):
    from deepspeed_tpu.serving.replica import _sync_tier_metrics
    from deepspeed_tpu.telemetry import Telemetry

    cfg = KVTierConfig(ram_bytes=1 << 20, nvme_dir=str(tmp_path))
    t = KVTier(cfg)
    b = _bundle(range(4 * BS))
    t.absorb(b)
    t.close(flush=True)
    t = KVTier(cfg)
    t.prefetch(b.chain)
    backend = types.SimpleNamespace(kv_tier=t)
    telem, marks = Telemetry(enabled=True), {}
    _sync_tier_metrics(telem, backend, marks)
    snap = telem.snapshot()
    fam = snap["serving_kv_tier_promote_ahead_total"]["series"]
    assert sum(s["value"] for s in fam) == 4
    # delta pattern: a second sync with no new stages adds nothing
    _sync_tier_metrics(telem, backend, marks)
    snap = telem.snapshot()
    fam = snap["serving_kv_tier_promote_ahead_total"]["series"]
    assert sum(s["value"] for s in fam) == 4
    t.close()


def test_fault_injection_torn_spill_detected_on_reopen(tmp_path):
    cfg = KVTierConfig(ram_bytes=64, nvme_dir=str(tmp_path))
    inj = FaultInjector(spec={"tier_torn_spill": 1}, env="", hard=False)
    t = KVTier(cfg, inj=inj)
    b = _bundle(range(4 * BS))
    t.absorb(b)
    # the first (deepest) page's record was written TORN and never
    # indexed: the chain's surviving prefix still promotes
    assert t.probe(b.chain) < 4
    out = t.extract(list(range(4 * BS)), BS)
    assert out is None or out.n_full < 4
    if out is not None:
        toy_verify(out)                       # what survives is clean
    t.close(flush=True)
    re = KVTier(cfg)
    assert re.spill.torn_skipped >= 1         # the open-time gate saw it
    assert re.probe(b.chain) < 4
    re.close()


def test_fault_injection_crash_mid_demote_is_hard():
    inj = FaultInjector(spec={"tier_crash_mid_demote": 1}, env="",
                        hard=False)           # soft here: catchable
    t = KVTier(KVTierConfig(ram_bytes=1 << 20), inj=inj)
    from deepspeed_tpu.runtime.resilience import InjectedFault
    with pytest.raises(InjectedFault):
        t.absorb(_bundle(range(2 * BS)))


def test_measure_tier_rates_probes_and_guesses(tmp_path):
    r = measure_tier_rates(str(tmp_path), size_bytes=1 << 20)
    assert r["ram_bytes_s"] > 0 and r["nvme_bytes_s"] > 0
    assert r["probed"] is True
    # an unwritable dir falls back to the guessed NVMe constant
    r2 = measure_tier_rates("/proc/definitely/not/writable",
                            size_bytes=1 << 20)
    assert r2["nvme_bytes_s"] == GUESS_NVME_BYTES_S
    assert r2["ram_bytes_s"] > 0
    r3 = measure_tier_rates(None, size_bytes=1 << 20)
    assert r3["nvme_bytes_s"] == GUESS_NVME_BYTES_S
    assert GUESS_RAM_BYTES_S > GUESS_NVME_BYTES_S


def test_plan_kv_source_three_way_decision():
    from deepspeed_tpu.serving import plan_kv_source
    kw = dict(page_bytes=48, block_size=16, prefill_tok_s=2000.0,
              pull_bytes_s=64e6, tier_bytes_s=1.2e9, overhead_s=0.0)
    # nothing covers the chain: recompute
    assert plan_kv_source(8, 0, 0, 0, **kw) == "recompute"
    # only a peer holds it, transfer beats prefill: pull
    assert plan_kv_source(8, 0, 8, 0, **kw) == "pull"
    # the local tier holds the same depth: promote beats shipping
    assert plan_kv_source(8, 0, 8, 8, **kw) == "tier"
    # tier shallower than the peer but still competitive on rate: the
    # deeper pull only wins when its extra coverage pays for the slower
    # transport — with tiny pages it does
    assert plan_kv_source(8, 0, 8, 2, **kw) == "pull"
    # a slow relay vs a fast prefill: recompute beats both
    slow = dict(kw, page_bytes=4 << 20, pull_bytes_s=1e6,
                tier_bytes_s=1e6, prefill_tok_s=1e6)
    assert plan_kv_source(8, 0, 8, 8, **slow) == "recompute"
    # min_pages gates marginal wins
    assert plan_kv_source(8, 7, 8, 8, min_pages=2, **kw) == "recompute"
    # local HBM hit already covers everything: recompute (= no action)
    assert plan_kv_source(8, 8, 8, 8, **kw) == "recompute"


# ---------------------------------------------------------------------------
# pool integration: demote under allocation pressure, promote via
# adopt_prefix — audited (tier 1)
# ---------------------------------------------------------------------------

def test_eviction_under_pressure_demotes_and_adopt_promotes(tmp_path):
    from deepspeed_tpu.inference import StateManager
    from deepspeed_tpu.inference.scheduler import SplitFuseScheduler

    tier = KVTier(KVTierConfig(ram_bytes=1 << 20, nvme_dir=str(tmp_path)))

    def sink(chains):
        for tokens, _blocks in chains:
            b = toy_prefix_bundle("", tokens, 4)
            if b is not None:
                tier.absorb(b)

    st = StateManager(num_blocks=16, block_size=4, max_seqs=4,
                      max_blocks_per_seq=8)
    st.attach_prefix_cache(PrefixCache(4))
    st.prefix_cache.evict_sink = sink
    sched = SplitFuseScheduler(st, chunk=8, pack=True)
    prompt = list(range(17))                  # 4 full pages + 1
    st.admit(1, prompt, 2)
    while True:
        plan = sched.next_step()
        if plan is None:
            break
        sched.mark_dispatched(plan)
        sched.commit(plan, {u: 900 for u in plan.all_uids if u >= 0})
        if st.seqs.get(1) is None or st.seqs[1].done:
            break
    st.release(1)                             # publishes 4 pages
    st.audit()
    assert st.prefix_cache.cached_blocks == 4
    # allocation pressure: admissions drain the free list until the
    # next one must evict cached pages — which DEMOTES them
    st.admit(2, [500 + i for i in range(9)], 20)   # 8 blocks: free 11→3
    st.audit()
    st.admit(3, [600 + i for i in range(5)], 11)   # 4 blocks: evicts 1
    st.audit()
    assert tier.stats()["demoted_pages"] >= 1
    st.release(2)
    st.release(3)
    st.audit()
    # the evicted chain promotes back through the refcounted pull API
    chain = chain_hashes(prompt[:16], 4)
    deep = tier.probe(chain)
    assert deep >= 1
    bundle = tier.extract(prompt[:deep * 4], 4)
    toy_verify(bundle)
    st.adopt_prefix(bundle.tokens, bundle.n_computed)
    st.audit()
    assert st.prefix_cache.cached_depth(prompt[:16]) >= deep
    # reconcile: every block accounted for
    for uid in sorted(st.seqs):
        st.release(uid)
    st.audit()
    tier.close()


def test_prefix_cache_sink_failure_never_breaks_eviction():
    pc = PrefixCache(4)
    pc.evict_sink = lambda chains: 1 / 0      # a broken sink
    blocks = iter(range(1, 100))
    pc.publish(list(range(8)), [next(blocks), next(blocks)], 0, 8)
    freed = pc.evict(2)                       # must still reclaim
    assert len(freed) == 2
    assert pc.demote_errors == 1
    assert pc.stats()["demote_errors"] == 1


def test_flush_prefix_cache_never_demotes():
    from deepspeed_tpu.inference import StateManager

    hits = []
    st = StateManager(num_blocks=16, block_size=4, max_seqs=2,
                      max_blocks_per_seq=8)
    st.attach_prefix_cache(PrefixCache(4))
    st.prefix_cache.evict_sink = lambda chains: hits.append(chains)
    blocks = st._alloc(2)
    st.prefix_cache.publish(list(range(8)), blocks, 0, 8)
    st.flush_prefix_cache()                   # the weight-swap path
    assert hits == []                         # drop, never demote
    st.audit()
    # ordinary pressure DOES demote
    blocks = st._alloc(2)
    st.prefix_cache.publish(list(range(8)), blocks, 0, 8)
    st.allocator.free(st._alloc(st.allocator.free_blocks
                                + st.prefix_cache.evictable_blocks))
    assert len(hits) == 1
    st.audit()


# ---------------------------------------------------------------------------
# engine integration: real pool, device gather/scatter (slow tier)
# ---------------------------------------------------------------------------

@pytest.mark.slow
def test_engine_tier_demote_promote_bit_identical(tmp_path):
    import jax
    import numpy as np

    from deepspeed_tpu.inference import InferenceEngineV2
    from deepspeed_tpu.models import build_model

    m = build_model("tiny-gpt2", hidden_size=256, num_heads=4)
    eng = InferenceEngineV2(
        m, config={"block_size": 8, "num_blocks": 64, "max_seqs": 4,
                   "chunk": 8, "max_seq_len": 128, "prefix_cache": True,
                   "kv_tier": True, "kv_tier_ram_bytes": 1 << 20,
                   "kv_tier_nvme_dir": str(tmp_path)},
        rng=jax.random.PRNGKey(5))
    rng = np.random.default_rng(7)
    prompt = list(map(int, rng.integers(0, 256, (21,))))
    eng.put(1, prompt, max_new_tokens=6)
    while not eng.query(1).get("done", False):
        eng.step()
    base = eng.flush(1)
    eng.state.audit()
    # the release published the full computed history (prompt +
    # generated): at least the prompt's 2 full pages are cached
    assert eng._prefix_cache.cached_blocks >= 2
    # force the whole trie out: eviction DEMOTES through the device
    # gather into the tier
    reclaimed = eng._prefix_cache.evict(len(eng._prefix_cache))
    eng.state.allocator.free(reclaimed)
    eng.state.audit()
    assert eng.stats["kv_tier_demoted_pages"] >= 2
    assert eng.kv_tier_stats()["ram_pages"] >= 2
    assert len(eng.kv_tier_digest()) >= 2
    # the same prompt now PROMOTES (adopt + scatter) instead of
    # recomputing, and the greedy stream is bit-identical
    eng.put(2, prompt, max_new_tokens=6)
    assert eng.stats["kv_tier_promotes"] == 1
    assert eng.state.seqs[2].prefix_hit_tokens >= 16
    eng.state.audit()
    while not eng.query(2).get("done", False):
        eng.step()
    assert eng.flush(2) == base, "tier-promoted stream diverged"
    eng.state.audit()
    # version skew: a tier chain from other weights never promotes
    eng._kv_tier.set_weight_version({"id": 9, "digest": "other"})
    eng.put(3, prompt, max_new_tokens=6)
    assert eng.stats["kv_tier_promotes"] == 1     # unchanged
    while not eng.query(3).get("done", False):
        eng.step()
    assert eng.flush(3) == base                   # recompute, identical
    eng.state.audit()


# ---------------------------------------------------------------------------
# serving tier: multiprocess promote-instead-of-recompute + chaos
# ---------------------------------------------------------------------------

def _tier_router(tmp_path, per_slot=None, n_replicas=2, log_tag="t",
                 cache_pages=0, tier=True, **rkw):
    from deepspeed_tpu.serving import FleetConfig, Router, RouterConfig

    replica_cfg = {"backend": "toy", "block_size": BS, "max_live": 8,
                   "vocab": VOCAB, "hb_interval_s": 0.03,
                   "tokens_per_step": 4, "cache_pages": cache_pages,
                   "prefill_chunk": 16, "prefill_delay_s": 0.004}
    if tier:
        replica_cfg["kv_tier"] = {
            "ram_bytes": 1 << 16,
            "nvme_dir": str(tmp_path / "tier")}
    fcfg = FleetConfig(
        n_replicas=n_replicas, replica=replica_cfg,
        per_slot=per_slot or {}, hb_timeout_s=1.0, backoff_base_s=0.05,
        log_dir=str(tmp_path / f"logs_{log_tag}"),
        snapshot_dir=str(tmp_path / f"snap_{log_tag}"))
    rkw.setdefault("rebalance", False)
    rkw.setdefault("kv_rate_probe", False)
    return Router(RouterConfig(
        fleet=fcfg, request_timeout_s=rkw.pop("request_timeout_s", 10.0),
        max_retries=rkw.pop("max_retries", 3), telemetry=True, **rkw))


def _snapshot_counter(snap_dir, metric, label=None):
    total = 0.0
    for f in os.listdir(snap_dir):
        if not f.endswith(".json"):
            continue
        with open(os.path.join(snap_dir, f)) as fh:
            snap = json.load(fh)
        fam = snap.get(metric)
        if not fam:
            continue
        for s in fam["series"]:
            if label is None or all(s["labels"].get(k) == v
                                    for k, v in label.items()):
                total += s["value"]
    return total


@pytest.mark.multiprocess
def test_tier_warm_placement_miss_promotes_not_recomputes(tmp_path):
    """The acceptance smoke's core: cache_pages=0 trims the radix after
    every release, so the HBM digest goes cold — but the trim DEMOTED
    the chain, so the same-prefix follow-up promotes from the tier
    (placement still lands it there via the tier digest) and the stream
    is bit-identical to the oracle."""
    shared = list(range(4 * BS))
    router = _tier_router(tmp_path, n_replicas=2, log_tag="warm")
    try:
        router.start(min_ready=2)
        t1 = router.submit(shared + [7, 8, 9], max_new_tokens=8,
                           trace_id="seed")
        res = router.run(deadline_s=60)
        assert res[t1]["status"] == "done"
        assert res[t1]["tokens"] == toy_stream(shared + [7, 8, 9], 8)
        for _ in range(15):                  # let tier digests land
            router.poll()
        seeded_slot = res[t1]["placed"][0]
        h = router.fleet.replicas[seeded_slot]
        assert h.tier_digest, "tier residency never reached the router"
        # HBM digest is cold (cache_pages=0 trimmed it)...
        assert not h.digest
        t2 = router.submit(shared + [3, 4, 5], max_new_tokens=8,
                           trace_id="warm")
        res = router.run(deadline_s=60)
        assert res[t2]["status"] == "done"
        assert res[t2]["tokens"] == toy_stream(shared + [3, 4, 5], 8)
        # ...and placement still co-located on the tier-warm replica
        assert res[t2]["placed"] == [seeded_slot]
        assert router.double_commits == 0
        for _ in range(15):                  # final telemetry sync
            router.poll()
        snap_dir = str(tmp_path / "snap_warm")
        assert _snapshot_counter(
            snap_dir, "serving_kv_tier_promotes_total") >= 1
        assert _snapshot_counter(
            snap_dir, "serving_kv_tier_demotes_total") >= 4
        assert _snapshot_counter(
            snap_dir, "serving_kv_tier_resident_bytes",
            {"tier": "ram"}) >= 0
    finally:
        router.close()


@pytest.mark.multiprocess
@pytest.mark.parametrize("fault", ["tier_torn_spill",
                                   "tier_crash_mid_demote"])
def test_injected_tier_failures_degrade_to_recompute_bit_identical(
        tmp_path, fault):
    """Chaos: a torn spill record (crash-mid-write shape) and a HARD
    crash mid-demote. Both degrade to recompute — every stream
    bit-identical to the uninterrupted oracle, zero double-commits; the
    crash case additionally proves the restarted replica reopens the
    torn tier without serving the damaged chain."""
    shared = list(range(4 * BS))
    router = _tier_router(
        tmp_path, n_replicas=2, log_tag=f"chaos_{fault}",
        per_slot={"0": {"faults": {fault: 1}}})
    try:
        router.start(min_ready=2)
        tids, prompts = [], []
        for i in range(4):
            p = shared + [600 + i]
            prompts.append(p)
            tids.append(router.submit(p, max_new_tokens=8,
                                      trace_id=f"c{i}"))
            for _ in range(3):
                router.poll()
        res = router.run(deadline_s=90)
        for tid, p in zip(tids, prompts):
            assert res[tid]["status"] == "done", res[tid]
            assert res[tid]["tokens"] == toy_stream(p, 8), \
                f"{fault}: stream diverged from the oracle"
        assert router.double_commits == 0
        assert router.replay_mismatches == 0
        if fault == "tier_crash_mid_demote":
            # the injected death was real (os._exit) and survived
            assert router.fleet.restarts_total >= 1
    finally:
        router.close()


@pytest.mark.multiprocess
def test_tier_version_skew_refused_on_promote_after_swap(tmp_path):
    """A weight swap between demote and promote: the tier invalidates
    its records, the follow-up recomputes under the new version and the
    stream still matches the (weight-independent) toy oracle."""
    from deepspeed_tpu.serving import write_toy_checkpoint

    shared = list(range(4 * BS))
    ckpt = str(tmp_path / "ckpt")
    write_toy_checkpoint(ckpt, "w1", vocab=VOCAB, block_size=BS)
    router = _tier_router(tmp_path, n_replicas=2, log_tag="skew")
    try:
        router.start(min_ready=2)
        t1 = router.submit(shared + [7], max_new_tokens=8,
                           trace_id="seed")
        res = router.run(deadline_s=60)
        assert res[t1]["status"] == "done"
        for _ in range(15):
            router.poll()
        dep = router.deploy(ckpt, tag="w1", deadline_s=60.0)
        assert dep["outcome"] == "ok", dep
        t2 = router.submit(shared + [9], max_new_tokens=8,
                           trace_id="postswap")
        res = router.run(deadline_s=60)
        assert res[t2]["status"] == "done"
        assert res[t2]["tokens"] == toy_stream(shared + [9], 8)
        for _ in range(15):
            router.poll()
        # no promote served old-weight KV after the swap: every tier
        # fallback/promote that DID happen carries the new version, and
        # the radix rebuilt from recompute — assert no skewed promote
        # reached the stream by oracle identity above; the counter may
        # legitimately be zero (records were invalidated eagerly)
        assert router.double_commits == 0
    finally:
        router.close()


def test_toy_backend_swap_invalidates_tier(tmp_path):
    from deepspeed_tpu.serving.replica import ToyBackend

    b = ToyBackend({"block_size": BS, "vocab": VOCAB, "cache_pages": 0,
                    "kv_tier": {"ram_bytes": 1 << 16,
                                "nvme_dir": str(tmp_path)}})
    chain_tokens = list(range(3 * BS))
    b._demote_evicted([(chain_tokens, [1, 2, 3])])
    chain = chain_hashes(chain_tokens, BS)
    assert b.kv_tier.probe(chain) == 3
    reason, _ = b.swap_weights(None, None, 2)     # revert-to-init swap
    assert reason is None
    assert b.kv_tier.probe(chain) == 0            # invalidated
    assert b._tier_promote(chain_tokens + [5]) == 0


def test_toy_backend_kv_export_serves_from_tier(tmp_path):
    """One replica's tier can warm another's HBM: kv_export falls back
    to the tier when it holds a deeper chain than the radix."""
    from deepspeed_tpu.serving.replica import ToyBackend

    b = ToyBackend({"block_size": BS, "vocab": VOCAB, "cache_pages": 0,
                    "kv_tier": {"ram_bytes": 1 << 16,
                                "nvme_dir": str(tmp_path)}})
    tokens = list(range(3 * BS))
    b._demote_evicted([(tokens, [1, 2, 3])])
    assert len(b.radix) == 0                      # HBM empty
    bundle = b.kv_export(tokens + [4, 5])
    assert bundle is not None and bundle.n_full == 3
    toy_verify(bundle)
    assert b.tier_digest() and b.tier_version() >= 1


def test_toy_page_payload_stable():
    # the oracle the whole toy suite rests on: payloads are pure
    # functions of the chain hash
    assert toy_page_payload(7) == toy_page_payload(7)
    assert toy_page_payload(7) != toy_page_payload(8)


def test_auto_min_pages_break_even_and_cap():
    """auto_min_pages sizes the promote-vs-recompute break-even from the
    measured byte rates: fast tiers admit short chains, slow tiers push
    the threshold up, and a tier whose per-page promote can never beat
    the recompute returns the cap (never 0 — an empty probe must not
    'promote')."""
    from deepspeed_tpu.inference.kvtier import auto_min_pages

    kw = dict(page_bytes=1 << 16, block_size=64, prefill_tok_s=2000.0,
              fixed_s=1e-2)
    # fast RAM: per-page promote (65536/1e9 = 65us) << recompute (32ms)
    # -> the fixed cost amortizes after a single page
    fast = auto_min_pages({"ram_bytes_s": 1e9}, **kw)
    assert fast == 1
    # slower tier -> higher threshold, still finite
    slow = auto_min_pages({"ram_bytes_s": 2.2e6}, **kw)
    assert fast < slow < 64
    # nvme flag selects the NVMe rate
    nv = auto_min_pages({"ram_bytes_s": 1e9, "nvme_bytes_s": 2.2e6},
                        nvme=True, **kw)
    assert nv == slow
    # promote-per-page >= recompute-per-page: no break-even, cap wins
    assert auto_min_pages({"ram_bytes_s": 1e3}, **kw) == 64
    assert auto_min_pages({}, **kw) == 64          # missing rate == dead
    # explicit cap respected on the no-win path and the clamp path
    assert auto_min_pages({"ram_bytes_s": 1e3}, cap=7, **{k: v for k, v
                          in kw.items()}) == 7


def test_refine_min_pages_histogram_driven_value_wins():
    """Live promote-latency refinement (PR-18 regression pin): once the
    sample budget is met, the OBSERVED per-page promote time — crc,
    verify and adopt included — replaces the startup probe's raw
    byte-rate in the break-even, and the refined value overwrites the
    auto-sized ``min_pages``. Under the budget nothing moves."""
    tier = KVTier(KVTierConfig(ram_bytes=1 << 20, min_pages=2))
    # 8 samples: under min_samples=16 → no refinement, cfg untouched
    for _ in range(8):
        tier.note_promote_latency(0.5, pages=1)
    assert tier.refine_min_pages(block_size=16) is None
    assert tier.cfg.min_pages == 2 and tier.min_pages_refinements == 0
    # 16 pathologically slow promotes (0.5 s/page vs 8 ms recompute):
    # promoting never wins → the histogram drives min_pages to the cap
    for _ in range(8):
        tier.note_promote_latency(0.5, pages=1)
    assert tier.refine_min_pages(block_size=16, cap=64) == 64
    assert tier.cfg.min_pages == 64
    assert tier.min_pages_refinements == 1
    # fast promotes dominate the record → the threshold comes back down
    for _ in range(4000):
        tier.note_promote_latency(1e-5, pages=4)
    n = tier.refine_min_pages(block_size=16, cap=64)
    assert n is not None and 1 <= n < 64
    assert tier.cfg.min_pages == n
    assert tier.min_pages_refinements == 2
    # idempotent at the same observations: no spurious refinement churn
    assert tier.refine_min_pages(block_size=16, cap=64) == n
    assert tier.min_pages_refinements == 2
    tier.close(flush=False)


def test_two_phase_extract_matches_one_shot_and_abandon_is_free(tmp_path):
    """PR-20 promote-ahead contract: ``extract_begin`` is a pure plan
    (walk + residency check, zero mutation — an abandoned handle owes
    nothing), ``extract_finish`` rebuilds the same bundle the one-shot
    ``extract`` would, and a handle whose pages were evicted between
    the phases finishes to None (callers recompute, never serve a
    torn promote)."""
    tokens = list(range(3 * BS))
    t = KVTier(KVTierConfig(ram_bytes=1 << 20, nvme_dir=str(tmp_path)))
    assert t.absorb(_bundle(tokens)) == 3
    before = t.stats()
    h = t.extract_begin(tokens + [7, 8], BS)
    assert h is not None and h["planned"] == 3
    # phase one moved nothing: abandoning here (owner crash before
    # finish) leaves the tier byte-identical
    assert t.stats() == before
    b2 = t.extract_finish(t.extract_begin(tokens + [7, 8], BS))
    assert b2 is not None and b2.n_full == 3
    toy_verify(b2)
    one = t.extract(tokens + [7, 8], BS)
    assert one.pages == b2.pages and one.chain == b2.chain
    # sizing leg: a RAM-only tier holding exactly one chain
    ram = t.stats()["ram_bytes"]
    t.close()
    t2 = KVTier(KVTierConfig(ram_bytes=ram, nvme_dir=None))
    assert t2.absorb(_bundle(tokens)) == 3
    h2 = t2.extract_begin(tokens, BS)
    assert h2 is not None and h2["planned"] == 3
    # residency shrinks between the phases: a new chain of the same
    # size evicts the planned pages wholesale
    t2.absorb(_bundle(range(500, 500 + 3 * BS)))
    assert t2.extract_finish(h2) is None     # stale plan -> recompute
    assert t2.extract_finish(None) is None   # begin already refused
    t2.close(flush=False)
