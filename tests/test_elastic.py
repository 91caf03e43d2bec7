"""Elastic fleet actuators (serving/elastic.py): preemption-aware
drain/spawn/re-role with tier flush and pre-warm.

The acceptance gate is the chaos matrix: graceful drain completes every
in-flight request and provably lands the victim's radix in its KV tier;
SIGKILL mid-drain-flush leaves a torn spill that reopens clean (skipped,
not fatal) with the stragglers replayed on peers; a spawn that crashes
on start trips the ordinary breaker; a preemption storm (N-1 replicas
SIGTERM'd at once) degrades to the survivor with ZERO breaker hits; and
a router restart mid-action resumes it from the journal — a replica
already told to retire is never resurrected. Every stream stays
bit-identical to the closed-form LCG oracle with double commits pinned
to zero.
"""
import http.server
import json
import os
import signal
import threading
import time

import pytest

from deepspeed_tpu.inference.kvtier import KVTier, KVTierConfig
from deepspeed_tpu.runtime.resilience import (GceMaintenancePoller,
                                              PreemptionHandler)
from deepspeed_tpu.serving import Router, RouterConfig, FleetConfig
from deepspeed_tpu.serving.disagg import ScaleAdvisor
from deepspeed_tpu.serving.placement import StickyMap
from deepspeed_tpu.serving.protocol import RequestRecord
from deepspeed_tpu.serving.replica import _mix

VOCAB = 1024
BS = 16


def toy_stream(prompt, n, vocab=VOCAB):
    seed = 0
    for t in prompt:
        seed = _mix(seed, int(t))
    out = []
    for i in range(n):
        seed = _mix(seed, i)
        out.append((seed >> 33) % vocab)
    return out


def make_router(tmp_path, n_replicas=2, replica=None, per_slot=None,
                log_tag="el", **rkw):
    replica_cfg = {"backend": "toy", "block_size": BS, "max_live": 4,
                   "vocab": VOCAB, "hb_interval_s": 0.03,
                   "tokens_per_step": 4}
    replica_cfg.update(replica or {})
    fkw = {}
    for k in ("hb_timeout_s", "backoff_base_s", "breaker_max_restarts",
              "breaker_window_s", "breaker_cooloff_s"):
        if k in rkw:
            fkw[k] = rkw.pop(k)
    fcfg = FleetConfig(
        n_replicas=n_replicas, replica=replica_cfg,
        per_slot=per_slot or {},
        hb_timeout_s=fkw.pop("hb_timeout_s", 1.0),
        backoff_base_s=fkw.pop("backoff_base_s", 0.05),
        log_dir=str(tmp_path / f"logs_{log_tag}"), **fkw)
    rkw.setdefault("elastic", True)
    rkw.setdefault("elastic_sustain_s", 0.1)
    rkw.setdefault("elastic_cooldown_s", 0.2)
    rkw.setdefault("scale_idle_s", 600.0)   # organic down-hints off by
    return Router(RouterConfig(                 # default: tests force them
        fleet=fcfg, request_timeout_s=rkw.pop("request_timeout_s", 15.0),
        max_retries=rkw.pop("max_retries", 3), **rkw))


def submit(router, recs):
    for r in recs:
        router.submit(r.prompt, tenant=r.tenant,
                      max_new_tokens=r.max_new_tokens,
                      priority=r.priority, trace_id=r.trace_id)


def force_hint(router, role, direction, ago_s=30.0):
    """Pin a sustained scale hint and freeze the advisor so organic
    updates can't clear it — the deterministic actuator trigger."""
    router._scale.hint_since[(role, direction)] = \
        time.monotonic() - ago_s
    router._scale.update = lambda *a, **k: None


def poll_until(router, pred, timeout_s=20.0):
    deadline = time.monotonic() + timeout_s
    while time.monotonic() < deadline:
        router.poll()
        if pred():
            return True
    return False


def assert_oracle(router, recs):
    res = router.results()
    by_id = {r.trace_id: r for r in recs}
    for tid, info in res.items():
        assert info["status"] == "done", (tid, info)
        rec = by_id[tid]
        assert info["tokens"] == toy_stream(rec.prompt,
                                            rec.max_new_tokens), tid
    assert router.double_commits == 0


def recs_of(n, base=0, prefix=None, max_new=16):
    pre = prefix if prefix is not None else [7, 7, 7, 7] * 8
    return [RequestRecord(prompt=pre + [base + i], max_new_tokens=max_new,
                          trace_id=f"r{base + i}") for i in range(n)]


# ---------------------------------------------------------------------------
# units
# ---------------------------------------------------------------------------

def test_sticky_heat_survives_forget_slot():
    m = StickyMap(cap=8)
    chain = [11, 22, 33]
    for _ in range(3):
        m.note(chain, slot=2)
    assert m.heat(chain) == 3
    assert m.lookup(chain) == (2, 3)          # lookup bumps heat too
    assert m.heat(chain) == 4
    m.forget_slot(2)
    assert m.lookup(chain) is None            # residency gone...
    assert m.heat(chain) == 4                 # ...hotness kept: it ranks
    assert m.heat([99]) == 0                  # pre-warm after the slot died


def test_scale_advisor_sustained_gate():
    class H:
        slot, role, max_live = 0, "mixed", 4
        load = {"live": 4}
    adv = ScaleAdvisor(min_interval_s=0.0, busy_util=0.85)
    t0 = 100.0
    adv.update(t0, [H()], n_queued=0, est_queue_wait_s=None)
    assert adv.hints[("mixed", "up")] == 1
    assert not adv.sustained("mixed", "up", t0, 1.0)       # just flipped
    adv.update(t0 + 2.0, [H()], n_queued=0, est_queue_wait_s=None)
    assert adv.sustained("mixed", "up", t0 + 2.0, 1.0)     # held 2s
    H.load = {"live": 0}
    adv.update(t0 + 3.0, [H()], n_queued=0, est_queue_wait_s=None)
    assert not adv.sustained("mixed", "up", t0 + 3.0, 1.0)  # cleared
    # a role that vanishes from the fleet drops its timestamps entirely
    adv.hint_since[("decode", "up")] = t0
    adv.update(t0 + 4.0, [H()], n_queued=0, est_queue_wait_s=None)
    assert ("decode", "up") not in adv.hint_since


class _FakeMetadata(http.server.BaseHTTPRequestHandler):
    event = ""

    def do_GET(self):
        assert self.headers.get("Metadata-Flavor") == "Google"
        body = _FakeMetadata.event.encode()
        self.send_response(200)
        self.send_header("Content-Length", str(len(body)))
        self.end_headers()
        self.wfile.write(body)

    def log_message(self, *a):
        pass


@pytest.fixture
def fake_metadata_server():
    srv = http.server.HTTPServer(("127.0.0.1", 0), _FakeMetadata)
    t = threading.Thread(target=srv.serve_forever, daemon=True)
    t.start()
    _FakeMetadata.event = ""
    yield f"http://127.0.0.1:{srv.server_port}"
    srv.shutdown()
    srv.server_close()


def test_gce_maintenance_poller_fake_metadata_server(fake_metadata_server):
    handler = PreemptionHandler()             # fresh, not the singleton
    poller = GceMaintenancePoller.install_from(
        {"metadata_url": fake_metadata_server, "poll_interval_s": 0.0,
         "poll_timeout_s": 2.0}, handler)
    assert poller is not None
    assert handler.check() is None            # quiet: "" means no event
    assert poller.polls >= 1 and poller.errors == 0
    _FakeMetadata.event = "TERMINATE_ON_HOST_MAINTENANCE"
    assert handler.check() == "maintenance:TERMINATE_ON_HOST_MAINTENANCE"
    _FakeMetadata.event = ""
    assert handler.check() is not None        # the latch is sticky
    # no metadata_url → no poller (the non-GCE default)
    assert GceMaintenancePoller.install_from({}, handler) is None


# ---------------------------------------------------------------------------
# actuators: retire / spawn+prewarm / re-role
# ---------------------------------------------------------------------------

def test_graceful_drain_retires_flushes_tier_and_spawn_rewarms(tmp_path):
    tier_cfg = {"kv_tier": {"nvme_dir": str(tmp_path / "tier"),
                            "ram_bytes": 1 << 20}}
    r = make_router(tmp_path, n_replicas=2, replica=tier_cfg,
                    log_tag="drain", elastic_min_replicas=1,
                    elastic_drain_deadline_s=6.0, rebalance=True)
    try:
        r.start(min_ready=2)
        recs = recs_of(8, max_new=48)
        submit(r, recs)
        for _ in range(6):
            r.poll()                      # dispatch lands on both slots
        force_hint(r, "mixed", "down")
        assert poll_until(
            r, lambda: r._elastic.actions_total.get("retire:ok"))
        out = r.run(deadline_s=60.0)
        assert all(v["status"] == "done" for v in out.values())
        assert_oracle(r, recs)
        victim = next(h for h in r.fleet.replicas if h.state == "retired")
        # the drain flush provably landed the radix in the victim's KV
        # tier: the spill store holds bytes after the process exited
        tdir = tmp_path / "tier" / f"r{victim.slot}"
        spilled = sum(p.stat().st_size for p in tdir.glob("*")
                      if p.is_file())
        assert spilled > 0
        # retired slots are invisible to placement and sticky affinity
        assert victim.slot not in {h.slot for h in r.fleet.ready()}
        assert victim.slot not in set(r._sticky._m.values())
        assert victim.digest is None and victim.tier_digest is None
        # scale back up: the revived slot reopens its tier warm and the
        # router pre-warms it with the hottest journaled chains
        r._scale.hint_since.clear()
        force_hint(r, "mixed", "up")
        recs2 = recs_of(8, base=100, max_new=48)
        submit(r, recs2)
        assert poll_until(
            r, lambda: r._elastic.actions_total.get("spawn:ok"),
            timeout_s=30.0)
        out2 = r.run(deadline_s=60.0)
        assert all(v["status"] == "done" for v in out2.values())
        st = r._elastic.stats()
        assert st["prewarm_sent"] >= 1
        assert st["prewarm_acks"] >= 1 and st["prewarm_pages"] >= 1
        assert r.double_commits == 0
    finally:
        r.close()


def test_sigkill_mid_drain_flush_torn_spill_skipped_and_replayed(tmp_path):
    tdir = tmp_path / "tier"
    per_slot = {"1": {"faults": {"replica_crash_mid_drain_flush": 1}}}
    r = make_router(tmp_path, n_replicas=2, per_slot=per_slot,
                    replica={"kv_tier": {"nvme_dir": str(tdir),
                                         "ram_bytes": 1 << 20}},
                    log_tag="torn", elastic_min_replicas=1,
                    elastic_drain_deadline_s=0.5)
    try:
        r.start(min_ready=2)
        recs = recs_of(10, max_new=64)
        submit(r, recs)
        for _ in range(8):
            r.poll()
        force_hint(r, "mixed", "down")
        # pin the victim: retire must hit the fault-armed slot 1
        r._assigned_n[0] = max(r._assigned_n.get(0, 0), 99)
        assert poll_until(
            r, lambda: any(k.startswith("retire:")
                           for k in r._elastic.actions_total))
        del r._assigned_n[0]
        out = r.run(deadline_s=60.0)
        # the victim died HARD mid-flush — every request still completes
        # exactly once (stragglers replayed on the peer), oracle-clean
        assert all(v["status"] == "done" for v in out.values())
        assert_oracle(r, recs)
        # the on-purpose drain never touches the breaker
        assert r.fleet.replicas[1].state == "retired"
        assert r.fleet.breaker_opens_total == 0
    finally:
        r.close()
    # the torn spill tail reopens clean: bad records are skipped, the
    # store is usable (the later revive path), never fatal
    tier = KVTier(KVTierConfig(ram_bytes=1 << 20,
                               nvme_dir=str(tdir / "r1")))
    assert tier.stats()["nvme_pages"] >= 0
    tier.close(flush=False)


def test_spawn_crash_on_start_trips_breaker(tmp_path):
    r = make_router(tmp_path, n_replicas=2, log_tag="spawncrash",
                    elastic_min_replicas=1,
                    elastic_spawn_deadline_s=30.0,
                    breaker_max_restarts=2, breaker_window_s=60.0,
                    backoff_base_s=0.02)
    try:
        r.start(min_ready=2)
        force_hint(r, "mixed", "down")
        assert poll_until(
            r, lambda: r._elastic.actions_total.get("retire:ok"))
        slot = next(h.slot for h in r.fleet.replicas
                    if h.state == "retired")
        # arm the parked slot to die at startup, then ask for scale-up:
        # the revive goes through the ordinary spawn/breaker machinery
        r.fleet.cfg.per_slot.setdefault(str(slot), {})["faults"] = {
            "replica_crash_on_start": True}
        r._scale.hint_since.clear()
        force_hint(r, "mixed", "up")
        assert poll_until(
            r, lambda: r._elastic.actions_total.get("spawn:breaker"),
            timeout_s=30.0)
        assert r.fleet.replicas[slot].state == "quarantined"
        assert r.fleet.breaker_opens_total >= 1
    finally:
        r.close()


def test_rerole_flips_at_quiesce_boundary_and_persists(tmp_path):
    r = make_router(tmp_path, n_replicas=3, log_tag="rerole",
                    per_slot={"0": {"role": "prefill"},
                              "1": {"role": "prefill"},
                              "2": {"role": "decode"}},
                    elastic_min_replicas=1)
    try:
        r.start(min_ready=3)
        force_hint(r, "decode", "up")
        force_hint(r, "prefill", "down")
        assert poll_until(
            r, lambda: r._elastic.actions_total.get("re_role:ok"))
        roles = {h.slot: h.role for h in r.fleet.replicas}
        assert sorted(roles.values()) == ["decode", "decode", "prefill"]
        flipped = next(s for s, role in roles.items()
                       if s in (0, 1) and role == "decode")
        # the flip is written through to per-slot config: a later
        # respawn of this slot comes back in its NEW role
        assert r.fleet.cfg.per_slot[str(flipped)]["role"] == "decode"
        assert r.fleet.replicas[flipped].state == "ready"
        # the flipped fleet still serves, oracle-clean
        r._scale.update = ScaleAdvisor.update.__get__(r._scale)
        recs = recs_of(6, base=200)
        submit(r, recs)
        out = r.run(deadline_s=60.0)
        assert all(v["status"] == "done" for v in out.values())
        assert_oracle(r, recs)
    finally:
        r.close()


# ---------------------------------------------------------------------------
# preemption
# ---------------------------------------------------------------------------

def test_preempted_replica_no_breaker_and_eager_invalidation(tmp_path):
    r = make_router(tmp_path, n_replicas=2, log_tag="preempt",
                    replica={"preempt": {"signals": ["SIGTERM"],
                                         "deadline_s": 2.0}})
    try:
        r.start(min_ready=2)
        recs = recs_of(8, max_new=48)
        submit(r, recs)
        for _ in range(10):
            r.poll()
        victim = r.fleet.replicas[1]
        os.kill(victim.proc.pid, signal.SIGTERM)
        # the preempt NOTICE (not the exit) invalidates routing state
        assert poll_until(r, lambda: victim.preempt_latched,
                          timeout_s=10.0)
        assert victim.slot not in set(r._sticky._m.values())
        assert victim.digest is None and victim.tier_digest is None
        out = r.run(deadline_s=60.0)
        assert all(v["status"] == "done" for v in out.values())
        assert_oracle(r, recs)
        assert poll_until(r, lambda: r.fleet.preemptions_total >= 1,
                          timeout_s=10.0)
        # preempted ≠ failed: no breaker hit, no failure budget spent
        assert r.fleet.breaker_opens_total == 0
        assert len(victim.deaths) == 0
    finally:
        r.close()


def test_preemption_storm_degrades_to_survivor(tmp_path):
    r = make_router(tmp_path, n_replicas=3, log_tag="storm",
                    replica={"preempt": {"signals": ["SIGTERM"],
                                         "deadline_s": 1.0}},
                    backoff_base_s=0.5)
    try:
        r.start(min_ready=3)
        recs = recs_of(9, max_new=48)
        submit(r, recs)
        for _ in range(10):
            r.poll()
        # N-1 replicas get the notice at once — the fleet degrades to
        # the survivor and still finishes everything exactly once
        for h in r.fleet.replicas[1:]:
            os.kill(h.proc.pid, signal.SIGTERM)
        out = r.run(deadline_s=90.0)
        assert all(v["status"] == "done" for v in out.values())
        assert_oracle(r, recs)
        assert poll_until(r, lambda: r.fleet.preemptions_total >= 2,
                          timeout_s=10.0)
        assert r.fleet.breaker_opens_total == 0
    finally:
        r.close()


def test_metadata_event_preempts_replica_end_to_end(tmp_path,
                                                    fake_metadata_server):
    _FakeMetadata.event = "TERMINATE_ON_HOST_MAINTENANCE"
    r = make_router(tmp_path, n_replicas=2, log_tag="gce", per_slot={
        "1": {"preempt": {"metadata_url": fake_metadata_server,
                          "poll_interval_s": 0.05,
                          "deadline_s": 1.0}}})
    try:
        r.start(min_ready=2)
        # slot 1 discovers the maintenance event via the poller — no
        # signal ever sent — drains, flushes and exits 83
        assert poll_until(r, lambda: r.fleet.preemptions_total >= 1,
                          timeout_s=20.0)
        assert r.fleet.breaker_opens_total == 0
        recs = recs_of(4, base=300)
        submit(r, recs)
        out = r.run(deadline_s=60.0)
        assert all(v["status"] == "done" for v in out.values())
    finally:
        r.close()


# ---------------------------------------------------------------------------
# deploys and journaled recovery
# ---------------------------------------------------------------------------

class _FakeDeploy:
    phase = "swap"
    wid = 99

    def __init__(self):
        self.active = True

    def tick(self, now):
        pass


def test_elastic_holds_off_during_rolling_deploy(tmp_path):
    r = make_router(tmp_path, n_replicas=2, log_tag="deploy",
                    elastic_min_replicas=1)
    try:
        r.start(min_ready=2)
        force_hint(r, "mixed", "down")
        r._deploy = _FakeDeploy()
        deadline = time.monotonic() + 1.5
        while time.monotonic() < deadline:
            r.poll()
        # deterministic: a drain never races a rolling deploy — the
        # controller starts nothing while the deploy is active
        assert r._elastic.action is None
        assert r._elastic.actions_total == {}
        r._deploy.active = False
        assert poll_until(
            r, lambda: r._elastic.actions_total.get("retire:ok"))
    finally:
        r.close()


def test_router_restart_mid_drain_resumes_retire(tmp_path):
    jdir = str(tmp_path / "wal")
    kw = dict(elastic_min_replicas=1, elastic_drain_deadline_s=4.0,
              journal_dir=jdir)
    a = make_router(tmp_path, n_replicas=2, log_tag="wal_a", **kw)
    try:
        a.start(min_ready=2)
        recs = recs_of(6, max_new=64)
        submit(a, recs)
        for _ in range(8):
            a.poll()
        force_hint(a, "mixed", "down")
        a._assigned_n[0] = max(a._assigned_n.get(0, 0), 99)  # pin victim 1
        assert poll_until(
            a, lambda: (a._elastic.action or {}).get("phase") == "drain")
        slot = a._elastic.action["slot"]
        assert slot == 1
    finally:
        a.fleet.abandon()       # router "crash": channels drop, no kill
    b = make_router(tmp_path, n_replicas=2, log_tag="wal_b", **kw)
    try:
        # the journaled drain-phase action was adopted, not restarted
        assert (b._elastic.action or {}).get("kind") == "retire"
        assert b._elastic.action["slot"] == slot
        # freeze the advisor as router a's was: the replayed backlog on one
        # replica is an organic UP hint, and on a slow host the retire
        # settles inside start() and the hint revives the parked slot
        # before the state is read
        b._scale.update = lambda *a, **k: None
        b.start(min_ready=1)
        assert poll_until(
            b, lambda: b._elastic.actions_total.get("retire:ok"),
            timeout_s=30.0)
        assert b.fleet.replicas[slot].state == "retired"
    finally:
        b.close()


def test_router_restart_after_retire_phase_never_resurrects(tmp_path):
    jdir = str(tmp_path / "wal2")
    kw = dict(elastic_min_replicas=1, elastic_drain_deadline_s=6.0,
              journal_dir=jdir)
    a = make_router(tmp_path, n_replicas=2, log_tag="wal2_a", **kw)
    try:
        a.start(min_ready=2)
        force_hint(a, "mixed", "down")
        assert poll_until(
            a, lambda: (a._elastic.action or {}).get("phase") == "retire")
        slot = a._elastic.action["slot"]
    finally:
        a.fleet.abandon()
    b = make_router(tmp_path, n_replicas=2, log_tag="wal2_b", **kw)
    try:
        # adopted pre-start: the slot is parked RETIRED before
        # fleet.start() could ever respawn it, and the action settled
        assert b.fleet.replicas[slot].state == "retired"
        assert b._elastic.action is None
        assert b._elastic.actions_total.get("retire:ok") == 1
        b.start(min_ready=1)
        b.poll()
        assert b.fleet.replicas[slot].state == "retired"
        recs = recs_of(4, base=400)
        submit(b, recs)
        out = b.run(deadline_s=60.0)
        assert all(v["status"] == "done" for v in out.values())
        assert b.fleet.replicas[slot].state == "retired"
    finally:
        b.close()
