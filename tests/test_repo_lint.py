"""Repo lint: no module-import-time jax device probes
(bin/check_import_time_devices.py — importing the package must leave the
chip free for the child that needs it, and import-time probes freeze the
platform before set_cpu_devices can run), no silent
``except Exception: pass`` swallows (bin/check_exception_swallows.py —
recovery paths must not eat the faults the resilience layer surfaces), and
no emitted metric/span tag that can't sanitize to a valid Prometheus
metric name (bin/check_metric_names.py — /metrics must never 500 on a
scrape because a rare branch registered a bad tag), and no KV block-list
mutation outside StateManager's refcounted alloc/free API
(bin/check_state_invariants.py — with the shared-prefix trie a stray
allocator.free or .blocks assignment frees pages other sequences still
serve from)."""
import importlib.util
import os

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)


def _load(name):
    spec = importlib.util.spec_from_file_location(
        name, os.path.join(ROOT, "bin", f"{name}.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


lint = _load("check_import_time_devices")
swallows = _load("check_exception_swallows")
metric_lint = _load("check_metric_names")
state_lint = _load("check_state_invariants")
reqtrace_lint = _load("check_reqtrace_events")
deadline_lint = _load("check_deadlines")
protocol_lint = _load("check_protocol_msgs")


def test_repo_has_no_import_time_device_probes():
    violations = lint.check_repo(ROOT)
    assert violations == [], "\n".join(violations)


def test_detector_flags_import_time_probe(tmp_path):
    bad = tmp_path / "bad.py"
    bad.write_text(
        "import jax\n"
        "KIND = jax.devices()[0].device_kind\n"          # module level
        "def fine():\n"
        "    return jax.devices()\n"                     # call time: ok
        "N = len(jax.local_devices())\n")
    out = lint.check_file(str(bad))
    assert len(out) == 2
    assert "jax.devices()" in out[0] and ":2:" in out[0]
    assert "jax.local_devices()" in out[1] and ":5:" in out[1]


def test_detector_flags_import_time_default_args(tmp_path):
    """Default-arg expressions evaluate at def time — import time for
    top-level functions."""
    bad = tmp_path / "bad2.py"
    bad.write_text(
        "import jax\n"
        "def f(n=len(jax.devices())):\n"
        "    return n\n")
    assert len(lint.check_file(str(bad))) == 1


# --- silent broad-exception swallows ---------------------------------------

def test_repo_has_no_silent_exception_swallows():
    violations = swallows.check_repo(ROOT)
    assert violations == [], "\n".join(violations)


def test_swallow_detector_flags_silent_broad_handlers(tmp_path):
    bad = tmp_path / "bad.py"
    bad.write_text(
        "def f():\n"
        "    try:\n"
        "        work()\n"
        "    except Exception:\n"       # silent broad: flagged
        "        pass\n"
        "    try:\n"
        "        work()\n"
        "    except:\n"                 # silent bare: flagged
        "        ...\n"
        "    try:\n"
        "        work()\n"
        "    except (ValueError, Exception):\n"  # broad inside tuple: flagged
        "        pass\n")
    out = swallows.check_file(str(bad))
    assert len(out) == 3
    assert ":4:" in out[0] and ":8:" in out[1] and ":12:" in out[2]


# --- Prometheus-safe metric/span tags ---------------------------------------

def test_repo_metric_tags_are_prometheus_safe():
    violations = metric_lint.check_repo(ROOT)
    assert violations == [], "\n".join(violations)


def test_metric_tag_detector_flags_unsalvageable_literals(tmp_path):
    bad = tmp_path / "bad.py"
    bad.write_text(
        "def f(reg, telem, mm):\n"
        "    reg.counter('')\n"                       # empty: flagged
        "    telem.span('\\u00e9\\u00e9')\n"          # sanitizes to '__': ok
        "    reg.histogram('serving/ttft s')\n"       # '/'+' ' → '_': ok
        "    reg.gauge(name_var)\n"                   # dynamic: not checked
        "    mm.write_counters({}, 3, prefix='Train/')\n"   # ok
        "    eng._emit_counters({}, 'Checkpoint/')\n"       # positional: ok
        "    reg.counter('9lives')\n")                # digit start: salvaged
    out = metric_lint.check_file(str(bad))
    assert len(out) == 1 and ":2:" in out[0] and "counter()" in out[0]


def test_metric_tag_detector_matches_runtime_sanitizer():
    """The lint's dependency-free sanitize mirror must agree with the
    runtime sanitizer it stands in for (drift here would let the lint
    pass tags the exposition rejects, or vice versa)."""
    from deepspeed_tpu.telemetry import sanitize_metric_name

    for tag in ("Resilience/rewinds", "Train/fwd_ms", "a b-c.d", "9x",
                "serving_ttft_s", "x:y", "__", "é"):
        assert metric_lint.sanitize(tag) == sanitize_metric_name(tag), tag


def test_metric_label_detector_flags_bad_names_and_dirty_values(tmp_path):
    """The per-tenant path's label rules: literal label names must be
    valid Prometheus label names; literal values that the runtime
    sanitizer would rewrite are latent dashboard-query mismatches."""
    bad = tmp_path / "bad.py"
    bad.write_text(
        "def f(reg):\n"
        "    reg.counter('x_total', labels={'tenant': 'acme'})\n"   # ok
        "    reg.gauge('y', labels={'le bad': 'v'})\n"        # name: flagged
        "    reg.counter('z_total', labels={'k': 'a b'})\n"   # value: flagged
        "    reg.histogram('h_s', labels={'kind': kind_var})\n"  # dyn: ok
        "    reg.counter('w_total', labels=lbls)\n")          # dyn dict: ok
    out = metric_lint.check_file(str(bad))
    assert len(out) == 2
    assert ":3:" in out[0] and "label name" in out[0]
    assert ":4:" in out[1] and "label value" in out[1]


def test_metric_lint_pins_the_tenant_cardinality_cap():
    """TENANT_CARDINALITY_CAP must exist in telemetry/reqtrace.py as an
    int literal in the lint's sane range — the scrape's only defense
    against tenant-label explosion — and the lint's label-value sanitizer
    mirror must agree with the runtime one."""
    assert metric_lint.check_cardinality_cap(ROOT) == []
    from deepspeed_tpu.telemetry import (TENANT_CARDINALITY_CAP,
                                         sanitize_label_value)

    lo, hi = metric_lint.CAP_RANGE
    assert lo <= TENANT_CARDINALITY_CAP <= hi
    for v in ("acme", "a b", "tenant/7", "x" * 200, "", "Ωmega", "a:b-c.d",
              42, None):
        assert metric_lint.sanitize_label_value(v) == \
            sanitize_label_value(v), v
    # a missing/dynamic cap is a violation, not a crash
    assert metric_lint.check_cardinality_cap("/nonexistent") != []


# --- metric-family documentation (docs/METRICS.md) ---------------------------

def test_every_emitted_metric_family_is_documented():
    """Drift guard for the auto-generated docs/METRICS.md reference:
    every literal serving_*/telemetry_* family emitted anywhere must be
    documented, and every documented family must still be emitted (run
    ``python bin/check_metric_names.py --write-doc`` after adding or
    removing one)."""
    violations = metric_lint.check_metrics_doc(ROOT)
    assert violations == [], "\n".join(violations)


def test_metric_family_collector_sees_emits_and_forwarders(tmp_path):
    """The collector must catch registry emits AND reqtrace's
    forwarders (_tenant_inc/_observe_slo carry the family name at a
    non-zero arg index), and the doc check must flag drift both ways."""
    pkg = tmp_path / "deepspeed_tpu"
    pkg.mkdir()
    (pkg / "m.py").write_text(
        "def f(reg, self, uid):\n"
        "    reg.counter('serving_x_total', help='xs counted')\n"
        "    reg.gauge('telemetry_y', help='ys')\n"
        "    self._tenant_inc('serving_tenant_z_total', 't', 1, 'zs')\n"
        "    self._observe_slo(uid, 'serving_tenant_w_s', 0.1, 1,\n"
        "                      'ws', 'w', None)\n"
        "    reg.counter('Train/ignored')\n")
    fams = metric_lint.collect_metric_families(str(tmp_path))
    assert set(fams) == {"serving_x_total", "telemetry_y",
                         "serving_tenant_z_total", "serving_tenant_w_s"}
    assert fams["serving_x_total"]["help"] == "xs counted"
    assert fams["serving_tenant_w_s"]["type"] == "histogram"
    # no doc at all -> one violation
    out = metric_lint.check_metrics_doc(str(tmp_path))
    assert len(out) == 1 and "missing" in out[0]
    # a doc covering only some families flags the missing AND the stale
    doc = tmp_path / "docs"
    doc.mkdir()
    (doc / "METRICS.md").write_text(
        "| `serving_x_total` |\n| `serving_gone_total` |\n")
    out = metric_lint.check_metrics_doc(str(tmp_path))
    assert any("telemetry_y" in v and "not documented" in v for v in out)
    assert any("serving_gone_total" in v and "no longer emitted" in v
               for v in out)
    # the generated doc round-trips clean
    (doc / "METRICS.md").write_text(
        metric_lint.render_metrics_doc(str(tmp_path)))
    assert metric_lint.check_metrics_doc(str(tmp_path)) == []


# --- reqtrace lifecycle coverage --------------------------------------------

def test_repo_reqtrace_lifecycle_events_all_emitted():
    violations = reqtrace_lint.check_repo(ROOT)
    assert violations == [], "\n".join(violations)


def test_reqtrace_detector_flags_undeclared_and_dark_kinds(tmp_path):
    """An emission under an undeclared kind AND a declared kind with zero
    emitters are both violations."""
    pkg = tmp_path / "deepspeed_tpu"
    (pkg / "telemetry").mkdir(parents=True)
    (pkg / "telemetry" / "reqtrace.py").write_text(
        "LIFECYCLE_EVENTS = ('admit', 'commit', 'release')\n"
        "class ReqTracer:\n"
        "    def demo(self, uid):\n"
        "        self.event(uid, 'admit')\n")
    (pkg / "engine.py").write_text(
        "def serve(rt, uid):\n"
        "    rt.event(uid, 'commit', tokens=1)\n"
        "    rt.event(uid, 'comit', tokens=1)\n"     # typo: flagged
        "    rt.event(uid, kind_var)\n")             # dynamic: not checked
    out = reqtrace_lint.check_repo(str(tmp_path))
    assert len(out) == 2, "\n".join(out)
    assert "comit" in out[0] and "not declared" in out[0]
    assert "'release'" in out[1] and "never emitted" in out[1]


def test_reqtrace_detector_rejects_non_literal_event_table(tmp_path):
    pkg = tmp_path / "deepspeed_tpu" / "telemetry"
    pkg.mkdir(parents=True)
    (pkg / "reqtrace.py").write_text(
        "LIFECYCLE_EVENTS = tuple(x for x in ('a',))\n")
    out = reqtrace_lint.check_repo(str(tmp_path))
    assert len(out) == 1 and "literal tuple" in out[0]


# --- refcounted block-list ownership ----------------------------------------

def test_repo_block_lists_go_through_refcounted_api():
    violations = state_lint.check_repo(ROOT)
    assert violations == [], "\n".join(violations)


def test_state_invariant_detector_flags_stray_mutations(tmp_path):
    bad = tmp_path / "deepspeed_tpu" / "bad.py"
    bad.parent.mkdir()
    bad.write_text(
        "def hijack(st, seq, pc):\n"
        "    st.allocator.free(seq.blocks)\n"        # stray free: flagged
        "    seq.blocks = []\n"                      # assignment: flagged
        "    seq.blocks.append(3)\n"                 # mutation: flagged
        "    pc.prefix_cache.evict(2)\n"             # cache mutator: flagged
        "    pc._prefix_cache.acquire([])\n"         # engine alias: flagged
        "    n = st.allocator.free_blocks\n"         # read: ok
        "    blocks = []\n"
        "    blocks.extend(seq.blocks)\n"            # local scratch: ok
        "    return n, pc.prefix_cache.stats()\n")   # read: ok
    out = state_lint.check_file(str(bad))
    assert len(out) == 5
    assert ":2:" in out[0] and "allocator.free()" in out[0]
    assert ":3:" in out[1] and "assignment" in out[1]
    assert ":4:" in out[2] and ".blocks.append()" in out[2]
    assert ":5:" in out[3] and "prefix_cache.evict()" in out[3]
    assert ":6:" in out[4] and "prefix_cache.acquire()" in out[4]


def test_state_invariant_detector_allows_the_api_itself(tmp_path):
    """The allowlisted StateManager methods in ragged.py keep their direct
    allocator/trie access — the rule targets everyone else."""
    f = tmp_path / "deepspeed_tpu" / "inference" / "ragged.py"
    f.parent.mkdir(parents=True)
    f.write_text(
        "class StateManager:\n"
        "    def _alloc(self, n):\n"
        "        self.allocator.free(self.prefix_cache.evict(1))\n"
        "        return self.allocator.allocate(n)\n"
        "    def release(self, uid):\n"
        "        self.allocator.free([1])\n"
        "        self.prefix_cache.publish([], [], 0, 0)\n"
        "    def elsewhere(self):\n"
        "        self.allocator.free([1])\n")        # wrong method: flagged
    out = state_lint.check_file(str(f))
    assert len(out) == 1 and ":9:" in out[0]


def test_swallow_detector_allows_narrow_logged_and_del(tmp_path):
    ok = tmp_path / "ok.py"
    ok.write_text(
        "def f():\n"
        "    try:\n"
        "        work()\n"
        "    except OSError:\n"          # narrow: a documented condition
        "        pass\n"
        "    try:\n"
        "        work()\n"
        "    except Exception as e:\n"   # broad but handled (logged)
        "        log(e)\n"
        "class C:\n"
        "    def __del__(self):\n"
        "        try:\n"
        "            self.close()\n"
        "        except Exception:\n"    # shutdown teardown race: idiomatic
        "            pass\n")
    assert swallows.check_file(str(ok)) == []


# --- bounded waits in the serving tier --------------------------------------

def test_serving_tier_has_no_unbounded_waits():
    violations = deadline_lint.check_repo(ROOT)
    assert violations == [], "\n".join(violations)


def test_deadline_detector_flags_bare_waits(tmp_path):
    serving = tmp_path / "deepspeed_tpu" / "serving"
    serving.mkdir(parents=True)
    bad = serving / "bad.py"
    bad.write_text(
        "import select, time\n"
        "def f(q, th, sock, proc, ch, ev):\n"
        "    q.get()\n"                            # bare get: flagged
        "    q.get(timeout=1.0)\n"                 # bounded: ok
        "    d = {}\n"
        "    d.get('k')\n"                         # dict.get: ok (argful)
        "    th.join()\n"                          # bare join: flagged
        "    th.join(timeout=2)\n"                 # ok
        "    ','.join(['a'])\n"                    # str.join: ok
        "    ev.wait()\n"                          # bare wait: flagged
        "    proc.wait(timeout=5)\n"               # ok
        "    proc.poll()\n"                        # non-blocking: ok
        "    sock.recv(4096)\n"                    # raw socket: flagged
        "    ch.recv(timeout=0.1)\n"               # deadline kw: ok
        "    sock.accept()\n"                      # flagged
        "    f2 = sock.makefile()\n"
        "    f2.readline()\n"                      # flagged
        "    select.select([0], [], [])\n"         # no timeout: flagged
        "    select.select([0], [], [], 0.5)\n"    # ok
        "    p = select.poll()\n"                  # constructor: flagged
        "    time.sleep(0.1)\n"                    # pacing: ok
        "    time.sleep(3600)\n")                  # forever-ish: flagged
    out = deadline_lint.check_file(str(bad))
    assert len(out) == 9, "\n".join(out)
    for frag in (":3:", ":7:", ":10:", ":13:", ":15:", ":17:", ":18:",
                 ":20:", ":22:"):
        assert any(frag in v for v in out), (frag, out)


def test_deadline_detector_flags_blocking_acquire_forms(tmp_path):
    """The shm-ring era rule: ``lock.acquire(True)`` blocks forever
    exactly like a bare ``acquire()`` but used to slip past the no-args
    check. Non-lock acquires (the prefix trie's ``acquire(nodes)``) pass
    a non-literal argument and stay legal."""
    serving = tmp_path / "deepspeed_tpu" / "serving"
    serving.mkdir(parents=True)
    bad = serving / "shmish.py"
    bad.write_text(
        "def f(lock, trie, nodes):\n"
        "    lock.acquire()\n"                      # bare: flagged
        "    lock.acquire(True)\n"                  # blocking: flagged
        "    lock.acquire(False)\n"                 # non-blocking: ok
        "    lock.acquire(True, 0.5)\n"             # positional timeout: ok
        "    lock.acquire(timeout=1.0)\n"           # ok
        "    trie.acquire(nodes)\n")                # not a lock: ok
    out = deadline_lint.check_file(str(bad))
    assert len(out) == 2, "\n".join(out)
    assert ":2:" in out[0] and ":3:" in out[1]
    assert "acquire(True)" in out[1]


def test_state_invariant_detector_allows_the_pull_api(tmp_path):
    """The cross-replica radix-pull surface (snapshot_prefix /
    release_prefix / adopt_prefix) is part of the refcounted API; the
    same trie calls anywhere else stay flagged."""
    f = tmp_path / "deepspeed_tpu" / "inference" / "ragged.py"
    f.parent.mkdir(parents=True)
    f.write_text(
        "class StateManager:\n"
        "    def snapshot_prefix(self, tokens):\n"
        "        nodes = self.prefix_cache.match(tokens)\n"
        "        self.prefix_cache.acquire(nodes)\n"
        "    def adopt_prefix(self, tokens, n):\n"
        "        nodes, dups = self.prefix_cache.adopt(tokens, [], n)\n"
        "        self.prefix_cache.release(nodes)\n"
        "        self.allocator.free(dups)\n"
        "    def rogue_pull(self):\n"
        "        self.prefix_cache.adopt([], [], 0)\n")   # flagged
    out = state_lint.check_file(str(f))
    assert len(out) == 1 and ":10:" in out[0]


def test_deadline_detector_honors_allowlist(tmp_path):
    """replica.py's serve() carries the fault-injected hang — THE
    unbounded sleep under test — and nothing else does."""
    serving = tmp_path / "deepspeed_tpu" / "serving"
    serving.mkdir(parents=True)
    rep = serving / "replica.py"
    rep.write_text(
        "import time\n"
        "def serve(inj):\n"
        "    time.sleep(3600)\n"                   # allowlisted hang
        "def other():\n"
        "    time.sleep(3600)\n")                  # flagged
    out = deadline_lint.check_file(str(rep))
    assert len(out) == 1 and ":5:" in out[0]


def test_deadline_lint_requires_the_serving_package():
    out = deadline_lint.check_repo("/nonexistent")
    assert len(out) == 1 and "missing" in out[0]


def test_deadline_lint_covers_journal_waits(tmp_path):
    """serving/journal.py is inside the linted package: the write-ahead
    log is on the router's poll path, so an unbounded wait smuggled into
    it (a blocking lock around fsync, a bare select) would hang the
    whole control plane — it is flagged like anywhere else in
    serving/."""
    serving = tmp_path / "deepspeed_tpu" / "serving"
    serving.mkdir(parents=True)
    (serving / "journal.py").write_text(
        "def append(lock, rec):\n"
        "    lock.acquire()\n"                     # flagged: unbounded
        "    lock.acquire(timeout=1.0)\n")         # bounded: ok
    out = deadline_lint.check_repo(str(tmp_path))
    assert len(out) == 1 and ":2:" in out[0]


def test_deadline_lint_covers_elastic_controller(tmp_path):
    """serving/elastic.py ticks inside the router poll loop: an
    unbounded wait in a drain/spawn/re-role actuator would stall every
    replica's heartbeat, so the deadline lint must sweep it like the
    rest of serving/ — no carve-out for new control-plane files."""
    serving = tmp_path / "deepspeed_tpu" / "serving"
    serving.mkdir(parents=True)
    (serving / "elastic.py").write_text(
        "def drain(proc, lock):\n"
        "    lock.acquire()\n"                     # flagged: unbounded
        "    proc.join(timeout=2.0)\n")            # bounded: ok
    out = deadline_lint.check_repo(str(tmp_path))
    assert len(out) == 1 and ":2:" in out[0]
    real = os.path.join(ROOT, "deepspeed_tpu", "serving", "elastic.py")
    assert os.path.exists(real)
    assert deadline_lint.check_repo(ROOT) == []


def test_serving_protocol_vocabulary_is_closed():
    """Every literal {"t": ...} message sent in serving/ has a receiver
    dispatch branch and vice versa (bin/check_protocol_msgs.py) — the
    resync vocabulary must not rot silently."""
    violations = protocol_lint.check_repo(ROOT)
    assert violations == [], "\n".join(violations)


def test_protocol_lint_pins_gang_vocabulary_both_directions():
    """The gang-prefill vocabulary (PR 16) is wired end to end: the
    router constructs gang_seg/gang_abort and the replica dispatches
    them; the replica constructs gang_seg_ok/gang_seg_fail and the
    router dispatches those — this pin keeps a refactor from quietly
    orphaning either direction (the lint would fire, but only on the
    side that ROT; a deleted pair vanishes from both maps and passes)."""
    sent: dict = {}
    handled: dict = {}
    serving = os.path.join(ROOT, "deepspeed_tpu", "serving")
    for dirpath, _, files in os.walk(serving):
        for f in sorted(files):
            if f.endswith(".py"):
                s, h, errs = protocol_lint.scan_file(
                    os.path.join(dirpath, f))
                assert errs == []
                sent.update(s)
                handled.update(h)
    for tag in ("gang_seg", "gang_abort", "gang_seg_ok",
                "gang_seg_fail"):
        assert tag in sent, f"{tag} no longer constructed"
        assert tag in handled, f"{tag} no longer dispatched"
    assert "router.py" in sent["gang_seg"]
    assert "replica.py" in handled["gang_seg"]
    assert "replica.py" in sent["gang_seg_ok"]
    assert "router.py" in handled["gang_seg_ok"]


def test_protocol_lint_pins_elastic_vocabulary_both_directions():
    """The elastic-actuator vocabulary (PR 18) is wired end to end: the
    router constructs retire/re_role/prewarm and the replica dispatches
    them; the replica constructs preempt/re_role_ok and the router
    dispatches those.  Same rationale as the gang pin above — a pair
    deleted from BOTH sides vanishes from both maps and would pass the
    generic closure check."""
    sent: dict = {}
    handled: dict = {}
    serving = os.path.join(ROOT, "deepspeed_tpu", "serving")
    for dirpath, _, files in os.walk(serving):
        for f in sorted(files):
            if f.endswith(".py"):
                s, h, errs = protocol_lint.scan_file(
                    os.path.join(dirpath, f))
                assert errs == []
                sent.update(s)
                handled.update(h)
    for tag in ("retire", "re_role", "prewarm", "preempt",
                "re_role_ok"):
        assert tag in sent, f"{tag} no longer constructed"
        assert tag in handled, f"{tag} no longer dispatched"
    for tag in ("retire", "re_role", "prewarm"):
        assert "replica.py" in handled[tag]
    assert "replica.py" in sent["preempt"]
    assert "router.py" in handled["preempt"]
    assert "replica.py" in sent["re_role_ok"]
    assert "router.py" in handled["re_role_ok"]


def test_protocol_detector_flags_dark_sends_and_phantom_handlers(
        tmp_path):
    serving = tmp_path / "deepspeed_tpu" / "serving"
    serving.mkdir(parents=True)
    (serving / "a.py").write_text(
        "def send(ch, msg, t):\n"
        "    ch.send({'t': 'ping'})\n"             # sent + handled: ok
        "    ch.send({'t': 'orphaned'})\n"         # dark send: flagged
        "    if t == 'ping':\n"
        "        pass\n"
        "    elif t in ('phantom', 'ping'):\n"     # phantom: flagged
        "        pass\n"
        "    if msg['t'] == 'ping':\n"
        "        pass\n")
    out = protocol_lint.check_repo(str(tmp_path))
    assert len(out) == 2, "\n".join(out)
    assert any("'orphaned'" in v and "void" in v for v in out), out
    assert any("'phantom'" in v and "dead" in v for v in out), out


def test_protocol_detector_recognizes_every_tag_idiom(tmp_path):
    """All three dispatch shapes count as handling — bare ``t``,
    ``msg["t"]``, ``msg.get("t")`` — and non-tag compares (phases,
    kinds) contribute nothing."""
    serving = tmp_path / "deepspeed_tpu" / "serving"
    serving.mkdir(parents=True)
    (serving / "b.py").write_text(
        "def recv(msg, t, phase):\n"
        "    a = {'t': 'x1'}\n"
        "    b = {'t': 'x2'}\n"
        "    c = {'t': 'x3'}\n"
        "    if t == 'x1': pass\n"
        "    if msg['t'] == 'x2': pass\n"
        "    if msg.get('t') == 'x3': pass\n"
        "    if phase == 'xfer': pass\n"           # not a tag compare
        "    return a, b, c\n")
    assert protocol_lint.check_repo(str(tmp_path)) == []


def test_protocol_detector_pins_ready_placement_fields(tmp_path):
    """A worker's ``ready`` names where it computes (``platform``,
    ``device_kind``): the router's only evidence that an engine worker
    is on the chip and not quietly on the CPU. A literal that drops
    either key is flagged; the repo's own sender carries both."""
    serving = tmp_path / "deepspeed_tpu" / "serving"
    serving.mkdir(parents=True)
    (serving / "c.py").write_text(
        "def hello(ch, t):\n"
        "    ch.send({'t': 'ready', 'pid': 1, 'platform': 'tpu'})\n"
        "    if t == 'ready': pass\n")
    out = protocol_lint.check_repo(str(tmp_path))
    assert len(out) == 1, "\n".join(out)
    assert "'ready'" in out[0] and "'device_kind'" in out[0]
    assert ":2:" in out[0]
    sent, _, errs = protocol_lint.scan_file(os.path.join(
        ROOT, "deepspeed_tpu", "serving", "replica.py"))
    assert errs == [] and "ready" in sent


def test_deadline_lint_covers_deploy_waits(tmp_path):
    """serving/deploy.py is inside the linted package: an unbounded
    wait smuggled into the deploy orchestrator (a blocking join on a
    quiesce, a bare select) is flagged like anywhere else in serving/ —
    every quiesce/probe/rollback wait must be deadline-bounded."""
    serving = tmp_path / "deepspeed_tpu" / "serving"
    serving.mkdir(parents=True)
    (serving / "deploy.py").write_text(
        "import select\n"
        "def wait_for_swap(t, fds):\n"
        "    t.join()\n"                           # flagged: unbounded
        "    select.select(fds, [], [])\n")        # flagged: no timeout
    out = deadline_lint.check_repo(str(tmp_path))
    assert len(out) == 2
    assert ":3:" in out[0] and ".join()" in out[0]
    assert ":4:" in out[1] and "select()" in out[1]


def test_state_invariant_detector_pins_weight_version_to_swap_api(
        tmp_path):
    """The weight-version stamp gates cross-replica KV transfer: a
    stray assignment anywhere outside the swap API (including annotated
    and private-alias forms) is flagged; the swap API itself and the
    constructors stay legal, as does the router-side ``wv`` mirror."""
    bad = tmp_path / "deepspeed_tpu" / "serving" / "router.py"
    bad.parent.mkdir(parents=True)
    bad.write_text(
        "class Router:\n"
        "    def _handle(self, h, eng):\n"
        "        eng.weight_version = {'id': 9}\n"   # flagged
        "        eng._weight_version: dict = {}\n"   # flagged (annotated)
        "        h.wv = {'id': 9}\n"                 # mirror attr: ok
        "        v = eng.weight_version\n")          # read: ok
    out = state_lint.check_file(str(bad))
    assert len(out) == 2
    assert ":3:" in out[0] and "weight_version" in out[0]
    assert ":4:" in out[1]
    ok = tmp_path / "deepspeed_tpu" / "inference" / "engine_v2.py"
    ok.parent.mkdir(parents=True)
    ok.write_text(
        "class Engine:\n"
        "    def __init__(self):\n"
        "        self._weight_version = {'id': 0}\n"     # ctor: ok
        "    def swap_weights(self, wid):\n"
        "        self._weight_version = {'id': wid}\n"   # swap API: ok
        "    def sneaky(self, wid):\n"
        "        self._weight_version = {'id': wid}\n")  # flagged
    out = state_lint.check_file(str(ok))
    assert len(out) == 1 and ":7:" in out[0]


# --- KV tiering (inference/kvtier.py) ---------------------------------------

def test_deadline_lint_covers_kvtier_waits(tmp_path):
    """inference/kvtier.py is lint-covered even though it lives outside
    serving/: the tier runs inside the replica event loop's admission
    and eviction paths, so an unbounded wait there wedges heartbeats
    exactly like a serving wait would (check_deadlines.EXTRA_FILES)."""
    # the real tree must carry the file (a rename would silently
    # de-cover it — EXTRA_FILES names it, this pins it exists)
    assert os.path.isfile(os.path.join(
        ROOT, "deepspeed_tpu", "inference", "kvtier.py"))
    serving = tmp_path / "deepspeed_tpu" / "serving"
    serving.mkdir(parents=True)
    kvt = tmp_path / "deepspeed_tpu" / "inference" / "kvtier.py"
    kvt.parent.mkdir(parents=True)
    kvt.write_text(
        "def read_spill(lock):\n"
        "    lock.acquire()\n"                     # flagged: unbounded
        "    lock.acquire(timeout=0.5)\n")         # bounded: ok
    out = deadline_lint.check_repo(str(tmp_path))
    assert len(out) == 1 and ":2:" in out[0] and "kvtier" in out[0]


def test_state_invariant_detector_pins_tier_mutators(tmp_path):
    """The KV tier's demote/promote mutators (absorb/extract/
    set_weight_version/close) are pinned to the wrappers next to the
    refcounted adopt API; reads (probe/has/stats/digest) stay legal
    anywhere, and the implementation file itself is exempt."""
    bad = tmp_path / "deepspeed_tpu" / "serving" / "router.py"
    bad.parent.mkdir(parents=True)
    bad.write_text(
        "def hijack(rep, bundle):\n"
        "    rep.kv_tier.absorb(bundle)\n"         # flagged
        "    rep._kv_tier.extract([], 16)\n"       # alias: flagged
        "    rep.kv_tier.probe([])\n"              # read: ok
        "    return rep.kv_tier.stats()\n")        # read: ok
    out = state_lint.check_file(str(bad))
    assert len(out) == 2, "\n".join(out)
    assert ":2:" in out[0] and "kv_tier.absorb()" in out[0]
    assert ":3:" in out[1] and "kv_tier.extract()" in out[1]
    # the allowlisted wrappers keep their access
    ok = tmp_path / "deepspeed_tpu" / "inference" / "engine_v2.py"
    ok.parent.mkdir(parents=True)
    ok.write_text(
        "class Engine:\n"
        "    def _demote_evicted(self, chains):\n"
        "        self._kv_tier.absorb(chains)\n"       # sink: ok
        "    def _tier_promote(self, toks):\n"
        "        return self._kv_tier.extract(toks, 16)\n"   # ok
        "    def sneaky(self):\n"
        "        self._kv_tier.close()\n")             # flagged
    out = state_lint.check_file(str(ok))
    assert len(out) == 1 and ":7:" in out[0]
    # kvtier.py itself (the implementation) is exempt
    impl = tmp_path / "deepspeed_tpu" / "inference" / "kvtier.py"
    impl.write_text(
        "class KVTier:\n"
        "    def helper(self):\n"
        "        self.kv_tier.absorb(None)\n")
    assert state_lint.check_file(str(impl)) == []


def test_state_invariant_detector_pins_evict_sink_attach(tmp_path):
    """The prefix cache's eviction sink is the demotion hook: assigning
    it anywhere outside the attach sites could silently redirect (or
    drop) demotions — flagged like every other ownership mutation."""
    bad = tmp_path / "deepspeed_tpu" / "serving" / "workload.py"
    bad.parent.mkdir(parents=True)
    bad.write_text(
        "def hijack(pc):\n"
        "    pc.evict_sink = None\n"                   # flagged
        "    s = pc.evict_sink\n")                     # read: ok
    out = state_lint.check_file(str(bad))
    assert len(out) == 1 and ":2:" in out[0] and "evict_sink" in out[0]
    ok = tmp_path / "deepspeed_tpu" / "inference" / "engine_v2.py"
    ok.parent.mkdir(parents=True)
    ok.write_text(
        "class Engine:\n"
        "    def __init__(self):\n"
        "        self._prefix_cache.evict_sink = self._demote_evicted\n")
    assert state_lint.check_file(str(ok)) == []


def test_repo_attn_dispatch_routes_through_registry():
    """Tree-verify dispatch pin: the kernel-vs-gather decision for BOTH
    decode and tree modes is attn_registry's static per-engine selection,
    consulted in exactly one forward site — the one module that imports
    the kernel. Ad-hoc conditionals are how the tree branch silently
    pinned the gather formulation for 10 PRs."""
    violations = state_lint.check_attn_registry(ROOT)
    assert violations == [], "\n".join(violations)


#: the blessed shape: the forward imports the kernel and consults both
#: selections; the engine computes them once and reads them to count
_FORWARD_OK = (
    "from ..ops.pallas.paged_attention import (paged_ragged_attention,\n"
    "                                          paged_work_list)\n"
    "class RaggedForward:\n"
    "    def __call__(self, tree_mode):\n"
    "        sel = self.attn_tree_sel if tree_mode else self.attn_decode_sel\n"
    "        if sel.is_pallas:\n"
    "            return paged_ragged_attention()\n")
_ENGINE_OK = (
    "from .forward import RaggedForward\n"
    "class Engine:\n"
    "    def __init__(self):\n"
    "        self._attn_decode_sel = select_attention(mode='x')\n"
    "        self._attn_tree_sel = select_attention(mode='y')\n"
    "        if self._attn_tree_sel.is_pallas:\n"   # init pin compose
    "            pass\n"
    "    def _emit_attn_kernel(self, mode):\n"
    "        return self._attn_decode_sel.path\n")


def _plant(tmp_path, engine: str, forward: str):
    inf = tmp_path / "deepspeed_tpu" / "inference"
    inf.mkdir(parents=True, exist_ok=True)
    (inf / "engine_v2.py").write_text(engine)
    (inf / "forward.py").write_text(forward)
    return inf


def test_attn_registry_detector_flags_adhoc_dispatch(tmp_path):
    """A planted import of the kernel outside the forward's module, and a
    second site that reads or rebinds the selections, are flagged."""
    inf = _plant(
        tmp_path,
        "from ..ops.pallas.paged_attention import paged_ragged_attention\n"
        + _ENGINE_OK +
        "    def _sneaky(self):\n"
        "        self._attn_tree_sel = select_attention(mode='z')\n"  # call + store
        "        if self._attn_decode_sel.is_pallas:\n"              # read
        "            return paged_ragged_attention()\n",
        _FORWARD_OK)
    out = state_lint.check_attn_registry(str(tmp_path))
    assert len(out) == 4, "\n".join(out)
    assert "engine_v2.py:1:" in out[0] \
        and "imports paged_ragged_attention" in out[0]
    assert ":12:" in out[1] and "_attn_tree_sel" in out[1] \
        and "assigned" in out[1]
    assert ":12:" in out[2] and "select_attention()" in out[2]
    assert ":13:" in out[3] and "_attn_decode_sel" in out[3] \
        and "read" in out[3]
    # an import inside a function of any other module is one too
    (inf / "other.py").write_text(
        "def f():\n"
        "    from ..ops.pallas.paged_attention import paged_work_list\n")
    out = state_lint.check_attn_registry(str(tmp_path))
    assert len(out) == 5 and any(
        "other.py:2:" in v and "paged_work_list" in v for v in out)
    (inf / "other.py").unlink()
    # the blessed shape is clean
    _plant(tmp_path, _ENGINE_OK, _FORWARD_OK)
    assert state_lint.check_attn_registry(str(tmp_path)) == []
    # no engine file at all (foreign checkout): not this lint's problem
    assert state_lint.check_attn_registry(str(tmp_path / "nope")) == []


def test_attn_registry_detector_requires_selection_reads(tmp_path):
    """A forward that consults NEITHER selection means dispatch regressed
    to an inline conditional — flagged even with zero other violations."""
    _plant(
        tmp_path, _ENGINE_OK,
        "from ..ops.pallas.paged_attention import paged_ragged_attention\n"
        "class RaggedForward:\n"
        "    def __call__(self):\n"
        "        if self.use_pallas:\n"
        "            return paged_ragged_attention()\n")
    out = state_lint.check_attn_registry(str(tmp_path))
    assert len(out) == 1, "\n".join(out)
    assert "no longer consults the attention registry" in out[0]


def test_protocol_lint_pins_push_vocabulary_both_directions():
    """The anticipatory-push vocabulary (PR 20) is wired end to end:
    the push planner constructs the declinable kv_push offer and the
    replica dispatches it; the replica constructs kv_push_ok/kv_push_no
    and the router dispatches those.  Same rationale as the gang and
    elastic pins above — a pair deleted from BOTH sides vanishes from
    both maps and would pass the generic closure check."""
    sent: dict = {}
    handled: dict = {}
    serving = os.path.join(ROOT, "deepspeed_tpu", "serving")
    for dirpath, _, files in os.walk(serving):
        for f in sorted(files):
            if f.endswith(".py"):
                s, h, errs = protocol_lint.scan_file(
                    os.path.join(dirpath, f))
                assert errs == []
                sent.update(s)
                handled.update(h)
    for tag in ("kv_push", "kv_push_ok", "kv_push_no"):
        assert tag in sent, f"{tag} no longer constructed"
        assert tag in handled, f"{tag} no longer dispatched"
    assert "push.py" in sent["kv_push"]
    assert "replica.py" in handled["kv_push"]
    assert "replica.py" in sent["kv_push_ok"]
    assert "router.py" in handled["kv_push_ok"]
    assert "replica.py" in sent["kv_push_no"]
    assert "router.py" in handled["kv_push_no"]
    # promote_hint is a put FIELD, not a "t" tag: pin both ends in
    # source so the overlap promise can't silently lose its producer
    # or its consumer
    with open(os.path.join(serving, "router.py")) as fh:
        assert "promote_hint" in fh.read()
    with open(os.path.join(serving, "replica.py")) as fh:
        assert "promote_hint" in fh.read()


def test_deadline_lint_covers_push_planner(tmp_path):
    """serving/push.py ticks inside the router poll loop: an unbounded
    wait while scoring candidates or launching an offer would stall
    every heartbeat, so the deadline lint must sweep it like the rest
    of serving/ — no carve-out for new control-plane files."""
    serving = tmp_path / "deepspeed_tpu" / "serving"
    serving.mkdir(parents=True)
    (serving / "push.py").write_text(
        "def launch(proc, lock):\n"
        "    lock.acquire()\n"                     # flagged: unbounded
        "    proc.join(timeout=2.0)\n")            # bounded: ok
    out = deadline_lint.check_repo(str(tmp_path))
    assert len(out) == 1 and ":2:" in out[0]
    real = os.path.join(ROOT, "deepspeed_tpu", "serving", "push.py")
    assert os.path.exists(real)
    assert deadline_lint.check_repo(ROOT) == []


def test_state_invariant_detector_pins_two_phase_extract(tmp_path):
    """The two-phase promote mutators (extract_begin/extract_finish,
    PR 20) are pinned to the tier_promote_begin/tier_promote_finish
    wrappers exactly like the one-shot extract — a router or planner
    calling them directly would bypass the verify/adopt/release
    sequence that keeps a torn promote from being served."""
    bad = tmp_path / "deepspeed_tpu" / "serving" / "router.py"
    bad.parent.mkdir(parents=True)
    bad.write_text(
        "def hijack(rep):\n"
        "    rep.kv_tier.extract_begin([], 16)\n"      # flagged
        "    rep._kv_tier.extract_finish(None)\n"      # alias: flagged
        "    rep.kv_tier.probe([])\n")                 # read: ok
    out = state_lint.check_file(str(bad))
    assert len(out) == 2, "\n".join(out)
    assert ":2:" in out[0] and "kv_tier.extract_begin()" in out[0]
    assert ":3:" in out[1] and "kv_tier.extract_finish()" in out[1]
    # the allowlisted wrappers keep their access (engine and replica)
    for fname in ("engine_v2.py", "replica.py"):
        sub = "inference" if fname == "engine_v2.py" else "serving"
        ok = tmp_path / "deepspeed_tpu" / sub / fname
        ok.parent.mkdir(parents=True, exist_ok=True)
        ok.write_text(
            "class B:\n"
            "    def tier_promote_begin(self, toks):\n"
            "        return self._kv_tier.extract_begin(toks, 16)\n"
            "    def tier_promote_finish(self, h, ahead=False):\n"
            "        return self._kv_tier.extract_finish(h)\n")
        assert state_lint.check_file(str(ok)) == [], fname
    # kvtier.py itself (the implementation) is exempt
    impl = tmp_path / "deepspeed_tpu" / "inference" / "kvtier.py"
    impl.write_text(
        "class KVTier:\n"
        "    def helper(self):\n"
        "        self.kv_tier.extract_begin(None, 16)\n")
    assert state_lint.check_file(str(impl)) == []


# --- a record kind's state is written once a program -------------------------

def test_record_writes_are_pinned_to_the_programs_one_write(tmp_path):
    """``merge_records`` (the write of a "conv" kind's record a slot) is
    legal in ``forward.merge_step`` and in ``engine_v2._window_program``
    and nowhere else: a second write inside a program, or one from the
    host, could land a decode window's record over a half-prefilled
    sequence's."""
    bad = tmp_path / "engine_v2.py"
    bad.write_text(
        "def _window_program(self, W):\n"
        "    def run(pool, new):\n"
        "        return merge_records(pool, slots, new)\n"      # allowed
        "    return run\n"
        "def _program(self, T):\n"
        "    return forward.merge_records(pool, slots, new)\n")  # flagged
    out = state_lint.check_file(str(bad))
    assert len(out) == 1 and ":6:" in out[0] and "merge_records" in out[0]
    ok = tmp_path / "forward.py"
    ok.write_text(
        "def merge_records(records, write_slots, new):\n"
        "    return records\n"
        "def merge_step(pools, slots, k_ys, v_ys, T):\n"
        "    return merge_records(pools[0], slots[0], k_ys[0])\n")
    assert state_lint.check_file(str(ok)) == []
