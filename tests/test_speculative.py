"""Speculative decoding (inference/speculative.py + engine_v2 wiring):
candidate-tree/acceptance host-logic units, StateManager's rollback-aware
provisional API under the full-pool audit (tier 1), and slow-tier engine
parity — the acceptance criterion is that GREEDY speculative decode is
bit-identical to baseline greedy decode for BOTH proposer backends, and
that mid-tree rejections followed by ``flush`` leave the pool clean."""
import numpy as np
import pytest

from deepspeed_tpu.inference import PrefixCache, StateManager
from deepspeed_tpu.inference.scheduler import (SpecAcceptTracker,
                                               SplitFuseScheduler)
from deepspeed_tpu.inference.speculative import (DraftModelProposer,
                                                 NGramProposer, SpecTree,
                                                 accept_walk, build_tree)


# ---------------------------------------------------------------------------
# candidate trees + exact acceptance (host-only, tier 1)
# ---------------------------------------------------------------------------

def test_build_tree_merges_shared_prefixes():
    t = build_tree(10, [[5, 6, 7], [5, 8], [9]])
    # node 1 (token 5) is shared by the first two chains: one verify slot
    assert t.tokens == [10, 5, 6, 7, 8, 9]
    assert t.parents == [-1, 0, 1, 2, 1, 0]
    assert t.n_nodes == 6 and t.n_candidates == 5
    assert t.depths() == [0, 1, 2, 3, 2, 1]
    assert t.children() == [[1, 5], [2, 4], [3], [], [], []]
    # max_nodes truncates in chain order, root always kept
    t2 = build_tree(10, [[5, 6, 7], [5, 8], [9]], max_nodes=3)
    assert t2.tokens == [10, 5, 6]
    # empty chains → a root-only tree (a plain decode step)
    t3 = build_tree(10, [])
    assert t3.n_nodes == 1 and t3.n_candidates == 0


def test_ancestor_mask_is_ancestors_only():
    t = build_tree(10, [[5, 6], [7]])          # 10 → {5 → 6, 7}
    m = t.ancestor_mask(6)
    assert m.shape == (6, 6)
    exp = np.zeros((6, 6), np.uint8)
    exp[0, 0] = 1                              # root sees itself
    exp[1, [0, 1]] = 1                         # 5 sees root + self
    exp[2, [0, 1, 2]] = 1                      # 6 sees root, 5, self
    exp[3, [0, 3]] = 1                         # 7 sees root + self — NOT 5
    np.testing.assert_array_equal(m, exp)      # padding rows stay zero
    with pytest.raises(ValueError):
        t.ancestor_mask(2)


def test_accept_walk_full_mid_and_root_rejection():
    t = build_tree(10, [[5, 6], [7]])          # nodes: 10, 5, 6, 7
    # full accept: root samples 5, node-5 samples 6, node-6 samples 42 —
    # 42 has no child, so it is the bonus token; visited = accepted path
    acc, vis = accept_walk(t, [5, 6, 42, 0])
    assert acc == [5, 6, 42] and vis == [0, 1, 2]
    # mid-tree rejection: root samples 5, node-5 samples 9 (≠ 6) — the 9
    # is the exact correction sample, the 6 subtree is dead
    acc, vis = accept_walk(t, [5, 9, 0, 0])
    assert acc == [5, 9] and vis == [0, 1]
    # immediate rejection: root samples 8 (neither 5 nor 7) — exactly one
    # token emitted, exactly the root visited: a plain decode step
    acc, vis = accept_walk(t, [8, 0, 0, 0])
    assert acc == [8] and vis == [0]
    # the OTHER branch accepts too
    acc, vis = accept_walk(t, [7, 0, 0, 11])
    assert acc == [7, 11] and vis == [0, 3]


def test_ngram_proposer_prompt_lookup():
    p = NGramProposer(depth=3, ngram_max=2, ngram_min=1, branches=2)
    # history: "1 2 3 4 ... 1 2" — the trailing (1, 2) matched earlier
    # continues with (3, 4, 1); a second, distinct-first-token branch
    # comes from the shorter 1-gram match ("2" followed by 3 — same first
    # token, skipped; dedup keeps branches genuinely diverse)
    hist = [1, 2, 3, 4, 1, 2, 3, 4, 1, 2]
    trees = p.propose({7: (hist, 3)})
    t = trees[7]
    assert t.tokens[0] == 2                    # root = committed last token
    assert t.n_candidates >= 3
    assert t.tokens[1:4] == [3, 4, 1]          # deepest match wins
    # no repeated n-gram → root-only tree, never an error
    t2 = p.propose({8: ([5, 6, 7, 8], 3)})[8]
    assert t2.n_candidates == 0
    # depth 0 (budget exhausted) → root-only even with matches
    t3 = p.propose({9: (hist, 0)})[9]
    assert t3.n_candidates == 0
    with pytest.raises(ValueError):
        NGramProposer(depth=2, ngram_max=1, ngram_min=2)


def test_ngram_probe_predicts_misses():
    """The probe engine_v2 consults before paying a pipeline drain: True
    iff propose() would build at least one candidate."""
    p = NGramProposer(depth=3, ngram_max=2, ngram_min=1)
    hist = [1, 2, 3, 4, 1, 2, 3, 4, 1, 2]
    assert p.probe({1: (hist, 3)})
    assert not p.probe({1: ([5, 6, 7, 8], 3)})     # no repeated n-gram
    assert not p.probe({1: (hist, 0)})             # budget-capped depth
    assert not p.probe({})
    # probe agrees with propose on mixed batches
    assert p.probe({1: ([5, 6, 7, 8], 3), 2: (hist, 3)})
    # existence check is branch-independent (first-hit scan)
    assert NGramProposer(depth=3, branches=4).probe({1: (hist, 3)})


def test_accept_tracker_adapts_depth():
    tr = SpecAcceptTracker(base_depth=4, shrink_below=0.35, grow_above=0.75)
    assert tr.depth(1) == 4
    # all-reject rounds shrink one step at a time down to the floor
    assert tr.observe(1, 4, 0) == (4, 3)
    assert tr.observe(1, 4, 0) == (3, 2)
    tr.observe(1, 4, 0)
    tr.observe(1, 4, 0)
    assert tr.depth(1) == 1
    tr.observe(1, 4, 0)
    assert tr.depth(1) == 1                    # floor holds
    # sustained acceptance grows back toward (never past) base
    for _ in range(8):
        tr.observe(1, 4, 4)
    assert tr.depth(1) == 4
    # pending prefill caps the returned depth (decode_window_mixed_cap)
    assert tr.depth(1, prefill_pending=True, mixed_cap=2) == 2
    assert tr.depth(1, prefill_pending=False, mixed_cap=2) == 4
    # root-only rounds carry no signal
    assert tr.observe(1, 0, 0) is None
    assert tr.rate(2) == 1.0                   # unseen uid: optimistic
    tr.forget(1)
    assert tr.depth(1) == 4


# ---------------------------------------------------------------------------
# StateManager rollback-aware provisional API (host-only, tier 1)
# ---------------------------------------------------------------------------

def _decode_ready(st, sched, uid, first_tok=7):
    """Commit prefill chunks until the sequence is decode-ready."""
    while st.seqs[uid].pending_tokens > 1 or not st.seqs[uid].n_generated:
        p = sched.next_step()
        assert p is not None
        sampled = {u: first_tok for _, u in p.sampled_rows()}
        sched.commit(p, sampled)


def test_provision_bounds_and_commit_speculative():
    st = StateManager(num_blocks=32, block_size=4, max_seqs=2,
                      max_blocks_per_seq=8)
    sched = SplitFuseScheduler(st, chunk=8)
    st.admit(1, [1, 2, 3, 4, 5], max_new_tokens=8)
    with pytest.raises(RuntimeError):
        st.provision(1, 2)                     # still prefilling
    _decode_ready(st, sched, 1)
    seq = st.seqs[1]
    assert seq.pending_tokens == 1 and seq.n_generated == 1
    with pytest.raises(ValueError):
        st.provision(1, -1)
    with pytest.raises(RuntimeError):
        st.provision(1, 7)                     # rem=7: depth+bonus > budget
    st.provision(1, 3)
    assert seq.n_provisional == 3
    st.audit()                                 # marker is audit-clean
    with pytest.raises(ValueError):
        st.commit_speculative(1, [])           # a verify commits >= 1
    with pytest.raises(RuntimeError):
        st.commit_speculative(1, [9] * 5)      # > provisioned + bonus
    n0 = seq.n_computed
    out = st.commit_speculative(1, [11, 12, 13])
    assert out == [11, 12, 13]
    assert seq.n_provisional == 0
    assert seq.n_computed == n0 + 3 and seq.tokens[-3:] == [11, 12, 13]
    assert seq.n_sched == seq.n_computed and seq.n_inflight == 0
    st.audit()
    # rollback: marker cleared, nothing else moves
    st.provision(1, 2)
    st.rollback_provisional(1)
    assert seq.n_provisional == 0
    st.rollback_provisional(99)                # unknown uid: no-op
    st.release(1)
    st.audit()
    assert st.allocator.free_blocks == 31


def test_commit_speculative_truncates_at_eos():
    st = StateManager(num_blocks=32, block_size=4, max_seqs=2,
                      max_blocks_per_seq=8)
    sched = SplitFuseScheduler(st, chunk=8)
    st.admit(1, [1, 2, 3], max_new_tokens=8, eos_id=42)
    _decode_ready(st, sched, 1)
    st.provision(1, 3)
    out = st.commit_speculative(1, [11, 42, 13])
    assert out == [11, 42] and st.seqs[1].done
    st.release(1)
    st.audit()


def test_rewind_floors_to_page_boundary_and_guards():
    st = StateManager(num_blocks=32, block_size=4, max_seqs=2,
                      max_blocks_per_seq=8)
    sched = SplitFuseScheduler(st, chunk=16)
    st.admit(1, list(range(10)), max_new_tokens=8)
    _decode_ready(st, sched, 1)
    seq = st.seqs[1]
    assert seq.n_computed == 10 and len(seq.tokens) == 11
    # divergent last token: lcp=10, capped at len-1=10, floored to 8
    st.rewind(1, list(range(10)) + [99])
    assert seq.n_computed == 8 and seq.n_sched == 8
    assert seq.n_generated == 0 and not seq.done
    assert seq.tokens[-1] == 99
    st.audit()
    with pytest.raises(ValueError):
        st.rewind(1, [])
    with pytest.raises(RuntimeError):
        st.rewind(1, list(range(25)))          # 5-block reservation = 20
    st.release(1)


def test_rewind_longer_history_caps_budget_to_reservation():
    """Regression: rewinding to a LONGER history (the draft-mirror resync
    after the target committed tokens) restarts the generation budget —
    which must be CAPPED to the admit-time block reservation, or an
    un-rewound mirror (target done, client delaying flush) decodes past
    its pages and the scheduler indexes off the block list."""
    st = StateManager(num_blocks=32, block_size=4, max_seqs=2,
                      max_blocks_per_seq=8)
    sched = SplitFuseScheduler(st, chunk=16)
    st.admit(1, [1, 2, 3, 4], max_new_tokens=6)    # 3-block reservation
    _decode_ready(st, sched, 1)
    seq = st.seqs[1]
    cap = len(seq.blocks) * 4
    st.rewind(1, list(range(9)))                   # longer history
    assert seq.max_new_tokens - seq.n_generated == cap - 9
    while not seq.done:                            # decode to exhaustion
        p = sched.next_step()
        assert p is not None
        sched.commit(p, {u: 7 for _, u in p.sampled_rows()})
    assert len(seq.tokens) <= cap                  # never past the pages
    st.audit()
    st.release(1)
    st.audit()


def test_rewind_never_rewrites_shared_prefix_pages():
    st = StateManager(num_blocks=32, block_size=4, max_seqs=2,
                      max_blocks_per_seq=8)
    st.attach_prefix_cache(PrefixCache(4))
    sched = SplitFuseScheduler(st, chunk=16)
    st.admit(1, list(range(8)), max_new_tokens=2)
    while not st.seqs[1].done:
        p = sched.next_step()
        sched.commit(p, {u: 7 for _, u in p.sampled_rows()})
    st.release(1)                              # publishes pages [0:8]
    st.admit(2, list(range(8)) + [100, 101], max_new_tokens=4)
    assert st.seqs[2].n_shared_blocks == 2
    with pytest.raises(RuntimeError):
        st.rewind(2, [0, 1, 2, 99, 4, 5, 6, 7, 100])   # inside shared pages
    with pytest.raises(RuntimeError):
        st.rewind(2, list(range(8)))           # not past the shared region
    st.rewind(2, list(range(8)) + [100])       # legal: suffix-only cut
    st.audit()
    st.release(2)
    st.audit()


def test_audit_flags_provisional_overrun():
    """A provisional extent past the block reservation must trip the
    audit (the invariant the engine's depth cap + provision() bound
    protect)."""
    st = StateManager(num_blocks=32, block_size=4, max_seqs=2,
                      max_blocks_per_seq=8)
    sched = SplitFuseScheduler(st, chunk=8)
    st.admit(1, [1, 2, 3], max_new_tokens=4)
    _decode_ready(st, sched, 1)
    st.provision(1, 2)
    st.seqs[1].blocks = st.seqs[1].blocks[:1]  # simulate corruption
    with pytest.raises(AssertionError):
        st.audit()


# ---------------------------------------------------------------------------
# engine_v2 parity + rollback (slow tier: engine jit compiles)
# ---------------------------------------------------------------------------

_CFG = {"block_size": 8, "num_blocks": 96, "max_seqs": 4, "chunk": 16,
        "max_seq_len": 192}


def _prompts():
    r = np.random.default_rng(0)
    motif = [int(t) for t in r.integers(0, 256, 8)]
    rep = (motif * 6)[:40]                     # prompt-lookup heaven
    rnd1 = [int(t) for t in r.integers(0, 256, 12)]
    rnd2 = [int(t) for t in r.integers(0, 256, 23)]
    return [rep, rnd1, rnd2]


@pytest.fixture(scope="module")
def spec_baseline():
    """Target model + a baseline (spec off) engine + its greedy streams."""
    import jax

    from deepspeed_tpu.inference import InferenceEngineV2
    from deepspeed_tpu.models import build_model

    model = build_model("tiny-gpt2", hidden_size=256, num_heads=4)
    base = InferenceEngineV2(model, config=dict(_CFG),
                             rng=jax.random.PRNGKey(5))
    ref = base.generate(_prompts(), max_new_tokens=16)
    return model, base, ref


def _spec_engine(model, monkeypatch, **over):
    """Engine with the SAME weights as the baseline (same model + same
    init rng — a built engine's params are layer-stacked in place, so
    they cannot be handed to a second constructor) and the audit on.

    Pins ``spec_verify_pallas=False``: these greedy-parity goldens were
    calibrated against the XLA gather verify formulation, and under bf16
    compute the Pallas tree kernel rounds sub-ulp near-ties differently
    (both formulations are correct to ~1 bf16 ulp; the degenerate tiny
    model sits EXACTLY on ties, so formulation choice is observable in
    the streams). The kernel path gets its own bit-identity coverage in
    test_v2_spec_pallas_vs_gather_stream_bit_identity below."""
    import jax

    from deepspeed_tpu.inference import InferenceEngineV2

    monkeypatch.setenv("DS_TPU_STATE_AUDIT", "1")
    cfg = {**_CFG, "spec_decode": "ngram", "spec_verify_pallas": False,
           **{k: v for k, v in over.items() if not k.startswith("draft")}}
    return InferenceEngineV2(
        model, config=cfg, rng=jax.random.PRNGKey(5),
        draft_model=over.get("draft_model"),
        draft_params=over.get("draft_params"),
        draft_rng=over.get("draft_rng"))


@pytest.mark.slow
def test_v2_spec_ngram_greedy_parity_across_depths(spec_baseline,
                                                   monkeypatch):
    """THE acceptance criterion: greedy spec decode (n-gram backend) emits
    bit-identical token streams to baseline greedy decode, across draft
    depths, with the full-pool audit on after every release. The
    repetitive prompt must actually exercise acceptance (tokens-per-verify
    > 1), the random prompts exercise rejection — parity must hold on
    both."""
    model, _, ref = spec_baseline
    for depth in (2, 4):
        eng = _spec_engine(model, monkeypatch, spec_depth=depth)
        got = eng.generate(_prompts(), max_new_tokens=16)
        for g, r in zip(got, ref):
            np.testing.assert_array_equal(np.asarray(g), np.asarray(r))
        st = eng.stats
        assert st["spec_rounds"] > 0 and st["spec_verifies"] > 0
        assert st["spec_proposed"] > 0
        # the motif prompt's candidates hit: > 1 token per verify forward
        assert (st["spec_accepted"] + st["spec_verifies"]) \
            / st["spec_verifies"] > 1.0
        assert 0.0 <= st["spec_accept_rate"] <= 1.0
        assert st["spec_steps_saved"] > 0
        eng.state.audit()                      # drained pool, no leftovers


@pytest.mark.slow
def test_v2_spec_draft_model_greedy_parity(spec_baseline, monkeypatch):
    """Draft-model backend, both regimes: a same-weights draft (argmax
    always agrees → near-total acceptance) and an independently
    initialized weak draft (mostly rejects) — greedy streams must be
    bit-identical to baseline either way; exactness never depends on the
    proposer being any good."""
    import jax

    model, base, ref = spec_baseline
    # strong: the draft IS the target — greedy proposals always verify
    eng = _spec_engine(model, monkeypatch, spec_decode="draft",
                       spec_depth=3, draft_model=model,
                       draft_rng=jax.random.PRNGKey(5))
    got = eng.generate(_prompts(), max_new_tokens=16)
    for g, r in zip(got, ref):
        np.testing.assert_array_equal(np.asarray(g), np.asarray(r))
    st = eng.stats
    assert st["spec_accept_rate"] > 0.9
    assert (st["spec_accepted"] + st["spec_verifies"]) \
        / st["spec_verifies"] > 2.0
    eng.state.audit()
    assert eng._draft_engine.state.allocator.free_blocks \
        == eng._draft_engine.config.num_blocks - 1     # mirrors released

    # weak: different init → proposals mostly reject, parity still exact
    eng = _spec_engine(model, monkeypatch, spec_decode="draft",
                       spec_depth=3, draft_model=model,
                       draft_rng=jax.random.PRNGKey(123))
    got = eng.generate(_prompts(), max_new_tokens=16)
    for g, r in zip(got, ref):
        np.testing.assert_array_equal(np.asarray(g), np.asarray(r))
    eng.state.audit()


@pytest.mark.slow
def test_v2_spec_mid_stream_flush_rolls_back_clean(spec_baseline,
                                                   monkeypatch):
    """Mid-tree rejections happen, then the request is flushed MID-stream
    (client hangup) with the audit on: release must leave no stale or
    double-owned page, and the pool must reconcile exactly."""
    model, base, _ = spec_baseline
    eng = _spec_engine(model, monkeypatch, spec_depth=4)
    rep = _prompts()[0]
    eng.put(1, rep, max_new_tokens=24)
    eng.put(2, list(np.random.default_rng(7).integers(0, 256, 15)),
            max_new_tokens=24)
    for _ in range(64):
        eng.step()
        if eng.stats["spec_rounds"] >= 2 \
                and not eng.query(1).get("done", True):
            break
    assert eng.stats["spec_rounds"] >= 1
    eng.flush(1)                               # mid-stream: audit runs here
    eng.flush(2)
    eng.state.audit()
    # pool reconciles exactly: everything is free or trie-published (the
    # auto prefix cache is ON here — release donates full computed pages,
    # which must hold ONLY committed tokens, never rejected candidates)
    assert eng.state.allocator.free_blocks \
        + eng.state.prefix_cache.cached_blocks == _CFG["num_blocks"] - 1
    assert not eng.state.seqs


@pytest.mark.slow
def test_v2_spec_with_prefix_cache_publishes_only_committed(spec_baseline,
                                                            monkeypatch):
    """Spec × shared-prefix cache: pages published at release must hold
    ONLY committed tokens (rejected candidates never reach the pool), so
    a second request warm-matching the prefix still greedy-matches the
    baseline stream, with the audit asserting trie ownership throughout."""
    model, base, _ = spec_baseline
    rep = _prompts()[0]
    tail = [9, 1, 250, 3]
    ref = base.generate([rep + tail], max_new_tokens=12)[0]

    eng = _spec_engine(model, monkeypatch, spec_depth=4,
                       prefix_cache=True)
    first = eng.generate([rep + tail], max_new_tokens=12)[0]
    np.testing.assert_array_equal(np.asarray(first), np.asarray(ref))
    hit0 = eng.stats["prefix_hit_tokens"]
    again = eng.generate([rep + tail], max_new_tokens=12)[0]
    np.testing.assert_array_equal(np.asarray(again), np.asarray(ref))
    assert eng.stats["prefix_hit_tokens"] > hit0   # warm path actually hit
    eng.state.audit()


@pytest.mark.slow
def test_v2_spec_config_gates(spec_baseline):
    """Refusals: ring mode, forced tp_overlap, unknown backend, missing
    draft model, degenerate depths."""
    import jax

    from deepspeed_tpu.inference import InferenceEngineV2
    from deepspeed_tpu.models import build_model

    model, base, _ = spec_baseline
    rng = jax.random.PRNGKey(5)
    for bad in ({"spec_decode": "medusa"}, {"spec_decode": "draft"},
                {"spec_decode": "ngram", "spec_depth": 0},
                {"spec_decode": "ngram", "spec_max_nodes": 1},
                {"spec_decode": "ngram", "tp_overlap": True}):
        with pytest.raises(ValueError):
            InferenceEngineV2(model, config={**_CFG, **bad}, rng=rng)
    win = build_model("tiny-gpt2", hidden_size=256, num_heads=4,
                      sliding_window=8, max_seq_len=256)
    with pytest.raises(ValueError):
        InferenceEngineV2(win, config={**_CFG, "max_seq_len": 256,
                                       "spec_decode": "ngram"}, rng=rng)


@pytest.mark.slow
def test_v2_spec_depth_adapts_and_notes_flight_recorder(spec_baseline,
                                                        monkeypatch):
    """A workload whose lookup proposals keep rejecting must shrink the
    tenant's draft depth (accept-rate EMA below the shrink threshold) and
    drop a ``spec_depth_adapt`` note in the flight recorder."""
    model, base, _ = spec_baseline
    eng = _spec_engine(model, monkeypatch, spec_depth=4)
    # repeated bigrams whose continuations disagree: matches fire (so
    # candidates ARE proposed) but the model's actual next token is
    # unrelated — near-zero acceptance
    r = np.random.default_rng(11)
    prompt = []
    for _ in range(12):
        prompt += [3, 5, int(r.integers(10, 250))]
    eng.generate([prompt], max_new_tokens=20)
    st = eng.stats
    assert st["spec_proposed"] > 0
    events = [e for e in eng._telem.recorder.events()
              if e["kind"] == "spec_depth_adapt"]
    if st["spec_accept_rate"] < 0.3:           # proposals did reject
        assert events and events[0]["old"] > events[0]["new"]
    for e in events:
        assert 0.0 <= e["rate"] <= 1.0


# ---------------------------------------------------------------------------
# tree-verify Pallas kernel: interpret-mode parity + registry (tier 1)
# ---------------------------------------------------------------------------

def _tree_kernel_case(kv_dtype, G):
    """Branchy SpecTree kernel inputs + slot geometry. Two live slots at
    different roots, one EMPTY slot (seq_len 0 — the kernel emits zeros
    there; the gather reference skips it, so parity compares live slots
    only), parents [-1,0,0,1,2,3]: two depth-1 siblings sharing one
    position, a two-node chain under one of them."""
    import jax.numpy as jnp

    rng = np.random.default_rng(42)
    S, T, KV, D, bs, nb, mp, Ts, L = 3, 6, 2, 64, 16, 8, 4, 8, 2
    H = KV * G
    pool = jnp.asarray(rng.standard_normal((L, 2, KV, nb, bs, D)) * 0.3,
                       kv_dtype)
    q = jnp.asarray(rng.standard_normal((S, T, H, D)) * 0.3, jnp.float32)
    ks = jnp.asarray(rng.standard_normal((S, KV, Ts, D)) * 0.3, jnp.float32)
    vs = jnp.asarray(rng.standard_normal((S, KV, Ts, D)) * 0.3, jnp.float32)
    tables = np.zeros((S, mp), np.int32)
    for s in range(S):
        tables[s] = rng.permutation(np.arange(1, nb))[:mp]
    parents = [-1, 0, 0, 1, 2, 3]
    depth = [0, 1, 1, 2, 2, 3]
    pos = np.zeros((S, T), np.int32)
    mask = np.zeros((S, T, T), np.uint8)
    lens = np.zeros((S,), np.int32)
    sst = np.zeros((S,), np.int32)
    for s in range(2):                         # slot 2 stays empty
        root = 10 + s * 7
        pos[s] = [root + d for d in depth]
        for i in range(T):
            j = i
            while j != -1:
                mask[s, i, j] = 1
                j = parents[j]
        lens[s] = root + 1 + max(depth)
        sst[s] = root
    mask[2, np.arange(T), np.arange(T)] = 1    # self-bit convention
    return (pool, q, ks, vs, jnp.asarray(tables), jnp.asarray(lens),
            jnp.asarray(pos[:, 0].copy()), jnp.asarray(sst),
            jnp.asarray(pos), jnp.asarray(mask))


def _tree_gather_ref(pool, q, ks, vs, tables, lens, sst, pos, mask, G,
                     window=None):
    """NumPy gather formulation of tree-verify attention (f32 all the
    way): per-slot page gather for the committed pool context, ancestors
    mask verbatim over the stage columns."""
    pool = np.asarray(pool, np.float32)
    q, ks, vs = (np.asarray(a, np.float32) for a in (q, ks, vs))
    tables, lens, sst = (np.asarray(a) for a in (tables, lens, sst))
    pos, mask = np.asarray(pos), np.asarray(mask)
    S, T, H, D = q.shape
    bs = pool.shape[4]
    out = np.zeros_like(q)
    for s in range(S):
        if lens[s] == 0:
            continue
        ctx = int(sst[s])
        blocks = tables[s][np.arange(ctx) // bs]
        offs = np.arange(ctx) % bs
        K = pool[1, 0, :, blocks, offs]        # layer_index=1: [ctx,KV,D]
        V = pool[1, 1, :, blocks, offs]
        for t in range(T):
            for h in range(H):
                kv = h // G
                kcol = np.concatenate([K[:, kv], ks[s, kv, :T]], 0)
                vcol = np.concatenate([V[:, kv], vs[s, kv, :T]], 0)
                sc = (q[s, t, h] @ kcol.T) / np.sqrt(D)
                m = np.zeros(ctx + T, bool)
                cpos = np.arange(ctx)
                m[:ctx] = cpos <= pos[s, t]
                if window:
                    m[:ctx] &= cpos > pos[s, t] - window
                m[ctx:] = mask[s, t] > 0
                sc = np.where(m, sc, -np.inf)
                w = np.exp(sc - sc.max())
                out[s, t, h] = (w / w.sum()) @ vcol
    return out


@pytest.mark.parametrize("kv_dtype,G,tol", [
    ("float32", 1, 2e-5), ("float32", 2, 2e-5),
    ("bfloat16", 2, 3e-2), ("float8_e4m3fn", 2, 8e-2),
])
def test_tree_kernel_parity_matrix(kv_dtype, G, tol):
    """Interpret-mode CPU parity, Pallas tree-verify vs the gather
    formulation: storage dtype x GQA x sliding window on a branchy
    SpecTree with an empty slot riding along. Reduced-precision pools
    compare against the round-tripped values so the tolerance isolates the
    kernel's fused q/p casts (the fp8 bound matches the long-context
    p-prescale test in test_paged_work_list.py).
    Ring mode is absent by design: the engine refuses spec decode in
    rolling-ring mode, so tree x ring is unreachable."""
    import jax.numpy as jnp

    from deepspeed_tpu.ops.pallas.paged_attention import \
        paged_ragged_attention

    dt = jnp.dtype(kv_dtype)
    pool, q, ks, vs, tables, lens, qst, sst, pos, mask = \
        _tree_kernel_case(dt, G)
    ref_pool = pool.astype(jnp.float32)        # round-tripped storage values
    live = np.asarray(lens) > 0
    for window in (None, 7):
        want = _tree_gather_ref(ref_pool, q, ks, vs, tables, lens, sst,
                                pos, mask, G, window=window)
        got = paged_ragged_attention(
            q, pool, ks, vs, tables, lens, qst, sst, block_size=16,
            layer_index=jnp.int32(1), window=window,
            tree_positions=pos, tree_mask=mask, interpret=True)
        err = np.abs(np.asarray(got, np.float32)[live] - want[live]).max()
        assert err < tol, (kv_dtype, G, window, err)


def test_tree_kernel_parity_stage_spans_pages():
    """More tree nodes than one page holds (T=20 at block_size 16 → a
    32-row stage in 2 page-sized tiles): the ancestors mask reaches the
    kernel as [S, stage_page, rows, page] so each grid step takes its
    page's columns whole (the layout Mosaic accepts at S > 1 —
    tests/test_chip_compile.py compiles it). Parity vs the gather
    formulation pins that the re-layout kept node/column order."""
    import jax.numpy as jnp

    from deepspeed_tpu.ops.pallas.paged_attention import \
        paged_ragged_attention

    rng = np.random.default_rng(7)
    S, T, KV, G, D, bs, nb, mp, Ts = 2, 20, 2, 2, 64, 16, 8, 4, 32
    pool = jnp.asarray(rng.standard_normal((2, 2, KV, nb, bs, D)) * 0.3,
                       jnp.float32)
    q = jnp.asarray(rng.standard_normal((S, T, KV * G, D)) * 0.3,
                    jnp.float32)
    ks = jnp.asarray(rng.standard_normal((S, KV, Ts, D)) * 0.3, jnp.float32)
    vs = jnp.asarray(rng.standard_normal((S, KV, Ts, D)) * 0.3, jnp.float32)
    tables = np.stack([rng.permutation(np.arange(1, nb))[:mp]
                       for _ in range(S)]).astype(np.int32)
    # two interleaved chains under one root: node i's parent is i-2
    parents = [-1, 0] + list(range(T - 2))
    depth = [0] * T
    for i in range(1, T):
        depth[i] = depth[parents[i]] + 1
    pos = np.zeros((S, T), np.int32)
    mask = np.zeros((S, T, T), np.uint8)
    lens = np.zeros((S,), np.int32)
    sst = np.zeros((S,), np.int32)
    for s in range(S):
        root = 9 + 11 * s
        pos[s] = [root + d for d in depth]
        for i in range(T):
            j = i
            while j != -1:
                mask[s, i, j] = 1
                j = parents[j]
        lens[s] = root + 1 + max(depth)
        sst[s] = root
    want = _tree_gather_ref(pool, q, ks, vs, tables, lens, sst, pos, mask,
                            G)
    got = paged_ragged_attention(
        q, pool, ks, vs, jnp.asarray(tables), jnp.asarray(lens),
        jnp.asarray(pos[:, 0].copy()), jnp.asarray(sst), block_size=bs,
        layer_index=jnp.int32(1), tree_positions=jnp.asarray(pos),
        tree_mask=jnp.asarray(mask), interpret=True)
    assert np.abs(np.asarray(got) - want).max() < 2e-5


def test_attn_registry_tree_gates():
    """select_attention's static gates: decode vs tree mode, the config
    pin reason, the tree-geometry gates (row tile, stage page tiling,
    mask VMEM budget) — every fallback carries a human-readable reason."""
    from deepspeed_tpu.inference.attn_registry import (
        TREE_MASK_VMEM_BYTES, select_attention)

    geo = dict(num_heads=8, kv_heads=8, head_dim=64, block_size=64,
               use_pallas=True)
    sel = select_attention(mode="decode", **geo)
    assert sel.is_pallas and sel.path == "pallas" and sel.mode == "decode"
    sel = select_attention(mode="tree", tree_nodes=8, stage_rows=8, **geo)
    assert sel.is_pallas and sel.reason == ""
    # config pin propagates its reason
    sel = select_attention(mode="tree", tree_nodes=8, stage_rows=8,
                           **{**geo, "use_pallas": False},
                           reason_not_usable="pinned off")
    assert not sel.is_pallas and sel.reason == "pinned off"
    # tree geometry gates, each with a distinct reason
    sel = select_attention(mode="tree", tree_nodes=0, stage_rows=8, **geo)
    assert not sel.is_pallas and "no tree nodes" in sel.reason
    sel = select_attention(mode="tree", tree_nodes=200, stage_rows=256,
                           **geo)
    assert not sel.is_pallas and "row" in sel.reason     # 200 rows > 128
    sel = select_attention(mode="tree", tree_nodes=8, stage_rows=72, **geo)
    assert not sel.is_pallas and "page" in sel.reason    # 72 % 64 != 0
    big = TREE_MASK_VMEM_BYTES // 4
    sel = select_attention(mode="tree", tree_nodes=4, stage_rows=big,
                           **{**geo, "block_size": big})
    assert not sel.is_pallas and "VMEM" in sel.reason
    with pytest.raises(ValueError):
        select_attention(mode="prefill", **geo)


def test_v2_engine_tree_selection_and_pin():
    """Engine wiring of the registry: the default tiny-gpt2 geometry
    selects the Pallas tree kernel; ``spec_verify_pallas=False`` pins the
    gather formulation (with the pin as reason); ``True`` on a geometry
    the kernel cannot serve refuses construction instead of silently
    falling back."""
    import jax

    from deepspeed_tpu.inference import InferenceEngineV2
    from deepspeed_tpu.models import build_model

    model = build_model("tiny-gpt2", hidden_size=256, num_heads=4)
    rng = jax.random.PRNGKey(5)
    eng = InferenceEngineV2(model, config=dict(_CFG), rng=rng)
    assert eng._attn_decode_sel.is_pallas
    assert eng._attn_tree_sel.is_pallas and eng._attn_tree_sel.mode == "tree"
    eng = InferenceEngineV2(
        model, config={**_CFG, "spec_verify_pallas": False}, rng=rng)
    assert eng._attn_decode_sel.is_pallas          # decode unaffected
    assert not eng._attn_tree_sel.is_pallas
    assert "spec_verify_pallas" in eng._attn_tree_sel.reason
    with pytest.raises(ValueError, match="spec_verify_pallas"):
        InferenceEngineV2(model, config={**_CFG, "use_pallas_decode": False,
                                         "spec_verify_pallas": True},
                          rng=rng)


def test_v2_spec_verify_dispatch_counted(monkeypatch):
    """No silent fallback: EVERY spec-verify dispatch lands in the
    stats formulation split (attn_{pallas,gather}_tree sums to the round
    count) and, with telemetry on, increments the labeled
    serving_attn_kernel_total counter."""
    import jax

    from deepspeed_tpu import telemetry as T
    from deepspeed_tpu.inference import InferenceEngineV2
    from deepspeed_tpu.models import build_model

    t = T.get_telemetry()
    prev = t.enabled
    t.reconfigure(enabled=True)
    try:
        c = t.registry.counter("serving_attn_kernel_total",
                               labels={"path": "pallas", "mode": "tree"})
        before = c.value
        model = build_model("tiny-gpt2", hidden_size=256, num_heads=4)
        eng = InferenceEngineV2(
            model, config={**_CFG, "spec_decode": "ngram", "spec_depth": 2},
            rng=jax.random.PRNGKey(5))
        assert eng._attn_tree_sel.is_pallas
        eng.generate([_prompts()[0][:24]], max_new_tokens=5)
        st = eng.stats
        assert st["spec_rounds"] > 0
        assert st["attn_pallas_tree"] + st["attn_gather_tree"] \
            == st["spec_rounds"]
        assert st["attn_gather_tree"] == 0         # pallas engine: no leaks
        assert c.value - before == st["attn_pallas_tree"]
    finally:
        t.reconfigure(enabled=prev)


@pytest.mark.slow
def test_v2_spec_pallas_vs_gather_stream_bit_identity(monkeypatch):
    """ISSUE 17 acceptance: one spec-decode engine pair, Pallas tree
    kernel vs gather formulation, greedy streams bit-identical end to
    end. Runs at float32 compute, where formulation rounding (~1e-7
    relative) sits far below any greedy top-2 gap — under bf16 the two
    formulations are both correct to ~1 ulp yet round EXACT logit ties
    differently (see _spec_engine), which is a property of the dtype,
    not of either kernel. Every round must land in the formulation
    counters: fallbacks would silently void the comparison."""
    import jax

    from deepspeed_tpu.inference import InferenceEngineV2
    from deepspeed_tpu.models import build_model

    monkeypatch.setenv("DS_TPU_STATE_AUDIT", "1")
    model = build_model("tiny-gpt2", hidden_size=256, num_heads=4)
    streams, stats = {}, {}
    for pin in (None, False):                      # auto → pallas; gather pin
        eng = InferenceEngineV2(
            model, config={**_CFG, "dtype": "float32",
                           "spec_decode": "ngram", "spec_depth": 4,
                           "spec_verify_pallas": pin},
            rng=jax.random.PRNGKey(5))
        path = eng._attn_tree_sel.path
        assert path == ("gather" if pin is False else "pallas")
        streams[path] = eng.generate(_prompts(), max_new_tokens=16)
        stats[path] = dict(eng.stats)
        eng.state.audit()
    for a, b in zip(streams["pallas"], streams["gather"]):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    for path in ("pallas", "gather"):
        st = stats[path]
        assert st["spec_rounds"] > 0
        assert st[f"attn_{path}_tree"] == st["spec_rounds"]
        other = "gather" if path == "pallas" else "pallas"
        assert st[f"attn_{other}_tree"] == 0
    # both engines did real speculative work, identically
    assert stats["pallas"]["spec_accepted"] == stats["gather"]["spec_accepted"]
