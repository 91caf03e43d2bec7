"""The decode block (PR 52): the decode-ready rows ride every prefill step as
a ``[max_seqs, 1]`` segment of the same program — one token each from the
same weights, pool and staged K/V as a ``[S, 1]`` step's. Held here: the
streams against pure steps, a block row's logits against the decode
program's, what the block may write, the compiled menu, the counters, and
an eos inside a block."""
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deepspeed_tpu.inference import engine_v2
from deepspeed_tpu.inference.engine_v2 import InferenceEngineV2
from deepspeed_tpu.models import build_model

ENGINE = {"block_size": 16, "num_blocks": 128, "max_seqs": 6, "chunk": 16,
          "max_seq_len": 256, "decode_window": 4, "dtype": jnp.float32}
#: dense, every layer sparse, a ring kind beside a table kind, records
PRESETS = ["tiny-llama", "tiny-olmoe", "tiny-smallthinker", "tiny-lfm2-moe"]
#: a mixed queue: (step of arrival, prompt tokens, tokens asked) — long
#: prompts (several chunks each) arrive while the earlier requests decode
QUEUE = [(0, 5, 24), (0, 70, 20), (3, 9, 24), (6, 100, 16), (12, 40, 24)]
FUSED = ("fused_steps", "fused_decode_tokens", "fused_empty_steps")


def build(preset, rides=True, **over):
    eng = InferenceEngineV2(build_model(preset, dtype=jnp.float32),
                            config={**ENGINE, **over},
                            rng=jax.random.PRNGKey(0))
    eng.scheduler.decode_rides = rides
    return eng


def prompts(eng, queue=QUEUE):
    rng = np.random.default_rng(0)
    return [rng.integers(0, eng.mcfg.vocab_size, n).tolist()
            for _, n, _ in queue]


def serve(eng, queue=QUEUE, eos=None, each_step=None):
    """Put the queue's requests at their steps and step the engine until
    all are flushed; ``{uid: stream}``."""
    todo = list(enumerate(zip(queue, prompts(eng, queue))))
    out, live, i = {}, set(), 0
    while todo or live:
        while todo and todo[0][1][0][0] <= i:
            uid, ((_, _, new), prompt) = todo.pop(0)
            eng.put(uid, prompt, max_new_tokens=new, eos_token_id=eos)
            live.add(uid)
        eng.step()
        if each_step is not None:
            each_step(eng)
        i += 1
        for uid in sorted(live):
            seq = eng.state.seqs.get(uid)
            if seq is not None and seq.done and not eng._uid_inflight(uid):
                out[uid] = eng.flush(uid)
                live.remove(uid)
    return out


def drive_to_live_block(eng):
    """Step ``eng`` through the queue until its scheduler's next plan is a
    prefill plan whose block carries decoders, at least one of them reading
    its token from the device (``use_last``); the plan, undispatched."""
    todo = list(enumerate(zip(QUEUE, prompts(eng))))
    for i in range(64):
        while todo and todo[0][1][0][0] <= i:
            uid, ((_, _, new), prompt) = todo.pop(0)
            eng.put(uid, prompt, max_new_tokens=new)
        has_prefill, _ = eng.scheduler.pending_kinds()
        if has_prefill and not eng._serve_toggle:
            plan = eng.scheduler.next_step()
            if plan.kind == "prefill" and plan.block.active.sum() >= 2 \
                    and plan.block.use_last.any() and plan.do_sample.any() \
                    and int(plan.positions.max()) > 0:
                return plan
        eng.step()
    raise AssertionError("no prefill plan with a live block came")


def run_program(eng, plan, monkeypatch):
    """``plan``'s step program on COPIES of the engine's state (it donates
    its pool and last tokens): ``(pools, last_tok, toks, logits)``, the
    logits those it sampled from."""
    seen = []
    sample = engine_v2.sample_logits

    def tapped(logits, rng, **kw):
        jax.debug.callback(lambda a: seen.append(np.asarray(a)), logits,
                           ordered=True)
        return sample(logits, rng, **kw)

    monkeypatch.setattr(engine_v2, "sample_logits", tapped)
    T, rows = plan.token_ids.shape[1], plan.token_ids.shape[0]
    eng._programs.pop((T, rows), None)          # trace it with the tap
    pools, last, toks = eng._program(T, rows)(
        eng.params, jax.tree.map(jnp.copy, eng.kv_pool),
        jnp.copy(eng._last_tok), *eng._plan_args(plan),
        jax.random.PRNGKey(3))
    jax.block_until_ready(toks)
    monkeypatch.setattr(engine_v2, "sample_logits", sample)
    eng._programs.pop((T, rows), None)
    return (jax.tree.map(np.asarray, pools), np.asarray(last),
            np.asarray(toks), seen[-1])


@pytest.mark.parametrize("preset", PRESETS)
def test_a_mixed_queue_serves_the_streams_of_pure_steps(preset, monkeypatch):
    """(a) every request's stream is the stream of the same engine driven
    through pure steps; (b) a block row's logits are the ``[S, 1]`` decode
    program's on the same state; (c) the block writes its live rows' one
    token (and record) and trash, nothing else — a prefilling sequence's
    record, ring pages and table are as the pure prefill step leaves
    them."""
    eng = build(preset)
    pure = serve(build(preset, rides=False))
    fused = serve(eng)
    assert fused == pure
    assert eng.stats["fused_decode_tokens"] > 0
    assert {u: len(t) for u, t in fused.items()} == \
        {u: q[2] for u, q in enumerate(QUEUE)}

    # --- one fused step, taken apart on the same state --------------------
    eng = build(preset)
    plan = drive_to_live_block(eng)
    block, rows = plan.block, plan.token_ids.shape[0]
    live = np.asarray(block.uids) >= 0
    pools_f, last_f, toks_f, logits_f = run_program(eng, plan, monkeypatch)
    pools_d, last_d, toks_d, logits_d = run_program(eng, block, monkeypatch)
    # (b) the block's rows: the decode program's logits and tokens
    np.testing.assert_allclose(logits_f[rows:][live], logits_d[live],
                               rtol=0, atol=1e-5)
    np.testing.assert_array_equal(toks_f[rows:][live], toks_d[live])
    np.testing.assert_array_equal(last_f[block.row_slots[live]],
                                  toks_d[live])
    # ... and the plan's rows: the same program's with a block of no row
    bare = plan.block
    plan.block = None
    pools_p, last_p, toks_p, logits_p = run_program(eng, plan, monkeypatch)
    plan.block = bare
    np.testing.assert_allclose(logits_f[:rows], logits_p[:rows], rtol=0,
                               atol=1e-5)
    sampled = np.asarray(plan.do_sample).astype(bool)
    np.testing.assert_array_equal(toks_f[:rows][sampled],
                                  toks_p[:rows][sampled])
    # a sequence the plan sampled keeps its token on the device, whatever
    # block row sits in its slot
    np.testing.assert_array_equal(last_f[plan.row_slots[sampled]],
                                  toks_p[:rows][sampled])
    # (c) against the pure prefill step's pools, the fused step's differ
    # only where a LIVE block row wrote its token; a dead row wrote trash
    bs = eng.config.block_size
    of_kind = {eng._kinds[0].name: (block.slot_map, None), **block.more}
    for k, pf, pp, pd in zip(eng._kinds, pools_f, pools_p, pools_d):
        if k.is_record:
            slots = set(np.nonzero(np.any(np.abs(pf - pp) > 1e-5,
                                          axis=(0, 2, 3)))[0])
            assert slots <= set(block.row_slots[live]) | {eng.state.max_seqs}
            assert set(of_kind[k.name][0][~live]) == {eng.state.max_seqs}
            np.testing.assert_allclose(pf[:, block.row_slots[live]],
                                       pd[:, block.row_slots[live]],
                                       rtol=0, atol=1e-5)
            continue
        slot_map = np.asarray(of_kind[k.name][0])[:, 0]
        assert (slot_map[~live] == 0).all()                  # trash block
        changed = np.argwhere(np.any(np.abs(pf - pp) > 1e-5,
                                     axis=(0, 1, 2, 5)))
        flat = {int(b) * bs + int(o) for b, o in changed if b != 0}
        assert flat and flat <= set(slot_map[live].tolist())
        for s in slot_map[live]:
            np.testing.assert_allclose(pf[:, :, :, s // bs, s % bs],
                                       pd[:, :, :, s // bs, s % bs],
                                       rtol=0, atol=1e-5)
    # the tables are the host's and the block holds only the decoders'
    assert not set(block.uids) & set(u for u in plan.uids if u >= 0)


def test_the_compiled_menu_does_not_know_the_block():
    """``program_shape_menu()`` and the keys of ``_programs`` after the
    drill of ``benchmark/traffic/mixed-queue-fixed.json``'s ``rehearse``
    are those of an engine whose prefill steps stay pure (the parent's)."""
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(root, "benchmark", "traffic",
                           "mixed-queue-fixed.json")) as f:
        drill = json.load(f)["rehearse"]["warmup"]["drill"]
    keys, menus = [], []
    for rides in (True, False):
        eng = build("tiny-smallthinker", rides=rides, decode_window=8)
        uid = 0
        for k, plen, max_new in drill:
            queue = [(0, int(plen), int(max_new))] * int(k)
            rng = np.random.default_rng(uid)
            for _, n, new in queue:
                eng.put(uid, rng.integers(0, 256, n).tolist(),
                        max_new_tokens=new)
                uid += 1
            while any(not s.done for s in eng.state.seqs.values()) \
                    or eng._inflight:
                eng.step()
            for u in list(eng.state.seqs):
                eng.flush(u)
        keys.append(set(eng._programs))
        menus.append(eng.scheduler.program_shape_menu())
    assert keys[0] == keys[1] and menus[0] == menus[1]
    prefill = {k for k in keys[0] if isinstance(k[0], int) and k[0] > 1}
    assert prefill and prefill <= set(menus[0])
    assert (1, ENGINE["max_seqs"]) in keys[0]        # the drill's last step


def test_counters_book_block_tokens_outside_the_decode_iterations():
    """A fused step books ``fused_steps`` / ``fused_decode_tokens`` (or
    ``fused_empty_steps``) and its tokens in ``decode_tokens`` — and moves
    neither ``decode_steps`` nor ``window_iters``, which the benchmark
    divides the decode programs' device time by."""
    eng = build("tiny-olmoe")
    seen, last = [], {}

    def each_step(eng):
        now = {k: eng.stats[k] for k in FUSED + (
            "prefill_steps", "decode_steps", "windows", "dispatches",
            "moe_routed_rows")}
        if last and now["prefill_steps"] > last["prefill_steps"]:
            seen.append({k: now[k] - last[k] for k in now})
        last.clear()
        last.update(now)

    out = serve(eng, each_step=each_step)
    st = eng.stats
    assert st["fused_steps"] + st["fused_empty_steps"] == st["prefill_steps"]
    assert st["fused_steps"] > 0 and st["fused_decode_tokens"] > 0
    assert st["decode_tokens"] == sum(len(t) for t in out.values()) \
        - sum(1 for q in QUEUE if q[1] > 1)   # (a prompt's first: prefill's)
    carried = [d for d in seen if d["fused_decode_tokens"]]
    assert carried
    top_k, layers = eng.mcfg.moe.top_k, eng.mcfg.num_layers
    for d in seen:
        # a prefill step's dispatch: no decode step, no window
        assert d["dispatches"] == 1
        assert d["decode_steps"] == 0 and d["windows"] == 0
        assert d["fused_steps"] + d["fused_empty_steps"] == 1
        # the block's rows are rows of the program's routed layers
        assert d["moe_routed_rows"] >= \
            d["fused_decode_tokens"] * top_k * layers
    # window iterations are booked at the commit, by the windows alone
    assert st["window_iters"] <= st["windows"] * ENGINE["decode_window"]
    pure = build("tiny-olmoe", rides=False)
    serve(pure)
    assert pure.stats["fused_steps"] == pure.stats["fused_decode_tokens"] == 0
    assert pure.stats["fused_empty_steps"] == pure.stats["prefill_steps"]
    assert pure.stats["decode_tokens"] == st["decode_tokens"]


def test_an_eos_inside_a_block_ends_the_stream_there():
    """A block row that samples its eos: the tokens the pipeline made past
    it are dropped at commit, the stream ends at the eos, and its blocks
    are released once."""
    # (no prefix cache: a released sequence's pages go back to the free
    # list, so the count below closes)
    fresh = lambda: build("tiny-llama", prefix_cache=False)
    streams = serve(fresh())
    # an eos for request 0 that it first meets in a token a block carried:
    # take it from a fused step's commit
    eng = fresh()
    fused_tokens = {}
    commit = eng._commit_entry

    def tapped(entry, toks_h, emitted):
        before = {u: len(t) for u, t in eng._results.items()}
        commit(entry, toks_h, emitted)
        if entry["kind"] == "plan" and entry["plan"].block is not None:
            for uid in entry["plan"].block.uids:
                if uid >= 0 and len(eng._results[uid]) > before[uid]:
                    fused_tokens.setdefault(uid, []).append(before[uid])

    eng._commit_entry = tapped
    assert serve(eng) == streams
    uid, at = next((u, i[0]) for u, i in sorted(fused_tokens.items())
                   if streams[u][i[0]] not in streams[u][:i[0]])
    eos = streams[uid][at]
    eng = fresh()
    total = eng.state.allocator.free_blocks
    cut = serve(eng, eos=eos)
    assert cut[uid] == streams[uid][:at + 1]
    for u, t in cut.items():        # every stream ends at its first eos
        want = streams[u]
        if eos in want:
            want = want[:want.index(eos) + 1]
        assert t == want
    assert not eng.state.seqs and not eng._inflight
    assert eng.state.allocator.free_blocks == total
    eng.state.audit()


@pytest.mark.parametrize("max_seqs, rings", [(4, True), (3, False)])
def test_ring_tp_needs_the_whole_stream_to_divide(max_seqs, rings):
    """Under ring collective-matmul TP the residual stream is split over
    the tensor axis by TOKEN: a prefill program rings where its chunks'
    tokens and the block's rows together divide the axis (4 slots at
    tensor 2) and falls back, counted, where they do not (3 slots: an odd
    stream); the tokens are those of the blocking path either way."""
    from deepspeed_tpu.models.transformer import ModelConfig, TransformerLM
    from deepspeed_tpu.parallel.topology import MeshConfig, MeshTopology

    mcfg = ModelConfig(vocab_size=128, hidden_size=64, num_layers=2,
                       num_heads=4, max_seq_len=256,
                       position_embedding="rope", norm="rmsnorm",
                       activation="silu_glu", dtype=jnp.float32)
    queue = [(0, 6, 12), (2, 40, 8), (5, 21, 8)]

    def run(overlap):
        eng = InferenceEngineV2(
            TransformerLM(mcfg), None, {
                **ENGINE, "tensor_parallel": 2, "max_seqs": max_seqs,
                "tp_overlap": overlap, "use_pallas_decode": False},
            topology=MeshTopology(MeshConfig(tensor=2, data=1)),
            rng=jax.random.PRNGKey(0))
        return serve(eng, queue), dict(eng.stats)

    on, stats_on = run(True)
    off, _ = run(False)
    assert on == off
    assert stats_on["fused_decode_tokens"] > 0
    assert (stats_on["tp_ring_matmuls"] > 0) == rings, stats_on
    assert (stats_on["tp_fallbacks"] == 0) == rings, stats_on
