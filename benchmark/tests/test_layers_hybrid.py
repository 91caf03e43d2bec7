"""The readers of a model of two kinds of layer on a hand-made context
(what the traced serving run hands over) and on a recorded trace with
hand-made scope maps: numbers where the program publishes what they read,
None — not an exception — where it does not (a parent commit)."""
import os

import pytest

from benchmark import common, work, work_hybrid
from benchmark.layers import (_hybrid, _scopes, decode_attn_window_share,
                              hybrid_attn_roofline, kv_global_pool_peak_util,
                              kv_window_pool_peak_util, window_clip_share)
from benchmark.runners import serve_hybrid

HERE = os.path.dirname(os.path.abspath(__file__))
CFG = {"hidden_size": 64, "num_attention_heads": 4, "num_key_value_heads": 2,
       "head_dim": 32, "intermediate_size": 32, "num_hidden_layers": 4,
       "vocab_size": 100, "sliding_window_size": 16,
       "sliding_window_layout": [0, 1, 1, 1] * 13,
       "preset": "tiny-smallthinker", "overrides": {}}
ENGINE = {"block_size": 8, "num_blocks": 64, "max_seqs": 4, "chunk": 16,
          "max_seq_len": 256}
PEAKS = {"bf16_flops": 1e12, "hbm_bytes_per_s": 1e9}


def ctx(by_program=None, stats=None, total=None):
    st = {"window_iters": 8, "decode_steps": 2, **(stats or {})}
    return {"trace": {"ops_by_program": by_program or {}, "programs": {}},
            "stats": st, "stats_total": total or {}, "model": CFG,
            "engine": ENGINE, "peaks": PEAKS,
            # uid 1: prompt 10, from 20 to 30 tokens (all decode, the
            # window binds from 16 on); uid 2 prefilling only
            "progress": ({1: (10, 20), 2: (50, 0)}, {1: (10, 30), 2: (50, 32)}),
            "uid_of": {}, "done_len": {}}


def test_pool_utilisation_by_kind():
    total = {"kv_blocks_peak_full": 21, "kv_blocks_peak_window": 10}
    # pools as the program derives them: 64 blocks; 4 slots x a ring of
    # ceil((16 + 16) / 8) + 1 = 5, + the trash block
    assert _hybrid.pool_blocks(ENGINE, CFG) == {"full": 64, "window": 21}
    assert kv_global_pool_peak_util.read(ctx(total=total)) == \
        pytest.approx(100 * 21 / 63)
    assert kv_window_pool_peak_util.read(ctx(total=total)) == 50.0
    assert kv_window_pool_peak_util.read(ctx()) is None     # no counters


def test_window_clip_share():
    assert window_clip_share.read(ctx(stats={
        "attn_pages_unclipped": 400, "attn_pages_clipped": 100})) == 25.0
    assert window_clip_share.read(ctx()) is None
    assert window_clip_share.read(ctx(stats={
        "attn_pages_unclipped": 0, "attn_pages_clipped": 0})) is None


def test_hybrid_roofline_counts_the_window():
    # 10 iterations x 4 layers = 40 calls of the one paged kernel
    c = ctx({"jit_run": {"paged_attn_decode": [0.004, 32.0]},
             "jit_step_decode": {"paged_attn_decode": [0.001, 8.0]},
             "jit_step_prefill": {"paged_attn_prefill": [9.0, 4.0]}})
    span = work_hybrid.decode_span(CFG, 20, 30)
    assert span["bytes"] == 2 * 2 * 32 * 2 * (
        sum(range(21, 31)) + 3 * 10 * 16)
    least, _ = work.least_time_s(span, PEAKS)
    assert hybrid_attn_roofline.read(c) == pytest.approx(100 * least / 0.005)
    # a configuration of one kind (no layout), a program without the
    # kernel: nothing to read
    one = dict(c, model={k: v for k, v in CFG.items()
                         if k != "sliding_window_layout"})
    assert hybrid_attn_roofline.read(one) is None
    assert hybrid_attn_roofline.read(ctx({"jit_run": {"fusion": [1., 40.]}})) \
        is None


def test_window_share_on_paper(monkeypatch):
    """One device, ``jit_run`` once (100-600): a window layer's kernel
    (100-250), a global layer's (250-350), their work list under
    ``attn_core`` alone (350-400), a matmul (400-600). The window layers'
    core is 150 of 500; the accepted scope table still files all three
    under ``attn_core``."""
    from benchmark import reduce_trace
    from benchmark.tests.test_reduce_trace import plane
    from deepspeed_tpu.profiling import trace as ptrace

    d0 = plane("/device:TPU:0", XLA_Modules=[("jit_run(11)", 100, 600)],
               XLA_Ops=[("%paged_attn_decode.2 = ...", 100, 250),
                        ("%paged_attn_decode.3 = ...", 250, 350),
                        ("%fusion.5 = ...", 350, 400),
                        ("%fusion.7 = ...", 400, 600)])
    host = plane("/host:CPU", python=[("bench_window", 0, 1000)])
    core = "jit(run)/while/body/attn_core/"
    maps = {"jit_run": {"programs": 1, "hlo_bytes": 1, "ops": {
        "paged_attn_decode.2": core + "attn_window/paged_attn_decode/call",
        "paged_attn_decode.3": core + "attn_full/paged_attn_decode/call",
        "fusion.5": core + "cumsum", "fusion.7": "jit(run)/head/dot"}}}
    planes = [host, d0]
    c = {"trace": reduce_trace.summarize(planes), "scope_maps": maps,
         "trace_dir": "hand-made"}
    monkeypatch.setattr(_scopes, "_tables", {
        "hand-made": _scopes.join(planes, maps, ptrace.scope_of)})
    monkeypatch.setattr(_hybrid, "_tables", {
        "hand-made": _scopes.join(planes, maps, _hybrid.kind_key)})
    assert decode_attn_window_share.read(c) == pytest.approx(30.0)
    assert _scopes.share(c, _scopes.DECODE_PROGRAMS, ("attn_core",)) \
        == pytest.approx(60.0)
    # a program that names no kind inside attn_core (one kind of layer, a
    # parent commit): nothing to read
    flat = {"jit_run": dict(maps["jit_run"], ops={
        k: v.replace("attn_window/", "").replace("attn_full/", "")
        for k, v in maps["jit_run"]["ops"].items()})}
    monkeypatch.setattr(_hybrid, "_tables", {
        "hand-made": _scopes.join(planes, flat, _hybrid.kind_key)})
    assert decode_attn_window_share.read(c) is None


def test_read_layers_leaves_out_what_a_parent_cannot_give():
    entry = {"metrics": {"per_layer": [
        {"name": n, "unit": "%"} for n in (
            "hybrid_attn_roofline", "decode_attn_window_share",
            "kv_window_pool_peak_util", "kv_global_pool_peak_util",
            "window_clip_share")]}}
    parent = ctx({"jit_run": {"fusion": [0.1, 3.0]}})
    parent["trace"]["host_only"] = True
    assert common.read_layers(entry, parent) == {}


class _Req:
    def __init__(self, n_prompt, n_new=40):
        self.prompt, self.max_new = [1] * n_prompt, n_new
        self.tokens = [2] * n_new


def test_the_reference_sample_is_stratified_by_class():
    spec = {"requests": 4, "long_prompt_min": 6144, "max_tokens": 15872,
            "rows": 16, "rows_tail": 8}
    ok = [_Req(100 + i) for i in range(20)] + [_Req(7000 + i) for i in range(3)]
    sample = serve_hybrid.stratified_sample(ok, spec, seed=9)
    assert sorted(len(r.prompt) >= 6144 for r in sample) == \
        [False, False, True, True]
    assert serve_hybrid.stratified_sample(ok, spec, 9) == sample   # seeded
    # too few long streams served: no sample, and the run is not correct
    assert serve_hybrid.stratified_sample(ok[:21], spec, 9) is None
    # rows: the last prompt position and the first served tokens', then the
    # last eight — all past the window for a long stream
    rows = serve_hybrid.checked_rows(7000, 40, spec)
    assert rows.tolist() == list(range(6999, 7015)) + list(range(7031, 7039))
    assert serve_hybrid.checked_rows(7000, 10, spec).tolist() == \
        list(range(6999, 7009))


def test_a_fixed_schedule_is_served_as_open_loop():
    t = {"kind": "open_loop_fixed",
         "arrivals": {"process": "fixed_slots", "rate": 2.0},
         "lead_in_s": 1.0, "pattern": ["short", "long"], "classes": {
             c: {"prompt_len": {"dist": "uniform", "min": 4, "max": 9},
                 "output_len": {"dist": "uniform", "min": 2, "max": 5}}
             for c in ("short", "long")}}
    s = serve_hybrid.generate(t, 3, 100, 9.0)
    assert s["kind"] == "open_loop" and len(s["requests"]) == 18
