"""The routed-expert readers on a hand-made context (what the traced
serving run hands over) and on a synthetic scope table: numbers where the
program publishes what they read, None — not an exception — where it does
not (a parent commit)."""
import pytest

from benchmark import common, work_moe
from benchmark.layers import (_scopes, decode_moe_experts_share,
                              decode_moe_route_share, grouped_matmul_roofline,
                              moe_tile_fill)

CFG = {"hidden_size": 64, "num_attention_heads": 4, "num_key_value_heads": 4,
       "intermediate_size": 32, "num_hidden_layers": 2, "vocab_size": 100,
       "num_experts": 8, "num_experts_per_tok": 2}
PEAKS = {"bf16_flops": 1e12, "hbm_bytes_per_s": 1e9}


def ctx(by_program, **stats):
    st = {"window_iters": 8, "decode_steps": 2, **stats}
    return {"trace": {"ops_by_program": by_program, "programs": {}},
            "stats": st, "model": CFG, "peaks": PEAKS, "tokens_emitted": 40}


def test_tile_fill_is_routed_over_padded_rows():
    assert moe_tile_fill.read(ctx({}, moe_routed_rows=300,
                                  moe_padded_rows=1200)) == 25.0
    assert moe_tile_fill.read(ctx({})) is None          # no such counters
    assert moe_tile_fill.read(ctx({}, moe_routed_rows=0,
                                  moe_padded_rows=0)) is None


def test_grouped_roofline_from_the_kernels_own_time():
    # 10 decode iterations x 2 layers x 3 GEMMs = 60 calls; 40 tokens -> a
    # mean batch of 4 rows a step
    c = ctx({"jit_run": {"grouped_matmul_fwd": [0.004, 48.0],
                         "paged_attn_decode": [0.5, 16.0]},
             "jit_step_decode": {"grouped_matmul_fwd.1": [0.001, 12.0]},
             "jit_step_prefill": {"grouped_matmul_fwd": [9.0, 6.0]}})
    touched = work_moe.experts_touched_uniform(CFG, 4.0)
    one = work_moe.grouped_matmul(CFG, 8.0, touched)
    least = max(one["flops"] * 20 / 1e12, one["bytes"] * 20 / 1e9)
    assert grouped_matmul_roofline.read(c) == pytest.approx(
        100 * least / 0.005)


def test_grouped_roofline_refuses_a_wrong_call_count_loudly(capsys):
    c = ctx({"jit_run": {"grouped_matmul_fwd": [0.004, 200.0]}})
    assert grouped_matmul_roofline.read(c) is None
    assert "KERNEL NAME AMBIGUOUS" in capsys.readouterr().out
    # a program without the kernel (a dense model, a parent commit)
    assert grouped_matmul_roofline.read(
        ctx({"jit_run": {"fusion.3": [0.004, 60.0]}})) is None


def test_scope_shares_from_a_scope_table(monkeypatch):
    table = {"jit_run": {("moe_experts", "fwd"): 6.0, ("moe_router", "fwd"): 0.5,
                         ("moe_dispatch", "fwd"): 1.0,
                         ("moe_combine", "fwd"): 0.5, ("attn_core", "fwd"): 1.0},
             "jit_step_decode": {("moe_experts", "fwd"): 1.0},
             "jit_step_prefill": {("moe_experts", "fwd"): 100.0}}
    monkeypatch.setattr(_scopes, "table", lambda ctx: table)
    assert decode_moe_experts_share.read({}) == pytest.approx(70.0)
    assert decode_moe_route_share.read({}) == pytest.approx(20.0)
    # a dense program's table has no such scope: 0 %, and no table: None
    monkeypatch.setattr(_scopes, "table", lambda ctx: {
        "jit_run": {("ffn", "fwd"): 1.0}})
    assert decode_moe_experts_share.read({}) == 0.0
    monkeypatch.setattr(_scopes, "table", lambda ctx: None)
    assert decode_moe_route_share.read({}) is None


def test_read_layers_leaves_out_what_a_parent_cannot_give():
    """``common.read_layers`` with a context as a program WITHOUT the
    counters, the kernel or the scope maps gives it: every new metric is
    left out, nothing raises."""
    entry = {"metrics": {"per_layer": [
        {"name": n, "unit": "%"} for n in (
            "decode_moe_experts_share", "decode_moe_route_share",
            "grouped_matmul_roofline", "moe_tile_fill")]}}
    parent = ctx({"jit_run": {"fusion": [0.1, 3.0]}})
    parent["trace"]["host_only"] = True          # no scope table either
    assert common.read_layers(entry, parent) == {}
