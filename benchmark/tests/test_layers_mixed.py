"""The readers of a stack of unlike layers on a hand-made context (what the
traced serving run hands over) and on a synthetic scope table: numbers where
the program publishes what they read — counted for the layers that HAVE the
mechanism, never over 100 % by counting a layer that is not there — and
None, not an exception, where it does not (a parent commit, another
model)."""
import pytest

from benchmark import common, work_mixed
from benchmark.layers import (_scopes, conv_carry_share, decode_conv_share,
                              mixed_attn_roofline, mixed_gmm_roofline)

#: published layers conv, attention, conv, conv, conv: 1 attention layer, 4
#: expert layers
CFG = {"hidden_size": 64, "num_attention_heads": 4, "num_key_value_heads": 2,
       "intermediate_size": 96, "moe_intermediate_size": 32,
       "num_hidden_layers": 5, "first_layer": 1, "num_dense_layers": 1,
       "vocab_size": 100, "num_experts": 8, "num_experts_per_tok": 2,
       "conv_L_cache": 3,
       "layer_types": ["conv", "conv", "full_attention", "conv", "conv",
                       "conv"]}
PEAKS = {"bf16_flops": 1e12, "hbm_bytes_per_s": 1e9}


def ctx(by_program, model=CFG, **stats):
    st = {"window_iters": 8, "decode_steps": 2, **stats}
    return {"trace": {"ops_by_program": by_program, "programs": {}},
            "stats": st, "model": model, "peaks": PEAKS, "tokens_emitted": 40,
            "progress": ({7: (100, 100), 8: (30, 12)},
                         {7: (100, 106), 8: (30, 30)}),
            "uid_of": {}, "done_len": {}}


def test_carry_share_is_carried_over_dispatched_prefill_rows():
    assert conv_carry_share.read(ctx({}, conv_chunks=40,
                                     conv_chunks_carried=30)) == 75.0
    assert conv_carry_share.read(ctx({})) is None        # no such counters
    assert conv_carry_share.read(ctx({}, conv_chunks=0,
                                     conv_chunks_carried=0)) is None


def test_gmm_roofline_counts_the_expert_layers():
    # 10 decode iterations x 4 expert layers x 3 GEMMs = 120 calls; 40
    # tokens -> a mean batch of 4 rows a step
    c = ctx({"jit_run": {"grouped_matmul_fwd": [0.008, 96.0],
                         "paged_attn_decode": [0.5, 8.0]},
             "jit_step_decode": {"grouped_matmul_fwd.1": [0.002, 24.0]},
             "jit_step_prefill": {"grouped_matmul_fwd": [9.0, 6.0]}})
    touched = work_mixed.experts_touched_uniform(CFG, 4.0)
    one = work_mixed.grouped_matmul(CFG, 8.0, touched)
    least = max(one["flops"] * 40 / 1e12, one["bytes"] * 40 / 1e9)
    assert mixed_gmm_roofline.read(c) == pytest.approx(100 * least / 0.010)
    # counted over all 5 layers (the generic reader's way) the same kernel
    # time would read 5/4 of that: the layer that is not there
    assert 5 * least / 4 > least


def test_gmm_roofline_refuses_a_wrong_call_count_loudly(capsys):
    # 150 calls = 3 x FIVE layers x 10 iterations: not this stack's
    c = ctx({"jit_run": {"grouped_matmul_fwd": [0.004, 300.0]}})
    assert mixed_gmm_roofline.read(c) is None
    assert "KERNEL NAME AMBIGUOUS" in capsys.readouterr().out
    assert mixed_gmm_roofline.read(
        ctx({"jit_run": {"fusion.3": [0.004, 120.0]}})) is None
    # another model's configuration (no layer_types): nothing to read
    other = {k: v for k, v in CFG.items() if k != "layer_types"}
    assert mixed_gmm_roofline.read(
        ctx({"jit_run": {"grouped_matmul_fwd": [0.008, 120.0]}},
            model=other)) is None


def test_attn_roofline_counts_the_attention_layer_only(capsys):
    # one attention layer: 10 calls in 10 iterations. uid 7 decoded from
    # 100 to 106 tokens; uid 8 is still inside its prompt (12 of 30)
    c = ctx({"jit_run": {"paged_attn_decode": [0.004, 8.0]},
             "jit_step_decode": {"paged_attn_decode": [0.001, 2.0]},
             "jit_step_prefill": {"paged_attn_prefill": [9.0, 4.0]}})
    span = work_mixed.attn_decode_span(CFG, 100, 106)
    least = max(span["flops"] / 1e12, span["bytes"] / 1e9)
    assert mixed_attn_roofline.read(c) == pytest.approx(100 * least / 0.005)
    # five calls an iteration (one a layer) is another model's walk
    many = ctx({"jit_run": {"paged_attn_decode": [0.004, 50.0]}})
    assert mixed_attn_roofline.read(many) is None
    assert "KERNEL NAME AMBIGUOUS" in capsys.readouterr().out


def test_conv_share_from_a_scope_table(monkeypatch):
    table = {"jit_run": {("moe_experts", "fwd"): 8.0,
                         ("conv_mix", "fwd"): 0.5, ("attn_core", "fwd"): 1.0},
             "jit_step_decode": {("conv_mix", "fwd"): 0.5},
             "jit_step_prefill": {("conv_mix", "fwd"): 100.0}}
    monkeypatch.setattr(_scopes, "table", lambda ctx: table)
    assert decode_conv_share.read({}) == pytest.approx(10.0)
    # a program without the scope (a parent commit, a model without conv
    # layers): nothing to read, not 0 %
    monkeypatch.setattr(_scopes, "table", lambda ctx: {
        "jit_run": {("ffn", "fwd"): 1.0}})
    assert decode_conv_share.read({}) is None
    monkeypatch.setattr(_scopes, "table", lambda ctx: None)
    assert decode_conv_share.read({}) is None


def test_read_layers_leaves_out_what_a_parent_cannot_give():
    entry = {"metrics": {"per_layer": [
        {"name": n, "unit": "%"} for n in (
            "decode_conv_share", "conv_carry_share", "mixed_gmm_roofline",
            "mixed_attn_roofline")]}}
    parent = ctx({"jit_run": {"fusion": [0.1, 3.0]}})
    parent["trace"]["host_only"] = True
    assert common.read_layers(entry, parent) == {}
