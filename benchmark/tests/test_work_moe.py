"""work_moe.py against hand-computed cases: active and total matmul
parameters, the grouped GEMM's FLOPs and bytes, a decode step of a
routed-expert stack, and the published OLMoE sizes."""
import json
import os

import pytest

from benchmark import work, work_moe

HERE = os.path.dirname(os.path.abspath(__file__))
#: small enough to count by hand: 4 experts of width 16, 2 a token
TOY = {"hidden_size": 8, "num_attention_heads": 2, "num_key_value_heads": 2,
       "intermediate_size": 16, "num_hidden_layers": 3, "vocab_size": 32,
       "num_experts": 4, "num_experts_per_tok": 2}


def test_params_by_hand():
    # per layer: q 8*2*4=64, o 64, k 64, v 64 -> 256; router 8*4=32;
    # one expert 3*8*16=384; head 8*32=256
    assert work_moe.attn_params(TOY) == 256
    assert work_moe.expert_params(TOY) == 384
    assert work_moe.matmul_params_total(TOY) == 3 * (256 + 32 + 4 * 384) + 256
    assert work_moe.matmul_params_active(TOY) == 3 * (256 + 32 + 2 * 384) + 256


def test_grouped_matmul_counts_routed_rows_and_touched_experts():
    w = work_moe.grouped_matmul(TOY, routed_rows=10, experts_touched=3)
    assert w["flops"] == 2 * 384 * 10            # padding rows are not work
    # weights of 3 experts once (bf16); a row: 8 in for gate, 8 for up, 16
    # out each, 16 in for down, 8 out -> 72 elements
    assert w["bytes"] == 3 * 384 * 2 + 10 * 72 * 2
    # more rows onto the same experts add rows, not weights
    w2 = work_moe.grouped_matmul(TOY, routed_rows=20, experts_touched=3)
    assert w2["bytes"] - w["bytes"] == 10 * 72 * 2


def test_touched_experts_under_uniform_routing():
    assert work_moe.experts_touched_uniform(TOY, 0) == 0
    assert work_moe.experts_touched_uniform(TOY, 1) == pytest.approx(2.0)
    assert work_moe.experts_touched_uniform(TOY, 50) == pytest.approx(4.0)
    assert 2.0 < work_moe.experts_touched_uniform(TOY, 2) < 4.0


def test_decode_step_reads_touched_experts_once_a_layer():
    d = work_moe.decode_step(TOY, [10, 20], experts_touched=3)
    assert d["flops"] == 2 * work_moe.matmul_params_active(TOY) * 2 \
        + work.attn_flops(TOY, 1, 9) + work.attn_flops(TOY, 1, 19)
    weights = 3 * (256 + 32 + 3 * 384) + 256
    kv_tok = 2 * 3 * 2 * 4 * 2
    assert d["bytes"] == weights * 2 + kv_tok * 30
    # no count given: the uniform expectation of the batch
    e = work_moe.decode_step(TOY, [10, 20])
    assert d["bytes"] - e["bytes"] == pytest.approx(
        3 * (3 - work_moe.experts_touched_uniform(TOY, 2)) * 384 * 2)


def test_published_olmoe_sizes():
    with open(os.path.join(HERE, "..", "configs",
                           "olmoe-1b-7b-0125-serve.json")) as f:
        cfg = dict(json.load(f), num_hidden_layers=16)
    # 6.8 B matmul parameters in all, 1.18 B of them active a token
    assert work_moe.matmul_params_total(cfg) == pytest.approx(6.82e9, rel=0.01)
    assert work_moe.matmul_params_active(cfg) == pytest.approx(1.18e9, rel=0.01)
    # one layer's experts: 768 MiB in bf16
    assert 64 * work_moe.expert_params(cfg) * 2 == 768 * 2 ** 20
    # a 48-row decode step reaches every expert, near enough
    assert work_moe.experts_touched_uniform(cfg, 48) > 63.8
