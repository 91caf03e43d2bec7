"""work_mixed.py against hand-computed cases: which layers have what, the
parameters a token multiplies and a chip holds, the grouped GEMM and the
attention of the layers that HAVE them, a decode step — and the published
LFM2-24B-A2B sizes of ISSUE 50's reckoning."""
import json
import os

import pytest

from benchmark import work_mixed

HERE = os.path.dirname(os.path.abspath(__file__))
#: small enough to count by hand: published layers conv, conv, attention,
#: conv, conv, conv; the stack is layers 1-5; 4 experts of width 6, 2 a token
TOY = {"hidden_size": 8, "num_attention_heads": 2, "num_key_value_heads": 1,
       "intermediate_size": 16, "moe_intermediate_size": 6,
       "num_hidden_layers": 5, "first_layer": 1, "num_dense_layers": 1,
       "vocab_size": 32, "num_experts": 4, "num_experts_per_tok": 2,
       "conv_L_cache": 3,
       "layer_types": ["conv", "conv", "full_attention", "conv", "conv",
                       "conv", "full_attention", "conv"]}


def test_layers_have_what_the_published_list_says():
    assert work_mixed.layer_types(TOY) == [
        "conv", "full_attention", "conv", "conv", "conv"]
    assert work_mixed.layers(TOY) == {"attention": 1, "conv": 4, "dense": 1,
                                      "experts": 4}
    with pytest.raises(ValueError):
        work_mixed.layer_types(dict(TOY, first_layer=6))


def test_params_by_hand():
    # attention: q 8*2*4=64, o 64, k 8*1*4=32, v 32 -> 192; conv: 4*64=256;
    # dense 3*8*16=384; router 8*4=32; one expert 3*8*6=144; head 8*32=256
    assert work_mixed.attn_params(TOY) == 192
    assert work_mixed.conv_params(TOY) == 256
    assert work_mixed.dense_params(TOY) == 384
    assert work_mixed.expert_params(TOY) == 144
    rest = 192 + 4 * 256 + 384 + 256
    assert work_mixed.matmul_params(TOY, 4) == rest + 4 * (32 + 4 * 144)
    assert work_mixed.matmul_params(TOY, 2) == rest + 4 * (32 + 2 * 144)


def test_grouped_matmul_is_one_expert_layers():
    w = work_mixed.grouped_matmul(TOY, routed_rows=10, experts_touched=3)
    assert w["flops"] == 2 * 144 * 10
    # a row: 8 in for gate, 8 for up, 6 out each, 6 in for down, 8 out = 42
    assert w["bytes"] == 3 * 144 * 2 + 10 * 42 * 2
    assert work_mixed.experts_touched_uniform(TOY, 1) == pytest.approx(2.0)
    assert work_mixed.experts_touched_uniform(TOY, 50) == pytest.approx(4.0)


def test_attention_counts_the_attention_layers_only():
    # one attention layer: K and V of a token = 2 * 1 * 4 * 2 bytes
    assert work_mixed.kv_bytes_token(TOY) == 16
    span = work_mixed.attn_decode_span(TOY, 10, 13)
    pairs = 11 + 12 + 13
    assert span["flops"] == 4.0 * 2 * 4 * pairs
    assert span["bytes"] == 16 * pairs
    assert work_mixed.attn_decode_span(TOY, 5, 5) == {"flops": 0.0,
                                                      "bytes": 0.0}
    # a second attention layer in the stack doubles both
    two = dict(TOY, num_hidden_layers=6)
    assert work_mixed.layers(two)["attention"] == 2
    assert work_mixed.attn_decode_span(two, 10, 13)["bytes"] == 32 * pairs


def test_a_record_does_not_grow_with_the_context():
    # 4 conv layers x 2 rows x 8 x bf16
    assert work_mixed.record_bytes(TOY) == 4 * 2 * 8 * 2


def test_decode_step_by_hand():
    d = work_mixed.decode_step(TOY, [10, 20], experts_touched=3)
    assert d["flops"] == 2 * work_mixed.matmul_params(TOY, 2) * 2 \
        + 4.0 * 2 * 4 * (10 + 20)
    assert d["bytes"] == work_mixed.matmul_params(TOY, 3) * 2 \
        + 16 * 30 + 2 * 128 * 2
    e = work_mixed.decode_step(TOY, [10, 20])
    assert d["bytes"] - e["bytes"] == pytest.approx(
        4 * (3 - work_mixed.experts_touched_uniform(TOY, 2)) * 144 * 2)


def test_published_lfm2_sizes():
    with open(os.path.join(HERE, "..", "configs",
                           "lfm2-24b-a2b-serve.json")) as f:
        cfg = json.load(f)
    assert work_mixed.layers(cfg) == {"attention": 1, "conv": 4, "dense": 1,
                                      "experts": 4}
    # ISSUE 50's reckoning: one expert 9.44 M, 64 of them 1.125 GiB a layer;
    # conv operator 16.8 M; attention 10.5 M; dense FF 72.4 M
    assert work_mixed.expert_params(cfg) == 3 * 2048 * 1536
    assert 64 * work_mixed.expert_params(cfg) * 2 == 1.125 * 2 ** 30
    assert work_mixed.conv_params(cfg) == pytest.approx(16.8e6, rel=0.01)
    assert work_mixed.attn_params(cfg) == pytest.approx(10.5e6, rel=0.01)
    assert work_mixed.dense_params(cfg) == pytest.approx(72.4e6, rel=0.01)
    # layers 1-5 and the head: 2.57 B + 134 M at rest, 5.03 GiB in bf16
    at_rest = work_mixed.matmul_params(cfg, 64)
    assert at_rest * 2 / 2 ** 30 == pytest.approx(5.03, abs=0.03)
    # the whole 40-layer model: 23.8 B ("24B"), 2.3 B active ("A2B")
    whole = dict(cfg, num_hidden_layers=40, first_layer=0,
                 num_dense_layers=2)
    assert work_mixed.matmul_params(whole, 64) == pytest.approx(23.7e9,
                                                               rel=0.02)
    assert work_mixed.matmul_params(whole, 4) == pytest.approx(2.2e9,
                                                              rel=0.1)
    # K and V: 2 KiB a token in the one attention layer; a record 32 KiB
    assert work_mixed.kv_bytes_token(cfg) == 2048
    assert work_mixed.record_bytes(cfg) == 4 * 8192
    # ~25 live rows touch about 50 of 64 experts
    assert 49 < work_mixed.experts_touched_uniform(cfg, 25) < 52
