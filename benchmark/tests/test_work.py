"""work.py against hand-computed cases: matmul parameters only, causal
attention halved, recomputation excluded."""
import json
import os

import pytest

from benchmark import work

HERE = os.path.dirname(os.path.abspath(__file__))
#: a model small enough to count by hand
TOY = {"hidden_size": 8, "num_attention_heads": 2, "num_key_value_heads": 1,
       "head_dim": 4, "intermediate_size": 16, "num_hidden_layers": 3,
       "vocab_size": 32}


def test_matmul_params_by_hand():
    # per layer: q 8*2*4=64, o 64, k 8*1*4=32, v 32 -> 192; SwiGLU 3*8*16=384
    # head 8*32=256; the input embedding (32*8) is NOT counted
    assert work.matmul_params(TOY) == 3 * (192 + 384) + 256


def test_attention_is_the_causal_half():
    # 4 queries, no cache: 1+2+3+4 = 10 pairs (not 16); 4 FLOPs a pair per
    # head-dim element: 3 layers * 4 * H=2 * D=4 * 10
    assert work.attn_flops(TOY, 4, 0) == 3 * 4 * 2 * 4 * 10
    # 2 new queries after 5 cached: 6 + 7 = 13 pairs
    assert work.attn_flops(TOY, 2, 5) == 3 * 4 * 2 * 4 * 13


def test_train_flops_per_token_excludes_recomputation():
    seq = 4
    want = 6 * work.matmul_params(TOY) + 3 * work.attn_flops(TOY, seq, 0) / seq
    assert work.train_flops_per_token(TOY, seq) == want
    # remat on or off is not an input: the count cannot depend on it
    assert "remat" not in work.train_flops_per_token.__code__.co_varnames


def test_decode_step_bytes_and_flops():
    w = work.decode_step(TOY, [3, 5])
    kv_tok = 2 * 3 * 1 * 4 * 2          # K and V, 3 layers, 1 KV head, D=4, bf16
    assert w["bytes"] == work.matmul_params(TOY) * 2 + kv_tok * 8
    assert w["flops"] == 2 * work.matmul_params(TOY) * 2 \
        + work.attn_flops(TOY, 1, 2) + work.attn_flops(TOY, 1, 4)


def test_prefill_chunk_counts_the_head_once():
    w = work.prefill_chunk(TOY, q_tokens=4, ctx_before=4, sampled_rows=1)
    body = work.matmul_params(TOY) - 8 * 32
    assert w["flops"] == 2 * body * 4 + 2 * 8 * 32 + work.attn_flops(TOY, 4, 4)


def test_mistral_widths_match_the_issue():
    with open(os.path.join(HERE, "..", "configs",
                           "mistral-7b-v0.3-train-l8.json")) as f:
        cfg = json.load(f)
    # 8 layers at the published widths: 1.88 B matmul parameters,
    # 11.7 GFLOP a token at sequence 2048
    assert work.matmul_params(cfg) == 8 * 218_103_808 + 4096 * 32768
    assert work.train_flops_per_token(cfg, 2048) == pytest.approx(11.68e9, rel=1e-3)


def test_roofline_says_which_bound():
    peak = work.peaks("TPU v5 lite")
    t, bound = work.least_time_s({"flops": 197e12, "bytes": 1.0}, peak)
    assert (round(t, 6), bound) == (1.0, "compute")
    t, bound = work.least_time_s({"flops": 1.0, "bytes": 819e9}, peak)
    assert (round(t, 6), bound) == (1.0, "memory")


def test_unknown_device_is_an_error():
    with pytest.raises(KeyError):
        work.peaks("TPU v9 imaginary")
    with pytest.raises(KeyError):
        work.peaks("_source")
