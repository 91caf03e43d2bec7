"""``work_latent.py`` against hand arithmetic at kanana-2-30b-a3b's
published widths (the configuration file the cell runs): the latent row, one
expert, one expert layer, the cut's bytes at rest."""
import json
import os

import pytest

from benchmark import work_latent as w

HERE = os.path.dirname(os.path.abspath(__file__))
with open(os.path.join(HERE, "..", "configs",
                       "kanana-2-30b-a3b-serve.json")) as f:
    CFG = json.load(f)
M = 1e6


def test_the_latent_row_is_1152_bytes_a_token_a_layer():
    assert w.latent_bytes_token(CFG) == (512 + 64) * 2 == 1152
    # per-head keys and values would be 32 x (192 + 128) x 2 B = 20 KiB
    assert 32 * (192 + 128) * 2 / w.latent_bytes_token(CFG) \
        == pytest.approx(17.8, abs=0.05)
    # one (query, key) pair, all 32 heads: scores over 576, values over 512
    assert w.latent_flops_pair(CFG) == 32 * 2 * (576 + 512)
    # 60 FLOPs a byte: under the chip's 240, memory-bound
    assert w.latent_flops_pair(CFG) / 1152 == pytest.approx(60.4, abs=0.1)


def test_parameters_of_attention_an_expert_and_an_expert_layer():
    assert w.layers(CFG) == {"attention": 5, "dense": 1, "experts": 4}
    # W_q 12.58 M + W_dkv 1.18 M + W_ukv 4.19 M + W_o 8.39 M = 26.3 M
    assert w.attn_params(CFG) == 2048 * 32 * 192 + 2048 * 576 \
        + 512 * 32 * 256 + 32 * 128 * 2048
    assert w.attn_params(CFG) / M == pytest.approx(26.35, abs=0.01)
    assert w.expert_params(CFG) == 3 * 2048 * 768        # 4.72 M
    assert w.expert_params(CFG) / M == pytest.approx(4.72, abs=0.005)
    assert w.shared_params(CFG) == 3 * 2048 * 1536       # 9.44 M, ungated
    assert w.dense_params(CFG) == 3 * 2048 * 6144        # 37.7 M
    # an expert layer: 26.3 + 0.26 (router) + 9.44 + 128 x 4.72 = 640 M
    assert w.expert_layer_params(CFG, 128) / M == pytest.approx(640.0,
                                                                abs=0.5)


def test_the_cut_at_rest_and_what_a_token_computes():
    # layers 0-4 and the untied head (the embedding is gathered, not
    # multiplied): 64 M + 4 x 640 M + 262.7 M
    at_rest = w.matmul_params(CFG, CFG["n_routed_experts"])
    assert at_rest / M == pytest.approx(64.1 + 4 * 640.0 + 262.7, abs=1.5)
    # bf16: 5.39 GiB of matmul weights + 0.49 GiB of embedding = 5.87 GiB
    embed = CFG["vocab_size"] * CFG["hidden_size"]
    assert (at_rest + embed) * 2 / 2 ** 30 == pytest.approx(5.87, abs=0.03)
    # a token's FLOPs follow 6 experts a layer, not 128
    active = w.matmul_params(CFG, CFG["num_experts_per_tok"])
    assert active / M == pytest.approx(
        64.1 + 4 * (26.35 + 0.26 + 9.44 + 6 * 4.72) + 262.7, abs=1.5)


def test_a_decode_step_reads_rows_and_touched_experts():
    ctxs = [17000] * 8 + [1000] * 8
    step = w.decode_step(CFG, ctxs)
    rows = 5 * 1152 * sum(ctxs)
    assert rows == pytest.approx(0.83e9, rel=0.01)
    touched = w.experts_touched_uniform(CFG, 16)
    assert touched == pytest.approx(68.6, abs=0.5)       # of 128
    assert step["bytes"] == w.matmul_params(CFG, touched) * 2 + rows
    # the experts' share: ~69 x 9.4 MB x 4 layers = 2.6 GB
    assert 4 * touched * w.expert_params(CFG) * 2 == pytest.approx(
        2.59e9, rel=0.02)
    # one layer's three grouped GEMMs over 16 x 6 routed rows
    g = w.grouped_matmul(CFG, 96, touched)
    assert g["flops"] == 2.0 * w.expert_params(CFG) * 96
    assert g["bytes"] == touched * w.expert_params(CFG) * 2 \
        + 96 * 3 * (2048 + 768) * 2


def test_a_decode_span_sums_the_contexts():
    span = w.latent_decode_span(CFG, 100, 103)
    assert span["bytes"] == 5 * 1152 * (101 + 102 + 103)
    assert span["flops"] == 5 * w.latent_flops_pair(CFG) * (101 + 102 + 103)
    assert w.latent_decode_span(CFG, 5, 5) == {"flops": 0.0, "bytes": 0.0}
