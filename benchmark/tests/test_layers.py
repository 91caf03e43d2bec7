"""The per-layer readers that do arithmetic of their own, on a hand-made
context (what the traced serving run hands over)."""
import pytest

from benchmark import work
from benchmark.layers import paged_attn_roofline, prefill_attn_roofline

CFG = {"hidden_size": 64, "num_attention_heads": 4, "num_key_value_heads": 2,
       "head_dim": 16, "intermediate_size": 128, "num_hidden_layers": 2,
       "vocab_size": 100}
PEAKS = {"bf16_flops": 1e12, "hbm_bytes_per_s": 1e9}
KV_TOK = 2 * 2 * 2 * 16 * 2          # K and V, layers, kv heads, width, bf16


def ctx(by_program, **stats):
    st = {"window_iters": 8, "decode_steps": 2, "prefill_steps": 2,
          "prefill_tokens": 100, **stats}
    # sequence 1: prompt 100, prefilled 0 -> 100 then decoded to 110 in the
    # window; sequence 2: already decoding, 50 -> 60 (prompt 40)
    return {"trace": {"ops_by_program": by_program}, "stats": st,
            "model": CFG, "peaks": PEAKS, "uid_of": {}, "done_len": {},
            "progress": ({2: (40, 50)}, {1: (100, 110), 2: (40, 60)})}


def test_each_form_reads_its_own_programs_and_its_own_work():
    c = ctx({"jit_run": {"closed_call": [0.004, 16.0]},
             "jit_step_decode": {"closed_call": [0.001, 4.0], "fusion": [9.0, 4.0]},
             "jit_step_prefill": {"closed_call": [0.002, 4.0]}})
    # decode: K/V of the context read once a step, 10 steps each sequence
    byts = KV_TOK * (105 * 10 + 55 * 10)
    flops = work.attn_flops(CFG, 10, 100) + work.attn_flops(CFG, 10, 50)
    least = max(byts / 1e9, flops / 1e12)
    assert paged_attn_roofline.read(c) == pytest.approx(100 * least / 0.005)
    # prefill: 100 tokens in chunks of 50 -> 2 reads of the mean context 50
    byts = KV_TOK * 50 * 2
    least = max(byts / 1e9, work.attn_flops(CFG, 100, 0) / 1e12)
    assert prefill_attn_roofline.read(c) == pytest.approx(100 * least / 0.002)


def test_a_second_kernel_under_the_generic_name_is_refused_loudly(capsys):
    twice = ctx({"jit_run": {"closed_call": [0.004, 40.0]}})    # 2 x layers x iterations
    assert paged_attn_roofline.read(twice) is None
    assert "KERNEL NAME AMBIGUOUS" in capsys.readouterr().out
    two_names = ctx({"jit_run": {"closed_call": [0.004, 10.0],
                                 "ragged_attention": [0.004, 10.0]}})
    assert paged_attn_roofline.read(two_names) is None
    assert "KERNEL NAME AMBIGUOUS" in capsys.readouterr().out
    assert prefill_attn_roofline.read(twice) is None            # nothing matched
