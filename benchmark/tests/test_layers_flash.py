"""``train_flash_attn_mfu`` on a hand-made context (what the traced
training run hands over)."""
import pytest

from benchmark import work
from benchmark.layers import train_flash_attn_mfu

CFG = {"hidden_size": 64, "num_attention_heads": 4, "num_key_value_heads": 2,
       "head_dim": 16, "intermediate_size": 128, "num_hidden_layers": 2,
       "vocab_size": 100}
PEAKS = {"bf16_flops": 1e12, "hbm_bytes_per_s": 1e9}


def ctx(ops, **over):
    # 3 steps of 8 rows x 256 tokens on 4 chips: 6 rows a chip
    return {"trace": {"ops_by_program": {"jit_train_step": ops}},
            "model": CFG, "peaks": PEAKS, "steps": 3, "seq": 256,
            "tokens_per_step": 8 * 256, "chips": 4, **over}


SPLIT = {"flash_attention_fwd": [0.002, 12.0],            # 2 x layers x steps
         "flash_attention_bwd_dq.1": [0.001, 6.0],
         "flash_attention_bwd_dkv": [0.001, 6.0],
         "fusion": [9.0, 100.0]}


def test_counts_forward_twice_and_backward_at_twice_forward():
    flops = 4 * work.attn_flops(CFG, 256, 0) * 6
    assert train_flash_attn_mfu.read(ctx(SPLIT)) \
        == pytest.approx(100 * flops / 0.004 / 1e12)
    merged = {"flash_attention_fwd": [0.002, 12.0],
              "flash_attention_bwd_dqkv": [0.003, 6.0]}
    assert train_flash_attn_mfu.read(ctx(merged)) \
        == pytest.approx(100 * flops / 0.005 / 1e12)


@pytest.mark.parametrize("ops,said", [
    ({"fusion": [9.0, 100.0]}, "none in the train step"),            # XLA attention
    ({**SPLIT, "flash_attention_fwd": [0.002, 40.0]}, "KERNEL CALLS UNEXPECTED"),
    ({"flash_attention_fwd": [0.002, 12.0]}, "KERNEL CALLS UNEXPECTED"),  # no backward
    ({"flash_attention_bwd_dq": [0.001, 6.0],
      "flash_attention_bwd_dkv": [0.001, 6.0]}, "KERNEL CALLS UNEXPECTED"),
])
def test_left_out_loudly(ops, said, capsys):
    assert train_flash_attn_mfu.read(ctx(ops)) is None
    assert said in capsys.readouterr().out


def test_a_program_without_the_kernels_or_the_table_reads_nothing(capsys):
    assert train_flash_attn_mfu.read(ctx({}, trace={"ops_by_program": {}})) is None
    assert train_flash_attn_mfu.read(ctx(SPLIT, peaks=None)) is None
