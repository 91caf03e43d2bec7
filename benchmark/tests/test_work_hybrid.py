"""``work_hybrid`` against hand counts: a window layer sees (and reads)
``min(context, window)`` keys a decode step, a global layer the whole
context."""
import pytest

from benchmark import work, work_hybrid

CFG = {"hidden_size": 2560, "num_attention_heads": 28,
       "num_key_value_heads": 4, "head_dim": 128, "intermediate_size": 768,
       "num_hidden_layers": 4, "vocab_size": 151936,
       "sliding_window_size": 4096,
       "sliding_window_layout": [0, 1, 1, 1] * 13}


def test_layers_by_kind_reads_the_first_layers_of_the_layout():
    assert work_hybrid.layers_by_kind(CFG) == {"global": 1, "window": 3}
    assert work_hybrid.layers_by_kind(dict(CFG, num_hidden_layers=8)) == \
        {"global": 2, "window": 6}


@pytest.mark.parametrize("context, window_keys", [
    (1000, 1000), (4096, 4096), (4097, 4096), (12000, 4096)])
def test_one_decode_step_by_hand(context, window_keys):
    """One decode step at ``context`` tokens (the new one included)."""
    assert work_hybrid.visible_keys(CFG, "global", 1, context - 1) == context
    assert work_hybrid.visible_keys(CFG, "window", 1, context - 1) \
        == window_keys
    # 4 FLOPs a query head, head dim and key; 1 global + 3 window layers
    f = work_hybrid.attn_flops(CFG, 1, context - 1)
    assert f["global"] == 4 * 28 * 128 * context
    assert f["window"] == 3 * 4 * 28 * 128 * window_keys
    # K and V, 4 KV heads x 128 x 2 bytes = 2 KiB a token a layer
    b = work_hybrid.kv_bytes_read(CFG, context)
    assert b["global"] == 2048 * context
    assert b["window"] == 3 * 2048 * window_keys
    span = work_hybrid.decode_span(CFG, context - 1, context)
    assert span["flops"] == f["global"] + f["window"]
    assert span["bytes"] == b["global"] + b["window"]


def test_a_chunk_that_crosses_the_window():
    # 8 new positions after 4090 cached: contexts 4091 .. 4098; the window
    # binds from the seventh on
    assert work_hybrid.visible_keys(CFG, "window", 8, 4090) == \
        sum(min(c, 4096) for c in range(4091, 4099))
    assert work_hybrid.visible_keys(CFG, "global", 8, 4090) == \
        sum(range(4091, 4099))


def test_below_the_window_it_is_works_count():
    """Where no context passes the window, both kinds are ``work.py``'s
    causal count (which knows one kind)."""
    got = sum(work_hybrid.attn_flops(CFG, 300, 700).values())
    assert got == pytest.approx(work.attn_flops(CFG, 300, 700))
    span = work_hybrid.decode_span(CFG, 700, 1000)
    assert span["flops"] == pytest.approx(work.attn_flops(CFG, 300, 700))
    assert work_hybrid.decode_span(CFG, 1000, 1000) == \
        {"flops": 0.0, "bytes": 0.0}


def test_past_the_window_the_old_count_would_pass_the_roofline():
    """What ``_shared.paged_roofline`` would count at 12,000 tokens is 2.3
    times what the kernel has to read: the reason the cell reports
    ``hybrid_attn_roofline`` and not ``paged_attn_roofline``."""
    old = 2 * 4 * 4 * 128 * 2 * 12000
    new = sum(work_hybrid.kv_bytes_read(CFG, 12000).values())
    assert old / new == pytest.approx(4 * 12000 / (12000 + 3 * 4096))
    assert old / new > 1.9
