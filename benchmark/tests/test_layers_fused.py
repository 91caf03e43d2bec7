"""The reader of the decode block's counters (PR 52): reads where the
program books them, None, not an exception, where it does not (a parent
commit). (A file of its own beside ``test_layers_mixed.py``: a PR that
claims a gain edits no file the benchmark already has.)"""
from benchmark import common
from benchmark.layers import fused_decode_share


def ctx(**stats):
    return {"trace": {"ops_by_program": {}, "programs": {}},
            "stats": {"window_iters": 8, "decode_steps": 2, **stats}}


def test_fused_share_is_block_tokens_over_decode_tokens():
    assert fused_decode_share.read(ctx(
        fused_decode_tokens=12, decode_tokens=120, fused_steps=5,
        fused_empty_steps=1)) == 10.0
    # a window with prefill steps whose blocks carried nothing reads 0
    assert fused_decode_share.read(ctx(
        fused_decode_tokens=0, decode_tokens=50)) == 0.0


def test_fused_share_reads_nothing_where_the_counters_are_absent():
    assert fused_decode_share.read(ctx(decode_tokens=120)) is None  # parent
    assert fused_decode_share.read(ctx(fused_decode_tokens=0,
                                       decode_tokens=0)) is None


def test_read_layers_leaves_it_out_on_a_parent():
    entry = {"metrics": {"per_layer": [
        {"name": "fused_decode_share", "unit": "%"}]}}
    assert common.read_layers(entry, ctx(decode_tokens=9)) == {}
    assert common.read_layers(entry, ctx(
        decode_tokens=8, fused_decode_tokens=2)) == {
            "fused_decode_share": {"value": 25.0, "unit": "%"}}
