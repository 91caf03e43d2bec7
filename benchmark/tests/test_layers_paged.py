"""``paged_attn_live_step_share`` on a hand-made context (what the traced
serving run hands over): a number where the program publishes the two
counters, None — not an exception — where it does not (a parent commit)."""
from benchmark import common
from benchmark.layers import paged_attn_live_step_share


def ctx(**stats):
    return {"trace": {"ops_by_program": {}, "programs": {}},
            "stats": {"window_iters": 8, "decode_steps": 2, **stats}}


def test_live_step_share_is_live_over_rectangle_steps():
    # 12 layers x 10 iterations: 35 of 6,192 steps a call read a page
    assert paged_attn_live_step_share.read(ctx(
        attn_steps_live=12 * 10 * 35, attn_steps_rect=12 * 10 * 6192)) \
        == 100.0 * 35 / 6192
    assert paged_attn_live_step_share.read(ctx(
        attn_steps_live=0, attn_steps_rect=6192)) == 0.0


def test_live_step_share_is_left_out_without_the_counters():
    assert paged_attn_live_step_share.read(ctx()) is None
    # the gather fallback books nothing: no rectangle, no share
    assert paged_attn_live_step_share.read(ctx(
        attn_steps_live=0, attn_steps_rect=0)) is None
    entry = {"metrics": {"per_layer": [
        {"name": "paged_attn_live_step_share", "unit": "%"}]}}
    assert common.read_layers(entry, ctx()) == {}
    assert common.read_layers(entry, ctx(
        attn_steps_live=1, attn_steps_rect=4)) == {
            "paged_attn_live_step_share": {"value": 25.0, "unit": "%"}}
