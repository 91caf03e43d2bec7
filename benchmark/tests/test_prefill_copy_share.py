"""The reader of ``prefill_copy_share`` (PR 55): plain copies' self time
inside the prefill step programs over those programs' time; None where no
prefill program ran. (A file of its own: a PR that claims a gain edits no
file the benchmark already has.)"""
import pytest

from benchmark import common
from benchmark.layers import prefill_copy_share


def ctx(programs, ops_by_program):
    return {"trace": {"programs": programs,
                      "ops_by_program": ops_by_program}, "stats": {}}


PREFILL = {"jit_step_prefill": {"s": 3.686, "runs": 52},
           "jit_run": {"s": 1.1, "runs": 200}}


def test_share_is_copy_seconds_over_the_prefill_programs_seconds():
    # (the kanana cell's parent: four pool-sized copies, 0.877 s of 3.686)
    got = prefill_copy_share.read(ctx(PREFILL, {
        "jit_step_prefill": {"copy": [0.877, 208],
                             "paged_latent_prefill": [2.03, 260],
                             "fusion": [0.2, 9000]},
        "jit_run": {"copy": [0.5, 10]}}))        # a decode program's: not in
    assert got == pytest.approx(100 * 0.877 / 3.686)


def test_a_prefill_program_with_no_copy_reads_zero():
    assert prefill_copy_share.read(ctx(PREFILL, {
        "jit_step_prefill": {"fusion": [0.2, 9000]}})) == 0.0
    # (nor does a missing table of the program's ops raise)
    assert prefill_copy_share.read(ctx(PREFILL, {})) == 0.0


@pytest.mark.parametrize("programs", [
    {}, {"jit_run": {"s": 1.1, "runs": 200}},
    {"jit_step_prefill": {"s": 0.0, "runs": 0}}],
    ids=["nothing_ran", "decode_only", "zero_seconds"])
def test_reads_nothing_where_no_prefill_program_ran(programs):
    assert prefill_copy_share.read(ctx(programs, {
        "jit_run": {"copy": [0.5, 10]}})) is None


def test_read_layers_reports_it_and_leaves_it_out():
    entry = {"metrics": {"per_layer": [
        {"name": "prefill_copy_share", "unit": "%"}]}}
    assert common.read_layers(entry, ctx({}, {})) == {}
    assert common.read_layers(entry, ctx(
        {"jit_step_prefill": {"s": 2.0, "runs": 4}},
        {"jit_step_prefill": {"copy": [0.5, 8]}})) == {
            "prefill_copy_share": {"value": 25.0, "unit": "%"}}
