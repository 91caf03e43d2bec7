"""The reader of ``prefill_attn_core_share`` (PR 60): ``attn_core`` +
``kv_stage`` self time inside the prefill step programs over those
programs' time, by the scope table; None where no prefill program ran or
the program has no scope maps. (A file of its own: a PR that claims a gain
edits no file the benchmark already has.)"""
import pytest

from benchmark import common
from benchmark import reduce_trace as rt
from benchmark.layers import _scopes, prefill_attn_core_share
from benchmark.tests.test_reduce_trace import plane
from deepspeed_tpu.profiling import trace as ptrace


@pytest.fixture(autouse=True)
def fresh_tables():
    _scopes._tables.clear()
    yield
    _scopes._tables.clear()


def hand_made(prefill=True):
    """One device. ``jit_step_prefill`` (100-600): the staged rows' pad
    (100-150), the latent kernel (150-450), an expert GEMM (450-600);
    ``jit_run`` (700-800): a decode kernel, which is not this metric's."""
    mods = [("jit_step_prefill(7)", 100, 600)] * prefill \
        + [("jit_run(3)", 700, 800)]
    ops = [("%pad_fusion.1 = ...", 100, 150),
           ("%paged_latent_prefill.2 = ...", 150, 450),
           ("%grouped_matmul_fwd.3 = ...", 450, 600)] * prefill \
        + [("%paged_latent_decode.4 = ...", 700, 800)]
    d0 = plane("/device:TPU:0", XLA_Modules=mods, XLA_Ops=ops)
    host = plane("/host:CPU", python=[("bench_window", 0, 1000)])
    pre = "jit(step_prefill)/while/body/"
    maps = {
        "jit_step_prefill": {"ops": ptrace.merge_scope_maps([{
            "pad_fusion.1": pre + "kv_stage/pad",
            "paged_latent_prefill.2":
                pre + "attn_core/paged_latent_prefill/pallas_call",
            "grouped_matmul_fwd.3":
                pre + "moe_experts/grouped_matmul_fwd/pallas_call"}]),
            "programs": 1, "hlo_bytes": 1},
        "jit_run": {"ops": ptrace.merge_scope_maps([{
            "paged_latent_decode.4":
                "jit(run)/while/body/attn_core/paged_latent_decode/"
                "pallas_call"}]), "programs": 1, "hlo_bytes": 1}}
    return [host, d0], maps


def ctx_of(planes, maps, path):
    _scopes._tables[path] = _scopes.join(planes, maps, ptrace.scope_of)
    return {"trace": rt.summarize(planes), "scope_maps": maps,
            "trace_dir": path}


def test_share_is_the_kernel_and_the_stage_over_the_prefill_programs():
    ctx = ctx_of(*hand_made(), "hand-made-prefill")
    assert prefill_attn_core_share.read(ctx) \
        == pytest.approx(100 * (50 + 300) / 500)
    # the decode programs' kernel is the decode twin's
    assert _scopes.share(ctx, _scopes.DECODE_PROGRAMS,
                         ("attn_core", "kv_stage")) == pytest.approx(100.0)


def test_reads_nothing_where_no_prefill_program_ran():
    ctx = ctx_of(*hand_made(prefill=False), "hand-made-decode-only")
    assert prefill_attn_core_share.read(ctx) is None


def test_a_rehearsal_and_a_program_without_maps_read_nothing(monkeypatch):
    assert prefill_attn_core_share.read(
        {"trace": {"host_only": True}}) is None
    planes, maps = hand_made()
    monkeypatch.setattr(_scopes, "_build", lambda ctx, path: None)
    assert prefill_attn_core_share.read(
        {"trace": rt.summarize(planes), "trace_dir": "no-maps"}) is None


def test_read_layers_reports_it_and_leaves_it_out():
    entry = {"metrics": {"per_layer": [
        {"name": "prefill_attn_core_share", "unit": "%"}]}}
    assert common.read_layers(
        entry, ctx_of(*hand_made(prefill=False), "left-out")) == {}
    assert common.read_layers(entry, ctx_of(*hand_made(), "reported")) == {
        "prefill_attn_core_share": {"value": pytest.approx(70.0),
                                    "unit": "%"}}
