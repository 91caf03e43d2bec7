"""Flops profiler + env report tests (reference
tests/unit/profiling/flops_profiler/test_flops_profiler.py analogue)."""
import io
import json
import os

import jax
import jax.numpy as jnp
import pytest

pytestmark = pytest.mark.slow  # multi-minute: engine jit compiles

from deepspeed_tpu.models import build_model
from deepspeed_tpu.profiling import (FlopsProfiler, cost_analysis,
                                     get_model_profile, human_flops,
                                     human_params)


def test_cost_analysis_matmul_flops():
    n = 128
    costs = cost_analysis(lambda a, b: a @ b,
                          jnp.ones((n, n)), jnp.ones((n, n)))
    # XLA counts 2*n^3 for an n^3 MAC matmul
    assert costs["flops"] == pytest.approx(2 * n**3)


def test_get_model_profile_numbers():
    m = build_model("tiny-gpt2")
    flops, macs, params = get_model_profile(
        m, input_shape=(2, 32), print_profile=False, as_string=False)
    assert flops > 0 and macs == pytest.approx(flops / 2)
    # params: model has ~24.6k params
    assert 10_000 < params < 100_000
    # FLOPs must be at least the analytic matmul floor: 2 * params-ish * tokens
    assert flops > 2 * params * 64 * 0.5


def test_per_module_tree_and_report():
    m = build_model("tiny-gpt2")
    prof = FlopsProfiler()
    res = prof.profile_model(m, jnp.zeros((1, 16), jnp.int32))
    paths = [r.path for r in res.modules]
    assert "" in paths  # root
    assert any("attn" in p for p in paths)
    root = res.modules[0]
    child_sum = sum(r.flops for r in res.modules if r.depth == 1)
    # children should account for most of the root's flops
    assert child_sum <= root.flops * 1.01
    assert child_sum > root.flops * 0.5
    buf = io.StringIO()
    prof.print_profile(res, file=buf)
    assert "Flops Profiler" in buf.getvalue()


def test_engine_integration(tmp_path):
    import numpy as np

    import deepspeed_tpu as ds

    out = tmp_path / "flops.txt"
    engine, *_ = ds.initialize(
        model=build_model("tiny-gpt2"),
        config={
            "train_micro_batch_size_per_gpu": 2,
            "optimizer": {"type": "AdamW", "params": {"lr": 1e-3}},
            "zero_optimization": {"stage": 0},
            "flops_profiler": {"enabled": True, "profile_step": 1,
                               "output_file": str(out)},
        })
    rng = np.random.default_rng(0)
    gbs = engine.config.train_batch_size
    batch = {"input_ids": rng.integers(0, 256, (gbs, 32)),
             "labels": rng.integers(0, 256, (gbs, 32))}
    engine.train_batch(batch)
    engine.train_batch(batch)
    text = out.read_text()
    assert "fwd FLOPs" in text
    assert engine.flops_profiler.profiled


def test_human_format():
    assert human_flops(2.5e12) == "2.50 T"
    assert human_params(1_300_000) == "1.30 M"


def test_env_report_runs(capsys):
    from deepspeed_tpu import env_report

    text = env_report.main()
    assert "deepspeed_tpu environment report" in text
    assert "jax" in text


#: a small scoped device trace recorded on a v5e
#: (benchmark/tests/record_scope_fixture.py) and what the chip run wrote
#: beside it: the program's scope maps and reduce_trace's own summary
_SCOPED_TRACE = os.path.join(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
    "benchmark", "tests", "data", "tpu_v5e_scopes.xplane.pb")


def test_trace_capture_and_breakdown(tmp_path):
    """profiling.trace: capture a trace (the machinery runs on any
    backend; a CPU trace has no device plane and reads back empty), and
    read per-op SELF device time back from a trace recorded on a TPU —
    on ``jax.profiler.ProfileData``, no TensorFlow."""
    import jax
    import jax.numpy as jnp

    from deepspeed_tpu.profiling.trace import (op_breakdown,
                                               print_breakdown, trace)

    @jax.jit
    def f(a, b):
        return (a @ b).sum()

    a = jnp.ones((256, 256)); b = jnp.ones((256, 256))
    jax.block_until_ready(f(a, b))          # compile outside the trace
    with trace(str(tmp_path)):
        jax.block_until_ready(f(a, b))
    if jax.default_backend() != "tpu":
        assert op_breakdown(str(tmp_path)) == {}

    with open(_SCOPED_TRACE.replace(".xplane.pb", ".expected.json")) as fh:
        expected = json.load(fh)
    totals = op_breakdown(_SCOPED_TRACE)
    assert totals and all(ms >= 0 for ms in totals.values())
    # the same self times as the benchmark's reader gave on the chip, op by
    # op, over the whole trace
    assert totals == pytest.approx(expected["ops_ms_whole_trace"], rel=1e-9)
    assert expected["kernel"] in totals
    assert expected["kernel"] in print_breakdown(_SCOPED_TRACE, top=50)
