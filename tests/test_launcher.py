"""Launcher + elasticity tests (reference tests/unit/launcher/test_run.py,
tests/unit/elasticity/test_elastic.py analogues)."""
import json
import os
import subprocess
import sys
from collections import OrderedDict

import pytest

from deepspeed_tpu.elasticity import (ElasticityError, compute_elastic_config,
                                      get_valid_chip_counts)
from deepspeed_tpu.launcher.launch import build_child_env, parse_args
from deepspeed_tpu.launcher.runner import (parse_hostfile,
                                           parse_inclusion_exclusion)

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


# -- hostfile ---------------------------------------------------------------
def test_parse_hostfile(tmp_path):
    hf = tmp_path / "hostfile"
    hf.write_text("# pod\nworker-0 slots=4\nworker-1 slots=4 # gen2\nsolo\n")
    res = parse_hostfile(str(hf))
    assert res == OrderedDict([("worker-0", 4), ("worker-1", 4), ("solo", 1)])


def test_parse_hostfile_rejects_dup(tmp_path):
    hf = tmp_path / "hostfile"
    hf.write_text("a slots=2\na slots=2\n")
    with pytest.raises(ValueError, match="duplicate"):
        parse_hostfile(str(hf))


def test_missing_hostfile_is_empty():
    assert parse_hostfile("/nonexistent/hostfile") == OrderedDict()


# -- include/exclude --------------------------------------------------------
def base_resources():
    return OrderedDict([("w0", 4), ("w1", 4), ("w2", 4)])


def test_include_whole_host():
    act = parse_inclusion_exclusion(base_resources(), "w1", "")
    assert act == OrderedDict([("w1", 4)])


def test_include_slots():
    act = parse_inclusion_exclusion(base_resources(), "w0:0,2@w2", "")
    assert act == OrderedDict([("w0", 2), ("w2", 4)])


def test_exclude_host_and_slots():
    act = parse_inclusion_exclusion(base_resources(), "", "w1@w2:3")
    assert act == OrderedDict([("w0", 4), ("w2", 3)])


def test_include_exclude_mutually_exclusive():
    with pytest.raises(ValueError):
        parse_inclusion_exclusion(base_resources(), "w0", "w1")


def test_include_unknown_host():
    with pytest.raises(ValueError):
        parse_inclusion_exclusion(base_resources(), "nope", "")


# -- per-node launcher env --------------------------------------------------
def test_build_child_env_multiproc():
    args = parse_args(["--nnodes", "2", "--node_rank", "1",
                       "--nproc_per_node", "4", "--master_addr", "10.0.0.1",
                       "--master_port", "1234", "train.py"])
    env = build_child_env({}, args, local_rank=2)
    assert env["DS_TPU_COORDINATOR"] == "10.0.0.1:1234"
    assert env["DS_TPU_NUM_PROCESSES"] == "8"
    assert env["DS_TPU_PROCESS_ID"] == "6"
    assert env["RANK"] == "6" and env["LOCAL_RANK"] == "2"


def test_build_child_env_singleproc_no_rendezvous():
    args = parse_args(["train.py"])
    env = build_child_env({}, args, local_rank=0)
    assert "DS_TPU_COORDINATOR" not in env
    assert env["WORLD_SIZE"] == "1"


@pytest.mark.parametrize("placed", [None, "/somewhere/else"])
def test_build_child_env_places_the_compile_cache(placed):
    """The launcher's child gets the fixed ``<checkout>/.jax_cache``
    through ``JAX_COMPILATION_CACHE_DIR`` — unless the caller's
    environment already places the cache, which is left alone."""
    base = {} if placed is None else {"JAX_COMPILATION_CACHE_DIR": placed}
    env = build_child_env(base, parse_args(["train.py"]), local_rank=0)
    assert env["JAX_COMPILATION_CACHE_DIR"] == (
        placed or os.path.join(REPO, ".jax_cache"))


def test_launch_end_to_end(tmp_path):
    """Spawn 2 local workers through the real launcher; each checks its env."""
    script = tmp_path / "worker.py"
    script.write_text(
        "import os, sys\n"
        "rank = int(os.environ['RANK']); ws = int(os.environ['WORLD_SIZE'])\n"
        "assert ws == 2\n"
        "open(os.path.join(os.path.dirname(__file__), f'ok_{rank}'), 'w').write('1')\n")
    rc = subprocess.call(
        [sys.executable, "-m", "deepspeed_tpu.launcher.launch",
         "--nnodes", "1", "--nproc_per_node", "2", str(script)],
        cwd=REPO, env={**os.environ, "JAX_PLATFORMS": "cpu"})
    assert rc == 0
    assert (tmp_path / "ok_0").exists() and (tmp_path / "ok_1").exists()


def test_launch_propagates_failure(tmp_path):
    script = tmp_path / "worker.py"
    script.write_text(
        "import os, sys, time\n"
        "if os.environ['RANK'] == '1': sys.exit(3)\n"
        "time.sleep(60)\n")  # must be torn down by peer failure, not finish
    rc = subprocess.call(
        [sys.executable, "-m", "deepspeed_tpu.launcher.launch",
         "--nnodes", "1", "--nproc_per_node", "2", str(script)],
        cwd=REPO, env={**os.environ, "JAX_PLATFORMS": "cpu"}, timeout=30)
    assert rc == 3


def test_runner_single_node_dry(tmp_path):
    """runner → launch → script, all local."""
    script = tmp_path / "t.py"
    script.write_text("import os; assert os.environ['WORLD_SIZE'] == '2'\n")
    rc = subprocess.call(
        [sys.executable, "-m", "deepspeed_tpu.launcher.runner",
         "--num_gpus", "2", str(script)],
        cwd=REPO, env={**os.environ, "JAX_PLATFORMS": "cpu"})
    assert rc == 0


# -- elasticity solver ------------------------------------------------------
def elastic_dict(**kw):
    d = {"enabled": True, "max_train_batch_size": 10000,
         "micro_batch_sizes": [8, 12, 16, 17], "min_gpus": 32,
         "max_gpus": 1500, "min_time": 20, "version": 0.1}
    d.update(kw)
    return {"elasticity": d}


def test_elastic_v01_basics():
    batch, valid = compute_elastic_config(elastic_dict())
    assert batch <= 10000
    # every valid chip count divides batch/m for some micro batch m
    for w in valid:
        assert any(batch % (m * w) == 0
                   for m in [8, 12, 16, 17]), (batch, w)
    assert all(32 <= w <= 1500 for w in valid)
    assert len(valid) > 10  # highly-composite batch → many valid counts


def test_valid_chip_counts_exact():
    # batch 48, micros [8, 12]: w valid iff 48 % (m*w) == 0 for some m
    valid = get_valid_chip_counts(48, [8, 12], 1, 64)
    assert valid == [1, 2, 3, 4, 6]


def test_elastic_rejects_conflicting_batch_terms():
    cfg = elastic_dict()
    cfg["train_batch_size"] = 512
    with pytest.raises(ElasticityError, match="train_batch_size"):
        compute_elastic_config(cfg)


def test_elastic_v02_node_level():
    cfg = elastic_dict(version=0.2, model_parallel_size=2,
                       num_gpus_per_node=8, micro_batch_sizes=[2, 4])
    batch, valid_dp, micro = compute_elastic_config(cfg, num_gpus=64)
    # 64 chips / mp2 = 32-way dp must be valid
    assert 32 in valid_dp
    assert micro in (2, 4)
    # dp sizes move in whole nodes: all multiples of 8/2 = 4
    assert all(v % 4 == 0 for v in valid_dp)


def test_elastic_v02_bad_mp():
    cfg = elastic_dict(version=0.2, model_parallel_size=3, num_gpus_per_node=8)
    with pytest.raises(ElasticityError, match="divisible"):
        compute_elastic_config(cfg)


def test_elastic_version_gate():
    with pytest.raises(ElasticityError, match="version"):
        compute_elastic_config(elastic_dict(version=0.05))


def test_elastic_disabled():
    with pytest.raises(ElasticityError, match="disabled|missing"):
        compute_elastic_config({"elasticity": {"enabled": False}})


def test_runner_elastic_nodes(tmp_path):
    """--elastic_training trims the hostfile to a valid node count."""
    from deepspeed_tpu.launcher.runner import parse_args as rparse
    from deepspeed_tpu.launcher.runner import resolve_elastic_nodes

    cfg_path = tmp_path / "ds.json"
    cfg_path.write_text(json.dumps(elastic_dict(
        micro_batch_sizes=[2, 4], min_gpus=1, max_gpus=64,
        max_train_batch_size=256)))
    args = rparse(["--elastic_training", "--deepspeed_config", str(cfg_path),
                   "t.py"])
    resources = OrderedDict((f"w{i}", 4) for i in range(5))
    active = resolve_elastic_nodes(args, resources)
    assert 0 < len(active) <= 5
    total = sum(active.values())
    batch, valid = compute_elastic_config(json.loads(cfg_path.read_text()))[:2]
    assert total in valid


# ---------------------------------------------------------------------------
# operator CLIs: ds_ssh / ds_elastic / ds_bench (reference bin/)
# ---------------------------------------------------------------------------

def test_ds_ssh_local_fallback(tmp_path, capsys):
    from deepspeed_tpu.launcher.tools import ds_ssh_main

    rc = ds_ssh_main(["-H", str(tmp_path / "nope"), "echo", "ds-ssh-ok"])
    assert rc == 0


def test_ds_elastic_cli(tmp_path, capsys):
    import json

    from deepspeed_tpu.launcher.tools import ds_elastic_main

    cfg = {"elasticity": {"enabled": True, "max_train_batch_size": 2000,
                          "micro_batch_sizes": [2, 4, 6], "min_gpus": 1,
                          "max_gpus": 128, "version": 0.2,
                          "ignore_non_elastic_batch_info": True,
                          "num_gpus_per_node": 4, "model_parallel_size": 1}}
    p = tmp_path / "ds.json"
    p.write_text(json.dumps(cfg))
    assert ds_elastic_main(["-c", str(p)]) == 0
    out = capsys.readouterr().out
    assert "train_batch=1920" in out
    assert ds_elastic_main(["-c", str(p), "-w", "16"]) == 0
    out = capsys.readouterr().out
    assert "micro_batch=6" in out and "gas=20" in out


def test_ds_bench_one_op():
    from jax.sharding import Mesh
    import jax
    import numpy as np

    from deepspeed_tpu.launcher.ds_bench import bench_op

    mesh = Mesh(np.array(jax.devices()), ("x",))
    r = bench_op("all_reduce", mesh, 1 << 12, trials=2, warmups=1)
    assert r["lat_us"] > 0 and r["algbw_GBps"] > 0
    assert r["busbw_GBps"] == r["algbw_GBps"] * 2 * 7 / 8  # n=8 factor
