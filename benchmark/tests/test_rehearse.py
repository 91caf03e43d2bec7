"""Rehearsals: every cell end to end at a tiny preset on the CPU
(``--rehearse``; the four-chip cell on four virtual devices), a run without
a chip refusing, and the proof that the harness is driven by data — a new
cell, configuration, traffic mix and per-layer metric are new files and one
manifest entry each, with no edit to a file that is there."""
import json
import os
import shutil
import subprocess
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
CONTRACT_KEYS = {"correct", "attempted", "failed", "metrics", "device"}


def run_cell(root, *argv, timeout=900):
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    env["JAX_PLATFORMS"] = "cpu"
    p = subprocess.run([sys.executable, "benchmark/run.py", *argv], cwd=root,
                       env=env, capture_output=True, text=True, timeout=timeout)
    return p.returncode, p.stdout.strip().splitlines()


def manifest(root=ROOT):
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        return json.load(f)


def test_without_a_chip_the_run_fails_and_prints_no_result():
    rc, lines = run_cell(ROOT, "--workload", "mistral7b-zero3-sft",
                         "--seed", "0", "--seconds", "2", "--trace", "0",
                         timeout=120)
    assert rc != 0
    assert not any(ln.startswith("{") and '"correct"' in ln for ln in lines)


@pytest.mark.rehearsal
@pytest.mark.parametrize("trace", [0, 1])
@pytest.mark.parametrize("cell", [w["name"] for w in manifest()["workloads"]])
def test_cell_rehearses(cell, trace):
    rc, lines = run_cell(ROOT, "--workload", cell, "--seed", "3", "--seconds",
                         "10", "--trace", str(trace), "--rehearse")
    assert rc == 0, "\n".join(lines[-30:])
    last = json.loads(lines[-1])
    assert set(last) - {"breakdown"} == CONTRACT_KEYS
    entry = next(w for w in manifest()["workloads"] if w["name"] == cell)
    assert last["device"]["platform"] == "cpu"
    assert last["device"]["count"] == entry["chips"]
    assert last["correct"] is True and last["failed"] == 0
    assert last["attempted"] > 0
    if not trace:
        want = {m["name"] for m in manifest()["end_to_end"]
                if cell in m.get("workloads", [cell])}
        assert set(last["metrics"]) == want
        assert all(v["value"] > 0 for v in last["metrics"].values())
    # reported by every run; that it is 0 is a property of the chip runs
    # (warm-up reaches the worker's programs only through requests, and a
    # rehearsal's handful of requests leaves shapes unreached)
    assert any("compilations inside the window:" in ln for ln in lines)
    if entry["chips"] == 4:
        assert any("compilations inside the window: 0" in ln for ln in lines)


def later_pr_view(tmp_path):
    """A copy of the benchmark as a later PR holds it (the program linked
    in), and every file's bytes, to show afterwards that none was edited."""
    root = str(tmp_path)
    shutil.copytree(os.path.join(ROOT, "benchmark"),
                    os.path.join(root, "benchmark"),
                    ignore=shutil.ignore_patterns("__pycache__"))
    os.symlink(os.path.join(ROOT, "deepspeed_tpu"),
               os.path.join(root, "deepspeed_tpu"))
    before = {}
    for d, _, files in os.walk(os.path.join(root, "benchmark")):
        for f in files:
            with open(os.path.join(d, f), "rb") as fh:
                before[os.path.join(d, f)] = fh.read()
    return root, before


def nothing_edited(before):
    for path, blob in before.items():
        with open(path, "rb") as fh:
            assert fh.read() == blob, f"{path} was edited"


@pytest.mark.rehearsal
def test_new_cell_config_traffic_and_metric_are_files_and_entries(tmp_path):
    """A later PR's view: copy the benchmark, ADD four files and four
    manifest entries, edit nothing — and the new cell runs, reporting the
    new per-layer metric."""
    root, before = later_pr_view(tmp_path)

    def load(rel):
        with open(os.path.join(ROOT, "benchmark", rel)) as f:
            return json.load(f)

    def add(rel, obj):
        path = os.path.join(root, "benchmark", rel)
        assert not os.path.exists(path)
        with open(path, "w") as f:
            f.write(obj if isinstance(obj, str) else json.dumps(obj))

    conf = load("configs/mistral-7b-v0.3-serve-l12.json")
    conf["rehearse"]["engine"]["max_seqs"] = 3           # a second deployment
    add("configs/second-serve.json", conf)
    traf = load("traffic/chat-steady.json")
    traf["rehearse"]["arrivals"] = {"process": "poisson", "rate": 3.0}
    add("traffic/chat-brisk.json", traf)                  # data only
    cell = load("workloads/mistral7b-chat-steady.json")
    cell.update(config="second-serve", traffic="chat-brisk")
    add("workloads/second-chat-brisk.json", cell)
    add("layers/windows_per_s.py",
        '"""Decode windows dispatched a second."""\n\n\ndef read(ctx):\n'
        '    return ctx["stats"]["windows"] / ctx["window_s"]\n')
    m = manifest()
    m["configs"].append({"name": "second-serve", "source": "x", "why": "y",
                         "file": "benchmark/configs/second-serve.json",
                         "reduced": ["num_hidden_layers"]})
    m["workloads"].append({"name": "second-chat-brisk", "chips": 1, "why": "z",
                           "config": "second-serve", "traffic": "chat-brisk"})
    for e in m["end_to_end"]:
        if "workloads" in e and "mistral7b-chat-steady" in e["workloads"]:
            e["workloads"].append("second-chat-brisk")
    m["per_layer"].append({"name": "windows_per_s", "unit": "1/s",
                           "better": "higher", "source": "program_counter",
                           "layer": "engine programs", "moves": "tpot_p90_ms",
                           "workloads": ["second-chat-brisk"]})
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(m, f)

    rc, lines = run_cell(root, "--workload", "second-chat-brisk", "--seed",
                         "1", "--seconds", "10", "--trace", "1", "--rehearse")
    assert rc == 0, "\n".join(lines[-30:])
    last = json.loads(lines[-1])
    assert last["metrics"]["windows_per_s"]["value"] > 0
    rc, lines = run_cell(root, "--workload", "second-chat-brisk", "--seed",
                         "1", "--seconds", "10", "--trace", "0", "--rehearse")
    assert rc == 0, "\n".join(lines[-30:])
    assert set(json.loads(lines[-1])["metrics"]) == {"tpot_p90_ms", "setup_s"}
    nothing_edited(before)


@pytest.mark.rehearsal
@pytest.mark.parametrize("trace", [0, 1])
def test_parked_cell_runs_once_its_entries_are_in_the_manifest(tmp_path, trace):
    """``benchmark/parked/<cell>.json`` holds the manifest entries of a cell
    whose files are in the tree but whose end-to-end metric is not admitted
    yet. Pasting them in — and nothing else — runs it."""
    root, before = later_pr_view(tmp_path)
    cell = "mistral7b-doc-batch"
    with open(os.path.join(ROOT, "benchmark", "parked", f"{cell}.json")) as f:
        parked = json.load(f)
    m = manifest()
    assert cell not in [w["name"] for w in m["workloads"]]
    m["workloads"].append(parked["workload"])
    m["end_to_end"] += parked["end_to_end"]
    m["per_layer"] += parked["per_layer"]
    for e in m["end_to_end"]:
        if e["name"] == "setup_s" and "workloads" in e:
            e["workloads"].append(cell)
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(m, f)
    rc, lines = run_cell(root, "--workload", cell, "--seed", "3", "--seconds",
                         "10", "--trace", str(trace), "--rehearse")
    assert rc == 0, "\n".join(lines[-30:])
    last = json.loads(lines[-1])
    assert last["correct"] is True and last["failed"] == 0
    if trace:
        assert "doc_ttft_p50_s" in last["metrics"]
    else:
        assert set(last["metrics"]) == {"serve_tok_per_s", "setup_s"}
        assert last["metrics"]["serve_tok_per_s"]["value"] > 0
    nothing_edited(before)
