#!/usr/bin/env python3
"""tools/scripts/hash_programs.py — hold two trees to the SAME step programs
on the CPU (ISSUE 54, part F; PR 50 did it by hand). For every serving
configuration of ``BENCHMARK.json`` that the tree can build, at its
``rehearse`` size: build the engine, serve three prompts (prefill chunks, a
decode step, decode windows, rows riding a prefill step), and print one
``HASH`` line a compiled program — the sha1 of its compiled text less the
metadata and the file/stack tables — and one for the served tokens. Run it
with each tree first on the path and diff the two outputs:

    python benchmark/tools/scripts/hash_programs.py <tree> > a.txt
    python benchmark/tools/scripts/hash_programs.py <other tree> > b.txt

A configuration whose preset a tree does not know is skipped by name (the
parent of the PR that adds it)."""
import hashlib
import json
import os
import re
import sys

tree = os.path.abspath(sys.argv[1])
sys.path.insert(0, tree)
import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

import deepspeed_tpu  # noqa: E402

assert deepspeed_tpu.__file__.startswith(tree), deepspeed_tpu.__file__
from deepspeed_tpu._jax_compat import set_cpu_devices  # noqa: E402

set_cpu_devices(1)
from deepspeed_tpu.inference.engine_v2 import InferenceEngineV2  # noqa: E402
from deepspeed_tpu.models import build_model  # noqa: E402

HERE = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
with open(os.path.join(os.path.dirname(HERE), "BENCHMARK.json")) as f:
    manifest = json.load(f)
for entry in manifest["configs"]:
    with open(os.path.join(os.path.dirname(HERE), entry["file"])) as f:
        conf = json.load(f)
    if not conf.get("mode", "").startswith("serve"):
        continue
    small = conf["rehearse"]
    try:
        model = build_model(small["preset"], **small.get("overrides", {}))
    except ValueError as e:
        print("SKIP", entry["name"], str(e)[:60])
        continue
    eng = InferenceEngineV2(model, config=dict(small["engine"]),
                            rng=jax.random.PRNGKey(0))
    out = eng.generate([list(range(1, 40)), [7, 3, 9], list(range(5, 22))],
                       max_new_tokens=10)
    rows = []
    for prog in eng._programs.values():
        if getattr(prog, "avals", None) is None:
            continue
        text = prog.fn.lower(*prog.avals[0],
                             **prog.avals[1]).compile().as_text()
        text = re.sub(r", metadata=\{[^}]*\}", "", text)
        text = "\n".join(ln for ln in text.splitlines()
                         if not re.match(r"\s*\d", ln))
        shapes = [tuple(a.shape) for a in jax.tree.leaves(prog.avals[0])
                  if hasattr(a, "shape")][-12:]
        rows.append((prog.module_name, str(shapes)[-90:],
                     hashlib.sha1(text.encode()).hexdigest()[:12]))
    for r in sorted(rows):
        print("HASH", entry["name"], *r)
    print("HASH", entry["name"], "tokens",
          hashlib.sha1(str(out).encode()).hexdigest()[:12])
    del eng
