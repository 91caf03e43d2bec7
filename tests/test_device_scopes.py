"""Named device time: the scope vocabulary, the kernel names, the scope map
parsed from compiled HLO, and the lazy registry of jitted programs
(``utils/annotations.py``, ``profiling/trace.py``).

A scope is metadata in the compiled program; the device trace carries only
instruction names. These tests hold the three together on the CPU: every
declared scope really lands in a compiled program (and nothing undeclared
is used), the parser tells forward, backward and recomputation apart and
names fusions the compiler left unnamed, and none of it costs a lowering or
a compile until a reader asks for the maps.
"""
from __future__ import annotations

import ast
import collections
import glob
import os

import jax
import jax.monitoring
import numpy as np
import pytest

from deepspeed_tpu.profiling import trace as ptrace
from deepspeed_tpu.utils.annotations import (DEVICE_SCOPES, MODULE_SCOPES,
                                             device_scope)

PKG = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
                   "deepspeed_tpu")
SERVING = ("embed", "weight_walk", "norm", "attn_qkv", "kv_stage",
           "attn_core", "attn_out", "ffn", "head", "sample", "kv_commit")
#: a routed-expert layer's, in place of ``ffn`` (none in a dense program)
MOE = ("moe_router", "moe_dispatch", "moe_experts", "moe_combine")
#: inside ``attn_core`` in a model of window AND full layers (held to its
#: compiled programs by tests/test_smallthinker.py)
KINDS = ("attn_full", "attn_window")
TRAINING = ("embed", "head_loss", "optimizer", "grad_check", "zero_gather",
            "zero_reduce")
#: a "conv" layer's operator and the one write of a program's records (held
#: to their compiled programs, and to no other model's, by
#: tests/test_lfm2_moe.py)
RECORDS = ("conv_mix", "state_commit")
#: latent attention absorbed: what the latent page costs beside the kernel
#: (held to its compiled programs, and to no other model's, by
#: tests/test_kanana2_pages.py)
LATENT = ("latent_absorb",)

#: lowerings and backend compiles seen by this process, in order
_EVENTS: list[str] = []
jax.monitoring.register_event_duration_secs_listener(
    lambda event, secs, **_: _EVENTS.append(event) if event in (
        "/jax/core/compile/jaxpr_to_mlir_module_duration",
        "/jax/core/compile/backend_compile_duration") else None)


def _scopes_in(maps: dict) -> dict[str, collections.Counter]:
    return {mod: collections.Counter(ptrace.scope_of(op)
                                     for op in m["ops"].values())
            for mod, m in maps.items()}


@pytest.fixture(scope="module")
def serving():
    """A tiny engine (Pallas paged attention, interpreted) that has served
    two prompts: prefill steps, a decode step and decode windows ran."""
    from deepspeed_tpu.inference.engine_v2 import InferenceEngineV2
    from deepspeed_tpu.models import build_model

    model = build_model("tiny-gpt2", hidden_size=256, num_heads=4)
    eng = InferenceEngineV2(
        model, config={"block_size": 8, "num_blocks": 64, "max_seqs": 2,
                       "chunk": 8, "max_seq_len": 128},
        rng=jax.random.PRNGKey(6))
    rng = np.random.default_rng(2)
    prompts = [list(map(int, rng.integers(0, 256, (n,)))) for n in (9, 14)]
    out = eng.generate(prompts, max_new_tokens=8)
    return eng, prompts, out


@pytest.fixture(scope="module")
def training():
    """A tiny rematted model under ZeRO-3 with the explicit (ZeRO++)
    gradient collectives and clipping, after one step on a 4 x 2 mesh."""
    import deepspeed_tpu as ds
    from deepspeed_tpu.models import build_model

    model = build_model("tiny-llama", remat=True)
    batch = {"input_ids": np.random.default_rng(0).integers(
        0, 256, (16, 32)).astype(np.int32)}
    engine, *_ = ds.initialize(model=model, sample_batch=batch, config={
        "train_micro_batch_size_per_gpu": 2, "bf16": {"enabled": True},
        "optimizer": {"type": "AdamW", "params": {"lr": 3e-4}},
        "gradient_clipping": 1.0,
        "zero_optimization": {"stage": 3, "zero_quantized_gradients": True,
                              "stage3_param_persistence_threshold": 0},
        "mesh": {"fsdp": 4, "data": 2}, "steps_per_print": 10 ** 6})
    loss = float(engine.train_batch(batch))
    assert np.isfinite(loss)
    return engine, batch


# ---- the vocabulary ---------------------------------------------------------

def test_device_scope_refuses_an_undeclared_name():
    with pytest.raises(ValueError, match="DEVICE_SCOPES"):
        device_scope("attention")
    with device_scope("ffn"):
        pass


def test_every_use_is_declared_and_every_declaration_used():
    """AST scan of the package: ``device_scope`` only ever gets a string
    literal, every literal is declared, and every declared name is used."""
    used: dict[str, list[str]] = collections.defaultdict(list)
    for path in glob.glob(os.path.join(PKG, "**", "*.py"), recursive=True):
        with open(path, encoding="utf-8") as f:
            tree = ast.parse(f.read())
        for node in ast.walk(tree):
            if isinstance(node, ast.Call) and getattr(
                    node.func, "id", getattr(node.func, "attr", "")) \
                    == "device_scope":
                arg = node.args[0]
                assert isinstance(arg, ast.Constant) and isinstance(
                    arg.value, str), f"{path}:{node.lineno}: not a literal"
                used[arg.value].append(f"{path}:{node.lineno}")
    assert not set(used) - set(DEVICE_SCOPES), \
        {k: v for k, v in used.items() if k not in DEVICE_SCOPES}
    assert not set(DEVICE_SCOPES) - set(used)
    assert set(SERVING) | set(MOE) | set(TRAINING) | set(KINDS) \
        | set(RECORDS) | set(LATENT) == set(DEVICE_SCOPES)


@pytest.mark.parametrize("name", SERVING)
def test_serving_scope_lands_in_the_compiled_programs(serving, name):
    """Each declared serving scope names instructions of the compiled HLO
    of the prefill step, the decode step or the decode window (the
    compiler may fuse a small scope away in one of them: ``kv_stage`` is
    a transpose and a pad in the prefill step)."""
    found = _scopes_in(ptrace.program_scope_maps(
        {"jit_step_prefill", "jit_step_decode", "jit_run"}))
    assert set(found) == {"jit_step_prefill", "jit_step_decode", "jit_run"}
    assert sum(scopes[(name, "fwd")] for scopes in found.values()) > 0, \
        (name, {m: dict(s) for m, s in found.items()})
    assert found["jit_run"][(name, "fwd")] > 0, dict(found["jit_run"])


@pytest.mark.parametrize("name", TRAINING)
def test_training_scope_lands_in_the_compiled_step(training, name):
    found = _scopes_in(ptrace.program_scope_maps({"jit_train_step"}))
    scopes = found["jit_train_step"]
    assert sum(n for (s, _), n in scopes.items() if s == name) > 0, \
        (name, dict(scopes))


@pytest.fixture(scope="module")
def moe_scopes():
    """(scopes of the compiled programs of a tiny OLMoE engine, from its OWN
    programs (the process-wide maps would fold them into the dense
    engine's under the same module names), its counters): the engine is
    dropped before the fixture returns."""
    import gc

    from deepspeed_tpu.inference.engine_v2 import InferenceEngineV2
    from deepspeed_tpu.models import build_model

    eng = InferenceEngineV2(
        build_model("tiny-olmoe"), rng=jax.random.PRNGKey(6),
        config={"block_size": 8, "num_blocks": 64, "max_seqs": 2, "chunk": 8,
                "max_seq_len": 128})
    eng.generate([[5, 6, 7, 8, 9, 10, 11, 12, 13]], max_new_tokens=8)
    found: dict = collections.defaultdict(collections.Counter)
    for prog in eng._programs.values():
        if getattr(prog, "avals", None) is not None:
            parsed = prog.scopes()
            found[parsed["module"]].update(
                ptrace.scope_of(op) for op in parsed["ops"].values())
    stats = dict(eng.stats)
    stats["qk_norm_in_attn_qkv"] = _rsqrt_under_attn_qkv(eng)
    del eng, prog
    gc.collect()
    return found, stats


def _rsqrt_under_attn_qkv(eng) -> int:
    """rsqrt instructions of the decode window program whose ``op_name``
    lies under ``attn_qkv``: the q/k normalisation, and nothing else."""
    prog = next(p for k, p in eng._programs.items()
                if isinstance(k, tuple) and k[0] == "win"
                and getattr(p, "avals", None) is not None)
    args, kwargs = prog.avals
    text = prog.fn.lower(*args, **kwargs).compile().as_text()
    return sum(1 for ln in text.splitlines()
               if " rsqrt(" in ln and "/attn_qkv/" in ln)


@pytest.mark.parametrize("name", MOE)
def test_moe_scope_is_in_an_moe_program_and_in_no_dense_one(serving,
                                                            moe_scopes, name):
    found, _ = moe_scopes
    assert {"jit_step_prefill", "jit_run"} <= set(found)
    for mod in ("jit_step_prefill", "jit_run"):
        assert found[mod][(name, "fwd")] > 0, (mod, dict(found[mod]))
        # every layer of that stack is sparse and has no shared expert:
        # nothing is left under ``ffn``
        assert found[mod][("ffn", "fwd")] == 0
    dense = _scopes_in(ptrace.program_scope_maps(
        {"jit_step_prefill", "jit_step_decode", "jit_run"}))
    for mod, scopes in dense.items():
        assert scopes[(name, "fwd")] == 0, (mod, dict(scopes))


def test_moe_counters_are_host_arithmetic(serving, moe_scopes):
    """``moe_routed_rows`` / ``moe_padded_rows`` are booked from the plan's
    shape at dispatch: no lowering, no compile, and nothing at all for a
    dense model."""
    eng, _, _ = serving
    before = len(_EVENTS)
    eng._count_moe(5, 8)
    eng._count_moe(5, 8, iters=4)
    assert len(_EVENTS) == before
    assert eng.stats["moe_routed_rows"] == eng.stats["moe_padded_rows"] == 0
    _, st = moe_scopes
    assert 0 < st["moe_routed_rows"] < st["moe_padded_rows"]


def test_attn_step_counters_are_host_arithmetic(serving, monkeypatch):
    """``attn_steps_live`` / ``attn_steps_rect`` are booked at dispatch from
    the plan's lengths: the paged kernel's steps that read a page against
    the slots x (table width + stage pages) rectangle, times iterations
    and layers. Hand-counted for this engine's 8-token pages and 16-page
    tables; no lowering, no compile, and nothing where the gather fallback
    serves."""
    eng, _, _ = serving
    L = eng.mcfg.num_layers
    assert eng.state.max_blocks_per_seq == 16 and eng.state.max_seqs == 2
    st = eng.stats
    assert 0 < st["attn_steps_live"] < st["attn_steps_rect"]
    assert st["attn_steps_live"] % L == st["attn_steps_rect"] % L == 0
    before, live, rect = len(_EVENTS), st["attn_steps_live"], \
        st["attn_steps_rect"]
    # a window of 4 iterations: slot 0 has 20 tokens in the pool (pages 0,
    # 1, 2 hold a key below 20) and its staged token: 4 steps; slot 1 is
    # empty; the rectangle is 2 x (16 + 1)
    eng._count_attn_steps(np.array([21, 0]), np.array([20, 0]), 8, iters=4)
    assert st["attn_steps_live"] - live == 4 * L * 4
    assert st["attn_steps_rect"] - rect == 4 * L * 34
    # a 24-token chunk (3 stage pages) behind 16 tokens (2 pool pages)
    live, rect = st["attn_steps_live"], st["attn_steps_rect"]
    eng._count_attn_steps(np.array([40, 0]), np.array([16, 0]), 24)
    assert st["attn_steps_live"] - live == L * 5
    assert st["attn_steps_rect"] - rect == L * 2 * 19
    monkeypatch.setattr(eng, "_attn_paged", False)
    eng._count_attn_steps(np.array([21, 0]), np.array([20, 0]), 8)
    assert st["attn_steps_rect"] - rect == L * 2 * 19
    assert len(_EVENTS) == before


def test_qk_norm_is_booked_to_attn_qkv_and_absent_from_a_dense_program(
        serving, moe_scopes):
    """OLMoE's q/k normalisation adds its rsqrt under ``attn_qkv``; a model
    without it compiles to a decode program with none there (the dense
    cells' programs gain no instruction from PR 25)."""
    eng, _, _ = serving
    assert _rsqrt_under_attn_qkv(eng) == 0
    assert moe_scopes[1]["qk_norm_in_attn_qkv"] >= 1


def test_no_scope_outside_the_vocabulary_is_read(serving, training):
    """What the reader calls a scope is a declared name, a flax module
    name, ``layer/<module>``, or the remainder."""
    allowed = set(DEVICE_SCOPES) | set(MODULE_SCOPES) | {
        f"layer/{m}" for m in MODULE_SCOPES} | {ptrace.UNSCOPED}
    for mod, scopes in _scopes_in(ptrace.program_scope_maps()).items():
        assert {s for s, _ in scopes} <= allowed, (mod, dict(scopes))


# ---- the parser -------------------------------------------------------------

def test_parser_tells_forward_backward_and_recomputation_apart(training):
    scopes = _scopes_in(ptrace.program_scope_maps(
        {"jit_train_step"}))["jit_train_step"]
    for scope in ("layer/attn", "layer/ffn"):
        for direction in ("fwd", "bwd", "recompute"):
            assert scopes[(scope, direction)] > 0, (scope, direction)
    # the update and the checks run once, in no direction but forward
    assert scopes[("optimizer", "fwd")] and not scopes[("optimizer", "bwd")]
    assert not any(s.startswith("layer_") for s, _ in scopes)


def test_parser_names_a_fusion_the_compiler_left_unnamed(training):
    """A fusion with an ``op_name`` of its own keeps it; one without takes
    it from the computation it calls, and stays unnamed only where that
    computation carries no name at all (a cast of a parameter)."""
    engine, batch = training
    prog = engine._train_step
    args, kwargs = prog.avals
    text = prog.fn.lower(*args, **kwargs).compile().as_text()
    module, ops = ptrace.parse_hlo_scopes(text)
    assert module == "jit_train_step"
    with_own = named_anyway = unnamed = 0
    for line in text.splitlines():
        m = ptrace._INSTRUCTION.match(line)
        if m is None or " fusion(" not in line:
            continue
        head = line[:line.find("backend_config=")] \
            if "backend_config=" in line else line
        if ptrace._OP_NAME.search(line):
            with_own += 1
            assert ops[m.group(2)] == ptrace._OP_NAME.search(line).group(1)
        elif ops[m.group(2)]:
            named_anyway += 1
        else:
            unnamed += 1
            callee = text[text.index(
                "%" + ptrace._CALLS.search(head).group(1) + " ("):]
            assert "op_name=" not in callee[:callee.index("\n}")], line[:200]
    assert with_own > 0 and named_anyway > 0, (with_own, named_anyway, unnamed)


@pytest.mark.parametrize("path, want", [
    ("jit(loss)/jvp(TransformerLM)/layer_0/attn/mul", ("layer/attn", "fwd")),
    ("jit(loss)/transpose(jvp(TransformerLM))/layer_11/ffn/dot_general",
     ("layer/ffn", "bwd")),
    ("jit(f)/transpose(jvp(TransformerLM))/checkpoint/rematted_computation/"
     "layer_3/attn/mul", ("layer/attn", "recompute")),
    ("jit(f)/jvp(TransformerLM)/ln_final/rsqrt", ("ln_final", "fwd")),
    ("jit(f)/transpose(jvp(head_loss))/scatter-add", ("head_loss", "bwd")),
    ("jit(run)/while/body/closed_call/attn_core/paged_attn_decode/pallas_call",
     ("attn_core", "fwd")),
    ("jit(run)/while/body/closed_call/ffn/DenseFFN/dot_general",
     ("ffn", "fwd")),
    ("jit(run)/while/body/dynamic_slice", ("unscoped", "fwd")),
    ("", ("unscoped", "fwd")),
    (None, ("unscoped", "fwd")),
])
def test_scope_of(path, want):
    assert ptrace.scope_of(path) == want


def test_merged_maps_count_a_disagreement_as_ambiguous():
    a = {"fusion.1": "jit(run)/ffn/dot_general", "fusion.2": "jit(run)/ffn/x",
         "copy.3": ""}
    b = {"fusion.1": "jit(run)/ffn/add", "fusion.2": "jit(run)/head/x",
         "fusion.9": "jit(run)/norm/mul"}
    merged = ptrace.merge_scope_maps([a, b])
    assert ptrace.scope_of(merged["fusion.1"]) == ("ffn", "fwd")
    assert merged["fusion.2"] == ptrace.AMBIGUOUS
    assert ptrace.scope_of(merged["fusion.9"]) == ("norm", "fwd")
    assert merged["copy.3"] == ""


#: a compiled step program as the v5e's compiler prints one (cut to what
#: the reader parses): the pool copied OUT of its pinned layout for a fused
#: read-modify-write and BACK after the chain of merges (a layout copy each
#: way), a second pool protected by a same-layout copy, and a small copy
_HLO_WITH_POOL_COPIES = """HloModule jit_step_prefill, is_scheduled=true

%fused_computation.273 (param_0: bf16[3,1,1,64,128,640]) -> bf16[3,1,1,64,128,640] {
  %param_0 = bf16[3,1,1,64,128,640]{5,4,0,3,2,1:T(8,128)(2,1)} parameter(0)
  ROOT %copy.9 = bf16[3,1,1,64,128,640]{5,4,0,3,2,1:T(8,128)(2,1)} copy(%param_0)
}

ENTRY %main.1 (kv_pool_0_.1: bf16[3,1,1,64,128,640], kv_pool_1_.1: bf16[1,2,4,96,128,128]) -> (bf16[3,1,1,64,128,640], bf16[1,2,4,96,128,128]) {
  %kv_pool_0_.1 = bf16[3,1,1,64,128,640]{5,4,3,2,1,0:T(8,128)(2,1)} parameter(0), metadata={op_name="kv_pool[0]"}
  %kv_pool_1_.1 = bf16[1,2,4,96,128,128]{5,4,3,2,1,0:T(8,128)(2,1)} parameter(1)
  %copy.267 = bf16[3,1,1,64,128,640]{5,4,0,3,2,1:T(8,128)(2,1)} copy(%kv_pool_0_.1), sharding={replicated}
  %fusion.164 = bf16[3,1,1,64,128,640]{5,4,0,3,2,1:T(8,128)(2,1)} fusion(%copy.267, %select_n.123), kind=kLoop, calls=%fused_computation.273
  %dynamic_update_slice.50 = bf16[3,1,1,64,128,640]{5,4,0,3,2,1:T(8,128)(2,1)} dynamic-update-slice(%fusion.164, %get-tuple-element.1210, %constant.206)
  %copy.284 = bf16[3,1,1,64,128,640]{5,4,3,2,1,0:T(8,128)(2,1)} copy(bf16[3,1,1,64,128,640]{5,4,0,3,2,1:T(8,128)(2,1)} %dynamic_update_slice.50)
  %copy.301 = bf16[1,2,4,96,128,128]{5,4,3,2,1,0:T(8,128)(2,1)} copy(%kv_pool_1_.1), metadata={op_name="jit(step_prefill)/kv_commit/dynamic_update_slice"}
  %dynamic_update_slice.60 = bf16[1,2,4,96,128,128]{5,4,3,2,1,0:T(8,128)(2,1)} dynamic-update-slice(%copy.301, %get-tuple-element.1211, %constant.206)
  %copy.12 = bf16[48,1,640]{2,1,0:T(8,128)(2,1)} copy(%get-tuple-element.7)
  ROOT %tuple.145 = (bf16[3,1,1,64,128,640]{5,4,3,2,1,0:T(8,128)(2,1)}, bf16[1,2,4,96,128,128]{5,4,3,2,1,0:T(8,128)(2,1)}) tuple(%copy.284, %dynamic_update_slice.60)
}
"""


def test_pool_sized_copies_names_layout_and_hazard_copies():
    """``pool_sized_copies`` over a compiled text: the copies of a POOL's
    shape with both layouts (a layout copy's differ, a hazard copy's are
    equal), their operand and who reads them; a small copy, and a copy
    inside a fused computation of another result than the entry's, are
    the reader's business only by their shape."""
    pools = [(3, 1, 1, 64, 128, 640), (1, 2, 4, 96, 128, 128)]
    got = {c["instruction"]: c for c in ptrace.pool_sized_copies(
        _HLO_WITH_POOL_COPIES, pools)}
    assert sorted(got) == ["copy.267", "copy.284", "copy.301", "copy.9"]
    out, back, hazard = got["copy.267"], got["copy.284"], got["copy.301"]
    assert out["operand"] == "kv_pool_0_.1" and out["users"] == ["fusion.164"]
    # (an operand printed bare takes the layout of the line that defines it)
    assert out["operand_layout"] == "{5,4,3,2,1,0:T(8,128)(2,1)}"
    assert out["result_layout"] == "{5,4,0,3,2,1:T(8,128)(2,1)}"
    assert back["operand"] == "dynamic_update_slice.50"
    assert (back["operand_layout"], back["result_layout"]) == (
        out["result_layout"], out["operand_layout"])
    assert back["users"] == ["tuple.145"]
    assert hazard["operand_layout"] == hazard["result_layout"]
    assert hazard["shape"] == [1, 2, 4, 96, 128, 128]
    assert hazard["users"] == ["dynamic_update_slice.60"]
    # one pool's shape only: the other's copies are not named
    assert [c["instruction"] for c in ptrace.pool_sized_copies(
        _HLO_WITH_POOL_COPIES, pools[1:])] == ["copy.301"]
    assert ptrace.pool_sized_copies(_HLO_WITH_POOL_COPIES, [(48, 1, 64)]) == []


#: a small scoped device trace recorded on a v5e
#: (benchmark/tests/record_scope_fixture.py) with what the chip run wrote
#: beside it: the program's scope maps, the benchmark reader's numbers
_SCOPED_TRACE = os.path.join(os.path.dirname(PKG), "benchmark", "tests",
                             "data", "tpu_v5e_scopes.xplane.pb")


def test_scope_breakdown_of_a_recorded_trace():
    """profiling.trace.scope_breakdown: device time by program, scope and
    direction, from the maps the program published when the trace was
    recorded — forward, backward and recomputation apart, the named
    kernel under ``attn_core``, every program's rows summing to its op
    time."""
    from deepspeed_tpu.profiling.trace import (op_breakdown,
                                               print_breakdown,
                                               scope_breakdown)
    import json

    with open(_SCOPED_TRACE.replace(".xplane.pb", ".expected.json")) as fh:
        expected = json.load(fh)
    table = scope_breakdown(_SCOPED_TRACE, maps=expected["maps"])
    assert set(table) >= {"jit_fx_decode", "jit_fx_train"}
    for scope in ("weight_walk", "attn_core", "ffn", "head"):
        assert table["jit_fx_decode"][(scope, "fwd")] > 0, scope
    train = table["jit_fx_train"]
    for direction in ("fwd", "bwd", "recompute"):
        assert train[("layer/ffn", direction)] > 0, direction
    assert sum(ms for t in table.values() for ms in t.values()) \
        == pytest.approx(sum(op_breakdown(_SCOPED_TRACE).values()))
    kernel_ms = op_breakdown(_SCOPED_TRACE)[expected["kernel"]]
    assert table["jit_fx_decode"][("attn_core", "fwd")] \
        == pytest.approx(kernel_ms)
    # without maps every op is unscoped, never guessed
    bare = scope_breakdown(_SCOPED_TRACE, maps={})
    assert all(k == ("unscoped", "fwd") for t in bare.values() for k in t)
    text = print_breakdown(_SCOPED_TRACE, by_scope=True,
                           maps=expected["maps"])
    assert "jit_fx_decode  weight_walk  fwd" in text


# ---- the registry is lazy ---------------------------------------------------

def test_registry_lowers_nothing_until_asked():
    """Building an engine and serving registers its programs — a thunk and
    abstract arguments — and adds no lowering and no compile: serving the
    same shapes again costs none at all, and the first request for the maps
    is what fetches and parses the compiled text (a second one is free)."""
    from deepspeed_tpu.inference.engine_v2 import InferenceEngineV2
    from deepspeed_tpu.models import build_model

    model = build_model("tiny-llama")
    eng = InferenceEngineV2(
        model, config={"block_size": 8, "num_blocks": 64, "max_seqs": 2,
                       "chunk": 8, "max_seq_len": 128},
        rng=jax.random.PRNGKey(1))
    prompts = [[3, 5, 7, 11, 13, 17, 19, 23, 29], [2] * 14]
    first = eng.generate(prompts, max_new_tokens=8)
    progs = [p for p in ptrace.registered_programs()
             if p in eng._programs.values()]
    assert progs and len(progs) == len(eng._programs)
    assert all(p._parsed is None for p in progs)
    assert all(p.avals is not None for p in progs)
    leaves = jax.tree.leaves([p.avals for p in progs])
    assert all(isinstance(a, jax.ShapeDtypeStruct) for a in leaves)

    # serving again: nothing lowers or compiles but a program the async
    # pipeline had not needed yet (a window of another size), once
    n0 = len(_EVENTS)
    assert eng.generate(prompts, max_new_tokens=8) == first
    new = len(eng._programs) - len(progs)
    assert _EVENTS[n0:].count(
        "/jax/core/compile/jaxpr_to_mlir_module_duration") <= new, \
        "serving warmed shapes lowered again"
    assert _EVENTS[n0:].count(
        "/jax/core/compile/backend_compile_duration") <= new
    progs = list(eng._programs.values())
    assert all(p._parsed is None for p in progs)

    n0 = len(_EVENTS)
    maps = ptrace.program_scope_maps(
        {p.module_name for p in progs})
    # asking is what parses — and, the abstract arguments being those of
    # the real call, finds jit's own lowering and executable again: at
    # most one lowering a program, no backend compile
    assert _EVENTS[n0:].count(
        "/jax/core/compile/jaxpr_to_mlir_module_duration") <= len(progs)
    assert "/jax/core/compile/backend_compile_duration" not in _EVENTS[n0:]
    assert sum(m["programs"] for m in maps.values()) >= len(progs)
    assert all(p._parsed is not None for p in progs)
    n1 = len(_EVENTS)
    assert ptrace.program_scope_maps({p.module_name for p in progs}).keys() \
        == maps.keys()
    assert _EVENTS[n1:] == []


def test_registered_program_is_dropped_with_its_engine():
    fn = ptrace.register_program(jax.jit(lambda x: x + 1))
    assert int(fn(1)) == 2 and fn in ptrace.registered_programs()
    assert fn.lower(1).compile() is not None        # the jit's own surface
    del fn
    import gc
    gc.collect()
    assert all(p.module_name != "jit__lambda_"
               for p in ptrace.registered_programs())


# ---- kernel names -----------------------------------------------------------

def test_every_pallas_call_is_named():
    missing = []
    for path in glob.glob(os.path.join(PKG, "ops", "pallas", "*.py")):
        with open(path, encoding="utf-8") as f:
            tree = ast.parse(f.read())
        for node in ast.walk(tree):
            if isinstance(node, ast.Call) and getattr(
                    node.func, "attr", "") == "pallas_call":
                if not any(k.arg == "name" for k in node.keywords):
                    missing.append(f"{path}:{node.lineno}")
    assert not missing, missing


def _kernel_names(jaxpr) -> set[str]:
    """Names of the ``pallas_call`` equations of a jaxpr, sub-jaxprs
    (scan and while bodies, closed calls) included."""
    names = set()
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pallas_call":
            names.add(eqn.params["name"])
        for sub in jax.core.jaxprs_in_params(eqn.params):
            names |= _kernel_names(sub)
    return names


@pytest.mark.parametrize("form, keys, want", [
    ("decode", lambda k: k[0] == "win" or k[0] == 1, {"paged_attn_decode"}),
    ("prefill", lambda k: isinstance(k[0], int) and k[0] > 1,
     {"paged_attn_prefill", "paged_attn_decode"}),
])
def test_paged_kernel_has_one_name_per_form(serving, form, keys, want):
    """The benchmark's roofline reader leaves its metric out when one form's
    programs launch kernels of more than one name: the decode window and
    single-step programs launch ``paged_attn_decode`` and nothing else; the
    prefill step ``paged_attn_prefill`` for its chunks and, since PR 52,
    the decode form for its decode block (the parked
    ``prefill_attn_roofline`` would refuse itself there: ``PERF.md``
    section 7)."""
    eng, _, _ = serving
    assert eng._attn_decode_sel.is_pallas
    progs = [p for k, p in eng._programs.items() if keys(k)]
    assert progs, (form, list(eng._programs))
    for prog in progs:
        args, kwargs = prog.avals
        names = _kernel_names(jax.make_jaxpr(prog.fn)(*args, **kwargs).jaxpr)
        assert names == want, (form, names)
