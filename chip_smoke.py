#!/usr/bin/env python3
"""chip_smoke.py — does this tree still start on the chip?

Drives the two main paths once, through the entry points a user calls, at
the full width of gpt2-350m (24 layers, nothing cut; weights and data from
``--seed``), and checks what comes out by the repo's own means:

  train         ``ds.initialize`` + ``engine.train_batch`` (bf16, AdamW,
                ZeRO, micro-batch 8 x 1024, ``attn_impl`` left at ``auto``):
                losses finite, near ln(vocab), distinct and falling; the
                compiled step carries the flash kernel; the first loss
                agrees with the same step under ``attn_impl="xla"``.
  serve         a ``Router`` over one real ``InferenceEngineV2`` worker:
                every request done, zero double commits, the worker says
                which platform it runs on, Pallas dispatches counted.
  serve_parity  once the worker has given the chip back: the same engine
                in-process against itself under ``use_pallas_decode=False``
                on teacher-forced prefixes (per-step argmax — free-running
                bf16 chains flip on near-ties between formulations).

``--chips 4`` runs ONE other phase and none of the above: gpt2-350m under
ZeRO-3 on ``mesh {fsdp: 2, tensor: 2}`` against a one-device run of the
same seed and batches in the same process.

One process for each chip: a chip belongs to one process at a time, so
this parent never initialises a JAX backend. It starts each phase as a
child, one after another, relays its lines, and builds the last line of
stdout from what the children reported:

  {"ok": true, "device": {"platform": "tpu", "kind": "...", "count": 1}}

Any failure in any phase: ``"ok": false`` and a non-zero exit. A host
without an accelerator fails in seconds, before any phase is built.
``--rehearse`` runs the same code on the CPU at tiny sizes (interpret-mode
kernels, virtual devices for ``--chips 4``); its last line then names
``cpu`` truthfully, and the driver never passes it.
"""
from __future__ import annotations

import argparse
import json
import math
import os
import signal
import subprocess
import sys
import threading
import time

HERE = os.path.dirname(os.path.abspath(__file__))
#: phase output that should come back from a chip run (replica logs,
#: telemetry snapshots, the streams handed from serve to serve_parity)
OUT_DIR = os.path.join(HERE, "chiprun_out", "chip_smoke")
MARK = "CHIP_SMOKE_PHASE "
#: the whole run must end inside the driver's 1200 s
BUDGET_S = 1150.0

PHASES = {1: ("train", "serve", "serve_parity"), 4: ("train_sharded",)}

#: gpt2-350m whole: depth is what a smoke may cut, and this one cuts nothing
REAL = {
    "model": "gpt2-350m", "overrides": {},
    "micro_batch": 8, "seq": 1024, "steps": 5, "lr": 3e-4,
    # KV pool: 96 pages x 12 MiB nominal = 1152 MiB, and at head width 64
    # the row-major pool occupies TWICE its nominal bytes on the device
    # (lane padding to 128) — 2.25 GiB beside 0.7 GiB of weights
    "engine": {"block_size": 128, "num_blocks": 96, "max_seqs": 8,
               "chunk": 128, "max_seq_len": 1024},
    # short prompts, two sharing a 256-token prefix, one longer than any
    # single prefill chunk of a full batch
    "prompts": {"short": (24, 57), "shared_prefix": 256,
                "shared_tails": (40, 72), "long": 700},
    "gen": 32, "sharded_overrides": {},
}
#: same code, sizes a CPU finishes in a minute or two. One head of width
#: 64 keeps the flash and paged kernels eligible (interpret mode)
REHEARSAL = {
    "model": "tiny-gpt2", "overrides": {"num_heads": 1, "max_seq_len": 256},
    "micro_batch": 2, "seq": 128, "steps": 4, "lr": 3e-3,
    "engine": {"block_size": 16, "num_blocks": 96, "max_seqs": 4,
               "chunk": 16, "max_seq_len": 256},
    "prompts": {"short": (5, 11), "shared_prefix": 32,
                "shared_tails": (6, 9), "long": 150},
    "gen": 8,
    # the tensor axis needs heads to split: the preset's four
    "sharded_overrides": {"max_seq_len": 256},
}
#: serve_parity checks every PARITY_STRIDE-th step of each served stream
PARITY_STRIDE = 2
#: four chips: global batch 4 (two per data-parallel rank), sized when
#: both sides ran XLA attention (a full gpt2-350m step at micro-batch 8
#: needed 19 GiB of the chip's 15.75). Since PR 29 both sides run the flash
#: kernel: the one-device mesh directly, the sharded one per shard
SHARDED_GLOBAL_BATCH = 4


class SmokeFailure(AssertionError):
    """A phase's check did not hold."""


def check(cond: bool, what: str) -> None:
    if not cond:
        raise SmokeFailure(what)
    print(f"  ok: {what}", flush=True)


# ---------------------------------------------------------------------------
# children: everything below the next rule runs in a phase's own process
# ---------------------------------------------------------------------------

class CompileClock:
    """Seconds spent in XLA backend compiles (or, on a persistent-cache
    hit, in reading the executable back) and cache hits, from jax's own
    monitoring events — compile time printed apart from run time."""

    def __init__(self):
        import jax.monitoring as mon

        self.seconds = 0.0
        self.cache_hits = 0
        mon.register_event_duration_secs_listener(self._on_duration)
        mon.register_event_listener(self._on_event)

    def _on_duration(self, event: str, secs: float, **_) -> None:
        if event == "/jax/core/compile/backend_compile_duration":
            self.seconds += secs

    def _on_event(self, event: str, **_) -> None:
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1

    def report(self) -> dict:
        return {"compile_s": round(self.seconds, 2),
                "compile_cache_hits": self.cache_hits}


def require_device(args) -> dict:
    """FIRST thing a device phase does: name the device jax gives this
    process and refuse the wrong one — a CPU run is never a pass."""
    import jax

    devs = jax.devices()
    dev = {"platform": str(devs[0].platform),
           "kind": str(devs[0].device_kind), "count": len(devs)}
    print(f"  device: {dev}", flush=True)
    want = "cpu" if args.rehearse else "tpu"
    if dev["platform"] != want:
        raise SmokeFailure(f"jax runs on {dev['platform']!r}, this run "
                           f"needs {want!r}")
    if dev["count"] != args.chips:
        raise SmokeFailure(f"{dev['count']} device(s) visible, this run "
                           f"needs {args.chips}")
    return dev


def memory_line(tag: str) -> list:
    """bytes in use / peak / limit on each device, where the backend
    reports them (the CPU backend does not)."""
    import jax

    out = []
    for d in jax.devices():
        st = d.memory_stats() or {}
        out.append({k: st.get(k) for k in
                    ("bytes_in_use", "peak_bytes_in_use", "bytes_limit")})
    print(f"  memory[{tag}]: " + "; ".join(
        f"dev{i} in_use={m['bytes_in_use']} peak={m['peak_bytes_in_use']} "
        f"limit={m['bytes_limit']}" for i, m in enumerate(out)), flush=True)
    return out


def seeded_batches(seed: int, steps: int, batch: int, seq: int,
                   vocab: int):
    """Fresh batch every step (distinct losses), all drawn from one
    seeded 64-token sub-vocabulary — structure a few AdamW steps can
    learn, so the loss visibly falls from ~ln(vocab)."""
    import numpy as np

    rng = np.random.default_rng(seed)
    support = rng.choice(vocab, size=min(64, vocab), replace=False)
    return [{"input_ids": support[rng.integers(0, len(support),
                                               (batch, seq))].astype(np.int32)}
            for _ in range(steps)]


def train_config(sz: dict, seed: int, micro_batch: int, mesh: dict) -> dict:
    """The DeepSpeed-style config the README opens with."""
    return {
        "train_micro_batch_size_per_gpu": micro_batch,
        "bf16": {"enabled": True},
        "optimizer": {"type": "AdamW", "params": {"lr": sz["lr"]}},
        "zero_optimization": {"stage": 3},
        "mesh": mesh,
        "seed": seed,
        "steps_per_print": 1,
    }


def compiled_train_step(engine, batch: dict):
    """The engine's jitted step, compiled ahead of its first call from
    abstract arguments (the state is donated, so not from live ones).
    Each abstract leaf mirrors its array exactly — committed leaves carry
    their sharding, uncommitted ones (the step counters) none — so the
    lowered module is the one the first ``train_batch`` asks for, and
    that call finds this executable instead of compiling a second one (a
    replicated sharding on a step counter was enough to miss: 69 s)."""
    import jax

    def abstract(tree):
        return jax.tree.map(lambda a: jax.ShapeDtypeStruct(
            a.shape, a.dtype, sharding=a.sharding if a.committed else None),
            tree)

    sharded = engine._shard_batch(engine._reshape_for_gas(batch),
                                  with_gas_dim=True)
    return engine._train_step.lower(abstract(engine.state),
                                    abstract(sharded)).compile()


def run_steps(engine, batches) -> tuple[list, float]:
    import jax

    t0 = time.perf_counter()
    losses = [engine.train_batch(b) for b in batches]
    jax.block_until_ready(losses[-1])
    return [float(x) for x in losses], time.perf_counter() - t0


def phase_train(sz: dict, args) -> dict:
    dev = require_device(args)
    clock = CompileClock()
    import deepspeed_tpu as ds
    from deepspeed_tpu.models import build_model
    from deepspeed_tpu.ops.native import lib_status

    loaded, detail = lib_status()
    print(f"  native host library: "
          f"{'loaded' if loaded else 'numpy fallback active'} — {detail}",
          flush=True)

    vocab = build_model(sz["model"], **sz["overrides"]).config.vocab_size
    batches = seeded_batches(args.seed, sz["steps"], sz["micro_batch"],
                             sz["seq"], vocab)
    cfg = train_config(sz, args.seed, sz["micro_batch"],
                       {"fsdp": 1, "data": 1})

    # the reference first: same seed => same initial weights, forward only
    # under XLA attention (its full step does not fit beside Adam state at
    # this batch). One engine at a time on the chip.
    t0 = time.perf_counter()
    ref_engine, *_ = ds.initialize(
        model=build_model(sz["model"], attn_impl="xla", **sz["overrides"]),
        config=dict(cfg))
    check(ref_engine.attention_formulation[0] == "xla",
          f"reference engine runs XLA attention "
          f"({ref_engine.attention_formulation[1]})")
    ref_loss = float(ref_engine.eval_batch(batches[0]))
    ref_engine.close()
    print(f"  xla-attention loss on batch 0: {ref_loss:.5f} "
          f"({time.perf_counter() - t0:.1f}s incl. build)", flush=True)

    t0 = time.perf_counter()
    engine, *_ = ds.initialize(
        model=build_model(sz["model"], **sz["overrides"]), config=dict(cfg))
    build_s = time.perf_counter() - t0
    chosen, why_not = engine.attention_formulation
    check(chosen == "pallas",
          f"attn_impl='auto' chose the flash kernel ({why_not or 'usable'})")
    compiled = compiled_train_step(engine, batches[0])
    n_kernels = compiled.as_text().count("tpu_custom_call")
    if dev["platform"] == "tpu":
        check(n_kernels > 0, f"compiled train step carries the flash "
                             f"kernel ({n_kernels} tpu_custom_call)")
    else:
        print("  (interpret mode: the kernel lowers to plain HLO, no "
              "tpu_custom_call to look for)", flush=True)
    ma = compiled.memory_analysis()
    print(f"  train step program: args {ma.argument_size_in_bytes} B, "
          f"temp {ma.temp_size_in_bytes} B, "
          f"code {ma.generated_code_size_in_bytes} B", flush=True)

    first, first_s = run_steps(engine, batches[:1])
    rest, rest_s = run_steps(engine, batches[1:])
    losses = first + rest
    mem = memory_line("after train steps")
    engine.close()
    print(f"  losses: {losses}", flush=True)
    check(all(math.isfinite(x) for x in losses), "every loss finite")
    check(abs(losses[0] - math.log(vocab)) < 1.0,
          f"first loss {losses[0]:.4f} near ln({vocab}) = "
          f"{math.log(vocab):.4f}")
    check(len(set(losses)) == len(losses), "losses distinct")
    check(losses[-1] < losses[0], "losses falling")
    check(abs(losses[0] - ref_loss) <= 1e-2 * abs(ref_loss),
          f"first loss {losses[0]:.5f} within 1e-2 of the XLA-attention "
          f"run's {ref_loss:.5f}")
    return {"device": dev, "losses": losses, "xla_ref_loss": ref_loss,
            "attention": chosen, "flash_kernels": n_kernels,
            "engine_build_s": round(build_s, 2),
            "first_step_s": round(first_s, 2),
            "run_s_per_step": round(rest_s / max(len(rest), 1), 4),
            "native_loaded": loaded, "memory": mem, **clock.report()}


def per_device_state_bytes(state) -> dict:
    """Bytes of the train state each device actually holds, from the
    arrays' own shards."""
    import jax

    held: dict = {}
    for leaf in jax.tree.leaves(state):
        for sh in leaf.addressable_shards:
            held[sh.device.id] = held.get(sh.device.id, 0) + sh.data.nbytes
    return held


def phase_train_sharded(sz: dict, args) -> dict:
    dev = require_device(args)
    clock = CompileClock()
    import re

    import jax

    import deepspeed_tpu as ds
    from deepspeed_tpu.models import build_model
    from deepspeed_tpu.parallel.topology import MeshTopology

    model = build_model(sz["model"], **sz["sharded_overrides"])
    G = SHARDED_GLOBAL_BATCH
    batches = seeded_batches(args.seed, sz["steps"], G, sz["seq"],
                             model.config.vocab_size)

    def run(tag, micro_batch, mesh, topology=None):
        t0 = time.perf_counter()
        engine, *_ = ds.initialize(
            model=model, topology=topology,
            config=train_config(sz, args.seed, micro_batch, mesh))
        print(f"  [{tag}] mesh {engine.topology.axis_sizes}, attention "
              f"{engine.attention_formulation}", flush=True)
        compiled = compiled_train_step(engine, batches[0])
        losses, _ = run_steps(engine, batches)
        print(f"  [{tag}] losses {losses} "
              f"({time.perf_counter() - t0:.1f}s)", flush=True)
        return engine, compiled, losses

    # one device first, then the mesh: both hold full Adam state, and
    # device 0 has room for one of them at a time
    one, _, ref = run("one-device", G, {"fsdp": 1, "data": 1},
                      MeshTopology({"fsdp": 1, "data": 1},
                                   devices=jax.devices()[:1]))
    one.close()
    engine, compiled, losses = run("fsdp2 x tensor2", G // 2,
                                   {"fsdp": 2, "tensor": 2, "data": 1})
    txt = compiled.as_text()
    colls = {k: len(re.findall(rf"\b{k}(?:-start)?\(", txt)) for k in
             ("all-gather", "all-reduce", "reduce-scatter", "all-to-all",
              "collective-permute")}
    print(f"  collectives in the compiled sharded step: {colls}",
          flush=True)
    mem = memory_line("after sharded steps")
    held = per_device_state_bytes(engine.state)
    # fp32 master + both Adam moments, were they all on one device
    whole = 12 * engine.num_parameters()
    print(f"  train-state bytes held per device: {held}; whole "
          f"master+optimizer state = {whole}", flush=True)
    engine.close()

    check(all(math.isfinite(x) for x in ref + losses), "every loss finite")
    check(sum(colls.values()) > 0, "the sharded step carries collectives")
    check(len(held) == args.chips, f"state spread over {len(held)} devices")
    check(max(held.values()) < 0.5 * whole,
          "no device holds the whole master-plus-optimizer state")
    in_use = [m["bytes_in_use"] for m in mem]
    if all(b is not None for b in in_use):
        check(max(in_use) < whole, "no device's bytes_in_use reaches the "
                                   "whole master-plus-optimizer state")
    worst = max(abs(a - b) / abs(b) for a, b in zip(losses, ref))
    check(worst <= 1e-2, f"sharded losses within 1e-2 of the one-device "
                         f"run at every step (worst {worst:.2e})")
    check(losses[-1] < losses[0], "losses falling")
    return {"device": dev, "losses": losses, "one_device_losses": ref,
            "collectives": colls, "state_bytes_per_device": held,
            "memory": mem, **clock.report()}


def serve_prompts(sz: dict, seed: int, vocab: int) -> list:
    import numpy as np

    rng = np.random.default_rng(seed)
    p = sz["prompts"]

    def toks(n):
        return [int(t) for t in rng.integers(0, vocab, n)]

    shared = toks(p["shared_prefix"])
    return ([toks(n) for n in p["short"]]
            + [shared + toks(n) for n in p["shared_tails"]]
            + [toks(p["long"])])


def snapshot_counter(snap_dir: str, metric: str, **labels) -> float:
    total = 0.0
    for f in sorted(os.listdir(snap_dir)):
        if not f.endswith(".json"):
            continue
        with open(os.path.join(snap_dir, f), encoding="utf-8") as fh:
            fam = json.load(fh).get(metric)
        for s in (fam or {}).get("series", ()):
            if all(s["labels"].get(k) == v for k, v in labels.items()):
                total += s["value"]
    return total


def phase_serve(sz: dict, args) -> dict:
    """The serving tier as a user starts it. This process runs the Router,
    which never touches a device; the engine worker is ITS child and the
    only process on the chip."""
    from deepspeed_tpu.models import get_model_config
    from deepspeed_tpu.serving import FleetConfig, Router, RouterConfig

    want = "cpu" if args.rehearse else "tpu"
    log_dir = os.path.join(OUT_DIR, "serve_logs")
    snap_dir = os.path.join(OUT_DIR, "serve_snapshots")
    for d in (log_dir, snap_dir):
        os.makedirs(d, exist_ok=True)
        for f in os.listdir(d):
            os.remove(os.path.join(d, f))
    vocab = get_model_config(sz["model"], **sz["overrides"]).vocab_size
    # worker placement is explicit: inherited from this environment on
    # the chip; named when rehearsing on the CPU
    fleet = FleetConfig(
        n_replicas=1,
        replica={"backend": "engine", "model": sz["model"],
                 "overrides": sz["overrides"], "seed": args.seed,
                 "engine": dict(sz["engine"])},
        env={"JAX_PLATFORMS": "cpu"} if args.rehearse else {},
        # a worker stops heartbeating while it compiles: liveness limits
        # must outlast a cold program build
        hb_timeout_s=240.0, ready_timeout_s=420.0,
        log_dir=log_dir, snapshot_dir=snap_dir)
    router = Router(RouterConfig(fleet=fleet, request_timeout_s=420.0))
    waves = []
    try:
        t0 = time.perf_counter()
        router.start(min_ready=1)
        ready_s = time.perf_counter() - t0
        h = router.fleet.replicas[0]
        print(f"  worker ready in {ready_s:.1f}s: platform={h.platform} "
              f"device_kind={h.device_kind} max_live={h.max_live}",
              flush=True)
        check(h.platform == want, f"the worker's ready names {want!r}")
        # wave 1 pays every program's compile; wave 2 (fresh tokens, same
        # shapes) is the run time
        for wave in range(2):
            prompts = serve_prompts(sz, args.seed + wave, vocab)
            t0 = time.perf_counter()
            tids = [router.submit(p, max_new_tokens=sz["gen"])
                    for p in prompts]
            results = router.run(deadline_s=600.0)
            dt = time.perf_counter() - t0
            res = [results[t] for t in tids]
            print(f"  wave {wave + 1}: {len(res)} requests in {dt:.2f}s, "
                  f"status {[r['status'] for r in res]}, hit_pages "
                  f"{[r['hit_pages'] for r in res]}", flush=True)
            check(all(r["status"] == "done" for r in res),
                  f"wave {wave + 1}: every request done")
            check(all(len(r["tokens"]) == sz["gen"] for r in res),
                  f"wave {wave + 1}: {sz['gen']} tokens each")
            check(all(0 <= t < vocab for r in res for t in r["tokens"]),
                  f"wave {wave + 1}: tokens inside the vocabulary")
            waves.append({"seconds": round(dt, 2), "streams": [
                {"prompt": p, "tokens": r["tokens"]}
                for p, r in zip(prompts, res)]})
        for _ in range(20):              # let the last heartbeat's
            router.poll(0.05)            # telemetry snapshot land
        check(router.double_commits == 0, "router.double_commits == 0")
        pallas = snapshot_counter(snap_dir, "serving_attn_kernel_total",
                                  path="pallas")
        gather = snapshot_counter(snap_dir, "serving_attn_kernel_total",
                                  path="gather")
        check(pallas > 0 and gather == 0,
              f"worker counted Pallas decode dispatches "
              f"(pallas={pallas:.0f}, gather={gather:.0f})")
        dev = {"platform": h.platform, "kind": h.device_kind}
    finally:
        router.close()                   # the worker exits: chip released
    with open(os.path.join(OUT_DIR, "serve_streams.json"), "w",
              encoding="utf-8") as f:
        json.dump(waves[0]["streams"], f)
    return {"worker": dev, "worker_ready_s": round(ready_s, 2),
            "first_wave_s_incl_compiles": waves[0]["seconds"],
            "second_wave_s": waves[1]["seconds"],
            "pallas_dispatches": pallas}


def teacher_forced(engine, streams: list) -> list:
    """For every ``PARITY_STRIDE``-th step of every served stream: feed the
    prompt plus the stream so far, generate TWO tokens. The first is the
    argmax a prefill-form program gives at that step, the second a
    decode-form program's one step later."""
    prefixes = [s["prompt"] + s["tokens"][:i]
                for s in streams
                for i in range(0, len(s["tokens"]), PARITY_STRIDE)]
    out = []
    n = engine.config.max_seqs
    for i in range(0, len(prefixes), n):
        out += engine.generate(prefixes[i:i + n], max_new_tokens=2)
    return out


def phase_serve_parity(sz: dict, args) -> dict:
    dev = require_device(args)
    clock = CompileClock()
    import jax

    from deepspeed_tpu.inference.engine_v2 import InferenceEngineV2
    from deepspeed_tpu.models import build_model

    with open(os.path.join(OUT_DIR, "serve_streams.json"),
              encoding="utf-8") as f:
        streams = json.load(f)
    model = build_model(sz["model"], **sz["overrides"])

    def build(**extra):
        return InferenceEngineV2(model, rng=jax.random.PRNGKey(args.seed),
                                 config={**sz["engine"], **extra})

    before = memory_line("before engines")
    t0 = time.perf_counter()
    eng = build()
    sel = eng._attn_decode_sel
    print(f"  engine attention: decode={sel.path} ({sel.reason or 'kernel'})"
          f", tree={eng._attn_tree_sel.path}", flush=True)
    check(sel.is_pallas, "the engine's decode selection is the Pallas path")
    m, e = model.config, eng.config
    nominal = (m.num_layers * 2 * m.kv_heads * e.num_blocks * e.block_size
               * m.head_dim * 2)
    pad = 2 if m.head_dim < 128 else 1   # lanes pad to 128 (row-major pool)
    limit = before[0]["bytes_limit"]
    print(f"  KV pool: {nominal} B nominal, budgeted at x{pad} = "
          f"{pad * nominal} B", flush=True)
    if limit:
        check(pad * nominal < 0.5 * limit,
              "the pool at its padded size fits in half the device")
    teacher_forced(eng, streams)         # pays this engine's compiles
    warm_s = time.perf_counter() - t0
    memory_line("after warm-up (pallas engine)")
    t0 = time.perf_counter()
    got = teacher_forced(eng, streams)
    run_s = time.perf_counter() - t0
    check(eng.stats["attn_pallas_decode"] > 0
          and eng.stats["attn_gather_decode"] == 0,
          f"decode dispatches ran the kernel "
          f"({eng.stats['attn_pallas_decode']} pallas, 0 gather)")

    ref_eng = build(use_pallas_decode=False)
    check(not ref_eng._attn_decode_sel.is_pallas,
          "reference engine runs the gather formulation")
    want = teacher_forced(ref_eng, streams)
    memory_line("both engines")

    served = [s["tokens"][i] for s in streams
              for i in range(0, len(s["tokens"]), PARITY_STRIDE)]
    n = len(got)
    first = sum(g[0] == w[0] for g, w in zip(got, want))
    both = [(g, w) for g, w in zip(got, want) if g[0] == w[0]]
    second = sum(g[1] == w[1] for g, w in both)
    worker = sum(g[0] == s for g, s in zip(got, served))
    print(f"  teacher-forced argmax, pallas vs gather: prefill-form "
          f"{first}/{n}, decode-form {second}/{len(both)}; in-process vs "
          f"the worker's stream {worker}/{n}", flush=True)
    check(first >= 0.9 * n, "prefill-form steps agree with gather (>= 90%)")
    check(both and second >= 0.9 * len(both),
          "decode-form steps agree with gather (>= 90%)")
    check(worker >= 0.9 * n,
          "the worker's stream is this engine's argmax (>= 90%)")
    return {"device": dev, "steps": n, "prefill_form_agree": first,
            "decode_form_agree": second, "worker_agree": worker,
            "warm_s_incl_compiles": round(warm_s, 2),
            "run_s": round(run_s, 2), **clock.report()}


CHILD_PHASES = {"train": phase_train, "serve": phase_serve,
                "serve_parity": phase_serve_parity,
                "train_sharded": phase_train_sharded}


def child_main(args) -> int:
    os.environ.setdefault("DS_TPU_LOG_LEVEL", "info")
    try:
        if args.rehearse:
            from deepspeed_tpu._jax_compat import set_cpu_devices

            set_cpu_devices(args.chips)
        from deepspeed_tpu.utils.compile_cache import enable_compile_cache

        print(f"  compile cache: {enable_compile_cache()}", flush=True)
        report = CHILD_PHASES[args.phase](
            REHEARSAL if args.rehearse else REAL, args)
        print(MARK + json.dumps({"phase": args.phase, "ok": True, **report}),
              flush=True)
        return 0
    except Exception as e:  # noqa: BLE001 — the boundary: report, exit 1
        import traceback

        traceback.print_exc()
        print(MARK + json.dumps({"phase": args.phase, "ok": False,
                                 "error": f"{type(e).__name__}: {e}"[:2000]}),
              flush=True)
        return 1


# ---------------------------------------------------------------------------
# the parent: stdlib only, never a JAX backend
# ---------------------------------------------------------------------------

def run_child(phase: str, args, deadline: float) -> dict:
    cmd = [sys.executable, "-u", os.path.abspath(__file__), "--phase", phase,
           "--chips", str(args.chips), "--seed", str(args.seed)]
    if args.rehearse:
        cmd.append("--rehearse")
    print(f"=== phase {phase} ===", flush=True)
    t0 = time.perf_counter()
    report: dict = {"phase": phase, "ok": False}
    # its own session: the worker a phase starts dies with the phase
    proc = subprocess.Popen(cmd, stdout=subprocess.PIPE, text=True,
                            cwd=HERE, start_new_session=True)
    timer = None
    try:
        timer = threading.Timer(max(deadline - time.monotonic(), 1.0),
                                lambda: os.killpg(proc.pid, signal.SIGKILL))
        timer.start()
        for line in proc.stdout:
            if line.startswith(MARK):
                report = json.loads(line[len(MARK):])
            else:
                print(f"[{phase}] {line.rstrip()}", flush=True)
        rc = proc.wait()
    finally:
        if timer is not None:
            timer.cancel()
        try:
            os.killpg(proc.pid, signal.SIGKILL)     # stragglers, if any
        except ProcessLookupError:
            pass
        proc.wait()
    report["wall_s"] = round(time.perf_counter() - t0, 1)
    if rc != 0:
        report["ok"] = False
    if not report["ok"]:
        report.setdefault("error", f"exit code {rc}, no report" + (
            " (killed at the time limit?)" if rc < 0 else ""))
    print(f"=== phase {phase}: {'ok' if report['ok'] else 'FAILED'} in "
          f"{report['wall_s']}s — "
          + json.dumps({k: v for k, v in report.items()
                        if k not in ("phase", "ok", "memory")}), flush=True)
    return report


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--chips", type=int, choices=sorted(PHASES), default=1)
    ap.add_argument("--rehearse", action="store_true",
                    help="tiny sizes on the CPU; the last line says cpu")
    ap.add_argument("--phase", choices=sorted(CHILD_PHASES),
                    help=argparse.SUPPRESS)
    args = ap.parse_args(argv)
    if args.phase:
        return child_main(args)

    deadline = time.monotonic() + BUDGET_S
    os.makedirs(OUT_DIR, exist_ok=True)
    t0 = time.perf_counter()
    reports = []
    for phase in PHASES[args.chips]:
        reports.append(run_child(phase, args, deadline))
        if not reports[-1]["ok"]:
            break                        # later phases build on this one
    ok = len(reports) == len(PHASES[args.chips]) \
        and all(r["ok"] for r in reports)
    # every device phase names the device it ran on; they must agree, and
    # the serve worker must have been on that same platform
    devices = [r["device"] for r in reports if r.get("device")]
    workers = [r["worker"] for r in reports if r.get("worker")]
    device = devices[0] if devices else None
    if ok and (device is None or any(d != device for d in devices) or any(
            w != {"platform": device["platform"], "kind": device["kind"]}
            for w in workers)):
        ok = False
        print(f"device reports disagree: {devices} / workers {workers}",
              flush=True)
    print(f"chip_smoke: {'passed' if ok else 'FAILED'} in "
          f"{time.perf_counter() - t0:.1f}s; phases "
          f"{[(r['phase'], r['ok'], r['wall_s']) for r in reports]}",
          flush=True)
    print(json.dumps({"ok": ok, "device": device}), flush=True)
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
