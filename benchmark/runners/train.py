"""Training runner: one cell of a ``mode: train`` configuration, once, in
this one process (it drives all the chips of the host).

``ds.initialize`` builds the seeded model under the configuration's
DeepSpeed config; the plain reference checks the initial loss of batch 0's
first row; the one train step is compiled ahead of its first call; a few
warm steps; then steps for ``--seconds`` (each ending in
``block_until_ready``), or, with ``--trace 1``, a few steps under
``jax.profiler``.
"""
from __future__ import annotations

import json
import math
import os
import shutil
import sys
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))

from benchmark import common  # noqa: E402
from benchmark.common import BenchFailure, say  # noqa: E402
from benchmark.traffic.generate import generate  # noqa: E402


def compiled_train_step(engine, batch: dict):
    """The engine's jitted step compiled ahead of its first call from
    abstract arguments that mirror the live ones exactly (committed leaves
    carry their sharding, uncommitted ones none), so the first
    ``train_batch`` finds this executable. Copied from ``chip_smoke.py``."""
    import jax

    def abstract(tree):
        return jax.tree.map(lambda a: jax.ShapeDtypeStruct(
            a.shape, a.dtype, sharding=a.sharding if a.committed else None),
            tree)

    sharded = engine._shard_batch(engine._reshape_for_gas(batch),
                                  with_gas_dim=True)
    return engine._train_step.lower(abstract(engine.state),
                                    abstract(sharded)).compile()


def reference_loss(engine, row: np.ndarray, m) -> float:
    """The plain reference's loss of one row from the engine's INITIAL
    master weights, a layer at a time: each float32 layer is gathered onto
    device 0 (0.9 GB at these widths), used and dropped."""
    import jax
    from jax.sharding import SingleDeviceSharding

    from benchmark.reference import dense_decoder as ref

    src = engine.state.master if engine.state.master is not None \
        else engine.state.params
    one = SingleDeviceSharding(jax.devices()[0])
    here = lambda t: jax.device_put(t, one)
    logits = ref.forward_logits(
        row, embed=here(src["embed"]),
        layer=lambda i: here(ref.program_layer(src, i)),
        num_layers=m.num_layers, ln_final=here(src["ln_final"]["scale"]),
        unembed=here(src["unembed"]), theta=float(m.rope_theta),
        eps=float(m.norm_eps))
    return float(ref.lm_loss(logits, row))


def main() -> int:
    args = common.runner_args()
    entry, cell, config, traffic = common.load_cell(args.workload)
    conf = common.pick(config, args.rehearse)
    traffic = common.pick(traffic, args.rehearse)
    cellp = common.pick(cell, args.rehearse)
    try:
        dev = common.require_device(entry["chips"], args.rehearse)
    except BenchFailure as e:
        say(f"FAILED: {e}")
        return 1
    clock = common.CompileClock()
    import jax

    import deepspeed_tpu as ds
    from benchmark import reduce_trace, work
    from deepspeed_tpu.models import build_model
    from deepspeed_tpu.utils.compile_cache import enable_compile_cache

    say(f"compile cache: {enable_compile_cache()}")
    model = build_model(conf["preset"], **conf["overrides"])
    m = model.config
    sched = generate(traffic, args.seed, m.vocab_size, args.seconds)
    batches = sched["batches"]
    tokens_per_step = sched["tokens_per_step"]
    say(f"{len(batches)} batches of {batches.shape[1:]} from {sched['docs']} "
        f"documents")
    # the sample batch fixes the shapes the engine plans for (the default
    # would be max_seq_len = 32768 positions a row)
    engine, *_ = ds.initialize(model=model,
                               config={**conf["deepspeed"], "seed": args.seed},
                               sample_batch={"input_ids": batches[0]})
    say(f"engine: mesh {engine.topology.axis_sizes}, attention "
        f"{engine.attention_formulation}, {engine.num_parameters():,} "
        f"parameters")
    held: dict = {}
    for leaf in jax.tree.leaves(engine.state):
        for sh in leaf.addressable_shards:
            held[sh.device.id] = held.get(sh.device.id, 0) + sh.data.nbytes
    say(f"train-state bytes per device: {held}")

    # correctness, part 1: before step 1, the reference on the initial
    # weights against the engine's own evaluation of the same row
    row = batches[0, 0]
    t0 = time.monotonic()
    ref_loss = reference_loss(engine, row, m)
    eval_loss = float(engine.eval_batch(
        {"input_ids": np.repeat(row[None], batches.shape[1], axis=0)}))
    rel = abs(eval_loss - ref_loss) / abs(ref_loss)
    say(f"reference loss {ref_loss:.6f}, engine eval_batch {eval_loss:.6f} "
        f"on batch 0 row 0 (rel {rel:.2e}, {time.monotonic() - t0:.1f}s)")

    batch = lambda i: {"input_ids": batches[i % len(batches)]}
    compiled = compiled_train_step(engine, batch(0))
    ma = compiled.memory_analysis()
    say(f"train step program: args {ma.argument_size_in_bytes} B, temp "
        f"{ma.temp_size_in_bytes} B per device")
    losses = []
    n_warm = int(traffic.get("warm_steps", 2))
    for i in range(n_warm):
        losses.append(float(jax.block_until_ready(
            engine.train_batch(batch(i)))))
    say(f"warm steps: losses {losses}; {json.dumps(clock.report())}")

    w0 = time.monotonic()
    step_t = []
    trace_summary = None
    if args.trace:
        trace_dir = os.path.join(common.OUT_DIR, args.workload, "trace")
        shutil.rmtree(trace_dir, ignore_errors=True)
        reduce_trace.start(trace_dir)
        with jax.profiler.TraceAnnotation(reduce_trace.WINDOW_SPAN):
            for i in range(int(traffic.get("trace_steps", 3))):
                with jax.profiler.StepTraceAnnotation("train", step_num=i):
                    losses.append(float(jax.block_until_ready(
                        engine.train_batch(batch(n_warm + i)))))
                step_t.append(time.monotonic())
        trace_summary = reduce_trace.stop_and_summarize(
        trace_dir, host_only=args.rehearse)
    else:
        i = n_warm
        while time.monotonic() - w0 < args.seconds:
            if i == len(batches):
                say("more steps than batches: the schedule wraps (raise "
                    "max_steps_per_s in the traffic file)")
            losses.append(float(jax.block_until_ready(
                engine.train_batch(batch(i)))))
            step_t.append(time.monotonic())
            i += 1
    w1 = step_t[-1]
    steps = len(step_t)
    in_window = clock.in_window(w0, w1)
    say(f"window: {steps} steps in {w1 - w0:.3f}s; losses "
        f"{[round(x, 4) for x in losses]}")
    say(f"compilations inside the window: {in_window}; "
        f"{json.dumps(clock.report())}")
    mem = common.memory_peak_bytes()

    ln_v = math.log(m.vocab_size)
    problems = []
    if not all(math.isfinite(x) for x in losses):
        problems.append("a loss is not finite")
    if abs(losses[0] - ln_v) >= 1.0:
        problems.append(f"first loss {losses[0]:.4f} not within 1.0 of "
                        f"ln({m.vocab_size}) = {ln_v:.4f}")
    if not losses[-1] < losses[0]:
        problems.append("the last loss is not below the first")
    tol = float(cellp["reference"]["loss_rel_tolerance"])
    if rel > tol:
        problems.append(f"engine loss differs from the reference by "
                        f"{rel:.2e} (> {tol})")
    for p in problems:
        say(f"NOT CORRECT: {p}")
    tok_s_chip = steps * tokens_per_step / (w1 - w0) / entry["chips"]
    device = {**dev, "memory_peak_bytes": int(mem)}
    if not args.trace:
        common.emit(entry, 0, correct=not problems, attempted=steps, failed=0,
                    values={"train_tok_per_s_chip": tok_s_chip,
                            "setup_s": w0 - common.T0}, device=device)
        return 0
    ctx = {"trace": trace_summary, "model": conf, "steps": steps,
           "seq": int(traffic["seq"]), "tokens_per_step": tokens_per_step,
           "chips": entry["chips"], "window_s": w1 - w0,
           "peaks": work.peaks(dev["kind"]) if not args.rehearse else None,
           "memory_peak_bytes": mem}
    say("collectives by kind (s per device in the traced window): "
        + json.dumps(trace_summary["collectives"]["by_kind"]))
    device.update(busy_s=trace_summary["busy_s"],
                  window_s=trace_summary["window_s"])
    common.emit(entry, 1, correct=not problems, attempted=steps, failed=0,
                values=common.read_layers(entry, ctx), device=device,
                breakdown=reduce_trace.breakdown(trace_summary))
    return 0


if __name__ == "__main__":
    sys.exit(main())
