"""Fault-tolerance suite (runtime/resilience.py + hardened checkpointing):
the crash-recovery matrix driven end-to-end through the deterministic
fault-injection harness — NaN-at-step-k rewind+reconverge, kill between
state commit and 'latest', torn latest / truncated tag / corrupt manifest
fallback, SIGTERM priority save + agent preemption restart — all on the
virtual CPU mesh. Engine cases use a tiny linear-regression loss_fn engine
(compiles in seconds; the tiny-gpt2 matrix case is SLOWTIER)."""
import json
import os
import signal
import subprocess
import sys
import textwrap
import threading
import time
import types

import numpy as np
import pytest

import deepspeed_tpu as ds
from deepspeed_tpu.config import ResilienceConfig
from deepspeed_tpu.runtime.resilience import (
    PREEMPTED_EXIT_CODE,
    DivergenceError,
    DivergenceSentinel,
    FaultInjector,
    HangWatchdog,
    InjectedFault,
    Preempted,
    PreemptionHandler,
    parse_fault_spec,
)

W_DIM = 8
W_TRUE = np.arange(W_DIM, dtype=np.float32)


def _loss_fn(p, batch):
    import jax.numpy as jnp

    pred = batch["x"] @ p["w"]
    return jnp.mean((pred - batch["y"]) ** 2)


def tiny_engine(resilience=None, **over):
    cfg = {
        "train_micro_batch_size_per_gpu": 2,
        "optimizer": {"type": "AdamW", "params": {"lr": 1e-1}},
        "mesh": {"fsdp": 8, "data": 1},
        "steps_per_print": 10_000,
    }
    cfg.update(over)
    if resilience is not None:
        cfg["resilience"] = resilience
    return ds.initialize(loss_fn=_loss_fn,
                         params={"w": np.zeros(W_DIM, np.float32)},
                         config=cfg)[0]


def batch_for(step, B):
    """Deterministic data order keyed on the global step — the rewind
    contract: the driver re-derives its position from engine.global_steps."""
    rng = np.random.default_rng(1000 + step)
    x = rng.standard_normal((B, W_DIM)).astype(np.float32)
    return {"x": x, "y": x @ W_TRUE}


def drive(engine, target, save_dir=None, save_every=2):
    """Train to ``target`` steps, re-deriving data from global_steps (so a
    rewind replays the exact stream); returns {step: loss}."""
    B = engine.config.train_batch_size
    losses = {}
    while engine.global_steps < target:
        loss = float(engine.train_batch(batch_for(engine.global_steps, B)))
        if engine.last_step_rewound:
            continue
        losses[engine.global_steps] = loss
        if save_dir is not None and engine.global_steps % save_every == 0:
            engine.save_checkpoint(save_dir)
    return losses


# --------------------------------------------------------------------------
# pure-host units
# --------------------------------------------------------------------------

def test_fault_spec_parsing():
    assert parse_fault_spec(None) == {}
    assert parse_fault_spec("nan_grads_step=4,crash_before_latest") == {
        "nan_grads_step": 4, "crash_before_latest": True}
    assert parse_fault_spec('{"stall_train_step_s": 0.5}') == {
        "stall_train_step_s": 0.5}
    inj = FaultInjector({"nan_grads_step": 3})
    assert inj.nan_scale(2) == 1.0
    assert np.isnan(inj.nan_scale(3))
    assert inj.nan_scale(3) == 1.0      # single-shot: replay is clean


def test_sentinel_escalation_skip_rewind_abort():
    cfg = ResilienceConfig(loss_spike_factor=2.0, max_consecutive_bad=2,
                           max_rewinds=1)
    s = DivergenceSentinel(cfg)
    assert s.observe(1.0, True) == "ok"
    assert s.observe(float("nan"), True) == "skip"      # streak 1
    assert s.observe(1.0, False) == "rewind"            # streak 2 → escalate
    s.note_rewind()
    assert s.observe(1.0, True) == "ok"
    assert s.observe(10.0, True) == "spike"             # 10 > 2 * EMA
    assert s.observe(10.0, True) == "abort"             # budget (1) spent


def test_watchdog_dumps_all_thread_stacks_on_stall():
    reports = []
    wd = HangWatchdog(0.15, on_stall=reports.append)
    with wd.guard("probe"):
        time.sleep(0.5)
    assert wd.stall_count == 1
    assert "'probe' stalled" in reports[0]
    assert "MainThread" in reports[0] and "time.sleep" in reports[0]
    with wd.guard("fast"):     # completing inside the budget: no dump
        pass
    assert wd.stall_count == 1


def test_watchdog_self_terminates_with_distinct_code(tmp_path):
    script = tmp_path / "wd.py"
    script.write_text(textwrap.dedent("""
        import time
        from deepspeed_tpu.runtime.resilience import HangWatchdog
        wd = HangWatchdog(0.1, exit_on_stall=True)
        with wd.guard("hang"):
            time.sleep(30)
    """))
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env = {**os.environ, "JAX_PLATFORMS": "cpu",
           "PYTHONPATH": os.environ.get("PYTHONPATH", "") + os.pathsep + repo}
    proc = subprocess.run([sys.executable, str(script)], env=env, timeout=120)
    from deepspeed_tpu.runtime.resilience import WATCHDOG_EXIT_CODE

    assert proc.returncode == WATCHDOG_EXIT_CODE


def test_wait_for_checkpoint_timeout_is_structured():
    from deepspeed_tpu.runtime import CheckpointWaitTimeout
    from deepspeed_tpu.runtime.checkpointing import wait_for_checkpoint

    wedged = threading.Thread(target=time.sleep, args=(5,), daemon=True)
    wedged.start()
    eng = types.SimpleNamespace(_latest_thread=wedged)
    t0 = time.monotonic()
    with pytest.raises(CheckpointWaitTimeout) as ei:
        wait_for_checkpoint(eng, timeout_s=0.2)
    assert time.monotonic() - t0 < 3
    assert ei.value.phase == "commit+latest"
    assert ei.value.waited_s == pytest.approx(0.2)


def test_agent_backoff_grows_exponentially_with_jitter(tmp_path):
    from deepspeed_tpu.elasticity import ElasticAgent

    script = tmp_path / "fail.py"
    script.write_text("import sys; sys.exit(9)\n")
    agent = ElasticAgent(
        [sys.executable, str(script)],
        {"elasticity": {"enabled": True, "version": 0.1,
                        "micro_batch_sizes": [1, 2, 4],
                        "max_train_batch_size": 16,
                        "min_gpus": 1, "max_gpus": 8}},
        available_chips_fn=lambda: 8, max_restarts=4, backoff_s=1.0,
        backoff_jitter=0.25, seed=0)
    delays = []
    agent._sleep = delays.append
    assert agent.run() == 9
    assert agent.restart_count == 5          # initial + 4 retries exhausted
    assert len(delays) == 4
    for n, d in enumerate(delays, start=1):  # 2^(n-1) ± 25% jitter
        base = 2.0 ** (n - 1)
        assert 0.75 * base <= d <= 1.25 * base
    assert all(b > a for a, b in zip(delays, delays[1:]))
    assert all(h["cause"] == "failure" for h in agent.history[:-1])


def test_agent_preemption_restart_spares_failure_budget(tmp_path):
    from deepspeed_tpu.elasticity import ElasticAgent

    marker = tmp_path / "came_back"
    script = tmp_path / "preempt.py"
    script.write_text(textwrap.dedent(f"""
        import os, sys
        m = {str(marker)!r}
        if os.path.exists(m):
            sys.exit(0)
        open(m, "w").write("1")
        sys.exit({PREEMPTED_EXIT_CODE})
    """))
    agent = ElasticAgent(
        [sys.executable, str(script)],
        {"elasticity": {"enabled": True, "version": 0.1,
                        "micro_batch_sizes": [1, 2, 4],
                        "max_train_batch_size": 16,
                        "min_gpus": 1, "max_gpus": 8}},
        available_chips_fn=lambda: 8, max_restarts=0,  # ZERO failure budget
        backoff_s=0.01, seed=0)
    delays = []
    agent._sleep = delays.append
    assert agent.run() == 0                  # restarted despite budget 0
    assert agent.restart_count == 0
    assert agent.preemption_count == 1
    assert agent.history[0]["cause"] == "preemption"
    assert len(delays) == 1


def test_dataloader_batch_for_step_matches_iteration():
    from deepspeed_tpu.runtime.data import DataLoader

    data = {"input_ids": np.arange(40 * 3).reshape(40, 3)}
    loader = DataLoader(data, batch_size=8, shuffle=True, seed=7)
    per_epoch = len(loader)
    stream = []
    for epoch in range(2):
        loader.set_epoch(epoch)
        stream.extend(b["input_ids"] for b in loader)
    for step in (0, 3, per_epoch, 2 * per_epoch - 1):
        np.testing.assert_array_equal(
            loader.batch_for_step(step)["input_ids"], stream[step])


def test_monitor_write_counters_csv(tmp_path):
    from deepspeed_tpu.monitor import MonitorMaster

    cfg = types.SimpleNamespace(
        tensorboard=None, wandb=None, comet=None,
        csv_monitor=types.SimpleNamespace(enabled=True,
                                          output_path=str(tmp_path),
                                          job_name="job"))
    mm = MonitorMaster(cfg)
    assert mm.enabled
    mm.write_counters({"rewinds": 2, "save_s": 0.5}, step=7,
                      prefix="Resilience/")
    mm.flush()
    out = (tmp_path / "job" / "Resilience_rewinds.csv").read_text()
    assert "7,2.0" in out


# --------------------------------------------------------------------------
# engine integration (tiny loss_fn engine — cheap compiles)
# --------------------------------------------------------------------------

def test_bf16_nonfinite_step_skipped_in_program():
    """A NaN at step 2 in a bf16 run (no fp16 scaler!) must skip the
    optimizer update in-program and keep training — the seed had no
    non-finite defense outside fp16."""
    eng = tiny_engine(resilience={"fault_injection": {"nan_grads_step": 2},
                                  "max_consecutive_bad": 3})
    losses = drive(eng, 5)
    assert eng.skipped_steps == 1            # opt step didn't advance
    assert eng.resilience_counters["skipped_steps"] == 1
    assert eng.resilience_counters["rewinds"] == 0
    assert np.isnan(losses[3])               # the poisoned step's loss
    assert np.isfinite(losses[4]) and np.isfinite(losses[5])  # recovered
    assert all(np.isfinite(l) for l in np.asarray(eng.state.params["w"],
                                                  np.float32))


def test_nan_rewind_reconverges_to_clean_trajectory(tmp_path):
    """Acceptance case: NaN at step k → rewind to the last verified
    checkpoint, data order replayed from the restored step → the recovered
    run reproduces the uninjected trajectory exactly."""
    clean = drive(tiny_engine(), 8, save_dir=str(tmp_path / "clean"))
    eng = tiny_engine(resilience={"fault_injection": {"nan_grads_step": 4},
                                  "max_consecutive_bad": 1, "max_rewinds": 2})
    injected = drive(eng, 8, save_dir=str(tmp_path / "inj"))
    assert eng.resilience_counters["rewinds"] == 1
    assert injected[8] == pytest.approx(clean[8], rel=1e-6)
    assert injected == pytest.approx(clean, rel=1e-6)


def test_imperative_step_sentinel_observes():
    """The forward/backward/step triplet is guarded too: the apply program
    returns the fused flag and step() feeds the sentinel."""
    def bad_batch(eng):
        B = eng.config.train_batch_size
        return {"x": np.ones((B, W_DIM), np.float32),
                "y": np.full((B,), np.inf, np.float32)}  # inf loss → NaN grads

    eng = tiny_engine(resilience={"max_consecutive_bad": 3})
    eng.backward(bad_batch(eng))
    eng.step()
    assert eng.skipped_steps == 1            # in-program skip, bf16 path
    assert eng.resilience_counters["skipped_steps"] == 1

    eng2 = tiny_engine(resilience={"max_consecutive_bad": 1})
    eng2.backward(bad_batch(eng2))
    with pytest.raises(DivergenceError):     # no checkpoint to rewind to
        eng2.step()


def _exact_batch(step, B):
    """Small integers only, so that every sum the loss and its gradient
    take is exact in bf16, fp16 and float32 in ANY order — the sharded
    program and the test's plain arithmetic then agree bit for bit."""
    rng = np.random.default_rng(77 + step)
    return {"x": rng.integers(-1, 2, (B, W_DIM)).astype(np.float32),
            "y": rng.integers(-2, 3, (B,)).astype(np.float32)}


def _bits(tree):
    import jax

    return [np.asarray(l).tobytes() for l in jax.tree.leaves(tree)]


GUARDS = {
    "bf16-sentinel": {"bf16": {"enabled": True}},
    "fp16-scaler": {"fp16": {"enabled": True, "initial_scale_power": 8},
                    "bf16": {"enabled": False}},
    "fp32-sentinel": {"bf16": {"enabled": False}},
}


@pytest.mark.parametrize("clip", [0.0, 1.0], ids=["noclip", "clip"])
@pytest.mark.parametrize("gas", [1, 2], ids=["gas1", "gas2"])
@pytest.mark.parametrize("guard", list(GUARDS))
def test_guarded_step_updates_exactly_and_skips_bit_for_bit(guard, gas, clip):
    """The fused step's tail (``engine._apply_grads``): the skip is a select
    inside the update, the gradients arrive in the backward's dtype when
    there is nothing to accumulate. On a finite batch the new state equals
    the arithmetic written out here — cast to float32, unscale, accumulate
    from zero, ``/ gas``, clip by the global norm, ``optimizer.update``, cast
    back — EXACTLY; with the fault rail's ``nan_scale`` armed, params,
    master, moments and the optimizer's step are bit for bit the old state,
    ``finite`` is False, ``global_step`` advanced and ``skipped_steps``
    counts it; the next finite step trains on."""
    import jax
    import jax.numpy as jnp

    eng = tiny_engine(resilience={"fault_injection": {"nan_grads_step": 1},
                                  "max_consecutive_bad": 3},
                      gradient_accumulation_steps=gas,
                      gradient_clipping=clip, **GUARDS[guard])
    flags = []
    observe = eng.resilience.observe_step
    eng.resilience.observe_step = lambda loss, finite: (
        flags.append(bool(finite)), observe(loss, finite))[1]
    B = eng.config.train_batch_size
    assert B == 16 * gas
    scale = eng.get_loss_scale()
    assert scale == (256.0 if guard == "fp16-scaler" else 1.0)
    mixed = guard != "fp32-sentinel"
    old = jax.device_get(eng.state)
    assert (old.master is not None) == mixed

    @jax.jit
    def expected(params, master, opt_state, batch):
        acc = jax.tree.map(lambda p: jnp.zeros(p.shape, jnp.float32), params)
        for mb in range(gas):
            micro = {k: v[mb * 16:(mb + 1) * 16] for k, v in batch.items()}
            g = jax.grad(lambda p: _loss_fn(p, micro) * scale)(params)
            acc = jax.tree.map(
                lambda a, g: a + g.astype(jnp.float32) / scale, acc, g)
        grads = jax.tree.map(lambda a: a / gas, acc)
        if clip:
            norm = jnp.sqrt(sum(jnp.sum(jnp.square(g))
                                for g in jax.tree.leaves(grads)))
            factor = jnp.minimum(1.0, clip / (norm + 1e-6))
            grads = jax.tree.map(lambda g: g * factor, grads)
        new_master, new_opt = eng.optimizer.update(
            grads, opt_state, master,
            lr=eng.lr_schedule(opt_state.step))
        return jax.tree.map(lambda m, p: m.astype(p.dtype), new_master,
                            params), new_master, new_opt

    want_p, want_m, want_o = expected(
        old.params, old.master if mixed else old.params, old.opt_state,
        _exact_batch(0, B))

    # step 0, finite: the update, exactly
    assert np.isfinite(float(eng.train_batch(_exact_batch(0, B))))
    got = jax.device_get(eng.state)
    assert got.params["w"].dtype == {"bf16-sentinel": jnp.bfloat16,
                                     "fp16-scaler": jnp.float16,
                                     "fp32-sentinel": jnp.float32}[guard]
    assert np.any(np.asarray(got.params["w"], np.float32) != 0)
    assert _bits(got.params) == _bits(want_p)
    if mixed:
        assert _bits(got.master) == _bits(want_m)
    assert _bits(got.opt_state) == _bits(want_o)
    assert int(got.opt_state.step) == 1 and flags == [True]

    # step 1, poisoned: nothing but global_step (and the scaler) moves
    assert np.isnan(float(eng.train_batch(_exact_batch(1, B))))
    skipped = jax.device_get(eng.state)
    for part in ("params", "master", "opt_state"):
        assert _bits(getattr(skipped, part)) == _bits(getattr(got, part)), part
    assert flags == [True, False]
    assert int(skipped.global_step) == eng.global_steps == 2
    assert eng.skipped_steps == 1
    assert eng.get_loss_scale() == scale         # hysteresis 2: not yet halved
    if guard != "fp16-scaler":  # an fp16 overflow is the scaler's, not a bad step
        assert eng.resilience_counters["skipped_steps"] == 1

    # step 2, finite again: training goes on from the untouched state
    assert np.isfinite(float(eng.train_batch(_exact_batch(2, B))))
    assert flags == [True, False, True]
    assert int(eng.state.opt_state.step) == 2 and eng.skipped_steps == 1
    assert _bits(jax.device_get(eng.state.params)) != _bits(got.params)


def test_divergence_abort_without_checkpoint():
    eng = tiny_engine(resilience={"fault_injection": {"nan_grads_step": 1},
                                  "max_consecutive_bad": 1})
    B = eng.config.train_batch_size
    float(eng.train_batch(batch_for(0, B)))
    with pytest.raises(DivergenceError, match="no checkpoint"):
        eng.train_batch(batch_for(1, B))


def test_torn_latest_and_truncated_tag_fall_back(tmp_path):
    d = str(tmp_path / "ck")
    eng = tiny_engine()
    drive(eng, 4, save_dir=d, save_every=2)   # tags at steps 2 and 4
    # (a) torn latest (empty file) → newest verified tag wins
    latest = os.path.join(d, "latest")
    open(latest, "w").close()
    e2 = tiny_engine()
    e2.load_checkpoint(d)
    assert e2.global_steps == 4
    # (b) latest names a tag whose state file is truncated → previous tag
    with open(latest, "w") as f:
        f.write("global_step4")
    state_dir = os.path.join(d, "global_step4", "state")
    victim = next(os.path.join(dp, fn) for dp, _, fns in os.walk(state_dir)
                  for fn in sorted(fns) if os.path.getsize(
                      os.path.join(dp, fn)) > 1)
    with open(victim, "r+b") as f:
        f.truncate(os.path.getsize(victim) // 2)
    e3 = tiny_engine()
    e3.load_checkpoint(d)
    assert e3.global_steps == 2
    # (c) explicit tag request on the damaged tag fails loudly
    from deepspeed_tpu.runtime.checkpointing import CheckpointIntegrityError

    with pytest.raises(CheckpointIntegrityError, match="truncated"):
        tiny_engine().load_checkpoint(d, tag="global_step4")


def test_corrupt_manifest_entry_falls_back(tmp_path):
    d = str(tmp_path / "ck")
    eng = tiny_engine()
    drive(eng, 4, save_dir=d, save_every=2)
    # flip bytes in a step-4 state file: size unchanged, checksum wrong
    state_dir = os.path.join(d, "global_step4", "state")
    victim = next(os.path.join(dp, fn) for dp, _, fns in os.walk(state_dir)
                  for fn in sorted(fns) if os.path.getsize(
                      os.path.join(dp, fn)) > 8)
    with open(victim, "r+b") as f:
        f.seek(0)
        first = f.read(8)
        f.seek(0)
        f.write(bytes(b ^ 0xFF for b in first))
    e2 = tiny_engine()
    e2.load_checkpoint(d)
    assert e2.global_steps == 2


def test_crash_between_commit_and_latest_resumes_previous(tmp_path):
    """The mid-save kill matrix, via injection: state committed but
    'latest' not advanced → resume lands on the previous verified tag."""
    d = str(tmp_path / "ck")
    eng = tiny_engine()
    drive(eng, 2, save_dir=d, save_every=2)            # step-2 tag committed
    B = eng.config.train_batch_size
    float(eng.train_batch(batch_for(2, B)))
    for point in ("crash_after_commit", "crash_before_latest"):
        eng.resilience.injector.spec[point] = True     # arm mid-save kill
        eng.resilience.injector._consumed.discard(point)
        with pytest.raises(InjectedFault):
            eng.save_checkpoint(d, tag=f"doomed_{point}")
        e2 = tiny_engine()
        e2.load_checkpoint(d)
        assert e2.global_steps == 2                    # previous tag wins
    # the doomed-but-committed tags never became 'latest'
    with open(os.path.join(d, "latest")) as f:
        assert f.read().strip() == "global_step2"


def test_retention_never_gcs_resume_target(tmp_path):
    d = str(tmp_path / "ck")
    eng = tiny_engine(checkpoint={"keep_n": 2})
    drive(eng, 3, save_dir=d, save_every=1)            # tags 1,2,3 → 1 GC'd
    tags = sorted(t for t in os.listdir(d) if t != "latest")
    assert tags == ["global_step2", "global_step3"]
    e2 = tiny_engine(checkpoint={"keep_n": 2})
    e2.load_checkpoint(d, tag="global_step2")          # resume target
    drive(e2, 5, save_dir=d, save_every=1)             # saves 3(over), 4, 5
    tags = sorted(t for t in os.listdir(d) if t != "latest")
    # newest 2 kept AND the resume target survives every GC pass
    assert "global_step2" in tags
    assert "global_step5" in tags and "global_step4" in tags


def test_preemption_sigterm_priority_save_in_process(tmp_path):
    d = str(tmp_path / "ck")
    old = signal.getsignal(signal.SIGTERM)
    try:
        eng = tiny_engine()
        drive(eng, 2, save_dir=d, save_every=2)
        B = eng.config.train_batch_size
        os.kill(os.getpid(), signal.SIGTERM)           # the eviction notice
        with pytest.raises(Preempted) as ei:
            eng.train_batch(batch_for(2, B))
        assert ei.value.code == PREEMPTED_EXIT_CODE
        assert ei.value.checkpoint_path is not None
        # the priority save is synchronous, verified, and at the live step
        from deepspeed_tpu.runtime.checkpointing import tag_status

        status, _ = tag_status(ei.value.checkpoint_path)
        assert status == "verified"
        e2 = tiny_engine()
        e2.load_checkpoint(d)
        assert e2.global_steps == 2                    # saved BEFORE step 3
        assert PreemptionHandler.instance().check() is None  # latch cleared
    finally:
        signal.signal(signal.SIGTERM, old)


def test_preemption_maintenance_hook(tmp_path):
    d = str(tmp_path / "ck")
    eng = tiny_engine(resilience={"preemption_signals": []})
    from deepspeed_tpu.runtime.resilience import PreemptionHandler as PH

    eng.resilience.preemption = PH.instance()
    drive(eng, 2, save_dir=d, save_every=2)
    fired = {"n": 0}

    def maintenance_event():
        fired["n"] += 1
        return fired["n"] >= 2          # second poll reports the event

    eng.resilience.preemption.register_hook(maintenance_event)
    try:
        B = eng.config.train_batch_size
        float(eng.train_batch(batch_for(2, B)))        # poll 1: healthy
        with pytest.raises(Preempted) as ei:
            eng.train_batch(batch_for(3, B))           # poll 2: evicted
        assert "maintenance" in ei.value.cause
    finally:
        eng.resilience.preemption._hooks.clear()
        PH.instance().clear()


# --------------------------------------------------------------------------
# subprocess end-to-end (real signals, real process death)
# --------------------------------------------------------------------------

CHILD_COMMON = """
    import json, os, signal, sys
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    from deepspeed_tpu._jax_compat import set_cpu_devices
    set_cpu_devices(2)
    import numpy as np
    import deepspeed_tpu as ds
    import jax.numpy as jnp

    W = np.arange(4, dtype=np.float32)

    def loss_fn(p, batch):
        return jnp.mean((batch["x"] @ p["w"] - batch["y"]) ** 2)

    work = sys.argv[1]
    engine, *_ = ds.initialize(
        loss_fn=loss_fn, params={"w": np.zeros(4, np.float32)},
        config={
            "train_micro_batch_size_per_gpu": 2,
            "optimizer": {"type": "AdamW", "params": {"lr": 1e-1}},
            "mesh": {"fsdp": 2, "data": 1},
            "steps_per_print": 10_000,
        })
    ckpt = os.path.join(work, "ckpt")
    if os.path.exists(os.path.join(ckpt, "latest")):
        engine.load_checkpoint(ckpt)
    B = engine.config.train_batch_size

    def batch_for(step):
        rng = np.random.default_rng(1000 + step)
        x = rng.standard_normal((B, 4)).astype(np.float32)
        return {"x": x, "y": x @ W}

    def log_step(loss):
        with open(os.path.join(work, "log.jsonl"), "a") as log:
            log.write(json.dumps({
                "step": engine.global_steps, "loss": loss,
                "restart": os.environ.get("DS_TPU_ELASTIC_RESTART", "0"),
            }) + chr(10))
"""

ELASTIC = {"enabled": True, "version": 0.1, "micro_batch_sizes": [1, 2, 4],
           "max_train_batch_size": 4, "min_gpus": 1, "max_gpus": 2}


def _run_agent(tmp_path, child_body, max_restarts=2):
    from deepspeed_tpu.elasticity import ElasticAgent

    script = tmp_path / "train.py"
    script.write_text(textwrap.dedent(CHILD_COMMON) +
                      textwrap.dedent(child_body))
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env = {"PYTHONPATH": os.environ.get("PYTHONPATH", "")
           + os.pathsep + repo}
    agent = ElasticAgent(
        [sys.executable, str(script), str(tmp_path)],
        {"elasticity": ELASTIC}, available_chips_fn=lambda: 2,
        max_restarts=max_restarts, backoff_s=0.05, seed=0, env=env)
    rc = agent.run()
    records = [json.loads(l) for l in
               (tmp_path / "log.jsonl").read_text().splitlines()]
    return agent, rc, records


@pytest.mark.multiprocess
def test_sigterm_worker_saves_then_agent_restarts_from_it(tmp_path):
    """Acceptance case: a real SIGTERM mid-run produces a verified priority
    checkpoint and a PREEMPTED exit; the agent relaunches (budget
    untouched) and the job resumes from the saved step and completes."""
    agent, rc, records = _run_agent(tmp_path, f"""
        TARGET = 6
        while engine.global_steps < TARGET:
            loss = float(engine.train_batch(batch_for(engine.global_steps)))
            log_step(loss)
            if engine.global_steps == 2:
                engine.save_checkpoint(ckpt)
            if engine.global_steps == 3 and \\
                    not os.path.exists(os.path.join(work, "evicted")):
                open(os.path.join(work, "evicted"), "w").write("1")
                os.kill(os.getpid(), signal.SIGTERM)
                # next train_batch performs the priority save and exits
                # {PREEMPTED_EXIT_CODE}; anything past the loop is a bug
        print("DONE")
    """, max_restarts=0)
    assert rc == 0
    assert agent.preemption_count == 1
    assert agent.restart_count == 0          # failure budget untouched
    assert agent.history[0]["cause"] == "preemption"
    steps_by_restart = {}
    for r in records:
        steps_by_restart.setdefault(r["restart"], []).append(r["step"])
    # the priority save beat the sync-cadence save: incarnation 2 resumed
    # from step 3 (the SIGTERM step), not the step-2 scheduled checkpoint
    assert min(steps_by_restart["1"]) == 4
    assert max(steps_by_restart["1"]) == 6
    assert all(np.isfinite(r["loss"]) for r in records)


@pytest.mark.multiprocess
def test_hard_kill_mid_save_resumes_from_previous_tag(tmp_path):
    """A hard os._exit between state commit and 'latest' (no unwind, like a
    node loss) leaves 'latest' on the previous tag; the agent's failure
    restart resumes there and the job completes."""
    agent, rc, records = _run_agent(tmp_path, """
        TARGET = 5
        while engine.global_steps < TARGET:
            loss = float(engine.train_batch(batch_for(engine.global_steps)))
            log_step(loss)
            if engine.global_steps == 3 and \\
                    not os.path.exists(os.path.join(work, "killed")):
                open(os.path.join(work, "killed"), "w").write("1")
                os.environ["DS_TPU_FAULT_HARD"] = "1"
                engine.resilience.injector.hard = True
                engine.resilience.injector.spec["crash_before_latest"] = True
            engine.save_checkpoint(ckpt)
        print("DONE")
    """, max_restarts=2)
    from deepspeed_tpu.runtime.resilience import INJECTED_CRASH_EXIT_CODE

    assert rc == 0
    assert agent.restart_count == 1
    assert agent.history[0]["cause"] == "failure"
    assert agent.history[0]["exit"] == INJECTED_CRASH_EXIT_CODE
    second = [r["step"] for r in records if r["restart"] == "1"]
    # step 3's save died pre-'latest' → resumed from step 2's tag and
    # re-trained step 3
    assert min(second) == 3
    assert max(second) == 5


# --------------------------------------------------------------------------
# SLOWTIER: full-model crash-recovery on a different mesh shape
# --------------------------------------------------------------------------

@pytest.mark.slow
def test_fallback_resume_on_different_mesh_shape(tmp_path):
    """Corrupted newest tag + resume under a different mesh/ZeRO stage:
    verified-fallback composes with reshard-on-load (the universal
    checkpoint property)."""
    from deepspeed_tpu.models import build_model

    def mk(stage, mesh):
        return ds.initialize(model=build_model("tiny-gpt2"), config={
            "train_micro_batch_size_per_gpu": 2,
            "optimizer": {"type": "AdamW", "params": {"lr": 1e-2}},
            "zero_optimization": {"stage": stage},
            "mesh": mesh,
            "steps_per_print": 10_000,
        })[0]

    d = str(tmp_path / "ck")
    eng = mk(2, {"fsdp": 8})
    rng = np.random.default_rng(0)
    b = {"input_ids": rng.integers(
        0, 256, (eng.config.train_batch_size, 32)).astype(np.int32)}
    eng.train_batch(b)
    eng.save_checkpoint(d)                   # global_step1 (verified)
    eng.train_batch(b)
    eng.save_checkpoint(d)                   # global_step2 (to be torn)
    victim_dir = os.path.join(d, "global_step2", "state")
    victim = next(os.path.join(dp, fn) for dp, _, fns in os.walk(victim_dir)
                  for fn in sorted(fns)
                  if os.path.getsize(os.path.join(dp, fn)) > 1)
    with open(victim, "r+b") as f:
        f.truncate(os.path.getsize(victim) // 2)

    eng2 = mk(3, {"fsdp": 2, "data": 4})     # different stage AND mesh
    eng2.load_checkpoint(d)
    assert eng2.global_steps == 1            # fell back past the torn tag
    loss = float(eng2.train_batch(b))
    assert np.isfinite(loss)
