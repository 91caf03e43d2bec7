"""Fault-tolerant training: divergence rewind, preemption-safe saves,
hang watchdog, and a deterministic fault-injection harness.

No single reference-file analogue — the reference's fp16 loss scaler
(runtime/fp16/loss_scaler.py) skips overflowed steps, but bf16 runs have no
non-finite defense, torn ``latest`` tags crash the resume, and preemption
handling lives outside the repo entirely. This module is the CheckFreq
(Mohan et al., FAST'21) / Bamboo (Thorpe et al., NSDI'23) layer built
natively on the orbax checkpoint path and the elasticity agent:

- :class:`DivergenceSentinel` — every train step returns a fused
  non-finite/loss-spike flag (bf16 included; the device already skipped the
  bad update); the host policy escalates skip-step → rewind to the last
  verified checkpoint → abort after the rewind budget.
- :class:`PreemptionHandler` — SIGTERM/SIGINT (plus pluggable maintenance
  -event hooks) request a priority synchronous save that supersedes any
  in-flight async save, then exit with :data:`PREEMPTED_EXIT_CODE` so the
  elastic agent restarts with backoff instead of burning its failure budget.
- :class:`HangWatchdog` — a stall timer around blocking device work (train
  step, restore, checkpoint wait) that dumps all-thread stacks + device
  diagnostics, and optionally self-terminates with
  :data:`WATCHDOG_EXIT_CODE` so a supervisor can relaunch.
- :class:`FaultInjector` — config/env-driven deterministic injection points
  (``nan_grads_step``, ``crash_before_latest``, ``truncate_tag``, …) so
  every recovery path is exercised on CPU in tests.

The manager is glue; checkpoint integrity (manifest checksums, verified-tag
fallback, retention) lives in runtime/checkpointing.py.
"""
from __future__ import annotations

import math
import os
import signal
import sys
import threading
import time
import traceback
from contextlib import contextmanager, nullcontext
from typing import Any, Callable

from ..utils.logging import logger

#: worker exit code meaning "I was preempted and saved a checkpoint" — the
#: elastic agent restarts these with backoff, without spending its
#: failure-restart budget
PREEMPTED_EXIT_CODE = 83

#: worker exit code of a watchdog self-termination after a stall dump
WATCHDOG_EXIT_CODE = 85

#: hard-crash exit code of fault-injected kills (DS_TPU_FAULT_HARD=1)
INJECTED_CRASH_EXIT_CODE = 77


class DivergenceError(RuntimeError):
    """Training diverged past the rewind budget (or had no checkpoint to
    rewind to); the job should stop rather than keep poisoning state."""


class InjectedFault(RuntimeError):
    """A fault-injection point fired in soft mode (test-visible crash)."""

    def __init__(self, point: str, where: str):
        super().__init__(f"injected fault '{point}' at {where}")
        self.point = point
        self.where = where


class CheckpointWaitTimeout(TimeoutError):
    """``wait_for_checkpoint`` exceeded its bound — the async save thread
    is wedged, which must surface as a structured error, not a hang."""

    def __init__(self, phase: str, waited_s: float):
        super().__init__(
            f"checkpoint wait timed out after {waited_s:.1f}s in phase "
            f"'{phase}' (async save thread wedged?)")
        self.phase = phase
        self.waited_s = waited_s


class Preempted(SystemExit):
    """Raised at a step boundary after the priority save; carries
    :data:`PREEMPTED_EXIT_CODE` so an uncaught instance exits the worker
    with the code the elastic agent recognizes."""

    def __init__(self, cause: str, checkpoint_path: str | None):
        super().__init__(PREEMPTED_EXIT_CODE)
        self.cause = cause
        self.checkpoint_path = checkpoint_path


# --------------------------------------------------------------------------
# Fault injection
# --------------------------------------------------------------------------

def _parse_value(v: str):
    for cast in (int, float):
        try:
            return cast(v)
        except ValueError:
            pass
    return {"true": True, "false": False}.get(v.lower(), v)


def parse_fault_spec(raw: str | None) -> dict[str, Any]:
    """``DS_TPU_FAULT_INJECT`` format: JSON object, or
    ``point=value,point2`` (bare point → True)."""
    if not raw:
        return {}
    raw = raw.strip()
    if raw.startswith("{"):
        import json

        return dict(json.loads(raw))
    out: dict[str, Any] = {}
    for part in raw.split(","):
        part = part.strip()
        if not part:
            continue
        if "=" in part:
            k, v = part.split("=", 1)
            out[k.strip()] = _parse_value(v.strip())
        else:
            out[part] = True
    return out


class FaultInjector:
    """Deterministic, single-shot fault injection.

    Points are armed from the config section merged with the
    ``DS_TPU_FAULT_INJECT`` env var (env wins), and each fires exactly once
    — a rewind replaying the same step must not re-trip the fault.

    Known points (value semantics in parentheses):
      ``nan_grads_step`` (int k)      NaN scales the loss at global step k
      ``crash_after_commit`` (bool)   die after state commit, before manifest
      ``crash_before_latest`` (bool)  die after manifest, before 'latest'
      ``crash_after_latest`` (bool)   die right after the 'latest' write
      ``truncate_tag`` (bool)         truncate a state file after the save
      ``stall_train_step_s`` (float)  sleep inside the train-step guard

    Fleet-level points (serving/replica.py — the chaos matrix; all
    count-based via :meth:`countdown`, so every failover path is exercised
    at a SEEDED request/chunk index, not by chance):
      ``replica_slow_start_s`` (float)       sleep before the ready handshake
      ``replica_crash_on_start`` (bool)      die at startup, every incarnation
                                             (the crash-loop → breaker drill)
      ``replica_crash_on_put`` (int k)       die handling the k-th admit
      ``replica_crash_during_prefill`` (int) die on the k-th prefill step
      ``replica_hang_after_chunks`` (int k)  stop the event loop (heartbeats
                                             included) before sending the
                                             k-th stream chunk...
      ``replica_hang_s`` (float)             ...for this long (default 3600;
                                             finite values un-hang so the
                                             stale-delivery dedup path runs)
      ``replica_drop_done`` (int k)          swallow the k-th completion reply
                                             (lost-reply → request deadline)
      ``replica_stall_stream_after_chunks``  (int k) stop sending stream
                                             messages after the k-th chunk
                                             while heartbeats CONTINUE (the
                                             wedged-engine shape; un-stalled
                                             late delivery drills dedup)...
      ``replica_stall_stream_s`` (float)     ...for at least this long
                                             (default 1.0) AND until the
                                             router flushes a stalled
                                             request: the late delivery is
                                             stale whatever the host's pace

    Weight-swap points (serving/deploy.py rolling deploys; armed per-slot
    via ``FleetConfig.per_slot`` like the rest of the chaos matrix):
      ``swap_crash_mid_quiesce`` (int k)     die handling the k-th swap
                                             message, after quiesce and
                                             before the load — the restart
                                             comes up on the OLD version
                                             and the deploy aborts
      ``swap_corrupt_manifest`` (int k)      the k-th swap's checkpoint
                                             fails manifest verification
                                             (structured "integrity"
                                             refusal; old weights serve)
      ``swap_canary_degrade`` (float s)      after the next successful
                                             swap, every decoded token
                                             pays an extra s seconds —
                                             the canary LOOKS healthy at
                                             the handshake, so the deploy
                                             health gate must catch it

    KV-tier points (inference/kvtier.py — armed per-slot via the
    replica config's ``faults`` like the rest; the tier consumes them
    through its own ``inj`` reference):
      ``tier_torn_spill`` (int k)            the k-th demoted page's
                                             spill record is written
                                             TORN (half the bytes, never
                                             indexed) — the on-disk
                                             shape of a crash mid-write;
                                             the next tier open's crc +
                                             length gate must count and
                                             skip it, and the chain's
                                             promote degrades to
                                             recompute
      ``tier_crash_mid_demote`` (int k)      die HARD between the k-th
                                             demoted page's spill write
                                             and its index update — the
                                             restarted replica reopens
                                             the tier over a torn
                                             segment and every affected
                                             request recomputes,
                                             bit-identical

    Elastic points (serving/elastic.py + the replica preemption path;
    armed per-slot via the replica config's ``faults``):
      ``replica_crash_mid_drain_flush`` (int k)  die HARD between the
                                             k-th drained chain's tier
                                             spill and the retire exit —
                                             the torn record is skipped
                                             on the next open and the
                                             router replays the in-flight
                                             requests elsewhere
      ``preempt_ignore_deadline`` (bool)     a preempted replica keeps
                                             decoding past its emergency
                                             deadline (the misbehaving-
                                             worker shape: the router's
                                             liveness timeout reaps it)

    Router-side points (serving/router.py, armed via
    ``RouterConfig.faults`` and always HARD — the journal chaos matrix
    SIGKILLs the CONTROL PLANE at each journaled phase, all count-based
    via :meth:`countdown`):
      ``router_crash_after_admit`` (int k)   die after journaling the
                                             k-th admit (admitted-unplaced
                                             recovery)
      ``router_crash_after_place`` (int k)   die after the k-th placement
                                             went out (mid-stream
                                             recovery: daemons keep
                                             decoding, resync re-attaches)
      ``router_crash_before_relay_ack``      (int k) die between the
                                             importer's mig_ack and the
                                             ack relay to the pinned
                                             handoff source
      ``router_crash_mid_kv_pull`` (int k)   die right after starting a
                                             placement-time radix pull
                                             (the puller's local deadline
                                             recomputes)
      ``router_crash_mid_deploy_canary``     (int k) die while a rolling
                                             deploy sits in its canary
                                             phase (recovery rolls the
                                             fleet back deterministically)
      ``router_crash_mid_elastic`` (int k)   die right after journaling
                                             the k-th elastic transition
                                             (restart must neither
                                             resurrect a retiring
                                             replica nor forget a
                                             half-spawned one)

    Crashes raise :class:`InjectedFault` (catchable in-process), or hard-kill
    the process with ``os._exit(INJECTED_CRASH_EXIT_CODE)`` when
    ``DS_TPU_FAULT_HARD=1`` (or ``hard=True``) — the subprocess tests use
    the hard mode to simulate a real mid-save kill with no unwind handlers
    running; replica workers pin it so an injected crash is a real
    no-unwind process death.
    """

    def __init__(self, spec: dict | None = None, env: str | None = None,
                 hard: bool | None = None):
        self.spec: dict[str, Any] = dict(spec or {})
        self.spec.update(parse_fault_spec(
            env if env is not None else os.environ.get("DS_TPU_FAULT_INJECT")))
        self._consumed: set[str] = set()
        self._counts: dict[str, int] = {}
        self.hard = os.environ.get("DS_TPU_FAULT_HARD") == "1" \
            if hard is None else bool(hard)
        if self.spec:
            logger.warning(f"fault injection ARMED: {sorted(self.spec)} "
                           f"(hard={self.hard}) — this is a drill")

    def has(self, point: str) -> bool:
        return point in self.spec and point not in self._consumed

    def value(self, point: str):
        return self.spec.get(point)

    def fire(self, point: str):
        """Consume and return the point's value, or None if not armed."""
        if not self.has(point):
            return None
        self._consumed.add(point)
        return self.spec[point]

    def countdown(self, point: str) -> bool:
        """Count-based firing for per-occurrence points: an int value k
        fires on the k-th call (bare True = the first), then the point is
        consumed. Deterministic chaos drills key off these — "the 3rd
        admit", "the 2nd stream chunk" — so a failover path is pinned to
        a seeded index instead of left to timing."""
        if point not in self.spec or point in self._consumed:
            return False
        self._counts[point] = self._counts.get(point, 0) + 1
        v = self.spec[point]
        k = 1 if v is True else int(v)
        if self._counts[point] < k:
            return False
        self._consumed.add(point)
        return True

    def crash_now(self, point: str, where: str) -> None:
        """Unconditional crash (callers gate via :meth:`countdown`)."""
        logger.error(f"fault injection: crashing at '{point}' ({where})")
        if self.hard:
            # no unwind, no atexit, no orbax cleanup — a real SIGKILL shape
            os._exit(INJECTED_CRASH_EXIT_CODE)
        raise InjectedFault(point, where)

    def maybe_crash(self, point: str, where: str) -> None:
        if self.fire(point) is None:
            return
        self.crash_now(point, where)

    def nan_scale(self, step: int) -> float:
        """1.0, or NaN exactly once when ``step`` hits ``nan_grads_step``."""
        k = self.spec.get("nan_grads_step")
        if k is not None and "nan_grads_step" not in self._consumed \
                and int(k) == int(step):
            self._consumed.add("nan_grads_step")
            logger.warning(f"fault injection: NaN into grads at step {step}")
            return float("nan")
        return 1.0

    def maybe_stall(self, point: str) -> None:
        v = self.fire(point)
        if v:
            time.sleep(float(v))


# --------------------------------------------------------------------------
# Preemption
# --------------------------------------------------------------------------

class PreemptionHandler:
    """Process-wide preemption latch: signal handlers + pluggable
    maintenance-event hooks set a flag that the engine consumes at the next
    step boundary. One instance per process (signal handlers are global);
    multiple engines share it.

    A TPU maintenance-event poller registers via :meth:`register_hook` —
    any hook returning truthy marks the process preempted with that cause.
    """

    _instance: "PreemptionHandler | None" = None

    def __init__(self):
        self._requested: str | None = None
        self._hooks: list[Callable[[], Any]] = []
        self._installed: set[str] = set()

    @classmethod
    def instance(cls) -> "PreemptionHandler":
        if cls._instance is None:
            cls._instance = PreemptionHandler()
        return cls._instance

    @classmethod
    def install(cls, signals: list[str]) -> "PreemptionHandler":
        self = cls.instance()
        for name in signals:
            if name in self._installed:
                continue
            signum = getattr(signal, name, None)
            if signum is None:
                logger.warning(f"preemption: unknown signal '{name}'")
                continue
            try:
                signal.signal(signum,
                              lambda sn, frame, _n=name: self.request(_n))
                self._installed.add(name)
            except ValueError:
                # signal handlers only install from the main thread — an
                # engine built in a worker thread still gets hook-driven
                # preemption, just not signal-driven
                logger.warning(f"preemption: cannot install {name} handler "
                               f"outside the main thread")
        return self

    def register_hook(self, fn: Callable[[], Any]) -> None:
        """``fn()`` truthy → preemption (e.g. a TPU maintenance-event
        poller); polled at every step boundary."""
        self._hooks.append(fn)

    def request(self, cause: str) -> None:
        # runs inside signal handlers — no locks (a non-reentrant acquire
        # here could deadlock against a main-thread holder); a plain str
        # store is atomic under the GIL and first-cause-wins is best-effort
        if self._requested is None:
            self._requested = cause
        logger.warning(f"preemption requested (cause: {cause}); priority "
                       f"save at the next step boundary")

    def check(self) -> str | None:
        if self._requested is None:
            for fn in self._hooks:
                try:
                    hit = fn()
                except Exception as e:
                    logger.warning(f"preemption hook {fn} raised {e!r}; "
                                   f"ignoring this poll")
                    continue
                if hit:
                    self.request(f"maintenance:{hit}" if hit is not True
                                 else "maintenance")
                    break
        return self._requested

    def clear(self) -> None:
        self._requested = None


class GceMaintenancePoller:
    """GCE ``maintenance-event`` metadata poller — the pluggable hook the
    :class:`PreemptionHandler` was built for. On GCE/TPU-VM hosts the
    metadata server announces host maintenance (live migration or
    termination) on
    ``/computeMetadata/v1/instance/maintenance-event`` minutes before
    the SIGTERM lands; polling it turns preemption from a signal race
    into a planned drain (training: priority checkpoint; serving: the
    elastic drain-flush-exit path in serving/replica.py).

    The poller is a callable returning falsy (no event / error / rate
    limit) or the event string (truthy → ``request("maintenance:<ev>")``
    via the handler's hook protocol). ``base_url`` is the test seam: a
    fake metadata HTTP server stands in for
    ``http://metadata.google.internal`` (real-TPU validation stays on
    the ROADMAP's blocked list). Every fetch carries ``timeout_s`` —
    a wedged metadata server must never wedge a step boundary — and
    ``interval_s`` rate-limits the HTTP round-trips (between polls the
    hook returns the cached verdict's falsy side, never a stale event).
    """

    METADATA_PATH = "/computeMetadata/v1/instance/maintenance-event"
    #: metadata values that mean "nothing scheduled"
    QUIET = ("", "NONE")

    def __init__(self, base_url: str = "http://metadata.google.internal",
                 interval_s: float = 1.0, timeout_s: float = 0.5):
        self.base_url = str(base_url).rstrip("/")
        self.interval_s = float(interval_s)
        self.timeout_s = float(timeout_s)
        self.polls = 0
        self.errors = 0
        self._next_t = 0.0

    def _fetch(self) -> str | None:
        """One metadata GET; None on any transport failure (counted)."""
        import urllib.error
        import urllib.request

        req = urllib.request.Request(
            self.base_url + self.METADATA_PATH,
            headers={"Metadata-Flavor": "Google"})
        try:
            with urllib.request.urlopen(
                    req, timeout=self.timeout_s) as resp:
                return resp.read(1024).decode("utf-8", "replace").strip()
        except (urllib.error.URLError, OSError, ValueError):
            self.errors += 1
            return None

    def __call__(self) -> str | None:
        now = time.monotonic()
        if now < self._next_t:
            return None
        self._next_t = now + self.interval_s
        self.polls += 1
        ev = self._fetch()
        if ev is None or ev.upper() in self.QUIET:
            return None
        return ev

    @classmethod
    def install_from(cls, cfg: dict | None,
                     handler: "PreemptionHandler | None" = None
                     ) -> "GceMaintenancePoller | None":
        """Wire a poller into the handler from a config dict (the shared
        seam: the training latch's resilience config and the serving
        replica's ``preempt`` block both pass their dict here). Returns
        the poller, or None when ``metadata_url`` is absent/falsy."""
        url = (cfg or {}).get("metadata_url")
        if not url:
            return None
        poller = cls(
            base_url=str(url),
            interval_s=float((cfg or {}).get("poll_interval_s", 1.0)),
            timeout_s=float((cfg or {}).get("poll_timeout_s", 0.5)))
        (handler or PreemptionHandler.instance()).register_hook(poller)
        return poller


# --------------------------------------------------------------------------
# Hang watchdog
# --------------------------------------------------------------------------

def _all_thread_stacks() -> str:
    frames = sys._current_frames()
    names = {t.ident: t.name for t in threading.enumerate()}
    out = []
    for ident, frame in frames.items():
        out.append(f"--- thread {names.get(ident, '?')} ({ident}) ---")
        out.append("".join(traceback.format_stack(frame)))
    return "\n".join(out)


def _device_diagnostics() -> str:
    """Best-effort device state for the stall report. Probes at CALL time
    only (import-time probes are lint-banned) and never raises — the
    watchdog must produce its report even when the backend is the thing
    that hung."""
    try:
        import jax

        devs = jax.devices()
        lines = [f"devices: {len(devs)} x "
                 f"{getattr(devs[0], 'device_kind', '?')} "
                 f"({getattr(devs[0], 'platform', '?')})"]
        try:
            n_live = sum(1 for _ in jax.live_arrays())
            lines.append(f"live arrays: {n_live}")
        except Exception as e:
            lines.append(f"live arrays: unavailable ({type(e).__name__})")
        return "\n".join(lines)
    except Exception as e:
        return f"device diagnostics unavailable: {type(e).__name__}: {e}"


class HangWatchdog:
    """Heartbeat around blocking device work. ``guard(what)`` arms a timer;
    if the block doesn't finish within ``timeout_s`` the watchdog dumps
    all-thread stacks + device diagnostics (log + optional file) and — when
    ``exit_on_stall`` — hard-exits with :data:`WATCHDOG_EXIT_CODE` so the
    supervisor relaunches instead of the job hanging on a dead ICI link.
    """

    def __init__(self, timeout_s: float = 0.0, *, exit_on_stall: bool = False,
                 on_stall: Callable[[str], None] | None = None,
                 dump_path: str | None = None):
        self.timeout_s = float(timeout_s or 0.0)
        self.exit_on_stall = exit_on_stall
        self.on_stall = on_stall
        self.dump_path = dump_path or os.environ.get("DS_TPU_WATCHDOG_DUMP")
        self.stall_count = 0

    @contextmanager
    def guard(self, what: str, timeout_s: float | None = None):
        timeout = self.timeout_s if timeout_s is None else float(timeout_s)
        if timeout <= 0:
            yield
            return
        timer = threading.Timer(timeout, self._stall, args=(what, timeout))
        timer.daemon = True
        timer.start()
        try:
            yield
        finally:
            timer.cancel()

    def _stall(self, what: str, timeout: float) -> None:
        self.stall_count += 1
        report = (f"WATCHDOG: '{what}' stalled for {timeout:.1f}s\n"
                  f"{_device_diagnostics()}\n{_all_thread_stacks()}")
        logger.error(report)
        if self.dump_path:
            try:
                with open(self.dump_path, "a") as f:
                    f.write(report + "\n")
            except OSError as e:
                logger.error(f"watchdog dump write failed: {e}")
        if self.on_stall is not None:
            try:
                self.on_stall(report)
            except Exception as e:
                logger.error(f"watchdog on_stall callback raised {e!r}")
        if self.exit_on_stall:
            logger.error(f"watchdog: self-terminating with exit code "
                         f"{WATCHDOG_EXIT_CODE} for supervisor relaunch")
            os._exit(WATCHDOG_EXIT_CODE)


# --------------------------------------------------------------------------
# Divergence sentinel
# --------------------------------------------------------------------------

class DivergenceSentinel:
    """Classify each observed step as ok/bad and decide the escalation.

    Bad = non-finite flag from the device (the update was already skipped
    in-program), or a finite loss above ``loss_spike_factor * EMA``.
    ``max_consecutive_bad`` bad steps escalate to ``"rewind"``;
    ``max_rewinds`` rewinds escalate to ``"abort"``. Pure host logic — no
    jax imports — so tests drive it with synthetic sequences.
    """

    def __init__(self, cfg):
        self.cfg = cfg
        self.ema: float | None = None
        self.bad_streak = 0
        self.rewinds = 0

    def classify(self, loss: float, finite: bool) -> str:
        """'ok' | 'skip' (non-finite, device skipped) | 'spike'."""
        if not finite or not math.isfinite(loss):
            return "skip"
        if (self.cfg.loss_spike_factor > 0 and self.ema is not None
                and loss > self.cfg.loss_spike_factor * max(self.ema, 1e-12)):
            return "spike"
        return "ok"

    def observe(self, loss: float, finite: bool,
                defer_nonfinite: bool = False) -> str:
        """Returns the action: 'ok' | 'skip' | 'spike' | 'rewind' | 'abort'.

        ``defer_nonfinite``: the fp16 dynamic scaler OWNS overflow recovery
        (skip + scale shrink is its normal warmup behavior, reference
        loss_scaler.py) — under it, non-finite steps are reported but never
        escalate; spikes (finite blow-ups the scaler can't see) still do.
        """
        kind = self.classify(loss, finite)
        if kind == "ok":
            beta = self.cfg.loss_ema_beta
            self.ema = loss if self.ema is None else \
                beta * self.ema + (1.0 - beta) * loss
            self.bad_streak = 0
            return "ok"
        if kind == "skip" and defer_nonfinite:
            return "skip"
        self.bad_streak += 1
        if self.bad_streak < self.cfg.max_consecutive_bad:
            return kind
        if self.rewinds >= self.cfg.max_rewinds:
            return "abort"
        return "rewind"

    def note_rewind(self) -> None:
        self.rewinds += 1
        self.bad_streak = 0
        self.ema = None


# --------------------------------------------------------------------------
# Manager (engine glue)
# --------------------------------------------------------------------------

class ResilienceManager:
    """Owns the per-engine resilience state and wires sentinel, preemption,
    watchdog and injector into the train loop. Built by the engine at init;
    checkpoint commit/load events flow in through ``record_*`` calls from
    runtime/checkpointing.py."""

    def __init__(self, engine, cfg):
        self.engine = engine
        self.cfg = cfg
        self.injector = FaultInjector(cfg.fault_injection)
        self.sentinel = DivergenceSentinel(cfg) \
            if (cfg.sentinel or cfg.loss_spike_factor > 0) else None
        self.watchdog = HangWatchdog(cfg.watchdog_timeout_s,
                                     exit_on_stall=cfg.watchdog_exit,
                                     on_stall=self._flight_dump_on_stall)
        self.preemption: PreemptionHandler | None = None
        if cfg.preemption_signals:
            self.preemption = PreemptionHandler.install(cfg.preemption_signals)
        #: (save_dir, tag) of the newest fully committed+verified save
        self.last_verified: tuple[str, str] | None = None
        self.last_save_dir: str | None = cfg.rewind_dir
        self.last_step_rewound = False
        self._since_check = 0
        self.counters: dict[str, float] = {
            "bad_steps": 0, "skipped_steps": 0, "rewinds": 0,
            "preemptions": 0, "aborts": 0,
        }

    # -- telemetry (telemetry/) ------------------------------------------
    @staticmethod
    def _telemetry():
        from ..telemetry import get_telemetry

        return get_telemetry()

    def _flight_dump_on_stall(self, report: str) -> None:
        """Watchdog stall callback: the stack dump says WHERE the job is
        stuck; the flight record adds WHAT it was doing — the most recent
        spans, discrete events, and a metrics snapshot."""
        self._telemetry().flight_dump(
            "hang", detail=report.splitlines()[0] if report else None)

    # -- checkpoint bookkeeping (called from checkpointing.py) -----------
    def record_save_dir(self, save_dir: str) -> None:
        self.last_save_dir = save_dir

    def record_committed(self, save_dir: str, tag: str,
                         durations: dict | None = None) -> None:
        self.last_verified = (save_dir, tag)
        self._telemetry().note("checkpoint_commit", tag=tag,
                               **{k: round(v, 3)
                                  for k, v in (durations or {}).items()})
        if durations:
            self.engine._emit_counters(durations, "Checkpoint/")

    # -- watchdog --------------------------------------------------------
    def guard(self, what: str):
        if self.watchdog.timeout_s <= 0:
            return nullcontext()
        return self.watchdog.guard(what)

    # -- fault injection into the step -----------------------------------
    def arm_batch(self, batch: dict, global_batch: int) -> dict:
        """When NaN injection is configured, ride a ``_fault_scale`` leaf
        into the batch (shape [B] so GAS reshape/sharding treat it like any
        column); the loss multiplies by its mean — 1.0 except at the armed
        step. Host-side single-shot: a rewind replaying step k is clean."""
        if "nan_grads_step" not in self.injector.spec:
            return batch
        import numpy as np

        scale = self.injector.nan_scale(self.engine.global_steps)
        batch = dict(batch)
        batch["_fault_scale"] = np.full((global_batch,), scale, np.float32)
        return batch

    # -- preemption ------------------------------------------------------
    def check_preemption(self) -> None:
        """Called at every step boundary; on a pending request performs the
        priority save and raises :class:`Preempted` (a SystemExit carrying
        PREEMPTED_EXIT_CODE)."""
        if self.preemption is None:
            return
        cause = self.preemption.check()
        if cause is None:
            return
        self.counters["preemptions"] += 1
        self._telemetry().note("preemption", cause=cause,
                               step=self.engine.global_steps)
        path = None
        try:
            path = self.priority_save()
        finally:
            # clear before raising: an in-process test catching the exit
            # must not leave the process-wide latch poisoned
            self.preemption.clear()
        self._emit_sentinel_events()
        logger.warning(
            f"preemption ({cause}): exiting {PREEMPTED_EXIT_CODE} "
            f"{'with verified checkpoint ' + path if path else 'WITHOUT a save'}")
        raise Preempted(cause, path)

    def priority_save(self) -> str | None:
        """Synchronous save that supersedes any in-flight async save: wait
        for the in-flight commit (bounded), then write a fresh synchronous
        checkpoint so the very latest step survives the preemption."""
        if not self.cfg.preemption_save:
            return None
        save_dir = self.last_save_dir
        if save_dir is None:
            logger.error("preemption: no checkpoint directory known (no "
                         "prior save_checkpoint and no resilience.rewind_dir)"
                         " — exiting without a save")
            return None
        from . import checkpointing as ckpt

        try:
            ckpt.wait_for_checkpoint(self.engine)
        except Exception as e:
            logger.warning(f"preemption: in-flight async save wait failed "
                           f"({e!r}); superseding with the sync save")
        prev_async = self.engine.config.checkpoint.async_save
        self.engine.config.checkpoint.async_save = False
        try:
            with self.guard("preemption_save"):
                return ckpt.save_checkpoint(self.engine, save_dir)
        finally:
            self.engine.config.checkpoint.async_save = prev_async

    # -- sentinel --------------------------------------------------------
    def observe_step(self, loss, finite) -> None:
        """Post-step hook. ``loss``/``finite`` may be device arrays; they
        are only synced every ``check_interval`` steps (each sync is a
        device barrier — amortize on real slices)."""
        self.last_step_rewound = False
        if self.sentinel is None:
            return
        self._since_check += 1
        if self._since_check < self.cfg.check_interval:
            return
        self._since_check = 0
        loss_f = float(loss)
        finite_b = True if finite is None else bool(finite)
        scaler_active = getattr(self.engine.state, "scaler", None) is not None
        action = self.sentinel.observe(loss_f, finite_b,
                                       defer_nonfinite=scaler_active)
        if action == "ok":
            return
        self.counters["bad_steps"] += 1
        self._telemetry().note("bad_step", step=self.engine.global_steps,
                               action=action, loss=loss_f)
        if action in ("skip", "spike"):
            if action == "skip":
                self.counters["skipped_steps"] += 1
            logger.warning(
                f"sentinel: bad step at {self.engine.global_steps} "
                f"({action}, loss={loss_f}); streak "
                f"{self.sentinel.bad_streak}/{self.cfg.max_consecutive_bad}")
            self._emit_sentinel_events()
            return
        if action == "abort":
            self.counters["aborts"] += 1
            self._emit_sentinel_events()
            self._telemetry().flight_dump(
                "divergence", detail=f"abort at step "
                f"{self.engine.global_steps} (loss={loss_f})")
            raise DivergenceError(
                f"training diverged: {self.sentinel.bad_streak} consecutive "
                f"bad steps at step {self.engine.global_steps} after "
                f"{self.sentinel.rewinds} rewinds (budget "
                f"{self.cfg.max_rewinds}) — aborting")
        self._rewind(loss_f)

    def _rewind(self, loss_f: float) -> None:
        load_dir = self.cfg.rewind_dir or \
            (self.last_verified[0] if self.last_verified else None) or \
            self.last_save_dir
        if load_dir is None:
            self.counters["aborts"] += 1
            self._telemetry().flight_dump(
                "divergence", detail=f"no checkpoint to rewind to at step "
                f"{self.engine.global_steps}")
            raise DivergenceError(
                f"training diverged at step {self.engine.global_steps} "
                f"(loss={loss_f}) and there is no checkpoint to rewind to "
                f"(no prior save_checkpoint / resilience.rewind_dir)")
        from . import checkpointing as ckpt

        bad_step = self.engine.global_steps
        with self.guard("rewind_restore"):
            ckpt.load_checkpoint(self.engine, load_dir)
        self.sentinel.note_rewind()
        self.counters["rewinds"] += 1
        self.last_step_rewound = True
        self._telemetry().note("rewind", from_step=bad_step,
                               to_step=self.engine.global_steps,
                               loss=loss_f)
        logger.warning(
            f"sentinel: REWOUND from step {bad_step} (loss={loss_f}) to "
            f"verified checkpoint at step {self.engine.global_steps} "
            f"(rewind {self.sentinel.rewinds}/{self.cfg.max_rewinds}); "
            f"resume data order from the restored step")
        self._emit_sentinel_events()

    def _emit_sentinel_events(self) -> None:
        self.engine._emit_counters(self.counters, "Resilience/")
