"""Serving runner: one cell of a ``mode: serve`` configuration, once.

``--trace 0`` (end to end): this process runs the ``Router`` — which never
touches a device — over ONE ``InferenceEngineV2`` worker, the only process
on the chip, and offers the cell's seeded traffic through
``submit``/``poll`` with its own timestamps. When the worker has given the
chip back, the plain reference checks a seeded sample of the served
streams, in this process, on the chip.

``--trace 1`` (per layer): the worker cannot trace a window (program gap,
PERF.md), so this process itself holds the chip: it drives
``serving.replica.EngineBackend`` with ``put``/``step`` — the calls the
replica loop makes — on the same schedule, a few seconds of it under
``jax.profiler`` with the program's telemetry spans on.
"""
from __future__ import annotations

import json
import os
import shutil
import sys
import threading
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))

from benchmark import common  # noqa: E402
from benchmark.common import BenchFailure, say  # noqa: E402
from benchmark.traffic.generate import generate  # noqa: E402

HOOK_DIR = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                        "worker_hook")
class Req:
    """One request, with the client's own clock."""
    __slots__ = ("idx", "prompt", "max_new", "due", "sent", "first", "done",
                 "status", "tokens", "tid", "client", "phase")

    def __init__(self, idx, prompt, max_new, due=None, client=None,
                 phase="window"):
        self.idx, self.prompt, self.max_new = idx, prompt, int(max_new)
        self.due, self.client, self.phase = due, client, phase
        self.sent = self.first = self.done = None
        self.status, self.tokens, self.tid = None, [], None


class RouterClient:
    """The served path as a user holds it: ``Router.submit``/``poll``/
    ``result`` and nothing private."""

    def __init__(self, router, scan_every_s: float = 0.004):
        self.router = router
        self.open: dict[str, Req] = {}
        self._scan_every = scan_every_s
        self._last_scan = 0.0

    def busy(self) -> bool:
        return bool(self.open)

    def submit(self, req: Req) -> None:
        from deepspeed_tpu.serving import AdmissionError

        req.sent = time.monotonic()
        try:
            req.tid = self.router.submit(req.prompt,
                                         max_new_tokens=req.max_new)
            self.open[req.tid] = req
        except AdmissionError as e:
            req.done, req.status = req.sent, f"refused:{e.reason}"

    def pump(self, budget_s: float) -> list[Req]:
        self.router.poll(max(budget_s, 0.0))
        now = time.monotonic()
        if now - self._last_scan < self._scan_every:
            return []
        self._last_scan = now
        finished = []
        for tid, req in list(self.open.items()):
            res = self.router.result(tid)
            if req.first is None and res["tokens"]:
                req.first = now
            if res["status"] not in ("queued", "assigned", "recovering",
                                     "gang"):
                req.done, req.status = now, res["status"]
                req.tokens = res["tokens"]
                del self.open[tid]
                finished.append(req)
        return finished


class LocalClient:
    """The traced path: ``EngineBackend.put``/``step`` in this process,
    with the admission retry the replica loop and router do between them
    (a put the engine cannot take yet waits in a FIFO)."""

    def __init__(self, backend):
        from deepspeed_tpu.runtime.resilience import FaultInjector
        from deepspeed_tpu.serving.protocol import RequestRecord

        self.backend, self._Rec = backend, RequestRecord
        self.inj = FaultInjector(spec={}, env="", hard=False)
        self.open: dict[str, Req] = {}
        self.waiting: list[Req] = []
        self.tokens_emitted = 0
        self.peak_blocks = 0
        self.uid_of: dict[str, int] = {}     # every request ever admitted
        self._n = 0

    def busy(self) -> bool:
        return bool(self.open or self.waiting)

    def submit(self, req: Req) -> None:
        req.sent = time.monotonic()
        self._n += 1
        req.tid = f"b{self._n}"
        self.waiting.append(req)

    def pump(self, budget_s: float) -> list[Req]:
        while self.waiting and len(self.open) < self.backend.max_live:
            req = self.waiting[0]
            why = self.backend.put(self._Rec(
                trace_id=req.tid, prompt=req.prompt,
                max_new_tokens=req.max_new))
            if why is not None:
                break                    # capacity: retry next pump
            self.open[req.tid] = self.waiting.pop(0)
            self.uid_of[req.tid] = self.backend._uids[req.tid]
        if not self.backend.has_work():
            time.sleep(min(max(budget_s, 0.0), 0.001))
            return []
        finished = []
        events = self.backend.step(self.inj)
        now = time.monotonic()
        # blocks held by LIVE sequences: the allocator's own count also holds
        # the prefix trie's released pages and so trends to the whole pool
        self.peak_blocks = max(self.peak_blocks, sum(
            len(q.blocks) for q in self.backend.eng.state.seqs.values()))
        for rid, kind, toks, _ in events:
            req = self.open.get(rid)
            if req is None:
                continue
            if kind == "chunk":
                if req.first is None and toks:
                    req.first = now
                self.tokens_emitted += len(toks)
            elif kind == "done":
                req.done, req.status, req.tokens = now, "done", list(toks)
                del self.open[rid]
                finished.append(req)
        return finished


def drain(client, seconds: float) -> bool:
    """Pump until nothing is open or ``seconds`` have passed; True if the
    client ran empty."""
    deadline = time.monotonic() + seconds
    while client.busy() and time.monotonic() < deadline:
        client.pump(0.005)
    return not client.busy()


def run_load(client, sched: dict, t_start: float, send_until: float,
             drain_s: float, phase: str = "window", marks=()) -> list[Req]:
    """Offer one schedule from ``t_start`` until ``send_until``, then drain
    for at most ``drain_s``. ``marks`` are ``(time, callable)`` pairs in
    time order, each called once from the load loop when its time has come
    (the traced run opens its window so). Returns every request sent."""
    reqs: list[Req] = []
    marks = list(marks)

    def pump(budget_s: float):
        while marks and time.monotonic() >= marks[0][0]:
            marks.pop(0)[1]()
        return client.pump(budget_s)

    if sched["kind"] == "open_loop":
        todo = [Req(i, r["prompt"], r["max_new"], due=t_start + r["due_s"],
                    phase=phase) for i, r in enumerate(sched["requests"])]
        todo = [r for r in todo if r.due < send_until]
        nxt = 0
        while True:
            now = time.monotonic()
            if now >= send_until:
                break
            while nxt < len(todo) and todo[nxt].due <= now:
                client.submit(todo[nxt])
                reqs.append(todo[nxt])
                nxt += 1
            wait = (todo[nxt].due if nxt < len(todo) else send_until) - now
            pump(min(max(wait, 0.0), 0.005))
    elif sched["kind"] == "closed_loop":
        queues = [list(q) for q in sched["clients"]]
        n = 0

        def send_next(c: int) -> None:
            nonlocal n
            if not queues[c]:
                raise BenchFailure(f"client {c} ran out of requests: raise "
                                   f"max_rate_per_client in the traffic file")
            r = queues[c].pop(0)
            req = Req(n, r["prompt"], r["max_new"], client=c, phase=phase)
            n += 1
            client.submit(req)
            reqs.append(req)

        for c in range(len(queues)):
            send_next(c)
        while time.monotonic() < send_until:
            for req in pump(0.005):
                # (a finished request of an earlier phase has no caller)
                if req.client is not None and time.monotonic() < send_until:
                    send_next(req.client)
    else:
        raise BenchFailure(f"traffic kind {sched['kind']!r} cannot be served")
    drain(client, drain_s)
    return reqs


def warm_up(client, traffic: dict, seed: int, vocab: int) -> None:
    """Set-up: reach the programs this cell's traffic uses, through
    requests (the worker has no other door). The drill's bursts (``[k,
    prompt_len, max_new]``) walk the prefill shapes and the decode windows
    down to the single step; the cell's lead-in traffic does the rest."""
    rng = np.random.default_rng([seed, 7])
    for k, plen, max_new in traffic["warmup"]["drill"]:
        burst = [Req(-1, rng.integers(0, vocab, int(plen)).tolist(), max_new,
                     phase="warm") for _ in range(int(k))]
        for r in burst:
            client.submit(r)
        if not drain(client, 900.0):
            raise BenchFailure("warm-up drill did not finish in 900 s")
        bad = [r.status for r in burst if r.status != "done"]
        if bad:
            raise BenchFailure(f"warm-up drill request ended {bad}")


def warm_menu(client, eng, spec: dict, seed: int, vocab: int) -> int:
    """Traced run only (this process holds the engine): reach every prefill
    program the scheduler can emit for at most ``max_rows`` sequences with
    pending prompt and at most ``max_chunk`` tokens a row — exactly, through
    ``put``/``step``: a burst of k prompts of T tokens, all admitted before
    the next step, is one (T, k) plan. The menu is the scheduler's own
    (``program_shape_menu``), never a copy of its arithmetic. A compile
    inside a traced window of a few seconds leaves nothing to read."""
    rng = np.random.default_rng([seed, 13])
    room = eng.config.max_seq_len - 4
    menu = [(T, k) for T, k in eng.scheduler.program_shape_menu()
            if k <= int(spec["max_rows"]) and T <= int(spec["max_chunk"])]
    for T, k in menu:
        for _ in range(k):
            client.submit(Req(-1, rng.integers(0, vocab, min(T, room)).tolist(),
                              2, phase="warm"))
        if not drain(client, 900.0):
            raise BenchFailure(f"warm-up of prefill shape {(T, k)} did not "
                               f"finish in 900 s")
    return len(menu)


def pct(xs, q):
    return float(np.percentile(np.asarray(xs, np.float64), q)) if len(xs) \
        else float("nan")


def judge(reqs: list[Req], w0: float, w1: float, kind: str) -> dict:
    """End-to-end numbers from the client's own timestamps."""
    out: dict = {}
    if kind == "open_loop":
        judged = [r for r in reqs if w0 <= r.due < w1]
        ok = [r for r in judged if r.status == "done"
              and len(r.tokens) == r.max_new and r.first is not None]
        ttft = [r.first - r.due for r in ok]
        tpot = [(r.done - r.first) / (r.max_new - 1) * 1e3
                for r in ok if r.max_new > 1]
        late = [r.sent - r.due for r in judged]
        out.update(attempted=len(judged), failed=len(judged) - len(ok),
                   ok=ok, ttft_p90_s=pct(ttft, 90), tpot_p90_ms=pct(tpot, 90),
                   ttft_p50_s=pct(ttft, 50), tpot_p50_ms=pct(tpot, 50),
                   n_ttft=len(ttft), n_tpot=len(tpot),
                   late_p50_s=pct(late, 50),
                   late_max_s=max(late) if late else float("nan"))
    else:
        # whole completions inside the window (the estimator the chip runs
        # of PR 22 measured): the tokens of every completion after the
        # first, over the time from the first to the last — a rate between
        # completions, free of the +-1 request at each edge of the window
        fin = sorted((r for r in reqs if r.done is not None
                      and w0 <= r.done < w1), key=lambda r: r.done)
        ok = [r for r in fin if r.status == "done"
              and len(r.tokens) == r.max_new]
        rate = float("nan")
        if len(ok) >= 2 and ok[-1].done > ok[0].done:
            rate = sum(len(r.prompt) + len(r.tokens) for r in ok[1:]) \
                / (ok[-1].done - ok[0].done)
        ttft = [r.first - r.sent for r in ok if r.first is not None]
        out.update(attempted=len(fin), failed=len(fin) - len(ok), ok=ok,
                   serve_tok_per_s=rate, doc_ttft_p50_s=pct(ttft, 50),
                   n_completed=len(ok), late_p50_s=0.0, late_max_s=0.0)
    return out


# ---------------------------------------------------------------------------
# correctness
# ---------------------------------------------------------------------------

def structural(ok: list[Req], vocab: int) -> list[str]:
    bad = []
    for r in ok:
        if len(r.tokens) != r.max_new:
            bad.append(f"request {r.idx}: {len(r.tokens)} tokens, asked "
                       f"{r.max_new}")
        elif not all(0 <= t < vocab for t in r.tokens):
            bad.append(f"request {r.idx}: token outside the vocabulary")
    return bad


def reference_check(ok: list[Req], conf: dict, cellp: dict,
                    seed: int) -> tuple[bool, dict]:
    """The served token's reference logit against the maximum, teacher-
    forced, for a seeded sample of served streams. Runs in THIS process,
    on the device the worker has just given back."""
    import jax
    import jax.numpy as jnp

    from benchmark.reference import dense_decoder as ref
    from deepspeed_tpu.inference.weights import load_tp_params
    from deepspeed_tpu.models import build_model
    from deepspeed_tpu.parallel.topology import MeshConfig, MeshTopology

    spec = cellp["reference"]
    fits = [r for r in ok if len(r.prompt) + r.max_new <= spec["max_tokens"]]
    rng = np.random.default_rng([seed, 11])
    rng.shuffle(fits)
    sample = fits[:int(spec["requests"])]
    if len(sample) < int(spec["requests"]):
        return False, {"error": f"only {len(sample)} served streams fit the "
                                f"reference's {spec['max_tokens']} tokens"}
    model = build_model(conf["preset"], **conf["overrides"])
    m = model.config
    # the same seeded weights the worker built: same initialiser, same key,
    # same cast (bf16) — held as they are served, cast up a layer at a time
    topo = MeshTopology(MeshConfig(tensor=1, data=1),
                        devices=jax.devices()[:1])
    params, _ = load_tp_params(model, None, jax.random.PRNGKey(seed), topo,
                               jnp.bfloat16)
    bucket = int(spec.get("pad_to", 256))
    worst = {"prefill_form": 0.0, "decode_form": 0.0}
    rows_checked = 0
    for r in sample:
        P, n = len(r.prompt), min(r.max_new, int(spec["rows"]))
        toks = r.prompt + r.tokens
        S = -(-len(toks) // bucket) * bucket
        padded = np.zeros(S, np.int32)
        padded[:len(toks)] = toks
        rows = np.arange(P - 1, P - 1 + n)
        logits = np.asarray(ref.forward_logits(
            padded, embed=params["embed"],
            layer=lambda i: ref.program_layer(params, i),
            num_layers=m.num_layers, ln_final=params["ln_final"]["scale"],
            unembed=params["unembed"], theta=float(m.rope_theta),
            eps=float(m.norm_eps), rows=rows))
        served = np.asarray(r.tokens[:n])
        margin = logits.max(axis=1) - logits[np.arange(n), served]
        worst["prefill_form"] = max(worst["prefill_form"], float(margin[0]))
        if n > 1:
            worst["decode_form"] = max(worst["decode_form"],
                                       float(margin[1:].max()))
        rows_checked += n
    tol = float(spec["logit_tolerance"])
    good = max(worst.values()) <= tol
    return good, {"requests": len(sample), "rows": rows_checked,
                  "worst_margin": worst, "tolerance": tol}


# ---------------------------------------------------------------------------
# the two paths
# ---------------------------------------------------------------------------

def replica_config(conf: dict, seed: int, telemetry: bool = False) -> dict:
    eng = dict(conf["engine"])
    if telemetry:
        eng["telemetry"] = True
    return {"backend": "engine", "model": conf["preset"],
            "overrides": conf["overrides"], "seed": seed, "engine": eng}


def watch_worker(status_path: str, stop: threading.Event, router) -> None:
    """While the worker builds: leave at once if it reports the wrong
    device (its own hook has already stopped it), or if it died — the fleet
    would respawn it until ``ready_timeout_s``, and a build that failed
    once (no memory) fails again."""
    while not stop.wait(0.2):
        if router.fleet.replicas[0].epoch > 0:
            say("REFUSED: the worker died while it was being built (its log "
                "is under chiprun_out/benchmark/<cell>/logs)")
            os.killpg(0, 9)
        try:
            with open(status_path, encoding="utf-8") as f:
                st = json.load(f)
        except (OSError, ValueError):
            continue
        if st.get("error"):
            say(f"REFUSED: {st['error']}")
            os.killpg(0, 9)              # this runner's whole session


def start_router(conf: dict, seed: int, out_dir: str, chips: int,
                 rehearse: bool):
    """A ``Router`` (not started) over one engine worker as the
    configuration file fixes it, the worker observed through
    ``worker_hook`` — and the watcher that ends this session if the worker
    reports the wrong device or dies while it is built. Returns (router,
    the hook's status file, the watcher's stop event)."""
    from deepspeed_tpu.serving import FleetConfig, Router, RouterConfig

    os.makedirs(out_dir, exist_ok=True)
    status_path = os.path.join(out_dir, "worker_status.json")
    if os.path.exists(status_path):
        os.remove(status_path)
    env = {"DS_BENCH_WORKER_STATUS": status_path,
           "DS_BENCH_WANT_PLATFORM": "cpu" if rehearse else "tpu",
           "DS_BENCH_WANT_CHIPS": str(chips),
           "PYTHONPATH": HOOK_DIR + os.pathsep + os.environ.get(
               "PYTHONPATH", "")}
    if rehearse:
        env["JAX_PLATFORMS"] = "cpu"
    fleet = FleetConfig(n_replicas=1, replica=replica_config(conf, seed),
                        env=env, log_dir=os.path.join(out_dir, "logs"),
                        **conf.get("fleet", {}))
    router = Router(RouterConfig(fleet=fleet, **conf.get("router", {})))
    stop = threading.Event()
    threading.Thread(target=watch_worker, args=(status_path, stop, router),
                     daemon=True).start()
    return router, status_path, stop


def read_status(path: str) -> dict:
    for _ in range(20):
        try:
            with open(path, encoding="utf-8") as f:
                return json.load(f)
        except (OSError, ValueError):
            time.sleep(0.1)
    raise BenchFailure("the worker's hook wrote no status")


def end_to_end(args, entry, cellp, conf, traffic, vocab) -> None:
    router, status_path, stop = start_router(
        conf, args.seed, os.path.join(common.OUT_DIR, args.workload),
        entry["chips"], args.rehearse)
    want = "cpu" if args.rehearse else "tpu"
    lead, secs = float(traffic["lead_in_s"]), args.seconds
    sched = generate(traffic, args.seed, vocab, lead + secs)
    try:
        router.start(min_ready=1)
        stop.set()
        h = router.fleet.replicas[0]
        say(f"worker ready: platform={h.platform} kind={h.device_kind} "
            f"max_live={h.max_live}")
        if h.platform != want:
            raise BenchFailure(f"the worker's ready names {h.platform!r}")
        client = RouterClient(router)
        warm_up(client, traffic, args.seed, vocab)
        say(f"warm-up done; worker compiles so far: "
            f"{len(read_status(status_path)['compiles'])}")
        t_start = time.monotonic() + 0.05
        w0, w1 = t_start + lead, t_start + lead + secs
        reqs = run_load(client, sched, t_start, w1,
                        float(traffic["drain_s"]))
        double_commits = router.double_commits
        time.sleep(0.6)                  # the hook's last word
        status = read_status(status_path)
    finally:
        stop.set()
        router.close()                   # the worker exits: chip released
    res = judge(reqs, w0, w1, sched["kind"])
    in_window = sum(1 for t, _ in status["compiles"] if w0 <= t <= w1)
    say(f"window: attempted {res['attempted']} failed {res['failed']}; "
        f"samples " + json.dumps({k: v for k, v in res.items()
                                  if k.startswith("n_")})
        + f"; generator lateness p50 {res['late_p50_s'] * 1e3:.2f} ms max "
        f"{res['late_max_s'] * 1e3:.2f} ms; compilations inside the window: "
        f"{in_window} (of {len(status['compiles'])} in the run, "
        f"{sum(s for _, s in status['compiles']):.1f} s)")
    say("also: " + json.dumps({k: round(v, 4) for k, v in res.items()
                               if isinstance(v, float)}))
    if in_window:
        # not gated: the worker has no warm message, so a shape the drill
        # and the lead-in did not reach compiles (or is read from the
        # cache) on first use — but never in silence
        say(f"COMPILED INSIDE THE WINDOW: {in_window} program(s); this run's "
            f"numbers carry that stall")
    if not res["ok"]:
        raise BenchFailure("no request completed inside the window")
    problems = structural(res["ok"], vocab)
    if double_commits:
        problems.append(f"router.double_commits == {double_commits}")
    dev = common.check_device(status["device"], entry["chips"], args.rehearse)
    mine = common.require_device(entry["chips"], args.rehearse)
    if {k: mine[k] for k in ("platform", "kind")} != \
            {k: dev[k] for k in ("platform", "kind")}:
        problems.append(f"worker device {dev} is not this process's {mine}")
    common.CompileClock()
    from deepspeed_tpu.utils.compile_cache import enable_compile_cache
    enable_compile_cache()
    t0 = time.monotonic()
    good, detail = reference_check(res["ok"], conf, cellp, args.seed)
    say(f"reference check ({time.monotonic() - t0:.1f}s): "
        f"{'ok' if good else 'FAILED'} {json.dumps(detail)}")
    for p in problems[:10]:
        say(f"NOT CORRECT: {p}")
    values = dict(res, setup_s=w0 - common.T0)
    common.emit(entry, 0, correct=good and not problems
                and res["failed"] == 0 and res["attempted"] > 0,
                attempted=res["attempted"], failed=res["failed"],
                values=values,
                device={**dev, "memory_peak_bytes":
                        int(status["memory_peak_bytes"])})


def traced(args, entry, cellp, conf, traffic, vocab) -> None:
    dev = common.require_device(entry["chips"], args.rehearse)
    clock = common.CompileClock()
    import jax

    from benchmark import reduce_trace, work
    from deepspeed_tpu.serving.replica import EngineBackend
    from deepspeed_tpu.utils.compile_cache import enable_compile_cache

    say(f"compile cache: {enable_compile_cache()}")
    backend = EngineBackend(replica_config(conf, args.seed, telemetry=True))
    eng = backend.eng
    say(f"engine built; decode attention path: {eng._attn_decode_sel.path}")
    client = LocalClient(backend)
    warm_up(client, traffic, args.seed, vocab)
    n_menu = warm_menu(client, eng, cellp["trace_warm"], args.seed, vocab)
    say(f"warm-up done: {n_menu} prefill shapes of the scheduler's menu; "
        f"{json.dumps(clock.report())}")
    lead = float(traffic["lead_in_s"])
    secs = min(args.seconds, float(cellp.get("trace_seconds", 4.0)))
    sched = generate(traffic, args.seed, vocab, lead + secs)
    trace_dir = os.path.join(common.OUT_DIR, args.workload, "trace")
    shutil.rmtree(trace_dir, ignore_errors=True)

    t_start = time.monotonic() + 0.05
    w0, w1 = t_start + lead, t_start + lead + secs
    marks: dict = {}

    def progress() -> dict:
        return {s.uid: (len(s.tokens) - s.n_generated, s.kv_next)
                for s in eng.state.seqs.values()}

    def open_window() -> None:
        reduce_trace.start(trace_dir)
        # (made after the profiler has started: a span made before records
        # nothing)
        marks["span"] = jax.profiler.TraceAnnotation(reduce_trace.WINDOW_SPAN)
        marks["span"].__enter__()
        marks.update(on=time.monotonic(), stats0=dict(eng.stats),
                     emitted0=client.tokens_emitted, prog0=progress())
        client.peak_blocks = 0

    reqs = run_load(client, sched, t_start, w1, 0.0, marks=[(w0, open_window)])
    if "on" not in marks:
        raise BenchFailure("the traced window never opened")
    marks["span"].__exit__(None, None, None)
    marks.update(off=time.monotonic(), stats1=dict(eng.stats),
                 emitted1=client.tokens_emitted, prog1=progress())
    summary = reduce_trace.stop_and_summarize(
        trace_dir, host_only=args.rehearse)
    drain(client, float(traffic["drain_s"]))
    res = judge(reqs, w0, w1 + float(traffic["drain_s"]), sched["kind"])
    stats = {k: marks["stats1"][k] - marks["stats0"][k]
             for k, v in marks["stats1"].items()
             if isinstance(v, (int, float)) and k in marks["stats0"]}
    done_len = {r.tid: len(r.prompt) + len(r.tokens) for r in reqs
                if r.done is not None}
    ctx = {"trace": summary, "stats": stats, "stats_total": dict(eng.stats),
           "engine": conf["engine"], "model": conf,
           "peaks": work.peaks(dev["kind"]) if not args.rehearse else None,
           "window_s": marks["off"] - marks["on"],
           "tokens_emitted": marks["emitted1"] - marks["emitted0"],
           "kv_peak_blocks": client.peak_blocks,
           "progress": (marks["prog0"], marks["prog1"]),
           "uid_of": dict(client.uid_of), "done_len": done_len,
           "judged": res, "memory_peak_bytes": common.memory_peak_bytes()}
    in_window = clock.in_window(marks["on"], marks["off"])
    say(f"traced {ctx['window_s']:.2f}s: device busy {summary['busy_s']:.3f}s "
        f"of {summary['window_s']:.3f}s; programs "
        + json.dumps({k: [round(v['s'], 4), round(v['runs'])]
                      for k, v in summary["programs"].items()})
        + f"; compilations inside the window: {in_window}; "
        f"stats {json.dumps(stats)}")
    problems = structural(res["ok"], vocab)
    if eng.stats.get("attn_gather_decode", 0):
        problems.append("decode dispatches fell back to the gather path")
    for p in problems[:10]:
        say(f"NOT CORRECT: {p}")
    metrics = common.read_layers(entry, ctx)
    common.emit(entry, 1, correct=not problems and res["failed"] == 0,
                attempted=res["attempted"], failed=res["failed"],
                values=metrics,
                device={**dev, "memory_peak_bytes": ctx["memory_peak_bytes"],
                        "busy_s": summary["busy_s"],
                        "window_s": summary["window_s"]},
                breakdown=reduce_trace.breakdown(summary))


def main() -> int:
    args = common.runner_args()
    entry, cell, config, traffic = common.load_cell(args.workload)
    conf = common.pick(config, args.rehearse)
    traffic = common.pick(traffic, args.rehearse)
    cellp = common.pick(cell, args.rehearse)
    from deepspeed_tpu.models import get_model_config

    vocab = get_model_config(conf["preset"], **conf["overrides"]).vocab_size
    try:
        (traced if args.trace else end_to_end)(
            args, entry, cellp, conf, traffic, vocab)
    except BenchFailure as e:
        say(f"FAILED: {e}")
        return 1
    return 0


if __name__ == "__main__":
    sys.exit(main())
