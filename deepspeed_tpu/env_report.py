"""Environment / compatibility report — the ``ds_report`` analogue
(reference deepspeed/env_report.py + bin/ds_report).

Reports framework versions, visible devices, and per-feature compatibility
(the analogue of the reference's op-builder compatibility matrix: instead of
CUDA extensions we probe Pallas lowering, native host extensions, and
distributed bring-up prerequisites).

Run as ``python -m deepspeed_tpu.env_report``.
"""
from __future__ import annotations

import importlib
import os
import shutil
import sys

GREEN_OK = "\033[92m[OKAY]\033[0m"
RED_NO = "\033[91m[NO]\033[0m"
YELLOW_WARN = "\033[93m[WARN]\033[0m"


def _version(mod_name: str) -> str | None:
    try:
        mod = importlib.import_module(mod_name)
        return getattr(mod, "__version__", "unknown")
    except Exception:
        return None


def feature_report() -> list[tuple[str, bool, str]]:
    """Probe each optional capability: (name, compatible, detail)."""
    import jax

    feats: list[tuple[str, bool, str]] = []

    # device backend
    try:
        devs = jax.devices()
        plat = devs[0].platform
        feats.append(("device backend", True, f"{plat} x{len(devs)}"))
        on_tpu = plat == "tpu" or devs[0].device_kind.lower().startswith("tpu")
    except Exception as e:
        feats.append(("device backend", False, str(e)))
        on_tpu = False

    # pallas lowering (flash attention kernel path)
    try:
        from .ops.pallas import flash_attention  # noqa: F401

        feats.append(("pallas kernels", True,
                      "TPU lowering" if on_tpu else "interpret-mode fallback on CPU"))
    except Exception as e:
        feats.append(("pallas kernels", False, str(e)))

    # native host extension (async I/O + SIMD optimizer)
    try:
        from .ops.native import lib_status

        ok, detail = lib_status()
        feats.append(("native host ops (aio/cpu-adam)", ok, detail))
    except Exception:
        feats.append(("native host ops (aio/cpu-adam)", False,
                      "not built (python fallback active)"))

    # checkpointing backend
    feats.append(("orbax checkpointing", _version("orbax.checkpoint") is not None,
                  f"orbax {_version('orbax.checkpoint')}"))

    # multi-host distributed
    has_coord = bool(os.environ.get("DS_TPU_COORDINATOR")
                     or os.environ.get("COORDINATOR_ADDRESS")
                     or os.environ.get("JAX_COORDINATOR_ADDRESS"))
    feats.append(("multi-host init env", True,
                  "coordinator set" if has_coord else "single-process (no coordinator env)"))

    # launcher tooling
    for tool in ("ssh", "pdsh", "srun", "mpirun"):
        if shutil.which(tool):
            feats.append((f"launcher: {tool}", True, shutil.which(tool)))

    # C++ toolchain (for building native ops from source)
    cxx = shutil.which("g++") or shutil.which("clang++")
    feats.append(("C++ toolchain", cxx is not None, cxx or "no g++/clang++"))

    # speculative decoding (inference/speculative.py): both proposer
    # backends are pure in-process logic — availability is an import
    # check, not a hardware one (the verify forward runs wherever the
    # engine does)
    try:
        from .inference import speculative as _spec  # noqa: F401
        feats.append(("inference: speculative decoding", True,
                      "engine_v2 spec_decode={'ngram','draft'} "
                      "(tree-verify over the paged pool)"))
    except Exception as e:  # pragma: no cover — import breakage only
        feats.append(("inference: speculative decoding", False, str(e)))

    # serving attention formulation (inference/attn_registry.py): which
    # path a representative engine geometry would dispatch, per mode,
    # WITH the fallback reason — the report-level mirror of the
    # serving_attn_kernel_total{path,mode} counter
    try:
        from .inference.attn_registry import select_attention
        from .ops.pallas.paged_attention import paged_attention_usable

        geo = dict(num_heads=8, kv_heads=8, head_dim=64, block_size=64)
        usable = paged_attention_usable(**geo)
        parts = []
        for mode, kw in (("decode", {}),
                         ("tree", {"tree_nodes": 8, "stage_rows": 8})):
            sel = select_attention(
                mode=mode, use_pallas=usable,
                reason_not_usable="" if usable else "kernel gate off "
                "(pltpu/head geometry)", **geo, **kw)
            parts.append(f"{mode}={sel.path}" +
                         (f" ({sel.reason})" if sel.reason else ""))
        feats.append(("serving: attention formulation", usable,
                      "; ".join(parts) +
                      ("" if on_tpu else " [interpret-mode on CPU]")))
    except Exception as e:  # pragma: no cover — import breakage only
        feats.append(("serving: attention formulation", False, str(e)))

    # serving tier (serving/): router + replica fleet are pure stdlib
    # multiprocessing over the engine — availability is an import check
    try:
        from . import serving as _serving  # noqa: F401
        feats.append((
            "serving: multi-replica router", True,
            "serving.Router over N engine_v2 workers (prefix-cache-aware "
            "placement, retry-with-replay failover, SLO shedding, "
            "circuit breaker; driven by tests/test_serving.py)"))
    except Exception as e:  # pragma: no cover — import breakage only
        feats.append(("serving: multi-replica router", False, str(e)))

    # disaggregated prefill/decode (serving/disagg.py over the KV-page
    # migration primitive in inference/migration.py): host logic + the
    # engine's pool read/scatter — an import check here too
    try:
        from .inference import migration as _mig  # noqa: F401
        from .serving import disagg as _disagg  # noqa: F401
        feats.append((
            "serving: disaggregated prefill/decode", True,
            "FleetConfig roles=['prefill','decode',...] — KV page-bundle "
            "handoff through the router (pinned-until-ack, resumable, "
            "bit-identical greedy), remote replicas via --listen "
            "sockets, scale-hint gauges; driven by tests/test_disagg.py"))
    except Exception as e:  # pragma: no cover — import breakage only
        feats.append(("serving: disaggregated prefill/decode", False,
                      str(e)))

    # fleet-wide KV reuse (serving/shm.py + router kv_pull/rebalance):
    # the shm ring needs a working POSIX shared-memory mount, so probe
    # one for real — relay-only hosts still serve, just slower intra-host
    try:
        from .serving import shm as _shm
        ring = _shm.open_ring(_shm.MIN_RING_BYTES)
        have_shm = ring is not None
        if ring is not None:
            ring.close()
        feats.append((
            "serving: distributed prefix cache", True,
            "placement-time cross-replica radix pulls (RouterConfig."
            "kv_pull, cost-model gated, recompute-safe) + hot-replica "
            "rebalancing; intra-host shm page ring "
            + ("available" if have_shm else
               "UNAVAILABLE (router relay only)")
            + "; driven by tests/test_kv_pull.py"))
    except Exception as e:  # pragma: no cover — import breakage only
        feats.append(("serving: distributed prefix cache", False,
                      str(e)))

    # KV tiering (inference/kvtier.py): HBM → host RAM → NVMe under the
    # fleet radix — pure host code; probe the spill dir + the rate probe
    try:
        from .inference import kvtier as _kvtier
        rates = _kvtier.measure_tier_rates()
        feats.append((
            "inference: KV tiering (HBM → host RAM → NVMe)", True,
            "prefix-cache eviction demotes chains into a bounded "
            "host-RAM ring + NVMe spill (kind=\"prefix\" PageBundles, "
            "crc+length gated, torn-spill-safe); admission misses "
            "promote via adopt_prefix instead of recomputing; "
            f"probed RAM rate {rates['ram_bytes_s'] / 1e9:.1f} GB/s; "
            "engine kv_tier=True / replica cfg kv_tier={...}; "
            "driven by tests/test_kvtier.py"))
    except Exception as e:  # pragma: no cover — import breakage only
        feats.append(("inference: KV tiering (HBM → host RAM → NVMe)",
                      False, str(e)))

    # anticipatory KV movement (serving/push.py + router/replica
    # overlap): proactive pushes, promote-ahead, transfer/compute
    # overlap — pure host logic, availability is an import check
    try:
        from .serving import push as _push  # noqa: F401
        feats.append((
            "serving: anticipatory KV movement (push/overlap)", True,
            "RouterConfig(kv_push=True, kv_overlap=True) — idle-window "
            "heat-scored pushes of hot chains to digest-cold replicas "
            "over declinable kv_push offers (demand joins in-flight "
            "transfers), promote_hint starts the two-phase tier "
            "extract concurrent with admission, and overlap promises "
            "prefill the suffix during the transfer with commit-or-"
            "rollback settlement; driven by tests/test_kv_push.py"))
    except Exception as e:  # pragma: no cover — import breakage only
        feats.append(("serving: anticipatory KV movement (push/overlap)",
                      False, str(e)))

    # gang prefill (serving/router.py + parallel/sequence.py): one long
    # prompt's prefill sharded across the fleet — pure host logic
    try:
        from .serving.placement import plan_gang_prefill as _pgp  # noqa: F401
        feats.append((
            "serving: gang prefill (fleet-sharded prompts)", True,
            "RouterConfig.gang_prefill — long prompts split page-"
            "aligned across K prefill-role replicas, merged KV staged "
            "member-to-member over kind=\"prefix\" bundles, first "
            "token on the final member; cost-model gated, any failure "
            "collapses to single-replica (bit-identical); "
            "driven by tests/test_gang.py"))
    except Exception as e:  # pragma: no cover — import breakage only
        feats.append(("serving: gang prefill (fleet-sharded prompts)",
                      False, str(e)))

    # zero-downtime weight deploys (serving/deploy.py): rolling hot-swap
    # behind the router — pure host logic, availability is an import check
    try:
        from .serving import deploy as _deploy  # noqa: F401
        feats.append((
            "serving: zero-downtime weight deploys", True,
            "Router.deploy(ckpt) — verified-manifest rolling swap "
            "(canary + probe + health-gated soak, auto-rollback, "
            "version-skew-safe KV); engine_v2.swap_weights/save_weights; "
            "driven by tests/test_deploy.py"))
    except Exception as e:  # pragma: no cover — import breakage only
        feats.append(("serving: zero-downtime weight deploys", False,
                      str(e)))

    # crash-safe control plane (serving/journal.py): write-ahead request
    # journal + fleet re-adoption — pure host logic, import check
    try:
        from .serving import journal as _journal  # noqa: F401
        feats.append((
            "serving: crash-safe router (journal + resync)", True,
            "RouterConfig.journal_dir — crc'd segmented write-ahead log "
            "(fsync always|interval|none), restart replays + re-adopts "
            "daemon replicas via resync (streams re-attach, exactly-"
            "once); driven by tests/test_journal.py"))
    except Exception as e:  # pragma: no cover — import breakage only
        feats.append(("serving: crash-safe router (journal + resync)",
                      False, str(e)))

    # elastic fleet actuators (serving/elastic.py): scale hints become
    # journaled drain/spawn/re-role — pure host logic, import check
    try:
        from .serving import elastic as _elastic  # noqa: F401
        feats.append((
            "serving: elastic fleet (drain/spawn/re-role)", True,
            "RouterConfig.elastic=True — sustained scale hints drive "
            "journaled deadline-bounded drain/retire (KV-tier flush), "
            "spawn with peer pre-warm, prefill<->decode re-role; "
            "SIGTERM / GCE maintenance preemption exits 83 (classified, "
            "no breaker); driven by tests/test_elastic.py"))
    except Exception as e:  # pragma: no cover — import breakage only
        feats.append(("serving: elastic fleet (drain/spawn/re-role)",
                      False, str(e)))

    # telemetry / monitor backends (telemetry/ + monitor/): which push
    # backends can actually activate, and where the pull endpoint +
    # flight recorder would land for this process
    for name, mods in (("monitor: tensorboard",
                        ("torch.utils.tensorboard", "tensorboardX")),
                       ("monitor: wandb", ("wandb",)),
                       ("monitor: comet", ("comet_ml",))):
        hit = next((m for m in mods if _importable(m)), None)
        feats.append((name, hit is not None,
                      f"{hit} importable" if hit else "package not installed"))
    feats.append(("monitor: prometheus", True,
                  "stdlib exposition (always available)"))
    port = os.environ.get("DS_TPU_TELEMETRY_PORT")
    telem_on = os.environ.get("DS_TPU_TELEMETRY", "") not in ("", "0", "false")
    feats.append((
        "telemetry (spans/metrics/SLOs)", True,
        ("enabled via DS_TPU_TELEMETRY" if telem_on
         else "disabled (config telemetry.enabled / DS_TPU_TELEMETRY=1)")
        + (f", /metrics port {port}" if port else ", no HTTP port")))
    rt_on = os.environ.get("DS_TPU_REQTRACE", "") not in ("", "0", "false")
    feats.append((
        "reqtrace (per-request lifecycle tracing)", True,
        "enabled via DS_TPU_REQTRACE (trace IDs, per-tenant series, "
        "SLO-breach auto-capture)" if rt_on
        else "disabled (engine_v2 reqtrace=True / telemetry.reqtrace / "
             "DS_TPU_REQTRACE=1)"))
    # fleet tracing (telemetry/fleettrace.py over serving/): pure host
    # logic on the line protocol — availability is an import check
    try:
        from .telemetry import fleettrace as _ft  # noqa: F401
        feats.append((
            "fleet tracing (cross-replica postmortems)", True,
            "RouterConfig(fleet_trace=True) — router-minted trace IDs "
            "adopted fleet-wide, heartbeat clock-offset estimation, "
            "merged clock-aligned timelines, black-box dumps "
            "(bin/ds_postmortem), straggler gauges"))
    except Exception as e:  # pragma: no cover — import breakage only
        feats.append(("fleet tracing (cross-replica postmortems)", False,
                      str(e)))
    # fleet watchtower (telemetry/timeseries.py + alerts.py + bin/ds_top):
    # time-series store, anomaly alerting, live ops console — pure host
    # logic, so availability is an import check; the detail row names the
    # knob, the retention defaults, and the loaded default-rule pack
    try:
        from .telemetry import timeseries as _ts
        from .telemetry.alerts import default_fleet_rules as _dfr
        _rules = _dfr()
        _names = ", ".join(r.name for r in _rules[:3])
        feats.append((
            "fleet watchtower (store/alerts/ds_top)", True,
            f"RouterConfig(watchtower=True) — on-disk time-series store "
            f"(retention {_ts.DEFAULT_RETENTION_BYTES >> 20} MiB), "
            f"{len(_rules)} default rules ({_names}, ...), /alerts + "
            f"/series endpoints, bin/ds_top console"))
    except Exception as e:  # pragma: no cover — import breakage only
        feats.append(("fleet watchtower (store/alerts/ds_top)", False,
                      str(e)))
    fr = os.environ.get("DS_TPU_FLIGHT_RECORDER")
    feats.append(("flight recorder", True,
                  f"dumps to {fr}" if fr
                  else "log-only (set DS_TPU_FLIGHT_RECORDER or "
                       "telemetry.flight_recorder_path)"))
    return feats


def _importable(mod_name: str) -> bool:
    try:
        return importlib.util.find_spec(mod_name) is not None
    except (ImportError, ValueError, ModuleNotFoundError):
        return False


def main(hide_errors: bool = False) -> str:
    import jax

    from .version import __version__

    lines = ["-" * 72,
             "deepspeed_tpu environment report (ds_report analogue)",
             "-" * 72,
             f"deepspeed_tpu ......... {__version__}",
             f"python ................ {sys.version.split()[0]}",
             f"jax ................... {_version('jax')}",
             f"jaxlib ................ {_version('jaxlib')}",
             f"flax .................. {_version('flax')}",
             f"optax ................. {_version('optax')}",
             f"orbax-checkpoint ...... {_version('orbax.checkpoint')}",
             f"numpy ................. {_version('numpy')}",
             "-" * 72,
             "feature compatibility:"]
    for name, ok, detail in feature_report():
        mark = GREEN_OK if ok else RED_NO
        lines.append(f"  {name:<34s} {mark}  {detail}")
    lines.append("-" * 72)
    try:
        lines.append(f"default backend: {jax.default_backend()}, "
                     f"devices: {[str(d) for d in jax.devices()]}")
    except Exception as e:
        if not hide_errors:
            lines.append(f"device query failed: {e}")
    lines.append("-" * 72)
    text = "\n".join(lines)
    print(text)
    return text


def cli_main() -> int:
    """Console-script entry (pyproject ``ds-tpu-report``)."""
    main()
    return 0


if __name__ == "__main__":
    main()
