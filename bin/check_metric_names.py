#!/usr/bin/env python
"""Repo lint: every emitted metric/span tag must be a valid Prometheus
metric name after sanitization.

The /metrics endpoint (telemetry/exposition.py) renders every registered
metric; a tag that can't sanitize to ``[a-zA-Z_:][a-zA-Z0-9_:]*`` would make
the exposition raise — a 500 on every scrape until someone notices the
dashboard went dark. The registry already raises at CREATION time
(telemetry/metrics.py ``sanitize_metric_name``), but that fires on the
first hot-path emit of a rarely-taken branch; this lint moves the failure
to test time by checking every STRING LITERAL passed as the first argument
of a metric/span emit call (``counter``/``gauge``/``histogram``/``span``/
``step_span``/``note``) plus ``write_counters`` tag prefixes.

Dynamic (non-literal) names can't be checked statically — the runtime
sanitizer remains the backstop for those.

Label checks (the per-tenant attribution path, telemetry/reqtrace.py):
literal ``labels={...}`` dicts on metric emits must carry valid label
NAMES (``[a-zA-Z_][a-zA-Z0-9_]*``) and literal label VALUES that survive
``sanitize_label_value`` unchanged (a literal that the runtime would
mangle is a latent dashboard-query mismatch). The lint also pins the
runtime cardinality bound: ``TENANT_CARDINALITY_CAP`` must exist in
telemetry/reqtrace.py as an integer literal in [1, 64] — the constant
that keeps an untrusted tenant population from exploding the scrape.

Metric-family documentation (docs/METRICS.md): every ``serving_*`` /
``telemetry_*`` family emitted with a literal name is collected
(``collect_metric_families``) and must appear in the auto-generated
reference — ``check_metrics_doc`` flags both undocumented emissions and
stale doc entries, and ``--write-doc`` regenerates the file. The drift
test lives in tests/test_repo_lint.py next to the tag lint.

Usage: ``python bin/check_metric_names.py [root]`` — prints violations as
``path:line: message``, exits nonzero if any. Enforced from
tests/test_repo_lint.py. ``python bin/check_metric_names.py --write-doc
[root]`` regenerates docs/METRICS.md.
"""
from __future__ import annotations

import ast
import os
import re
import sys

#: method names whose first string-literal argument is a metric/span tag
EMIT_METHODS = ("counter", "gauge", "histogram", "span", "step_span", "note")

#: methods whose ``labels=`` kwarg (when a literal dict) is validated
LABELED_METHODS = ("counter", "gauge", "histogram")

#: methods whose ``prefix`` kwarg (or the given positional index) prepends
#: to metric tags — write_counters(counters, step, prefix) and the
#: engine's _emit_counters(counters, prefix) that forwards to it
PREFIX_METHODS = {"write_counters": 2, "_emit_counters": 1}

#: where the runtime cardinality cap lives + its legal range (an upper
#: bound too: 64 tenants x a handful of series is the most a scrape
#: should ever carry per family)
CAP_FILE = "deepspeed_tpu/telemetry/reqtrace.py"
CAP_NAME = "TENANT_CARDINALITY_CAP"
CAP_RANGE = (1, 64)

_VALID_NAME = re.compile(r"[a-zA-Z_:][a-zA-Z0-9_:]*\Z")
_INVALID_CHARS = re.compile(r"[^a-zA-Z0-9_:]")
_VALID_LABEL_NAME = re.compile(r"[a-zA-Z_][a-zA-Z0-9_]*\Z")
_LABEL_VALUE_BAD = re.compile(r"[^A-Za-z0-9_\-./:]")
LABEL_VALUE_MAX_LEN = 64


def sanitize(name: str) -> str:
    """Mirror of telemetry/metrics.py ``sanitize_metric_name`` (kept
    dependency-free so the lint never imports jax); a drift test in
    tests/test_telemetry.py pins the two together."""
    out = _INVALID_CHARS.sub("_", name)
    if out and out[0].isdigit():
        out = "_" + out
    return out


def sanitize_label_value(value) -> str:
    """Mirror of telemetry/metrics.py ``sanitize_label_value`` (same
    dependency-free rule; tests/test_reqtrace.py pins the two together)."""
    out = _LABEL_VALUE_BAD.sub("_", str(value))[:LABEL_VALUE_MAX_LEN]
    return out or "unknown"


def tag_problem(tag: str) -> str | None:
    """None if ``tag`` survives sanitization as a valid Prometheus name."""
    s = sanitize(tag)
    if not _VALID_NAME.fullmatch(s):
        return (f"tag {tag!r} sanitizes to {s!r}, which is not a valid "
                f"Prometheus metric name ([a-zA-Z_:][a-zA-Z0-9_:]*)")
    return None


def _literal_tags(node: ast.Call) -> list[tuple[str, str]]:
    """(role, literal) tags this emit call carries, if statically known."""
    f = node.func
    if not isinstance(f, ast.Attribute):
        return []
    out: list[tuple[str, str]] = []
    if f.attr in EMIT_METHODS and node.args \
            and isinstance(node.args[0], ast.Constant) \
            and isinstance(node.args[0].value, str):
        out.append((f.attr, node.args[0].value))
    if f.attr in PREFIX_METHODS:
        idx = PREFIX_METHODS[f.attr]
        for kw in node.keywords:
            if kw.arg == "prefix" and isinstance(kw.value, ast.Constant) \
                    and isinstance(kw.value.value, str):
                out.append((f.attr, kw.value.value + "x"))  # prefix + tag
        if len(node.args) > idx and isinstance(node.args[idx], ast.Constant) \
                and isinstance(node.args[idx].value, str):
            out.append((f.attr, node.args[idx].value + "x"))
    return out


def _label_problems(node: ast.Call) -> list[str]:
    """Violations in a literal ``labels={...}`` kwarg: bad label names,
    or literal values the runtime sanitizer would mangle (exposition would
    then show a DIFFERENT value than the code wrote — dashboard queries
    against the literal silently match nothing)."""
    f = node.func
    if not (isinstance(f, ast.Attribute) and f.attr in LABELED_METHODS):
        return []
    out: list[str] = []
    for kw in node.keywords:
        if kw.arg != "labels" or not isinstance(kw.value, ast.Dict):
            continue
        for k, v in zip(kw.value.keys, kw.value.values):
            if isinstance(k, ast.Constant) and isinstance(k.value, str) \
                    and not _VALID_LABEL_NAME.fullmatch(k.value):
                out.append(f"label name {k.value!r} is not a valid "
                           f"Prometheus label name "
                           f"([a-zA-Z_][a-zA-Z0-9_]*)")
            if isinstance(v, ast.Constant) \
                    and isinstance(v.value, (str, int, float)):
                lit = str(v.value)
                if sanitize_label_value(lit) != lit:
                    out.append(f"literal label value {lit!r} would be "
                               f"rewritten by sanitize_label_value() — "
                               f"emit the sanitized form")
    return out


def check_file(path: str) -> list[str]:
    with open(path, encoding="utf-8") as f:
        src = f.read()
    try:
        tree = ast.parse(src, filename=path)
    except SyntaxError as e:
        return [f"{path}:{e.lineno}: unparseable ({e.msg})"]
    out: list[str] = []
    for node in ast.walk(tree):
        if not isinstance(node, ast.Call):
            continue
        for role, tag in _literal_tags(node):
            problem = tag_problem(tag)
            if problem:
                out.append(f"{path}:{node.lineno}: {role}() {problem}")
        for problem in _label_problems(node):
            out.append(f"{path}:{node.lineno}: {node.func.attr}() "
                       f"{problem}")
    return out


def check_cardinality_cap(root: str) -> list[str]:
    """The per-tenant path must carry an enforced cardinality bound:
    ``TENANT_CARDINALITY_CAP`` in telemetry/reqtrace.py, an int literal in
    CAP_RANGE. A refactor that removes or de-literalizes it would drop the
    scrape's only defense against tenant-label explosion."""
    path = os.path.join(root, *CAP_FILE.split("/"))
    if not os.path.exists(path):
        return [f"{path}:0: {CAP_NAME} host file missing"]
    with open(path, encoding="utf-8") as f:
        try:
            tree = ast.parse(f.read(), filename=path)
        except SyntaxError as e:
            return [f"{path}:{e.lineno}: unparseable ({e.msg})"]
    for node in ast.walk(tree):
        if isinstance(node, ast.Assign):
            for t in node.targets:
                if isinstance(t, ast.Name) and t.id == CAP_NAME:
                    v = node.value
                    if not (isinstance(v, ast.Constant)
                            and isinstance(v.value, int)
                            and not isinstance(v.value, bool)):
                        return [f"{path}:{node.lineno}: {CAP_NAME} must be "
                                f"an integer LITERAL (statically "
                                f"checkable), found "
                                f"{ast.dump(v)[:60]}"]
                    lo, hi = CAP_RANGE
                    if not lo <= v.value <= hi:
                        return [f"{path}:{node.lineno}: {CAP_NAME} = "
                                f"{v.value} outside the sane range "
                                f"[{lo}, {hi}]"]
                    return []
    return [f"{path}:0: {CAP_NAME} not found — the per-tenant series "
            f"cardinality bound is gone"]


# --- watchtower alert rules (telemetry/alerts.py) ---------------------------

#: where the rule pack + severity vocabulary live
ALERTS_FILE = "deepspeed_tpu/telemetry/alerts.py"
#: the allowed severity vocabulary — also pinned as the SEVERITIES tuple
#: literal in ALERTS_FILE (rule severities become the ``severity`` label
#: on serving_alerts_{firing,total} and the /alerts JSON)
ALERT_SEVERITIES = ("info", "warning", "critical")


def check_alert_rules(root: str) -> list[str]:
    """Watchtower drift-pins, same discipline as the tag lint:

    - every literal ``name=`` on an ``AlertRule(...)`` call must survive
      ``sanitize_label_value`` unchanged (rule names become the ``rule``
      label on ``serving_alerts_*`` and the fingerprints in ``/alerts`` —
      a name the runtime rewrites breaks dashboard queries AND dedup);
    - every literal ``severity=`` must be in ALERT_SEVERITIES;
    - every literal ``metric=`` must name a family actually emitted
      somewhere with a literal name (a rule watching a renamed metric
      would silently never fire — the nastiest observability failure);
    - the ``SEVERITIES`` tuple in alerts.py must literally equal
      ALERT_SEVERITIES (the runtime validator and this lint must agree).
    """
    path = os.path.join(root, *ALERTS_FILE.split("/"))
    if not os.path.exists(path):
        return [f"{path}:0: watchtower rules file missing"]
    with open(path, encoding="utf-8") as f:
        try:
            tree = ast.parse(f.read(), filename=path)
        except SyntaxError as e:
            return [f"{path}:{e.lineno}: unparseable ({e.msg})"]
    out: list[str] = []
    fams = set(collect_metric_families(root))
    sev_pinned = False
    for node in ast.walk(tree):
        if isinstance(node, ast.Assign):
            for t in node.targets:
                if isinstance(t, ast.Name) and t.id == "SEVERITIES":
                    v = node.value
                    vals = tuple(
                        e.value for e in getattr(v, "elts", [])
                        if isinstance(e, ast.Constant)) \
                        if isinstance(v, (ast.Tuple, ast.List)) else None
                    if vals != ALERT_SEVERITIES:
                        out.append(
                            f"{path}:{node.lineno}: SEVERITIES must be the "
                            f"literal tuple {ALERT_SEVERITIES!r} (the lint "
                            f"and the runtime validator must agree), found "
                            f"{vals!r}")
                    sev_pinned = True
        if not (isinstance(node, ast.Call) and (
                (isinstance(node.func, ast.Name)
                 and node.func.id == "AlertRule")
                or (isinstance(node.func, ast.Attribute)
                    and node.func.attr == "AlertRule"))):
            continue
        kwargs = {kw.arg: kw.value for kw in node.keywords}
        name_v = kwargs.get("name") or (node.args[0] if node.args else None)
        if isinstance(name_v, ast.Constant) and isinstance(name_v.value, str):
            lit = name_v.value
            if sanitize_label_value(lit) != lit:
                out.append(
                    f"{path}:{node.lineno}: alert rule name {lit!r} would "
                    f"be rewritten by sanitize_label_value() — it is the "
                    f"'rule' label value and the fingerprint prefix")
        sev_v = kwargs.get("severity")
        if isinstance(sev_v, ast.Constant) and isinstance(sev_v.value, str) \
                and sev_v.value not in ALERT_SEVERITIES:
            out.append(
                f"{path}:{node.lineno}: alert severity {sev_v.value!r} not "
                f"in {ALERT_SEVERITIES!r}")
        met_v = kwargs.get("metric")
        if isinstance(met_v, ast.Constant) and isinstance(met_v.value, str) \
                and met_v.value.startswith(DOC_PREFIXES) \
                and met_v.value not in fams:
            out.append(
                f"{path}:{node.lineno}: alert rule watches metric "
                f"{met_v.value!r}, which is not emitted with a literal "
                f"name anywhere — the rule would silently never fire")
    if not sev_pinned:
        out.append(f"{path}:0: SEVERITIES tuple not found — the severity "
                   f"vocabulary pin is gone")
    return out


def _targets(root: str) -> list[str]:
    targets = []
    for dirpath, _, files in os.walk(os.path.join(root, "deepspeed_tpu")):
        targets += [os.path.join(dirpath, f) for f in files
                    if f.endswith(".py")]
    return sorted(targets)


def check_repo(root: str) -> list[str]:
    out: list[str] = []
    for path in _targets(root):
        out += check_file(path)
    out += check_cardinality_cap(root)
    return out


# --- metric-family documentation (docs/METRICS.md) --------------------------

#: only user-facing scrape families are documented; internal monitor tag
#: prefixes (Train/, Resilience/, ...) stay out of scope
DOC_PREFIXES = ("serving_", "telemetry_")
DOC_FILE = "docs/METRICS.md"
#: method -> (name arg index, metric type, help arg index | None).
#: counter/gauge/histogram are the registry emits; _tenant_inc and
#: _observe_slo are reqtrace's forwarders whose literal family names
#: would otherwise be invisible to a static scan.
FAMILY_METHODS = {
    "counter": (0, "counter", None),
    "gauge": (0, "gauge", None),
    "histogram": (0, "histogram", None),
    "_tenant_inc": (0, "counter", 3),
    "_observe_slo": (1, "histogram", 4),
}


def _str_arg(node: ast.Call, idx: int | None, kwarg: str | None = None):
    if kwarg is not None:
        for kw in node.keywords:
            if kw.arg == kwarg and isinstance(kw.value, ast.Constant) \
                    and isinstance(kw.value.value, str):
                return kw.value.value
    if idx is not None and len(node.args) > idx \
            and isinstance(node.args[idx], ast.Constant) \
            and isinstance(node.args[idx].value, str):
        return node.args[idx].value
    return None


def collect_metric_families(root: str) -> dict[str, dict]:
    """Every ``serving_*``/``telemetry_*`` family emitted with a literal
    name anywhere in the package: {name: {type, help, file}}. Dynamic
    names can't be collected statically — same caveat as the tag lint."""
    fams: dict[str, dict] = {}
    for path in _targets(root):
        with open(path, encoding="utf-8") as f:
            try:
                tree = ast.parse(f.read(), filename=path)
            except SyntaxError:
                continue                 # check_file reports it
        rel = os.path.relpath(path, root)
        for node in ast.walk(tree):
            if not (isinstance(node, ast.Call)
                    and isinstance(node.func, ast.Attribute)
                    and node.func.attr in FAMILY_METHODS):
                continue
            name_idx, mtype, help_idx = FAMILY_METHODS[node.func.attr]
            name = _str_arg(node, name_idx)
            if name is None or not name.startswith(DOC_PREFIXES):
                continue
            help_s = _str_arg(node, help_idx, kwarg="help") or ""
            ent = fams.get(name)
            if ent is None or (not ent["help"] and help_s):
                fams[name] = {"type": mtype, "help": help_s, "file": rel}
    return fams


#: the serving engine's host loop, written by hand (spans and
#: ``engine.stats`` keys are no registry families: nothing collects them
#: statically); ``render_metrics_doc`` appends it as it stands
ENGINE_LOOP_DOC = """\
## The serving engine's loop: spans and `engine.stats`

Spans (`telemetry/spans.py`, only with telemetry on; each is mirrored into
`jax.profiler.TraceAnnotation` under its bare name, its scalar arguments as
the event's stats). `seq` numbers the entries appended to the engine's
pipeline (`InferenceEngineV2._inflight`), one counter bumped at the append:
the spans of one entry share it.

| span | arguments | what it covers |
|---|---|---|
| `plan` | `kind` (`window` \\| `step`), `seq` (of the entry it plans) | the scheduler's next step, or a decode window's host arrays: what `plan_s` times |
| `dispatch` | `kind` (`window` \\| `prefill` \\| `decode` \\| `spec_verify`), `W` (a window's iterations) or `T` (a step's tokens a row), `seq` | the enqueue of the entry's program: inside `dispatch_s` |
| `drain_block` | `kind` (`window` \\| `plan`), `seq` | the host blocked on the oldest entry's readback: what `drain_block_s` times |
| `commit` | `seq`, `depth` (entries in flight when it joined) | the entry's tokens into the sequences: what `commit_s` times |
| `replica_step` | none | `EngineBackend.step`'s own work after `engine.step()` returned (events packed, finished requests flushed): not round the engine's step, or every idle gap of a device trace would take its name |
| `admit` | `prompt` | `StateManager.admit` inside `put` |

`engine.stats`, the pipeline entry by entry (always on, counters only):

| key | what it counts |
|---|---|
| `entries_dispatched`, `inflight_depth_sum` | entries appended, and the sum over them of the entries already in flight: the mean is how far the host runs ahead of the device |
| `entries_committed`, `inflight_residence_s` | entries committed, and the sum of (end of commit - append): what a dispatched step spends in the pipeline |
| `prefill_entries_committed`, `prefill_residence_s` | the same two for prefill plans alone |
| `decode_tokens` | tokens generated by decode steps and by prefill steps' decode blocks (booked at dispatch) and decode windows (booked at commit) |
| `fused_steps`, `fused_decode_tokens`, `fused_empty_steps` | the decode block of a prefill step (the decode-ready rows as a `[max_seqs, 1]` segment of the same program): steps whose block carried a decode token, the tokens that left that way — in `decode_tokens` too, in NO `decode_steps` / `window_iters`: their device time is `jit_step_prefill`'s — and steps whose block had no live row; booked at dispatch beside `prefill_steps` |
| `replica_step_s`, `engine_step_s` | booked by `EngineBackend.step`: its own wall time, and `engine.step()`'s inside it |
| `kv_blocks_live_<kind>`, `kv_blocks_peak_<kind>` | by PAGED kind of layer (`full`: a table that grows; `window`: a bounded ring; `latent`: latent attention's ONE row `[c, k_r]` a token, a table that grows): blocks live sequences hold, sampled after every dispatch (`StateManager.sample`), and the run's peak |
| `ring_blocks_reused` | ring slots a page past the window overwrote in place (`StateManager.note_written`, booked at dispatch) |
| `attn_steps_live_<kind>`, `attn_steps_rect_<kind>` | `attn_steps_live` / `attn_steps_rect` split by kind of layer (each kind walks its own table) |
| `attn_pages_unclipped`, `attn_pages_clipped` | pool pages a table that grew with the context would have walked in the window layers, and those of them the window kind did not walk |
| `state_records_live`, `state_records_peak` | a RECORD kind's state (`conv`: the last `conv_taps - 1` inputs of a short convolution, one record a layer and slot, no pages): records live — one a sequence in a slot — sampled after every dispatch, and the run's peak; present only where the model has such layers |
| `latent_rows_written` | latent attention (`kv_lora_rank`): rows `[c, k_r]` the dispatched programs were to write, a token a row whatever the layers — a plan's live tokens and its decode block's, a window's scheduled iterations a slot (host arithmetic at dispatch); present only where the model's paged kind is `latent`. The kernel's steps are booked in `attn_steps_*` (and `attn_steps_*_latent`) as the K/V kernel's are; its device time and the absorb's are the scopes `attn_core` and `latent_absorb` (`W_dkv`, the latent's norm, the rope key, `W_uk` folded into the query, `W_uv` after the weighted sum). Since PR 60 a prefill CHUNK past the forms' break-even (`latent_prefill_breakeven`: 158 tokens at kanana-2's widths) runs EXPANDED — kernel `paged_latent_prefill` as before, under `attn_core`: each page up-projected in VMEM a head, no fold and no `W_uv` einsum round it — and every decode row stays absorbed; the engine's `paged:` line says which, with the head group. The benchmark's `prefill_attn_core_share` reads `attn_core` + `kv_stage` over the prefill step programs' device self time (`benchmark/layers/prefill_attn_core_share.py`, the five serving cells; `decode_attn_core_share` is its twin over the decode programs) |
| `conv_chunks`, `conv_chunks_carried` | prefill rows dispatched, and those of them whose first position is past 0: they started from the record their sequence's last chunk left, not from zeros (booked on the host at dispatch) |
| `moe_routed_rows`, `moe_padded_rows`, `attn_steps_*` | booked with the layers that HAVE the mechanism — the expert layers, the layers of each paged kind — not `num_layers` (a stack of unlike layers) |
| `moe_masked_rows` | (token, choice) entries of dispatched programs whose row carried no request — an empty slot of a window, a chunk's padding — and which the expert sort's liveness mask therefore left out: `(rows x iterations - live tokens) x top_k x expert layers`, host arithmetic at dispatch beside `moe_routed_rows` (a slot that meets its EOS inside a window is masked on the device from there on and still counted as routed) |

A replica worker that leaves logs one line from them: `pipeline: depth ...
residence ... ms over ... entries (prefill ... ms over ...); replica step
... % outside the engine`, where prefill steps ran `; fused: ... steps
carried ... decode tokens, ... carried none`, and with routed experts
`; experts: ... entries routed, ... masked out of the sort, ... buffer rows`.
"""


def render_metrics_doc(root: str) -> str:
    fams = collect_metric_families(root)
    lines = [
        "# Metric-family reference (auto-generated)",
        "",
        "Every `serving_*` / `telemetry_*` family emitted with a literal",
        "name in `deepspeed_tpu/`. Regenerate with",
        "`python bin/check_metric_names.py --write-doc`;",
        "`tests/test_repo_lint.py` fails when an emitted family is",
        "missing here (or a documented one is no longer emitted).",
        "",
        "| family | type | help | emitted in |",
        "|---|---|---|---|",
    ]
    for name in sorted(fams):
        e = fams[name]
        help_s = " ".join(e["help"].split()).replace("|", "\\|")
        lines.append(f"| `{name}` | {e['type']} | {help_s} "
                     f"| {e['file']} |")
    lines += ["", ENGINE_LOOP_DOC]
    return "\n".join(lines)


def check_metrics_doc(root: str) -> list[str]:
    """Drift test: every emitted family is documented, every documented
    family is still emitted."""
    doc_path = os.path.join(root, *DOC_FILE.split("/"))
    fams = collect_metric_families(root)
    if not os.path.exists(doc_path):
        return [f"{doc_path}:0: metric reference missing — run "
                f"bin/check_metric_names.py --write-doc"]
    with open(doc_path, encoding="utf-8") as f:
        doc = f.read()
    documented = set(re.findall(
        r"`((?:serving|telemetry)_[a-zA-Z0-9_:]+)`", doc))
    out = []
    for name in sorted(set(fams) - documented):
        out.append(f"{fams[name]['file']}:0: metric family {name!r} is "
                   f"emitted but not documented in {DOC_FILE} — run "
                   f"bin/check_metric_names.py --write-doc")
    for name in sorted(documented - set(fams)):
        out.append(f"{doc_path}:0: documented family {name!r} is no "
                   f"longer emitted anywhere — run "
                   f"bin/check_metric_names.py --write-doc")
    return out


def main(argv: list[str]) -> int:
    args = list(argv[1:])
    write_doc = "--write-doc" in args
    if write_doc:
        args.remove("--write-doc")
    root = args[0] if args else \
        os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    if write_doc:
        path = os.path.join(root, *DOC_FILE.split("/"))
        os.makedirs(os.path.dirname(path), exist_ok=True)
        with open(path, "w", encoding="utf-8") as f:
            f.write(render_metrics_doc(root))
        print(f"wrote {path}")
        return 0
    violations = check_repo(root) + check_metrics_doc(root) \
        + check_alert_rules(root)
    for v in violations:
        print(v)
    if violations:
        print(f"{len(violations)} metric tag/doc violation(s) found")
    return 1 if violations else 0


if __name__ == "__main__":
    sys.exit(main(sys.argv))
