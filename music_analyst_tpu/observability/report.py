"""Cross-run telemetry analytics: ``telemetry-report``.

PR 1 gave each run a telemetry dir, PR 2 a perf gate between *two* runs;
this reads *across* runs: bench driver captures (``BENCH_r*.json``), raw
bench JSON lines, and telemetry run dirs (``run_manifest.json`` +
``telemetry.jsonl`` + ``flight_record.json``) aggregate into one
run-over-run report — metric trajectory, error-taxonomy histogram,
stall/queue-depth breakdown, recompile counts.

Two classification sources, newest-wins:

* explicit ``error_kind`` (bench lines written after this PR carry the
  watchdog's verdict; flight records carry ``taxonomy``), else
* :func:`classify_error`, a pattern table over error strings and
  process tails, for captures that carry no explicit verdict.

Exit codes follow ``profiling/diff.py``: 0 = newest run healthy, 1 = the
newest run failed (the report names its taxonomy), 2 = no usable input.
Jax-free by design — it must run on a host with no usable backend.
"""

from __future__ import annotations

import json
import os
from typing import Any, Dict, List, Optional

# Ordered pattern table: first match wins.  ``backend_lost`` is a backend
# that was working and went away mid-run (the router's verdict on a dead
# replica, a transport that dropped).  Failing to *get* a backend —
# "Unable to initialize backend", UNAVAILABLE at start-up: no chip, or a
# chip held by another process — deliberately matches nothing here: it
# is ``unknown_error``, never transient, never retried.
_ERROR_PATTERNS = (
    ("backend_lost", ("backend lost",)),
    ("fault_injected", ("fault injected", "injectedfault", "injectedfatal")),
    ("host_oom", (
        "memoryerror", "out of memory", "cannot allocate memory",
        "oom-kill",
    )),
    ("compile_hang", (
        "compile timed out", "compile hang", "compile stall",
        "stuck compiling",
    )),
    ("stage_stall", ("stage stall", "stage_stall")),
    ("serve_stall", ("serve stall", "serve_stall", "serve.dispatch")),
    ("decode_stall", ("decode stall", "decode_stall", "decode.dispatch")),
    ("router_stall", ("router stall", "router_stall", "router.dispatch",
                      "replica lost", "replica_lost")),
    ("deadline_expired", ("deadline",)),
    ("unclean_shutdown", ("unclean shutdown", "unclean_shutdown",
                          "journal without clean marker")),
    ("harness_killed", ("killed by harness", "sigkill")),
)


def classify_error(
    message: Optional[str], rc: Optional[int] = None
) -> Optional[str]:
    """Map a legacy error string (and/or exit code) to a taxonomy code.

    Returns None for "no error" (empty message with a zero rc); a
    nonempty message that matches nothing classifies as
    ``unknown_error`` — the histogram should show *that* the run failed
    even when it cannot say why.
    """
    text = (message or "").lower()
    for kind, needles in _ERROR_PATTERNS:
        if any(needle in text for needle in needles):
            return kind
    if rc == 124:  # coreutils `timeout` — the driver's outer kill
        return "harness_killed"
    if "timed out" in text or "timeout" in text:
        return "attempt_timeout"
    if text:
        return "unknown_error"
    if rc not in (None, 0):
        return "unknown_error"
    return None


# ---------------------------------------------------------------- loading


def _label(source: str) -> str:
    base = os.path.basename(os.path.normpath(source))
    return base[:-5] if base.endswith(".json") else base


def _bench_line_record(
    payload: Dict[str, Any], label: str, rc: Optional[int] = None
) -> Dict[str, Any]:
    error = payload.get("error")
    kind = payload.get("error_kind") or classify_error(error, rc)
    return {
        "label": label,
        "kind": "bench",
        "ok": kind is None,
        "metric": payload.get("metric"),
        "value": payload.get("value"),
        "unit": payload.get("unit"),
        "error": error,
        "error_kind": kind,
        "flight_record": payload.get("flight_record"),
        "telemetry": payload.get("telemetry"),
    }


def _capture_record(payload: Dict[str, Any], label: str) -> Dict[str, Any]:
    """A driver capture: {"n", "cmd", "rc", "tail", "parsed"}."""
    rc = payload.get("rc")
    parsed = payload.get("parsed")
    if isinstance(parsed, dict):
        rec = _bench_line_record(parsed, label, rc)
        rec["rc"] = rc
        return rec
    # No bench line survived: classify the process tail.
    kind = classify_error(payload.get("tail"), rc) or "unknown_error"
    return {
        "label": label,
        "kind": "bench",
        "ok": False,
        "metric": None,
        "value": None,
        "error": f"no bench line (rc={rc})",
        "error_kind": kind,
        "rc": rc,
    }


def _scan_jsonl(path: str) -> Dict[str, Any]:
    """Cheap single pass over a telemetry.jsonl: event count, watchdog
    trips, and the resilience events (injected faults, retries,
    recoveries, failovers) keyed by site."""
    events = 0
    trips: List[Dict[str, Any]] = []
    faults: Dict[str, int] = {}
    retries: Dict[str, int] = {}
    recoveries: Dict[str, int] = {}
    failovers: Dict[str, int] = {}
    with open(path, "r", encoding="utf-8") as fh:
        for line in fh:
            line = line.strip()
            if not line:
                continue
            events += 1
            try:
                event = json.loads(line)
            except json.JSONDecodeError:
                continue
            name = event.get("name")
            attrs = event.get("attrs") or {}
            site = attrs.get("site", "?")
            if name == "watchdog_trip":
                trips.append(attrs)
            elif name == "fault_injected":
                faults[site] = faults.get(site, 0) + 1
            elif name == "retry":
                retries[site] = retries.get(site, 0) + 1
            elif name == "retry_recovered":
                recoveries[site] = recoveries.get(site, 0) + 1
            elif name == "failover_retry":
                failovers[site] = failovers.get(site, 0) + 1
            elif name == "serving_failover":  # batcher reload — no site attr
                failovers["serving.dispatch"] = (
                    failovers.get("serving.dispatch", 0) + 1
                )
    return {
        "events": events,
        "trips": trips,
        "faults": faults,
        "retries": retries,
        "recoveries": recoveries,
        "failovers": failovers,
    }


# Headline series the cross-run trajectory tracks (first→last per run).
# These are the fleet-health numbers an operator graphs first; the full
# series stays in metrics.jsonl for anything deeper.
_METRICS_HEADLINES = (
    "requests.rates.req_s",
    "requests.rates.shed_s",
    "decode.rates.tokens_s",
    "requests.admitted",
    "requests.shed",
)

# The alert-record fields worth carrying into the cross-run history.
_ALERT_FIELDS = (
    "alert", "state", "tenant", "t", "burn_fast", "burn_slow",
    "threshold", "trace_id",
)


def _scan_metrics_jsonl(path: str) -> Dict[str, Any]:
    """Single pass over a ``metrics.jsonl`` (observability/metrics_plane):
    sample count + time span, first→last of each headline series, and
    every burn-rate alert record."""
    samples = 0
    t_first: Optional[float] = None
    t_last: Optional[float] = None
    first: Dict[str, float] = {}
    last: Dict[str, float] = {}
    alerts: List[Dict[str, Any]] = []
    try:
        with open(path, "r", encoding="utf-8") as fh:
            for line in fh:
                line = line.strip()
                if not line:
                    continue
                try:
                    rec = json.loads(line)
                except json.JSONDecodeError:
                    continue
                if not isinstance(rec, dict):
                    continue
                if rec.get("type") == "alert":
                    alerts.append(
                        {k: rec.get(k) for k in _ALERT_FIELDS}
                    )
                    continue
                if rec.get("type") != "sample":
                    continue
                samples += 1
                t = rec.get("t")
                if isinstance(t, (int, float)):
                    t_first = t if t_first is None else t_first
                    t_last = t
                flat = rec.get("metrics") or {}
                for key in _METRICS_HEADLINES:
                    value = flat.get(key)
                    if isinstance(value, (int, float)):
                        first.setdefault(key, value)
                        last[key] = value
    except OSError:
        return {"summary": None, "alerts": []}
    summary: Optional[Dict[str, Any]] = None
    if samples:
        summary = {
            "samples": samples,
            "span_s": (
                round(t_last - t_first, 6)
                if t_first is not None and t_last is not None else None
            ),
            "series": {
                key: {"first": first.get(key), "last": last[key]}
                for key in last
            },
        }
    return {"summary": summary, "alerts": alerts}


_LEDGER_FIELDS = (
    "goodput_fraction", "coverage", "engine_wall_s", "ticks",
    "tokens_committed", "ledger_drops",
)


def _ledger_summary(ledger: Dict[str, Any],
                    records: int = 0) -> Optional[Dict[str, Any]]:
    """Compact digest of one engine-ledger snapshot (engine_ledger.py's
    ``snapshot()`` shape); None when the engine never ticked."""
    if not isinstance(ledger, dict) or not ledger.get("ticks"):
        return None
    out: Dict[str, Any] = {k: ledger.get(k) for k in _LEDGER_FIELDS}
    out["records"] = records
    fractions = ledger.get("fractions")
    if isinstance(fractions, dict):
        out["fractions"] = dict(fractions)
    chip = ledger.get("chip_seconds")
    if isinstance(chip, dict):
        out["chip_seconds"] = dict(chip)
    return out


def _scan_ledger_jsonl(path: str) -> Dict[str, Any]:
    """Single pass over ``engine_ledger.jsonl``.  Each record is a
    CUMULATIVE snapshot, so the last one IS the run's final ledger;
    earlier goodput fractions form the within-run trajectory."""
    final: Optional[Dict[str, Any]] = None
    records = 0
    goodput_first: Optional[float] = None
    try:
        with open(path, "r", encoding="utf-8") as fh:
            for line in fh:
                line = line.strip()
                if not line:
                    continue
                try:
                    rec = json.loads(line)
                except json.JSONDecodeError:
                    continue
                if not isinstance(rec, dict) or rec.get("type") != "ledger":
                    continue
                ledger = rec.get("ledger")
                if not isinstance(ledger, dict):
                    continue
                records += 1
                final = ledger
                g = ledger.get("goodput_fraction")
                if goodput_first is None and isinstance(g, (int, float)):
                    goodput_first = g
    except OSError:
        return {"summary": None}
    summary = _ledger_summary(final, records) if final else None
    if summary is not None and goodput_first is not None:
        summary["goodput_first"] = goodput_first
    return {"summary": summary}


def _dir_record(directory: str, label: str) -> Optional[Dict[str, Any]]:
    """A telemetry run dir: manifest + JSONL + optional flight record."""
    manifest_path = os.path.join(directory, "run_manifest.json")
    jsonl_path = os.path.join(directory, "telemetry.jsonl")
    flight_path = os.path.join(directory, "flight_record.json")
    rec: Dict[str, Any] = {
        "label": label, "kind": "run_dir", "ok": True,
        "error": None, "error_kind": None,
    }
    found = False
    if os.path.exists(manifest_path):
        found = True
        try:
            with open(manifest_path, "r", encoding="utf-8") as fh:
                manifest = json.load(fh)
        except (json.JSONDecodeError, OSError):
            manifest = {}
        counters = manifest.get("counters") or {}
        compile_info = manifest.get("compile") or {}
        rec.update(
            engine=manifest.get("engine"),
            wall_seconds=manifest.get("wall_seconds"),
            compile_count=compile_info.get("count"),
            compile_seconds=compile_info.get("seconds"),
            recompiles=int(counters.get("profiling.recompiles", 0)),
            pipeline=manifest.get("pipeline") or {},
        )
        obs = manifest.get("observability") or {}
        trips = (obs.get("watchdog") or {}).get("trips") or []
        if trips:
            rec["trips"] = trips
        # Histogram quantile summaries (p50/p95/p99) — serving latency
        # first and foremost, but any quantile-bearing histogram shows.
        quantiles: Dict[str, Dict[str, Any]] = {}
        for name, hist in (manifest.get("histograms") or {}).items():
            if isinstance(hist, dict) and hist.get("p50_s") is not None:
                quantiles[name] = {
                    k: hist.get(k) for k in ("p50_s", "p95_s", "p99_s")
                }
        if quantiles:
            rec["latency_quantiles"] = quantiles
        serving = manifest.get("serving")
        if serving:
            rec["serving"] = serving
        # Tail-sampled trace exemplars (telemetry/reqtrace.py): quantile
        # trace ids that dereference into request_traces.jsonl.
        exemplars = manifest.get("trace_exemplars")
        if exemplars:
            rec["trace_exemplars"] = exemplars
        resilience = manifest.get("resilience")
        if resilience:
            rec["resilience"] = resilience
        # A run that started after an unclean predecessor (SIGKILL, cord
        # pull): the *previous* run's failure, witnessed by this one's
        # journal scan — reported without failing this run.
        if manifest.get("unclean_shutdown"):
            rec["unclean_shutdown"] = True
            rec["unclean_witness"] = manifest.get("unclean_witness")
    if os.path.exists(jsonl_path):
        found = True
        scan = _scan_jsonl(jsonl_path)
        rec["events"] = scan["events"]
        if scan["trips"]:
            rec.setdefault("trips", [])
            rec["trips"] = scan["trips"]  # JSONL is ground truth
        for key in ("faults", "retries", "recoveries", "failovers"):
            if scan[key]:
                rec.setdefault("resilience_events", {})[key] = scan[key]
    metrics_path = os.path.join(directory, "metrics.jsonl")
    if os.path.exists(metrics_path):
        found = True
        scan = _scan_metrics_jsonl(metrics_path)
        if scan["summary"]:
            rec["metrics"] = scan["summary"]
        if scan["alerts"]:
            rec["alerts"] = scan["alerts"]
    ledger_path = os.path.join(directory, "engine_ledger.jsonl")
    if os.path.exists(ledger_path):
        found = True
        scan = _scan_ledger_jsonl(ledger_path)
        if scan["summary"]:
            rec["engine_ledger"] = scan["summary"]
    if "engine_ledger" not in rec:
        # No JSONL (flush disarmed) — the manifest's final decode stats
        # still carry the ledger snapshot.
        manifest_ledger = (
            ((rec.get("serving") or {}).get("decode") or {}).get("ledger")
        )
        summary = _ledger_summary(manifest_ledger or {})
        if summary is not None:
            rec["engine_ledger"] = summary
    if os.path.exists(flight_path):
        found = True
        try:
            with open(flight_path, "r", encoding="utf-8") as fh:
                flight = json.load(fh)
            rec["flight_record"] = flight_path
            rec["error_kind"] = (
                flight.get("taxonomy")
                or classify_error(flight.get("detail"))
                or "unknown_error"
            )
            rec["error"] = flight.get("detail") or flight.get("reason")
            rec["ok"] = False
        except (json.JSONDecodeError, OSError):
            pass
    if rec.get("trips") and rec.get("error_kind") is None:
        rec["error_kind"] = rec["trips"][-1].get("taxonomy", "unknown_error")
        rec["error"] = f"watchdog tripped on {rec['trips'][-1].get('task')}"
        rec["ok"] = False
    return rec if found else None


def load_run(source: str) -> Optional[Dict[str, Any]]:
    """Normalize one source (file or dir) into a run record, or None."""
    label = _label(source)
    if os.path.isdir(source):
        return _dir_record(source, label)
    if not os.path.exists(source):
        return None
    try:
        with open(source, "r", encoding="utf-8") as fh:
            payload = json.load(fh)
    except (json.JSONDecodeError, OSError):
        return None
    if not isinstance(payload, dict):
        return None
    if "parsed" in payload and "rc" in payload:
        return _capture_record(payload, label)
    if "metric" in payload and "value" in payload:
        return _bench_line_record(payload, label)
    if "schema" in payload and "reason" in payload:  # bare flight record
        return {
            "label": label, "kind": "flight", "ok": False,
            "error": payload.get("detail") or payload.get("reason"),
            "error_kind": payload.get("taxonomy") or "unknown_error",
            "flight_record": source,
        }
    return None


# -------------------------------------------------------------- reporting


def build_report(records: List[Dict[str, Any]]) -> Dict[str, Any]:
    """Aggregate normalized run records (oldest→newest input order)."""
    taxonomy: Dict[str, int] = {}
    trajectory: List[Dict[str, Any]] = []
    stalls: List[Dict[str, Any]] = []
    recompiles: Dict[str, int] = {}
    latencies: List[Dict[str, Any]] = []
    resilience_sites: Dict[str, Dict[str, int]] = {}
    router_fleet: List[Dict[str, Any]] = []
    speculation_runs: List[Dict[str, Any]] = []
    metrics_runs: List[Dict[str, Any]] = []
    alert_history: List[Dict[str, Any]] = []
    ledger_runs: List[Dict[str, Any]] = []
    chip_seconds_by_tenant: Dict[str, float] = {}

    def _site(site: str) -> Dict[str, int]:
        return resilience_sites.setdefault(
            site,
            {"trips": 0, "retries": 0, "recoveries": 0,
             "gave_up": 0, "failovers": 0},
        )

    for rec in records:
        if rec.get("error_kind"):
            taxonomy[rec["error_kind"]] = taxonomy.get(rec["error_kind"], 0) + 1
        if rec.get("metric") is not None:
            trajectory.append({
                "label": rec["label"],
                "metric": rec["metric"],
                "value": rec.get("value"),
                "ok": rec["ok"],
            })
        if rec.get("recompiles"):
            recompiles[rec["label"]] = rec["recompiles"]
        for name, q in (rec.get("latency_quantiles") or {}).items():
            entry = {
                "label": rec["label"],
                "name": name,
                "p50_s": q.get("p50_s"),
                "p95_s": q.get("p95_s"),
                "p99_s": q.get("p99_s"),
            }
            # Attach the matching trace exemplars so "p99 is slow" comes
            # with a trace id to pull the waterfall for.
            exemplar = (rec.get("trace_exemplars") or {}).get(name)
            if isinstance(exemplar, dict):
                entry["exemplars"] = {
                    p: exemplar[p]
                    for p in ("p50", "p95", "p99") if p in exemplar
                }
            latencies.append(entry)
        for name, pipe in (rec.get("pipeline") or {}).items():
            for stage in pipe.get("stages") or []:
                if stage.get("stall_s") or stage.get("queue_depth_max"):
                    stalls.append({
                        "label": rec["label"],
                        "pipeline": name,
                        "stage": stage.get("stage"),
                        "stall_s": stage.get("stall_s"),
                        "queue_depth_max": stage.get("queue_depth_max"),
                    })
        # Per-site fault/retry/failover rollup.  The manifest's digest is
        # authoritative where present; JSONL event counts fill in for
        # dirs whose run died before the manifest landed.
        resilience = rec.get("resilience") or {}
        scanned = rec.get("resilience_events") or {}
        for site, info in (resilience.get("faults") or {}).items():
            _site(site)["trips"] += int(info.get("trips", 0))
        for site, info in (resilience.get("retries") or {}).items():
            entry = _site(site)
            entry["retries"] += int(info.get("retries", 0))
            entry["recoveries"] += int(info.get("recoveries", 0))
            entry["gave_up"] += int(info.get("gave_up", 0))
        if not resilience:
            for site, n in (scanned.get("faults") or {}).items():
                _site(site)["trips"] += int(n)
            for site, n in (scanned.get("retries") or {}).items():
                _site(site)["retries"] += int(n)
            for site, n in (scanned.get("recoveries") or {}).items():
                _site(site)["recoveries"] += int(n)
        for site, n in (scanned.get("failovers") or {}).items():
            _site(site)["failovers"] += int(n)
        # Scale-out serving: per-replica rollup of the manifest's
        # serving.router section (serving/router.py stats()).
        router = (rec.get("serving") or {}).get("router")
        if router:
            router_fleet.append({
                "label": rec["label"],
                "replica_count": router.get("replica_count"),
                "healthy_count": router.get("healthy_count"),
                "dispatched": router.get("dispatched"),
                "requeued": router.get("requeued"),
                "shed": router.get("shed"),
                "respawned": router.get("respawns"),
                "health_transitions": len(
                    router.get("health_transitions") or []
                ),
                "replicas": {
                    name: {
                        "dispatched": snap.get("dispatched"),
                        "requeues": snap.get("requeues"),
                        "respawns": snap.get("respawns"),
                        "health": snap.get("health"),
                    }
                    for name, snap in (router.get("replicas") or {}).items()
                },
            })
        # Speculative decoding: per-run acceptance digest from the
        # manifest's serving.decode.speculation section (decode_loop
        # stats()), rolled up into cross-run quantiles below.
        spec = ((rec.get("serving") or {}).get("decode") or {}).get(
            "speculation"
        ) or {}
        # Metrics-plane trajectory + burn-rate alert history (scanned
        # from metrics.jsonl by _dir_record above).
        metrics = rec.get("metrics")
        if metrics:
            metrics_runs.append({"label": rec["label"], **metrics})
        for alert in rec.get("alerts") or []:
            alert_history.append({"label": rec["label"], **alert})
        # Engine goodput ledger: per-run attribution digest (scanned from
        # engine_ledger.jsonl, or the manifest's serving.decode.ledger)
        # → cross-run goodput trajectory + fleet chip-second totals.
        ledger = rec.get("engine_ledger")
        if ledger:
            ledger_runs.append({"label": rec["label"], **ledger})
            for tenant, secs in (ledger.get("chip_seconds") or {}).items():
                if isinstance(secs, (int, float)):
                    chip_seconds_by_tenant[tenant] = round(
                        chip_seconds_by_tenant.get(tenant, 0.0) + secs, 6
                    )
        if spec.get("enabled"):
            speculation_runs.append({
                "label": rec["label"],
                "k": spec.get("k"),
                "dispatches": spec.get("dispatches"),
                "plain_ticks": spec.get("plain_ticks"),
                "fallbacks": spec.get("fallbacks"),
                "acceptance_rate": spec.get("acceptance_rate"),
                "accepted_tokens_per_dispatch": spec.get(
                    "accepted_tokens_per_dispatch"
                ),
            })

    def _quantiles(values: List[Any]) -> Optional[Dict[str, Any]]:
        vals = sorted(
            float(v) for v in values if isinstance(v, (int, float))
        )
        if not vals:
            return None

        def q(p: float) -> float:
            return vals[min(len(vals) - 1, int(round(p * (len(vals) - 1))))]

        return {"n": len(vals), "p50": q(0.5), "p95": q(0.95),
                "max": vals[-1]}

    speculation = {
        "runs": speculation_runs,
        "acceptance_rate": _quantiles(
            [r["acceptance_rate"] for r in speculation_runs]
        ),
        "accepted_tokens_per_dispatch": _quantiles(
            [r["accepted_tokens_per_dispatch"] for r in speculation_runs]
        ),
    }
    newest = records[-1] if records else None
    return {
        "schema": 1,
        "runs": records,
        "n_runs": len(records),
        "n_failed": sum(1 for r in records if not r["ok"]),
        "metric_trajectory": trajectory,
        "taxonomy_histogram": dict(
            sorted(taxonomy.items(), key=lambda kv: (-kv[1], kv[0]))
        ),
        "stalls": stalls,
        "recompiles": recompiles,
        "latency_quantiles": latencies,
        "resilience": dict(sorted(resilience_sites.items())),
        "router_fleet": router_fleet,
        "speculation": speculation,
        "metrics_runs": metrics_runs,
        "alert_history": alert_history,
        "ledger_runs": ledger_runs,
        "chip_seconds_by_tenant": dict(
            sorted(chip_seconds_by_tenant.items(),
                   key=lambda kv: (-kv[1], kv[0]))
        ),
        "newest": {
            "label": newest["label"],
            "ok": newest["ok"],
            "error_kind": newest.get("error_kind"),
        } if newest else None,
    }


def render_report(report: Dict[str, Any]) -> List[str]:
    """The human-facing text rendering (one line list, print-ready)."""
    lines = [
        f"telemetry-report: {report['n_runs']} run(s), "
        f"{report['n_failed']} failed"
    ]
    if report["metric_trajectory"]:
        lines.append("metric trajectory:")
        for point in report["metric_trajectory"]:
            value = point["value"]
            shown = f"{value:.1f}" if isinstance(value, (int, float)) else "-"
            flag = "" if point["ok"] else "  [FAILED]"
            lines.append(
                f"  {point['label']}: {point['metric']} = {shown}{flag}"
            )
    if report["taxonomy_histogram"]:
        lines.append("error taxonomy:")
        width = max(len(k) for k in report["taxonomy_histogram"])
        for kind, n in report["taxonomy_histogram"].items():
            lines.append(f"  {kind.ljust(width)}  {'#' * n} ({n})")
    if report["stalls"]:
        lines.append("pipeline stalls (stall_s / queue_depth_max):")
        for s in report["stalls"]:
            lines.append(
                f"  {s['label']} {s['pipeline']}.{s['stage']}: "
                f"{s['stall_s']} / {s['queue_depth_max']}"
            )
    if report["recompiles"]:
        lines.append("recompiles:")
        for label, n in report["recompiles"].items():
            lines.append(f"  {label}: {n}")
    if report.get("latency_quantiles"):
        lines.append("latency quantiles (p50/p95/p99 s):")
        for q in report["latency_quantiles"]:
            def _fmt(value: Any) -> str:
                return (f"{value:.6f}"
                        if isinstance(value, (int, float)) else "-")
            lines.append(
                f"  {q['label']} {q['name']}: "
                f"{_fmt(q['p50_s'])} / {_fmt(q['p95_s'])} / "
                f"{_fmt(q['p99_s'])}"
            )
            exemplars = q.get("exemplars") or {}
            if exemplars:
                shown = " ".join(
                    f"{p}={exemplars[p].get('trace_id')}"
                    for p in ("p50", "p95", "p99") if p in exemplars
                )
                lines.append(f"    trace exemplars: {shown}")
    if report.get("resilience"):
        lines.append(
            "fault/retry recovery (trips / retries / recoveries / "
            "gave_up / failovers):"
        )
        width = max(len(site) for site in report["resilience"])
        for site, c in report["resilience"].items():
            lines.append(
                f"  {site.ljust(width)}  {c['trips']} / {c['retries']} / "
                f"{c['recoveries']} / {c['gave_up']} / {c['failovers']}"
            )
    if report.get("router_fleet"):
        lines.append(
            "router fleet (per replica: dispatched / requeues / health):"
        )
        for fleet in report["router_fleet"]:
            lines.append(
                f"  {fleet['label']}: {fleet['replica_count']} replica(s), "
                f"{fleet['dispatched']} dispatched, "
                f"{fleet['requeued']} requeued, "
                f"{fleet['respawned'] or 0} respawned, "
                f"{fleet['health_transitions']} health transition(s)"
            )
            for name, snap in (fleet["replicas"] or {}).items():
                lines.append(
                    f"    {name}: {snap['dispatched']} / "
                    f"{snap['requeues']} / {snap['health']}"
                )
    speculation = report.get("speculation") or {}
    if speculation.get("runs"):
        lines.append(
            "speculative decoding (k / tok-per-dispatch / acceptance / "
            "fallbacks):"
        )

        def _num(value: Any) -> str:
            return (f"{value:.2f}"
                    if isinstance(value, (int, float)) else "-")

        for run in speculation["runs"]:
            lines.append(
                f"  {run['label']}: k={run['k']}, "
                f"{_num(run['accepted_tokens_per_dispatch'])} / "
                f"{_num(run['acceptance_rate'])} / "
                f"{run['fallbacks'] or 0}"
            )
        for key, title in (
            ("acceptance_rate", "acceptance rate"),
            ("accepted_tokens_per_dispatch", "accepted tokens/dispatch"),
        ):
            quants = speculation.get(key)
            if quants:
                lines.append(
                    f"  {title} across {quants['n']} run(s): "
                    f"p50={_num(quants['p50'])} p95={_num(quants['p95'])} "
                    f"max={_num(quants['max'])}"
                )
    if report.get("metrics_runs"):
        lines.append("metrics plane (headline series, first -> last):")

        def _mnum(value: Any) -> str:
            return (f"{value:.2f}"
                    if isinstance(value, (int, float)) else "-")

        for run in report["metrics_runs"]:
            span = run.get("span_s")
            span_text = (f" over {span:.1f}s"
                         if isinstance(span, (int, float)) else "")
            lines.append(
                f"  {run['label']}: {run['samples']} sample(s){span_text}"
            )
            for key, point in sorted((run.get("series") or {}).items()):
                lines.append(
                    f"    {key}: {_mnum(point.get('first'))} -> "
                    f"{_mnum(point.get('last'))}"
                )
    if report.get("alert_history"):
        lines.append("burn-rate alert history:")
        for alert in report["alert_history"]:
            tenant = (f" tenant={alert['tenant']}"
                      if alert.get("tenant") else "")
            trace = (f" trace={alert['trace_id']}"
                     if alert.get("trace_id") else "")
            lines.append(
                f"  {alert['label']} {alert.get('alert')}{tenant}: "
                f"{alert.get('state')} "
                f"burn {alert.get('burn_fast')}x/{alert.get('burn_slow')}x "
                f"(threshold {alert.get('threshold')}x){trace}"
            )
    if report.get("ledger_runs"):
        lines.append("engine ledger (goodput trajectory):")

        def _lnum(value: Any) -> str:
            return (f"{value:.2f}"
                    if not isinstance(value, bool)
                    and isinstance(value, (int, float)) else "-")
        for run in report["ledger_runs"]:
            fractions = run.get("fractions") or {}
            wall = run.get("engine_wall_s")
            wall_text = (f" wall={wall:.2f}s"
                         if isinstance(wall, (int, float)) else "")
            drops = run.get("ledger_drops") or 0
            drops_text = f" drops={drops}" if drops else ""
            lines.append(
                f"  {run['label']}: goodput={_lnum(run.get('goodput_fraction'))} "
                f"prefill={_lnum(fractions.get('prefill'))} "
                f"spec_waste={_lnum(fractions.get('spec_waste'))} "
                f"idle={_lnum(fractions.get('idle_bubble'))} "
                f"coverage={_lnum(run.get('coverage'))}"
                f"{wall_text}{drops_text}"
            )
        if report.get("chip_seconds_by_tenant"):
            lines.append("chip-seconds by tenant (all runs):")
            total = sum(
                v for v in report["chip_seconds_by_tenant"].values()
                if isinstance(v, (int, float))
            )
            for tenant, secs in report["chip_seconds_by_tenant"].items():
                share = (f" ({secs / total:.0%})"
                         if total and isinstance(secs, (int, float)) else "")
                lines.append(f"  {tenant:<16} {_lnum(secs)}s{share}")
    newest = report.get("newest")
    if newest is not None:
        verdict = ("ok" if newest["ok"]
                   else f"FAILED ({newest['error_kind']})")
        lines.append(f"newest run {newest['label']}: {verdict}")
    return lines


def run_telemetry_report(
    sources: List[str], json_output: bool = False
) -> int:
    """CLI entry.  Exit 0 = newest healthy, 1 = newest failed, 2 = no
    usable input — diff.py's gate semantics, so CI can chain them."""
    import sys

    records: List[Dict[str, Any]] = []
    skipped: List[str] = []
    for source in sources:
        rec = load_run(source)
        if rec is None:
            skipped.append(source)
        else:
            records.append(rec)
    for source in skipped:
        print(f"telemetry-report: skipping unusable source: {source}",
              file=sys.stderr)
    if not records:
        print("telemetry-report: no usable runs among "
              f"{len(sources)} source(s)", file=sys.stderr)
        return 2
    report = build_report(records)
    if json_output:
        print(json.dumps(report, default=str))
    else:
        for line in render_report(report):
            print(line)
    return 0 if report["newest"]["ok"] else 1


# ----------------------------------------------------------- trace-report
#
# ``trace-report`` reconstructs cross-process request waterfalls from the
# per-process records in ``request_traces.jsonl`` (telemetry/reqtrace.py:
# each process that handled a kept request appended ONE line with its
# spans).  Records sharing a ``trace_id`` are one request's journey; the
# ``parent`` span pointer links a replica worker's record back to the
# router front end's record.  Jax-free, like telemetry-report.

from music_analyst_tpu.telemetry.reqtrace import (  # noqa: E402  (jax-free)
    PHASE_NAMES,
    TRACE_FILE,
)

_MAX_RENDERED_TRACES = 20


def _iter_trace_files(source: str) -> List[str]:
    """A source is a trace .jsonl itself, or a directory holding
    ``request_traces*.jsonl`` (the profile dir)."""
    if os.path.isdir(source):
        out = []
        try:
            names = sorted(os.listdir(source))
        except OSError:
            return []
        stem = TRACE_FILE[: -len(".jsonl")]
        for name in names:
            if name.startswith(stem) and name.endswith(".jsonl"):
                out.append(os.path.join(source, name))
        return out
    if source.endswith(".jsonl") and os.path.exists(source):
        return [source]
    return []


def _alert_trace_ids(source: str) -> List[str]:
    """Trace ids named by burn-rate alert records in an alert file
    (``metrics.jsonl``, or any JSONL of ``type == "alert"`` records from
    observability/metrics_plane.py).  Directories, non-JSONL files, and
    files without alert records return [] — they are trace sources, not
    alert sources."""
    if not os.path.isfile(source) or not source.endswith((".jsonl", ".json")):
        return []
    ids: List[str] = []
    try:
        with open(source, "r", encoding="utf-8") as fh:
            for line in fh:
                line = line.strip()
                if not line:
                    continue
                try:
                    rec = json.loads(line)
                except json.JSONDecodeError:
                    continue
                if (isinstance(rec, dict) and rec.get("type") == "alert"
                        and isinstance(rec.get("trace_id"), str)):
                    ids.append(rec["trace_id"])
    except OSError:
        return []
    return ids


def load_trace_records(sources: List[str]) -> List[Dict[str, Any]]:
    """Every parseable trace record across all sources, input order."""
    records: List[Dict[str, Any]] = []
    for source in sources:
        for path in _iter_trace_files(source):
            try:
                with open(path, "r", encoding="utf-8") as fh:
                    for line in fh:
                        line = line.strip()
                        if not line:
                            continue
                        try:
                            rec = json.loads(line)
                        except json.JSONDecodeError:
                            continue
                        if (isinstance(rec, dict)
                                and isinstance(rec.get("trace_id"), str)
                                and isinstance(rec.get("spans"), list)):
                            records.append(rec)
            except OSError:
                continue
    return records


def _phase_spans(record: Dict[str, Any]) -> List[Dict[str, Any]]:
    return [
        s for s in record.get("spans") or []
        if isinstance(s, dict) and s.get("cat") == "phase"
        and s.get("name") in PHASE_NAMES
        and isinstance(s.get("t"), (int, float))
        and isinstance(s.get("dur"), (int, float))
    ]


def _span_extent(record: Dict[str, Any]) -> Optional[float]:
    phases = _phase_spans(record)
    if not phases:
        return None
    t0 = min(s["t"] for s in phases)
    t1 = max(s["t"] + s["dur"] for s in phases)
    return max(t1 - t0, 0.0)


def _pick_root(records: List[Dict[str, Any]]) -> Dict[str, Any]:
    """The request's entry process: a record with no parent span, else
    the one whose admit phase starts earliest (a journal-replay record
    points at a crashed predecessor whose line may never have landed)."""
    roots = [r for r in records if not r.get("parent")]
    pool = roots or records

    def admit_t(rec: Dict[str, Any]) -> float:
        starts = [
            s["t"] for s in _phase_spans(rec) if s["name"] == "admit"
        ]
        if starts:
            return min(starts)
        phases = _phase_spans(rec)
        return min((s["t"] for s in phases), default=float("inf"))

    return min(pool, key=admit_t)


def build_waterfall(records: List[Dict[str, Any]]) -> Dict[str, Any]:
    """One trace id's records → waterfall + critical-path attribution.

    Attribution uses the ROOT record's phase spans only: by construction
    (the cursor partition in reqtrace.py) they tile the root process's
    wall time, so their shares of the wire latency are exact and sum to
    the coverage figure.  Child records (replica workers) show up both
    as the root's ``downstream`` phase and, nested, as their own
    per-phase breakdown under ``downstream/``.
    """
    root = _pick_root(records)
    phases = _phase_spans(root)
    wire = root.get("wire_s")
    if not isinstance(wire, (int, float)) or wire < 0:
        wire = _span_extent(root)
    phase_seconds: Dict[str, float] = {}
    for span in phases:
        phase_seconds[span["name"]] = (
            phase_seconds.get(span["name"], 0.0) + span["dur"]
        )
    covered = sum(phase_seconds.values())
    coverage = (covered / wire) if wire else None
    attribution = {
        name: {
            "seconds": round(seconds, 6),
            "share": round(seconds / wire, 4) if wire else None,
        }
        for name, seconds in sorted(
            phase_seconds.items(), key=lambda kv: -kv[1]
        )
    }
    children = [
        r for r in records
        if r is not root and r.get("parent") == root.get("span")
    ]
    downstream: Dict[str, Any] = {}
    for child in children:
        breakdown: Dict[str, float] = {}
        for span in _phase_spans(child):
            breakdown[span["name"]] = (
                breakdown.get(span["name"], 0.0) + span["dur"]
            )
        downstream[f"{child.get('role', 'worker')}:{child.get('span')}"] = {
            name: round(seconds, 6)
            for name, seconds in sorted(
                breakdown.items(), key=lambda kv: -kv[1]
            )
        }
    phase_names = {s["name"] for s in phases}
    complete = (
        "admit" in phase_names
        and "reply" in phase_names
        and isinstance(wire, (int, float)) and wire is not None
    )
    out: Dict[str, Any] = {
        "trace_id": root["trace_id"],
        "complete": complete,
        "wire_s": round(wire, 6) if isinstance(wire, (int, float)) else None,
        "coverage": round(coverage, 4) if coverage is not None else None,
        "kept": root.get("kept"),
        "op": root.get("op"),
        "tenant": root.get("tenant"),
        "role": root.get("role"),
        "n_records": len(records),
        "attribution": attribution,
        "records": records,
    }
    if downstream:
        out["downstream"] = downstream
    dropped = sum(int(r.get("spans_dropped") or 0) for r in records)
    if dropped:
        out["spans_dropped"] = dropped
    return out


def build_trace_report(records: List[Dict[str, Any]]) -> Dict[str, Any]:
    by_id: Dict[str, List[Dict[str, Any]]] = {}
    for rec in records:
        by_id.setdefault(rec["trace_id"], []).append(rec)
    traces = [build_waterfall(recs) for recs in by_id.values()]
    traces.sort(key=lambda t: (t["wire_s"] is None, -(t["wire_s"] or 0.0)))
    complete = [t for t in traces if t["complete"]]
    kept_reasons: Dict[str, int] = {}
    for t in traces:
        reason = t.get("kept") or "?"
        kept_reasons[reason] = kept_reasons.get(reason, 0) + 1
    return {
        "schema": 1,
        "n_traces": len(traces),
        "n_complete": len(complete),
        "n_records": len(records),
        "kept_reasons": dict(
            sorted(kept_reasons.items(), key=lambda kv: (-kv[1], kv[0]))
        ),
        "traces": traces,
    }


def render_trace_report(report: Dict[str, Any]) -> List[str]:
    """Waterfall text: one block per trace (slowest first), each span on
    its own line offset-aligned to the trace's start."""

    def _pct(value: Any) -> str:
        return f"{value * 100.0:.1f}%" if isinstance(value, float) else "-"

    lines = [
        f"trace-report: {report['n_traces']} trace(s) "
        f"({report['n_complete']} complete) from "
        f"{report['n_records']} process record(s)"
    ]
    alert_filter = report.get("alert_filter")
    if alert_filter:
        lines.append(
            f"alert filter: {alert_filter['n_alert_records']} alert "
            f"record(s) -> {len(alert_filter['trace_ids'])} trace id(s)"
        )
    if report["kept_reasons"]:
        shown = ", ".join(
            f"{k}={n}" for k, n in report["kept_reasons"].items()
        )
        lines.append(f"kept: {shown}")
    for trace in report["traces"][:_MAX_RENDERED_TRACES]:
        wire = trace["wire_s"]
        wire_text = f"{wire:.6f}s" if isinstance(wire, float) else "?"
        flag = "" if trace["complete"] else "  [INCOMPLETE]"
        lines.append(
            f"trace {trace['trace_id']}: wire {wire_text}, "
            f"coverage {_pct(trace['coverage'])}, kept={trace['kept']}, "
            f"{trace['n_records']} process(es){flag}"
        )
        starts = [
            s["t"]
            for rec in trace["records"]
            for s in rec.get("spans") or []
            if isinstance(s.get("t"), (int, float))
        ]
        t_zero = min(starts) if starts else 0.0
        for rec in sorted(
            trace["records"],
            key=lambda r: min(
                (s["t"] for s in _phase_spans(r)), default=float("inf")
            ),
        ):
            depth = 0 if not rec.get("parent") else 1
            pad = "  " * (depth + 1)
            lines.append(
                f"{pad}[{rec.get('role', '?')} pid={rec.get('pid')}] "
                f"span={rec.get('span')}"
            )
            for span in sorted(
                rec.get("spans") or [], key=lambda s: s.get("t", 0.0)
            ):
                mark = "·" if span.get("cat") == "detail" else "█"
                lines.append(
                    f"{pad}  {mark} {span['name']:<14} "
                    f"+{span['t'] - t_zero:.6f}s  {span['dur']:.6f}s"
                )
        shares = " | ".join(
            f"{name} {_pct(info['share'])}"
            for name, info in trace["attribution"].items()
        )
        if shares:
            lines.append(f"  attribution: {shares}")
        for child, breakdown in (trace.get("downstream") or {}).items():
            inner = ", ".join(
                f"{name}={seconds:.6f}s"
                for name, seconds in breakdown.items()
            )
            lines.append(f"  downstream {child}: {inner}")
    hidden = report["n_traces"] - min(
        report["n_traces"], _MAX_RENDERED_TRACES
    )
    if hidden > 0:
        lines.append(f"... {hidden} more trace(s) not shown")
    return lines


def run_trace_report(sources: List[str], json_output: bool = False) -> int:
    """CLI entry.  Exit 0 = at least one complete waterfall, 1 = traces
    found but none complete, 2 = no usable input — the 0/1/2 gate
    semantics telemetry-report and profile-diff already use.

    A source holding burn-rate alert records (``metrics.jsonl``) is an
    *alert* source: its named ``trace_id``s become a filter, and the
    trace records are pulled from the alert file's own directory — so
    "the pager fired" resolves straight to the breaching waterfalls.
    """
    import sys

    alert_records = 0
    wanted: set = set()
    trace_sources: List[str] = []
    for source in sources:
        ids = _alert_trace_ids(source)
        if ids:
            alert_records += len(ids)
            wanted.update(ids)
            trace_sources.append(
                os.path.dirname(os.path.abspath(source))
            )
        else:
            trace_sources.append(source)
    records = load_trace_records(trace_sources)
    if wanted:
        records = [r for r in records if r["trace_id"] in wanted]
    if not records:
        print(
            f"trace-report: no trace records among {len(sources)} "
            "source(s) (expected request_traces*.jsonl lines"
            + (" matching the alert trace ids" if wanted else "")
            + ")",
            file=sys.stderr,
        )
        return 2
    report = build_trace_report(records)
    if wanted:
        report["alert_filter"] = {
            "n_alert_records": alert_records,
            "trace_ids": sorted(wanted),
        }
    if json_output:
        print(json.dumps(report, default=str))
    else:
        for line in render_trace_report(report):
            print(line)
    return 0 if report["n_complete"] else 1
