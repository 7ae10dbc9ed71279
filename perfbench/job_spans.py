"""A job part's ``telemetry.jsonl`` as the program wrote it: the instant of
its ``run_start`` event and its spans with start, length, thread and attrs,
all on the host's monotonic clock.  ``common.telemetry_spans`` keeps names
and intervals only, which is what labelling a gap needs; the metrics of a
job's edges and of its reader also need the events and the attrs (``seq``,
``pipeline``, ``rows``).  Shared by ``layer_metrics/{read_batch_ms,
job_head_ms,job_tail_ms}.py`` and ``tools/job_edges.py``."""

from __future__ import annotations

import json
import os
from typing import Any, Callable, Dict, List, Optional

import common

PIPELINE = "pipeline"  # the name `sentiment` gives its prefetch pipeline


def read_log(jsonl_path: str) -> Optional[Dict[str, Any]]:
    """``{"run_start": t_mono or None, "spans": [...]}`` of one job part, or
    ``None`` where it left no log.  A span is ``{"name", "t_mono", "dur_s",
    "end", "thread", "attrs"}``; spans come in the order they ended."""
    if not os.path.exists(jsonl_path):
        return None
    log: Dict[str, Any] = {"run_start": None, "spans": []}
    with open(jsonl_path, encoding="utf-8") as fh:
        for line in fh:
            event = json.loads(line)
            if event.get("type") == "span":
                log["spans"].append({
                    "name": event["name"], "t_mono": event["t_mono"],
                    "dur_s": event["dur_s"],
                    "end": event["t_mono"] + event["dur_s"],
                    "thread": event.get("thread"),
                    "attrs": event.get("attrs", {}),
                })
            elif event.get("name") == "run_start" and log["run_start"] is None:
                log["run_start"] = event["t_mono"]
    return log


def named(log: Dict[str, Any], name: str, **attrs: Any) -> List[Dict[str, Any]]:
    """The log's spans of one name whose attrs hold ``attrs``, by start."""
    found = [s for s in log["spans"] if s["name"] == name
             and all(s["attrs"].get(k) == v for k, v in attrs.items())]
    return sorted(found, key=lambda s: s["t_mono"])


def first_item(log: Dict[str, Any], name: str) -> Optional[Dict[str, Any]]:
    """The span ``name`` of the pipeline's first item (``seq`` 0), or
    ``None``: a program that does not number its items has no such span."""
    found = named(log, name, pipeline=PIPELINE, seq=0)
    return found[0] if found else None


def full_batch_reads(log: Dict[str, Any]) -> List[Dict[str, Any]]:
    """The pipeline's ``read`` spans at the full batch: within 20% of the
    most ``rows`` (a job's last batch is short and reads in less)."""
    reads = [s for s in named(log, "read", pipeline=PIPELINE)
             if "rows" in s["attrs"]]
    if not reads:
        return []
    most = max(s["attrs"]["rows"] for s in reads)
    return [s for s in reads if s["attrs"]["rows"] >= 0.8 * most]


def read_batch_ms(log: Dict[str, Any]) -> Optional[float]:
    """Median length (ms) of the job's full-batch ``read`` spans."""
    reads = full_batch_reads(log)
    return 1e3 * common.median([s["dur_s"] for s in reads]) if reads else None


def head_ms(log: Dict[str, Any]) -> Optional[float]:
    """``run_start`` to the end of the first item's ``h2d`` span (ms): from
    there the first program is on the device's queue."""
    h2d = first_item(log, "h2d")
    if h2d is None or log["run_start"] is None:
        return None
    return 1e3 * (h2d["end"] - log["run_start"])


def tail_ms(log: Dict[str, Any]) -> Optional[float]:
    """End of the last ``compute`` span (the device has nothing left of
    this job) to the end of ``manifest`` (ms)."""
    compute, manifest = named(log, "compute"), named(log, "manifest")
    if not compute or not manifest:
        return None
    return 1e3 * (manifest[-1]["end"] - max(s["end"] for s in compute))


def sentiment_logs(artifacts: Dict[str, Any]) -> List[Dict[str, Any]]:
    """The parsed log of the ``sentiment`` part of every job of the run."""
    logs = []
    for job in artifacts.get("jobs", ()):
        part = job["parts"].get("sentiment")
        log = part and read_log(os.path.join(part["dir"], "telemetry.jsonl"))
        if log:
            logs.append(log)
    return logs


def median_over_jobs(
    artifacts: Dict[str, Any],
    per_job: Callable[[Dict[str, Any]], Optional[float]],
) -> Optional[float]:
    """``per_job`` of every ``sentiment`` log, the median over the jobs
    that have the spans; ``None`` where none has (the program before it
    recorded them)."""
    values = [per_job(log) for log in sentiment_logs(artifacts)]
    values = [v for v in values if v is not None]
    return common.median(values) if values else None
