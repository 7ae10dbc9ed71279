"""What every driver needs: paths, the device check, the compile counter,
the profiler switch, spans on the host's monotonic clock, percentiles."""

from __future__ import annotations

import contextlib
import json
import math
import os
import shutil
import sys
import time
from typing import Any, Dict, Iterator, List, Optional, Sequence

from trace_reduce import WINDOW_NAME

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
REPO_ROOT = os.path.dirname(BENCH_DIR)
OUT_ROOT = os.path.join(BENCH_DIR, "out")

NO_DEVICE_EXIT = 3


def load_json(path: str) -> Any:
    with open(path, encoding="utf-8") as fh:
        return json.load(fh)


def with_rehearsal_overrides(config: Dict[str, Any]) -> Dict[str, Any]:
    """The configuration at the tiny size of its ``rehearsal`` section."""
    config = dict(config)
    for key, value in config["rehearsal"].items():
        config[key] = ({**config[key], **value}
                       if isinstance(value, dict) else value)
    return config


def expected_label(p_positive: float, threshold: float, tolerance: float,
                   ) -> Optional[str]:
    """The label the reference's P(positive) stands for, or ``None`` where
    it is within ``tolerance`` of a boundary (0.5, and the Neutral
    threshold on either side) and so decides nothing."""
    margin = min(abs(p_positive - 0.5), abs(p_positive - threshold),
                 abs(p_positive - (1.0 - threshold)))
    if margin <= tolerance:
        return None
    if max(p_positive, 1.0 - p_positive) < threshold:
        return "Neutral"
    return "Positive" if p_positive > 0.5 else "Negative"


def note(**fields: Any) -> None:
    """One JSON object on an earlier line of standard output."""
    print(json.dumps(fields, default=str), flush=True)


def percentile(values: Sequence[float], q: float) -> float:
    """Nearest-rank percentile: the smallest value with at least ``q`` of
    the sample at or below it."""
    if not values:
        raise ValueError("percentile of an empty sample")
    ordered = sorted(values)
    rank = max(1, math.ceil(q * len(ordered)))
    return ordered[min(rank, len(ordered)) - 1]


def median(values: Sequence[float]) -> float:
    ordered = sorted(values)
    mid = len(ordered) // 2
    if len(ordered) % 2:
        return ordered[mid]
    return 0.5 * (ordered[mid - 1] + ordered[mid])


def require_devices(chips: int, rehearsal: bool) -> List[Any]:
    """The devices of this process, or exit without a result: a cell runs
    on a TPU with at least the chips it asks for, and on nothing else."""
    import jax

    try:
        devices = jax.devices()
    except RuntimeError as exc:
        print(f"perfbench: no JAX backend: {exc}", file=sys.stderr)
        raise SystemExit(NO_DEVICE_EXIT)
    platform = devices[0].platform
    if rehearsal:
        if len(devices) < chips:
            print(f"perfbench: rehearsal needs {chips} virtual device(s)",
                  file=sys.stderr)
            raise SystemExit(NO_DEVICE_EXIT)
        return devices
    if platform != "tpu" or len(devices) < chips:
        print(
            f"perfbench: the cell needs {chips} TPU chip(s); JAX has "
            f"{len(devices)} {platform} device(s)", file=sys.stderr,
        )
        raise SystemExit(NO_DEVICE_EXIT)
    return devices


def device_report(devices: Sequence[Any]) -> Dict[str, Any]:
    """``device`` of the result line, as JAX reports it.

    ``memory_peak_bytes`` is, on the fullest chip, ``peak_bytes_in_use``
    plus ``peak_bytes_reserved`` of ``memory_stats()``.  On this TPU
    runtime the first holds what the process allocated (parameters, staged
    batches, results) and the second the scratch set aside for the programs
    it runs; a step's temporaries live there, and the first alone reads
    0.31 GB while one 3.2 GB tensor of the step is live (PERF.md, PR 22).
    """
    peak = 0
    for dev in devices:
        stats = dev.memory_stats() or {}
        peak = max(peak, int(stats.get("peak_bytes_in_use", 0))
                   + int(stats.get("peak_bytes_reserved", 0)))
    return {
        "platform": devices[0].platform,
        "kind": devices[0].device_kind,
        "count": len(devices),
        "memory_peak_bytes": peak,
    }


class CompileLog:
    """Every XLA compilation of this process: when (monotonic clock) and
    how long, from ``jax.monitoring``.  A hit in the persistent cache is an
    event too, with the time it took to load."""

    EVENT = "/jax/core/compile/backend_compile_duration"

    def __init__(self) -> None:
        import jax.monitoring

        self.events: List[List[float]] = []
        jax.monitoring.register_event_duration_secs_listener(self._on_event)

    def _on_event(self, event: str, duration: float, **_: Any) -> None:
        if event == self.EVENT:
            self.events.append([time.monotonic(), float(duration)])

    def between(self, t0: float, t1: float) -> List[List[float]]:
        return [e for e in self.events if t0 <= e[0] <= t1]


class HostSpans:
    """Spans the harness records around its own calls into the program,
    on the monotonic clock (the program's telemetry spans use it too)."""

    def __init__(self) -> None:
        self.spans: List[Dict[str, Any]] = []

    def add(self, name: str, t_mono: float, dur_s: float) -> None:
        self.spans.append({"name": name, "t_mono": t_mono, "dur_s": dur_s})

    @contextlib.contextmanager
    def span(self, name: str) -> Iterator[None]:
        t0 = time.monotonic()
        try:
            yield
        finally:
            self.add(name, t0, time.monotonic() - t0)


def telemetry_spans(jsonl_path: str, ignore: Sequence[str] = ()) -> List[Dict]:
    """The program's own spans from a ``telemetry.jsonl`` it wrote, without
    the ``engine:<name>`` roots, which cover a whole run and label nothing."""
    out = []
    if not os.path.exists(jsonl_path):
        return out
    with open(jsonl_path, encoding="utf-8") as fh:
        for line in fh:
            event = json.loads(line)
            if (event.get("type") == "span" and event["name"] not in ignore
                    and not event["name"].startswith("engine:")):
                out.append({"name": event["name"], "t_mono": event["t_mono"],
                            "dur_s": event["dur_s"]})
    return out


class DeviceTrace:
    """One profiler trace of one region.  ``region()`` is the context the
    traced work runs in: it carries the window annotation that
    ``trace_reduce`` reads the window and the clock offset from."""

    def __init__(self, directory: str) -> None:
        self.directory = directory

    def start(self) -> None:
        import jax

        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0  # Python frames would swamp the file
        options.host_tracer_level = 1
        jax.profiler.start_trace(self.directory, profiler_options=options)

    def region(self):
        import jax

        return jax.profiler.TraceAnnotation(
            WINDOW_NAME, mono_ns=time.monotonic_ns())

    def stop(self) -> str:
        import glob

        import jax

        jax.profiler.stop_trace()
        found = sorted(glob.glob(os.path.join(
            self.directory, "plugins", "profile", "*", "*.xplane.pb")))
        if not found:
            raise RuntimeError(f"no .xplane.pb under {self.directory}")
        return found[-1]


def fresh_dir(path: str) -> str:
    shutil.rmtree(path, ignore_errors=True)
    os.makedirs(path)
    return path
