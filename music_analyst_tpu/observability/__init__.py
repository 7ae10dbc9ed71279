"""Crash forensics + stall classification + cross-run analytics.

Three pieces (see each module's docstring):

* :mod:`flight` — bounded telemetry ring dumped as ``flight_record.json``
  (thread stacks + vitals) on crash/signal/watchdog/deadline,
* :mod:`watchdog` — heartbeat monitor classifying hangs into the
  structured taxonomy (``backend_lost``, ``compile_hang``, ``stage_stall``,
  ``host_oom``, …),
* :mod:`report` — ``telemetry-report`` run-over-run aggregation.

Jax-free at import: safe before ``tests/conftest.py`` pins the platform
and in jax-free tools (``chip_smoke.py``'s parent).
"""

from music_analyst_tpu.observability.flight import (
    FlightRecorder,
    get_flight_recorder,
    install_flight_recorder,
)
from music_analyst_tpu.observability.report import (
    build_report,
    classify_error,
    load_run,
    render_report,
    run_telemetry_report,
)
from music_analyst_tpu.observability.watchdog import (
    TAXONOMY,
    HeartbeatWatchdog,
    beat,
    get_watchdog,
    resolve_watchdog_timeout,
    start_watchdog,
    stop_watchdog,
    watch,
)

__all__ = [
    "FlightRecorder",
    "get_flight_recorder",
    "install_flight_recorder",
    "build_report",
    "classify_error",
    "load_run",
    "render_report",
    "run_telemetry_report",
    "TAXONOMY",
    "HeartbeatWatchdog",
    "beat",
    "get_watchdog",
    "resolve_watchdog_timeout",
    "start_watchdog",
    "stop_watchdog",
    "watch",
]
