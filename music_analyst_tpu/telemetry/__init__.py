"""Unified run telemetry: spans, counters, JSONL event log, run manifest.

Usage (every engine follows this shape):

    from music_analyst_tpu.telemetry import get_telemetry

    tel = get_telemetry()
    with tel.run_scope("wordcount", output_dir):      # owns the sinks
        with tel.span("ingest") as sp:
            ...
            sp.set(bytes=n_bytes)
        tel.count("songs_ingested", n)

Artifacts (when a sink directory resolves — ``--telemetry-dir`` or the
engine's output dir): ``telemetry.jsonl`` (append-only, one event per
line) and ``run_manifest.json`` (device/compile/version/counter digest).
Schemas are documented in PERFORMANCE.md §"How to read a run".
"""

from music_analyst_tpu.telemetry.core import (
    DEFAULT_BUCKETS,
    Histogram,
    Span,
    Telemetry,
    configure,
    get_telemetry,
)
from music_analyst_tpu.telemetry.introspect import (
    collect_device_info,
    git_describe,
    install_jax_listeners,
    register_manifest_section,
    write_run_manifest,
)

__all__ = [
    "DEFAULT_BUCKETS",
    "Histogram",
    "Span",
    "Telemetry",
    "configure",
    "get_telemetry",
    "collect_device_info",
    "git_describe",
    "install_jax_listeners",
    "register_manifest_section",
    "write_run_manifest",
]
