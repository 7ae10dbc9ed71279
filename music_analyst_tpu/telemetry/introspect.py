"""Device/compile introspection + the run-manifest sink.

Everything here may import jax — it runs at run-scope exit or inside
``bench.py``'s measurement child, never at package import time (the test
harness must force ``JAX_PLATFORMS=cpu`` before the first jax import,
``tests/conftest.py``).

Compile visibility comes from ``jax.monitoring``: jax times every trace /
MLIR-lowering / backend-compile under ``/jax/core/compile/*_duration``
events (``jax/_src/dispatch.py``), and the persistent-compilation-cache
hit/miss counters ride the same bus.  One listener pair routes them into
the process registry; the manifest then reports XLA compile count/seconds
per run without wrapping any jax API.
"""

from __future__ import annotations

import json
import os
import subprocess
import sys
from typing import Any, Callable, Dict, List, Optional

from music_analyst_tpu.telemetry.core import Telemetry, get_telemetry

_LISTENERS_INSTALLED = False
# name -> () -> section (falsy = nothing to say): what a layer above
# telemetry adds to every run manifest, registered by that layer.
_SECTIONS: Dict[str, Callable[[], Any]] = {}
_GIT_DESCRIBE: Optional[str] = None
_GIT_PROBED = False


def install_jax_listeners() -> bool:
    """Route ``jax.monitoring`` events into the process registry.

    Idempotent; jax offers no per-listener deregistration, so the
    callbacks stay for the process lifetime and route to whatever the
    registry's current run is (disabled registries drop them).
    """
    global _LISTENERS_INSTALLED
    if _LISTENERS_INSTALLED:
        return True
    try:
        from jax import monitoring
    except Exception:  # pragma: no cover - jax always present in-repo
        return False

    def _on_event(event: str, **kwargs: Any) -> None:
        get_telemetry().record_jax_event(event)

    def _on_duration(event: str, duration: float, **kwargs: Any) -> None:
        get_telemetry().record_jax_event(event, duration)

    monitoring.register_event_listener(_on_event)
    monitoring.register_event_duration_secs_listener(_on_duration)
    _LISTENERS_INSTALLED = True
    return True


def register_manifest_section(name: str, section: Callable[[], Any]) -> None:
    """Have ``section()`` written under ``name`` in every run manifest of
    this process, whenever it returns something.  For the layers telemetry
    may not import (``serving``, the weight store): they call this when
    they are imported, so the arrow points down."""
    _SECTIONS[name] = section


def git_describe() -> Optional[str]:
    """``git describe --always --dirty`` of the repo, cached per process."""
    global _GIT_DESCRIBE, _GIT_PROBED
    if _GIT_PROBED:
        return _GIT_DESCRIBE
    _GIT_PROBED = True
    repo_root = os.path.dirname(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    )
    try:
        out = subprocess.run(
            ["git", "describe", "--always", "--dirty"],
            cwd=repo_root, capture_output=True, text=True, timeout=10,
        )
        if out.returncode == 0:
            _GIT_DESCRIBE = out.stdout.strip() or None
    except Exception:
        _GIT_DESCRIBE = None
    return _GIT_DESCRIBE


def peak_rss_bytes() -> Optional[int]:
    try:
        import resource

        # Linux reports ru_maxrss in KiB.
        return resource.getrusage(resource.RUSAGE_SELF).ru_maxrss * 1024
    except Exception:  # pragma: no cover - non-POSIX
        return None


def device_summary() -> Optional[Dict[str, Any]]:
    """Platform / kinds / count of this process's devices, or None when
    no backend is live here (an HTTP-passthrough server must not claim a
    chip just to describe it)."""
    import jax
    # Private API: jax 0.9.0 has no public way to ask without
    # initialising (`jax.extend.backend.backends()` starts them).  If a
    # JAX upgrade moves it, this import fails loudly and
    # tests/test_router.py's backend-free-parent test says so.
    from jax._src import xla_bridge

    if not xla_bridge.backends_are_initialized():
        return None
    return _describe(jax.devices())


def _describe(devices) -> Dict[str, Any]:
    return {
        "platform": devices[0].platform if devices else "unknown",
        "count": len(devices),
        "kinds": sorted({d.device_kind for d in devices}),
    }


def collect_device_info() -> Dict[str, Any]:
    """Platform, device count, and per-device ``memory_stats()`` where the
    plugin exposes them (TPU does; CPU-emulated meshes return None)."""
    import jax

    devices = jax.devices()
    per_device: List[Optional[Dict[str, Any]]] = []
    for d in devices:
        stats = None
        try:
            stats = d.memory_stats()
        except Exception:
            stats = None
        per_device.append(stats)
    return {**_describe(devices), "memory_stats": per_device}


def write_run_manifest(
    tel: Telemetry, directory: str, wall_seconds: float = 0.0
) -> str:
    """Write ``<directory>/run_manifest.json`` from the registry's state.

    The manifest is the one-glance answer to "what ran, where, and what
    did it cost": CLI argv, device platform/count/memory, mesh shape (when
    an engine annotated one), jax/jaxlib versions, git describe, peak RSS,
    XLA compile count/seconds, and the final counter/gauge/histogram/span
    aggregates.
    """
    import jax
    import jaxlib

    install_jax_listeners()
    with tel._lock:
        context = dict(tel.context)
        counters = dict(tel.counters)
        gauges = dict(tel.gauges)
        histograms = {k: h.as_dict() for k, h in tel.histograms.items()}
        jax_events = {
            k: {"count": int(n), "seconds": round(t, 6)}
            for k, (n, t) in sorted(tel.jax_events.items())
        }
        events = tel.events
        pipelines = dict(tel.pipelines)
    manifest: Dict[str, Any] = {
        "schema": 1,
        "engine": context.pop("engine", None),
        "argv": list(sys.argv[1:]),
        "wall_seconds": round(wall_seconds, 6),
        "python_version": sys.version.split()[0],
        "jax_version": jax.__version__,
        "jaxlib_version": jaxlib.__version__,
        "git_describe": git_describe(),
        # A process that must not initialise a backend (the replica
        # router) annotates the section itself, from its workers' stats.
        "device": context.pop("device", None) or collect_device_info(),
        "peak_rss_bytes": peak_rss_bytes(),
        "compile": tel.compile_stats(),
        "jax_events": jax_events,
        "context": context,
        "counters": counters,
        "gauges": gauges,
        "histograms": histograms,
        "spans": tel.top_spans(n=20),
        "pipeline": pipelines,
        "event_count": events,
        "telemetry_log": tel.sink_path,
    }
    # An unclean previous shutdown (journal without its clean marker, or
    # a stale non-drain flight record) is the same class of headline
    # fact: hoisted so telemetry-report and operators see it at a glance,
    # absent on runs that started clean.
    if context.get("unclean_shutdown"):
        manifest["unclean_shutdown"] = True
        if "unclean_witness" in context:
            manifest["unclean_witness"] = context["unclean_witness"]
    try:
        # Fault-injection + retry digest (resilience/): per-site trips and
        # per-site retry/recovery counts — only when something tripped or
        # retried, so fault-free runs keep the original key set.
        from music_analyst_tpu.resilience import fault_stats, retry_stats

        faults = fault_stats()
        # attempts bumps on every guarded call; a site earns a manifest
        # row only once it actually retried / recovered / gave up.
        retries = {
            site: counts
            for site, counts in retry_stats().items()
            if counts.get("retries") or counts.get("gave_up")
        }
        if faults or retries:
            resilience: Dict[str, Any] = {}
            if faults:
                resilience["faults"] = faults
            if retries:
                resilience["retries"] = retries
            manifest["resilience"] = resilience
    except Exception:
        pass
    try:
        # Persistent-corpus-cache hit/miss/bytes-saved — process-lifetime,
        # like the XLA cache stats; only present once the cache has been
        # consulted, so cache-free runs keep the original key set.
        from music_analyst_tpu.data.corpus_cache import cache_stats

        corpus_stats = cache_stats()
        if any(corpus_stats.values()):
            manifest["corpus_cache"] = corpus_stats
    except Exception:
        pass
    try:
        # Process-lifetime compile records (memoized engine callables
        # outlive a single run) — guarded so a jax-free manifest path or
        # a partial install never blocks the write.
        from music_analyst_tpu.profiling.compile import compile_records

        manifest["profiling"] = {
            "scope": "process",
            "compiles": compile_records(),
        }
    except Exception:
        pass
    # What the layers above say of themselves (the server's snapshot, the
    # weight store's cache and load digest): each registered its section
    # when it was imported, so a run that never loaded the layer pays no
    # import for it here and keeps the original key set.
    for name, section in list(_SECTIONS.items()):
        try:
            value = section()
            if value:
                manifest[name] = value
        except Exception:
            pass
    try:
        # Request-trace recorder digest + tail exemplars: quantile trace
        # ids a reader can resolve against request_traces.jsonl — only
        # when tracing was enabled, so untraced runs keep the key set.
        from music_analyst_tpu.telemetry.reqtrace import get_reqtrace

        rt = get_reqtrace()
        if rt.enabled:
            manifest["reqtrace"] = rt.stats()
            exemplars = rt.exemplars()
            if exemplars:
                manifest["trace_exemplars"] = exemplars
    except Exception:
        pass
    try:
        # Metrics plane digest (observability/metrics_plane.py): series
        # counters, active burn-rate alerts, fleet merge — only when
        # sampling was on, so unmetered runs keep the key set.
        from music_analyst_tpu.observability.metrics_plane import (
            get_metrics_plane,
        )

        plane = get_metrics_plane()
        if plane.enabled:
            manifest["metrics"] = plane.snapshot()
    except Exception:
        pass
    try:
        # Watchdog verdicts + flight-record pointer — only when there is
        # something to say, so unwatched runs keep the original key set.
        from music_analyst_tpu.observability.flight import get_flight_recorder
        from music_analyst_tpu.observability.watchdog import get_watchdog

        obs: Dict[str, Any] = {}
        wd = get_watchdog()
        if wd is not None:
            obs["watchdog"] = wd.snapshot()
        rec = get_flight_recorder()
        if rec.last_dump_path:
            obs["flight_record"] = rec.last_dump_path
        if obs:
            manifest["observability"] = obs
    except Exception:
        pass
    os.makedirs(directory, exist_ok=True)
    path = os.path.join(directory, "run_manifest.json")
    with open(path, "w", encoding="utf-8") as fh:
        json.dump(manifest, fh, indent=2, default=str)
        fh.write("\n")
    return path
