"""Device-time capture: profiler traces + a span-level Chrome trace.

Promoted from the old ``metrics/tracing.py`` (shimmed through PR 3,
removed in PR 4).  Two granularities:

* :func:`maybe_trace` / :func:`annotate` — the raw ``jax.profiler``
  capture (HLO timelines, per-op device time) for TensorBoard/Perfetto,
  unchanged semantics from the old module;
* :func:`profile_run` — the ``--profile-dir`` flag's backing: wraps a run
  in ``jax.profiler`` (a profiler that will not start fails the run —
  a profile that was asked for and silently not taken is worse than no
  run) **and** renders this run's telemetry spans into
  ``<dir>/trace_spans.json``, a self-contained Chrome-trace artifact
  (``chrome://tracing`` / Perfetto), and, beside a device trace,
  ``<dir>/op_scopes.json``: which ``jax.named_scope`` path each device
  operation of each compiled program was traced under
  (``profiling.compile.op_scopes``), to read the trace by scope.

Timing discipline: wall timings everywhere end in a host readback
(``np.asarray``) at the engines' sync points — the host needs the bytes
there anyway, and the readback waits for the device.
"""

from __future__ import annotations

import contextlib
import json
import os
from typing import Any, Dict, Iterator, List, Optional


@contextlib.contextmanager
def maybe_trace(trace_dir: Optional[str]) -> Iterator[None]:
    """Capture a ``jax.profiler`` trace into ``trace_dir`` when set."""
    if not trace_dir:
        yield
        return
    import jax

    with jax.profiler.trace(trace_dir):
        yield


@contextlib.contextmanager
def annotate(name: str) -> Iterator[None]:
    """Named region that shows up on the profiler timeline."""
    import jax

    with jax.profiler.TraceAnnotation(name):
        yield


def spans_to_chrome_trace(tel) -> Dict[str, Any]:
    """Render a registry's recorded spans as Chrome-trace JSON.

    Complete events (``ph: "X"``) on the monotonic clock, one ``tid`` per
    thread name; span attributes ride along in ``args``.  Raw spans cap at
    the registry's in-memory bound, so huge runs render their head — the
    aggregate table in the manifest stays exact.
    """
    with tel._lock:
        spans = list(tel.spans)
    if spans:
        base = min(sp.t_mono for sp in spans)
    else:
        base = 0.0
    tids: Dict[str, int] = {}
    events: List[Dict[str, Any]] = []
    for sp in spans:
        tid = tids.setdefault(sp.thread, len(tids) + 1)
        event: Dict[str, Any] = {
            "name": sp.name,
            "ph": "X",
            "ts": round((sp.t_mono - base) * 1e6, 3),
            "dur": round(sp.duration_s * 1e6, 3),
            "pid": 1,
            "tid": tid,
        }
        if sp.attrs:
            event["args"] = {k: str(v) for k, v in sp.attrs.items()}
        events.append(event)
    events.extend(
        {"name": "thread_name", "ph": "M", "pid": 1, "tid": tid,
         "args": {"name": thread}}
        for thread, tid in tids.items()
    )
    return {"traceEvents": events, "displayTimeUnit": "ms"}


def write_chrome_trace(tel, path: str) -> str:
    payload = spans_to_chrome_trace(tel)
    os.makedirs(os.path.dirname(path) or ".", exist_ok=True)
    with open(path, "w", encoding="utf-8") as fh:
        json.dump(payload, fh)
        fh.write("\n")
    return path


@contextlib.contextmanager
def profile_run(
    profile_dir: Optional[str], device_trace: bool = True
) -> Iterator[None]:
    """``--profile-dir``: device profiler capture + span Chrome trace.

    A ``jax.profiler`` that cannot start raises: the caller asked for a
    device trace.  ``device_trace=False`` is for a process that must not
    touch the backend (the replica-router parent — starting the profiler
    initialises every backend, and only the process that holds a chip can
    trace it); it still gets the span-level ``trace_spans.json``, which
    is rendered purely from host-side telemetry.  A device trace gets
    ``op_scopes.json`` beside it: the trace names operations as XLA does
    (``fusion.315``), the file says under which scope each was traced.
    """
    if not profile_dir:
        yield
        return
    from music_analyst_tpu.telemetry import get_telemetry

    tel = get_telemetry()
    os.makedirs(profile_dir, exist_ok=True)
    if device_trace:
        import jax

        jax.profiler.start_trace(profile_dir)
    try:
        yield
    finally:
        try:
            if device_trace:
                jax.profiler.stop_trace()
                from music_analyst_tpu.profiling.compile import op_scopes

                with open(os.path.join(profile_dir, "op_scopes.json"), "w",
                          encoding="utf-8") as fh:
                    json.dump(op_scopes(), fh)
        finally:
            write_chrome_trace(
                tel, os.path.join(profile_dir, "trace_spans.json")
            )
