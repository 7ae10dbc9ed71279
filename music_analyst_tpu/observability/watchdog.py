"""Heartbeat watchdog: classify stalls instead of reporting bare timeouts.

A bare "timed out after 40s" is a *guess* encoded in an error string.
This module makes the guess structural: anything that can hang (a
prefetch stage fn, a first compile, a device readback, an engine's fold
loop) runs
inside a :func:`watch` scope carrying a **kind**, and a monitor thread
classifies any scope that stops beating into a taxonomy code:

========  ==================  =====================================
kind      taxonomy            typical owner
========  ==================  =====================================
stage     ``stage_stall``     ``runtime/prefetch.py`` stage fns
compile   ``compile_hang``    ``profiling/compile.py`` lower+compile
device    ``device_stall``    engine collect()/step dispatch paths
host      ``host_stall``      host-side loops (persong fold)
serve     ``serve_stall``     ``serving/batcher.py`` dispatch edge
========  ==================  =====================================

A trip emits a ``watchdog_trip`` telemetry event, records itself for the
run manifest (``telemetry/introspect.py``), and dumps a flight record
(``observability/flight.py``) — so the *artifact* carries the taxonomy,
and ``bench.py`` can put ``"error_kind": "compile_hang"`` in its error
line instead of a guess.  The monitor never kills anything: enforcement
(process timeouts) stays with the caller; classification lives here.

Disabled by default — ``--watchdog-timeout`` / ``$MUSICAAL_WATCHDOG_S``
turn it on (0 = off).  When no watchdog is active the module-level
:func:`watch` / :func:`beat` fast-path to no-ops, so instrumentation is
unconditional in the engines (the telemetry pattern).
"""

from __future__ import annotations

import math
import os
import threading
import time
from contextlib import contextmanager
from typing import Any, Dict, Iterator, List, Optional

from music_analyst_tpu.telemetry import get_telemetry

# kind -> taxonomy code.  Unknown kinds classify as "unknown_stall" so a
# typo'd kind still produces a structured (if unhelpful) code, never a
# crash in the monitor thread.
TAXONOMY: Dict[str, str] = {
    "stage": "stage_stall",
    "compile": "compile_hang",
    "device": "device_stall",
    "host": "host_stall",
    "serve": "serve_stall",
    "decode": "decode_stall",
    "router": "router_stall",
}


def resolve_watchdog_timeout(
    value: Any = None, default: float = 0.0
) -> float:
    """Resolve ``--watchdog-timeout``: explicit flag wins, then
    ``$MUSICAAL_WATCHDOG_S``, then ``default``.  0 disables.

    A malformed *explicit* value raises (usage error); a malformed env
    var falls back to the default — the watchdog is a diagnostic aid and
    must never be the thing that crashes a run before it starts
    (the ``bench.py`` ``_env_deadline`` rule).
    """
    if value is None:
        raw = os.environ.get("MUSICAAL_WATCHDOG_S", "").strip()
        if not raw:
            return float(default)
        try:
            parsed = float(raw)
        except ValueError:
            return float(default)
        if not math.isfinite(parsed) or parsed < 0:
            return float(default)
        return parsed  # an explicit env 0 DISABLES even over a default
    try:
        timeout = float(value)
    except (TypeError, ValueError):
        raise ValueError(
            f"watchdog timeout must be a number of seconds >= 0, got {value!r}"
        ) from None
    if not math.isfinite(timeout) or timeout < 0:
        raise ValueError(
            f"watchdog timeout must be finite and >= 0, got {timeout}"
        )
    return timeout


class _Task:
    """One active watched scope."""

    __slots__ = ("name", "kind", "timeout_s", "last_beat", "started",
                 "thread", "tripped")

    def __init__(self, name: str, kind: str, timeout_s: float) -> None:
        self.name = name
        self.kind = kind
        self.timeout_s = timeout_s
        self.last_beat = time.monotonic()
        self.started = self.last_beat
        self.thread = threading.current_thread().name
        self.tripped = False


class HeartbeatWatchdog:
    """Monitor thread classifying stale heartbeats into the taxonomy.

    Tasks are keyed by name: re-entering a name (a looped engine) simply
    refreshes the entry.  A trip fires once per silence — a later beat
    rearms the task, so a slow-but-alive scope trips again only if it
    goes silent again.
    """

    def __init__(
        self,
        timeout_s: float,
        poll_s: Optional[float] = None,
        dump_flight_record: bool = True,
    ) -> None:
        if timeout_s <= 0:
            raise ValueError(f"timeout_s must be > 0, got {timeout_s}")
        self.timeout_s = float(timeout_s)
        self.poll_s = poll_s or max(0.05, min(1.0, self.timeout_s / 4.0))
        self.dump_flight_record = dump_flight_record
        self._tasks: Dict[str, _Task] = {}
        self._lock = threading.Lock()
        self._stop = threading.Event()
        self._thread: Optional[threading.Thread] = None
        self.trips: List[Dict[str, Any]] = []

    # ----------------------------------------------------------- lifecycle

    def start(self) -> "HeartbeatWatchdog":
        if self._thread is None or not self._thread.is_alive():
            self._stop.clear()
            self._thread = threading.Thread(
                target=self._monitor, name="watchdog", daemon=True
            )
            self._thread.start()
        return self

    def stop(self) -> None:
        self._stop.set()
        thread = self._thread
        if thread is not None and thread.is_alive():
            thread.join(timeout=2.0)
        self._thread = None

    # ------------------------------------------------------------- scoping

    @contextmanager
    def watch(
        self, name: str, kind: str = "stage",
        timeout_s: Optional[float] = None,
    ) -> Iterator[_Task]:
        """Mark ``name`` active for the duration; stale ⇒ trip."""
        task = _Task(name, kind, timeout_s or self.timeout_s)
        with self._lock:
            self._tasks[name] = task
        try:
            yield task
        finally:
            with self._lock:
                if self._tasks.get(name) is task:
                    del self._tasks[name]

    def beat(self, name: str) -> None:
        """Refresh + rearm a named task's heartbeat."""
        with self._lock:
            task = self._tasks.get(name)
            if task is not None:
                task.last_beat = time.monotonic()
                task.tripped = False

    # ------------------------------------------------------------- monitor

    def _monitor(self) -> None:
        while not self._stop.wait(self.poll_s):
            now = time.monotonic()
            with self._lock:
                stale = [
                    t for t in self._tasks.values()
                    if not t.tripped and now - t.last_beat > t.timeout_s
                ]
                for t in stale:
                    t.tripped = True
            for task in stale:
                try:
                    self._trip(task, now)
                except Exception:
                    pass  # the monitor must outlive any reporting failure

    def _trip(self, task: _Task, now: float) -> None:
        taxonomy = TAXONOMY.get(task.kind, "unknown_stall")
        trip = {
            "task": task.name,
            "kind": task.kind,
            "taxonomy": taxonomy,
            "stalled_s": round(now - task.last_beat, 3),
            "timeout_s": task.timeout_s,
            "thread": task.thread,
            "t_wall": round(time.time(), 6),
        }
        self.trips.append(trip)
        get_telemetry().event("watchdog_trip", **trip)
        if self.dump_flight_record:
            from music_analyst_tpu.observability.flight import (
                get_flight_recorder,
            )

            get_flight_recorder().dump(
                reason="watchdog",
                taxonomy=taxonomy,
                detail=(
                    f"{task.name} (kind={task.kind}, thread={task.thread}) "
                    f"silent for {trip['stalled_s']}s "
                    f"(timeout {task.timeout_s}s)"
                ),
            )

    # ------------------------------------------------------------ readouts

    def last_trip(self) -> Optional[Dict[str, Any]]:
        return self.trips[-1] if self.trips else None

    def snapshot(self) -> Dict[str, Any]:
        """JSON-able state for the run manifest / flight record."""
        now = time.monotonic()
        with self._lock:
            active = [
                {
                    "task": t.name,
                    "kind": t.kind,
                    "thread": t.thread,
                    "since_beat_s": round(now - t.last_beat, 3),
                    "tripped": t.tripped,
                }
                for t in self._tasks.values()
            ]
        return {
            "timeout_s": self.timeout_s,
            "active": active,
            "trips": list(self.trips),
        }


# ------------------------------------------------------- process singleton

_ACTIVE: Optional[HeartbeatWatchdog] = None


def start_watchdog(timeout_s: Any = None) -> Optional[HeartbeatWatchdog]:
    """Start (or replace) the process watchdog.  ``timeout_s`` resolves
    via :func:`resolve_watchdog_timeout`; <= 0 leaves it disabled and
    returns None."""
    global _ACTIVE
    timeout = resolve_watchdog_timeout(timeout_s)
    if timeout <= 0:
        return None
    if _ACTIVE is not None:
        _ACTIVE.stop()
    _ACTIVE = HeartbeatWatchdog(timeout).start()
    return _ACTIVE


def stop_watchdog() -> None:
    global _ACTIVE
    if _ACTIVE is not None:
        _ACTIVE.stop()
        _ACTIVE = None


def get_watchdog() -> Optional[HeartbeatWatchdog]:
    return _ACTIVE


@contextmanager
def watch(
    name: str, kind: str = "stage", timeout_s: Optional[float] = None
) -> Iterator[Optional[_Task]]:
    """Module-level scope: no-op (None) when no watchdog is active, so
    engines instrument unconditionally — the telemetry enabled-flag
    pattern."""
    wd = _ACTIVE
    if wd is None:
        yield None
        return
    with wd.watch(name, kind=kind, timeout_s=timeout_s) as task:
        yield task


def beat(name: str) -> None:
    wd = _ACTIVE
    if wd is not None:
        wd.beat(name)
