"""Backend failover: one structured re-init-and-retry, then fail.

The watchdog taxonomy (PR 4) can *name* a lost backend or a stalled
device; this module is what *acts* on the name.  An engine wraps its
device-dependent block in :func:`run_with_failover`:

1. the block runs; on success nothing else happens;
2. a failure classified as mid-run backend loss (``backend_lost`` /
   ``device_stall`` / a transient injected fault) triggers ONE re-init of
   the backend (caller-supplied ``reinit``) and one retry;
3. if that also fails the error propagates and the run exits non-zero.

There is no host-side compute path to finish on: a run that exits 0 did
its device work on the device.  A backend that cannot *initialise* is
not backend loss — it is never classified transient and never retried
(``observability/report.py``).
"""

from __future__ import annotations

from typing import Any, Callable, Optional

from music_analyst_tpu.resilience.policy import classify_retryable
from music_analyst_tpu.telemetry import get_telemetry

# Kinds that mean "the backend, not the program": worth a re-init.
FAILOVER_KINDS = frozenset(
    {"backend_lost", "device_stall", "fault_injected"}
)


def should_failover(exc: BaseException) -> bool:
    """True when ``exc`` reads as recoverable backend loss."""
    retryable, kind = classify_retryable(exc)
    return retryable and kind in FAILOVER_KINDS


def run_with_failover(
    fn: Callable[[], Any],
    *,
    site: str,
    reinit: Optional[Callable[[], None]] = None,
) -> Any:
    """Run ``fn``; on classified backend loss re-init and retry once.

    Anything not classified as backend loss — and any
    :class:`InjectedFatal` — propagates unchanged so logic errors keep
    failing fast; so does a second failure after the re-init.
    """
    tel = get_telemetry()
    try:
        return fn()
    except Exception as exc:
        if not should_failover(exc):
            raise
        _, kind = classify_retryable(exc)
        tel.count(f"failover.{site}.retries")
        tel.event(
            "failover_retry",
            site=site,
            kind=kind,
            error=str(exc)[:200],
        )
        if reinit is not None:
            try:
                reinit()
            except Exception as reinit_exc:
                tel.event(
                    "failover_reinit_failed",
                    site=site,
                    error=str(reinit_exc)[:200],
                )
        result = fn()
        tel.count(f"failover.{site}.recoveries")
        tel.event("failover_recovered", site=site)
        return result
