"""The one retry policy: exponential backoff, full jitter, cap, budget.

Before this module, every recovery loop in the tree was ad-hoc — the
Ollama client slept ``0.5 * 2**attempt`` with no jitter, no cap, and no
awareness that the bench deadline could not fit another attempt.  All
retrying now goes through :class:`RetryPolicy`:

- **Full jitter** (AWS-style): sleep ``uniform(0, min(cap, base·2^k))``.
  Correlated retries are how transient congestion becomes persistent
  congestion; jitter decorrelates them.
- **Deadline-aware**: never sleeps past the armed process deadline
  (bench.py arms it at suite dispatch via ``benchmarks._util``), and
  gives up immediately when the remaining budget cannot fit the next
  sleep — sleeping into a deadline converts a retryable error into a
  SIGTERM with no structured line.
- **Watchdog-aware**: a retry sleep inside a watched scope counts as
  silence, so sleeps are clamped below the active watchdog timeout.
- **Classified**: retryability reuses ``observability/report.py``'s
  error taxonomy; only transiently-classified failures (a backend
  lost mid-run, device stalls, timeouts, injected transient faults,
  OS-level I/O hiccups) are retried.  Logic errors — and a backend
  that cannot initialise — propagate on the first throw.
"""

from __future__ import annotations

import threading
import time
from typing import Any, Callable, Dict, Optional, Tuple

from music_analyst_tpu.observability.report import classify_error
from music_analyst_tpu.resilience.faults import InjectedFatal, InjectedFault
from music_analyst_tpu.telemetry import get_telemetry

# Taxonomy kinds worth another attempt: the failure is in the transport /
# device layer, not the program.
_TRANSIENT_KINDS = frozenset(
    {"backend_lost", "device_stall", "attempt_timeout", "fault_injected"}
)

# OSError subtypes that are verdicts about the input, not the transport.
_PERMANENT_OS_ERRORS = (
    FileNotFoundError,
    IsADirectoryError,
    NotADirectoryError,
    PermissionError,
)


def classify_retryable(exc: BaseException) -> Tuple[bool, Optional[str]]:
    """(retryable?, taxonomy kind) for an exception.

    Injected faults carry their verdict in their type; everything else is
    classified from its rendered message exactly the way telemetry-report
    would classify the run's death.
    """
    if isinstance(exc, InjectedFatal):
        return False, "fault_injected"
    if isinstance(exc, InjectedFault):
        return True, "fault_injected"
    kind = classify_error(f"{type(exc).__name__}: {exc}")
    if kind in _TRANSIENT_KINDS:
        return True, kind
    if isinstance(exc, (TimeoutError, ConnectionError)):
        return True, kind or "attempt_timeout"
    if isinstance(exc, OSError) and not isinstance(exc, _PERMANENT_OS_ERRORS):
        return True, kind
    return False, kind


# --- process retry deadline -------------------------------------------------
#
# Armed once per process (bench.py at suite dispatch, via
# benchmarks._util.arm_deadline).  Unarmed, retries only answer to the
# watchdog clamp.

_DEADLINE_AT: Optional[float] = None


def arm_retry_deadline(
    budget_s: Optional[float], *, clock: Callable[[], float] = time.monotonic
) -> None:
    """Arm (or, with None, disarm) the process-wide retry budget."""
    global _DEADLINE_AT
    _DEADLINE_AT = None if budget_s is None else clock() + float(budget_s)


def retry_deadline_remaining(
    *, clock: Callable[[], float] = time.monotonic
) -> Optional[float]:
    """Seconds left before the armed deadline; None when unarmed."""
    if _DEADLINE_AT is None:
        return None
    return _DEADLINE_AT - clock()


def _watchdog_cap() -> Optional[float]:
    """Longest sleep safe inside a watched scope (half the timeout)."""
    try:
        from music_analyst_tpu.observability.watchdog import get_watchdog

        wd = get_watchdog()
    except Exception:
        return None
    if wd is None:
        return None
    return wd.timeout_s / 2.0


# --- cross-run accounting ---------------------------------------------------

_STATS_LOCK = threading.Lock()
_STATS: Dict[str, Dict[str, int]] = {}


def _bump(site: str, key: str, n: int = 1) -> None:
    with _STATS_LOCK:
        entry = _STATS.setdefault(
            site, {"attempts": 0, "retries": 0, "recoveries": 0, "gave_up": 0}
        )
        entry[key] += n


def retry_stats() -> Dict[str, Dict[str, int]]:
    """Per-site attempt/retry/recovery counts for the run manifest."""
    with _STATS_LOCK:
        return {site: dict(counts) for site, counts in _STATS.items()}


def reset_retry_stats() -> None:
    with _STATS_LOCK:
        _STATS.clear()


class RetryPolicy:
    """Exponential backoff + full jitter + cap, budget- and fault-aware.

    ``retries`` is the number of RE-attempts after the first try.  The
    defaults (2 retries, 50 ms base, 2 s cap) suit host-side seams; the
    Ollama client overrides base/cap for network-scale latencies.
    """

    def __init__(
        self,
        retries: int = 2,
        base_s: float = 0.05,
        cap_s: float = 2.0,
        rng: Optional[Any] = None,
        sleep: Callable[[float], None] = time.sleep,
        deadline_fn: Callable[[], Optional[float]] = retry_deadline_remaining,
        classify: Callable[
            [BaseException], Tuple[bool, Optional[str]]
        ] = classify_retryable,
    ) -> None:
        if retries < 0:
            raise ValueError(f"retries must be >= 0, got {retries}")
        self.retries = int(retries)
        self.base_s = float(base_s)
        self.cap_s = float(cap_s)
        if rng is None:
            import random

            rng = random.Random()
        self._rng = rng
        self._sleep = sleep
        self._deadline_fn = deadline_fn
        self._classify = classify

    def backoff_s(self, attempt: int) -> float:
        """Full-jitter sleep before re-attempt ``attempt`` (1-based)."""
        ceiling = min(self.cap_s, self.base_s * (2 ** (attempt - 1)))
        cap = _watchdog_cap()
        if cap is not None:
            ceiling = min(ceiling, max(0.0, cap))
        return self._rng.uniform(0.0, ceiling)

    def call(
        self,
        fn: Callable[..., Any],
        *args: Any,
        site: str = "retry",
        **kwargs: Any,
    ) -> Any:
        """Run ``fn`` under the policy; raises the last error on give-up."""
        tel = get_telemetry()
        attempt = 0
        while True:
            attempt += 1
            _bump(site, "attempts")
            try:
                result = fn(*args, **kwargs)
            except Exception as exc:
                retryable, kind = self._classify(exc)
                if not retryable or attempt > self.retries:
                    if retryable:
                        _bump(site, "gave_up")
                        tel.count(f"retry.{site}.gave_up")
                    raise
                sleep_s = self.backoff_s(attempt)
                remaining = self._deadline_fn()
                if remaining is not None and sleep_s >= remaining:
                    # The budget cannot fit another attempt: re-raise NOW
                    # so the structured error line beats the deadline.
                    _bump(site, "gave_up")
                    tel.count(f"retry.{site}.gave_up")
                    raise
                _bump(site, "retries")
                tel.count(f"retry.{site}")
                tel.event(
                    "retry",
                    site=site,
                    attempt=attempt,
                    kind=kind,
                    sleep_s=round(sleep_s, 4),
                    error=str(exc)[:200],
                )
                if sleep_s > 0.0:
                    self._sleep(sleep_s)
                continue
            if attempt > 1:
                _bump(site, "recoveries")
                tel.count(f"retry.{site}.recovered")
                tel.event("retry_recovered", site=site, attempts=attempt)
            return result

    def wrap(
        self, fn: Callable[..., Any], site: str = "retry"
    ) -> Callable[..., Any]:
        def wrapped(*args: Any, **kwargs: Any) -> Any:
            return self.call(fn, *args, site=site, **kwargs)

        return wrapped


def resolve_http_retries(
    value: Optional[Any] = None, default: int = 2
) -> int:
    """Validated ``MUSICAAL_HTTP_RETRIES`` (the Ollama re-attempt count).

    Both an explicit value and the env var raise a clear ValueError on
    garbage — an HTTP retry knob silently falling back would hide the
    typo until the first outage needed it.
    """
    import os

    source = "http retries"
    if value is None:
        raw = os.environ.get("MUSICAAL_HTTP_RETRIES", "").strip()
        if not raw:
            return default
        source = "MUSICAAL_HTTP_RETRIES"
        value = raw
    try:
        retries = int(str(value).strip())
    except ValueError:
        raise ValueError(
            f"{source} must be an integer >= 0, got {value!r}"
        ) from None
    if retries < 0:
        raise ValueError(f"{source} must be >= 0, got {retries}")
    return retries
