"""Resilience layer: deterministic fault injection, retry policy, failover.

Three pieces, all jax-free at import so the CLI's host-side paths
(telemetry-report, profile-diff) and the jax-free packages that embed
fault points (data/, runtime/, observability/) stay importable on a dead
backend:

- :mod:`.faults` — a seeded fault-injection registry with named sites
  threaded through the real seams (``MUSICAAL_FAULTS`` /
  ``--inject-faults``).
- :mod:`.policy` — the one :class:`RetryPolicy` (exponential backoff,
  full jitter, cap, deadline-aware budget) shared by Ollama HTTP,
  prefetch stages, cache I/O, and serving dispatch.
- :mod:`.failover` — one structured re-init-and-retry of a backend
  lost mid-run; a second failure fails the run.
"""

from music_analyst_tpu.resilience.faults import (
    FaultRule,
    InjectedFault,
    InjectedFatal,
    configure_faults,
    fault_point,
    fault_stats,
    parse_fault_spec,
    resolve_fault_spec,
)
from music_analyst_tpu.resilience.policy import (
    RetryPolicy,
    arm_retry_deadline,
    classify_retryable,
    reset_retry_stats,
    resolve_http_retries,
    retry_deadline_remaining,
    retry_stats,
)
from music_analyst_tpu.resilience.failover import (
    run_with_failover,
    should_failover,
)

__all__ = [
    "FaultRule",
    "InjectedFault",
    "InjectedFatal",
    "configure_faults",
    "fault_point",
    "fault_stats",
    "parse_fault_spec",
    "resolve_fault_spec",
    "RetryPolicy",
    "arm_retry_deadline",
    "classify_retryable",
    "reset_retry_stats",
    "resolve_http_retries",
    "retry_deadline_remaining",
    "retry_stats",
    "run_with_failover",
    "should_failover",
]
