"""Shared helpers for the benchmark suites.

Every timed region ends with a host readback (``np.asarray``) of (a
slice of) the result: dispatch is asynchronous, and the readback waits
for the device.

``smoke()`` is the test hook: with ``MUSICAAL_BENCH_SMOKE=1`` every suite
shrinks to seconds-scale shapes so ``tests/test_benchmarks.py`` can keep
the whole registry runnable on the CPU mesh without paying chip-scale
compute.  A device number only ever comes from a full-size run on a
chip; every table names the platform it ran on (:func:`device_info`, or
:data:`CPU_CHILDREN` where a suite pins its child processes to the CPU).

A chip belongs to one process at a time: a suite whose children need it
touches no backend before they have exited.  Children that are protocol
stand-ins never see a chip — ``spawn_replicas`` starts mock workers with
``JAX_PLATFORMS=cpu``, the crash drills set it on their servers — and
the suite says so in its table.
"""

from __future__ import annotations

import os
import time
from typing import Callable, Tuple

import numpy as np


def smoke() -> bool:
    return os.environ.get("MUSICAAL_BENCH_SMOKE", "") not in ("", "0")


def device_info() -> dict:
    import jax

    devices = jax.devices()
    return {
        "platform": devices[0].platform,
        "n_devices": len(devices),
        "device": str(devices[0]),
    }


# What a suite reports when its child processes never see a chip.
CPU_CHILDREN = (
    "child processes pinned to the CPU (JAX_PLATFORMS=cpu): this suite "
    "drills the serving protocol, it does not time a device"
)


def timed(fn: Callable[[], object], repeats: int = 3) -> Tuple[float, object]:
    """Best-of-``repeats`` wall seconds for ``fn``, forced readback included.

    ``fn`` must return a SMALL device array (reduce big results to a scalar
    inside the jitted program) — it is fully read back inside the timed
    region so async dispatch can't under-report, and a big result would
    otherwise time the device→host copy instead of the program.  Best-of
    rather
    than mean: the quantity of interest is the program's steady-state cost,
    and the minimum is the estimator least contaminated by one-off host
    noise (same reasoning as timeit).
    """
    best = float("inf")
    result = None
    for _ in range(repeats):
        start = time.perf_counter()
        out = fn()
        if hasattr(out, "shape"):
            np.asarray(out)
        elapsed = time.perf_counter() - start
        if elapsed < best:
            best, result = elapsed, out
    return best, result


def readback(x) -> np.ndarray:
    return np.asarray(x)


# --- parent-deadline budget ------------------------------------------------
#
# bench.py's contract is ONE JSON line before $MUSICAAL_BENCH_DEADLINE_S
# elapses — for suites too (the driver runs `--suite=<name>` under the same
# wall clock).  Suites that launch children (coldstart's fresh-process
# runs) must therefore clamp child timeouts to what remains of the PARENT
# budget: a wedged child allowed e.g. 1200 s inside a 480 s window would
# eat the contractual line.  bench.py arms the deadline once at suite
# dispatch; unarmed (direct suite invocation, unit tests) the helpers keep
# the caller's original timeout.

_DEADLINE_AT: float | None = None
# Tail reserved for the suite to collect the child and print its line.
_BUDGET_SAFETY_S = 15.0


def arm_deadline(budget_s: float | None, *, clock=time.monotonic) -> None:
    """Start the suite-wide wall-clock budget (``None`` disarms).

    Also arms the resilience retry budget: a retry sleep inside a bench
    suite must never outlive the driver's wall clock, or the contractual
    JSON line loses to a SIGTERM.
    """
    global _DEADLINE_AT
    _DEADLINE_AT = None if budget_s is None else clock() + float(budget_s)
    try:
        from music_analyst_tpu.resilience.policy import arm_retry_deadline

        arm_retry_deadline(budget_s, clock=clock)
    except Exception:
        pass


def remaining_budget(*, clock=time.monotonic) -> float | None:
    """Seconds left before the armed deadline; ``None`` when unarmed."""
    if _DEADLINE_AT is None:
        return None
    return _DEADLINE_AT - clock()


def clamped_timeout(
    cap_s: float, safety_s: float = _BUDGET_SAFETY_S, *, clock=time.monotonic
) -> float:
    """A child timeout that fits inside the remaining parent budget.

    Returns ``cap_s`` unarmed; armed, the smaller of ``cap_s`` and what
    remains minus ``safety_s`` (floored at 1 s so a nearly-spent budget
    still fails fast with a TimeoutExpired instead of a ValueError).
    """
    left = remaining_budget(clock=clock)
    if left is None:
        return cap_s
    return max(1.0, min(cap_s, left - safety_s))
