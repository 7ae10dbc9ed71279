"""Scale-out serving: replica-router fleet throughput + failover drill.

Backs the "Scale-out serving" section in PERFORMANCE.md.  A fleet of
mock worker servers (each a full ``serve`` process on its own unix
socket — the overheads under test are the router's: wire hops,
join-shortest-queue dispatch, stats polling) is driven through the
``ReplicaRouter`` at increasing fleet widths, reporting per-width
throughput and the dispatch balance across replicas.

Two contract rows ride along:

* **balance** — at offered load ≫ fleet width, join-shortest-queue must
  spread dispatches across the replicas (no replica starves: each takes
  ≥ half its fair share);
* **failover drill** — SIGKILL one replica mid-burst; every admitted
  request must still settle (answered by a survivor after requeue, or a
  structured error), the health transition must be recorded, and the
  fleet must keep serving.
"""

from __future__ import annotations

import os
import signal
import sys
import tempfile
import time

from benchmarks import suite
from benchmarks._util import CPU_CHILDREN, smoke

_LYRICS = (
    "I love the sunshine and the happy days we share",
    "darkness and sorrow follow me through the lonely night",
    "la la la the radio plays our favourite song again",
    "broken hearts mend slowly under winter skies",
    "dancing together forever in the warm summer rain",
)


def _burst(router, n_requests: int, timeout_s: float = 120.0):
    """Submit ``n_requests`` through the router and wait for every reply."""
    start = time.perf_counter()
    reqs = [
        router.submit(i, "sentiment", _LYRICS[i % len(_LYRICS)])
        for i in range(n_requests)
    ]
    for req in reqs:
        if not req.wait(timeout=timeout_s):
            raise RuntimeError(f"request {req.id} never settled")
    return time.perf_counter() - start, reqs


@suite("router")
def run() -> dict:
    from music_analyst_tpu.serving.router import ReplicaRouter, spawn_replicas

    if smoke():
        widths, n_requests = (1, 2), 64
    else:
        widths, n_requests = (1, 2, 4), 1_024

    rows = []
    for width in widths:
        with tempfile.TemporaryDirectory(prefix="musicaal-bench-") as base:
            handles = spawn_replicas(
                width, base, model="mock", mock=True, warmup=False,
            )
            router = ReplicaRouter(
                handles, max_queue=n_requests + 1
            ).start()
            try:
                elapsed, reqs = _burst(router, n_requests)
                stats = router.stats()
            finally:
                router.drain()
            rps = n_requests / elapsed
            per_replica = {
                name: snap["dispatched"]
                for name, snap in stats["replicas"].items()
            }
            fair = n_requests / width
            balanced = all(d >= fair / 2 for d in per_replica.values())
            print(
                f"[router] {width} replica(s): {rps:.1f} req/s, "
                f"dispatch {per_replica}",
                file=sys.stderr,
            )
            rows.append({
                "replicas": width,
                "requests": n_requests,
                "seconds": round(elapsed, 4),
                "requests_per_s": round(rps, 2),
                "ok": sum(1 for r in reqs if r.response.get("ok")),
                "dispatch_per_replica": per_replica,
                "balanced": balanced,
            })

    # Failover drill: kill one of two replicas while its queue is hot.
    with tempfile.TemporaryDirectory(prefix="musicaal-bench-") as base:
        handles = spawn_replicas(2, base, model="mock", mock=True,
                                 warmup=False)
        router = ReplicaRouter(handles, max_queue=n_requests + 1,
                               poll_interval_s=0.1).start()
        try:
            warm_s, _ = _burst(router, max(8, n_requests // 8))
            victim = handles[0]
            os.kill(victim.proc.pid, signal.SIGKILL)
            elapsed, reqs = _burst(router, n_requests)
            stats = router.stats()
        finally:
            router.drain()
        answered = sum(1 for r in reqs if r.response is not None)
        oks = sum(1 for r in reqs if r.response.get("ok"))
        drill = {
            "killed": victim.name,
            "requests": n_requests,
            "answered": answered,
            "ok": oks,
            "requeued": stats["requeued"],
            "health_transitions": stats["health_transitions"],
            "survivor_health": handles[1].health,
            "zero_loss": answered == n_requests and oks == n_requests,
        }
        print(
            f"[router] failover drill: killed {victim.name}, "
            f"{oks}/{n_requests} ok, {stats['requeued']} requeued, "
            f"{len(stats['health_transitions'])} transition(s)",
            file=sys.stderr,
        )
        if not drill["zero_loss"]:
            raise RuntimeError(
                f"failover drill lost requests: {oks}/{n_requests} ok"
            )
        if not stats["health_transitions"]:
            raise RuntimeError("failover drill recorded no health transition")

    return {
        "suite": "router",
        "workers": CPU_CHILDREN,
        "smoke": smoke(),
        "rows": rows,
        "failover_drill": drill,
    }
