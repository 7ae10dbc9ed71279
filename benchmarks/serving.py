"""Serving layer: offered-load sweep × batch-size grid.

Backs the "Serving latency" section in PERFORMANCE.md.  A warm mock
backend (the keyword kernel — the serving overheads under test are
host-side: admission, coalescing, padding, dispatch) is driven through
the dynamic batcher at a grid of offered loads (burst sizes, as
multiples of ``max_batch``) × ``max_batch`` settings.  Each cell reports
throughput, batch occupancy, and p50/p95/p99 request latency from the
batcher's own histogram.

Two contract rows ride along:

* **coalescing win** — at offered load ≥ ``max_batch``, the batcher's
  throughput must beat sequential single-request dispatch (the
  ``max_batch=1`` baseline) by ≥ 2× (the ISSUE 8 acceptance bar);
* **overload shedding** — a burst 4× the admission bound must shed with
  structured ``queue_full`` errors while every admitted request still
  gets an answer and the server object survives;
* **response cache** — a Zipf(s≈1.0) catalog workload replayed against
  the content-addressed response cache must beat the cache-off control
  by ≥ 5× requests/s in the warm steady state (the ISSUE 20 bar), with
  hit-path latency that never touches the device.
"""

from __future__ import annotations

import math
import sys
import time

from benchmarks import suite
from benchmarks._util import device_info, smoke

_LYRICS = (
    "I love the sunshine and the happy days we share",
    "darkness and sorrow follow me through the lonely night",
    "la la la the radio plays our favourite song again",
    "broken hearts mend slowly under winter skies",
    "dancing together forever in the warm summer rain",
)


def _drive(ops, max_batch: int, n_requests: int,
           max_wait_ms: float = 2.0, max_queue: int | None = None):
    """Submit a burst of ``n_requests`` and wait for every reply."""
    from music_analyst_tpu.serving.batcher import DynamicBatcher

    batcher = DynamicBatcher(
        ops, max_batch=max_batch, max_wait_ms=max_wait_ms,
        max_queue=max_queue or (n_requests + 1),
    ).start()
    start = time.perf_counter()
    reqs = [
        batcher.submit(i, "sentiment", _LYRICS[i % len(_LYRICS)])
        for i in range(n_requests)
    ]
    for req in reqs:
        if not req.wait(timeout=120.0):
            raise RuntimeError(f"request {req.id} never settled")
    elapsed = time.perf_counter() - start
    batcher.drain()
    return elapsed, batcher.stats(), reqs


def _drive_texts(ops, texts, max_batch: int, response_cache=None,
                 max_wait_ms: float = 2.0):
    """Burst-submit an explicit text sequence; return wall, stats, reqs."""
    import gc

    from music_analyst_tpu.serving.batcher import DynamicBatcher

    gc.collect()
    batcher = DynamicBatcher(
        ops, max_batch=max_batch, max_wait_ms=max_wait_ms,
        max_queue=len(texts) + 1, response_cache=response_cache,
    ).start()
    start = time.perf_counter()
    reqs = [
        batcher.submit(i, "sentiment", text)
        for i, text in enumerate(texts)
    ]
    for req in reqs:
        if not req.wait(timeout=120.0):
            raise RuntimeError(f"request {req.id} never settled")
    elapsed = time.perf_counter() - start
    batcher.drain()
    return elapsed, batcher.stats(), reqs


def _zipf_cache_scenario(ops, max_batch: int) -> dict:
    """Zipf-catalog A/B: requests/s with the response cache (warm steady
    state) vs the cache-off control over the identical arrival list.

    The headline arms run at ``max_batch=1`` — per-dispatch serving,
    what a cache hit actually skips.  (On the CPU-emulated mock the
    keyword kernel's batched dispatch is ~tens of µs/request, the same
    order as Python submit overhead, so a batched control understates
    the win by construction; on real hardware a dispatch is ~ms.  The
    batched control rides along as its own row for that comparison.)

    The cache arm runs the same list twice — a cold pass that both
    answers (head hits appear as soon as the first occurrence settles)
    and populates, then a measured warm pass where every draw answers
    from cache without a device dispatch.  Hit-path p99 comes from the
    warm pass: a hash + dict lookup, far under any dispatch."""
    import tempfile

    from benchmarks.loadgen import _percentile, zipf_arrivals
    from music_analyst_tpu.serving.response_cache import (
        ResponseCache, backend_fingerprint,
    )

    n_draws = 800 if smoke() else 4000
    arrivals = zipf_arrivals(
        rate_rps=1000.0, duration_s=n_draws * 1.2 / 1000.0,
        catalog_size=1000, s=1.0, seed=7,
    )[:n_draws]
    texts = [a.text for a in arrivals]

    batched_s, _, _ = _drive_texts(ops, texts, max_batch=max_batch)
    batched_rps = len(texts) / batched_s

    with tempfile.TemporaryDirectory(prefix="musicaal-rcache-") as rc_dir:
        cache = ResponseCache(
            rc_dir, fingerprint=backend_fingerprint(model="mock"),
        )
        cold_s, _, _ = _drive_texts(
            ops, texts, max_batch=1, response_cache=cache,
        )
        cold_stats = cache.stats()
        cold_hit_rate = cold_stats["hit_rate"]
        # Interleaved best-of-3 on both arms: the one-pinned-CPU sandbox
        # has process-wide slow phases, so alternating the arms exposes
        # them to the same conditions and the min-wall ratio stays a
        # steady-state comparison rather than a scheduling lottery.
        warm_texts = texts * 3  # longer timed interval, same mixture
        off_s = math.inf
        warm_s = math.inf
        warm_batcher_stats = None
        warm_reqs = []
        for _ in range(3):
            off_s = min(off_s, _drive_texts(ops, texts, max_batch=1)[0])
            w_s, w_stats, w_reqs = _drive_texts(
                ops, warm_texts, max_batch=1, response_cache=cache,
            )
            if w_s < warm_s:
                warm_s, warm_batcher_stats, warm_reqs = w_s, w_stats, w_reqs
        off_rps = len(texts) / off_s
        warm_rps = len(warm_texts) / warm_s
        hit_ms = sorted(
            (r.t_settle - r.t_enqueue) * 1000.0
            for r in warm_reqs
            if r.t_settle is not None and r.meta.get("cached")
        )
        stats = cache.stats()

    print(
        f"[serving] zipf cache: control {off_rps:.0f} req/s → warm "
        f"{warm_rps:.0f} req/s ({warm_rps / off_rps:.1f}x; batched "
        f"control {batched_rps:.0f} req/s), cold hit rate "
        f"{cold_hit_rate:.2f}, hit p99 "
        f"{_percentile(hit_ms, 99.0):.3f} ms",
        file=sys.stderr,
    )
    return {
        "catalog_size": 1000,
        "zipf_s": 1.0,
        "draws": len(texts),
        "unique_texts": len(set(texts)),
        "control_requests_per_s": round(off_rps, 2),
        "batched_control_max_batch": max_batch,
        "batched_control_requests_per_s": round(batched_rps, 2),
        "cold_seconds": round(cold_s, 4),
        "cold_hit_rate": cold_hit_rate,
        "warm_requests_per_s": round(warm_rps, 2),
        "warm_speedup": round(warm_rps / off_rps, 2),
        "warm_speedup_vs_batched": round(warm_rps / batched_rps, 2),
        "warm_hits": warm_batcher_stats["cache_hits"],
        "hit_p50_ms": round(_percentile(hit_ms, 50.0), 4),
        "hit_p99_ms": round(_percentile(hit_ms, 99.0), 4),
        "stats": stats,
    }


@suite("serving")
def run() -> dict:
    from music_analyst_tpu.models.backend import ModelResidency
    from music_analyst_tpu.serving.server import build_ops

    if smoke():
        batch_grid, load_mults, n_base = (4, 8), (1, 4), 64
    else:
        batch_grid, load_mults, n_base = (8, 32, 64), (1, 4, 16), 2_048

    residency = ModelResidency(model="mock", mock=True)
    clf = residency.acquire()
    warm = residency.warmup(max(batch_grid))
    ops = build_ops(clf)

    # Sequential baseline: same requests, one per batch — what the
    # reference's call-per-song loop would do with a resident model.
    n_seq = max(n_base // 4, max(batch_grid))
    seq_s, seq_stats, _ = _drive(ops, max_batch=1, n_requests=n_seq)
    seq_rps = n_seq / seq_s
    print(f"[serving] sequential baseline: {seq_rps:.1f} req/s",
          file=sys.stderr)

    rows = []
    best_coalesced = 0.0
    for max_batch in batch_grid:
        for mult in load_mults:
            n = max(n_base, max_batch * mult)
            elapsed, stats, _ = _drive(ops, max_batch=max_batch,
                                       n_requests=n)
            rps = n / elapsed
            latency = stats["latency"]
            offered = max_batch * mult
            if offered >= max_batch:
                best_coalesced = max(best_coalesced, rps)
            print(
                f"[serving] max_batch={max_batch} offered={offered} "
                f"→ {rps:.1f} req/s, occupancy {stats['occupancy']}",
                file=sys.stderr,
            )
            rows.append({
                "max_batch": max_batch,
                "offered_load": offered,
                "requests": n,
                "seconds": round(elapsed, 4),
                "requests_per_s": round(rps, 2),
                "batches": stats["batches"],
                "occupancy": stats["occupancy"],
                "p50_s": latency.get("p50_s"),
                "p95_s": latency.get("p95_s"),
                "p99_s": latency.get("p99_s"),
            })

    # Overload: burst far past the admission bound; the contract is
    # structured shedding, full answers for the admitted, no crash.
    over_batch = max(batch_grid)
    over_queue = over_batch * 2
    _, over_stats, over_reqs = _drive(
        ops, max_batch=over_batch, n_requests=over_queue * 4,
        max_queue=over_queue,
    )
    shed_kinds = {
        r.response["error"]["kind"]
        for r in over_reqs if not r.response.get("ok")
    }
    overload = {
        "max_queue": over_queue,
        "offered": over_queue * 4,
        "admitted": over_stats["admitted"],
        "shed": over_stats["shed"],
        "completed": over_stats["completed"],
        "shed_kinds": sorted(shed_kinds),
        "all_answered": all(r.response is not None for r in over_reqs),
    }
    print(
        f"[serving] overload: {overload['shed']} shed "
        f"({overload['shed_kinds']}), {overload['completed']} completed",
        file=sys.stderr,
    )

    response_cache = _zipf_cache_scenario(ops, max_batch=max(batch_grid))

    return {
        "suite": "serving",
        **device_info(),
        "smoke": smoke(),
        "backend": getattr(clf, "name", "mock"),
        "warmup": warm,
        "sequential": {
            "requests": n_seq,
            "seconds": round(seq_s, 4),
            "requests_per_s": round(seq_rps, 2),
            "p50_s": seq_stats["latency"].get("p50_s"),
        },
        "rows": rows,
        "coalescing_speedup": round(best_coalesced / seq_rps, 2),
        "overload": overload,
        "response_cache": response_cache,
    }
