"""Chaos suite: injected faults vs. recovery latency and artifact bytes.

Backs the "Injecting faults & measuring recovery" section in
PERFORMANCE.md.  Each scenario runs the full wordcount engine over the
same synthetic corpus with one fault rule armed (``resilience/faults.py``
grammar) and asserts the resilience tentpole's two contracts:

* **byte identity** — every recovered run produces ``word_counts.csv``
  byte-identical to the clean run (the golden contracts hold under
  injected failure; a *persistent* device fault fails the run —
  ``tests/test_resilience.py`` — there is no host-side count path);
* **visible recovery** — the injected trips and the retries/failovers
  that absorbed them appear in the run's telemetry counters.

The reported ``recovery_overhead_s`` is scenario wall time minus the
clean baseline: what one transient fault at that seam costs end-to-end
(backoff sleep + re-attempt).  A serving scenario drives the dynamic
batcher through an injected dispatch failure the same way.
"""

from __future__ import annotations

import csv
import json
import os
import sys
import tempfile
import time

from benchmarks import suite
from benchmarks._util import device_info, smoke

# (scenario, fault spec) — specs use the public grammar.
_SCENARIOS = (
    ("ingest_transient", "ingest.read:error@1"),
    ("prefetch_transient", "prefetch.stage:error@1"),
    ("psum_transient", "collective.psum:error@1"),
)

_WORDS = (
    "sunshine shadow river mountain whisper thunder golden silver",
    "dancing alone together forever tomorrow yesterday morning",
    "broken hearts mend slowly under winter summer skies above",
)


def _write_corpus(path: str, n_rows: int) -> None:
    with open(path, "w", encoding="utf-8", newline="") as fh:
        writer = csv.writer(fh)
        writer.writerow(["artist", "song", "link", "text"])
        for i in range(n_rows):
            writer.writerow([
                f"Artist {i % 23}",
                f"Song {i}",
                f"/a{i % 23}/s{i}",
                _WORDS[i % len(_WORDS)],
            ])


def _run_once(dataset: str, out_dir: str, chunk_songs: int):
    from music_analyst_tpu.engines.wordcount import run_analysis

    start = time.perf_counter()
    run_analysis(
        dataset,
        output_dir=out_dir,
        write_split=False,
        quiet=True,
        use_corpus_cache=False,
        chunk_songs=chunk_songs,
    )
    elapsed = time.perf_counter() - start
    with open(os.path.join(out_dir, "word_counts.csv"), "rb") as fh:
        return elapsed, fh.read()


def _serving_scenario(n_requests: int) -> dict:
    """Injected dispatch failure: the batcher retry absorbs it."""
    from music_analyst_tpu.resilience import (
        configure_faults,
        fault_stats,
        reset_retry_stats,
        retry_stats,
    )
    from music_analyst_tpu.serving.batcher import DynamicBatcher

    reset_retry_stats()
    configure_faults("serving.dispatch:error@1")
    try:
        ops = {"echo": lambda texts: [{"label": t} for t in texts]}
        batcher = DynamicBatcher(
            ops, max_batch=8, max_wait_ms=1.0, max_queue=n_requests + 1
        ).start()
        start = time.perf_counter()
        reqs = [
            batcher.submit(i, "echo", f"row {i}") for i in range(n_requests)
        ]
        for req in reqs:
            if not req.wait(timeout=60.0):
                raise RuntimeError(f"request {req.id} never settled")
        elapsed = time.perf_counter() - start
        failed = sum(1 for r in reqs if not (r.response or {}).get("ok"))
        batcher.drain()
        return {
            "scenario": "serving_dispatch_transient",
            "spec": "serving.dispatch:error@1",
            "requests": n_requests,
            "failed_requests": failed,
            "all_answered": failed == 0,
            "wall_s": round(elapsed, 4),
            "faults": fault_stats(),
            "retries": {
                site: counts
                for site, counts in retry_stats().items()
                if counts.get("retries")
            },
        }
    finally:
        configure_faults(None)


def _decode_scenario(n_requests: int) -> dict:
    """Injected decode-dispatch failure: the continuous scheduler's retry
    absorbs it and every generate request still settles."""
    from music_analyst_tpu.models.llama import (
        LlamaConfig,
        LlamaZeroShotClassifier,
    )
    from music_analyst_tpu.resilience import (
        configure_faults,
        fault_stats,
        reset_retry_stats,
        retry_stats,
    )
    from music_analyst_tpu.serving.decode_loop import ContinuousScheduler

    reset_retry_stats()
    clf = LlamaZeroShotClassifier(
        config=LlamaConfig.tiny(), max_prompt_len=64
    )
    sched = ContinuousScheduler(
        clf, n_slots=2, prefill_chunk=16, prompt_region=32,
        max_new_tokens=4, max_queue=n_requests + 1,
    )
    sched.warmup()
    configure_faults("decode.step:error@1")
    try:
        start = time.perf_counter()
        reqs = [
            sched.submit(i, f"chaos lyric {i}", max_new_tokens=4)
            for i in range(n_requests)
        ]
        sched.run_until_idle()
        elapsed = time.perf_counter() - start
        failed = sum(1 for r in reqs if not (r.response or {}).get("ok"))
        return {
            "scenario": "decode_step_transient",
            "spec": "decode.step:error@1",
            "requests": n_requests,
            "failed_requests": failed,
            "all_answered": failed == 0,
            "wall_s": round(elapsed, 4),
            "faults": fault_stats(),
            "retries": {
                site: counts
                for site, counts in retry_stats().items()
                if counts.get("retries")
            },
        }
    finally:
        configure_faults(None)


def _router_scenario(n_requests: int) -> dict:
    """Injected router-dispatch failure: the router's in-place retry
    absorbs it (no replica marked unhealthy) and every request settles."""
    from music_analyst_tpu.resilience import (
        configure_faults,
        fault_stats,
        reset_retry_stats,
        retry_stats,
    )
    from music_analyst_tpu.serving.router import ReplicaRouter, spawn_replicas

    reset_retry_stats()
    configure_faults("router.dispatch:error@1")
    try:
        with tempfile.TemporaryDirectory(prefix="chaos_fleet_") as base:
            handles = spawn_replicas(2, base, model="mock", mock=True,
                                     warmup=False)
            router = ReplicaRouter(
                handles, max_queue=n_requests + 1
            ).start()
            try:
                start = time.perf_counter()
                reqs = [
                    router.submit(i, "sentiment", f"chaos row {i}")
                    for i in range(n_requests)
                ]
                for req in reqs:
                    if not req.wait(timeout=60.0):
                        raise RuntimeError(
                            f"request {req.id} never settled"
                        )
                elapsed = time.perf_counter() - start
                stats = router.stats()
            finally:
                router.drain()
        failed = sum(1 for r in reqs if not (r.response or {}).get("ok"))
        return {
            "scenario": "router_dispatch_transient",
            "spec": "router.dispatch:error@1",
            "requests": n_requests,
            "failed_requests": failed,
            "all_answered": failed == 0,
            "health_transitions": len(stats["health_transitions"]),
            "requeued": stats["requeued"],
            "wall_s": round(elapsed, 4),
            "faults": fault_stats(),
            "retries": {
                site: counts
                for site, counts in retry_stats().items()
                if counts.get("retries")
            },
        }
    finally:
        configure_faults(None)


def _prefix_lookup_scenario(n_requests: int) -> dict:
    """Corrupted/missed radix lookup (site ``kv_pages.lookup``): every
    faulted admit degrades to a full prefill with zero sharing — the
    generated bytes must match the clean warm-cache run exactly."""
    from music_analyst_tpu.models.llama import (
        PROMPT_TEMPLATE,
        LlamaConfig,
        LlamaZeroShotClassifier,
    )
    from music_analyst_tpu.resilience import configure_faults, fault_stats
    from music_analyst_tpu.serving.decode_loop import ContinuousScheduler

    clf = LlamaZeroShotClassifier(
        config=LlamaConfig.tiny(), max_prompt_len=128
    )
    prompts = [
        PROMPT_TEMPLATE.format(lyrics=f"chaos lyric number {i}")
        for i in range(n_requests)
    ]
    sched = ContinuousScheduler(
        clf, n_slots=2, prefill_chunk=32, prompt_region=128,
        max_new_tokens=4, max_queue=n_requests + 1,
    )
    sched.warmup()

    def _texts():
        reqs = [
            sched.submit(i, p, max_new_tokens=4)
            for i, p in enumerate(prompts)
        ]
        sched.run_until_idle()
        out = []
        for req in reqs:
            resp = req.response or {}
            if not resp.get("ok"):
                raise RuntimeError(f"generate {req.id} failed: "
                                   f"{resp.get('error')}")
            out.append(resp["text"])
        return out

    start = time.perf_counter()
    clean = _texts()  # warm pass — the radix tree now holds every prompt
    hits_before = sched.stats()["prefix_cache"]["hits"]
    configure_faults("kv_pages.lookup:error@1+")
    try:
        faulted = _texts()
        faults = fault_stats()
    finally:
        configure_faults(None)
    elapsed = time.perf_counter() - start
    stats = sched.stats()["prefix_cache"]
    return {
        "scenario": "prefix_lookup_corrupt",
        "spec": "kv_pages.lookup:error@1+",
        "requests": n_requests,
        "bytes_identical": faulted == clean,
        "fallbacks": stats["fallbacks"],
        "hits_while_faulted": stats["hits"] - hits_before,
        "all_fell_back": stats["fallbacks"] == n_requests,
        "trips": sum(int(i.get("trips", 0)) for i in faults.values()),
        "wall_s": round(elapsed, 4),
    }


def _spec_draft_scenario(n_requests: int) -> dict:
    """Injected drafter fault (site ``spec.draft``): every faulted tick
    degrades to plain non-speculative decode before any draft is built —
    the generated bytes must match the clean speculative run exactly
    (fewer tokens per dispatch, never a wrong one), and the degradation
    is visible as ``speculation.fallbacks``."""
    from music_analyst_tpu.models.llama import (
        LlamaConfig,
        LlamaZeroShotClassifier,
    )
    from music_analyst_tpu.resilience import configure_faults, fault_stats
    from music_analyst_tpu.serving.decode_loop import ContinuousScheduler

    clf = LlamaZeroShotClassifier(
        config=LlamaConfig.tiny(), max_prompt_len=64
    )
    sched = ContinuousScheduler(
        clf, n_slots=2, prefill_chunk=16, prompt_region=64,
        max_new_tokens=24, max_queue=n_requests + 1, speculate_k=4,
    )
    sched.warmup()

    def _texts(tag: str):
        reqs = [
            sched.submit(f"{tag}-{i}", f"spec chaos la la la lyric {i}",
                         max_new_tokens=24)
            for i in range(n_requests)
        ]
        sched.run_until_idle()
        out = []
        for req in reqs:
            resp = req.response or {}
            if not resp.get("ok"):
                raise RuntimeError(f"generate {req.id} failed: "
                                   f"{resp.get('error')}")
            out.append(resp["text"])
        return out

    start = time.perf_counter()
    clean = _texts("clean")
    spec_before = sched.stats()["speculation"]
    configure_faults("spec.draft:error@1+")
    try:
        faulted = _texts("faulted")
        trips = fault_stats()["spec.draft"]["trips"]
    finally:
        configure_faults(None)
    elapsed = time.perf_counter() - start
    spec = sched.stats()["speculation"]
    return {
        "scenario": "spec_draft_fault",
        "spec": "spec.draft:error@1+",
        "requests": n_requests,
        "bytes_identical": faulted == clean,
        "spec_dispatches_clean": spec_before["dispatches"],
        "spec_active_clean": spec_before["dispatches"] > 0,
        "fallbacks": spec["fallbacks"],
        "trips": trips,
        "all_fell_back": spec["fallbacks"] == trips and trips > 0,
        "wall_s": round(elapsed, 4),
    }


def _reqtrace_flush_scenario(n_requests: int) -> dict:
    """Injected trace-flush failure (site ``reqtrace.flush``): every
    flush attempt fails, so kept traces degrade to counted
    ``trace_drops`` — the replies themselves are untouched (same labels
    as the clean traced run, everything answers) and no torn trace file
    appears.  Tracing must never block the reply path."""
    from music_analyst_tpu.resilience import configure_faults, fault_stats
    from music_analyst_tpu.serving.batcher import DynamicBatcher
    from music_analyst_tpu.telemetry.reqtrace import (
        TRACE_FILE,
        configure_reqtrace,
    )

    ops = {"echo": lambda texts: [{"label": t.upper()} for t in texts]}

    def _run(tag: str, trace_dir: str):
        rt = configure_reqtrace(1.0, directory=trace_dir, role="bench")
        batcher = DynamicBatcher(
            ops, max_batch=8, max_wait_ms=1.0, max_queue=n_requests + 1
        ).start()
        try:
            reqs = [
                batcher.submit(f"{tag}-{i}", "echo", f"chaos row {i}")
                for i in range(n_requests)
            ]
            for req in reqs:
                if not req.wait(timeout=60.0):
                    raise RuntimeError(f"request {req.id} never settled")
                # The reply-write seam (server.py) owns finish_request;
                # this in-process drive replays it per settled reply so
                # the real flush path — and its fault gate — runs.
                rt.finish_request(req)
        finally:
            batcher.drain()
        labels = [(r.response or {}).get("label") for r in reqs]
        return labels, rt.stats()

    try:
        with tempfile.TemporaryDirectory(prefix="chaos_traces_") as base:
            clean_dir = os.path.join(base, "clean")
            faulted_dir = os.path.join(base, "faulted")
            start = time.perf_counter()
            clean_labels, clean_stats = _run("clean", clean_dir)
            configure_faults("reqtrace.flush:error@1+")
            try:
                faulted_labels, faulted_stats = _run("faulted", faulted_dir)
                trips = fault_stats()["reqtrace.flush"]["trips"]
            finally:
                configure_faults(None)
            elapsed = time.perf_counter() - start
            trace_path = os.path.join(faulted_dir, TRACE_FILE)
            faulted_file_empty = (
                not os.path.exists(trace_path)
                or os.path.getsize(trace_path) == 0
            )
    finally:
        # configure_reqtrace exported the dir/sample env for worker
        # inheritance — clear them so the disabled recorder stays off.
        os.environ.pop("MUSICAAL_TRACE_DIR", None)
        os.environ.pop("MUSICAAL_TRACE_SAMPLE", None)
        configure_reqtrace(None, None)
    return {
        "scenario": "reqtrace_flush_fault",
        "spec": "reqtrace.flush:error@1+",
        "requests": n_requests,
        "bytes_identical": faulted_labels == clean_labels,
        "all_answered": (
            all(label is not None for label in faulted_labels)
            and len(faulted_labels) == n_requests
        ),
        "flushed_clean": clean_stats["flushed"],
        "trace_drops": faulted_stats["trace_drops"],
        "trips": trips,
        "faulted_file_empty": faulted_file_empty,
        "degraded_to_drops": (
            clean_stats["flushed"] == n_requests
            and faulted_stats["trace_drops"] == n_requests
            and faulted_stats["flushed"] == 0
            and faulted_file_empty
        ),
        "wall_s": round(elapsed, 4),
    }


def _metrics_scrape_scenario(n_requests: int) -> dict:
    """Injected scrape failure (site ``metrics.scrape``): every scrape
    attempt trips, so the series degrades to a stale-marked plane with
    counted ``scrape_errors`` — the replies are byte-identical to the
    clean metered run, and no torn ``metrics.jsonl`` line ever lands
    (a failed scrape writes nothing at all).  Observability must never
    block — or bend — the reply path."""
    from music_analyst_tpu.observability.metrics_plane import (
        METRICS_FILE,
        configure_metrics,
    )
    from music_analyst_tpu.resilience import configure_faults, fault_stats
    from music_analyst_tpu.serving.batcher import DynamicBatcher

    ops = {"echo": lambda texts: [{"label": t.upper()} for t in texts]}

    def _run(tag: str, out_dir: str):
        plane = configure_metrics(25.0, directory=out_dir, role="bench")
        batcher = DynamicBatcher(
            ops, max_batch=8, max_wait_ms=1.0, max_queue=n_requests + 1
        ).start()
        plane.attach(lambda: {
            "requests": batcher.stats(), "slo": batcher.slo_snapshot(),
        })
        plane.start()
        try:
            reqs = [
                batcher.submit(f"{tag}-{i}", "echo", f"chaos row {i}")
                for i in range(n_requests)
            ]
            for req in reqs:
                if not req.wait(timeout=60.0):
                    raise RuntimeError(f"request {req.id} never settled")
        finally:
            batcher.drain()
            plane.close()
        labels = [(r.response or {}).get("label") for r in reqs]
        return labels, plane.snapshot()

    def _jsonl_intact(path: str):
        """(intact, n_lines): every line newline-terminated and parseable
        — the O_APPEND single-write discipline's observable contract."""
        if not os.path.exists(path):
            return True, 0
        n = 0
        with open(path, "r", encoding="utf-8") as fh:
            for line in fh:
                if not line.endswith("\n"):
                    return False, n
                try:
                    json.loads(line)
                except json.JSONDecodeError:
                    return False, n
                n += 1
        return True, n

    try:
        with tempfile.TemporaryDirectory(prefix="chaos_metrics_") as base:
            clean_dir = os.path.join(base, "clean")
            faulted_dir = os.path.join(base, "faulted")
            start = time.perf_counter()
            clean_labels, clean_snap = _run("clean", clean_dir)
            configure_faults("metrics.scrape:error@1+")
            try:
                faulted_labels, faulted_snap = _run("faulted", faulted_dir)
                trips = fault_stats()["metrics.scrape"]["trips"]
            finally:
                configure_faults(None)
            elapsed = time.perf_counter() - start
            clean_intact, clean_lines = _jsonl_intact(
                os.path.join(clean_dir, METRICS_FILE)
            )
            faulted_intact, faulted_lines = _jsonl_intact(
                os.path.join(faulted_dir, METRICS_FILE)
            )
    finally:
        # configure_metrics exported the interval/dir env for worker
        # inheritance — clear them so the disabled plane stays off.
        os.environ.pop("MUSICAAL_METRICS_INTERVAL_MS", None)
        os.environ.pop("MUSICAAL_METRICS_DIR", None)
        configure_metrics(None, None)
    return {
        "scenario": "metrics_scrape_fault",
        "spec": "metrics.scrape:error@1+",
        "requests": n_requests,
        "bytes_identical": faulted_labels == clean_labels,
        "all_answered": (
            all(label is not None for label in faulted_labels)
            and len(faulted_labels) == n_requests
        ),
        "samples_clean": clean_snap["samples"],
        "scrape_errors": faulted_snap["scrape_errors"],
        "trips": trips,
        "clean_file_intact": clean_intact,
        "clean_file_lines": clean_lines,
        "faulted_file_lines": faulted_lines,
        "degraded_to_stale": (
            clean_snap["samples"] >= 2  # baseline + final at minimum
            and clean_snap["scrape_errors"] == 0
            and clean_intact and clean_lines >= clean_snap["samples"]
            and faulted_snap["samples"] == 0
            and faulted_snap["scrape_errors"] == trips
            and trips > 0
            and bool(faulted_snap["stale"])
            and faulted_intact and faulted_lines == 0
        ),
        "wall_s": round(elapsed, 4),
    }


def _journal_scenario() -> dict:
    """Faulted appends + a torn segment tail (site ``journal.append``):
    the server-side append failure is absorbed (the request still
    answers, just un-journaled), and on restart the CRC scan counts the
    corruption and degrades the lost reply to a replayed recompute —
    never to a wrong or duplicate answer."""
    from music_analyst_tpu.resilience import configure_faults, fault_stats
    from music_analyst_tpu.serving.journal import RequestJournal

    with tempfile.TemporaryDirectory(prefix="chaos_journal_") as base:
        directory = os.path.join(base, "wal")
        journal = RequestJournal(directory, sync_every=1)
        journal.recover()
        configure_faults("journal.append:error@3")
        try:
            journal.record_admitted("a", "sentiment", "love and rain")
            journal.record_admitted("b", "sentiment", "cold gray sky")
            # Append 3 — reply "a" — trips: the reply stays in memory and
            # on the wire, but never reaches disk.
            journal.record_replied("a", {"ok": True, "label": "Positive"})
            journal.record_replied("b", {"ok": True, "label": "Negative"})
            trips = fault_stats()["journal.append"]["trips"]
        finally:
            configure_faults(None)
        append_errors = journal.stats()["append_errors"]
        # SIGKILL stand-in: abandon the handle (no close(), no compaction,
        # no clean marker) and tear the active segment's tail.
        segments = sorted(
            name for name in os.listdir(directory)
            if name.startswith("journal-")
        )
        with open(os.path.join(directory, segments[-1]), "ab") as fh:
            fh.write(b"\xff" * 12)
        reopened = RequestJournal(directory)
        unanswered = reopened.recover()
        stats = reopened.stats()
        replayed_ids = sorted(str(r.get("id")) for r in unanswered)
        lost_recomputes = reopened.lookup_reply("a") is None
        survivor = (reopened.lookup_reply("b") or {}).get("label")
    return {
        "scenario": "journal_append_fault",
        "spec": "journal.append:error@3",
        "trips": trips,
        "append_errors": append_errors,
        "corrupt_truncated": stats["corrupt_truncated"],
        "unclean_start": stats["unclean_start"],
        "replayed_ids": replayed_ids,
        "degraded_to_recompute": (
            append_errors >= 1
            and stats["corrupt_truncated"] >= 1
            and stats["unclean_start"]
            and replayed_ids == ["a"]  # the lost reply recomputes...
            and lost_recomputes
            and survivor == "Negative"  # ...the durable one dedups
        ),
    }


def _preempt_scenario() -> dict:
    """Injected ``scheduler.preempt`` fault: the steal is abandoned
    BEFORE any slot mutation, so the run degrades to "no preemption this
    tick" — the victim keeps its slot, every request still answers, and
    the bytes match a clean staged-preemption run.  Never a half-zeroed
    slot."""
    from music_analyst_tpu.models.llama import (
        LlamaConfig,
        LlamaZeroShotClassifier,
    )
    from music_analyst_tpu.resilience import configure_faults, fault_stats
    from music_analyst_tpu.serving.decode_loop import ContinuousScheduler

    clf = LlamaZeroShotClassifier(
        config=LlamaConfig.tiny(), max_prompt_len=64
    )
    sched = ContinuousScheduler(
        clf, n_slots=1, prefill_chunk=16, prompt_region=64,
        max_new_tokens=8, max_queue=8, page_size=8, kv_pages=32,
        ttft_slo_ms=1.0,  # arm preemption; deadlines below stay generous
    )
    sched.warmup()

    def _staged(tag: str) -> dict:
        low = sched.submit(f"low-{tag}", "slow chaos ballad",
                           max_new_tokens=8, priority=1,
                           deadline_ms=60_000.0)
        for _ in range(32):
            sched._tick()
            slot = sched._slots[0]
            if slot is not None and slot.active and slot.steps > 0:
                break
        high = sched.submit(f"high-{tag}", "gold chaos chorus",
                            max_new_tokens=8, priority=5,
                            deadline_ms=60_000.0)
        sched.run_until_idle()
        out = {}
        for req in (low, high):
            resp = req.response or {}
            if not resp.get("ok"):
                raise RuntimeError(f"{req.id} failed: {resp.get('error')}")
            out[str(req.id).split("-")[0]] = resp["text"]
        return out

    start = time.perf_counter()
    clean = _staged("clean")
    preempts_clean = sched.stats()["preemptions"]
    configure_faults("scheduler.preempt:error@1+")
    try:
        faulted = _staged("faulted")
        trips = fault_stats()["scheduler.preempt"]["trips"]
    finally:
        configure_faults(None)
    elapsed = time.perf_counter() - start
    stats = sched.stats()
    return {
        "scenario": "scheduler_preempt_fault",
        "spec": "scheduler.preempt:error@1+",
        "preemptions_clean": preempts_clean,
        "preemptions_faulted": stats["preemptions"] - preempts_clean,
        "preempt_faults": stats["preempt_faults"],
        "trips": trips,
        "bytes_identical": faulted == clean,
        "all_answered": True,  # _staged raises otherwise
        "wall_s": round(elapsed, 4),
    }


def _kv_quant_scenario(n_requests: int) -> dict:
    """Injected ``kv_quant.dequant`` fault: the quantized read path is
    unavailable, so an int8 scheduler degrades to the unquantized paged
    pool at construction — before any page is written.  Replies must be
    byte-identical to a clean ``kv_quant="none"`` run, and the degrade
    must be visible in the serving stats' ``kv_quant`` block."""
    from music_analyst_tpu.models.llama import (
        LlamaConfig,
        LlamaZeroShotClassifier,
    )
    from music_analyst_tpu.resilience import configure_faults, fault_stats
    from music_analyst_tpu.serving.decode_loop import ContinuousScheduler

    clf = LlamaZeroShotClassifier(
        config=LlamaConfig.tiny(), max_prompt_len=64
    )
    prompts = [f"quantized chaos lyric {i}" for i in range(n_requests)]
    kw = dict(n_slots=2, prefill_chunk=16, prompt_region=64,
              max_new_tokens=4, max_queue=n_requests + 1)

    def _texts(sched):
        reqs = [
            sched.submit(i, p, max_new_tokens=4)
            for i, p in enumerate(prompts)
        ]
        sched.run_until_idle()
        out = []
        for req in reqs:
            resp = req.response or {}
            if not resp.get("ok"):
                raise RuntimeError(f"generate {req.id} failed: "
                                   f"{resp.get('error')}")
            out.append(resp["text"])
        return out

    clean = _texts(ContinuousScheduler(clf, kv_quant="none", **kw))
    start = time.perf_counter()
    configure_faults("kv_quant.dequant:error@1+")
    try:
        sched = ContinuousScheduler(clf, kv_quant="int8", **kw)
        trips = fault_stats()["kv_quant.dequant"]["trips"]
    finally:
        configure_faults(None)
    faulted = _texts(sched)
    elapsed = time.perf_counter() - start
    stats = sched.stats()["kv_quant"]
    return {
        "scenario": "kv_quant_dequant_fault",
        "spec": "kv_quant.dequant:error@1+",
        "requests": n_requests,
        "bytes_identical": faulted == clean,
        "degraded": stats["degraded"],
        "scheme_after": stats["scheme"],
        "trips": trips,
        "wall_s": round(elapsed, 4),
    }


def _ledger_jsonl_intact(path: str):
    """(intact, n_lines): every line newline-terminated and parseable —
    the engine ledger's O_APPEND single-write contract, same discipline
    the metrics plane is held to."""
    if not os.path.exists(path):
        return True, 0
    n = 0
    with open(path, "r", encoding="utf-8") as fh:
        for line in fh:
            if not line.endswith("\n"):
                return False, n
            try:
                json.loads(line)
            except json.JSONDecodeError:
                return False, n
            n += 1
    return True, n


def _ledger_flush_scenario(n_requests: int) -> dict:
    """Injected engine-ledger flush failure (site ``ledger.flush``):
    every JSONL append attempt fails, so the ledger degrades to counted
    ``ledger_drops`` — the generated bytes are identical to the clean
    flushing run, in-memory attribution keeps accumulating, and no torn
    ``engine_ledger.jsonl`` line ever lands (a failed flush writes
    nothing at all)."""
    from music_analyst_tpu.models.llama import (
        LlamaConfig,
        LlamaZeroShotClassifier,
    )
    from music_analyst_tpu.observability.engine_ledger import LEDGER_FILE
    from music_analyst_tpu.resilience import configure_faults, fault_stats
    from music_analyst_tpu.serving.decode_loop import ContinuousScheduler

    clf = LlamaZeroShotClassifier(
        config=LlamaConfig.tiny(), max_prompt_len=64
    )
    prompts = [f"ledger chaos lyric {i}" for i in range(n_requests)]

    def _run(tag: str, out_dir: str):
        sched = ContinuousScheduler(
            clf, n_slots=2, prefill_chunk=16, prompt_region=64,
            max_new_tokens=4, max_queue=n_requests + 1,
            ledger_interval_ms=10, ledger_dir=out_dir,
        )
        sched.warmup()
        reqs = [
            sched.submit(f"{tag}-{i}", p, max_new_tokens=4)
            for i, p in enumerate(prompts)
        ]
        sched.drain()  # synchronous: finishes the backlog, final flush
        texts = []
        for req in reqs:
            resp = req.response or {}
            if not resp.get("ok"):
                raise RuntimeError(f"generate {req.id} failed: "
                                   f"{resp.get('error')}")
            texts.append(resp["text"])
        return texts, sched.stats()["ledger"]

    with tempfile.TemporaryDirectory(prefix="chaos_ledger_") as base:
        clean_dir = os.path.join(base, "clean")
        faulted_dir = os.path.join(base, "faulted")
        os.makedirs(clean_dir)
        os.makedirs(faulted_dir)
        start = time.perf_counter()
        clean_texts, clean_snap = _run("clean", clean_dir)
        configure_faults("ledger.flush:error@1+")
        try:
            faulted_texts, faulted_snap = _run("faulted", faulted_dir)
            trips = fault_stats()["ledger.flush"]["trips"]
        finally:
            configure_faults(None)
        elapsed = time.perf_counter() - start
        clean_intact, clean_lines = _ledger_jsonl_intact(
            os.path.join(clean_dir, LEDGER_FILE)
        )
        faulted_intact, faulted_lines = _ledger_jsonl_intact(
            os.path.join(faulted_dir, LEDGER_FILE)
        )
    return {
        "scenario": "ledger_flush_fault",
        "spec": "ledger.flush:error@1+",
        "requests": n_requests,
        "bytes_identical": faulted_texts == clean_texts,
        "flushes_clean": clean_snap["flushes"],
        "ledger_drops": faulted_snap["ledger_drops"],
        "trips": trips,
        "clean_file_intact": clean_intact,
        "clean_file_lines": clean_lines,
        "faulted_file_lines": faulted_lines,
        "degraded_to_drops": (
            clean_snap["flushes"] >= 1
            and clean_snap["ledger_drops"] == 0
            and clean_intact and clean_lines == clean_snap["flushes"]
            and faulted_snap["flushes"] == 0
            and faulted_snap["ledger_drops"] == trips
            and trips > 0
            and faulted_snap["ticks"] > 0  # accounting survived the drops
            and faulted_intact and faulted_lines == 0
        ),
        "wall_s": round(elapsed, 4),
    }


def _cache_publish_scenario() -> dict:
    """Injected cache-publish failure (site ``corpus_cache.publish``): a
    transient rename fault on the weight-quantization cache's atomic
    publish is retried in place — the entry still lands, readable, with
    a counted recovery."""
    import numpy as np

    from music_analyst_tpu.engines.wq_cache import WqCacheWriter
    from music_analyst_tpu.resilience import (
        configure_faults,
        fault_stats,
        reset_retry_stats,
        retry_stats,
    )

    reset_retry_stats()
    with tempfile.TemporaryDirectory(prefix="chaos_wqcache_") as base:
        configure_faults("corpus_cache.publish:error@1")
        try:
            start = time.perf_counter()
            writer = WqCacheWriter(base, "chaos-entry")
            writer.add("layer/kernel", np.ones((2, 2), np.float32))
            published = writer.publish()
            elapsed = time.perf_counter() - start
            trips = fault_stats()["corpus_cache.publish"]["trips"]
        finally:
            configure_faults(None)
    counts = retry_stats().get("corpus_cache.publish", {})
    return {
        "scenario": "cache_publish_transient",
        "spec": "corpus_cache.publish:error@1",
        "published": bool(published),
        "trips": trips,
        "recoveries": counts.get("recoveries", 0),
        "recovered": bool(published) and trips == 1
        and counts.get("recoveries", 0) >= 1,
        "wall_s": round(elapsed, 4),
    }


def _compile_first_scenario() -> dict:
    """Injected first-compile failure (site ``compile.first``): the
    profiled-jit wrapper retries the lower/compile under its backoff
    policy, so a transient compiler-side failure costs one retry — the
    compiled result is numerically identical to a clean compile."""
    import jax.numpy as jnp
    import numpy as np

    from music_analyst_tpu.profiling.compile import profiled_jit
    from music_analyst_tpu.resilience import (
        configure_faults,
        fault_stats,
        reset_retry_stats,
        retry_stats,
    )

    reset_retry_stats()
    x = jnp.arange(16, dtype=jnp.float32)
    clean = np.asarray(profiled_jit(
        lambda v: v * 3.0 + 1.0, name="chaos_compile_clean"
    )(x))
    configure_faults("compile.first:error@1")
    try:
        start = time.perf_counter()
        faulted = np.asarray(profiled_jit(
            lambda v: v * 3.0 + 1.0, name="chaos_compile_faulted"
        )(x))
        elapsed = time.perf_counter() - start
        trips = fault_stats()["compile.first"]["trips"]
    finally:
        configure_faults(None)
    counts = retry_stats().get("compile.first", {})
    return {
        "scenario": "compile_first_transient",
        "spec": "compile.first:error@1",
        "bytes_identical": bool(np.array_equal(clean, faulted)),
        "trips": trips,
        "recoveries": counts.get("recoveries", 0),
        "recovered": trips == 1 and counts.get("recoveries", 0) >= 1
        and bool(np.array_equal(clean, faulted)),
        "wall_s": round(elapsed, 4),
    }


def _checkpoint_stream_scenario() -> dict:
    """Injected checkpoint-stream faults (sites ``checkpoint.load`` and
    ``h2d.transfer``): one transient trip on each stage of the streaming
    weight loader — the prefetch pipeline's per-stage retry re-runs the
    unit from scratch and the loaded tree is identical to a clean load."""
    import jax
    import numpy as np

    from music_analyst_tpu.engines.checkpoint import load_quantized_params
    from music_analyst_tpu.resilience import configure_faults, fault_stats

    rng = np.random.default_rng(7)
    weights = {
        f"layer{i}": {
            "kernel": rng.standard_normal((8, 8)).astype(np.float32)
        }
        for i in range(3)
    }

    def _unit_source():
        for unit, tree in weights.items():
            yield unit, [(f"{unit}/kernel", tree["kernel"])]

    def _leaves(tree):
        return [np.asarray(leaf) for leaf in jax.tree_util.tree_leaves(tree)]

    clean = _leaves(load_quantized_params(weights, _unit_source, "int8"))
    spec = "checkpoint.load:error@1;h2d.transfer:error@1"
    configure_faults(spec)
    try:
        start = time.perf_counter()
        faulted = _leaves(load_quantized_params(weights, _unit_source, "int8"))
        elapsed = time.perf_counter() - start
        stats = fault_stats()
        trips = sum(int(stats[s]["trips"])
                    for s in ("checkpoint.load", "h2d.transfer"))
    finally:
        configure_faults(None)
    identical = len(clean) == len(faulted) and all(
        np.array_equal(a, b) for a, b in zip(clean, faulted)
    )
    return {
        "scenario": "checkpoint_stream_transient",
        "spec": spec,
        "bytes_identical": identical,
        "trips": trips,
        "recovered": trips == 2 and identical,
        "wall_s": round(elapsed, 4),
    }


def _ollama_request_scenario() -> dict:
    """Injected HTTP failure (site ``ollama.request``): the classifier's
    network retry absorbs a transient request fault — the batch still
    labels every row (the reference implementation dies on the first
    HTTP error; SURVEY.md §5).  The endpoint is a stub: chaos runs under
    zero egress."""
    import requests

    from music_analyst_tpu.models.ollama import OllamaClassifier
    from music_analyst_tpu.resilience import (
        configure_faults,
        fault_stats,
        reset_retry_stats,
        retry_stats,
    )

    class _StubResponse:
        status_code = 200

        def raise_for_status(self) -> None:
            return None

        @staticmethod
        def json():
            return {"response": "Positive"}

    reset_retry_stats()
    clf = OllamaClassifier(
        model="chaos-stub", retries=2, backoff_seconds=0.01
    )
    real_post = requests.post
    requests.post = lambda *args, **kwargs: _StubResponse()
    configure_faults("ollama.request:error@1")
    try:
        start = time.perf_counter()
        labels = clf.classify_batch(["happy happy chaos song"])
        elapsed = time.perf_counter() - start
        trips = fault_stats()["ollama.request"]["trips"]
    finally:
        requests.post = real_post
        configure_faults(None)
    counts = retry_stats().get("ollama.request", {})
    return {
        "scenario": "ollama_request_transient",
        "spec": "ollama.request:error@1",
        "labels": labels,
        "trips": trips,
        "recoveries": counts.get("recoveries", 0),
        "recovered": labels == ["Positive"] and trips == 1
        and counts.get("recoveries", 0) >= 1,
        "wall_s": round(elapsed, 4),
    }


def _response_cache_scenario(n_requests: int) -> dict:
    """Injected response-cache I/O faults (sites ``response_cache.read``
    and ``response_cache.write``): a faulted disk read degrades to
    recompute — byte-identical replies, counted ``read_fallbacks``, the
    on-disk entry NOT evicted (the next read may succeed) — and a
    faulted publish leaves the settle uncached (``write_errors``).  The
    cache can make an answer cheaper, never different."""
    from music_analyst_tpu.resilience import configure_faults, fault_stats
    from music_analyst_tpu.serving.batcher import DynamicBatcher
    from music_analyst_tpu.models.backend import ModelResidency
    from music_analyst_tpu.serving.response_cache import ResponseCache
    from music_analyst_tpu.serving.server import build_ops

    residency = ModelResidency(model="mock", mock=True)
    clf = residency.acquire()
    residency.warmup(8)
    ops = build_ops(clf)
    texts = [
        f"chaos cache lyric number {i} sunshine sorrow"
        for i in range(n_requests)
    ]

    def _replies(cache):
        batcher = DynamicBatcher(
            ops, max_batch=8, max_wait_ms=2.0,
            max_queue=n_requests + 1, response_cache=cache,
        ).start()
        reqs = [
            batcher.submit(i, "sentiment", t)
            for i, t in enumerate(texts)
        ]
        for req in reqs:
            if not req.wait(timeout=60.0):
                raise RuntimeError(f"request {req.id} never settled")
        batcher.drain()
        return [
            {k: v for k, v in (req.response or {}).items() if k != "id"}
            for req in reqs
        ]

    start = time.perf_counter()
    with tempfile.TemporaryDirectory(prefix="chaos_rcache_") as tmp:
        rc_dir = os.path.join(tmp, "cache")
        writer = ResponseCache(rc_dir, fingerprint="chaos")
        clean = _replies(writer)  # cold: computes + publishes every entry
        stores = writer.stats()["stores"]

        # Faulted reads against a fresh instance (cold memory tier, so
        # every lookup goes to disk): all degrade to recompute.
        reader = ResponseCache(rc_dir, fingerprint="chaos")
        configure_faults("response_cache.read:error@1+")
        try:
            faulted = _replies(reader)
            read_trips = sum(
                int(i.get("trips", 0)) for i in fault_stats().values()
            )
        finally:
            configure_faults(None)
        read_stats = reader.stats()

        # Faulted publishes into an empty dir: replies settle uncached.
        writer2 = ResponseCache(os.path.join(tmp, "wfault"),
                                fingerprint="chaos")
        configure_faults("response_cache.write:error@1+")
        try:
            wrote = _replies(writer2)
            write_trips = sum(
                int(i.get("trips", 0)) for i in fault_stats().values()
            )
        finally:
            configure_faults(None)
        write_stats = writer2.stats()
    elapsed = time.perf_counter() - start

    return {
        "scenario": "response_cache_io",
        "spec": ("response_cache.read:error@1+"
                 ";response_cache.write:error@1+"),
        "requests": n_requests,
        "stores": stores,
        "bytes_identical": faulted == clean and wrote == clean,
        "read_fallbacks": read_stats["read_fallbacks"],
        "hits_while_read_faulted": read_stats["hits"],
        "entries_evicted_by_fault": read_stats["corrupt"],
        "degraded_to_recompute": (
            read_stats["read_fallbacks"] == n_requests
            and read_stats["hits"] == 0
            and read_stats["corrupt"] == 0
        ),
        "write_errors": write_stats["write_errors"],
        "writes_degraded_uncached": (
            write_stats["write_errors"] == n_requests
            and write_stats["stores"] == 0
        ),
        "read_trips": read_trips,
        "write_trips": write_trips,
        "wall_s": round(elapsed, 4),
    }


@suite("chaos")
def run() -> dict:
    from music_analyst_tpu.resilience import (
        configure_faults,
        fault_stats,
        reset_retry_stats,
        retry_stats,
    )

    n_rows, chunk_songs = (200, 64) if smoke() else (20_000, 2_048)

    scenarios = []
    with tempfile.TemporaryDirectory(prefix="chaos_bench_") as tmp:
        dataset = os.path.join(tmp, "songs.csv")
        _write_corpus(dataset, n_rows)

        configure_faults(None)
        # Untimed warm-up: pay first-compile once, so the clean baseline
        # and the injected runs compare steady-state against steady-state
        # and recovery_overhead_s isolates the retry cost.
        _run_once(dataset, os.path.join(tmp, "warmup"), chunk_songs)
        clean_s, clean_bytes = _run_once(
            dataset, os.path.join(tmp, "clean"), chunk_songs
        )
        print(f"[chaos] clean baseline: {clean_s:.3f}s "
              f"({n_rows} rows)", file=sys.stderr)

        for name, spec in _SCENARIOS:
            reset_retry_stats()
            configure_faults(spec)
            try:
                wall_s, got = _run_once(
                    dataset, os.path.join(tmp, name), chunk_songs
                )
                faults = fault_stats()  # before disarm clears the registry
            finally:
                configure_faults(None)
            identical = got == clean_bytes
            trips = sum(
                int(info.get("trips", 0)) for info in faults.values()
            )
            retries = {
                site: counts
                for site, counts in retry_stats().items()
                if counts.get("retries")
            }
            row = {
                "scenario": name,
                "spec": spec,
                "bytes_identical": identical,
                "trips": trips,
                "retries": retries,
                "wall_s": round(wall_s, 4),
                "recovery_overhead_s": round(wall_s - clean_s, 4),
            }
            scenarios.append(row)
            print(
                f"[chaos] {name}: identical={identical} trips={trips} "
                f"overhead={row['recovery_overhead_s']:+.3f}s",
                file=sys.stderr,
            )

        serving = _serving_scenario(64 if smoke() else 512)
        print(
            f"[chaos] serving: answered={serving['all_answered']} "
            f"wall={serving['wall_s']:.3f}s",
            file=sys.stderr,
        )

        decode = _decode_scenario(4 if smoke() else 16)
        print(
            f"[chaos] decode: answered={decode['all_answered']} "
            f"wall={decode['wall_s']:.3f}s",
            file=sys.stderr,
        )

        router = _router_scenario(32 if smoke() else 256)
        print(
            f"[chaos] router: answered={router['all_answered']} "
            f"wall={router['wall_s']:.3f}s",
            file=sys.stderr,
        )

        prefix = _prefix_lookup_scenario(4 if smoke() else 16)
        print(
            f"[chaos] prefix_lookup: identical="
            f"{prefix['bytes_identical']} fallbacks={prefix['fallbacks']} "
            f"wall={prefix['wall_s']:.3f}s",
            file=sys.stderr,
        )

        spec_draft = _spec_draft_scenario(4 if smoke() else 16)
        print(
            f"[chaos] spec_draft: identical="
            f"{spec_draft['bytes_identical']} "
            f"fallbacks={spec_draft['fallbacks']} "
            f"wall={spec_draft['wall_s']:.3f}s",
            file=sys.stderr,
        )

        preempt = _preempt_scenario()
        print(
            f"[chaos] preempt_fault: identical="
            f"{preempt['bytes_identical']} "
            f"faults={preempt['preempt_faults']} "
            f"wall={preempt['wall_s']:.3f}s",
            file=sys.stderr,
        )

        kv_quant = _kv_quant_scenario(4 if smoke() else 16)
        print(
            f"[chaos] kv_quant: identical="
            f"{kv_quant['bytes_identical']} "
            f"degraded={kv_quant['degraded']} "
            f"wall={kv_quant['wall_s']:.3f}s",
            file=sys.stderr,
        )

        journal_wal = _journal_scenario()
        print(
            f"[chaos] journal_append: degraded_to_recompute="
            f"{journal_wal['degraded_to_recompute']} "
            f"corrupt={journal_wal['corrupt_truncated']}",
            file=sys.stderr,
        )

        reqtrace_flush = _reqtrace_flush_scenario(16 if smoke() else 128)
        print(
            f"[chaos] reqtrace_flush: identical="
            f"{reqtrace_flush['bytes_identical']} "
            f"drops={reqtrace_flush['trace_drops']} "
            f"degraded={reqtrace_flush['degraded_to_drops']}",
            file=sys.stderr,
        )

        metrics_scrape = _metrics_scrape_scenario(16 if smoke() else 128)
        print(
            f"[chaos] metrics_scrape: identical="
            f"{metrics_scrape['bytes_identical']} "
            f"scrape_errors={metrics_scrape['scrape_errors']} "
            f"degraded={metrics_scrape['degraded_to_stale']}",
            file=sys.stderr,
        )

        ledger_flush = _ledger_flush_scenario(4 if smoke() else 16)
        print(
            f"[chaos] ledger_flush: identical="
            f"{ledger_flush['bytes_identical']} "
            f"drops={ledger_flush['ledger_drops']} "
            f"degraded={ledger_flush['degraded_to_drops']}",
            file=sys.stderr,
        )

        cache_publish = _cache_publish_scenario()
        print(
            f"[chaos] cache_publish: recovered="
            f"{cache_publish['recovered']}",
            file=sys.stderr,
        )

        response_cache = _response_cache_scenario(16 if smoke() else 128)
        print(
            f"[chaos] response_cache: identical="
            f"{response_cache['bytes_identical']} "
            f"read_fallbacks={response_cache['read_fallbacks']} "
            f"write_errors={response_cache['write_errors']}",
            file=sys.stderr,
        )

        compile_first = _compile_first_scenario()
        print(
            f"[chaos] compile_first: recovered="
            f"{compile_first['recovered']}",
            file=sys.stderr,
        )

        checkpoint_stream = _checkpoint_stream_scenario()
        print(
            f"[chaos] checkpoint_stream: identical="
            f"{checkpoint_stream['bytes_identical']} "
            f"trips={checkpoint_stream['trips']}",
            file=sys.stderr,
        )

        ollama_request = _ollama_request_scenario()
        print(
            f"[chaos] ollama_request: recovered="
            f"{ollama_request['recovered']}",
            file=sys.stderr,
        )

    reset_retry_stats()
    return {
        "suite": "chaos",
        "device": device_info(),
        "smoke": smoke(),
        "rows": n_rows,
        "chunk_songs": chunk_songs,
        "clean_wall_s": round(clean_s, 4),
        "scenarios": scenarios,
        "serving": serving,
        "decode": decode,
        "router": router,
        "prefix_lookup": prefix,
        "spec_draft": spec_draft,
        "preempt_fault": preempt,
        "kv_quant_fault": kv_quant,
        "journal_append": journal_wal,
        "reqtrace_flush": reqtrace_flush,
        "metrics_scrape": metrics_scrape,
        "ledger_flush": ledger_flush,
        "cache_publish": cache_publish,
        "response_cache": response_cache,
        "compile_first": compile_first,
        "checkpoint_stream": checkpoint_stream,
        "ollama_request": ollama_request,
        "all_identical": all(
            s["bytes_identical"] for s in scenarios
        ) and prefix["bytes_identical"] and spec_draft["bytes_identical"]
        and preempt["bytes_identical"]
        and kv_quant["bytes_identical"]
        and reqtrace_flush["bytes_identical"]
        and metrics_scrape["bytes_identical"]
        and ledger_flush["bytes_identical"]
        and response_cache["bytes_identical"]
        and compile_first["bytes_identical"]
        and checkpoint_stream["bytes_identical"],
        "all_recovered": all(
            s["trips"] > 0 for s in scenarios
        ) and serving["all_answered"] and decode["all_answered"]
        and router["all_answered"] and prefix["all_fell_back"]
        and spec_draft["all_fell_back"]
        and preempt["preempt_faults"] > 0
        and preempt["preemptions_faulted"] == 0
        and kv_quant["degraded"]
        and journal_wal["degraded_to_recompute"]
        and reqtrace_flush["degraded_to_drops"]
        and metrics_scrape["degraded_to_stale"]
        and ledger_flush["degraded_to_drops"]
        and cache_publish["recovered"]
        and response_cache["degraded_to_recompute"]
        and response_cache["writes_degraded_uncached"]
        and compile_first["recovered"]
        and checkpoint_stream["recovered"]
        and ollama_request["recovered"],
    }
