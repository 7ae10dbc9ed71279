"""Bounded-depth staged pipeline executor (host↔device overlap).

Every engine used to hand-roll its own overlap: the sentiment engine kept
one batch in flight, the per-song counter managed a deque of pool
futures, bench.py had a third copy, and everything else ran ingest →
tokenize → transfer → compute strictly serially.  This module is the one
shared executor: a source iterator feeds a chain of stages, each stage
runs in its own thread (or worker pool) connected by bounded queues, and
the consumer iterates results **in submission order** while up to
``depth`` items per hop are in flight ahead of it.

Why bounded: the host tokenizer sustains ~15× the device throughput
(PERFORMANCE.md), so an unbounded queue would happily buffer the whole
corpus in RAM.  ``depth`` is the backpressure knob — each queue holds at
most ``depth`` items, so a fast producer blocks instead of ballooning,
and device memory holds at most ``depth + 1`` staged batches.

Failure contract (tests/test_runtime_pipeline.py):

* an exception in any stage (or in the source) is forwarded down the
  chain as a poison pill and re-raised in the consumer **promptly** — a
  failing stage can never deadlock the run, because every blocking queue
  operation is a cancellable poll loop;
* closing the consumer generator early cancels the pipeline, drains the
  queues, and joins every thread before returning.

Accounting: each stage tracks items, work seconds, **stall** seconds
(waiting for input — the upstream stage is the bottleneck), backpressure
seconds (waiting for output space — the downstream is), and the max
depth its input queue reached.  On completion the pipeline publishes
``<name>.<stage>_stall_s`` / ``<name>.<stage>_queue_depth_max`` gauges
plus a structured record (:meth:`Telemetry.record_pipeline`) that lands
in the run manifest's ``pipeline`` section, and per-item spans so the
overlap shows up in ``trace_spans.json`` next to everything else: one
``read`` per item around the source's ``next()`` (with ``rows`` where the
item is a list), one span per stage and item under the stage's name, and
one ``wait`` per item on the consumer's thread (the time it stood waiting
for that item).  Each carries its true start, ``pipeline=<name>``,
``seq=<item index>`` (the spans of one item share it) and, as
``parent_id``, the span that was open on the thread that called
:meth:`PrefetchPipeline.run`.

``depth=0`` runs the same stages inline (no threads, no overlap) — the
apples-to-apples baseline the ``overlap`` bench suite compares against.
"""

from __future__ import annotations

import os
import queue
import threading
import time
from collections import deque
from concurrent.futures import ThreadPoolExecutor
from dataclasses import dataclass
from typing import Any, Callable, Dict, Iterable, Iterator, List, Sequence

from music_analyst_tpu.observability import watchdog
from music_analyst_tpu.resilience.faults import fault_point
from music_analyst_tpu.resilience.policy import RetryPolicy
from music_analyst_tpu.telemetry import get_telemetry

# Stage bodies are retried on transiently-classified failures (a backend
# lost mid-run, injected prefetch.stage faults) before poisoning
# the pipeline; logic errors still fail on the first throw.  Shared by
# the threaded and inline (depth=0) paths — both go through _timed_fn.
_STAGE_RETRY = RetryPolicy(base_s=0.05, cap_s=1.0)

DEFAULT_PREFETCH_DEPTH = 2

# Cancellation poll period for blocking queue ops.  Long enough that the
# steady state pays ~zero wakeups, short enough that close() returns fast.
_POLL_S = 0.05

# Thread-join grace at shutdown.  Stages only block in cancellable poll
# loops or in user fns; a user fn that ignores the cancel for longer than
# this is left to finish as a daemon rather than hanging the caller.
_JOIN_S = 5.0

_DONE = object()          # end-of-stream sentinel
_CANCELLED = object()     # internal: a queue op gave up on cancellation


class _Failure:
    """Poison pill carrying a stage's exception down the chain."""

    __slots__ = ("exc",)

    def __init__(self, exc: BaseException) -> None:
        self.exc = exc


@dataclass
class Stage:
    """One pipeline hop: ``fn(item) -> item`` under a stable ``name``.

    ``workers > 1`` runs the stage on an internal thread pool with a
    bounded in-flight window; results still leave the stage in submission
    order (the per-song engine's old deque window, generalized).  Set
    ``record_spans=False`` when ``fn`` records its own telemetry span
    (avoids double-counting in ``top_spans``).
    """

    name: str
    fn: Callable[[Any], Any]
    workers: int = 1
    record_spans: bool = True


class StageStats:
    """Accounting for one stage (or the source/sink pseudo-stages)."""

    __slots__ = (
        "name", "items", "work_s", "stall_s", "backpressure_s",
        "queue_depth_max",
    )

    def __init__(self, name: str) -> None:
        self.name = name
        self.items = 0
        self.work_s = 0.0
        self.stall_s = 0.0
        self.backpressure_s = 0.0
        self.queue_depth_max = 0

    def as_dict(self) -> Dict[str, Any]:
        return {
            "stage": self.name,
            "items": self.items,
            "work_s": round(self.work_s, 6),
            "stall_s": round(self.stall_s, 6),
            "backpressure_s": round(self.backpressure_s, 6),
            "queue_depth_max": self.queue_depth_max,
        }


def resolve_prefetch_depth(
    value: Any = None, default: int = DEFAULT_PREFETCH_DEPTH
) -> int:
    """Resolve a ``--prefetch-depth`` value: explicit argument wins, then
    ``$MUSICAAL_PREFETCH_DEPTH``, then the default.  0 = no overlap."""
    if value is None:
        raw = os.environ.get("MUSICAAL_PREFETCH_DEPTH", "").strip()
        if not raw:
            return default
        value = raw
    try:
        depth = int(value)
    except (TypeError, ValueError):
        raise ValueError(
            f"prefetch depth must be an integer >= 0, got {value!r}"
        ) from None
    if depth < 0:
        raise ValueError(f"prefetch depth must be >= 0, got {depth}")
    return depth


class PrefetchPipeline:
    """Run ``source → stages… → consumer`` with ``depth`` items per hop.

    One-shot: build, iterate :meth:`run`, read :meth:`summary`.  The
    consumer sees results strictly in source order regardless of depth or
    per-stage worker count.
    """

    def __init__(
        self,
        stages: Sequence[Stage],
        depth: int = DEFAULT_PREFETCH_DEPTH,
        name: str = "pipeline",
        sink_name: str = "compute",
    ) -> None:
        if depth < 0:
            raise ValueError(f"depth must be >= 0, got {depth}")
        for stage in stages:
            if stage.workers < 1:
                raise ValueError(
                    f"stage {stage.name!r}: workers must be >= 1"
                )
        self.stages = list(stages)
        self.depth = depth
        self.name = name
        self._cancel = threading.Event()
        self._threads: List[threading.Thread] = []
        self._queues: List[queue.Queue] = []
        self._source_stats = StageStats("source")
        self._stage_stats = [StageStats(s.name) for s in self.stages]
        self._sink_stats = StageStats(sink_name)
        self._published = False
        self._parent_id = None  # span open in run()'s caller

    def _span(self, name: str, t0: float, dur: float, seq: int,
              **attrs: Any) -> None:
        """One per-item span: ``t0`` is its start on the monotonic clock."""
        get_telemetry().record_span(
            name, dur, t_mono=t0, parent_id=self._parent_id,
            pipeline=self.name, seq=seq, **attrs,
        )

    # ------------------------------------------------------- queue helpers

    def _put(self, q: queue.Queue, item: Any, stats: StageStats = None) -> bool:
        """Blocking put that respects cancellation; waiting time counts as
        the producing stage's backpressure.  Returns False on cancel."""
        t0 = time.perf_counter()
        while not self._cancel.is_set():
            try:
                q.put(item, timeout=_POLL_S)
            except queue.Full:
                continue
            if stats is not None:
                stats.backpressure_s += time.perf_counter() - t0
            return True
        return False

    def _get(self, q: queue.Queue, stats: StageStats = None) -> Any:
        """Blocking get that respects cancellation; waiting time counts as
        the consuming stage's input stall.  Returns ``_CANCELLED`` on
        cancel."""
        t0 = time.perf_counter()
        while not self._cancel.is_set():
            if stats is not None:
                stats.queue_depth_max = max(stats.queue_depth_max, q.qsize())
            try:
                item = q.get(timeout=_POLL_S)
            except queue.Empty:
                continue
            if stats is not None:
                stats.stall_s += time.perf_counter() - t0
            return item
        return _CANCELLED

    # ------------------------------------------------------------- threads

    def _pump(self, source: Iterable[Any], q_out: queue.Queue) -> None:
        """Feed the first queue from the source iterator.  Source read time
        is the pseudo-stage's work (an ingest-bound run shows up here)."""
        stats = self._source_stats
        it = iter(source)
        while True:
            t0 = time.monotonic()
            try:
                item = next(it)
            except StopIteration:
                self._put(q_out, _DONE, stats)
                return
            except BaseException as exc:  # forwarded, re-raised in consumer
                self._put(q_out, _Failure(exc), stats)
                return
            self._account_read(t0, item)
            if not self._put(q_out, item, stats):
                return

    def _account_read(self, t0: float, item: Any) -> None:
        stats = self._source_stats
        dur = time.monotonic() - t0
        stats.work_s += dur
        # a list is a batch of rows; a tuple is one record (a chunk's
        # bounds, a batch's arrays) and its length counts fields
        rows = {"rows": len(item)} if isinstance(item, list) else {}
        self._span("read", t0, dur, stats.items, **rows)
        stats.items += 1

    def _timed_fn(self, stage: Stage, item: Any):
        """Run one stage fn; returns ``(start, duration_s, result |
        _Failure)``, the start on the monotonic clock: a ``workers > 1``
        stage accounts a result when it leaves the window, later than the
        work ended.

        The watchdog scope around the call is what turns "the bench went
        silent" into ``taxonomy: stage_stall`` naming the exact stage —
        a no-op unless a watchdog is active.
        """
        t0 = time.monotonic()
        try:
            with watchdog.watch(f"{self.name}.{stage.name}", kind="stage"):
                result = _STAGE_RETRY.call(
                    self._stage_once, stage, item, site="prefetch.stage"
                )
        except BaseException as exc:
            result = _Failure(exc)
        return t0, time.monotonic() - t0, result

    def _stage_once(self, stage: Stage, item: Any) -> Any:
        fault_point("prefetch.stage", stage=stage.name, pipeline=self.name)
        return stage.fn(item)

    def _account(
        self, stage: Stage, stats: StageStats, t0: float, dur: float
    ) -> None:
        stats.work_s += dur
        if stage.record_spans:
            # results leave a stage in submission order, so the count of
            # items accounted so far is this item's index
            self._span(stage.name, t0, dur, stats.items)
        stats.items += 1

    def _stage_loop(
        self, stage: Stage, stats: StageStats,
        q_in: queue.Queue, q_out: queue.Queue,
    ) -> None:
        """Coordinator thread for one stage.

        ``workers == 1`` processes inline; ``workers > 1`` keeps a bounded
        window of pool futures and emits results in submission order, so
        downstream ordering never depends on worker scheduling.
        """
        pool = (
            ThreadPoolExecutor(
                max_workers=stage.workers,
                thread_name_prefix=f"{self.name}-{stage.name}",
            )
            if stage.workers > 1 else None
        )
        window: deque = deque()
        window_cap = stage.workers * 2

        def emit(t0: float, dur: float, result: Any) -> bool:
            """Account + forward one result; False ends the loop (either
            cancellation or a failure that poisons the chain)."""
            self._account(stage, stats, t0, dur)
            if not self._put(q_out, result, stats):
                return False
            return not isinstance(result, _Failure)

        try:
            while True:
                item = self._get(q_in, stats)
                if item is _CANCELLED:
                    return
                if item is _DONE or isinstance(item, _Failure):
                    while window:
                        if not emit(*window.popleft().result()):
                            return
                    self._put(q_out, item, stats)
                    return
                if pool is None:
                    if not emit(*self._timed_fn(stage, item)):
                        return
                else:
                    window.append(pool.submit(self._timed_fn, stage, item))
                    if len(window) >= window_cap:
                        if not emit(*window.popleft().result()):
                            return
        finally:
            if pool is not None:
                pool.shutdown(wait=False, cancel_futures=True)

    # ------------------------------------------------------------ shutdown

    def _shutdown(self) -> None:
        """Cancel, drain, join, publish.  Idempotent; never raises."""
        self._cancel.set()
        for q in self._queues:
            while True:
                try:
                    q.get_nowait()
                except queue.Empty:
                    break
        for thread in self._threads:
            thread.join(timeout=_JOIN_S)
        self._publish()

    def _publish(self) -> None:
        if self._published:
            return
        self._published = True
        tel = get_telemetry()
        summary = self.summary()
        for entry in summary["stages"]:
            prefix = f"{self.name}.{entry['stage']}"
            tel.gauge(f"{prefix}_stall_s", entry["stall_s"])
            if entry["queue_depth_max"]:
                tel.gauge(
                    f"{prefix}_queue_depth_max", entry["queue_depth_max"]
                )
        tel.record_pipeline(self.name, summary)

    def summary(self) -> Dict[str, Any]:
        """JSON-able stats: per-stage stall/work/backpressure seconds and
        queue-depth high-water marks (the manifest ``pipeline`` entry)."""
        stats = [self._source_stats, *self._stage_stats, self._sink_stats]
        return {
            "depth": self.depth,
            "stages": [s.as_dict() for s in stats],
            "max_queue_depth": max(s.queue_depth_max for s in stats),
        }

    # ----------------------------------------------------------------- run

    def run(self, source: Iterable[Any]) -> Iterator[Any]:
        """Yield each source item after it has passed through every stage.

        Results arrive in source order.  A stage/source exception re-raises
        here; closing the generator (break / caller exception) cancels and
        joins the pipeline before control returns.
        """
        stack = get_telemetry()._stack()
        self._parent_id = stack[-1].span_id if stack else None
        if self.depth == 0:
            yield from self._run_inline(source)
            return
        self._queues = [
            queue.Queue(maxsize=self.depth)
            for _ in range(len(self.stages) + 1)
        ]
        self._threads = [
            threading.Thread(
                target=self._pump, args=(source, self._queues[0]),
                name=f"{self.name}-source", daemon=True,
            )
        ]
        for i, stage in enumerate(self.stages):
            self._threads.append(
                threading.Thread(
                    target=self._stage_loop,
                    args=(
                        stage, self._stage_stats[i],
                        self._queues[i], self._queues[i + 1],
                    ),
                    name=f"{self.name}-{stage.name}",
                    daemon=True,
                )
            )
        for thread in self._threads:
            thread.start()
        sink = self._sink_stats
        try:
            while True:
                t0 = time.monotonic()
                item = self._get(self._queues[-1], sink)
                if item is _DONE or item is _CANCELLED:
                    return
                if isinstance(item, _Failure):
                    raise item.exc
                self._span("wait", t0, time.monotonic() - t0, sink.items)
                sink.items += 1
                t0 = time.perf_counter()
                yield item
                sink.work_s += time.perf_counter() - t0
        finally:
            self._shutdown()

    def _run_inline(self, source: Iterable[Any]) -> Iterator[Any]:
        """depth=0: same stages, same accounting, no threads, no overlap."""
        try:
            it = iter(source)
            while True:
                t_wait = time.monotonic()
                try:
                    item = next(it)
                except StopIteration:
                    return
                self._account_read(t_wait, item)
                for stage, stats in zip(self.stages, self._stage_stats):
                    t0, dur, item = self._timed_fn(stage, item)
                    self._account(stage, stats, t0, dur)
                    if isinstance(item, _Failure):
                        raise item.exc
                # no overlap: the consumer waits through the read and
                # every stage of its item
                self._span("wait", t_wait, time.monotonic() - t_wait,
                           self._sink_stats.items)
                self._sink_stats.items += 1
                t0 = time.perf_counter()
                yield item
                self._sink_stats.work_s += time.perf_counter() - t0
        finally:
            self._publish()
