"""Batched sentiment pipeline (``sentiment_classifier.py`` parity).

Where the reference classifies one song per blocking HTTP round-trip
(``scripts/sentiment_classifier.py:144-154``), this engine batches songs and
dispatches whole batches to an on-device classifier backend:

* ``mock``   — the vectorized keyword kernel (``ops/keyword_sentiment.py``);
* ``distilbert`` — encoder classifier (``models/distilbert.py``);
* ``llama`` / ``kanana`` — zero-shot decoder LM (``models/llama.py``;
  ``kanana-2-30b-a3b`` is latent attention + sigmoid-routed experts).

Which families exist and what each takes is ``models/backend.py``'s table;
this engine builds its backend through that module's ``ModelResidency``.

Outputs are byte-for-byte the reference artifact formats:
``sentiment_totals.json`` (label→count, 2-space JSON) and
``sentiment_details.csv`` (``artist,song,label,latency_seconds`` with
4-decimal latency) — ``scripts/sentiment_classifier.py:156-164``.
"""

from __future__ import annotations

import contextlib
import csv
import dataclasses
import json
import os
import time
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

from music_analyst_tpu.data.csv_io import iter_songs
from music_analyst_tpu.models.backend import (
    ClassifierBackend,
    ModelResidency,
    has_buckets,
)
# Part of this module's surface: callers that build a backend themselves
# (the benchmark's drivers) import ``get_backend`` from here.
from music_analyst_tpu.models.backend import get_backend  # noqa: F401
from music_analyst_tpu.observability import watchdog
from music_analyst_tpu.runtime import (
    PrefetchPipeline,
    Stage,
    resolve_prefetch_depth,
)
from music_analyst_tpu.resilience.failover import run_with_failover
from music_analyst_tpu.resilience.faults import fault_point
from music_analyst_tpu.telemetry import get_telemetry
from music_analyst_tpu.utils.atomic import atomic_write
from music_analyst_tpu.utils.labels import SUPPORTED_LABELS


@dataclasses.dataclass
class SentimentRow:
    artist: str
    song: str
    label: str
    latency_seconds: float


@dataclasses.dataclass
class SentimentResult:
    counts: Dict[str, int]
    rows: List[SentimentRow]
    output_paths: Dict[str, str]
    songs_per_second: float


def _read_completed_details(details_path: str) -> Tuple[int, Dict[str, int]]:
    """Rows already classified in a previous (partial) run + their counts.

    A kill can land mid-write, leaving a torn final row (the writer flushes
    per batch, but the OS doesn't promise line atomicity).  Truncate the
    file to the last newline at even quote parity — a newline inside an
    open quoted field (multi-line artist/song) is row *content*, not a row
    end — so the torn row is re-classified instead of being counted done
    and appended onto.
    """
    with open(details_path, "rb+") as raw:
        # One forward streaming pass in bounded chunks: a newline is a row
        # boundary iff the quote count of the prefix ending there is even
        # ('""' escapes contribute two quotes, preserving parity; a newline
        # inside an open quoted field is row content).  Track the last such
        # boundary — everything after it is the torn row.  No copy of a
        # multi-GB details file is ever materialized.
        keep = 0
        quotes = 0
        size = 0
        while chunk := raw.read(1 << 22):
            start = 0
            while (nl := chunk.find(b"\n", start)) >= 0:
                quotes += chunk.count(b'"', start, nl)
                if quotes % 2 == 0:
                    keep = size + nl + 1
                start = nl + 1
            quotes += chunk.count(b'"', start)
            size += len(chunk)
        if keep != size:
            raw.truncate(keep)
    done = 0
    counts: Dict[str, int] = {label: 0 for label in SUPPORTED_LABELS}
    with open(details_path, newline="", encoding="utf-8") as fh:
        for row in csv.DictReader(fh):
            label = row.get("label", "")
            if label in counts:
                counts[label] += 1
            done += 1
    return done, counts


def run_sentiment(
    dataset_path: str,
    model: str = "mock",
    mock: bool = False,
    limit: Optional[int] = None,
    output_dir: str = "output",
    batch_size: int = 4096,
    backend: Optional[ClassifierBackend] = None,
    quiet: bool = False,
    resume: bool = False,
    songs: Optional[Iterable[Tuple[str, str, str]]] = None,
    mesh=None,
    length_buckets: Optional[Sequence[int]] = None,
    prefetch_depth: Optional[int] = None,
    weight_quant: Optional[str] = None,
) -> SentimentResult:
    """Classify the dataset and write the reference output artifacts.

    Rows stream into ``sentiment_details.csv`` as each batch completes, so a
    killed run leaves a valid prefix on disk; ``resume=True`` picks up from
    it (skipping already-classified rows and seeding the totals).  The
    reference has no recovery at all — every failure recomputes from the CSV
    (SURVEY.md §5 "Checkpoint/resume: none").

    ``songs`` overrides the dataset read with an already-parsed iterable of
    ``(artist, song, text)`` rows — the fused joint pipeline passes the
    records its single ingest captured, so the file is opened once per run
    (``limit`` is ignored then; the producer already applied it).

    ``prefetch_depth`` bounds how many batches ride ahead of the device in
    the tokenize→transfer pipeline (``--prefetch-depth``; default 2 via
    ``$MUSICAAL_PREFETCH_DEPTH``); 0 disables overlap entirely.  Output
    artifacts are byte-identical at every depth — only wall time changes.
    """
    if songs is not None and resume:
        # The resume skip count indexes the DictReader row order of a prior
        # standalone run; a captured-records stream uses the exact parser,
        # which counts malformed rows differently — mixing the two would
        # silently misattribute rows.  Checked before any output file is
        # touched.
        raise ValueError("resume=True cannot be combined with songs=")
    tel = get_telemetry()
    with tel.run_scope("sentiment", output_dir):
        return _run_sentiment_impl(
            tel, dataset_path, model, mock, limit, output_dir, batch_size,
            backend, quiet, resume, songs, mesh, length_buckets,
            prefetch_depth, weight_quant,
        )


def _run_sentiment_impl(
    tel, dataset_path, model, mock, limit, output_dir, batch_size,
    backend, quiet, resume, songs, mesh, length_buckets,
    prefetch_depth, weight_quant=None,
) -> SentimentResult:
    os.makedirs(output_dir, exist_ok=True)
    depth = resolve_prefetch_depth(prefetch_depth)
    if backend is not None and (
            mesh is not None or has_buckets(length_buckets)
            or weight_quant not in (None, "none")):
        # An injected backend was constructed by the caller; silently
        # dropping construction-time options here would be a lie.
        raise ValueError(
            "mesh=/length_buckets=/weight_quant= configure backend "
            "construction and cannot be combined with an explicit "
            "backend="
        )
    # One owner for the backend lifetime, batch runs included: the
    # device-loss recovery below reloads through the same object the
    # server's failover hook uses (models/backend.py).
    residency = ModelResidency(
        model=model, mock=mock, weight_quant=weight_quant, mesh=mesh,
        backend=backend, length_buckets=length_buckets,
    )
    with tel.span("backend_init", model=model, mock=bool(mock)):
        clf = residency.acquire()
    tel.annotate(backend=clf.name, batch_size=batch_size, prefetch_depth=depth)

    totals_path = os.path.join(output_dir, "sentiment_totals.json")
    details_path = os.path.join(output_dir, "sentiment_details.csv")

    skip = 0
    counts: Dict[str, int] = {label: 0 for label in SUPPORTED_LABELS}
    if resume and os.path.exists(details_path):
        skip, counts = _read_completed_details(details_path)

    rows: List[SentimentRow] = []  # rows classified by THIS run
    start = time.perf_counter()

    details_fh = open(
        details_path, "a" if skip else "w", newline="", encoding="utf-8"
    )
    writer = csv.DictWriter(
        details_fh, fieldnames=["artist", "song", "label", "latency_seconds"]
    )
    if not skip:
        writer.writeheader()

    def finish(index, rows_batch, handle, t_submit, measured) -> None:
        with tel.span("compute", rows=len(rows_batch), batch=index):
            # collect() is the device-blocking edge — a wedged device
            # hangs here without erroring; let the watchdog classify
            # that as device_stall instead of silence.  On a
            # CLASSIFIED device loss the batch is re-submitted once —
            # through a freshly-built backend when this engine owns
            # backend construction — before the failure propagates.
            state = {"handle": handle}

            def _collect():
                with watchdog.watch("sentiment.collect", kind="device"):
                    return clf.collect(state["handle"])

            def _reinit():
                nonlocal clf
                if backend is None:
                    clf = residency.reload()
                state["handle"] = clf.submit(
                    [text for _, _, text in rows_batch]
                )

            labels = run_with_failover(
                _collect, site="sentiment.collect", reinit=_reinit
            )
        elapsed = time.perf_counter() - t_submit
        # Submit→collect wall time per batch — the batched analogue of the
        # reference's per-song HTTP latency column.
        tel.observe("sentiment.batch_seconds", elapsed)
        tel.count("rows_classified", len(rows_batch))
        # Per-song latency: exact when the backend measures it (Ollama
        # passthrough), amortized batch time for device backends, 0.0 for
        # mock — matching the reference's per-row semantics.
        per_song = (
            elapsed / max(1, len(rows_batch)) if clf.reports_latency else 0.0
        )
        with tel.span("write", rows=len(rows_batch), batch=index):
            for i, ((artist, song, text), label) in enumerate(
                zip(rows_batch, labels)
            ):
                if measured and len(measured) == len(rows_batch):
                    latency = measured[i]
                else:
                    latency = 0.0 if not text.strip() else per_song
                counts[label] += 1
                rows.append(SentimentRow(artist, song, label, latency))
                writer.writerow(
                    {
                        "artist": artist,
                        "song": song,
                        "label": label,
                        "latency_seconds": f"{latency:.4f}",
                    }
                )
            details_fh.flush()

    def batches(source):
        batch: List[Tuple[str, str, str]] = []
        for idx, row in enumerate(source):
            if idx < skip:
                continue
            batch.append(row)
            if len(batch) >= batch_size:
                yield batch
                batch = []
        if batch:
            yield batch

    # Duck-typed backends (test doubles, user plugins) predate the staged
    # hooks — the historical floor is submit/collect, so missing hooks
    # degrade to that exact behavior: everything happens in the launch
    # stage, with no tokenize/transfer overlap.
    clf_prepare = getattr(clf, "prepare", None) or (lambda texts: texts)
    clf_transfer = getattr(clf, "transfer", None) or (lambda prepared: prepared)
    clf_launch = getattr(clf, "launch", None) or clf.submit

    def tokenize_stage(rows_batch):
        # Host half only: tokenization + batch planning.  Device dispatch
        # happens downstream so a slow tokenizer can't serialize the chip.
        texts = [text for _, _, text in rows_batch]
        return rows_batch, clf_prepare(texts)

    def h2d_stage(item):
        rows_batch, prepared = item
        # Injected h2d.transfer faults recover via the prefetch stage
        # retry (the whole stage body re-runs; launch is idempotent).
        fault_point("h2d.transfer", rows=len(rows_batch))
        t0 = time.perf_counter()
        handle = clf_launch(clf_transfer(prepared))
        # Snapshot measured latencies NOW: synchronous backends (Ollama)
        # classify inside launch() and overwrite last_latencies on the
        # next launch, which would mis-attribute them across batches.
        measured = getattr(clf, "last_latencies", None)
        return rows_batch, handle, t0, list(measured) if measured else None

    # Replaces the old hand-rolled one-deep submit/collect overlap: up to
    # ``depth`` batches tokenize and transfer ahead of the device, each hop
    # bounded (backpressure), stalls accounted per stage (the reference is
    # strictly serial, one HTTP call per song, SURVEY.md §3.2).
    pipe = PrefetchPipeline(
        [Stage("tokenize", tokenize_stage), Stage("h2d", h2d_stage)],
        depth=depth,
        name="pipeline",
        sink_name="compute",
    )
    # The pipeline records one ``read`` span per batch around the source.
    source = songs if songs is not None else iter_songs(
        dataset_path, limit=limit
    )
    try:
        # closing(): a collect()/write error below must cancel and join the
        # pipeline threads, not leave them prefetching into a dead run.
        with contextlib.closing(pipe.run(batches(source))) as results:
            for index, item in enumerate(results):
                finish(index, *item)
    finally:
        details_fh.close()
    wall = time.perf_counter() - start

    with tel.span("write_totals"), atomic_write(totals_path) as fh:
        json.dump(counts, fh, indent=2)

    if not quiet:
        print("Sentiment summary:")
        for label in SUPPORTED_LABELS:
            print(f"  {label}: {counts[label]}")
        print(f"Detailed results -> {details_path}")
        print(f"Aggregated counts -> {totals_path}")

    return SentimentResult(
        counts=counts,
        rows=rows,
        output_paths={"totals": totals_path, "details": details_path},
        songs_per_second=(len(rows) / wall if wall > 0 else 0.0),
    )
