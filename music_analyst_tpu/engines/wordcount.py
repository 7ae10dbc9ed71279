"""The word/artist-count analysis engine (``bin/parallel_spotify`` parity).

Pipeline (cf. the reference call stack, SURVEY.md §3.1):

1. preprocessing — header labels + column split artifacts
   (``output/split_columns/<artist>.csv``, ``<text>.csv``), exactly like
   rank 0 of the reference (``src/parallel_spotify.c:778-828``);
2. host ingest — C++/Python tokenizer builds vocab + dense id arrays
   (replaces the per-rank byte-slice read loops, ``:918-998``);
3. device compute — id shards over the mesh ``dp`` axis, per-chip dense
   histogram, one ``psum`` (replaces hash-table Send/Recv + rank-0 merge,
   ``:1002-1065``);
4. export — count-desc/strcmp-asc sorted CSVs, console report, and
   ``performance_metrics.json`` with per-chip timings
   (``:1027-1053,1084-1109``).
"""

from __future__ import annotations

import dataclasses
import os
import time
from typing import List, Optional, Tuple

import numpy as np

from music_analyst_tpu.data.corpus_cache import resolve_cache_dir
from music_analyst_tpu.data.csv_io import sort_count_entries, write_count_csv
from music_analyst_tpu.data.ingest import IngestResult, ingest_dataset
from music_analyst_tpu.data.splitter import (
    read_header_labels,
    sanitize_header_name,
    split_dataset_columns,
)
from music_analyst_tpu.metrics.perf import TimeStats, write_performance_metrics
from music_analyst_tpu.observability import watchdog
from music_analyst_tpu.metrics.timer import StageTimer
from music_analyst_tpu.ops.histogram import (
    resolve_chunk_songs,
    sharded_histogram,
    sharded_histogram_hostlocal_timed,
    sharded_histogram_streaming,
)
from music_analyst_tpu.parallel.mesh import data_parallel_mesh
from music_analyst_tpu.profiling.trace import annotate
from music_analyst_tpu.resilience.failover import run_with_failover


@dataclasses.dataclass
class AnalysisResult:
    word_entries: List[Tuple[str, int]]    # sorted count-desc, tie bytewise-asc
    artist_entries: List[Tuple[str, int]]
    total_songs: int
    total_words: int
    timings: dict
    output_paths: dict
    # Measured per-chip compute seconds — identical to the metrics file's
    # per_chip column and the samples behind compute_time (ingest share +
    # the chip's own count/merge time).
    per_chip_compute: List[float] = dataclasses.field(default_factory=list)


def run_analysis(
    dataset_path: str,
    output_dir: str = "output",
    word_limit: int = 0,
    artist_limit: int = 0,
    limit: Optional[int] = None,
    mesh=None,
    write_split: bool = True,
    ingest_backend: str = "auto",
    count_mode: str = "host-shard",
    quiet: bool = False,
    corpus: Optional[IngestResult] = None,
    ingest_seconds: float = 0.0,
    corpus_cache_dir: Optional[str] = None,
    use_corpus_cache: bool = True,
    chunk_songs=None,
) -> AnalysisResult:
    """Run the full analysis and write the reference's output artifacts.

    ``corpus`` supplies an already-ingested dataset (the fused joint
    pipeline parses once and shares the result); ``ingest_seconds`` is then
    the caller's measured ingest time, folded into the timing stats exactly
    as an in-engine ingest would be.

    ``corpus_cache_dir``/``use_corpus_cache`` control the persistent
    ingest cache (``data/corpus_cache.py``); ``chunk_songs`` selects the
    chunked streaming device path (``None`` = auto by corpus size, ``0`` =
    off, ``N`` = songs per chunk).  Every combination writes byte-identical
    CSVs — they only move where time and memory are spent.
    """
    from music_analyst_tpu.telemetry import get_telemetry

    tel = get_telemetry()
    timer = StageTimer()
    os.makedirs(output_dir, exist_ok=True)
    split_dir = os.path.join(output_dir, "split_columns")

    with tel.run_scope("wordcount", output_dir):
        return _run_analysis_instrumented(
            tel, timer, dataset_path, output_dir, split_dir, word_limit,
            artist_limit, limit, mesh, write_split, ingest_backend,
            count_mode, quiet, corpus, ingest_seconds,
            resolve_cache_dir(corpus_cache_dir, use_corpus_cache),
            chunk_songs,
        )


def _run_analysis_instrumented(
    tel, timer, dataset_path, output_dir, split_dir, word_limit,
    artist_limit, limit, mesh, write_split, ingest_backend, count_mode,
    quiet, corpus, ingest_seconds, cache_dir, chunk_songs,
) -> AnalysisResult:
    with timer.stage("split"):
        if write_split:
            artist_label, text_label = read_header_labels(dataset_path)
            split_dataset_columns(
                dataset_path,
                split_dir,
                sanitize_header_name(artist_label),
                sanitize_header_name(text_label),
                artist_label,
                text_label,
            )

    if corpus is None:
        with timer.stage("ingest"):
            corpus = ingest_dataset(
                dataset_path, limit=limit, backend=ingest_backend,
                cache_dir=cache_dir,
            )
    else:
        timer.seconds["ingest"] = ingest_seconds

    default_mesh = mesh is None
    if mesh is None:
        mesh = data_parallel_mesh()

    n_chips = mesh.devices.size
    chunk = resolve_chunk_songs(
        chunk_songs, corpus.song_count, corpus.token_count
    )
    tel.count("songs_ingested", corpus.song_count)
    tel.count("words_counted", corpus.token_count)
    tel.annotate(
        mesh_shape={
            name: int(size)
            for name, size in zip(mesh.axis_names, mesh.devices.shape)
        },
        count_mode=count_mode,
        chunk_songs=chunk,
    )
    def _device_counts():
        # np.asarray is the synchronization point: block_until_ready is not
        # reliable on every PJRT plugin, and the engine needs the host
        # copies anyway.  "host-shard" (default, and the faster layout on
        # every corpus measured) counts each shard where it was ingested
        # and psums dense vectors (O(vocab) transfer); "device-ids" ships
        # the id stream to HBM and scatter-adds there — the right layout
        # when the ids are already device-resident (selectable via
        # ``analyze --count-mode``).
        if chunk > 0:
            # Streaming path: the word histogram (the O(tokens) payload)
            # walks bounded chunks through the prefetch pipeline — its
            # chips are lock-stepped, so its wall-clock is every shard's
            # share.  The artist histogram is O(songs), far too small for
            # chunking to pay, and staying host-local keeps the measured
            # per-shard timing spread.
            with annotate("wordcount.word_histogram"):
                t0 = time.perf_counter()
                word_counts = sharded_histogram_streaming(
                    corpus.word_ids, corpus.word_offsets,
                    max(1, len(corpus.word_vocab)), mesh,
                    chunk_songs=chunk,
                )
                word_wall = time.perf_counter() - t0
            with annotate("wordcount.artist_histogram"):
                artist_counts, artist_times = (
                    sharded_histogram_hostlocal_timed(
                        corpus.artist_ids, max(1, len(corpus.artist_vocab)),
                        mesh,
                    )
                )
            per_shard = [
                word_wall + a for a in artist_times.per_chip_seconds()
            ]
            dp_coord = np.indices(mesh.devices.shape)[
                mesh.axis_names.index("dp")
            ].flatten()
            per_chip_compute = [per_shard[c] for c in dp_coord]
        elif count_mode == "host-shard":
            with annotate("wordcount.word_histogram"):
                word_counts, word_times = sharded_histogram_hostlocal_timed(
                    corpus.word_ids, max(1, len(corpus.word_vocab)), mesh
                )
            with annotate("wordcount.artist_histogram"):
                artist_counts, artist_times = (
                    sharded_histogram_hostlocal_timed(
                        corpus.artist_ids, max(1, len(corpus.artist_vocab)),
                        mesh,
                    )
                )
            # Shard i's measured compute: its own count phases plus the
            # lock-stepped collective merges every chip sits in together.
            per_shard = [
                w + a
                for w, a in zip(
                    word_times.per_chip_seconds(),
                    artist_times.per_chip_seconds(),
                )
            ]
            # One timing per dp shard; on a multi-axis mesh every device in
            # a dp row shares its shard's time (the non-dp axes replicate
            # the histogram work).  Map by each device's dp coordinate so
            # per_chip always has exactly one entry per device.
            dp_coord = np.indices(mesh.devices.shape)[
                mesh.axis_names.index("dp")
            ].flatten()
            per_chip_compute = [per_shard[c] for c in dp_coord]
        else:
            with annotate("wordcount.word_histogram"):
                word_counts = np.asarray(
                    sharded_histogram(
                        corpus.word_ids, max(1, len(corpus.word_vocab)), mesh
                    )
                )
            with annotate("wordcount.artist_histogram"):
                artist_counts = np.asarray(
                    sharded_histogram(
                        corpus.artist_ids, max(1, len(corpus.artist_vocab)),
                        mesh,
                    )
                )
            # One fused SPMD program: chips are lock-stepped, so each
            # chip's compute IS the program wall-clock (documented
            # TimeStats.uniform semantics).
            per_chip_compute = None
        return word_counts, artist_counts, per_chip_compute

    def _reinit_mesh():
        # A fresh Mesh re-keys the cached psum programs, forcing a clean
        # lower+compile against the (possibly recovered) backend.  A
        # caller-supplied mesh is left alone — replacing it behind the
        # caller's back could change axis names mid-run.
        nonlocal mesh
        if default_mesh:
            mesh = data_parallel_mesh()

    with timer.stage("device_compute"), watchdog.watch(
        "wordcount.device_compute", kind="device"
    ):
        # Classified backend loss (backend_lost / device_stall / injected
        # transient) gets one re-init-and-retry; a second failure fails
        # the run before any artifact is written.
        word_counts, artist_counts, per_chip_compute = run_with_failover(
            _device_counts,
            site="wordcount.device_compute",
            reinit=_reinit_mesh,
        )
    if per_chip_compute is None:
        per_chip_compute = [timer.seconds["device_compute"]] * n_chips
    # Grand totals are already global on the host (the reference needs an
    # MPI_Reduce only because each rank holds a partial count).
    total_words = corpus.token_count
    total_songs = corpus.song_count

    with timer.stage("aggregate_export"):
        word_entries = sort_count_entries(
            corpus.word_vocab.counts_to_entries(word_counts)
        )
        artist_entries = sort_count_entries(
            corpus.artist_vocab.counts_to_entries(artist_counts)
        )
        word_path = os.path.join(output_dir, "word_counts.csv")
        artist_path = os.path.join(output_dir, "top_artists.csv")
        write_count_csv(word_path, "word", word_entries, word_limit)
        write_count_csv(artist_path, "artist", artist_entries, artist_limit)

    # Reference timing semantics (src/parallel_spotify.c:850-851,1000,1068):
    # compute = local read+count; total = compute + aggregation/export.
    # Each chip's compute = the shared host ingest (one pass serves every
    # chip — the single-controller analogue of each rank's read) plus its
    # own measured count/merge time, so the min/avg/max spread is real
    # (cf. the reference's six MPI_Reduce stats, :1077-1082).
    ingest_seconds = timer.seconds.get("ingest", 0.0)
    export_seconds = timer.seconds.get("aggregate_export", 0.0)
    # From here on, "per-chip compute" MEANS ingest share + own count/merge
    # — the same quantity compute_time aggregates and per_chip lists, so
    # the metrics file is internally consistent.
    per_chip_compute = [ingest_seconds + c for c in per_chip_compute]
    compute_time = TimeStats.from_samples(per_chip_compute)
    total_time = TimeStats.from_samples(
        [c + export_seconds for c in per_chip_compute]
    )
    metrics_path = os.path.join(output_dir, "performance_metrics.json")
    devices = mesh.devices.flatten().tolist()
    with tel.span("write_metrics"):
        write_performance_metrics(
            metrics_path,
            processes=len(devices),
            total_songs=total_songs,
            total_words=total_words,
            compute_time=compute_time,
            total_time=total_time,
            per_chip=[
                {
                    "device": str(d),
                    "platform": d.platform,
                    # 9 decimals: the per-shard spread is microseconds on
                    # small corpora; 6 would round distinct measurements
                    # together.
                    "compute_seconds": round(seconds, 9),
                }
                for d, seconds in zip(devices, per_chip_compute)
            ],
            stages=dict(timer.seconds),
            device_platform=devices[0].platform if devices else "unknown",
        )

    if not quiet:
        print("=== Parallel Spotify Analysis ===")
        print(f"Total songs processed: {total_songs}")
        print(f"Total words counted: {total_words}")
        preview_words = word_entries[:10]
        print(f"Top {len(preview_words)} words:")
        for key, value in preview_words:
            print(f"  {key}: {value}")
        preview_artists = artist_entries[:10]
        print(f"Top {len(preview_artists)} artists:")
        for key, value in preview_artists:
            print(f"  {key}: {value} songs")

    return AnalysisResult(
        word_entries=word_entries,
        artist_entries=artist_entries,
        total_songs=total_songs,
        total_words=total_words,
        timings=dict(timer.seconds),
        output_paths={
            "word_counts": word_path,
            "top_artists": artist_path,
            "performance_metrics": metrics_path,
            "split_dir": split_dir,
        },
        per_chip_compute=list(per_chip_compute),
    )
