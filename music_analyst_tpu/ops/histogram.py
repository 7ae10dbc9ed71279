"""Dense token-id histograms with a single ``psum`` reduction.

This op replaces the reference's entire aggregation machinery: per-rank
string hash tables (``src/parallel_spotify.c:38-175``), the serialized
Send/Recv wire protocol (``:396-432``), and the rank-0 sequential merge
(``:1011-1025``).  With ids dense on the host side (``data/vocab.py``), the
per-chip histogram is one scatter-add and the cross-chip merge is one
all-reduce over ICI — O(vocab) bytes in a single collective instead of
O(entries) point-to-point string messages.

Design note — why there is no Pallas histogram kernel: scatter-add over a
large vocabulary is sort-shaped, and XLA's TPU lowering of ``.at[].add``
already emits the sort-based segmented reduction that suits the hardware
(SURVEY.md §7 step 3 says "Pallas scatter-add if profiling demands" — it
doesn't: the wordcount path is host-ingest-bound, see ``engines/sweep``
timings).  A hand kernel would have to one-hot compare each id block
against the vocab (O(N·V) VPU work) — strictly worse than XLA's O(N log N).
The Pallas budget went to the ops where explicit locality wins:
``ops/flash_attention.py`` and ``ops/paged_attention.py``.
"""

from __future__ import annotations

import dataclasses
import time
from functools import lru_cache, partial
from typing import List, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import shard_map
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from music_analyst_tpu.profiling.collectives import record_collective
from music_analyst_tpu.profiling.compile import profiled_jit
from music_analyst_tpu.resilience.faults import fault_point
from music_analyst_tpu.utils.shapes import round_pow2

PAD_ID = -1


def _token_histogram(ids: jax.Array, vocab_size: int) -> jax.Array:
    """Count id occurrences; ``PAD_ID`` (any negative id) is ignored.

    One fused masked scatter-add; int32 counts (the per-word corpus bound is
    well under 2^31 even for the 1M-song dataset).
    """
    with jax.named_scope("histogram"):
        valid = ids >= 0
        clipped = jnp.where(valid, ids, 0)
        return jnp.zeros((vocab_size,), jnp.int32).at[clipped].add(
            valid.astype(jnp.int32), mode="drop"
        )


token_histogram = profiled_jit(
    _token_histogram, name="token_histogram",
    static_argnames=("vocab_size",),
)


def shard_pad(values: np.ndarray, shards: int, pad_value: int) -> np.ndarray:
    """Right-pad a flat array so it splits evenly into ``shards`` pieces."""
    n = values.shape[0]
    padded_len = max(1, -(-n // shards)) * shards
    if padded_len == n:
        return values
    out = np.full((padded_len,), pad_value, dtype=values.dtype)
    out[:n] = values
    return out


# Shared power-of-two shape policy (utils/shapes.py).
_bucket = round_pow2


def _bucket_linear(n: int, step: int) -> int:
    """Round up to a multiple of ``step``: bounded shape count with far
    less padding than power-of-two buckets (padding is transferred to the
    device, and host→device bandwidth is the wordcount bottleneck)."""
    return max(step, -(-n // step) * step)


# --- compiled-collective cache -------------------------------------------
#
# The shard_map callables below are built once per (mesh, axis[, vocab]) and
# memoized: constructing ``jax.jit(shard_map(lambda ...))`` inside every
# call would miss jit's own cache on every invocation (fresh lambda
# identity) and re-trace — which made sweep wall-times compilation-bound
# rather than scaling-meaningful.  ``Mesh`` is hashable by (devices, axis
# names), so it is a sound cache key; the handful of meshes a process ever
# builds bounds the cache.

@lru_cache(maxsize=None)
def _psum_ids_histogram(mesh: Mesh, axis: str, padded_vocab: int):
    def local(x):
        return jax.lax.psum(token_histogram(x, padded_vocab), axis)

    return profiled_jit(
        shard_map(local, mesh=mesh, in_specs=P(axis), out_specs=P()),
        name="psum_ids_histogram",
    )


@lru_cache(maxsize=None)
def _psum_rows(mesh: Mesh, axis: str):
    def local(h):
        return jax.lax.psum(h[0], axis)

    return profiled_jit(
        shard_map(local, mesh=mesh, in_specs=P(axis, None), out_specs=P()),
        name="psum_rows",
    )


@lru_cache(maxsize=None)
def _psum_scalar(mesh: Mesh, axis: str):
    def local(x):
        return jax.lax.psum(jnp.sum(x), axis)

    return profiled_jit(
        shard_map(local, mesh=mesh, in_specs=P(axis), out_specs=P()),
        name="psum_scalar",
    )


def sharded_histogram(
    ids: np.ndarray,
    vocab_size: int,
    mesh: Mesh,
    axis: str = "dp",
) -> jax.Array:
    """Global histogram of ``ids`` sharded over ``axis`` of ``mesh``.

    Each device scatter-adds its shard into a local dense vector, then one
    ``psum`` over ``axis`` produces the replicated global histogram — the
    TPU-native equivalent of the reference's hash-table shuffle + merge
    (SURVEY.md §2.4 key insight).

    Both the id-array length and the vocab size are bucketed to powers of
    two (padding ids are ignored, excess vocab slots read zero and are
    sliced off), so different corpora reuse the same compiled program.
    """
    ids = np.asarray(ids, dtype=np.int32)
    bucket_len = _bucket_linear(ids.shape[0], 1 << 22)
    padded = np.full((bucket_len,), PAD_ID, dtype=np.int32)
    padded[: ids.shape[0]] = ids
    padded = shard_pad(padded, mesh.shape[axis], PAD_ID)
    padded_vocab = _bucket(vocab_size, 1 << 10)
    # Each device all-reduces its padded_vocab-wide int32 histogram.
    record_collective(
        "histogram.device_ids", "psum",
        payload_bytes=padded_vocab * 4, n_devices=mesh.shape[axis],
        axis=axis,
    )
    fault_point("collective.psum", op="histogram.device_ids")
    return _psum_ids_histogram(mesh, axis, padded_vocab)(padded)[:vocab_size]


@dataclasses.dataclass(frozen=True)
class HistogramTimings:
    """Per-shard measured compute for the host-local histogram.

    ``count_seconds[i]`` is shard *i*'s own counting wall-clock — the honest
    analogue of each MPI rank timing its local count loop
    (``src/parallel_spotify.c:850-851,1000``); they genuinely differ across
    shards.  ``merge_seconds`` is the lock-stepped collective (every chip
    spends it together — one SPMD program).
    """

    count_seconds: Tuple[float, ...]
    merge_seconds: float

    def per_chip_seconds(self) -> List[float]:
        return [s + self.merge_seconds for s in self.count_seconds]


def sharded_histogram_hostlocal_timed(
    ids: np.ndarray,
    vocab_size: int,
    mesh: Mesh,
    axis: str = "dp",
) -> Tuple[np.ndarray, HistogramTimings]:
    """Histogram with host-local counting and a device ``psum`` merge.

    The locality structure of a multi-host deployment (and of the
    reference): each shard's ids are counted where they were ingested and
    only dense count vectors cross to the device for the collective merge.
    Per-shard transfer is O(vocab), not O(tokens) — the right trade when
    the token matrix has no other reason to be device-resident (the
    ``sharded_histogram`` ids-on-device path serves the joint pipeline,
    where it does).

    Returns the counts plus measured :class:`HistogramTimings` (each
    shard's count phase timed individually — the per-rank timing column the
    metrics writer reports).
    """
    ids = np.asarray(ids, dtype=np.int32)
    shards = mesh.shape[axis]
    padded_vocab = _bucket(vocab_size, 1 << 10)
    chunks = np.array_split(ids, shards)
    local = np.zeros((shards, padded_vocab), dtype=np.int32)
    count_seconds = []
    for i, chunk in enumerate(chunks):
        t0 = time.perf_counter()
        valid = chunk[chunk >= 0]
        if valid.size:
            local[i] = np.bincount(valid, minlength=padded_vocab)
        count_seconds.append(time.perf_counter() - t0)
    record_collective(
        "histogram.hostlocal_merge", "psum",
        payload_bytes=padded_vocab * 4, n_devices=shards, axis=axis,
    )
    t0 = time.perf_counter()
    fault_point("collective.psum", op="histogram.hostlocal_merge")
    # np.asarray IS the sync point: the host needs the counts anyway.
    merged = np.asarray(_psum_rows(mesh, axis)(local))[:vocab_size]
    merge_seconds = time.perf_counter() - t0
    return merged, HistogramTimings(tuple(count_seconds), merge_seconds)


def sharded_histogram_hostlocal(
    ids: np.ndarray,
    vocab_size: int,
    mesh: Mesh,
    axis: str = "dp",
) -> np.ndarray:
    """:func:`sharded_histogram_hostlocal_timed` without the timings."""
    counts, _ = sharded_histogram_hostlocal_timed(ids, vocab_size, mesh, axis)
    return counts


# --- chunked streaming device path ----------------------------------------
#
# ``sharded_histogram`` device-puts the whole id stream at once: simple,
# but peak host+device memory is O(corpus).  The streaming path below
# instead walks fixed-size song-aligned chunks through the shared
# ``runtime/prefetch.py`` pipeline — pad (host) → H2D → accumulate into a
# per-chip dense histogram — and pays the single ``psum`` only once at the
# end.  Chunk lengths are power-of-two bucketed, so every chunk reuses ONE
# compiled accumulate program, and the H2D of chunk k+1 overlaps the
# scatter-add of chunk k.  Peak memory is O(chunk · depth), independent of
# corpus size — the property the million-song north star needs.

_AUTO_STREAM_MIN_TOKENS = 1 << 22   # below this, chunking is pure overhead
_AUTO_CHUNK_TARGET_TOKENS = 1 << 21  # ~8 MiB of int32 ids per chunk
_STREAM_CHUNK_FLOOR = 1 << 12


def resolve_chunk_songs(
    chunk_songs, song_count: int, token_count: int
) -> int:
    """Resolve a ``--chunk-songs`` value to songs per chunk (0 = off).

    Explicit ``0`` disables streaming; an explicit positive value is
    clamped to the corpus.  ``None``/``"auto"`` streams only when the
    corpus is big enough for chunking to pay (small corpora keep the
    single-put paths and their per-shard timing semantics), sizing chunks
    so each carries ~``_AUTO_CHUNK_TARGET_TOKENS`` ids.
    """
    if chunk_songs is not None and chunk_songs != "auto":
        n = int(chunk_songs)
        if n < 0:
            raise ValueError(f"chunk-songs must be >= 0, got {n}")
        return 0 if n == 0 else min(n, max(1, song_count))
    if token_count < _AUTO_STREAM_MIN_TOKENS or song_count <= 1:
        return 0
    avg_tokens = max(1.0, token_count / song_count)
    return max(1, min(song_count, int(_AUTO_CHUNK_TARGET_TOKENS / avg_tokens)))


@lru_cache(maxsize=None)
def _stream_accum(mesh: Mesh, axis: str, padded_vocab: int):
    """One streaming step: add a chunk's per-shard histogram into the
    running per-chip accumulator.  No collective here — chips stay
    independent until the final ``_psum_rows`` merge."""

    def local(hist, ids):
        with jax.named_scope("histogram"):
            return hist + _token_histogram(ids, padded_vocab)[None, :]

    return profiled_jit(
        shard_map(
            local, mesh=mesh,
            in_specs=(P(axis, None), P(axis)), out_specs=P(axis, None),
        ),
        name="stream_accum_histogram",
    )


def sharded_histogram_streaming(
    ids: np.ndarray,
    offsets: np.ndarray,
    vocab_size: int,
    mesh: Mesh,
    axis: str = "dp",
    chunk_songs: int = 0,
    prefetch_depth=None,
) -> np.ndarray:
    """Global histogram via bounded chunks overlapped with H2D transfer.

    ``offsets`` (int64 ``[songs+1]``, from ``IngestResult``) keeps chunks
    song-aligned, so ``--chunk-songs`` means what it says.  Identical
    counts to :func:`sharded_histogram` at every chunk size — padding ids
    are ``PAD_ID`` and the scatter-add drops them.
    """
    from music_analyst_tpu.runtime.prefetch import (
        PrefetchPipeline, Stage, resolve_prefetch_depth,
    )
    from music_analyst_tpu.telemetry import get_telemetry

    ids = np.asarray(ids) if ids.dtype == np.int32 else np.asarray(
        ids, dtype=np.int32
    )
    offsets = np.asarray(offsets, dtype=np.int64)
    song_count = offsets.shape[0] - 1
    if chunk_songs <= 0:
        raise ValueError("sharded_histogram_streaming needs chunk_songs > 0")
    if song_count <= 0 or ids.shape[0] == 0:
        return np.zeros((vocab_size,), dtype=np.int32)
    shards = mesh.shape[axis]
    padded_vocab = _bucket(vocab_size, 1 << 10)
    bounds = list(range(0, song_count, chunk_songs)) + [song_count]
    token_bounds = [int(offsets[b]) for b in bounds]
    max_chunk_tokens = max(
        e - s for s, e in zip(token_bounds, token_bounds[1:])
    )
    # One compiled program for every chunk: pow2-bucket the chunk length,
    # then round up so it splits evenly over the shards.
    bucket_len = _bucket(max(1, max_chunk_tokens), _STREAM_CHUNK_FLOOR)
    bucket_len = -(-bucket_len // shards) * shards
    chunk_sharding = NamedSharding(mesh, P(axis))
    hist_sharding = NamedSharding(mesh, P(axis, None))
    accum = _stream_accum(mesh, axis, padded_vocab)

    def _pad(span):
        start, end = span
        chunk = np.full((bucket_len,), PAD_ID, dtype=np.int32)
        chunk[: end - start] = ids[start:end]
        return chunk

    def _h2d(chunk):
        return jax.device_put(chunk, chunk_sharding)

    hist = jax.device_put(
        np.zeros((shards, padded_vocab), dtype=np.int32), hist_sharding
    )
    n_chunks = len(token_bounds) - 1
    depth = resolve_prefetch_depth(prefetch_depth)
    pipe = PrefetchPipeline(
        stages=[Stage("chunk_pad", _pad), Stage("h2d", _h2d)],
        depth=depth,
        name="stream_histogram",
        sink_name="accumulate",
    )
    for dev_chunk in pipe.run(zip(token_bounds, token_bounds[1:])):
        hist = accum(hist, dev_chunk)
    tel = get_telemetry()
    tel.count("histogram.stream_chunks", n_chunks)
    tel.count("histogram.stream_h2d_bytes", n_chunks * bucket_len * 4)
    record_collective(
        "histogram.stream_merge", "psum",
        payload_bytes=padded_vocab * 4, n_devices=shards, axis=axis,
    )
    fault_point("collective.psum", op="histogram.stream_merge")
    # np.asarray IS the sync point: the host needs the counts anyway.
    return np.asarray(_psum_rows(mesh, axis)(hist))[:vocab_size]


def sharded_total(values: np.ndarray, mesh: Mesh, axis: str = "dp") -> int:
    """``psum`` of per-shard scalar contributions.

    The analogue of the reference's grand-total reduction
    (``MPI_Reduce(SUM)``, ``src/parallel_spotify.c:1004-1005``); padding
    contributes zeros.
    """
    padded = shard_pad(np.asarray(values, dtype=np.int64), mesh.shape[axis], 0)
    record_collective(
        "histogram.scalar_total", "psum",
        payload_bytes=8, n_devices=mesh.shape[axis], axis=axis,
    )
    fault_point("collective.psum", op="histogram.scalar_total")
    return int(_psum_scalar(mesh, axis)(padded))
