"""Persistent corpus cache + chunked streaming histogram contracts.

Three golden properties, tested differentially:

1. A warm-cache ``run_analysis`` writes byte-identical ``word_counts.csv``
   / ``top_artists.csv`` to a cold run AND matches the serial oracle —
   the cache may accelerate ingest, never change output bytes.
2. A corrupt entry (truncated ``.npy``, stale schema) is detected,
   counted, deleted, and falls back to a fresh ingest — the cache can
   never fail a run.
3. The chunked streaming device path produces bit-identical histograms
   to the whole-corpus put at EVERY chunk size (including sizes that
   don't divide the song count).

Plus the two satellite fixes: the XLA-cache enable failure staying
retryable, and bench child timeouts clamping to the parent budget.
"""

import json
import os
from collections import Counter

import numpy as np
import pytest

from music_analyst_tpu.data import corpus_cache
from music_analyst_tpu.data.csv_io import iter_dataset_exact, sort_count_entries
from music_analyst_tpu.data.ingest import ingest_dataset
from music_analyst_tpu.data.tokenizer import tokenize_ascii


def _stats_delta(before, after):
    return {k: after[k] - before.get(k, 0) for k in after}


# ------------------------------------------------------------ cache core


def test_cold_store_then_warm_hit_roundtrip(fixture_csv, tmp_path):
    cache_dir = str(tmp_path / "cache")
    before = corpus_cache.cache_stats()
    cold = ingest_dataset(str(fixture_csv), backend="python",
                          cache_dir=cache_dir)
    warm = ingest_dataset(str(fixture_csv), backend="python",
                          cache_dir=cache_dir)
    delta = _stats_delta(before, corpus_cache.cache_stats())
    assert delta["stores"] == 1
    assert delta["hits"] == 1
    assert delta["corrupt"] == 0
    assert delta["bytes_saved"] == os.path.getsize(fixture_csv)

    assert warm.song_count == cold.song_count
    assert warm.token_count == cold.token_count
    np.testing.assert_array_equal(np.asarray(warm.word_ids),
                                  np.asarray(cold.word_ids))
    np.testing.assert_array_equal(np.asarray(warm.word_offsets),
                                  np.asarray(cold.word_offsets))
    np.testing.assert_array_equal(np.asarray(warm.artist_ids),
                                  np.asarray(cold.artist_ids))
    assert warm.word_vocab.tokens == cold.word_vocab.tokens
    assert warm.artist_vocab.tokens == cold.artist_vocab.tokens
    # Zero-copy contract: the warm arrays are memory-mapped, not copies.
    assert isinstance(warm.word_ids, np.memmap)


def test_capture_records_round_trips_through_cache(fixture_csv, tmp_path):
    cache_dir = str(tmp_path / "cache")
    cold = ingest_dataset(str(fixture_csv), backend="python",
                          capture_records=True, cache_dir=cache_dir)
    warm = ingest_dataset(str(fixture_csv), backend="python",
                          capture_records=True, cache_dir=cache_dir)
    assert warm.has_records
    assert bytes(warm.records_blob) == bytes(cold.records_blob)
    np.testing.assert_array_equal(np.asarray(warm.record_offsets),
                                  np.asarray(cold.record_offsets))
    # And the plain entry is distinct: a records-less request must not be
    # served the record-bearing entry or vice versa.
    key_plain = corpus_cache.corpus_key(str(fixture_csv), None, False,
                                        "python")
    key_rec = corpus_cache.corpus_key(str(fixture_csv), None, True, "python")
    assert key_plain != key_rec


def test_key_separates_backend_limit_and_content(fixture_csv, tmp_path):
    path = str(fixture_csv)
    base = corpus_cache.corpus_key(path, None, False, "python")
    assert corpus_cache.corpus_key(path, None, False, "native") != base
    assert corpus_cache.corpus_key(path, 5, False, "python") != base
    # Any byte change re-keys; a pure rename does not.
    copy = tmp_path / "renamed.csv"
    copy.write_bytes(fixture_csv.read_bytes())
    assert corpus_cache.corpus_key(str(copy), None, False, "python") == base
    copy.write_bytes(fixture_csv.read_bytes() + b"x")
    assert corpus_cache.corpus_key(str(copy), None, False, "python") != base


def test_resolve_cache_dir_precedence(monkeypatch, tmp_path):
    monkeypatch.delenv("MUSICAAL_CORPUS_CACHE", raising=False)
    assert corpus_cache.resolve_cache_dir(None, False) is None
    assert corpus_cache.resolve_cache_dir("/x", None) == "/x"
    monkeypatch.setenv("MUSICAAL_CORPUS_CACHE", "off")
    assert corpus_cache.resolve_cache_dir(None, None) is None
    assert corpus_cache.resolve_cache_dir("/x", None) == "/x"  # arg wins
    monkeypatch.setenv("MUSICAAL_CORPUS_CACHE", str(tmp_path))
    assert corpus_cache.resolve_cache_dir(None, None) == str(tmp_path)
    monkeypatch.delenv("MUSICAAL_CORPUS_CACHE", raising=False)
    assert corpus_cache.resolve_cache_dir(None, None) == os.path.expanduser(
        "~/.cache/musicaal_corpus"
    )


# ----------------------------------------------------- corruption handling


def _entry_dir(cache_dir, path):
    key = corpus_cache.corpus_key(path, None, False, "python")
    return os.path.join(cache_dir, key)


def test_truncated_npy_falls_back_to_fresh_ingest(fixture_csv, tmp_path):
    cache_dir = str(tmp_path / "cache")
    path = str(fixture_csv)
    cold = ingest_dataset(path, backend="python", cache_dir=cache_dir)
    entry = _entry_dir(cache_dir, path)
    ids_path = os.path.join(entry, "word_ids.npy")
    with open(ids_path, "r+b") as fh:
        fh.truncate(os.path.getsize(ids_path) // 2)

    before = corpus_cache.cache_stats()
    assert corpus_cache.load(cache_dir, path, None, False, "python") is None
    delta = _stats_delta(before, corpus_cache.cache_stats())
    assert delta["corrupt"] == 1
    assert delta["hits"] == 0
    assert not os.path.isdir(entry)  # corrupt entry evicted

    # The engine-level path re-ingests and re-stores transparently.
    fresh = ingest_dataset(path, backend="python", cache_dir=cache_dir)
    assert fresh.token_count == cold.token_count
    assert os.path.isdir(entry)


def test_stale_schema_falls_back(fixture_csv, tmp_path):
    cache_dir = str(tmp_path / "cache")
    path = str(fixture_csv)
    ingest_dataset(path, backend="python", cache_dir=cache_dir)
    entry = _entry_dir(cache_dir, path)
    meta_path = os.path.join(entry, "meta.json")
    with open(meta_path, encoding="utf-8") as fh:
        meta = json.load(fh)
    meta["schema"] = corpus_cache.SCHEMA_VERSION + 999
    with open(meta_path, "w", encoding="utf-8") as fh:
        json.dump(meta, fh)

    before = corpus_cache.cache_stats()
    assert corpus_cache.load(cache_dir, path, None, False, "python") is None
    delta = _stats_delta(before, corpus_cache.cache_stats())
    assert delta["corrupt"] == 1
    assert not os.path.isdir(entry)


def test_store_never_raises_on_unwritable_dir(fixture_csv, tmp_path):
    corpus = ingest_dataset(str(fixture_csv), backend="python")
    missing = str(tmp_path / "no" / "such" / "file.csv")
    # Bad source path (corpus_key can't stat it): returns False, no raise.
    assert corpus_cache.store(str(tmp_path), missing, None, False,
                              "python", corpus) is False


# ---------------------------------------------- differential: run_analysis


def _oracle_entries(data: bytes):
    words = Counter()
    artists = Counter()
    for artist_raw, text_raw in iter_dataset_exact(data):
        words.update(tokenize_ascii(text_raw))
        if artist_raw:
            artists[artist_raw.decode("utf-8", errors="replace")] += 1
    return sort_count_entries(words.items()), sort_count_entries(
        artists.items()
    )


def test_warm_run_analysis_byte_identical_to_cold_and_oracle(
    fixture_csv, tmp_path
):
    from music_analyst_tpu.engines.wordcount import run_analysis

    cache_dir = str(tmp_path / "cache")
    before = corpus_cache.cache_stats()
    cold_out = tmp_path / "cold"
    warm_out = tmp_path / "warm"
    run_analysis(str(fixture_csv), output_dir=str(cold_out),
                 corpus_cache_dir=cache_dir, write_split=False, quiet=True)
    result = run_analysis(str(fixture_csv), output_dir=str(warm_out),
                          corpus_cache_dir=cache_dir, write_split=False,
                          quiet=True)
    delta = _stats_delta(before, corpus_cache.cache_stats())
    assert delta["hits"] >= 1

    for name in ("word_counts.csv", "top_artists.csv"):
        assert (cold_out / name).read_bytes() == (warm_out / name).read_bytes()

    word_entries, artist_entries = _oracle_entries(fixture_csv.read_bytes())
    assert result.word_entries == word_entries
    assert result.artist_entries == artist_entries

    # The run manifest carries the cache stats (telemetry/introspect.py).
    manifest = json.loads((warm_out / "run_manifest.json").read_text())
    assert manifest["corpus_cache"]["hits"] >= 1


def test_no_corpus_cache_opt_out_writes_nothing(fixture_csv, tmp_path):
    from music_analyst_tpu.engines.wordcount import run_analysis

    cache_dir = tmp_path / "cache"
    run_analysis(str(fixture_csv), output_dir=str(tmp_path / "out"),
                 corpus_cache_dir=str(cache_dir), use_corpus_cache=False,
                 write_split=False, quiet=True)
    assert not cache_dir.exists()


# --------------------------------------------------- streaming histogram


def test_resolve_chunk_songs():
    from music_analyst_tpu.ops.histogram import (
        _AUTO_STREAM_MIN_TOKENS,
        resolve_chunk_songs,
    )

    # Explicit: 0 = off, N = N (clamped to the corpus), negative rejected.
    assert resolve_chunk_songs(0, 100, 10_000) == 0
    assert resolve_chunk_songs(7, 100, 10_000) == 7
    assert resolve_chunk_songs(500, 100, 10_000) == 100
    with pytest.raises(ValueError):
        resolve_chunk_songs(-1, 100, 10_000)
    # Auto: off below the streaming floor, bounded chunks above it.
    assert resolve_chunk_songs(None, 100, 10_000) == 0
    assert resolve_chunk_songs("auto", 100, 10_000) == 0
    big = _AUTO_STREAM_MIN_TOKENS * 2
    chunk = resolve_chunk_songs(None, 1_000_000, big)
    assert 1 <= chunk <= 1_000_000


@pytest.mark.parametrize("chunk_songs", [1, 3, 7, 16, 1000])
@pytest.mark.parametrize("depth", [0, 2])
def test_streaming_histogram_bit_identical(fixture_csv, chunk_songs, depth):
    from music_analyst_tpu.ops.histogram import (
        sharded_histogram,
        sharded_histogram_streaming,
    )
    from music_analyst_tpu.parallel.mesh import data_parallel_mesh

    corpus = ingest_dataset(str(fixture_csv), backend="python")
    mesh = data_parallel_mesh()
    vocab = max(1, len(corpus.word_vocab))
    baseline = np.asarray(sharded_histogram(corpus.word_ids, vocab, mesh))
    streamed = sharded_histogram_streaming(
        corpus.word_ids, corpus.word_offsets, vocab, mesh,
        chunk_songs=chunk_songs, prefetch_depth=depth,
    )
    np.testing.assert_array_equal(streamed, baseline)


def test_streaming_run_analysis_byte_identical(fixture_csv, tmp_path):
    """word_counts.csv must not depend on the chunk size (golden
    contract: output bytes are invariant across device strategies)."""
    from music_analyst_tpu.engines.wordcount import run_analysis

    ref_out = tmp_path / "chunk0"
    run_analysis(str(fixture_csv), output_dir=str(ref_out), chunk_songs=0,
                 write_split=False, quiet=True)
    ref_words = (ref_out / "word_counts.csv").read_bytes()
    ref_artists = (ref_out / "top_artists.csv").read_bytes()
    for chunk in (1, 5, 64):
        out = tmp_path / f"chunk{chunk}"
        run_analysis(str(fixture_csv), output_dir=str(out),
                     chunk_songs=chunk, write_split=False, quiet=True)
        assert (out / "word_counts.csv").read_bytes() == ref_words
        assert (out / "top_artists.csv").read_bytes() == ref_artists


def test_streaming_empty_and_bad_args(fixture_csv):
    from music_analyst_tpu.ops.histogram import sharded_histogram_streaming
    from music_analyst_tpu.parallel.mesh import data_parallel_mesh

    mesh = data_parallel_mesh()
    with pytest.raises(ValueError):
        sharded_histogram_streaming(
            np.zeros(0, np.int32), np.zeros(1, np.int64), 4, mesh,
            chunk_songs=0,
        )
    empty = sharded_histogram_streaming(
        np.zeros(0, np.int32), np.zeros(1, np.int64), 4, mesh, chunk_songs=2,
    )
    np.testing.assert_array_equal(empty, np.zeros(4, np.int32))


# ------------------------------------------------------------- satellites


def test_compile_cache_rule(monkeypatch, tmp_path):
    """$JAX_COMPILATION_CACHE_DIR set ⇒ the code sets no directory;
    unset ⇒ the one fixed in-checkout path; a cache that cannot be
    enabled raises (no counter, no cold-compile run)."""
    import jax

    from music_analyst_tpu.utils import cache as xla_cache

    prev_dir = jax.config.jax_compilation_cache_dir
    prev_min = jax.config.jax_persistent_cache_min_compile_time_secs
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    assert xla_cache.REPO_CACHE_DIR == os.path.join(repo, ".jax_cache")
    try:
        sentinel = str(tmp_path / "untouched")
        jax.config.update("jax_compilation_cache_dir", sentinel)
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path / "env"))
        assert xla_cache.enable_persistent_compilation_cache() == str(
            tmp_path / "env"
        )
        assert jax.config.jax_compilation_cache_dir == sentinel
        assert not (tmp_path / "env").exists()  # JAX makes it, not us

        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
        fixed = tmp_path / "checkout" / ".jax_cache"
        monkeypatch.setattr(xla_cache, "REPO_CACHE_DIR", str(fixed))
        assert xla_cache.enable_persistent_compilation_cache() == str(fixed)
        assert jax.config.jax_compilation_cache_dir == str(fixed)
        assert fixed.is_dir()

        def boom(*args, **kwargs):
            raise OSError("read-only checkout")

        monkeypatch.setattr(os, "makedirs", boom)
        with pytest.raises(OSError):
            xla_cache.enable_persistent_compilation_cache()
    finally:
        jax.config.update("jax_compilation_cache_dir", prev_dir)
        jax.config.update(
            "jax_persistent_cache_min_compile_time_secs", prev_min
        )


def test_bench_child_timeout_clamps_to_parent_budget():
    from benchmarks import _util

    now = [1000.0]

    def clock():
        return now[0]

    try:
        # Unarmed: the caller's cap passes through untouched.
        _util.arm_deadline(None)
        assert _util.clamped_timeout(1200.0, clock=clock) == 1200.0
        # Armed with 480 s: a 1200 s cap clamps to budget minus safety.
        _util.arm_deadline(480.0, clock=clock)
        assert _util.clamped_timeout(1200.0, clock=clock) == pytest.approx(
            480.0 - _util._BUDGET_SAFETY_S
        )
        # Small caps under the budget are untouched.
        assert _util.clamped_timeout(30.0, clock=clock) == 30.0
        # Nearly-spent budget floors at 1 s (child launches and times out
        # rather than clamped_timeout raising on a non-positive value).
        now[0] = 1000.0 + 479.0
        assert _util.clamped_timeout(1200.0, clock=clock) == 1.0
    finally:
        _util.arm_deadline(None)
