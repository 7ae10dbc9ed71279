"""REAL multi-process execution: two JAX processes, Gloo CPU collectives.

The reference verifies its parallelism by actually running N ranks
(``mpirun -np N``, src/parallel_spotify.c:725-730); the JAX-native
equivalent is two OS processes under ``jax.distributed.initialize`` with
4 virtual CPU devices each (8 global).  Each child ingests a disjoint
record range, merges vocabularies through the coordinator, psums dense
histograms across all 8 devices, and the coordinator's word_counts.csv
must be byte-identical to a single-process run of the same corpus.

These children must NOT inherit the conftest's in-process jax setup —
they configure their own platform via env before importing jax.
"""

import os
import socket
import subprocess
import sys

_CHILD = r"""
import os, sys
proc_id = int(sys.argv[1])
port = sys.argv[2]
dataset = sys.argv[3]
out_dir = sys.argv[4]
os.environ["JAX_PLATFORMS"] = "cpu"
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
import jax
# jaxlib >= 0.4.36 dropped the implicit multiprocess CPU emulation: cross-
# process collectives on the CPU backend now need an explicit collectives
# implementation or psum fails with "Multiprocess computations aren't
# implemented on the CPU backend".  Gloo ships in-tree.
jax.config.update("jax_cpu_collectives_implementation", "gloo")
jax.distributed.initialize(
    coordinator_address=f"localhost:{port}", num_processes=2,
    process_id=proc_id,
)
assert jax.process_count() == 2, jax.process_count()
assert len(jax.devices()) == 8, len(jax.devices())
from music_analyst_tpu.parallel.distributed import distributed_wordcount
result = distributed_wordcount(dataset, output_dir=out_dir)
print(f"RESULT {result['total_songs']} {result['total_words']}")
"""


def test_distributed_wordcount_single_process_degenerates(tmp_path):
    """With one process the same code path must reduce to the plain
    engine result (every collective degrades per multihost.py)."""
    import numpy as np

    from music_analyst_tpu.data.csv_io import (
        sort_count_entries,
        write_count_csv,
    )
    from music_analyst_tpu.data.ingest import ingest_python
    from music_analyst_tpu.data.synthetic import generate_dataset
    from music_analyst_tpu.parallel.distributed import distributed_wordcount

    dataset = tmp_path / "songs.csv"
    generate_dataset(str(dataset), num_songs=60, seed=9)
    result = distributed_wordcount(str(dataset), output_dir=str(tmp_path / "o"))
    corpus = ingest_python(dataset.read_bytes())
    assert result["processes"] == 1
    assert result["total_songs"] == corpus.song_count
    assert result["total_words"] == corpus.token_count
    counts = np.bincount(
        corpus.word_ids[corpus.word_ids >= 0],
        minlength=len(corpus.word_vocab),
    )
    expect = tmp_path / "expect.csv"
    write_count_csv(
        str(expect), "word",
        sort_count_entries(corpus.word_vocab.counts_to_entries(counts)),
    )
    assert (tmp_path / "o" / "word_counts.csv").read_bytes() == expect.read_bytes()


def _free_port() -> int:
    with socket.socket() as s:
        s.bind(("localhost", 0))
        return s.getsockname()[1]


def test_two_process_wordcount_matches_single_process(tmp_path):
    from music_analyst_tpu.data.csv_io import write_count_csv, sort_count_entries
    from music_analyst_tpu.data.ingest import ingest_python
    from music_analyst_tpu.data.synthetic import generate_dataset

    dataset = tmp_path / "songs.csv"
    generate_dataset(str(dataset), num_songs=300, seed=21)
    out_dir = tmp_path / "dist_out"

    env = dict(os.environ)
    env.pop("JAX_PLATFORMS", None)
    env.pop("XLA_FLAGS", None)
    port = str(_free_port())
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    procs = [
        subprocess.Popen(
            [sys.executable, "-c", _CHILD, str(p), port, str(dataset),
             str(out_dir)],
            stdout=subprocess.PIPE, stderr=subprocess.PIPE, text=True,
            env=env, cwd=repo,
        )
        for p in (0, 1)
    ]
    outs = []
    try:
        for proc in procs:
            out, err = proc.communicate(timeout=240)
            assert proc.returncode == 0, f"child failed:\n{err[-1500:]}"
            outs.append(out)
    finally:
        # A failed/timed-out child must not leave its peer blocked in
        # jax.distributed.initialize holding the coordinator port.
        for proc in procs:
            if proc.poll() is None:
                proc.kill()

    # Both processes report identical global totals.
    results = [
        line for out in outs for line in out.splitlines()
        if line.startswith("RESULT ")
    ]
    assert len(results) == 2 and results[0] == results[1], results

    # Coordinator's export is byte-identical to the single-process oracle.
    import numpy as np

    corpus = ingest_python(dataset.read_bytes())
    counts = np.bincount(
        corpus.word_ids[corpus.word_ids >= 0],
        minlength=len(corpus.word_vocab),
    )
    expect_path = tmp_path / "expect_word_counts.csv"
    write_count_csv(
        str(expect_path), "word",
        sort_count_entries(corpus.word_vocab.counts_to_entries(counts)),
    )
    got = (out_dir / "word_counts.csv").read_bytes()
    assert got == expect_path.read_bytes()
    total_songs = int(results[0].split()[1])
    assert total_songs == corpus.song_count

    artist_counts = np.bincount(
        corpus.artist_ids[corpus.artist_ids >= 0],
        minlength=len(corpus.artist_vocab),
    )
    expect_artists = tmp_path / "expect_top_artists.csv"
    write_count_csv(
        str(expect_artists), "artist",
        sort_count_entries(
            corpus.artist_vocab.counts_to_entries(artist_counts)
        ),
    )
    assert (out_dir / "top_artists.csv").read_bytes() == expect_artists.read_bytes()

    # The coordinator emits the multi-controller performance_metrics.json
    # (reference: per-rank MPI_Reduce timing stats) with one genuinely
    # measured sample per process.
    import json

    metrics = json.loads((out_dir / "performance_metrics.json").read_text())
    assert metrics["processes"] == 2
    assert metrics["total_songs"] == corpus.song_count
    per_proc = metrics["per_chip"]
    assert [entry["process"] for entry in per_proc] == [0, 1]
    samples = [entry["compute_seconds"] for entry in per_proc]
    assert all(s > 0 for s in samples)
    # Independent clocks: two processes never measure the same nanosecond.
    assert samples[0] != samples[1]
    # compute_time rounds to 6 decimals, samples keep 9.
    assert abs(metrics["compute_time"]["min_seconds"] - min(samples)) < 1e-5
    assert abs(metrics["compute_time"]["max_seconds"] - max(samples)) < 1e-5
    assert metrics["total_time"]["avg_seconds"] >= (
        metrics["compute_time"]["avg_seconds"]
    )
