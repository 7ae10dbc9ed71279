"""Prefetch-depth × wire-dtype sweep for the host↔device data plane.

Answers two questions the ISSUE-3 data plane raised:

* **depth** — how many batches should the bounded pipeline
  (``runtime/prefetch.py``) stage ahead of the device?  An ingest-bound
  source (emulated here with a metered per-chunk delay, the shape a
  slow disk or a cold page cache produces) serializes the whole
  run at depth 0; depth ≥ 2 should hide the source behind compute.  The
  per-depth ``pipeline.*`` stall columns show *where* the remaining wall
  time lives — ``compute_stall_s`` high means the device starves
  (deepen), ``h2d``/``tokenize`` stalls high mean the source is the
  bottleneck (no depth will help).
* **wire dtype** — what do the int16 id/length wires
  (``runtime/wire.py``) save against an int32 baseline, in bytes and in
  wall time?  Measured at the default depth with the same params so the
  only variable is the wire.

Depth cells run through ``run_sentiment`` itself — the measured number
is the shipped engine, and each cell's stall columns are read back from
the same ``pipeline`` manifest section a production run writes.
"""

from __future__ import annotations

import os
import tempfile
import time

import numpy as np

from benchmarks import suite
from benchmarks._util import device_info, smoke

_DEPTHS = (0, 1, 2, 3)


def _corpus(n: int, seed: int) -> list:
    from music_analyst_tpu.data.synthetic import _WORDS

    rng = np.random.default_rng(seed)
    words = np.array(_WORDS)
    return [
        " ".join(rng.choice(words, size=max(3, int(rng.normal(80, 25)))))
        for _ in range(n)
    ]


def _slow_rows(texts, chunk: int, delay_s: float):
    """Synthetic ingest-bound source: every ``chunk`` rows costs
    ``delay_s`` of pure source latency, like a cold read or a remote
    fetch.  Deterministic, so the depth sweep A/Bs only the overlap."""
    for i, text in enumerate(texts):
        if i % chunk == 0:
            time.sleep(delay_s)
        yield ("bench", f"song-{i}", text)


def _classify_run(clf, texts, batch, chunk, delay_s, depth) -> dict:
    from music_analyst_tpu.engines.sentiment import run_sentiment
    from music_analyst_tpu.telemetry import get_telemetry

    out_dir = tempfile.mkdtemp(prefix=f"overlap_d{depth}_")
    t0 = time.perf_counter()
    run_sentiment(
        "",  # unused: songs= bypasses the dataset read
        output_dir=out_dir,
        batch_size=batch,
        backend=clf,
        quiet=True,
        songs=_slow_rows(texts, chunk, delay_s),
        prefetch_depth=depth,
    )
    wall = time.perf_counter() - t0
    tel = get_telemetry()
    stages = {
        s["stage"]: s
        for s in tel.pipeline_summary().get("pipeline", {}).get("stages", ())
    }
    counters = dict(tel.counters)
    return {
        "depth": depth,
        "wall_s": round(wall, 3),
        "songs_per_s": round(len(texts) / wall, 1),
        "h2d_stall_s": stages.get("h2d", {}).get("stall_s", 0.0),
        "compute_stall_s": stages.get("compute", {}).get("stall_s", 0.0),
        "max_queue_depth": tel.pipeline_summary()
        .get("pipeline", {})
        .get("max_queue_depth", 0),
        "h2d_bytes": counters.get("pipeline.h2d_bytes", 0),
        "h2d_bytes_saved": counters.get("pipeline.h2d_bytes_saved", 0),
    }


@suite("overlap")
def run() -> dict:
    from music_analyst_tpu.models.distilbert import (
        DistilBertClassifier,
        DistilBertConfig,
    )
    from music_analyst_tpu.telemetry import configure, get_telemetry

    if smoke():
        cfg, n, batch, max_len = DistilBertConfig.tiny(), 512, 128, 64
    else:
        cfg, n, batch, max_len = DistilBertConfig(), 8192, 1024, 128
    chunk, delay_s = 64, 0.003

    if not get_telemetry().enabled:
        # The stall columns come off the telemetry registry; a bare
        # `bench.py --suite=overlap` invocation has it unconfigured.
        configure(enabled=True, directory=None)

    texts = _corpus(n, seed=13)
    clf = DistilBertClassifier(config=cfg, max_len=max_len, seed=0)
    clf.classify_batch(texts[:batch])  # compile outside every timed cell

    out = {
        "suite": "overlap",
        **device_info(),
        "smoke": smoke(),
        "songs": n,
        "batch": batch,
        "max_len": max_len,
        "source_delay_s_per_chunk": delay_s,
        "depths": [
            _classify_run(clf, texts, batch, chunk, delay_s, d)
            for d in _DEPTHS
        ],
    }
    base = out["depths"][0]["wall_s"]
    for cell in out["depths"]:
        cell["speedup_vs_depth0"] = round(base / cell["wall_s"], 3)

    # Wire-dtype A/B at the default depth: same params, same corpus, the
    # int32 wire forced onto a second classifier view.
    wide = DistilBertClassifier(config=cfg, max_len=max_len, seed=0)
    wide.params = clf.params
    wide._wire_dtype = np.int32
    wide._index_dtype = np.int32
    wide.classify_batch(texts[:batch])  # compile the int32 variants
    narrow_cell = out["depths"][2]  # depth 2 already measured above
    wide_cell = _classify_run(
        wide, texts, batch, chunk, delay_s, _DEPTHS[2]
    )
    out["wire"] = {
        "int16": {
            k: narrow_cell[k]
            for k in ("wall_s", "songs_per_s", "h2d_bytes", "h2d_bytes_saved")
        },
        "int32": {
            k: wide_cell[k]
            for k in ("wall_s", "songs_per_s", "h2d_bytes", "h2d_bytes_saved")
        },
    }
    return out
