"""Telemetry registry: spans, counters, sinks, manifest, engine wiring.

The byte-stability tests at the bottom are the load-bearing ones: turning
telemetry ON must not perturb the golden artifacts (``word_counts.csv``
byte-identical, ``performance_metrics.json`` structurally identical) —
the whole subsystem rides alongside the reference contracts, never in
them.
"""

import json
import os
import threading
import time

import pytest

from music_analyst_tpu.telemetry import (
    DEFAULT_BUCKETS,
    Histogram,
    Telemetry,
    configure,
    get_telemetry,
)


@pytest.fixture(autouse=True)
def _fresh_telemetry():
    """Each test gets a clean, enabled registry; the CLI's configure()
    mutates process-wide state, so restore the default afterwards."""
    yield configure(enabled=True, directory=None)
    configure(enabled=True, directory=None)


# ---------------------------------------------------------------- spans


def test_span_nesting_links_parents():
    tel = Telemetry()
    with tel.span("outer") as outer:
        with tel.span("middle") as middle:
            with tel.span("inner", rows=3) as inner:
                pass
    assert outer.parent_id is None
    assert middle.parent_id == outer.span_id
    assert inner.parent_id == middle.span_id
    assert inner.attrs == {"rows": 3}
    assert all(sp.duration_s >= 0.0 for sp in tel.spans)
    # Completion order: innermost closes first.
    assert [sp.name for sp in tel.spans] == ["inner", "middle", "outer"]


def test_span_attrs_via_set():
    tel = Telemetry()
    with tel.span("work") as sp:
        sp.set(rows=7, backend="mock")
    assert tel.spans[0].attrs == {"rows": 7, "backend": "mock"}


def test_record_span_preserves_duration():
    tel = Telemetry()
    tel.record_span("tokenize", 1.25, rows=10)
    sp = tel.spans[0]
    assert sp.name == "tokenize" and sp.duration_s == 1.25
    assert tel.span_aggregates["tokenize"] == [1, 1.25, 1.25]


def test_record_span_stamps_now_minus_duration_by_default():
    tel = Telemetry()
    before_mono, before_wall = time.monotonic(), time.time()
    tel.record_span("tokenize", 0.5)
    sp = tel.spans[0]
    # the start is "now - duration": right only when recorded the instant
    # the work ends
    assert before_mono - 0.5 <= sp.t_mono <= time.monotonic() - 0.5
    assert before_wall - 0.5 <= sp.t_wall <= time.time() - 0.5
    assert sp.parent_id is None


def test_record_span_keeps_the_start_it_is_given():
    tel = Telemetry()
    start = time.monotonic() - 3.0  # the work began three seconds ago
    before_wall = time.time()
    with tel.span("caller") as caller:
        tel.record_span("read", 0.25, t_mono=start, rows=4)
        tel.record_span("read", 0.25, t_mono=start, parent_id=77)
    first, second = tel.spans[0], tel.spans[1]
    assert first.t_mono == start and first.duration_s == 0.25
    # t_wall is the same instant on the wall clock, not "now - duration"
    assert before_wall - 3.0 - 0.05 <= first.t_wall <= time.time() - 3.0 + 0.05
    assert first.attrs == {"rows": 4}  # t_mono / parent_id are not attrs
    # the recording thread's stack names the parent unless one is given
    assert first.parent_id == caller.span_id
    assert second.parent_id == 77 and second.attrs == {}
    assert first.as_event()["t_mono"] == round(start, 6)


def test_spans_are_thread_safe():
    tel = Telemetry()
    n_threads, per_thread = 8, 50
    errors = []

    def work(i):
        try:
            for j in range(per_thread):
                with tel.span(f"t{i}"):
                    tel.count("iterations")
                tel.record_span("measured", 0.001)
        except Exception as exc:  # pragma: no cover - failure path
            errors.append(exc)

    threads = [
        threading.Thread(target=work, args=(i,)) for i in range(n_threads)
    ]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert not errors
    assert tel.counters["iterations"] == n_threads * per_thread
    assert tel.span_aggregates["measured"][0] == n_threads * per_thread
    # Each thread's stack is its own: no span got a cross-thread parent.
    for sp in tel.spans:
        if sp.parent_id is not None:
            parent = next(p for p in tel.spans if p.span_id == sp.parent_id)
            assert parent.thread == sp.thread


def test_disabled_registry_is_inert(tmp_path):
    tel = Telemetry(enabled=False)
    with tel.span("x") as sp:
        sp.set(rows=1)  # _NullSpan absorbs attrs
    tel.count("c")
    tel.observe("h", 0.5)
    tel.record_span("y", 1.0)
    with tel.run_scope("engine", str(tmp_path)):
        pass
    assert tel.spans == [] and tel.counters == {} and tel.events == 0
    assert list(tmp_path.iterdir()) == []


# ------------------------------------------------- counters / histograms


def test_counter_aggregation():
    tel = Telemetry()
    tel.count("songs", 10)
    tel.count("songs", 5)
    tel.count("retries")
    assert tel.counters == {"songs": 15, "retries": 1}


def test_histogram_buckets():
    h = Histogram(buckets=(0.01, 0.1, 1.0))
    for v in (0.005, 0.05, 0.5, 5.0, 0.05):
        h.observe(v)
    d = h.as_dict()
    assert d["buckets_le"] == [0.01, 0.1, 1.0, "inf"]
    assert d["counts"] == [1, 2, 1, 1]
    assert d["count"] == 5
    assert d["sum_s"] == pytest.approx(5.605)


def test_observe_uses_default_buckets():
    tel = Telemetry()
    tel.observe("lat", 0.02)
    assert tel.histograms["lat"].buckets == tuple(sorted(DEFAULT_BUCKETS))


def test_compile_stats_counts_backend_compile_only():
    tel = Telemetry()
    tel.record_jax_event("/jax/core/compile/backend_compile_duration", 2.0)
    tel.record_jax_event("/jax/core/compile/backend_compile_duration", 1.0)
    tel.record_jax_event("/jax/core/compile/jaxpr_trace_duration", 9.0)
    tel.record_jax_event("/jax/compilation_cache/cache_hits")
    stats = tel.compile_stats()
    assert stats == {"count": 2, "seconds": 3.0}


def test_top_spans_ranked_by_total():
    tel = Telemetry()
    tel.record_span("slow", 3.0)
    tel.record_span("fast", 0.1)
    tel.record_span("fast", 0.2)
    top = tel.top_spans(2)
    assert [t["name"] for t in top] == ["slow", "fast"]
    assert top[1]["count"] == 2 and top[1]["max_s"] == 0.2


# ----------------------------------------------------- run scope + sinks


def test_run_scope_writes_jsonl_and_manifest(tmp_path):
    tel = Telemetry()
    with tel.run_scope("wordcount", str(tmp_path)):
        with tel.span("ingest", rows=4):
            pass
        tel.count("songs_ingested", 4)
        tel.annotate(mesh_shape={"dp": 8})

    log = tmp_path / "telemetry.jsonl"
    assert log.exists()
    events = [json.loads(line) for line in log.read_text().splitlines()]
    assert events, "JSONL log must not be empty"
    # Every line is a self-describing event with both clocks.
    for ev in events:
        assert ev["type"] in ("span", "event")
        assert "t_wall" in ev and "t_mono" in ev
    names = [ev["name"] for ev in events]
    assert names[0] == "run_start"
    assert names[-2:] == ["run_end", "manifest"]
    assert "ingest" in names and "engine:wordcount" in names
    ingest = next(ev for ev in events if ev["name"] == "ingest")
    assert ingest["attrs"] == {"rows": 4} and ingest["dur_s"] >= 0.0
    run_end = next(ev for ev in events if ev["name"] == "run_end")
    assert run_end["attrs"]["counters"] == {"songs_ingested": 4}

    manifest = json.loads((tmp_path / "run_manifest.json").read_text())
    for key in (
        "schema", "engine", "argv", "wall_seconds", "jax_version",
        "jaxlib_version", "git_describe", "device", "peak_rss_bytes",
        "compile", "counters", "context", "spans", "event_count",
    ):
        assert key in manifest, key
    assert manifest["engine"] == "wordcount"
    assert manifest["device"]["platform"] == "cpu"
    assert manifest["device"]["count"] == 8  # the emulated test mesh
    assert manifest["counters"] == {"songs_ingested": 4}
    assert manifest["context"]["mesh_shape"] == {"dp": 8}
    assert {"count", "seconds"} <= set(manifest["compile"])


def test_nested_run_scopes_degrade_to_spans(tmp_path):
    """joint -> wordcount/sentiment: one owner, ONE manifest, nested
    engines show up as engine:<name> spans instead of resetting state."""
    tel = Telemetry()
    outer_dir = tmp_path / "outer"
    inner_dir = tmp_path / "inner"
    with tel.run_scope("joint", str(outer_dir)):
        tel.count("songs", 2)
        with tel.run_scope("wordcount", str(inner_dir)):
            tel.count("songs", 3)
    assert not inner_dir.exists()  # nested scope opened no sink
    manifest = json.loads((outer_dir / "run_manifest.json").read_text())
    assert manifest["engine"] == "joint"
    assert manifest["counters"] == {"songs": 5}  # not reset by the nest
    names = [
        json.loads(line)["name"]
        for line in (outer_dir / "telemetry.jsonl").read_text().splitlines()
    ]
    assert "engine:wordcount" in names
    assert names.count("run_start") == 1 and names.count("run_end") == 1


def test_back_to_back_runs_reset_state(tmp_path):
    tel = Telemetry()
    with tel.run_scope("a", str(tmp_path / "a")):
        tel.count("rows", 1)
    with tel.run_scope("b", str(tmp_path / "b")):
        pass
    manifest_b = json.loads(
        (tmp_path / "b" / "run_manifest.json").read_text()
    )
    assert manifest_b["counters"] == {}  # run a's counters did not bleed


def test_explicit_directory_wins_over_output_dir(tmp_path):
    tel = Telemetry()
    tel.directory = str(tmp_path / "telemetry")
    with tel.run_scope("x", str(tmp_path / "output")):
        pass
    assert (tmp_path / "telemetry" / "telemetry.jsonl").exists()
    assert (tmp_path / "telemetry" / "run_manifest.json").exists()
    assert not (tmp_path / "output").exists()


def test_memory_only_when_no_directory(tmp_path, monkeypatch):
    monkeypatch.chdir(tmp_path)
    tel = Telemetry()
    with tel.run_scope("x", None):
        tel.count("rows", 1)
    assert list(tmp_path.iterdir()) == []
    assert tel.events > 0  # still counted in memory


def test_jsonl_appends_across_runs(tmp_path):
    tel = Telemetry()
    for _ in range(2):
        with tel.run_scope("x", str(tmp_path)):
            pass
    lines = (tmp_path / "telemetry.jsonl").read_text().splitlines()
    assert sum(json.loads(l)["name"] == "run_start" for l in lines) == 2


# ------------------------------------------------------- engine wiring


def test_stage_timer_spans_and_seconds_agree():
    from music_analyst_tpu.metrics.timer import StageTimer

    tel = get_telemetry()
    timer = StageTimer()
    with timer.stage("device_compute"):
        pass
    with timer.stage("device_compute"):
        pass
    # StageTimer semantics unchanged: accumulated float per stage name.
    assert set(timer.seconds) == {"device_compute"}
    assert timer.seconds["device_compute"] >= 0.0
    # ... and each stage() also recorded a telemetry span.
    assert tel.span_aggregates["device_compute"][0] == 2


def test_wordcount_engine_emits_required_stage_spans(fixture_csv, tmp_path):
    from music_analyst_tpu.engines.wordcount import run_analysis

    run_analysis(
        str(fixture_csv), output_dir=str(tmp_path),
        ingest_backend="python", quiet=True,
    )
    log = tmp_path / "telemetry.jsonl"
    assert log.exists()
    events = [json.loads(line) for line in log.read_text().splitlines()]
    names = {ev["name"] for ev in events}
    # ≥1 span per pipeline stage (the acceptance bar): ingest, compute,
    # write — plus the split stage this engine owns.
    assert {"split", "ingest", "device_compute", "aggregate_export"} <= names
    manifest = json.loads((tmp_path / "run_manifest.json").read_text())
    assert manifest["engine"] == "wordcount"
    assert manifest["counters"]["songs_ingested"] > 0
    assert manifest["counters"]["words_counted"] > 0
    assert manifest["context"]["mesh_shape"]["dp"] == 8


def test_sentiment_engine_emits_stage_spans(fixture_csv, tmp_path):
    from music_analyst_tpu.engines.sentiment import run_sentiment

    run_sentiment(
        str(fixture_csv), mock=True, output_dir=str(tmp_path), quiet=True,
    )
    events = [
        json.loads(line)
        for line in (tmp_path / "telemetry.jsonl").read_text().splitlines()
    ]
    names = {ev["name"] for ev in events}
    assert {"read", "wait", "compute", "write", "backend_init",
            "write_totals", "manifest"} <= names
    manifest = json.loads((tmp_path / "run_manifest.json").read_text())
    assert manifest["engine"] == "sentiment"
    assert manifest["counters"]["rows_classified"] > 0
    assert "sentiment.batch_seconds" in manifest["histograms"]


class _SleepyBackend:
    """Duck-typed backend whose device-blocking edge takes 20 ms."""

    name = "sleepy"
    reports_latency = False

    def submit(self, texts):
        return ["Neutral"] * len(texts)

    def collect(self, handle):
        time.sleep(0.02)
        return handle


def _run_sleepy_sentiment(csv_path, out_dir):
    from music_analyst_tpu.engines.sentiment import run_sentiment

    run_sentiment(
        csv_path, backend=_SleepyBackend(), batch_size=1,
        output_dir=out_dir, quiet=True,
    )


def _run_python_analysis(csv_path, out_dir):
    from music_analyst_tpu.data.synthetic import generate_dataset
    from music_analyst_tpu.engines.wordcount import run_analysis

    # The eight-row fixture is a 4 ms job, of which the fixed loop
    # overhead between spans (0.4 ms) is a tenth: tile a job of real
    # length instead.
    dataset = os.path.join(os.path.dirname(out_dir), "songs.csv")
    if not os.path.exists(dataset):
        generate_dataset(dataset, num_songs=2000, seed=0, mean_words=60)
    run_analysis(
        dataset, output_dir=out_dir, ingest_backend="python", quiet=True,
        use_corpus_cache=False,
    )


def _tiled_share(log_path, tiles, at_least):
    """Share of ``run_start`` → end of ``manifest`` that the spans named in
    ``tiles`` cover on the thread that recorded ``manifest``."""
    events = [json.loads(line) for line in log_path.read_text().splitlines()]
    run_start = next(ev for ev in events if ev["name"] == "run_start")
    manifest = events[-1]
    assert manifest["type"] == "span" and manifest["name"] == "manifest"
    names = [ev["name"] for ev in events if ev["type"] == "span"]
    assert tiles <= set(names)
    for name, count in at_least.items():
        assert names.count(name) >= count
    t0, t1 = run_start["t_mono"], manifest["t_mono"] + manifest["dur_s"]
    covered, edge = 0.0, t0
    for start, end in sorted(
        (ev["t_mono"], ev["t_mono"] + ev["dur_s"]) for ev in events
        if ev["type"] == "span" and ev["name"] in tiles
        and ev["thread"] == manifest["thread"]
    ):
        covered += max(0.0, min(end, t1) - max(start, edge))
        edge = max(edge, min(end, t1))
    return covered / (t1 - t0)


@pytest.mark.parametrize(
    "run_engine, tiles, at_least",
    [
        (
            _run_sleepy_sentiment,
            {"backend_init", "wait", "compute", "write", "write_totals",
             "manifest"},
            {"wait": 8, "compute": 8, "write": 8},
        ),
        (
            _run_python_analysis,
            {"split", "ingest", "device_compute", "aggregate_export",
             "write_metrics", "manifest"},
            {},
        ),
    ],
    ids=["sentiment", "analyze"],
)
def test_engine_spans_tile_the_calling_thread(
    fixture_csv, tmp_path, run_engine, tiles, at_least
):
    """The design rule of the engines' spans: on the thread that called the
    engine, every instant from ``run_start`` to the end of ``manifest``
    lies inside one of the named spans, but for file opens and loop
    overhead.  A hole here is idle time no label can name."""
    # what a process pays once (lazy imports, compiles) is not a hole
    run_engine(str(fixture_csv), str(tmp_path / "warmup"))
    # A hole in the design is there in every job; one the scheduler made
    # (a thread start on a busy machine) is not: the best of three jobs.
    shares = []
    while len(shares) < 3 and max(shares, default=0.0) < 0.9:
        out_dir = tmp_path / f"job{len(shares)}"
        run_engine(str(fixture_csv), str(out_dir))
        shares.append(
            _tiled_share(out_dir / "telemetry.jsonl", tiles, at_least))
    assert max(shares) >= 0.9, shares


def test_persong_engine_emits_stage_spans(fixture_csv, tmp_path):
    from music_analyst_tpu.engines.persong import run_per_song_wordcount

    run_per_song_wordcount(
        str(fixture_csv), output_dir=str(tmp_path), quiet=True,
    )
    events = [
        json.loads(line)
        for line in (tmp_path / "telemetry.jsonl").read_text().splitlines()
    ]
    names = {ev["name"] for ev in events}
    assert {"ingest", "tokenize", "write"} <= names
    manifest = json.loads((tmp_path / "run_manifest.json").read_text())
    assert manifest["counters"]["rows_processed"] > 0
    assert manifest["counters"]["words_counted"] > 0


def test_train_step_records_spans():
    import jax.numpy as jnp
    import numpy as np

    from music_analyst_tpu.engines.train import (
        init_train_state,
        make_optimizer,
        make_train_step,
    )
    from music_analyst_tpu.models.llama import LlamaConfig, LlamaModel

    cfg = LlamaConfig.tiny()
    model = LlamaModel(cfg)
    opt = make_optimizer(1e-3)
    token_ids = jnp.asarray(
        np.random.default_rng(0).integers(1, cfg.vocab_size, (2, 16))
    )
    lengths = jnp.asarray([16, 12])
    state = init_train_state(model, opt, (token_ids, lengths))
    step = make_train_step(model, opt)
    tel = get_telemetry()
    before = tel.span_aggregates.get("train_step", [0])[0]
    state, loss = step(state, token_ids, lengths)
    state, loss = step(state, token_ids, lengths)
    assert tel.span_aggregates["train_step"][0] == before + 2
    assert tel.counters["train_steps"] >= 2
    assert jnp.isfinite(loss)


# --------------------------------------------------- golden byte parity


def test_artifacts_identical_with_and_without_telemetry(
    fixture_csv, tmp_path
):
    """The acceptance bar: word_counts.csv byte-identical, and
    performance_metrics.json structurally identical (timings jitter
    run-to-run; keys/counts must not)."""
    from music_analyst_tpu.engines.wordcount import run_analysis

    on_dir, off_dir = tmp_path / "on", tmp_path / "off"
    configure(enabled=True, directory=None)
    run_analysis(
        str(fixture_csv), output_dir=str(on_dir),
        ingest_backend="python", quiet=True,
    )
    configure(enabled=False)
    run_analysis(
        str(fixture_csv), output_dir=str(off_dir),
        ingest_backend="python", quiet=True,
    )

    assert (on_dir / "word_counts.csv").read_bytes() == (
        off_dir / "word_counts.csv"
    ).read_bytes()
    assert (on_dir / "top_artists.csv").read_bytes() == (
        off_dir / "top_artists.csv"
    ).read_bytes()

    def structure(obj):
        if isinstance(obj, dict):
            return {k: structure(v) for k, v in sorted(obj.items())}
        if isinstance(obj, list):
            return [structure(v) for v in obj]
        return type(obj).__name__

    on_metrics = json.loads((on_dir / "performance_metrics.json").read_text())
    off_metrics = json.loads(
        (off_dir / "performance_metrics.json").read_text()
    )
    assert structure(on_metrics) == structure(off_metrics)
    # Count fields ARE deterministic — pin them exactly.
    for key in ("total_songs", "total_words", "processes"):
        assert on_metrics[key] == off_metrics[key]

    # Telemetry-off wrote no extra files.
    assert not (off_dir / "telemetry.jsonl").exists()
    assert not (off_dir / "run_manifest.json").exists()
    assert (on_dir / "telemetry.jsonl").exists()
