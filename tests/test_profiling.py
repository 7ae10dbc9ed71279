"""Profiling layer: compile introspection, collective accounting, traces,
and the profile-diff regression gate.

The acceptance pins from the observability issue live here: a two-shape
workload must show exactly 2 compiles + 1 recompile (and the counter must
land in ``telemetry.jsonl``), collective byte counters must match the
analytic ring costs on the 8-device CPU mesh, ``profile-diff`` must catch
a synthetic 20% throughput regression with a nonzero exit, and golden
artifacts must stay byte-identical with profiling enabled.
"""

import json

import numpy as np
import pytest

from music_analyst_tpu.profiling.collectives import (
    all_gather_bytes,
    all_to_all_bytes,
    ppermute_bytes,
    psum_bytes,
    record_collective,
    stage_table,
)
from music_analyst_tpu.profiling.diff import run_profile_diff
from music_analyst_tpu.telemetry import configure, get_telemetry


@pytest.fixture(autouse=True)
def _fresh_telemetry():
    """Clean registry + empty stage-table accumulator per test."""
    from music_analyst_tpu.profiling.collectives import _STAGE_LOCK, _STAGE_TOTALS

    with _STAGE_LOCK:
        _STAGE_TOTALS.clear()
    yield configure(enabled=True, directory=None)
    configure(enabled=True, directory=None)
    with _STAGE_LOCK:
        _STAGE_TOTALS.clear()


def _jsonl_events(path, name=None):
    events = [
        json.loads(line) for line in path.read_text().splitlines() if line
    ]
    if name is not None:
        events = [e for e in events if e.get("name") == name]
    return events


# ------------------------------------------------------ analytic estimators


def test_ring_cost_estimators_hand_computed():
    # Ring all-reduce: reduce-scatter + all-gather halves.
    assert psum_bytes(1024, 8) == 2 * 7 * 1024 // 8
    assert all_gather_bytes(512, 8) == 7 * 512
    assert all_to_all_bytes(800, 8) == 7 * 800 // 8
    assert ppermute_bytes(64) == 64
    # Single participant moves nothing (ppermute still sends to itself's
    # neighbor — a ring of one is the identity, but the estimator reports
    # the payload; callers don't issue it on 1-device meshes).
    assert psum_bytes(1024, 1) == 0
    assert all_gather_bytes(512, 1) == 0
    assert all_to_all_bytes(800, 1) == 0


def test_record_collective_rejects_unknown_kind():
    with pytest.raises(ValueError, match="unknown collective kind"):
        record_collective("s", "broadcastify", payload_bytes=1, n_devices=2)


def test_record_collective_counters_events_and_stage_table(tmp_path):
    tel = get_telemetry()
    with tel.run_scope("x", str(tmp_path)):
        per_dev = record_collective(
            "stage_a", "psum", payload_bytes=4096, n_devices=8
        )
        record_collective(
            "stage_b", "ppermute", payload_bytes=64, n_devices=8, count=10
        )
        assert per_dev == psum_bytes(4096, 8)
        counters = dict(tel.counters)
        # run_scope exit emits + clears the table; snapshot it while open.
        rows = {r["stage"]: r for r in stage_table()}
    assert counters["collectives.psum_bytes"] == psum_bytes(4096, 8)
    assert counters["collectives.ppermute_bytes"] == 64 * 10
    assert (
        counters["collectives.total_bytes"]
        == psum_bytes(4096, 8) + 64 * 10
    )
    assert rows["stage_a"]["bytes"] == psum_bytes(4096, 8)
    assert rows["stage_b"]["calls"] == 10

    log = tmp_path / "telemetry.jsonl"
    events = _jsonl_events(log, "collective")
    assert {e["attrs"]["stage"] for e in events} == {"stage_a", "stage_b"}
    (table_event,) = _jsonl_events(log, "collective_stage_table")
    table = {r["stage"]: r for r in table_event["attrs"]["rows"]}
    assert table["stage_b"]["bytes"] == 64 * 10


# -------------------------------------------------- compile introspection


def test_recompile_detector_two_shapes(tmp_path):
    """Two distinct input shapes ⇒ exactly 2 compiles and 1 recompile,
    both visible in the JSONL stream (the issue's acceptance pin)."""
    import jax.numpy as jnp

    from music_analyst_tpu.profiling.compile import profiled_jit

    fn = profiled_jit(lambda x: x * 2 + 1, name="recompile_probe")
    tel = get_telemetry()
    with tel.run_scope("x", str(tmp_path)):
        a = np.arange(8, dtype=np.float32)
        b = np.arange(16, dtype=np.float32)
        np.testing.assert_allclose(np.asarray(fn(a)), a * 2 + 1)
        np.testing.assert_allclose(np.asarray(fn(a)), a * 2 + 1)  # cached
        np.testing.assert_allclose(np.asarray(fn(b)), b * 2 + 1)  # recompile
        counters = dict(tel.counters)
    assert counters["profiling.compiles"] == 2
    assert counters["profiling.recompiles"] == 1
    assert len(fn.records) == 2

    log = tmp_path / "telemetry.jsonl"
    compiles = [
        e for e in _jsonl_events(log, "compile")
        if e["attrs"]["fn"] == "recompile_probe"
    ]
    assert len(compiles) == 2
    (recompile,) = _jsonl_events(log, "recompile")
    assert recompile["attrs"]["fn"] == "recompile_probe"
    assert "float32[8]" in recompile["attrs"]["prev_aval"]
    assert "float32[16]" in recompile["attrs"]["new_aval"]
    # The recompile counter must land in the stream's run_end record too.
    (run_end,) = _jsonl_events(log, "run_end")
    assert run_end["attrs"]["counters"]["profiling.recompiles"] == 1


def test_compile_record_fields():
    from music_analyst_tpu.profiling.compile import profiled_jit

    fn = profiled_jit(lambda x: x @ x.T, name="record_fields_probe")
    x = np.ones((4, 4), dtype=np.float32)
    np.asarray(fn(x))
    (rec,) = fn.records.values()
    d = rec.as_dict()
    assert d["name"] == "record_fields_probe"
    assert "float32[4, 4]" in d["aval_key"]
    # The HLO fingerprint is the run-comparison anchor; cost/memory fields
    # are backend-dependent (CPU PJRT has no memory_analysis) and may be
    # null, but must be numeric when present.
    assert isinstance(d["hlo_fingerprint"], str) and d["hlo_fingerprint"]
    assert d["compile_seconds"] > 0
    for key in ("flops", "bytes_accessed", "temp_bytes"):
        assert d[key] is None or isinstance(d[key], (int, float))


def test_static_keyword_keys_a_compile_and_reaches_the_executable():
    """A static argument given by keyword is part of the compile's key (one
    record a value) and is left out of the executable's call, which was
    specialised on it: the compiled program itself runs, not a second
    compile on the plain-jit fallback."""
    import jax.numpy as jnp

    from music_analyst_tpu.profiling.compile import profiled_jit

    fn = profiled_jit(lambda x, width=None: x[: (width or x.shape[0])] * 2,
                      name="static_probe", static_argnames=("width",))
    x = jnp.arange(8, dtype=jnp.float32)
    assert np.asarray(fn(x, width=3)).tolist() == [0.0, 2.0, 4.0]
    assert np.asarray(fn(x, width=5)).shape == (5,)
    assert np.asarray(fn(x)).shape == (8,)
    assert np.asarray(fn(x, width=3)).shape == (3,)  # cached
    assert len(fn.records) == 3
    assert all(exe is not None for exe in fn._compiled.values())
    assert fn._jit._cache_size() == 0  # the fallback never compiled


def test_profiled_jit_under_outer_jit_defers_to_plain_jit():
    """jit-of-jit (the shard_map local fns): tracers must pass through."""
    import jax

    from music_analyst_tpu.profiling.compile import profiled_jit

    inner = profiled_jit(lambda x: x + 1, name="nested_probe")
    outer = jax.jit(lambda x: inner(x) * 3)
    np.testing.assert_allclose(
        np.asarray(outer(np.float32(2.0))), 9.0
    )
    # The traced call must NOT have minted an AOT record for the tracer.
    assert all("Traced" not in k for k in inner.records)


def test_manifest_profiling_section(tmp_path):
    from music_analyst_tpu.profiling.compile import profiled_jit

    fn = profiled_jit(lambda x: x - 5, name="manifest_probe")
    tel = get_telemetry()
    with tel.run_scope("x", str(tmp_path)):
        np.asarray(fn(np.arange(4, dtype=np.int32)))
    manifest = json.loads((tmp_path / "run_manifest.json").read_text())
    names = {rec["name"] for rec in manifest["profiling"]["compiles"]}
    assert "manifest_probe" in names


# ------------------------------------------- collective bytes vs analytic


def test_sharded_histogram_bytes_match_analytic(tmp_path):
    from music_analyst_tpu.ops.histogram import sharded_histogram
    from music_analyst_tpu.parallel.mesh import data_parallel_mesh
    from music_analyst_tpu.utils.shapes import round_pow2

    mesh = data_parallel_mesh(8)
    vocab = 100
    ids = np.arange(vocab, dtype=np.int32)
    tel = get_telemetry()
    with tel.run_scope("x", str(tmp_path)):
        counts = np.asarray(sharded_histogram(ids, vocab, mesh))
        counters = dict(tel.counters)
    np.testing.assert_array_equal(counts, np.ones(vocab, dtype=np.int32))
    padded_vocab = round_pow2(vocab, 1 << 10)
    expected = psum_bytes(padded_vocab * 4, 8)
    assert counters["collectives.psum_bytes"] == expected
    assert counters["collectives.total_bytes"] == expected


def test_pipeline_records_ppermute_and_broadcast(tmp_path):
    import jax.numpy as jnp

    from music_analyst_tpu.parallel.mesh import data_parallel_mesh
    from music_analyst_tpu.parallel.pipeline import pipeline_apply

    mesh = data_parallel_mesh(4, axis="pp")
    n_stages, n_micro, mb, dim = 4, 3, 2, 8
    params = {"w": jnp.ones((n_stages, 1, dim))}
    microbatches = jnp.ones((n_micro, mb, dim), jnp.float32)
    tel = get_telemetry()
    with tel.run_scope("x", str(tmp_path)):
        pipeline_apply(
            lambda p, x: x + p["w"][0], params, microbatches, mesh, axis="pp"
        )
        counters = dict(tel.counters)
    act = mb * dim * 4
    assert counters["collectives.ppermute_bytes"] == act * (
        n_micro + n_stages - 1
    )
    assert counters["collectives.psum_bytes"] == psum_bytes(
        n_micro * act, n_stages
    )


# --------------------------------------------------------- trace artifacts


def test_profile_run_writes_chrome_trace(tmp_path):
    from music_analyst_tpu.profiling.trace import profile_run

    tel = get_telemetry()
    with profile_run(str(tmp_path / "prof")):
        with tel.span("unit_test_stage", rows=7):
            pass
    trace = json.loads((tmp_path / "prof" / "trace_spans.json").read_text())
    events = trace["traceEvents"]
    (span_event,) = [e for e in events if e["name"] == "unit_test_stage"]
    assert span_event["ph"] == "X"
    assert span_event["dur"] >= 0
    assert span_event["args"]["rows"] == "7"
    assert any(e["ph"] == "M" and e["name"] == "thread_name" for e in events)


def test_profile_run_raises_when_the_profiler_cannot_start(
    tmp_path, monkeypatch
):
    """A device trace that was asked for and silently not taken is worse
    than no run: the failure propagates, nothing is swallowed."""
    import jax

    from music_analyst_tpu.profiling.trace import profile_run

    def refuse(*args, **kwargs):
        raise RuntimeError("profiler service unavailable")

    monkeypatch.setattr(jax.profiler, "start_trace", refuse)
    with pytest.raises(RuntimeError, match="profiler service unavailable"):
        with profile_run(str(tmp_path / "prof")):
            raise AssertionError("the body must not run unprofiled")


def test_profile_run_without_device_trace_stays_off_the_profiler(
    tmp_path, monkeypatch
):
    """The replica-router parent holds no chip: it gets the span trace
    without ever starting jax.profiler (which initialises the backend)."""
    import jax

    from music_analyst_tpu.profiling.trace import profile_run

    def forbidden(*args, **kwargs):
        raise AssertionError("jax.profiler touched with device_trace=False")

    monkeypatch.setattr(jax.profiler, "start_trace", forbidden)
    monkeypatch.setattr(jax.profiler, "stop_trace", forbidden)
    with profile_run(str(tmp_path / "prof"), device_trace=False):
        pass
    assert (tmp_path / "prof" / "trace_spans.json").exists()


def test_cli_profile_dir_flag(fixture_csv, tmp_path, capsys):
    from music_analyst_tpu.cli.main import main

    prof = tmp_path / "prof"
    rc = main(
        [
            "analyze", str(fixture_csv),
            "--output-dir", str(tmp_path / "out"),
            "--ingest", "python",
            "--profile-dir", str(prof),
        ]
    )
    capsys.readouterr()
    assert rc == 0
    assert (prof / "trace_spans.json").exists()


# --------------------------------------------------- profile-diff gate


def _bench_line(value, metric="sentiment_songs_per_sec_distilbert"):
    return {"metric": metric, "value": value, "unit": "songs/sec"}


def test_profile_diff_detects_20pct_regression(tmp_path, capsys):
    a = tmp_path / "a.json"
    b = tmp_path / "b.json"
    a.write_text(json.dumps(_bench_line(1000.0)))
    b.write_text(json.dumps(_bench_line(800.0)))  # synthetic -20%
    assert run_profile_diff(str(a), str(b)) == 1
    out = capsys.readouterr().out
    assert "REGRESSION" in out


def test_profile_diff_passes_within_threshold(tmp_path, capsys):
    a = tmp_path / "a.json"
    b = tmp_path / "b.json"
    a.write_text(json.dumps(_bench_line(1000.0)))
    b.write_text(json.dumps(_bench_line(950.0)))  # -5% < 10% threshold
    assert run_profile_diff(str(a), str(b)) == 0
    assert "verdict: ok" in capsys.readouterr().out


def test_profile_diff_threshold_flag(tmp_path, capsys):
    a = tmp_path / "a.json"
    b = tmp_path / "b.json"
    a.write_text(json.dumps(_bench_line(1000.0)))
    b.write_text(json.dumps(_bench_line(950.0)))
    assert run_profile_diff(str(a), str(b), threshold=0.02) == 1
    capsys.readouterr()


def test_profile_diff_manifest_wall_regression(tmp_path, capsys):
    a = tmp_path / "a.json"
    b = tmp_path / "b.json"
    a.write_text(json.dumps({"schema": 1, "wall_seconds": 10.0}))
    b.write_text(json.dumps({"schema": 1, "wall_seconds": 14.0}))  # +40%
    assert run_profile_diff(str(a), str(b)) == 1
    capsys.readouterr()


def test_profile_diff_bad_input_exits_2(tmp_path, capsys):
    a = tmp_path / "a.json"
    a.write_text(json.dumps(_bench_line(1000.0)))
    assert run_profile_diff(str(a), "not json at all") == 2
    capsys.readouterr()


def test_profile_diff_cli_subcommand(tmp_path, capsys):
    from music_analyst_tpu.cli.main import main

    a = tmp_path / "a.json"
    b = tmp_path / "b.json"
    a.write_text(json.dumps(_bench_line(1000.0)))
    b.write_text(json.dumps(_bench_line(790.0)))
    assert main(["profile-diff", str(a), str(b)]) == 1
    assert main(["profile-diff", str(a), str(a)]) == 0
    capsys.readouterr()


# ------------------------------------------------- golden-artifact safety


def test_word_counts_byte_identical_with_profiling(fixture_csv, tmp_path,
                                                   capsys):
    """Profiling must ride alongside the golden contracts, never in them:
    the same analysis with telemetry off vs profiling fully on produces
    byte-identical word_counts.csv."""
    from music_analyst_tpu.cli.main import main

    rc = main(
        [
            "analyze", str(fixture_csv),
            "--output-dir", str(tmp_path / "plain"),
            "--ingest", "python",
            "--no-telemetry",
        ]
    )
    assert rc == 0
    configure(enabled=True, directory=None)
    rc = main(
        [
            "analyze", str(fixture_csv),
            "--output-dir", str(tmp_path / "profiled"),
            "--ingest", "python",
            "--profile-dir", str(tmp_path / "prof"),
        ]
    )
    assert rc == 0
    capsys.readouterr()
    assert (
        (tmp_path / "plain" / "word_counts.csv").read_bytes()
        == (tmp_path / "profiled" / "word_counts.csv").read_bytes()
    )
