"""Flight recorder + heartbeat watchdog + telemetry-report (PR 4).

The acceptance anchors (ISSUE 4):

* a deliberately hung prefetch stage trips the watchdog and dumps a
  parseable ``flight_record.json`` with thread stacks, the last ≥64
  telemetry events, and taxonomy ``stage_stall``;
* SIGTERM during a sentiment run leaves a record (and the process still
  dies by SIGTERM — the handler chains to the default disposition);
* ``telemetry-report`` over driver captures (synthetic ones, written
  into ``tmp_path``) classifies each failure, and never reads a backend
  that could not *initialise* as transient.

Everything runs on the CPU-emulated mesh (conftest forces it).
"""

from __future__ import annotations

import json
import os
import pathlib
import subprocess
import sys
import textwrap
import time

import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
import bench  # noqa: E402

from music_analyst_tpu.observability.flight import FlightRecorder  # noqa: E402
from music_analyst_tpu.observability.report import (  # noqa: E402
    build_report,
    classify_error,
    load_run,
    run_telemetry_report,
)
from music_analyst_tpu.observability.watchdog import (  # noqa: E402
    HeartbeatWatchdog,
    resolve_watchdog_timeout,
    start_watchdog,
    stop_watchdog,
)
from music_analyst_tpu.telemetry import configure, get_telemetry  # noqa: E402

REPO_ROOT = pathlib.Path(__file__).parent.parent


@pytest.fixture(autouse=True)
def _clean_observability_state():
    """Each test starts with no watchdog and a quiescent recorder."""
    stop_watchdog()
    yield
    stop_watchdog()
    from music_analyst_tpu.observability.flight import get_flight_recorder

    get_flight_recorder().uninstall()
    configure(enabled=True, directory=None)


# ------------------------------------------------------------------ flight


def test_flight_ring_is_bounded_and_taps_survive_reconfigure():
    tel = configure(enabled=True, directory=None)
    rec = FlightRecorder(capacity=16)
    tel.add_tap(rec.record)
    try:
        for i in range(40):
            tel.event("filler", i=i)
        events = rec.events()
        assert len(events) == 16
        assert events[-1]["attrs"]["i"] == 39  # newest kept, oldest dropped
        # configure() resets run state — the tap must keep recording.
        tel = configure(enabled=True, directory=None)
        tel.event("after_reset")
        assert rec.events()[-1]["name"] == "after_reset"
    finally:
        tel.remove_tap(rec.record)


def test_flight_dump_writes_parseable_record(tmp_path):
    tel = configure(enabled=True, directory=None)
    rec = FlightRecorder()
    tel.add_tap(rec.record)
    try:
        tel.count("songs", 7)
        for i in range(5):
            tel.event("warm", i=i)
        path = rec.dump(
            reason="unit_test", taxonomy="host_oom", detail="synthetic",
            directory=str(tmp_path),
        )
    finally:
        tel.remove_tap(rec.record)
    assert path == str(tmp_path / "flight_record.json")
    with open(path, encoding="utf-8") as fh:
        record = json.load(fh)
    assert record["schema"] == 1
    assert record["reason"] == "unit_test"
    assert record["taxonomy"] == "host_oom"
    assert record["counters"]["songs"] == 7
    assert [e["name"] for e in record["events"][-5:]] == ["warm"] * 5
    # faulthandler stacks: at least this very test frame is visible.
    assert "thread_stacks" in record and record["thread_stacks"]
    assert "test_observability" in record["thread_stacks"]
    assert record["vitals"]["pid"] == os.getpid()
    assert rec.dump_count == 1 and rec.last_dump_path == path


def test_flight_install_is_idempotent_and_uninstalls():
    rec = FlightRecorder()
    rec.install(signals=False, excepthook=False)
    rec.install(signals=False, excepthook=False)
    tel = get_telemetry()
    assert tel._taps.count(rec.record) == 1
    rec.uninstall()
    assert rec.record not in tel._taps


# ---------------------------------------------------------------- watchdog


def test_watchdog_stage_hang_trips_and_dumps(tmp_path, monkeypatch):
    """THE acceptance test: a hung prefetch stage ⇒ flight_record.json
    with thread stacks, ≥64 telemetry events, and taxonomy stage_stall."""
    from music_analyst_tpu.observability.flight import (
        install_flight_recorder,
    )
    from music_analyst_tpu.runtime import PrefetchPipeline, Stage

    monkeypatch.setenv("MUSICAAL_FLIGHT_RECORD_DIR", str(tmp_path))
    tel = configure(enabled=True, directory=None)
    install_flight_recorder(signals=False, excepthook=False)
    # Enough history that the dump proves the ring really holds the tail.
    for i in range(80):
        tel.event("preamble", i=i)
    wd = start_watchdog(0.3)
    assert wd is not None

    record_path = tmp_path / "flight_record.json"

    def hanging_stage(item):
        # Hang until the record is on disk: the watchdog lists the trip
        # before it takes the thread stacks, so leaving on ``wd.trips``
        # races the dump and the stacks can miss this frame.
        deadline = time.time() + 15.0
        while not record_path.exists() and time.time() < deadline:
            time.sleep(0.02)
        return item

    pipe = PrefetchPipeline(
        [Stage("tokenize", hanging_stage)], depth=1, name="bench"
    )
    results = list(pipe.run([1]))
    stop_watchdog()
    assert results == [1]
    assert wd.trips, "watchdog never tripped on the hung stage"
    trip = wd.trips[0]
    assert trip["taxonomy"] == "stage_stall"
    assert trip["task"] == "bench.tokenize"

    assert record_path.exists()
    with open(record_path, encoding="utf-8") as fh:
        record = json.load(fh)
    assert record["reason"] == "watchdog"
    assert record["taxonomy"] == "stage_stall"
    assert len(record["events"]) >= 64
    # The stacks must point at the actual hung frame.
    assert "hanging_stage" in record["thread_stacks"]
    assert record["watchdog"]["trips"][0]["task"] == "bench.tokenize"


def test_watchdog_beat_rearms_and_scope_exit_unregisters():
    wd = HeartbeatWatchdog(timeout_s=0.2, dump_flight_record=False).start()
    try:
        with wd.watch("steady", kind="host"):
            for _ in range(6):
                time.sleep(0.1)
                wd.beat("steady")
        assert wd.trips == []  # beats kept it alive past 3 timeouts
        with wd.watch("silent", kind="device"):
            time.sleep(0.6)
        assert [t["taxonomy"] for t in wd.trips] == ["device_stall"]
        time.sleep(0.4)  # scope exited: no further trips accumulate
        assert len(wd.trips) == 1
    finally:
        wd.stop()


def test_watchdog_noop_when_disabled():
    from music_analyst_tpu.observability.watchdog import beat, watch

    assert start_watchdog(0) is None  # 0 = disabled
    with watch("anything", kind="device") as task:
        assert task is None
        beat("anything")  # must not raise


def test_resolve_watchdog_timeout(monkeypatch):
    monkeypatch.delenv("MUSICAAL_WATCHDOG_S", raising=False)
    assert resolve_watchdog_timeout() == 0.0
    assert resolve_watchdog_timeout(default=120.0) == 120.0
    assert resolve_watchdog_timeout("2.5") == 2.5  # explicit flag wins
    with pytest.raises(ValueError):
        resolve_watchdog_timeout("2min")
    with pytest.raises(ValueError):
        resolve_watchdog_timeout(-1)
    monkeypatch.setenv("MUSICAAL_WATCHDOG_S", "45")
    assert resolve_watchdog_timeout() == 45.0
    assert resolve_watchdog_timeout(10) == 10.0  # flag beats env
    monkeypatch.setenv("MUSICAAL_WATCHDOG_S", "0")
    assert resolve_watchdog_timeout(default=120.0) == 0.0  # env 0 disables
    monkeypatch.setenv("MUSICAAL_WATCHDOG_S", "soon")
    assert resolve_watchdog_timeout(default=7.0) == 7.0  # malformed → default


def test_manifest_carries_observability_section(tmp_path):
    tel = configure(enabled=True, directory=str(tmp_path))
    start_watchdog(30.0)
    with tel.run_scope("persong", str(tmp_path)):
        pass
    stop_watchdog()
    with open(tmp_path / "run_manifest.json", encoding="utf-8") as fh:
        manifest = json.load(fh)
    assert manifest["observability"]["watchdog"]["timeout_s"] == 30.0


# ----------------------------------------------------------------- SIGTERM


def test_sigterm_during_sentiment_run_leaves_record(tmp_path):
    """SIGTERM mid-run: the handler dumps flight_record.json and then
    chains to the default disposition, so the process still dies BY
    SIGTERM (the parent's view of the exit status is unchanged)."""
    fixture = REPO_ROOT / "tests" / "fixtures" / "mini_songs.csv"
    script = textwrap.dedent(
        """
        import os, signal, threading, time
        from music_analyst_tpu.observability import install_flight_recorder
        from music_analyst_tpu.engines.sentiment import run_sentiment

        install_flight_recorder()

        class SlowBackend:
            name = "slow-mock"
            def classify_batch(self, texts):
                time.sleep(30)
                return ["Neutral"] * len(texts)

        threading.Timer(
            1.0, lambda: os.kill(os.getpid(), signal.SIGTERM)
        ).start()
        run_sentiment(
            %r, output_dir=%r, quiet=True, batch_size=2,
            backend=SlowBackend(), prefetch_depth=1,
        )
        """
        % (str(fixture), str(tmp_path / "out"))
    )
    env = dict(os.environ)
    env["MUSICAAL_FLIGHT_RECORD_DIR"] = str(tmp_path)
    proc = subprocess.run(
        [sys.executable, "-c", script],
        capture_output=True, text=True, timeout=60,
        cwd=str(REPO_ROOT), env=env,
    )
    assert proc.returncode == -15, (proc.returncode, proc.stderr[-500:])
    record_path = tmp_path / "flight_record.json"
    assert record_path.exists(), proc.stderr[-500:]
    with open(record_path, encoding="utf-8") as fh:
        record = json.load(fh)
    assert record["reason"] == "signal:SIGTERM"
    assert record["thread_stacks"]


# ---------------------------------------------------------- classification


def test_classify_error_patterns():
    assert classify_error("replica-1: backend lost mid-run") == "backend_lost"
    assert classify_error("MemoryError") == "host_oom"
    assert classify_error("compile timed out") == "compile_hang"
    assert classify_error("", rc=124) == "harness_killed"
    assert classify_error("deadline gone: no attempt fit inside the "
                          "deadline") == "deadline_expired"
    assert classify_error("step timed out") == "attempt_timeout"
    assert classify_error("weird explosion") == "unknown_error"
    assert classify_error("", rc=0) is None
    assert classify_error(None) is None


def test_backend_init_failure_is_never_transient():
    """No chip, or a chip held by another process: fatal and immediate —
    not classified as backend loss, not retried, not failed over."""
    from music_analyst_tpu.resilience import classify_retryable, should_failover

    for message in (
        "RuntimeError: Unable to initialize backend 'tpu': UNAVAILABLE: "
        "TPU backend setup/compile error",
        "UNAVAILABLE: No TPU devices found",
    ):
        assert classify_error(message) == "unknown_error"
        exc = RuntimeError(message)
        assert classify_retryable(exc) == (False, "unknown_error")
        assert not should_failover(exc)


def _write_capture(path, rc, parsed=None, tail=""):
    """A driver capture as the round driver writes it."""
    path.write_text(json.dumps(
        {"n": 1, "cmd": "python bench.py", "rc": rc, "tail": tail,
         "parsed": parsed}
    ))
    return str(path)


def test_report_classifies_bench_captures(tmp_path):
    ok = {"metric": bench.METRIC, "value": 8730.8, "unit": "songs/sec"}
    sources = [
        _write_capture(tmp_path / "BENCH_a.json", 0, parsed=ok),
        _write_capture(tmp_path / "BENCH_b.json", 124, tail=""),
        _write_capture(
            tmp_path / "BENCH_c.json", 1,
            tail="RuntimeError: Unable to initialize backend 'tpu'",
        ),
    ]
    records = [load_run(s) for s in sources]
    assert all(r is not None for r in records)
    by_label = {r["label"]: r for r in records}
    assert by_label["BENCH_a"]["ok"] is True
    assert by_label["BENCH_b"]["error_kind"] == "harness_killed"
    assert by_label["BENCH_c"]["error_kind"] == "unknown_error"
    report = build_report(records)
    assert report["taxonomy_histogram"] == {
        "harness_killed": 1, "unknown_error": 1,
    }
    assert report["newest"] == {
        "label": "BENCH_c", "ok": False, "error_kind": "unknown_error",
    }


def test_telemetry_report_over_synthetic_runs(tmp_path, capsys):
    """Two synthetic telemetry run dirs + a failed driver capture render
    the taxonomy histogram; exit 1 because the newest run failed."""
    # Run A: healthy manifest with a pipeline stall breakdown + recompiles.
    run_a = tmp_path / "run_a"
    run_a.mkdir()
    (run_a / "run_manifest.json").write_text(json.dumps({
        "schema": 1, "engine": "sentiment", "wall_seconds": 12.5,
        "compile": {"count": 3, "seconds": 4.2},
        "counters": {"profiling.recompiles": 2},
        "pipeline": {"pipeline": {"depth": 2, "stages": [
            {"stage": "tokenize", "items": 10, "work_s": 1.0,
             "stall_s": 0.4, "backpressure_s": 0.0, "queue_depth_max": 2},
        ], "max_queue_depth": 2}},
    }))
    (run_a / "telemetry.jsonl").write_text(
        "\n".join(json.dumps({"type": "event", "name": "x"})
                  for _ in range(5)) + "\n"
    )
    # Run B: a watchdog trip in the JSONL and a flight record on disk.
    run_b = tmp_path / "run_b"
    run_b.mkdir()
    (run_b / "telemetry.jsonl").write_text(json.dumps({
        "type": "event", "name": "watchdog_trip",
        "attrs": {"task": "bench.h2d", "taxonomy": "stage_stall"},
    }) + "\n")
    (run_b / "flight_record.json").write_text(json.dumps({
        "schema": 1, "reason": "watchdog", "taxonomy": "stage_stall",
        "detail": "bench.h2d silent for 2s", "events": [],
    }))
    failed = _write_capture(tmp_path / "BENCH_z.json", 124)
    rc = run_telemetry_report([str(run_a), str(run_b), failed])
    out = capsys.readouterr().out
    assert rc == 1  # newest (the killed capture) failed
    assert "error taxonomy:" in out
    assert "stage_stall" in out and "harness_killed" in out
    assert "pipeline stalls" in out and "tokenize" in out
    assert "recompiles" in out and "run_a: 2" in out
    assert "FAILED (harness_killed)" in out


def test_telemetry_report_exit_codes(tmp_path, capsys):
    assert run_telemetry_report([str(tmp_path / "nope.json")]) == 2
    capsys.readouterr()
    ok_line = tmp_path / "ok.json"
    ok_line.write_text(json.dumps({
        "metric": bench.METRIC, "value": 100.0, "unit": "songs/sec",
    }))
    assert run_telemetry_report([str(ok_line)]) == 0
    capsys.readouterr()


def test_cli_telemetry_report_subcommand(capsys, tmp_path):
    from music_analyst_tpu.cli.main import main

    ok = {"metric": bench.METRIC, "value": 8730.8, "unit": "songs/sec"}
    rc = main(["telemetry-report", "--json",
               _write_capture(tmp_path / "BENCH_a.json", 124),
               _write_capture(tmp_path / "BENCH_b.json", 0, parsed=ok)])
    out = capsys.readouterr().out.strip().splitlines()
    assert rc == 0  # newest is the healthy capture
    report = json.loads(out[-1])
    assert report["taxonomy_histogram"] == {"harness_killed": 1}


def test_nothing_in_package_imports_removed_shim():
    """Satellite: metrics/tracing.py is gone and nothing references it
    (the runtime-pipeline suite has the import-level twin of this)."""
    pkg_root = REPO_ROOT / "music_analyst_tpu"
    assert not (pkg_root / "metrics" / "tracing.py").exists()
    offenders = [
        str(p) for p in pkg_root.rglob("*.py")
        if "metrics.tracing" in p.read_text(encoding="utf-8")
    ]
    assert offenders == []
