"""The three metrics read from the program's own spans (``read_batch_ms``,
``job_head_ms``, ``job_tail_ms``) and the parser they share, on hand-written
``telemetry.jsonl`` files whose answers are worked out here; a log of the
program before it recorded these spans gives ``None`` and ``run.py`` leaves
the metric out of the line."""

import json
import os
import types

import pytest

import job_spans
from layer_metrics import job_head_ms, job_tail_ms, read_batch_ms
from tools import job_edges


def _span(name, t_mono, dur_s, thread="MainThread", **attrs):
    event = {"type": "span", "name": name, "span_id": 0, "parent_id": None,
             "thread": thread, "t_wall": 1.7e9 + t_mono, "t_mono": t_mono,
             "dur_s": dur_s}
    if attrs:
        event["attrs"] = attrs
    return event


def _event(name, t_mono):
    return {"type": "event", "name": name, "t_wall": 1.7e9 + t_mono,
            "t_mono": t_mono}


def _pipe(name, t_mono, dur_s, seq, thread, **attrs):
    return _span(name, t_mono, dur_s, thread=thread, pipeline="pipeline",
                 seq=seq, **attrs)


# Job A: three full batches of 4 rows and a short last one.  Head: the
# first h2d ends at 100.250, 250 ms after run_start.  Full-batch reads of
# 200, 220 and 260 ms: median 220 (the 50 ms read of the 1-row batch does
# not count).  Tail: last compute ends at 102.000, manifest at 102.080.
JOB_A = [
    _event("run_start", 100.000),
    _span("backend_init", 100.001, 0.002),
    _pipe("read", 100.010, 0.200, 0, "pipeline-source", rows=4),
    _pipe("tokenize", 100.211, 0.030, 0, "pipeline-tokenize"),
    _pipe("h2d", 100.242, 0.008, 0, "pipeline-h2d"),
    _pipe("wait", 100.005, 0.246, 0, "MainThread"),
    _pipe("read", 100.211, 0.220, 1, "pipeline-source", rows=4),
    _pipe("h2d", 100.470, 0.004, 1, "pipeline-h2d"),
    _pipe("read", 100.432, 0.260, 2, "pipeline-source", rows=4),
    _pipe("read", 100.693, 0.050, 3, "pipeline-source", rows=1),
    _span("compute", 100.251, 0.400, rows=4, batch=0),
    _pipe("wait", 100.660, 0.001, 1, "MainThread"),
    _span("compute", 101.900, 0.100, rows=1, batch=3),
    _span("write", 102.000, 0.020, rows=1, batch=3),
    _span("write_totals", 102.021, 0.010),
    _span("engine:sentiment", 100.000, 2.032),
    _event("run_end", 102.036),
    _span("manifest", 102.035, 0.045),
]

# Job B: two full batches (reads of 300 and 100 ms: median 200) and a read
# of another pipeline, which is not this metric's.  Head 400 ms, tail 120.
JOB_B = [
    _event("run_start", 200.000),
    _pipe("read", 200.010, 0.300, 0, "pipeline-source", rows=4),
    _pipe("h2d", 200.390, 0.010, 0, "pipeline-h2d"),
    _pipe("wait", 200.005, 0.396, 0, "MainThread"),
    _pipe("read", 200.320, 0.100, 1, "pipeline-source", rows=4),
    _span("read", 200.500, 0.900, thread="stream-source",
          pipeline="stream_histogram", seq=0, rows=4),
    _span("compute", 200.401, 1.000, rows=4, batch=0),
    _span("compute", 202.000, 1.000, rows=4, batch=1),
    _span("write", 203.000, 0.050, rows=4, batch=1),
    _span("write_totals", 203.051, 0.009),
    _event("run_end", 203.070),
    _span("manifest", 203.065, 0.055),
]

# The program before this PR: one summed `ingest` "span", stage spans
# without `seq`, no `read`, `wait`, `write_totals` or `manifest`.
JOB_OLD = [
    _event("run_start", 300.000),
    _span("tokenize", 300.200, 0.030, thread="pipeline-tokenize",
          pipeline="pipeline"),
    _span("h2d", 300.231, 0.008, thread="pipeline-h2d", pipeline="pipeline"),
    _span("compute", 300.240, 1.000, rows=4),
    _span("write", 301.240, 0.020, rows=4),
    _span("ingest", 300.014, 3.150, thread="pipeline-source", rows=8),
    _span("engine:sentiment", 300.000, 1.300),
    _event("run_end", 301.301),
]


def _artifacts(tmp_path, *jobs):
    out = []
    for index, events in enumerate(jobs):
        directory = tmp_path / f"job{index}" / "sentiment"
        directory.mkdir(parents=True)
        if events is not None:
            (directory / "telemetry.jsonl").write_text(
                "".join(json.dumps(ev) + "\n" for ev in events))
        out.append({"index": index, "parts": {
            "sentiment": {"dir": str(directory), "seconds": 2.0}}})
    return {"jobs": out}


def test_parser_keeps_events_threads_and_attrs(tmp_path):
    artifacts = _artifacts(tmp_path, JOB_A)
    (log,) = job_spans.sentiment_logs(artifacts)
    assert log["run_start"] == 100.000
    assert len(log["spans"]) == 16  # the two events are not spans
    first = job_spans.first_item(log, "read")
    assert first["thread"] == "pipeline-source"
    assert first["attrs"] == {"pipeline": "pipeline", "seq": 0, "rows": 4}
    assert first["end"] == pytest.approx(100.210)
    assert [s["attrs"]["batch"] for s in job_spans.named(log, "compute")] == [0, 3]
    assert job_spans.named(log, "compute", batch=3)[0]["t_mono"] == 101.900
    assert job_spans.read_log(str(tmp_path / "nothing.jsonl")) is None


def test_one_job_by_hand(tmp_path):
    artifacts = _artifacts(tmp_path, JOB_A)
    assert read_batch_ms.read(artifacts) == pytest.approx(220.0)
    assert job_head_ms.read(artifacts) == pytest.approx(250.0)
    assert job_tail_ms.read(artifacts) == pytest.approx(80.0)


def test_median_over_jobs_by_hand(tmp_path):
    artifacts = _artifacts(tmp_path, JOB_A, JOB_B)
    assert read_batch_ms.read(artifacts) == pytest.approx((220.0 + 200.0) / 2)
    assert job_head_ms.read(artifacts) == pytest.approx((250.0 + 400.0) / 2)
    assert job_tail_ms.read(artifacts) == pytest.approx((80.0 + 120.0) / 2)
    # a job that left no log, or an analyze-only job, takes no part
    artifacts["jobs"].append({"index": 2, "parts": {"analyze": {"dir": "x"}}})
    artifacts = {"jobs": artifacts["jobs"]
                 + _artifacts(tmp_path / "more", None)["jobs"]}
    assert job_head_ms.read(artifacts) == pytest.approx(325.0)


@pytest.mark.parametrize("reader", [read_batch_ms, job_head_ms, job_tail_ms])
def test_without_the_new_spans_there_is_no_number(tmp_path, reader):
    assert reader.read(_artifacts(tmp_path, JOB_OLD)) is None
    assert reader.read({"jobs": []}) is None
    assert reader.read({}) is None
    # jobs that have the spans decide; those that lack them are left out
    mixed = _artifacts(tmp_path / "mixed", JOB_OLD, JOB_A)
    assert reader.read(mixed) is not None


def test_run_leaves_out_a_metric_whose_spans_are_absent(
        tmp_path, monkeypatch, capsys):
    """``run.py`` with a driver that hands it artifacts of the old program:
    the line holds the metrics that have a source and not the three."""
    import run

    def fake_driver(events):
        artifacts = _artifacts(tmp_path / str(len(events)), events)
        artifacts.update(setup={"backend_init_s": 7.0, "compile_s": 6.0},
                         device={"memory_peak_bytes": 5}, window_compiles=0,
                         trace=None)
        return types.SimpleNamespace(
            setup=lambda cell: {},
            run=lambda state, seconds, trace: {
                "correct": True, "attempted": 1, "failed": 0,
                "device": {"platform": "none"}, "breakdown": None,
                "artifacts": artifacts, "measures": {}})

    real_import = run.importlib.import_module
    lines = {}
    for label, events in (("old", JOB_OLD), ("new", JOB_A)):
        monkeypatch.setattr(
            run.importlib, "import_module",
            lambda name, events=events: (
                fake_driver(events) if name.startswith("drivers.")
                else real_import(name)))
        assert run.main(["--workload", "sentiment_corpus", "--seconds", "1",
                         "--trace", "1"]) == 0
        lines[label] = json.loads(capsys.readouterr().out.splitlines()[-1])
    new = {"read_batch_ms", "job_head_ms", "job_tail_ms"}
    assert not new & set(lines["old"]["metrics"])
    assert {"backend_init_s", "compile_s"} <= set(lines["old"]["metrics"])
    assert set(lines["new"]["metrics"]) == set(lines["old"]["metrics"]) | new
    assert lines["new"]["metrics"]["job_head_ms"] == {
        "value": pytest.approx(250.0), "unit": "ms"}


def test_job_edges_row_and_order(tmp_path):
    run_dir = tmp_path / "out" / "sentiment_corpus" / "run"
    for job, events in (("job10", JOB_B), ("job2", JOB_A),
                        ("warmup", JOB_OLD)):
        directory = run_dir / job / "sentiment"
        directory.mkdir(parents=True)
        (directory / "telemetry.jsonl").write_text(
            "".join(json.dumps(ev) + "\n" for ev in events))
    found = job_edges.job_logs(str(tmp_path / "out"), "*")
    assert [(cell, job) for cell, job, _ in found] == [
        ("sentiment_corpus", "warmup"), ("sentiment_corpus", "job2"),
        ("sentiment_corpus", "job10")]
    row = job_edges.row(job_spans.read_log(found[1][2]))
    assert row == {
        "job_s": pytest.approx(2.080), "head": pytest.approx(250.0),
        "tail": pytest.approx(80.0), "read0": pytest.approx(200.0),
        "read_med": pytest.approx(220.0), "tokenize0": pytest.approx(30.0),
        "h2d0": pytest.approx(8.0), "wait_sum": pytest.approx(247.0),
        "write_totals": pytest.approx(10.0), "manifest": pytest.approx(45.0),
    }
    old = job_edges.row(job_spans.read_log(found[0][2]))
    assert set(old) == set(job_edges.COLUMNS)
    assert all(value is None for value in old.values())
    assert os.path.basename(found[0][2]) == "telemetry.jsonl"
