"""Telemetry must never violate the driver-facing contracts.

Two hard lines in the sand: ``bench.py`` prints exactly ONE JSON line on
stdout (telemetry sub-object inside it) or, on any failure, none at all
with a non-zero exit; and ``--no-telemetry`` CLI runs leave ZERO extra
files behind.
"""

from __future__ import annotations

import json
import os
import sys

import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
import bench  # noqa: E402

from music_analyst_tpu.cli.main import main  # noqa: E402
from music_analyst_tpu.telemetry import configure, get_telemetry  # noqa: E402


@pytest.fixture(autouse=True)
def _restore_telemetry():
    """main() calls configure(); undo whatever a test left behind."""
    yield
    configure(enabled=True, directory=None)


def test_bench_is_one_process_and_refuses_a_non_tpu_backend(
    capsys, monkeypatch
):
    """``python bench.py`` measures in this process; without a TPU (and
    without the smoke label) it fails before doing any work and prints NO
    result line — there is no structured zero to mistake for a number."""
    monkeypatch.delenv("MUSICAAL_BENCH_SMOKE", raising=False)
    for gone in ("_run_parent", "_probe_child", "_probe_device",
                 "_salvage", "_fresh_flight_record", "RETRY_SLEEPS"):
        assert not hasattr(bench, gone), gone
    with pytest.raises(RuntimeError, match="measures on a TPU"):
        bench.main([])
    assert capsys.readouterr().out == ""


def test_bench_measure_summary_shape():
    """The summary measure() embeds has the fixed three-key shape the
    capture tooling reads (without running the heavy measurement)."""
    tel = configure(enabled=True, directory=None)
    with tel.span("measure"):
        pass
    summary = tel.summary(top=3)
    assert set(summary) == {"events", "top_spans", "compile"}
    assert summary["events"] >= 1
    assert summary["top_spans"][0]["name"] == "measure"
    assert {"count", "seconds"} <= set(summary["compile"])


def test_cli_no_telemetry_writes_zero_extra_files(fixture_csv, tmp_path):
    out = tmp_path / "out"
    rc = main([
        "wordcount-per-song", str(fixture_csv),
        "--output-dir", str(out), "--no-telemetry",
    ])
    assert rc == 0
    assert sorted(p.name for p in out.iterdir()) == [
        "word_counts_by_song.csv", "word_counts_global.csv",
    ]
    assert not get_telemetry().enabled


def test_cli_telemetry_dir_emits_parseable_artifacts(fixture_csv, tmp_path):
    out, tdir = tmp_path / "out", tmp_path / "telemetry"
    rc = main([
        "sentiment", str(fixture_csv), "--mock", "--limit", "3",
        "--output-dir", str(out), "--telemetry-dir", str(tdir),
    ])
    assert rc == 0
    events = [
        json.loads(line)
        for line in (tdir / "telemetry.jsonl").read_text().splitlines()
    ]
    assert events and all("t_mono" in ev for ev in events)
    manifest = json.loads((tdir / "run_manifest.json").read_text())
    assert manifest["engine"] == "sentiment"
    assert manifest["device"]["platform"] == "cpu"
    assert manifest["device"]["count"] == 8
    assert "compile" in manifest
    # The run's own output dir got no telemetry files — they went to the
    # explicit --telemetry-dir.
    assert not (out / "telemetry.jsonl").exists()
    assert not (out / "run_manifest.json").exists()


def test_cli_default_telemetry_lands_in_output_dir(fixture_csv, tmp_path):
    out = tmp_path / "out"
    rc = main([
        "wordcount-per-song", str(fixture_csv), "--output-dir", str(out),
    ])
    assert rc == 0
    assert (out / "telemetry.jsonl").exists()
    manifest = json.loads((out / "run_manifest.json").read_text())
    assert manifest["engine"] == "persong"
    assert manifest["counters"]["rows_processed"] > 0


def test_split_stays_memory_only_without_flag(fixture_csv, tmp_path):
    """The split listing is a compared artifact: no telemetry files may
    appear in its output dir unless --telemetry-dir points elsewhere."""
    cols = tmp_path / "cols"
    rc = main(["split", str(fixture_csv), "--output-dir", str(cols)])
    assert rc == 0
    assert not any(p.name.startswith(("telemetry", "run_manifest"))
                   for p in cols.iterdir())
