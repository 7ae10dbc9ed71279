"""Sentiment pipeline e2e with the mock backend: artifacts + counts."""

import csv
import json

from music_analyst_tpu.engines.sentiment import get_backend, run_sentiment
from tests.test_keyword_sentiment import reference_mock_classify


def test_end_to_end_mock(fixture_csv, tmp_path):
    result = run_sentiment(
        str(fixture_csv), mock=True, output_dir=str(tmp_path), quiet=True
    )
    # Oracle: run the reference heuristic over the same DictReader rows.
    import csv as _csv

    with open(fixture_csv, newline="", encoding="utf-8") as fh:
        rows = list(_csv.DictReader(fh))
    want = [reference_mock_classify(r.get("text") or "") for r in rows]
    got = [r.label for r in result.rows]
    assert got == want

    totals = json.loads((tmp_path / "sentiment_totals.json").read_text())
    assert list(totals.keys()) == ["Positive", "Neutral", "Negative"]
    assert sum(totals.values()) == len(rows)

    with open(tmp_path / "sentiment_details.csv", newline="") as fh:
        detail_rows = list(csv.DictReader(fh))
    assert [r["label"] for r in detail_rows] == want
    assert all(
        len(r["latency_seconds"].split(".")[1]) == 4 for r in detail_rows
    ), "latency must be 4-decimal formatted"


def test_limit_respected(fixture_csv, tmp_path):
    result = run_sentiment(
        str(fixture_csv), mock=True, limit=2, output_dir=str(tmp_path), quiet=True
    )
    assert len(result.rows) == 2


def test_backend_dispatch():
    assert get_backend("llama3", mock=True).name == "mock"
    assert get_backend("mock").name == "mock"


def test_length_buckets_end_to_end(fixture_csv, tmp_path):
    """Bucketed encoder run produces the full artifact set with one label
    per dataset row."""
    result = run_sentiment(
        str(fixture_csv),
        model="distilbert-tiny",
        output_dir=str(tmp_path),
        quiet=True,
        length_buckets=(16, 32),
        batch_size=4,
    )
    assert sum(result.counts.values()) == len(result.rows) > 0
    assert (tmp_path / "sentiment_totals.json").exists()


def test_length_buckets_rejected_for_non_encoder(fixture_csv, tmp_path):
    import pytest

    with pytest.raises(ValueError, match="encoder-classifier"):
        run_sentiment(
            str(fixture_csv), mock=True, output_dir=str(tmp_path),
            quiet=True, length_buckets=(16,),
        )


def test_injected_backend_guard_matches_get_backend_unset(fixture_csv,
                                                          tmp_path):
    """run_sentiment's injected-backend guard and get_backend must agree on
    what an "unset" length_buckets is: an empty sequence means no buckets
    in both entry points (r4 advisor finding), while a non-empty one still
    raises alongside an explicit backend."""
    import pytest

    from music_analyst_tpu.models.mock import MockKeywordClassifier

    result = run_sentiment(
        str(fixture_csv), backend=MockKeywordClassifier(),
        output_dir=str(tmp_path), quiet=True, length_buckets=(),
    )
    assert sum(result.counts.values()) == len(result.rows) > 0
    with pytest.raises(ValueError, match="cannot be combined"):
        run_sentiment(
            str(fixture_csv), backend=MockKeywordClassifier(),
            output_dir=str(tmp_path), quiet=True, length_buckets=(16,),
        )
    # A scalar slip gets a clear message at both entry points, not a bare
    # len(int) TypeError from deep inside.
    with pytest.raises(TypeError, match="sequence of ints"):
        run_sentiment(
            str(fixture_csv), backend=MockKeywordClassifier(),
            output_dir=str(tmp_path), quiet=True, length_buckets=32,
        )
    with pytest.raises(TypeError, match="sequence of ints"):
        get_backend("distilbert-tiny", length_buckets=32)

