"""Percentiles, latency from the due time, and the arrival schedules."""

import pytest

import common
import loadgen


def test_nearest_rank_percentiles():
    values = list(range(1, 101))
    assert common.percentile(values, 0.50) == 50
    assert common.percentile(values, 0.99) == 99
    assert common.percentile(values, 1.0) == 100
    assert common.percentile([7.0], 0.99) == 7.0
    assert common.percentile([3, 1, 2], 0.5) == 2
    with pytest.raises(ValueError):
        common.percentile([], 0.5)
    assert common.median([4, 1, 3, 2]) == 2.5


def test_latency_runs_from_the_due_time_not_from_the_send():
    # four requests due every 10 ms; the generator stalls and sends the
    # last two 30 ms late; the server answers each 5 ms after it was sent
    due = [0.010, 0.020, 0.030, 0.040]
    sent = [0.010, 0.020, 0.060, 0.070]
    received = [s + 0.005 for s in sent]
    out = loadgen.summarize(due, sent, received, limit_ms=20)
    # from the send every latency would be 5 ms; from the due time the
    # stall shows: 5, 5, 35, 35
    assert out["latency_p50_ms"] == pytest.approx(5.0)
    assert out["latency_p99_ms"] == pytest.approx(35.0)
    assert out["met_limit_share"] == pytest.approx(0.5)
    assert out["lateness_median_ms"] == pytest.approx(15.0)
    assert out["lateness_max_ms"] == pytest.approx(30.0)


def test_an_unanswered_request_ranks_last_and_misses_the_limit():
    due = [0.0, 0.1, 0.2, 0.3]
    sent = list(due)
    received = [0.01, 0.11, None, 0.31]
    out = loadgen.summarize(due, sent, received, limit_ms=100)
    assert out["unanswered"] == 1
    assert out["latency_p99_ms"] == float("inf")
    assert out["latency_p50_ms"] == pytest.approx(10.0)
    assert out["met_limit_share"] == pytest.approx(0.75)


def test_schedules_are_seeded_and_hold_their_rate():
    a = loadgen.poisson_times(200.0, 10.0, seed=3)
    assert a == loadgen.poisson_times(200.0, 10.0, seed=3)
    assert a != loadgen.poisson_times(200.0, 10.0, seed=4)
    assert a == sorted(a) and 0 < a[0] and a[-1] < 10.0
    assert len(a) == pytest.approx(2000, rel=0.1)
    spec = {"process": "poisson", "rate_rps": 200.0}
    assert loadgen.arrival_times(spec, 10.0, 3) == a


def test_bursts_carry_the_burst_rate_in_the_burst():
    times = loadgen.burst_times(100.0, 500.0, period_s=10.0, burst_s=2.0,
                                duration_s=100.0, seed=1)
    inside = sum(1 for t in times if t % 10.0 < 2.0)
    assert inside == pytest.approx(500 * 20, rel=0.1)
    assert len(times) - inside == pytest.approx(100 * 80, rel=0.1)
    with pytest.raises(ValueError):
        loadgen.arrival_times({"process": "nope"}, 1.0, 0)
