"""The reduction from a trace to busy time, idle share, per-operation totals
and labelled gaps: on hand-made intervals with known answers, and on a small
trace recorded on a TPU v5e (``small.xplane.pb``: four executions of one
1024x1024 bfloat16 matmul-and-sum, a 20 ms sleep after each, recorded by
``tools/probe.py`` with the harness's spans in ``small_spans.json``)."""

import json
import os

import pytest

import trace_reduce as tr

HERE = os.path.dirname(os.path.abspath(__file__))


def test_merge_is_the_union():
    assert tr.merge_intervals([(5, 9), (0, 3), (2, 4), (9, 9), (20, 30)]) == [
        (0, 4), (5, 9), (20, 30)]


def test_clip_and_gaps():
    busy = tr.clip_intervals([(0, 4), (5, 9), (20, 30)], 2, 25)
    assert busy == [(2, 4), (5, 9), (20, 25)]
    assert tr.gaps_between(busy, 2, 25) == [(4, 5), (9, 20)]
    assert tr.gaps_between([], 0, 10) == [(0, 10)]
    assert tr.gaps_between([(3, 5)], 0, 10) == [(0, 3), (5, 10)]


def test_gap_goes_to_the_shortest_span_covering_half_of_it():
    spans = [("job", 0, 1000), ("read", 100, 400), ("write", 350, 600)]
    assert tr.label_gap((150, 300), spans) == "read"      # both cover it whole
    assert tr.label_gap((300, 600), spans) == "write"     # covers 250 of 300
    assert tr.label_gap((500, 900), spans) == "job"       # write covers a quarter
    assert tr.label_gap((900, 1400), spans) == "job"      # nothing covers half
    assert tr.label_gap((2000, 3000), spans) == "(no host span)"


def test_small_gaps_are_summed_apart():
    million = 1_000_000
    gaps = [(0, 50_000), (million, 3 * million), (5 * million, 5 * million + 10)]
    out = tr.attribute_gaps(gaps, [("sleep", 0, 10 * million)])
    assert out == {"(gaps under 0.1 ms)": 50_010, "sleep": 2 * million}


def test_short_name():
    text = "%fusion.12 = bf16[4,8]{1,0} fusion(bf16[4,8] %p), kind=kLoop"
    assert tr.short_name(text) == "fusion.12"
    assert tr.short_name("jit__forward(123)") == "jit__forward(123)"


@pytest.fixture(scope="module")
def reduced():
    with open(os.path.join(HERE, "small_spans.json")) as fh:
        spans = json.load(fh)
    return tr.reduce_file(os.path.join(HERE, "small.xplane.pb"), spans)


def test_recorded_trace_window_and_busy(reduced):
    # the window is the harness's annotation: 86.610239 ms on the trace
    assert reduced["window_s"] == pytest.approx(0.086610239, abs=1e-9)
    # 4 executions of 11.84 us of matmul plus the copies around them
    assert reduced["busy_s"] == pytest.approx(4.7431e-05, abs=1e-9)
    assert reduced["idle_share_worst"] == pytest.approx(
        1 - 4.7431e-05 / 0.086610239, abs=1e-9)
    assert list(reduced["devices"]) == ["/device:TPU:0"]


def test_recorded_trace_op_totals_and_module_runs(reduced):
    ops = dict(reduced["device_ops"])
    assert reduced["device_ops"][0][0] == "convolution_reduce_fusion"
    assert ops["convolution_reduce_fusion"] == pytest.approx(4.7365e-05, abs=1e-9)
    assert sum(ops.values()) == pytest.approx(reduced["busy_s"], abs=1e-9)
    runs = tr.module_runs(reduced, "jit__lambda")
    assert len(runs) == 4
    assert all(r == pytest.approx(11.864e-6, abs=2e-9) for r in runs)
    assert tr.module_runs(reduced, "no_such_program") == []


def test_recorded_trace_gaps_are_labelled_by_the_host_spans(reduced):
    gaps = dict(reduced["idle_gaps"])
    # the device idles through the four sleeps, and for a third of a
    # millisecond in all inside the steps (dispatch and the wait for the result)
    assert reduced["idle_gaps"][0][0] == "sleep"
    assert gaps["sleep"] == pytest.approx(0.086236136, abs=1e-9)
    assert gaps["step"] == pytest.approx(0.00032666, abs=1e-9)
    assert sum(gaps.values()) == pytest.approx(
        reduced["window_s"] - reduced["busy_s"], abs=1e-9)


def test_a_trace_without_a_device_plane_is_an_error():
    class NoPlanes:
        planes = ()

    with pytest.raises(ValueError):
        tr.reduce_profile(NoPlanes())
