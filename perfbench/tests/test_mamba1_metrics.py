"""The three readers of the Mamba-1 cell on a hand-written job:
``mamba1_step_mfu``, ``selective_scan_roofline`` and
``selective_scan_time_share``; a program that records none of what they read
(the parent of the PR that added them, or another decoder's scoring step,
the Mamba-2 hybrid's among them) gives ``None``, not an error."""

import json
import os

import pytest

import common
import flops_jamba
from layer_metrics import (
    mamba1_step_mfu,
    selective_scan_roofline,
    selective_scan_time_share,
)

CONFIG = common.load_json(os.path.join(
    common.BENCH_DIR, "configs", "ai21-jamba2-3b.json"))

STEPS = [
    {"rows": 64, "width": 1024, "tokens_real": 20_600,
     "token_pairs": 4_300_000, "label_positions": 3,
     "label_positions_real": 3, "mamba1_layers": 26,
     "attention_layers": 2, "slots": 24_576, "state_bytes": 596_377_600},
    {"rows": 64, "width": 1024, "tokens_real": 21_300,
     "token_pairs": 4_600_000, "label_positions": 3,
     "label_positions_real": 3, "mamba1_layers": 26,
     "attention_layers": 2, "slots": 24_576, "state_bytes": 596_377_600},
]


def _job(tmp_path, name, steps):
    directory = tmp_path / name
    directory.mkdir()
    events = [{"type": "event", "name": "run_start", "t_mono": 10.0}]
    for i, attrs in enumerate(steps):
        events.append({"type": "span", "name": "compute", "t_mono": 10.0 + i,
                       "dur_s": 0.8, "thread": "MainThread",
                       "attrs": {"batch": i, **attrs}})
    (directory / "telemetry.jsonl").write_text(
        "\n".join(json.dumps(e) for e in events) + "\n")
    return {"parts": {"sentiment": {
        "dir": str(directory), "seconds": 2.0,
        "manifest": {"counters": {}, "gauges": {}}}}}


def _artifacts(tmp_path, steps=STEPS):
    trace = {"devices": {"/device:TPU:0": {
        "module_runs_s": {"jit__score_labels(5)": [1.40, 1.50],
                          "jit_other": [9.0]},
        "op_s": {"_selective_scan_call.3": 0.060,
                 "_selective_scan_call.7": 0.070,
                 "_packed_flash_call.2": 0.004,
                 "convolution_bitcast_fusion.2": 0.3}}}}
    return {"config": CONFIG, "device": {"kind": "TPU v5 lite"},
            "trace": trace, "jobs": [_job(tmp_path, "job0", steps)]}


def test_step_mfu_is_the_traced_jobs_operations_over_peak_and_the_program(
        tmp_path):
    work = sum(flops_jamba.step_flops(CONFIG, s) for s in STEPS)
    got = mamba1_step_mfu.read(_artifacts(tmp_path))
    assert got == pytest.approx(100.0 * work / 197e12 / 2.90)
    assert 0 < got < 100


def test_kernel_roofline_is_its_least_time_over_the_scan_operations(
        tmp_path):
    flops_needed = sum(flops_jamba.selective_scan_flops(CONFIG, s)
                       for s in STEPS)
    bytes_needed = sum(flops_jamba.selective_scan_bytes(CONFIG, s)
                       for s in STEPS)
    least = max(flops_needed / 197e12, bytes_needed / 819e9)
    assert least == bytes_needed / 819e9        # memory-bound at these widths
    got = selective_scan_roofline.read(_artifacts(tmp_path))
    assert got == pytest.approx(100.0 * least / 0.130)
    assert 0 < got < 100


def test_time_share_is_the_scan_operations_part_of_the_program(tmp_path):
    assert selective_scan_time_share.read(_artifacts(tmp_path)) == (
        pytest.approx(100.0 * 0.130 / 2.90))


def test_a_program_without_the_spans_or_the_kernel_reads_nothing(tmp_path):
    # the Mamba-2 hybrid's compute span and trace: a recurrent state, but
    # no Mamba-1 layer and no ``_selective_scan`` operation
    bare = [{"rows": 32, "tokens_real": 10_300, "token_pairs": 2_200_000,
             "label_positions_real": 3, "ssm_layers": 9,
             "attention_layers": 1, "assignments_held": 515_000}] * 2
    artifacts = _artifacts(tmp_path, steps=bare)
    artifacts["trace"]["devices"]["/device:TPU:0"]["op_s"] = {
        "_ssd_chunk_call.2": 0.1}
    for reader in (mamba1_step_mfu, selective_scan_roofline,
                   selective_scan_time_share):
        assert reader.read(artifacts) is None
    artifacts["trace"] = None
    for reader in (mamba1_step_mfu, selective_scan_roofline,
                   selective_scan_time_share):
        assert reader.read(artifacts) is None
    assert mamba1_step_mfu.read({"jobs": [], "trace": {"devices": {
        "d": {"module_runs_s": {}, "op_s": {}}}}}) is None
