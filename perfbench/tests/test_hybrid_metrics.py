"""The four readers of the hybrid cell on a hand-written job:
``hybrid_step_mfu``, ``kda_prefill_roofline``, ``kda_time_share`` and
``recurrent_state_bytes``; a program that records none of what they read
(the parent of the PR that added them, or another decoder's scoring step)
gives ``None``, not an error."""

import json
import os

import pytest

import common
import flops_ling
from layer_metrics import (
    hybrid_step_mfu,
    kda_prefill_roofline,
    kda_time_share,
    recurrent_state_bytes,
)

CONFIG = common.load_json(os.path.join(
    common.BENCH_DIR, "configs", "ling-3.0-flash-vl.json"))

STEPS = [
    {"rows": 64, "width": 1024, "tokens_real": 20_600,
     "token_pairs": 4_400_000, "label_positions": 24,
     "label_positions_real": 3, "moe_capacity": 24_576,
     "assignments": 988_800, "assignments_held": 251_000,
     "label_assignments_held": 2_300, "kda_layers": 6, "mla_layers": 1,
     "state_bytes": 807_600_128},
    {"rows": 64, "width": 1024, "tokens_real": 22_100,
     "token_pairs": 5_000_000, "label_positions": 24,
     "label_positions_real": 3, "moe_capacity": 24_576,
     "assignments": 1_060_800, "assignments_held": 262_000,
     "label_assignments_held": 2_250, "kda_layers": 6, "mla_layers": 1,
     "state_bytes": 807_600_128},
]
GAUGES = {"recurrent_state_bytes": 807_600_128, "latent_cache_bytes": 76_087_296}


def _job(tmp_path, name, steps, gauges):
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
        "manifest": {"counters": {}, "gauges": gauges}}}}


def _artifacts(tmp_path, steps=STEPS, gauges=GAUGES):
    trace = {"devices": {"/device:TPU:0": {
        "module_runs_s": {"jit__score_labels(5)": [0.70, 0.74],
                          "jit_other": [9.0]},
        "op_s": {"_kda_chunk_call.3": 0.040, "_kda_chunk_call.7": 0.050,
                 "_packed_prefill_call.2": 0.004,
                 "ragged-dot-none.2": 0.3}}}}
    return {"config": CONFIG, "device": {"kind": "TPU v5 lite"},
            "trace": trace,
            "jobs": [_job(tmp_path, "job0", steps, gauges),
                     _job(tmp_path, "job1", steps[:1], {
                         **gauges, **({"recurrent_state_bytes": 403_800_064}
                                      if gauges else {})})]}


def test_step_mfu_is_the_traced_jobs_operations_over_peak_and_the_program(
        tmp_path):
    work = sum(flops_ling.step_flops(CONFIG, s) for s in STEPS)
    got = hybrid_step_mfu.read(_artifacts(tmp_path))
    assert got == pytest.approx(100.0 * work / 197e12 / 1.44)
    assert 0 < got < 100


def test_kernel_roofline_is_its_least_time_over_the_kda_operations(tmp_path):
    flops_needed = sum(flops_ling.kda_prefill_flops(CONFIG, s) for s in STEPS)
    bytes_needed = sum(flops_ling.kda_prefill_bytes(CONFIG, s) for s in STEPS)
    least = max(flops_needed / 197e12, bytes_needed / 819e9)
    assert least == bytes_needed / 819e9        # memory-bound at these widths
    got = kda_prefill_roofline.read(_artifacts(tmp_path))
    assert got == pytest.approx(100.0 * least / 0.090)
    assert 0 < got < 100


def test_time_share_is_the_kda_operations_part_of_the_program(tmp_path):
    assert kda_time_share.read(_artifacts(tmp_path)) == pytest.approx(
        100.0 * 0.090 / 1.44)


def test_state_bytes_reads_the_manifests_gauge(tmp_path):
    # job0 807,600,128, job1 403,800,064: the median of two is their mean
    assert recurrent_state_bytes.read(_artifacts(tmp_path)) == (
        pytest.approx((807_600_128 + 403_800_064) / 2))


def test_a_program_without_the_spans_or_the_kernel_reads_nothing(tmp_path):
    # another decoder's compute span and trace: no KDA layer, no held share
    bare = [{"rows": 32, "tokens_real": 9000, "token_pairs": 3_000_000,
             "label_positions_real": 3}] * 2
    artifacts = _artifacts(tmp_path, steps=bare, gauges={})
    artifacts["trace"]["devices"]["/device:TPU:0"]["op_s"] = {
        "_packed_prefill_call.2": 0.1}
    for reader in (hybrid_step_mfu, kda_prefill_roofline, kda_time_share,
                   recurrent_state_bytes):
        assert reader.read(artifacts) is None
    artifacts["trace"] = None
    for reader in (hybrid_step_mfu, kda_prefill_roofline, kda_time_share):
        assert reader.read(artifacts) is None
    assert hybrid_step_mfu.read({"jobs": [], "trace": {"devices": {
        "d": {"module_runs_s": {}, "op_s": {}}}}}) is None
    assert recurrent_state_bytes.read({"jobs": []}) is None
