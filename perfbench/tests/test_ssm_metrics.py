"""The four readers of the state-space cell on a hand-written job:
``ssm_step_mfu``, ``ssd_prefill_roofline``, ``ssd_time_share`` and
``ssm_state_bytes``; a program that records none of what they read (the
parent of the PR that added them, or another decoder's scoring step, the
KDA hybrid's among them) gives ``None``, not an error."""

import json
import os

import pytest

import common
import flops_granite
from layer_metrics import (
    ssd_prefill_roofline,
    ssd_time_share,
    ssm_state_bytes,
    ssm_step_mfu,
)

CONFIG = common.load_json(os.path.join(
    common.BENCH_DIR, "configs", "granite-4.0-h-small.json"))

STEPS = [
    {"rows": 32, "width": 1024, "tokens_real": 10_300,
     "token_pairs": 2_200_000, "label_positions": 3,
     "label_positions_real": 3, "moe_capacity": 12_288,
     "assignments": 1_030_000, "assignments_held": 515_000,
     "label_assignments_held": 480, "ssm_layers": 9, "attention_layers": 1,
     "state_bytes": 1_222_557_696},
    {"rows": 32, "width": 1024, "tokens_real": 11_050,
     "token_pairs": 2_500_000, "label_positions": 3,
     "label_positions_real": 3, "moe_capacity": 12_288,
     "assignments": 1_105_000, "assignments_held": 549_000,
     "label_assignments_held": 470, "ssm_layers": 9, "attention_layers": 1,
     "state_bytes": 1_222_557_696},
]
GAUGES = {"recurrent_state_bytes": 1_222_557_696,
          "kv_cache_bytes": 135_266_304}
COUNTERS = {"ssm.tokens": 192_150}


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
        "manifest": {"counters": COUNTERS if gauges else {},
                     "gauges": gauges}}}}


def _artifacts(tmp_path, steps=STEPS, gauges=GAUGES):
    trace = {"devices": {"/device:TPU:0": {
        "module_runs_s": {"jit__score_labels(5)": [0.70, 0.74],
                          "jit_other": [9.0]},
        "op_s": {"_ssd_chunk_call.3": 0.040, "_ssd_chunk_call.7": 0.050,
                 "_flash_call.2": 0.004,
                 "ragged-dot-none.2": 0.3}}}}
    return {"config": CONFIG, "device": {"kind": "TPU v5 lite"},
            "trace": trace,
            "jobs": [_job(tmp_path, "job0", steps, gauges),
                     _job(tmp_path, "job1", steps[:1], {
                         **gauges, **({"recurrent_state_bytes": 611_278_848}
                                      if gauges else {})})]}


def test_step_mfu_is_the_traced_jobs_operations_over_peak_and_the_program(
        tmp_path):
    work = sum(flops_granite.step_flops(CONFIG, s) for s in STEPS)
    got = ssm_step_mfu.read(_artifacts(tmp_path))
    assert got == pytest.approx(100.0 * work / 197e12 / 1.44)
    assert 0 < got < 100


def test_kernel_roofline_is_its_least_time_over_the_ssd_operations(tmp_path):
    flops_needed = sum(flops_granite.ssd_prefill_flops(CONFIG, s) for s in STEPS)
    bytes_needed = sum(flops_granite.ssd_prefill_bytes(CONFIG, s) for s in STEPS)
    least = max(flops_needed / 197e12, bytes_needed / 819e9)
    assert least == bytes_needed / 819e9        # memory-bound at these widths
    got = ssd_prefill_roofline.read(_artifacts(tmp_path))
    assert got == pytest.approx(100.0 * least / 0.090)
    assert 0 < got < 100


def test_time_share_is_the_ssd_operations_part_of_the_program(tmp_path):
    assert ssd_time_share.read(_artifacts(tmp_path)) == pytest.approx(
        100.0 * 0.090 / 1.44)


def test_state_bytes_reads_the_manifests_gauge(tmp_path):
    # job0 1,222,557,696, job1 611,278,848: the median of two is their mean
    assert ssm_state_bytes.read(_artifacts(tmp_path)) == (
        pytest.approx((1_222_557_696 + 611_278_848) / 2))


def test_a_program_without_the_spans_or_the_kernel_reads_nothing(tmp_path):
    # the KDA hybrid's compute span and trace: a recurrent state, a held
    # share, but no state-space layer and no ``_ssd_`` operation
    bare = [{"rows": 64, "tokens_real": 20_600, "token_pairs": 4_400_000,
             "label_positions_real": 3, "kda_layers": 6, "mla_layers": 1,
             "assignments_held": 251_000}] * 2
    artifacts = _artifacts(tmp_path, steps=bare, gauges={})
    artifacts["trace"]["devices"]["/device:TPU:0"]["op_s"] = {
        "_kda_chunk_call.2": 0.1}
    for reader in (ssm_step_mfu, ssd_prefill_roofline, ssd_time_share,
                   ssm_state_bytes):
        assert reader.read(artifacts) is None
    artifacts["trace"] = None
    for reader in (ssm_step_mfu, ssd_prefill_roofline, ssd_time_share):
        assert reader.read(artifacts) is None
    assert ssm_step_mfu.read({"jobs": [], "trace": {"devices": {
        "d": {"module_runs_s": {}, "op_s": {}}}}}) is None
    assert ssm_state_bytes.read({"jobs": []}) is None
    # the KDA hybrid's gauge is not this cell's: no ``ssm.tokens`` beside it
    kda = _artifacts(tmp_path / "kda" if (tmp_path / "kda").mkdir() is None
                     else tmp_path, steps=bare, gauges=GAUGES)
    for job in kda["jobs"]:
        job["parts"]["sentiment"]["manifest"]["counters"] = {
            "kda.tokens": 123_600}
    assert ssm_state_bytes.read(kda) is None
