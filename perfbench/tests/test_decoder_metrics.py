"""The three readers of the zero-shot decoder's cell on a hand-written job:
``decoder_step_mfu``, ``moe_expert_load_max_over_mean`` and
``padded_token_share``; a program that records none of what they read (the
parent of the PR that added them) gives ``None``, not an error."""

import json
import os

import pytest

import common
import flops_decoder
from layer_metrics import (
    decoder_step_mfu,
    moe_expert_load_max_over_mean,
    padded_token_share,
)

CONFIG = common.load_json(os.path.join(
    common.BENCH_DIR, "configs", "kanana-2-30b-a3b.json"))

STEPS = [
    {"rows": 32, "width": 1024, "tokens_real": 9000, "token_pairs": 1_500_000,
     "label_positions": 24, "label_positions_real": 3,
     "expert_load_max_over_mean": [1.5, 2.0, 2.5, 3.0, 3.5, 9.0]},
    {"rows": 32, "width": 512, "tokens_real": 7000, "token_pairs": 900_000,
     "label_positions": 24, "label_positions_real": 3,
     "expert_load_max_over_mean": [1.0, 1.0, 2.0, 2.0, 2.0, 2.0]},
]


def _job(tmp_path, name, steps, counters):
    directory = tmp_path / name
    directory.mkdir()
    events = [{"type": "event", "name": "run_start", "t_mono": 10.0}]
    for i, attrs in enumerate(steps):
        events.append({"type": "span", "name": "compute", "t_mono": 10.0 + i,
                       "dur_s": 0.4, "thread": "MainThread",
                       "attrs": {"batch": i, **attrs}})
    (directory / "telemetry.jsonl").write_text(
        "\n".join(json.dumps(e) for e in events) + "\n")
    return {"parts": {"sentiment": {
        "dir": str(directory), "seconds": 1.0,
        "manifest": {"counters": counters}}}}


def _artifacts(tmp_path, steps=STEPS, counters=None, runs=(0.40, 0.30)):
    counters = {"decoder.tokens_real": 16_192,
                "decoder.tokens_computed": 50_688} if counters is None else counters
    trace = {"devices": {"/device:TPU:0": {"module_runs_s": {
        "jit__score_labels(123)": list(runs), "jit_other": [9.0]}}}}
    return {"config": CONFIG, "device": {"kind": "TPU v5 lite"},
            "trace": trace,
            "jobs": [_job(tmp_path, "job0", steps, counters),
                     _job(tmp_path, "job1", steps[:1], counters)]}


def test_step_mfu_is_the_traced_jobs_operations_over_peak_and_time(tmp_path):
    work = sum(flops_decoder.step_flops(CONFIG, s) for s in STEPS)
    got = decoder_step_mfu.read(_artifacts(tmp_path))
    assert got == pytest.approx(100.0 * work / 197e12 / 0.70)
    assert 0 < got < 100


def test_load_ratio_is_the_median_over_steps_layers_then_jobs(tmp_path):
    # job0: 12 values, median 2.0; job1: its one step's six, median 2.75
    assert moe_expert_load_max_over_mean.read(
        _artifacts(tmp_path)) == pytest.approx((2.0 + 2.75) / 2)


def test_padded_share_reads_the_manifests_counters(tmp_path):
    assert padded_token_share.read(_artifacts(tmp_path)) == pytest.approx(
        100.0 * (1 - 16_192 / 50_688))


def test_a_program_without_the_counters_reads_nothing(tmp_path):
    bare = [{"rows": 32}, {"rows": 32}]  # the compute span before this PR
    artifacts = _artifacts(tmp_path, steps=bare, counters={})
    assert decoder_step_mfu.read(artifacts) is None
    assert moe_expert_load_max_over_mean.read(artifacts) is None
    assert padded_token_share.read(artifacts) is None
    artifacts["trace"] = None
    assert decoder_step_mfu.read(artifacts) is None
    assert decoder_step_mfu.read({"jobs": [], "trace": {"devices": {
        "d": {"module_runs_s": {}}}}}) is None
