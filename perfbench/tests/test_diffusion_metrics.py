"""The four readers of the block-diffusion cell on a hand-written job:
``diffusion_step_mfu``, ``denoise_time_share``, ``denoise_passes_per_block``
and ``block_causal_attention_roofline``; a program that records none of what
they read (the parent of the PR that added them) gives ``None``, not an
error."""

import json
import os

import pytest

import common
import flops_sdar
from layer_metrics import (
    block_causal_attention_roofline,
    denoise_passes_per_block,
    denoise_time_share,
    diffusion_step_mfu,
)

CONFIG = common.load_json(os.path.join(
    common.BENCH_DIR, "configs", "sdar-30b-a3b-chat.json"))

STEPS = [
    {"rows": 32, "width": 1024, "tokens_real": 9000, "tokens_prefilled": 8952,
     "token_pairs": 3_100_000, "pass_pairs": 730_000, "block_length": 4,
     "gen_blocks": 4, "denoise_passes": 16, "commit_passes": 4,
     "positions_masked": 1200, "moe_capacity": 12288},
    {"rows": 32, "width": 1024, "tokens_real": 11000,
     "tokens_prefilled": 10948, "token_pairs": 4_000_000,
     "pass_pairs": 890_000, "block_length": 4, "gen_blocks": 4,
     "denoise_passes": 14, "commit_passes": 4, "positions_masked": 1100,
     "moe_capacity": 12288},
]
COUNTERS = {"diffusion.blocks": 8, "diffusion.denoise_passes": 30,
            "diffusion.commit_passes": 8}


def _job(tmp_path, name, steps, counters):
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
        "manifest": {"counters": counters}}}}


def _artifacts(tmp_path, steps=STEPS, counters=COUNTERS):
    trace = {"devices": {"/device:TPU:0": {
        "module_runs_s": {"jit__diffusion_prefill(7)": [0.25, 0.27],
                          "jit__diffusion_denoise(9)": [0.50, 0.46],
                          "jit_other": [9.0]},
        "op_s": {"_flash_call.3": 0.004, "_flash_call.5": 0.006,
                 "ragged-dot-none.2": 0.3}}}}
    return {"config": CONFIG, "device": {"kind": "TPU v5 lite"},
            "trace": trace,
            "jobs": [_job(tmp_path, "job0", steps, counters),
                     _job(tmp_path, "job1", steps[:1],
                          {**counters, "diffusion.denoise_passes": 32})]}


def test_step_mfu_is_the_traced_jobs_operations_over_peak_and_both_programs(
        tmp_path):
    work = sum(flops_sdar.step_flops(CONFIG, s) for s in STEPS)
    got = diffusion_step_mfu.read(_artifacts(tmp_path))
    assert got == pytest.approx(100.0 * work / 197e12 / 1.48)
    assert 0 < got < 100


def test_denoise_share_is_the_block_loops_part_of_the_steps_device_time(
        tmp_path):
    assert denoise_time_share.read(_artifacts(tmp_path)) == pytest.approx(
        100.0 * 0.96 / 1.48)


def test_passes_per_block_reads_the_manifests_counters(tmp_path):
    # job0 30 / 8, job1 32 / 8: the median of two is their mean
    assert denoise_passes_per_block.read(_artifacts(tmp_path)) == (
        pytest.approx((3.75 + 4.0) / 2))


def test_kernel_roofline_is_its_least_time_over_its_device_time(tmp_path):
    flops_needed = sum(flops_sdar.block_causal_attention_flops(CONFIG, s)
                       for s in STEPS)
    bytes_needed = sum(flops_sdar.block_causal_attention_bytes(CONFIG, s)
                       for s in STEPS)
    least = max(flops_needed / 197e12, bytes_needed / 819e9)
    got = block_causal_attention_roofline.read(_artifacts(tmp_path))
    assert got == pytest.approx(100.0 * least / 0.010)
    assert 0 < got < 100


def test_a_program_without_the_programs_or_counters_reads_nothing(tmp_path):
    bare = [{"rows": 32}, {"rows": 32}]  # the compute span before this PR
    artifacts = _artifacts(tmp_path, steps=bare, counters={})
    artifacts["trace"]["devices"]["/device:TPU:0"] = {
        "module_runs_s": {"jit__score_labels(3)": [0.3]},
        "op_s": {"_prefill_call.2": 0.1}}
    for reader in (diffusion_step_mfu, denoise_time_share,
                   denoise_passes_per_block,
                   block_causal_attention_roofline):
        assert reader.read(artifacts) is None
    artifacts["trace"] = None
    for reader in (diffusion_step_mfu, denoise_time_share,
                   block_causal_attention_roofline):
        assert reader.read(artifacts) is None
    assert diffusion_step_mfu.read({"jobs": [], "trace": {"devices": {
        "d": {"module_runs_s": {}, "op_s": {}}}}}) is None
