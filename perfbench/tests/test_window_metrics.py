"""The four readers of the window cell on a hand-written job:
``window_step_mfu``, ``window_attention_roofline``, ``gqa_time_share`` and
``attention_tile_waste_share``; a program that records none of what they
read (the parent of the PR that added them, or another decoder's scoring
step) gives ``None``, not an error."""

import json
import os

import pytest

import common
import flops_laguna
import scope_reduce
from layer_metrics import (
    attention_tile_waste_share,
    gqa_time_share,
    window_attention_roofline,
    window_step_mfu,
)

CONFIG = common.load_json(os.path.join(
    common.BENCH_DIR, "configs", "laguna-s-2.1.json"))


def _step(tokens, full, window, tiles):
    return {"rows": 32, "width": 1024, "tokens_real": tokens,
            "label_positions": 3, "label_positions_real": 3,
            "attention_layers_full": 2, "attention_layers_window": 3,
            "token_pairs_full": full, "token_pairs_window": window,
            "label_pairs_full": 3 * (tokens + 32),
            "label_pairs_window": 3 * 32 * 500,
            "token_pairs": 2 * full + 3 * window, "token_pairs_tiles": tiles,
            "moe_capacity": 24_576, "assignments": tokens * 40,
            "assignments_held": tokens * 20, "label_assignments_held": 1_900}


STEPS = [_step(22_700, 8_300_000, 7_450_000, 5 * 92 * 512 * 512),
         _step(21_900, 7_900_000, 7_100_000, 5 * 88 * 512 * 512)]


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
        "module_runs_s": {"jit__score_labels(5)": [0.60, 0.64],
                          "jit_other": [9.0]},
        "op_s": {"_flash_call.3": 0.050, "_flash_call.7": 0.150,
                 "_ssd_chunk_call.2": 0.004,
                 "ragged-dot-none.2": 0.3}}}}
    return {"config": CONFIG, "device": {"kind": "TPU v5 lite"},
            "trace": trace,
            "jobs": [_job(tmp_path, "job0", steps),
                     _job(tmp_path, "job1", steps[:1])]}


def test_step_mfu_is_the_traced_jobs_operations_over_peak_and_the_program(
        tmp_path):
    work = sum(flops_laguna.step_flops(CONFIG, s) for s in STEPS)
    got = window_step_mfu.read(_artifacts(tmp_path))
    assert got == pytest.approx(100.0 * work / 197e12 / 1.24)
    assert 0 < got < 100


def test_attention_roofline_is_its_least_time_over_the_flash_operations(
        tmp_path):
    flops_needed = sum(flops_laguna.attention_flops(CONFIG, s, labels=False)
                       for s in STEPS)
    bytes_needed = sum(flops_laguna.attention_prefill_bytes(CONFIG, s)
                       for s in STEPS)
    least = max(flops_needed / 197e12, bytes_needed / 819e9)
    assert least == flops_needed / 197e12     # compute-bound at these rows
    got = window_attention_roofline.read(_artifacts(tmp_path))
    assert got == pytest.approx(100.0 * least / 0.200)
    assert 0 < got < 100


def test_tile_waste_is_what_the_kernel_computed_outside_the_mask(tmp_path):
    def waste(steps):
        return 100.0 * (1.0 - sum(s["token_pairs"] for s in steps)
                        / sum(s["token_pairs_tiles"] for s in steps))

    # the median over the two jobs is their mean
    assert attention_tile_waste_share.read(_artifacts(tmp_path)) == (
        pytest.approx((waste(STEPS) + waste(STEPS[:1])) / 2))
    assert 50 < waste(STEPS) < 75


def test_gqa_time_share_reads_the_rows_scope_parts_has(tmp_path, monkeypatch):
    reduced = {"modules": {"jit__score_labels": {
        "seconds": 2.0, "parts": {
            "prefill.gqa": 0.8, "labels.gqa": 0.1, "prefill.matmul": 0.6,
            "prefill.other": 0.3, "labels.matmul": 0.2}}}}
    monkeypatch.setattr(scope_reduce, "for_artifacts", lambda a: reduced)
    assert gqa_time_share.read({}) == pytest.approx(45.0)
    table = common.load_json(scope_reduce.PARTS_PATH)["programs"][
        "jit__score_labels"]
    for inner in ("gqa.proj", "gqa.rope", "gqa.kernel", "gqa.gate",
                  "gqa.out"):
        path = f"jit(_score_labels)/prefill/LlamaModel/layer_1/gqa/{inner}/dot"
        assert scope_reduce.part_of(path, table) == "prefill.gqa"
        path = f"jit(_score_labels)/labels/vmap(LlamaModel)/gqa/{inner}/exp"
        assert scope_reduce.part_of(path, table) == "labels.gqa"


def test_a_program_without_the_spans_or_the_kernel_reads_nothing(tmp_path,
                                                                 monkeypatch):
    # the state-space hybrid's compute span and trace: attention layers, a
    # held share, but no layers by kind and no pairs by tile
    bare = [{"rows": 32, "tokens_real": 10_300, "token_pairs": 2_200_000,
             "label_positions_real": 3, "ssm_layers": 9,
             "attention_layers": 1, "assignments_held": 515_000}] * 2
    artifacts = _artifacts(tmp_path, steps=bare)
    for reader in (window_step_mfu, window_attention_roofline,
                   attention_tile_waste_share):
        assert reader.read(artifacts) is None
    artifacts = _artifacts(tmp_path / "x" if (tmp_path / "x").mkdir() is None
                           else tmp_path)
    artifacts["trace"]["devices"]["/device:TPU:0"]["op_s"] = {
        "_ssd_chunk_call.2": 0.1}
    assert window_attention_roofline.read(artifacts) is None
    artifacts["trace"] = None
    for reader in (window_step_mfu, window_attention_roofline):
        assert reader.read(artifacts) is None
    monkeypatch.setattr(scope_reduce, "for_artifacts", lambda a: None)
    assert gqa_time_share.read(artifacts) is None
    assert attention_tile_waste_share.read({"jobs": []}) is None
    assert window_step_mfu.read({"jobs": [], "trace": {"devices": {
        "d": {"module_runs_s": {}, "op_s": {}}}}}) is None
