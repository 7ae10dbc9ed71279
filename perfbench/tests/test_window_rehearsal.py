"""The window cell end to end at the tiny size on the CPU (``--rehearsal``):
the driver builds the backend through ``get_backend``, checks its widths,
its attention kinds, its share and the four assumed readings against the
configuration file, compares the first batch with the float32 reference
(choices, label scores, every layer's keys and values), runs its jobs and
the readers find their spans.  Says nothing about a chip."""

import json
import os
import subprocess
import sys

import common


def test_window_sentiment_long_lyrics_rehearsal():
    proc = subprocess.run(
        [sys.executable, os.path.join(common.BENCH_DIR, "run.py"),
         "--workload", "window_sentiment_long_lyrics", "--seed", "3000000007",
         "--seconds", "1", "--trace", "1", "--rehearsal"],
        capture_output=True, text=True, cwd=common.REPO_ROOT, timeout=900)
    assert proc.returncode == 0, proc.stderr[-2000:]
    lines = [json.loads(line) for line in proc.stdout.splitlines()
             if line.startswith("{")]
    last = lines[-1]
    assert last["correct"] is True and last["failed"] == 0
    assert {"attention_tile_waste_share", "pipeline_stall_share",
            "read_batch_ms", "job_head_ms", "job_tail_ms",
            "device_idle_share"} <= set(last["metric_names"])
    # the cell is on no list of another decoder's metrics
    assert not {"padded_token_share", "expert_time_share",
                "ssm_state_bytes"} & set(last["metric_names"])
    reference = next(l["checks"]["reference"] for l in lines if "setup" in l)
    assert reference["ok"] and reference["labels_wrong"] == []
    assert reference["choices_compared"] > 0
    # most of the sampled rows are longer than the rehearsal's 200 tokens
    assert sum(n > 200 for n in reference["row_tokens"]) >= 2
    for name in ("keys_median", "values_median", "keys_max", "values_max"):
        assert 0 < reference[name] < reference["tolerance"][name]


def test_the_probe_reads_each_control_as_not_correct():
    """``tools/window_reference_probe.py`` at the tiny size: the system
    comes out ``correct`` through the driver's own comparison, the int8
    reference and each wrong program made in the system do not."""
    proc = subprocess.run(
        [sys.executable,
         os.path.join(common.BENCH_DIR, "tools", "window_reference_probe.py"),
         "--rehearsal", "--seeds", "1"],
        capture_output=True, text=True, cwd=common.REPO_ROOT, timeout=900)
    assert proc.returncode == 0, proc.stderr[-2000:]
    line = json.loads(proc.stdout.splitlines()[-1])
    assert line["system"]["correct"] and not line["system"]["limits_failed"]
    for control in ("int8", "without_window", "without_yarn_factor",
                    "without_gate"):
        assert line[control]["correct"] is False
        assert line[control]["limits_failed"]
