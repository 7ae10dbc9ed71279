"""The hybrid cell end to end at the tiny size on the CPU (``--rehearsal``):
the driver builds the backend through ``get_backend``, checks its widths
and its share against the configuration file, compares the first batch
with the float32 reference (choices, label scores, states, latents), runs
its jobs and the readers find their spans and gauges.  Says nothing about a
chip."""

import json
import os
import subprocess
import sys

import common


def test_hybrid_sentiment_releases_rehearsal():
    proc = subprocess.run(
        [sys.executable, os.path.join(common.BENCH_DIR, "run.py"),
         "--workload", "hybrid_sentiment_releases", "--seed", "3000000001",
         "--seconds", "1", "--trace", "1", "--rehearsal"],
        capture_output=True, text=True, cwd=common.REPO_ROOT, timeout=900)
    assert proc.returncode == 0, proc.stderr[-2000:]
    lines = [json.loads(line) for line in proc.stdout.splitlines()
             if line.startswith("{")]
    last = lines[-1]
    assert last["correct"] is True and last["failed"] == 0
    assert {"recurrent_state_bytes", "pipeline_stall_share", "read_batch_ms",
            "job_head_ms", "job_tail_ms", "device_idle_share"} <= set(
                last["metric_names"])
    reference = next(l["checks"]["reference"] for l in lines if "setup" in l)
    assert reference["ok"] and reference["labels_wrong"] == []
    assert reference["choices_compared"] > 0
    assert 0 < reference["state_median"] < reference["tolerance"][
        "state_median"]
