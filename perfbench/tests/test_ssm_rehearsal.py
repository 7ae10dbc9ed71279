"""The state-space cell end to end at the tiny size on the CPU
(``--rehearsal``): the driver builds the backend through ``get_backend``,
checks its widths and its share against the configuration file, compares the
first batch with the float32 reference (choices, label scores, states,
convolution tails, keys and values), runs its jobs and the readers find
their spans and gauges.  Says nothing about a chip."""

import json
import os
import subprocess
import sys

import common


def test_ssm_sentiment_releases_rehearsal():
    proc = subprocess.run(
        [sys.executable, os.path.join(common.BENCH_DIR, "run.py"),
         "--workload", "ssm_sentiment_releases", "--seed", "3000000007",
         "--seconds", "1", "--trace", "1", "--rehearsal"],
        capture_output=True, text=True, cwd=common.REPO_ROOT, timeout=900)
    assert proc.returncode == 0, proc.stderr[-2000:]
    lines = [json.loads(line) for line in proc.stdout.splitlines()
             if line.startswith("{")]
    last = lines[-1]
    assert last["correct"] is True and last["failed"] == 0
    assert {"ssm_state_bytes", "pipeline_stall_share", "read_batch_ms",
            "job_head_ms", "job_tail_ms", "device_idle_share"} <= set(
                last["metric_names"])
    # the cell is on no list of the accepted metric that reads the same gauge
    assert "recurrent_state_bytes" not in last["metric_names"]
    reference = next(l["checks"]["reference"] for l in lines if "setup" in l)
    assert reference["ok"] and reference["labels_wrong"] == []
    assert reference["choices_compared"] > 0
    for name in ("state_median", "conv_median", "keys_median",
                 "values_median"):
        assert 0 < reference[name] < reference["tolerance"][name]
