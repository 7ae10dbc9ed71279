"""Device-count sweep of the analysis engine (reference run_performance.sh).

Backs the sweep/scaling story: the analogue of the reference's
``scripts/run_performance.sh:21-26`` loop over ``mpirun -np N``.  Runs
``engines/sweep.run_sweep`` over np ∈ {1,2,4,8} and reports per-N wall
clock and device-compute time.

Honesty note: under the round driver only ONE real chip is attached, so
the sweep runs on an 8-virtual-device CPU mesh in a subprocess (exactly
the mesh the test suite validates collectives on, SURVEY.md §4) and this
sandbox pins Python to one core — the numbers demonstrate that the sweep
harness runs and that per-N metrics are captured per the reference's
schema, NOT hardware ICI scaling.  ``caveat`` says so machine-readably.
"""

from __future__ import annotations

import json
import os
import subprocess
import sys
import tempfile

from benchmarks import suite
from benchmarks._util import smoke

_CHILD = r"""
import json, os, sys
from music_analyst_tpu.data.synthetic import generate_dataset
from music_analyst_tpu.engines.sweep import run_sweep
tmp = sys.argv[1]
n_songs = int(sys.argv[2])
path = os.path.join(tmp, "songs.csv")
generate_dataset(path, num_songs=n_songs, seed=5)
summary = run_sweep(path, output_dir=os.path.join(tmp, "out"), quiet=True)
print("SWEEP " + json.dumps(summary))
"""


@suite("scaling")
def run() -> dict:
    n_songs = 2_000 if smoke() else 50_000
    with tempfile.TemporaryDirectory() as tmp:
        env = dict(
            os.environ,
            JAX_PLATFORMS="cpu",
            XLA_FLAGS=(
                os.environ.get("XLA_FLAGS", "")
                + " --xla_force_host_platform_device_count=8"
            ).strip(),
        )
        proc = subprocess.run(
            [sys.executable, "-c", _CHILD, tmp, str(n_songs)],
            capture_output=True, text=True, env=env,
            cwd=os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
            timeout=1800,
        )
        if proc.returncode != 0:
            raise RuntimeError(f"scaling child failed: {proc.stderr[-400:]}")
        summary = None
        for line in reversed(proc.stdout.strip().splitlines()):
            if line.startswith("SWEEP "):
                summary = json.loads(line[len("SWEEP "):])
                break
        if summary is None:
            raise RuntimeError("scaling child emitted no summary")
    return {
        "suite": "scaling",
        "smoke": smoke(),
        "mesh": "child pinned to the CPU (JAX_PLATFORMS=cpu), 8 virtual "
                "devices",
        "caveat": (
            "CPU-emulated mesh: validates the sweep harness + per-N "
            "metrics capture, not hardware ICI scaling"
        ),
        "corpus_songs": n_songs,
        "runs": summary.get("runs", []),
    }
