"""Cold-start cost: fresh-process run with empty vs warmed XLA cache.

Backs the "Cold starts" section in PERFORMANCE.md.  Each measurement is a
REAL fresh Python process (subprocess) running a DistilBERT sentiment
batch end-to-end; the only variable is whether ``JAX_COMPILATION_CACHE_DIR``
points at an empty directory or one populated by the previous run.  The
delta is what the persistent compilation cache (``utils/cache.py``) buys
every CLI invocation after the first.
"""

from __future__ import annotations

import json
import os
import subprocess
import sys
import tempfile
import time

from benchmarks import suite
from benchmarks._util import clamped_timeout, device_info, smoke

# A healthy fresh-process run is minutes at worst; but under bench.py the
# whole parent owes its JSON line before $MUSICAAL_BENCH_DEADLINE_S, so
# the cap is clamped to the remaining parent budget at launch time.
_CHILD_CAP_S = 1200.0

_CHILD = r"""
import json, sys, time
start = time.perf_counter()
from music_analyst_tpu.utils.cache import enable_persistent_compilation_cache
enable_persistent_compilation_cache()
from music_analyst_tpu.models.distilbert import (
    DistilBertClassifier, DistilBertConfig,
)
cfg = DistilBertConfig.tiny() if len(sys.argv) > 1 else None
clf = DistilBertClassifier(config=cfg, max_len=128)
labels = clf.classify_batch(["la la love and rain"] * 256)
print(json.dumps({"seconds": time.perf_counter() - start,
                  "n": len(labels)}))
"""


def _fresh_run(cache_dir: str, tiny: bool) -> float:
    # The deliberate per-run directory goes through JAX's own variable:
    # with it set, utils/cache.py sets no directory in code.
    env = dict(os.environ, JAX_COMPILATION_CACHE_DIR=cache_dir)
    args = [sys.executable, "-c", _CHILD] + (["tiny"] if tiny else [])
    proc = subprocess.run(
        args, capture_output=True, text=True, env=env,
        cwd=os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        timeout=clamped_timeout(_CHILD_CAP_S),
    )
    if proc.returncode != 0:
        raise RuntimeError(f"coldstart child failed: {proc.stderr[-400:]}")
    for line in reversed(proc.stdout.strip().splitlines()):
        if line.startswith("{"):
            return json.loads(line)["seconds"]
    raise RuntimeError("coldstart child emitted no JSON")


@suite("coldstart")
def run() -> dict:
    tiny = smoke()
    with tempfile.TemporaryDirectory() as cache_dir:
        t0 = time.perf_counter()
        cold_s = _fresh_run(cache_dir, tiny)
        warm_s = _fresh_run(cache_dir, tiny)  # same dir, now populated
        cache_files = sum(len(files) for _, _, files in os.walk(cache_dir))
        wall = time.perf_counter() - t0
    return {
        "suite": "coldstart",
        **device_info(),
        "smoke": tiny,
        "model": "DistilBertConfig.tiny" if tiny else "DistilBERT full-size",
        "cold_process_seconds": round(cold_s, 2),
        "warm_process_seconds": round(warm_s, 2),
        "speedup": round(cold_s / warm_s, 2),
        "cache_entries": cache_files,
        "suite_wall_seconds": round(wall, 2),
    }
