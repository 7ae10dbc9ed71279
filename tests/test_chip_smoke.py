"""``chip_smoke.py`` keeps its own rules where the sandbox can check them.

The smoke's proof is a chip run; what tier-1 can pin is the process
discipline around it: the parent never loads jax (a parent that has
touched JAX holds the chip, and a child that needs it then fails or
hangs), and without an accelerator and without ``--rehearsal`` there is
a non-zero exit and no result line.
"""

from __future__ import annotations

import os
import pathlib
import subprocess
import sys

REPO_ROOT = pathlib.Path(__file__).parent.parent


def _python(script: str, **env) -> subprocess.CompletedProcess:
    return subprocess.run(
        [sys.executable, "-c", script], cwd=str(REPO_ROOT),
        capture_output=True, text=True, timeout=120,
        env=dict(os.environ, **env),
    )


def test_chip_smoke_imports_without_jax():
    """Importing the script, and everything its parent imports from the
    repo (corpus generator, Python oracle), leaves jax unloaded."""
    proc = _python(
        "import sys\n"
        "import chip_smoke\n"
        "from music_analyst_tpu.data.csv_io import iter_songs, "
        "sort_count_entries, write_count_csv\n"
        "from music_analyst_tpu.data.ingest import ingest_python\n"
        "from music_analyst_tpu.data.synthetic import generate_dataset\n"
        "loaded = sorted(m for m in sys.modules "
        "if m == 'jax' or m.startswith(('jax.', 'jaxlib')))\n"
        "assert not loaded, loaded\n"
    )
    assert proc.returncode == 0, proc.stderr[-800:]


def test_chip_smoke_alone_fails_without_a_result(tmp_path):
    """In a directory that holds chip_smoke.py and nothing else of the
    repo: non-zero, nothing on stdout."""
    script = tmp_path / "chip_smoke.py"
    script.write_bytes((REPO_ROOT / "chip_smoke.py").read_bytes())
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    proc = subprocess.run(
        [sys.executable, str(script)], cwd=str(tmp_path), env=env,
        capture_output=True, text=True, timeout=120,
    )
    assert proc.returncode != 0
    assert proc.stdout == ""
