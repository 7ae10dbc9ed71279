"""Compile/device-level profiling layered on the telemetry registry.

Four pieces (PERFORMANCE.md §"Profiling a run"):

* ``profiling/compile.py`` — :func:`profiled_jit` wraps the jit
  lower/compile boundary: per-compile ``cost_analysis()`` FLOPs/bytes,
  ``memory_analysis()``, an HLO fingerprint, and a recompile detector
  keyed on abstract avals (``profiling.compiles`` / ``.recompiles``
  counters + a ``compile``/``recompile`` event per occurrence);
  :func:`op_scopes`, derived when asked: each instruction of every
  executable held, with the ``jax.named_scope`` path it was traced under
  (what names the operations of a device trace).
* ``profiling/collectives.py`` — analytic per-step byte estimates for
  ``psum`` / ``all_gather`` / all-to-all / ``ppermute`` from mesh shape
  + payload shape (``collectives.*_bytes`` counters + one ``collective``
  event per call site = the per-stage table in ``telemetry.jsonl``).
* ``profiling/trace.py`` — device-time capture: ``jax.profiler`` traces
  plus a Chrome-trace artifact rendered from this run's telemetry spans
  (``--profile-dir``); wall timings end in a host readback of the
  result (``np.asarray``), which waits for the device.
* ``profiling/diff.py`` — the regression gate behind
  ``python -m music_analyst_tpu profile-diff A B`` and
  ``bench.py --baseline``.

Import discipline: this package (and everything it re-exports here) must
stay importable before jax — ``tests/conftest.py`` forces the CPU
platform first.  Submodules that need jax import it lazily or are only
imported from already-jax-bound modules.
"""

from music_analyst_tpu.profiling.collectives import (
    all_gather_bytes,
    all_to_all_bytes,
    emit_stage_table,
    ppermute_bytes,
    psum_bytes,
    record_collective,
    stage_table,
)
from music_analyst_tpu.profiling.diff import load_metrics, run_profile_diff

__all__ = [
    "all_gather_bytes",
    "all_to_all_bytes",
    "emit_stage_table",
    "ppermute_bytes",
    "psum_bytes",
    "record_collective",
    "stage_table",
    "load_metrics",
    "run_profile_diff",
    "profiled_jit",
    "compile_records",
    "op_scopes",
]


def __getattr__(name):
    # profiled_jit/compile_records/op_scopes live in a jax-importing module;
    # resolve them lazily so `import music_analyst_tpu.profiling` stays
    # jax-free.
    if name in ("profiled_jit", "compile_records", "op_scopes",
                "ProfiledFunction"):
        from music_analyst_tpu.profiling import compile as _compile

        return getattr(_compile, name)
    raise AttributeError(name)
