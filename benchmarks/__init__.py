"""Runnable benchmark suites backing PERFORMANCE.md.

Every table in PERFORMANCE.md regenerates from a suite here so numbers can
be re-verified on hardware instead of trusted as prose:

    python bench.py --list-suites
    python bench.py --suite=<name>

Each suite prints human-readable progress to stderr and one JSON document
(the table) to stdout.  Suites register themselves via the :func:`suite`
decorator at import time.
"""

from __future__ import annotations

import importlib
import json
import sys
from typing import Callable, Dict

_SUITES: Dict[str, Callable[[], dict]] = {}

# Suite modules; imported lazily so `python bench.py` (headline path) never
# pays for them and a broken suite can't take down the others' listing.
_SUITE_MODULES = (
    "benchmarks.roofline",
    "benchmarks.flash_sweep",
    "benchmarks.mla_prefill",
    "benchmarks.kda_prefill",
    "benchmarks.ssd_prefill",
    "benchmarks.window_prefill",
    "benchmarks.grouped_matmul",
    "benchmarks.generation",
    "benchmarks.coldstart",
    "benchmarks.ingest",
    "benchmarks.scaling",
    "benchmarks.joint",
    "benchmarks.llama_zeroshot",
    "benchmarks.sentiment_int8",
    "benchmarks.bucketing",
    "benchmarks.overlap",
    "benchmarks.streaming",
    "benchmarks.wq_store",
    "benchmarks.serving",
    "benchmarks.continuous",
    "benchmarks.router",
    "benchmarks.chaos",
    "benchmarks.slo",
    "benchmarks.crash",
)


def suite(name: str):
    """Register ``fn() -> dict`` as a named suite."""

    def register(fn: Callable[[], dict]) -> Callable[[], dict]:
        _SUITES[name] = fn
        return fn

    return register


def _load_all() -> None:
    for module in _SUITE_MODULES:
        try:
            importlib.import_module(module)
        except Exception as exc:  # a broken suite must not hide the rest
            print(f"[benchmarks] skipping {module}: {exc}", file=sys.stderr)


def suite_names() -> list:
    _load_all()
    return sorted(_SUITES)


def run_suite(name: str) -> int:
    _load_all()
    if name not in _SUITES:
        print(
            f"unknown suite {name!r}; have: {', '.join(sorted(_SUITES))}",
            file=sys.stderr,
        )
        return 2
    print(json.dumps(_SUITES[name](), indent=2))
    return 0
