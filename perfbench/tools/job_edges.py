"""The edges and the reader of every ``sentiment`` job a run left under
``perfbench/out/<cell>/run/job*/sentiment/telemetry.jsonl``, one row a job,
from the program's own spans: job seconds (``run_start`` to the end of
``manifest``), head (``run_start`` to the end of the first ``h2d``), tail
(end of the last ``compute`` to the end of ``manifest``), the first and the
median full-batch ``read``, the first ``tokenize`` and ``h2d``, the sum of
the consumer's ``wait``, ``write_totals``, ``manifest``; all in ms but the
job's seconds.  Slow and fast jobs side by side show which of these differs
(PERF.md, section 7, "what sets a job's speed").  Not part of a run.

    python3 perfbench/tools/job_edges.py [--out perfbench/out] [--cell sentiment_corpus]
"""

from __future__ import annotations

import argparse
import glob
import os
import re
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH_DIR = os.path.dirname(HERE)
sys.path[:0] = [BENCH_DIR, os.path.dirname(BENCH_DIR)]

import common  # noqa: E402
import job_spans  # noqa: E402

COLUMNS = ("job_s", "head", "tail", "read0", "read_med", "tokenize0", "h2d0",
           "wait_sum", "write_totals", "manifest")


def row(log) -> dict:
    """One job's numbers; a span the log lacks leaves its column ``None``."""
    def ms(span):
        return None if span is None else 1e3 * span["dur_s"]

    def last(name):
        found = job_spans.named(log, name)
        return found[-1] if found else None

    manifest = last("manifest")
    waits = job_spans.named(log, "wait", pipeline=job_spans.PIPELINE)
    return {
        "job_s": (manifest["end"] - log["run_start"]
                  if manifest and log["run_start"] is not None else None),
        "head": job_spans.head_ms(log),
        "tail": job_spans.tail_ms(log),
        "read0": ms(job_spans.first_item(log, "read")),
        "read_med": job_spans.read_batch_ms(log),
        "tokenize0": ms(job_spans.first_item(log, "tokenize")),
        "h2d0": ms(job_spans.first_item(log, "h2d")),
        "wait_sum": 1e3 * sum(s["dur_s"] for s in waits) if waits else None,
        "write_totals": ms(last("write_totals")),
        "manifest": ms(manifest),
    }


def job_logs(out_root: str, cell: str):
    """``(cell, job, path)`` of every sentiment log under ``out_root``, jobs
    in the order they ran (the warm-up job first)."""
    found = []
    for path in glob.glob(os.path.join(
            out_root, cell, "run", "*", "sentiment", "telemetry.jsonl")):
        parts = path.split(os.sep)
        number = re.fullmatch(r"job(\d+)", parts[-3])
        found.append((parts[-5], int(number.group(1)) if number else -1,
                      parts[-3], path))
    return [(name, job, path) for name, _, job, path in sorted(found)]


def main() -> int:
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument("--out", default=common.OUT_ROOT)
    parser.add_argument("--cell", default="*")
    args = parser.parse_args()
    found = job_logs(args.out, args.cell)
    if not found:
        print(f"job_edges: no sentiment job under {args.out}", file=sys.stderr)
        return 1
    print(" ".join(["cell".ljust(26), "job".ljust(7)]
                   + [c.rjust(12) for c in COLUMNS]))
    for cell, job, path in found:
        values = row(job_spans.read_log(path))
        cells = ["-".rjust(12) if values[c] is None
                 else f"{values[c]:12.3f}" for c in COLUMNS]
        print(" ".join([cell.ljust(26), job.ljust(7)] + cells))
    return 0


if __name__ == "__main__":
    sys.exit(main())
