"""The traced job of a ``--trace 1`` run as a table: device seconds by part
of each step program (``scope_reduced.json``, written by
``perfbench/scope_reduce.py``: self time of every device operation, summed
by the ``jax.named_scope`` path it was traced under), then the longest
operations with their part and path.  The table section 5 of ``PERF.md``
carries for each cell.  Not part of a run.

    python3 perfbench/tools/scope_table.py perfbench/out/<cell>
    python3 perfbench/tools/scope_table.py <a kept scope_reduced.json>
"""

from __future__ import annotations

import json
import os
import sys


def lines(reduced: dict) -> list:
    busy = reduced["busy_s"]
    lost = reduced["unmapped_s"]
    out = [f"device busy {busy:.3f} s of the traced job; to no scope "
           f"{lost:.4f} s ({100 * lost / busy:.2f}%)"]
    by_time = sorted(reduced["modules"].items(),
                     key=lambda kv: -kv[1]["seconds"])
    for module, summed in by_time:
        out.append(f"{module}: {summed['seconds']:.3f} s in "
                   f"{summed['executions']:g} executions "
                   f"({100 * summed['seconds'] / busy:.1f}% of busy)")
        for part, seconds in sorted(summed["parts"].items(),
                                    key=lambda kv: -kv[1]):
            out.append(f"  {part:<22}{seconds:>9.3f} s"
                       f"{100 * seconds / summed['seconds']:>7.1f}%")
    out.append("the longest operations:")
    for op in reduced["top_ops"]:
        out.append(f"  {op['seconds']:>8.3f} s  {op['part']:<18}"
                   f"{op['module']}:{op['op']}  {op['op_name']}")
    return out


def main(argv) -> int:
    if len(argv) != 1:
        print(__doc__, file=sys.stderr)
        return 2
    path = (os.path.join(argv[0], "scope_reduced.json")
            if os.path.isdir(argv[0]) else argv[0])
    with open(path, encoding="utf-8") as fh:
        print("\n".join(lines(json.load(fh))))
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
