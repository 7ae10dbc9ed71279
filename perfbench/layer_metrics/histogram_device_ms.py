"""Device time of one chunk of the streaming word histogram: the median
length of the executions of the accumulate program in the traced window."""

import common
import trace_reduce

MODULE = "local"


def read(artifacts):
    if not artifacts.get("trace"):
        return None
    runs = trace_reduce.module_runs(artifacts["trace"], MODULE)
    # the merge (`psum_rows`) shares the program name and runs once a
    # histogram, in microseconds; the chunk accumulations are the long runs
    runs = [r for r in runs if r >= 0.2 * max(runs)] if runs else []
    return 1e3 * common.median(runs) if runs else None
