"""Device time of one full-batch encoder step: the median length of the
executions of the jitted forward program in the traced window."""

import common
import trace_reduce

MODULE = "forward"


def full_batch_runs(artifacts):
    """Lengths of the forward program's executions at the full batch: a
    job's last, partial batch runs a smaller program, so only executions
    within 20% of the longest count."""
    if not artifacts.get("trace"):
        return []
    runs = trace_reduce.module_runs(artifacts["trace"], MODULE)
    return [r for r in runs if r >= 0.8 * max(runs)]


def read(artifacts):
    runs = full_batch_runs(artifacts)
    return 1e3 * common.median(runs) if runs else None
