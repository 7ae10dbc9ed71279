"""Share (%) of the step program(s)' device time spent around the grouped
expert matmuls and not in them, over the traced job: the self time of the
operations traced under ``moe.dispatch`` (the sort by expert, the group
sizes, the row gather to expert order) and ``moe.combine`` (the gather
back, the float32 reshape, the weighted sum) over that of every operation
of the programs (``scope_reduce.py``).  What a fused dispatch would win."""

import scope_reduce
from layer_metrics import expert_time_share

KINDS = ("dispatch", "combine")


def read(artifacts):
    return scope_reduce.part_share(
        artifacts, expert_time_share.PROGRAMS,
        lambda part: scope_reduce.kind(part) in KINDS)
