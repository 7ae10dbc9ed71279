"""Share (%) of the scoring program's device time that the grouped-query
attention layers take over the traced job, prefill and label passes alike:
the self time of the operations traced under ``gqa`` (projections, QK-norm,
RoPE, the gate, the put-back at ``[B, S]``, the kernel and the output
projection together: the rows ``prefill.gqa`` and ``labels.gqa`` of
``scope_parts.json``) over that of every operation of the program
(``scope_reduce.py``).  High means attention, not the experts, sets the
rate."""

import scope_reduce

PROGRAMS = ("jit__score_labels",)
KINDS = ("gqa",)


def read(artifacts):
    return scope_reduce.part_share(
        artifacts, PROGRAMS, lambda part: scope_reduce.kind(part) in KINDS)
