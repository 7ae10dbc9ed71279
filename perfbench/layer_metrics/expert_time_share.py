"""Share (%) of the step program(s)' device time that the routed layers'
feed-forward halves take over the traced job, prefill and passes alike:
the self time of the operations traced under ``moe.route``, ``moe.experts``
(its ``moe.dispatch``, ``moe.matmul`` and ``moe.combine`` and what stands
beside them) and ``moe.shared`` over that of every operation of the
programs (``scope_reduce.py``)."""

import scope_reduce

PROGRAMS = ("jit__score_labels", "jit__diffusion_prefill",
            "jit__diffusion_denoise")
KINDS = ("route", "dispatch", "matmul", "combine", "experts", "shared")


def read(artifacts):
    return scope_reduce.part_share(
        artifacts, PROGRAMS, lambda part: scope_reduce.kind(part) in KINDS)
