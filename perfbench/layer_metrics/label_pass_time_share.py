"""Share (%) of the scoring program's device time that the label
continuations take over the traced job: the self time of the operations
traced under the scope ``labels`` (``models/llama.score_labels_program``:
the log-softmax of the prompt's last logits, the continuations' passes on
the caches and what scores them) over that of every operation of
``jit__score_labels`` (``scope_reduce.py``).  Low means the prefill sets
the rate."""

import scope_reduce

PROGRAMS = ("jit__score_labels",)


def read(artifacts):
    return scope_reduce.part_share(
        artifacts, PROGRAMS, lambda part: part.startswith("labels."))
