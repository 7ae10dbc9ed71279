"""Share (%) of the encoder step's device time that the feed-forward halves
take over the traced job: the self time of the operations traced under
``encoder.ffn`` (``models/distilbert.TransformerBlock``: both matmuls, the
GELU and the layer norm behind them) over that of every operation of
``jit__forward`` (``scope_reduce.py``)."""

import scope_reduce

PROGRAMS = ("jit__forward",)


def read(artifacts):
    return scope_reduce.part_share(
        artifacts, PROGRAMS, lambda part: part == "encoder.ffn")
