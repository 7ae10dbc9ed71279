"""Operations and bytes of one encoder step, computed from its shapes.

The count the algorithm needs, not what a compiler happened to emit: a
multiply-add is two operations, and only matrix multiplications are counted
(layer norms, GELU, softmax and the embedding lookup are left out; they are
under 1% at these widths).

Hand count for DistilBERT-base (dim 768, FFN 3,072, 6 layers, 128 tokens):
per token and layer ``8*768**2`` (Q, K, V, O) + ``4*768*3072`` (FFN) +
``4*128*768`` (scores and weighted values) = 14.55 MFLOP, so about
87 MFLOP a token and 11.2 GFLOP a song.
"""

from __future__ import annotations

import json
import os
from typing import Dict


def encoder_flops_per_token(model: Dict, seq_len: int) -> float:
    dim, ffn = model["dim"], model["hidden_dim"]
    per_layer = 8 * dim * dim + 4 * dim * ffn + 4 * seq_len * dim
    return float(model["n_layers"] * per_layer)


def encoder_step_flops(model: Dict, rows: int, seq_len: int) -> float:
    """Matmul operations of one forward step over ``rows`` x ``seq_len``
    tokens, classifier head (on one token a row) included."""
    dim = model["dim"]
    head = 2 * dim * dim + 2 * dim * model.get("n_classes", 2)
    return rows * (seq_len * encoder_flops_per_token(model, seq_len) + head)


def encoder_step_bytes(model: Dict, rows: int, seq_len: int,
                       weight_bytes: int = 2, act_bytes: int = 2) -> float:
    """Bytes one step must move at the least: every layer's weights once,
    and each layer's input and output activations once."""
    dim, ffn, layers = model["dim"], model["hidden_dim"], model["n_layers"]
    weights = layers * (4 * dim * dim + 2 * dim * ffn) * weight_bytes
    activations = layers * 2 * rows * seq_len * dim * act_bytes
    return float(weights + activations)


def load_peaks(device_kind: str) -> Dict:
    path = os.path.join(os.path.dirname(os.path.abspath(__file__)), "peaks.json")
    with open(path, encoding="utf-8") as fh:
        table = json.load(fh)
    if device_kind not in table:
        raise KeyError(
            f"device kind {device_kind!r} is not in perfbench/peaks.json; "
            "add its published peaks with their source"
        )
    return table[device_kind]


def roofline_seconds(flops: float, bytes_moved: float, peaks: Dict) -> Dict:
    """The least time the chip could take, and which bound sets it."""
    by_compute = flops / peaks["bf16_flops_per_s"]
    by_memory = bytes_moved / peaks["hbm_bytes_per_s"]
    return {
        "seconds": max(by_compute, by_memory),
        "bound": "compute" if by_compute >= by_memory else "memory",
    }
