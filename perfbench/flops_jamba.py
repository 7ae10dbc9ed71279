"""Operations and least bytes of one scoring step of the Jamba decoder
(``models/llama.py`` ``_score_labels`` on a ``jamba`` configuration: one
prompt prefill a row through Mamba-1 and grouped-query mixers, then the label
continuations), computed from the configuration file and the **real** counts
of the step, whatever implements it: padding and filler slots, the upper
triangle of causal attention and the kernel's chunks do not enter.  A
multiply-add is two operations; only matrix multiplications and the
recurrence are counted (norms, SiLU, softplus, the short convolution at 8
operations a channel, softmax and the embedding lookup are left out; under
1% at these widths).

Per position that goes through the layers (a prompt token, or a label token
whose forward pass is read), with ``D`` hidden, inner width ``I``, state
``N``, step rank ``R``, ``Hq | Hkv`` attention heads of ``d``, SwiGLU width
``F``:

* Mamba-1 layer, projections: ``2 * (D * 2I + I * (R + 2N) + R * I + I *
  D)`` (``[x | z]``, ``[r | B | C]``, the step and the output), and the
  recurrence ``7 * I * N`` (``delta A``, its ``exp``, the decay, ``delta x
  B``, the sum, and the multiply-add of ``y = S C``: seven an entry);
* attention layer, projections: ``2 * (D * (Hq + 2 Hkv) * d + Hq * d *
  D)``; attention ``2 * Hq * 2 * d`` per causal (query, key) pair;
* every layer's dense SwiGLU: ``6 * D * F``;

and the tied head, ``2 * D * vocabulary``, once per position whose logits
are read: the prompt's last, and each label token but the label's last.

Hand count at the published widths (D 2,560, I 5,120, N 16, R 160, 20 | 1
heads of 128, F 8,192, vocabulary 65,536; 26 Mamba-1 layers and 2 attention
layers): Mamba-1 projections ``2 * 41,123,840 = 82.25`` MFLOP, recurrence
``0.573``; attention projections ``2 * 13,762,560 = 27.53``; a SwiGLU
``125.83``.  A position costs ``26 * 82.82 + 2 * 27.53 + 28 * 125.83 =
5,731.6`` MFLOP, a pair ``2 * 2 * 20 * 256 = 20,480`` FLOP, a head position
``335.5`` MFLOP.  One row of 320 prompt tokens and three two-token labels:
positions ``323``, pairs ``320 * 321 / 2 + 3 * 320 + 6 = 52,326``, head
positions ``4``: ``323 * 5,731.6 + 52,326 * 0.02048 + 4 * 335.5 = 1,853.7``
GFLOP.
"""

from __future__ import annotations

from typing import Dict, Mapping


def _layers(config: Mapping):
    """``(Mamba-1 layers, attention layers)`` by the source's rule."""
    period, offset = config["attn_layer_period"], config["attn_layer_offset"]
    total = config["num_hidden_layers"]
    attention = sum(i % period == offset for i in range(total))
    return total - attention, attention


def _widths(config: Mapping):
    """``(D, I, N, R)``."""
    d = config["hidden_size"]
    return (d, config["mamba_expand"] * d, config["mamba_d_state"],
            config["mamba_dt_rank"])


def mamba_projection_flops(config: Mapping) -> float:
    d, inner, n, rank = _widths(config)
    return float(2 * (d * 2 * inner + inner * (rank + 2 * n) + rank * inner
                      + inner * d))


def recurrence_flops(config: Mapping) -> float:
    """The recurrence a token, all channels of one layer."""
    _, inner, n, _ = _widths(config)
    return float(7 * inner * n)


def attention_projection_flops(config: Mapping) -> float:
    d, heads = config["hidden_size"], config["num_attention_heads"]
    head_dim = d // heads
    return float(2 * (d * (heads + 2 * config["num_key_value_heads"])
                      * head_dim + heads * head_dim * d))


def position_flops(config: Mapping) -> float:
    """Operations of one position through every layer, attention's score
    and value products left out."""
    mamba, attention = _layers(config)
    swiglu = 6 * config["hidden_size"] * config["intermediate_size"]
    return (mamba * (mamba_projection_flops(config)
                     + recurrence_flops(config))
            + attention * attention_projection_flops(config)
            + (mamba + attention) * swiglu)


def pair_flops(config: Mapping) -> float:
    """Operations of one causal (query, key) pair through the attention
    layers: ``q k`` and ``p v``, a multiply-add a channel each."""
    heads = config["num_attention_heads"]
    return float(_layers(config)[1] * 2 * heads
                 * 2 * (config["hidden_size"] // heads))


def head_flops(config: Mapping) -> float:
    return float(2 * config["hidden_size"] * config["vocab_size"])


def step_counts(step: Mapping) -> Dict[str, int]:
    """Positions, causal pairs and head positions of one step from what the
    program recorded on its ``compute`` span: ``rows``, ``tokens_real``,
    ``token_pairs`` and ``label_positions_real``."""
    rows, tokens = int(step["rows"]), int(step["tokens_real"])
    labels = int(step["label_positions_real"])
    label_pairs = labels * tokens + rows * labels * (labels + 1) // 2
    return {
        "positions": tokens + rows * labels,
        "pairs": int(step["token_pairs"]) + label_pairs,
        "head_positions": rows * (1 + labels),
    }


def step_flops(config: Mapping, step: Mapping) -> float:
    counts = step_counts(step)
    return (counts["positions"] * position_flops(config)
            + counts["pairs"] * pair_flops(config)
            + counts["head_positions"] * head_flops(config))


def scan_flops_a_token(config: Mapping) -> float:
    """What the selective-scan kernel computes a token and a layer: the
    step's product ``r W_dt`` (``2 R I``) and the recurrence."""
    _, inner, _, rank = _widths(config)
    return 2 * rank * inner + recurrence_flops(config)


def selective_scan_flops(config: Mapping, step: Mapping) -> float:
    """Operations of the prefill's recurrence in one step, all Mamba-1
    layers: the real prompt tokens (the label continuations run token by
    token and are not the kernel's)."""
    return (int(step["tokens_real"]) * _layers(config)[0]
            * scan_flops_a_token(config))


def selective_scan_bytes(config: Mapping, step: Mapping,
                         act_bytes: int = 2) -> float:
    """Least bytes of the same: a real token's ``x`` read and ``y`` written
    (``act_bytes`` a channel), its ``r`` (``act_bytes`` a rank), ``B`` and
    ``C`` (``act_bytes`` an entry); a call's ``A_log`` (float32 ``I x N``),
    ``D`` and ``b_dt`` (float32 a channel) and ``W_dt`` (``act_bytes`` ``R x
    I``); a row's float32 state written once.  A kernel that read the step
    ``delta`` whole in place of ``r`` would move more than this, never
    less."""
    _, inner, n, rank = _widths(config)
    a_token = 2 * inner * act_bytes + rank * act_bytes + 2 * n * act_bytes
    a_call = 4 * inner * n + 2 * 4 * inner + rank * inner * act_bytes
    a_row = 4 * inner * n
    return float(_layers(config)[0] * (int(step["tokens_real"]) * a_token
                                       + a_call + int(step["rows"]) * a_row))
