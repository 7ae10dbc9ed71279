"""Operations and least bytes of one scoring step of the state-space hybrid
decoder (``models/llama.py`` ``_score_labels`` on a ``granitemoehybrid``
configuration: one prompt prefill a row through Mamba-2 and grouped-query
mixers, then the label continuations), computed from the configuration file
and the **real** counts of the step, whatever implements it: padding and
filler slots, the upper triangle of causal attention, the kernel's own chunk
and how the experts are grouped do not enter.  A multiply-add is two
operations; only matrix multiplications and the recurrence are counted
(norms, SiLU, softplus, the short convolution at 8 operations a channel,
softmax, the router's top-k, the sort, the multipliers and the embedding
lookup are left out; under 1% at these widths).

Per position that goes through the layers (a prompt token, or a label token
whose forward pass is read), with ``D`` hidden, ``H`` Mamba heads of ``P``,
inner width ``I = H P``, state ``N``, ``Hq | Hkv`` attention heads of ``d``:

* Mamba-2 layer, projections: ``2 * (D * (2I + 2N + H) + I * D)`` (``[z |
  xBC | dt]`` and the output), and the recurrence in chunked form at the
  PUBLISHED chunk ``L = mamba_chunk_size`` with the triangle counted as it is
  (``(L + 1) / 2`` causal pairs a token): ``H * (2 * P * (L + 1) / 2 + 4 * N
  * P) + 2 * N * (L + 1) / 2`` (a head's decayed triangle times its inputs;
  the read of the carried state and the state's update, ``2 N P`` each; ``C
  B^T`` once for all heads).  At 256 that is within 1% of the token-by-token
  recurrence's own ``6 N P`` a head, so the count is the same work whichever
  form runs, and the kernel's own chunk (128) does not enter.
* attention layer, projections: ``2 * (D * (Hq + 2 Hkv) * d + Hq * d * D)``;
  attention ``2 * Hq * 2 * d`` per causal (query, key) pair
* every layer's feed-forward half: ``6 * D * intermediate_size`` an
  assignment to an expert HELD here (the span's ``assignments_held`` and
  ``label_assignments_held``: what the absent experts would run is the other
  chip's work) ``+ 6 * D * shared_intermediate_size + 2 * D * E`` a position
  (the shared SwiGLU; the router over all ``E`` =
  ``published.num_local_experts``)

and the tied head, ``2 * D * vocabulary`` (the slice held), once per position
whose logits are read: the prompt's last, and each label token but the
label's last.

Hand count at the published widths (D 4,096, H 128, P 64, I 8,192, N 128, L
256, 32 | 8 heads of 128, experts of 768, shared 1,536, E 72, vocabulary
50,176; nine Mamba-2 layers and one attention layer): Mamba projections ``2 *
102,236,160 = 204.47`` MFLOP, recurrence ``128 * (64 * 257 + 32,768) + 128 *
257 = 6.333``; attention projections ``2 * 41,943,040 = 83.89``; a layer's
shared SwiGLU and router ``37.75 + 0.59 = 38.34``; an assignment ``18.87``.
A position costs ``9 * 210.80 + 83.89 + 10 * 38.34 = 2,364.5`` MFLOP before
its assignments, a pair ``2 * 32 * 256 = 16,384`` FLOP, a head position
``411.0`` MFLOP.  One row of 260 prompt tokens and three two-token labels
with 5 of 10 assignments held in every layer: positions ``263``, assignments
``263 * 10 * 5 = 13,150``, pairs ``260 * 261 / 2 + 3 * 260 + 6 = 34,716``,
head positions ``4``: ``263 * 2,364.5 + 13,150 * 18.874 + 34,716 * 0.016384
+ 4 * 411.04 = 872.28`` GFLOP.
"""

from __future__ import annotations

from typing import Mapping

from flops_ling import step_counts  # noqa: F401  the span's counts, as Ling's


def _layers(config: Mapping):
    """``(mamba layers, attention layers)``."""
    mamba = sum(kind == "mamba" for kind in config["layer_types"])
    return mamba, len(config["layer_types"]) - mamba


def _inner(config: Mapping) -> int:
    return config["mamba_n_heads"] * config["mamba_d_head"]


def mamba_projection_flops(config: Mapping) -> float:
    d, inner = config["hidden_size"], _inner(config)
    fused = 2 * inner + 2 * config["mamba_d_state"] + config["mamba_n_heads"]
    return float(2 * (d * fused + inner * d))


def ssd_flops(config: Mapping) -> float:
    """The recurrence in chunked form at the published chunk, a token, all
    heads of one layer, causal pairs only."""
    heads, width, n = (config["mamba_n_heads"], config["mamba_d_head"],
                       config["mamba_d_state"])
    pairs = (config["mamba_chunk_size"] + 1) / 2
    return float(heads * (2 * width * pairs + 4 * n * width) + 2 * n * pairs)


def attention_projection_flops(config: Mapping) -> float:
    d, heads = config["hidden_size"], config["num_attention_heads"]
    head_dim = d // heads
    return float(2 * (d * (heads + 2 * config["num_key_value_heads"])
                      * head_dim + heads * head_dim * d))


def position_flops(config: Mapping) -> float:
    """Operations of one position through every layer, attention's score
    and value products and the routed experts' assignments left out."""
    mamba, attention = _layers(config)
    d = config["hidden_size"]
    router_width = (config.get("published") or {}).get(
        "num_local_experts", config["num_local_experts"])
    a_feed_forward = (6 * d * config["shared_intermediate_size"]
                      + 2 * d * router_width)
    return (mamba * (mamba_projection_flops(config) + ssd_flops(config))
            + attention * attention_projection_flops(config)
            + (mamba + attention) * a_feed_forward)


def assignment_flops(config: Mapping) -> float:
    return float(6 * config["hidden_size"] * config["intermediate_size"])


def pair_flops(config: Mapping) -> float:
    """Operations of one causal (query, key) pair through the attention
    layers: ``q k`` and ``p v``, a multiply-add a channel each."""
    heads = config["num_attention_heads"]
    return float(_layers(config)[1] * 2 * heads
                 * 2 * (config["hidden_size"] // heads))


def head_flops(config: Mapping) -> float:
    return float(2 * config["hidden_size"] * config["vocab_size"])


def step_flops(config: Mapping, step: Mapping) -> float:
    counts = step_counts(step)
    return (counts["positions"] * position_flops(config)
            + counts["assignments"] * assignment_flops(config)
            + counts["pairs"] * pair_flops(config)
            + counts["head_positions"] * head_flops(config))


def ssd_prefill_flops(config: Mapping, step: Mapping) -> float:
    """Operations of the prefill's recurrence in one step, all Mamba-2
    layers: the real prompt tokens (the label continuations run token by
    token and are not the kernel's)."""
    return int(step["tokens_real"]) * _layers(config)[0] * ssd_flops(config)


def ssd_prefill_bytes(config: Mapping, step: Mapping,
                      act_bytes: int = 2) -> float:
    """Least bytes of the same: a real token's ``x`` and ``y`` (``act_bytes``
    a channel), ``B`` and ``C`` (``act_bytes`` a state channel), its step
    ``delta`` (float32 a head), and one float32 state a row a head written
    once."""
    heads, n = config["mamba_n_heads"], config["mamba_d_state"]
    a_token = 2 * _inner(config) * act_bytes + 2 * n * act_bytes + 4 * heads
    a_row = _inner(config) * n * 4
    return float(_layers(config)[0] * (int(step["tokens_real"]) * a_token
                                       + int(step["rows"]) * a_row))
