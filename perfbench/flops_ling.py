"""Operations and least bytes of one scoring step of the hybrid decoder
(``models/llama.py`` ``_score_labels`` on a ``ling_hybrid`` configuration:
one prompt prefill a row through KDA and gated-MLA mixers, then the label
continuations), computed from the configuration file and the **real**
counts of the step, whatever implements it: padding and filler slots, the
upper triangle of causal attention, the form of latent attention, the form
of the recurrence (chunked or a token a step) and how the experts are
grouped do not enter.  A multiply-add is two operations; only matrix
multiplications and the recurrence are counted (norms, RoPE, SiLU, the
short convolutions at 8 operations a channel, softmax, the router's sigmoid
and top-k, the sort and the embedding lookup are left out; under 1% at
these widths).

Per position that goes through the layers (a prompt token, or a label token
whose forward pass is read), with ``D`` hidden, ``H`` heads, ``d`` the KDA
head width, ``n | r | v`` MLA's nope / rope / value widths, ``c`` its rank:

* KDA layer, projections: ``2 * (5 * D * H*d + H*d * D + D * H)`` (q, k, v,
  the decay and the output gate; the output; beta), and the recurrence:
  ``6 * d * d`` a head (decay and read ``k^T S``, write ``k u^T``, read
  ``S^T q``: three passes over the state at a multiply-add each)
* MLA layer, projections: ``2 * (D*H*(n+r) + D*(c+r) + c*H*(n+v) + H*v*D +
  D*H)`` (the last is the head-wise gate); attention ``2 * H * (n+r+v)`` per
  causal (query, key) pair
* dense layer: ``6 * D * intermediate``
* routed layer: ``6 * D * moe_intermediate`` an assignment to an expert
  HELD here (the span's ``assignments_held`` and ``label_assignments_held``:
  what the absent experts would run is other chips' work) ``+ 6 * D * shared
  + 2 * D * E`` a position (shared expert; the router over all ``E`` =
  ``published.num_experts``)

and the head, ``2 * D * vocabulary`` (the slice held), once per position
whose logits are read: the prompt's last, and each label token but the
label's last.

Hand count at the published widths (D 2,560, H 32, d 128, 128|64|128, c 512,
dense 6,144, experts of 768, shared 768, E 512, vocabulary 39,296; layers:
dense KDA, five routed KDA, one routed MLA): KDA projections ``2 * 62,996,480
= 125.99`` MFLOP, recurrence ``32 * 6 * 128 * 128 = 3.146``; MLA projections
``2 * 31,965,184 = 63.93``; dense ``94.37``; a routed layer's shared expert
and router ``11.80 + 2.62 = 14.42``; an assignment ``11.80``.  A position
costs ``6 * 129.14 + 63.93 + 94.37 + 6 * 14.42 = 1,019.64`` MFLOP before its
assignments, a pair ``2 * 32 * 320 = 20,480`` FLOP, a head position
``201.2`` MFLOP.  One row of 260 prompt tokens and three two-token labels
with 2 of 8 assignments held in every routed layer: positions ``263``,
assignments ``263 * 6 * 2 = 3,156``, pairs ``260 * 261 / 2 + 3 * 260 + 6 =
34,716``, head positions ``4``: ``263 * 1,019.64 + 3,156 * 11.796 + 34,716
* 0.02048 + 4 * 201.2 = 306.91`` GFLOP.
"""

from __future__ import annotations

from typing import Dict, Mapping


def _layers(config: Mapping):
    """``(kda layers, mla layers, dense layers, routed layers)``."""
    layers = config["num_hidden_layers"]
    ids = (config.get("model") or {}).get("layer_ids") or range(layers)
    mla = sum((i + 1) % config["layer_group_size"] == 0 for i in ids)
    dense = min(config["first_k_dense_replace"], layers)
    return layers - mla, mla, dense, layers - dense


def kda_projection_flops(config: Mapping) -> float:
    d_model, heads = config["hidden_size"], config["num_attention_heads"]
    width = heads * config["head_dim"]
    return float(2 * (5 * d_model * width + width * d_model
                      + d_model * heads))


def kda_recurrence_flops(config: Mapping) -> float:
    """The gated delta rule, a token, all heads of one layer."""
    return float(config["num_attention_heads"] * 6 * config["head_dim"] ** 2)


def mla_projection_flops(config: Mapping) -> float:
    d, heads = config["hidden_size"], config["num_attention_heads"]
    nope, rope = config["qk_nope_head_dim"], config["qk_rope_head_dim"]
    v, rank = config["v_head_dim"], config["kv_lora_rank"]
    return float(2 * (d * heads * (nope + rope) + d * (rank + rope)
                      + rank * heads * (nope + v) + heads * v * d
                      + d * heads))


def position_flops(config: Mapping) -> float:
    """Operations of one position through every layer, attention's score
    and value products and the routed experts' assignments left out."""
    kda, mla, dense, routed = _layers(config)
    d = config["hidden_size"]
    router_width = (config.get("published") or {}).get(
        "num_experts", config["num_experts"])
    a_routed_layer = (6 * d * config["moe_shared_expert_intermediate_size"]
                      + 2 * d * router_width)
    return (kda * (kda_projection_flops(config)
                   + kda_recurrence_flops(config))
            + mla * mla_projection_flops(config)
            + dense * 6 * d * config["intermediate_size"]
            + routed * a_routed_layer)


def assignment_flops(config: Mapping) -> float:
    return float(6 * config["hidden_size"] * config["moe_intermediate_size"])


def pair_flops(config: Mapping) -> float:
    """Operations of one causal (query, key) pair through the MLA layers."""
    per_head = (config["qk_nope_head_dim"] + config["qk_rope_head_dim"]
                + config["v_head_dim"])
    return float(_layers(config)[1] * 2 * config["num_attention_heads"]
                 * per_head)


def head_flops(config: Mapping) -> float:
    return float(2 * config["hidden_size"] * config["vocab_size"])


def step_counts(step: Mapping) -> Dict[str, int]:
    """Positions, held assignments, causal pairs and head positions of one
    step from what the program recorded on its ``compute`` span: ``rows``,
    ``tokens_real``, ``token_pairs``, ``label_positions_real``,
    ``assignments_held`` (the prefill's real positions') and
    ``label_assignments_held`` (the label positions' whose forward is
    read)."""
    rows, tokens = int(step["rows"]), int(step["tokens_real"])
    labels = int(step["label_positions_real"])
    label_pairs = labels * tokens + rows * labels * (labels + 1) // 2
    return {
        "positions": tokens + rows * labels,
        "assignments": int(step["assignments_held"])
        + int(step.get("label_assignments_held", 0)),
        "pairs": int(step["token_pairs"]) + label_pairs,
        "head_positions": rows * (1 + labels),
    }


def step_flops(config: Mapping, step: Mapping) -> float:
    counts = step_counts(step)
    return (counts["positions"] * position_flops(config)
            + counts["assignments"] * assignment_flops(config)
            + counts["pairs"] * pair_flops(config)
            + counts["head_positions"] * head_flops(config))


def kda_prefill_flops(config: Mapping, step: Mapping) -> float:
    """Operations of the prefill's recurrence in one step, all KDA layers:
    the real prompt tokens (the label continuations run token by token and
    are not the kernel's)."""
    return (int(step["tokens_real"]) * _layers(config)[0]
            * kda_recurrence_flops(config))


def kda_prefill_bytes(config: Mapping, step: Mapping,
                      act_bytes: int = 2) -> float:
    """Least bytes of the same: a real token's q, k, v and o (``act_bytes``
    each a channel), its log-decay (float32 a channel) and beta (float32 a
    head), and one float32 state a row a head written once."""
    heads, d = config["num_attention_heads"], config["head_dim"]
    a_token = heads * (4 * d * act_bytes + 4 * d + 4)
    a_row = heads * d * d * 4
    return float(_layers(config)[0] * (int(step["tokens_real"]) * a_token
                                       + int(step["rows"]) * a_row))
