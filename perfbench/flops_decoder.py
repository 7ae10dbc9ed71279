"""Operations and least bytes of one scoring step of the zero-shot decoder
(``models/llama.py`` ``_score_labels``: one prompt prefill a row, then the
label continuations), computed from the configuration file and the **real**
token counts of the step, whatever implements it: padding positions, the
upper triangle of causal attention, the form of latent attention (expanded
or absorbed) and how the experts are grouped do not enter.  A multiply-add
is two operations; only matrix multiplications are counted (norms, RoPE,
SiLU, softmax, the router's sigmoid and top-k, the sort and the embedding
lookup are left out; under 1% at these widths).

Per position that goes through the layers (a prompt token, or a label token
whose forward pass is read), per layer, with ``D`` hidden, ``H`` heads,
``n | r | v`` the nope / rope / value head widths and ``c`` the latent rank:

* MLA projections: ``2 * (D*H*(n+r) + D*(c+r) + c*H*(n+v) + H*v*D)``
* attention: ``2 * H * (n+r+v)`` per (query, key) pair, causal pairs only
* dense layer: ``6 * D * intermediate``
* routed layer: ``top_k * 6 * D * moe_intermediate`` (the experts a token
  is sent to) ``+ 6 * D * n_shared * moe_intermediate + 2 * D * E`` (router)

and the head, ``2 * D * vocabulary``, once per position whose logits are
read: the prompt's last, and each label token but the label's last.

Hand count at the published widths (D 2,048, H 32, 128|64|128, c 512, dense
6,144, 128 experts of 768 at 6 a token, 2 shared, vocabulary 128,256; 1 dense
+ 6 routed layers): MLA ``2 * 26,345,472 = 52.69`` MFLOP; dense FFN
``75.50``; routed FFN ``56.62 + 18.87 + 0.52 = 76.02``; so a position costs
``7 * 52.69 + 75.50 + 6 * 76.02 = 900.46`` MFLOP in matmuls, a pair
``7 * 2 * 32 * 320 = 143,360`` FLOP and a head position ``525.3`` MFLOP.  One
row of 260 prompt tokens and three two-token labels: positions ``260 + 3``,
pairs ``260*261/2 + 3*260 + 6 = 34,716``, head positions ``1 + 3``:
``263 * 900.46 + 34,716 * 0.14336 + 4 * 525.34 = 243.90`` GFLOP.
"""

from __future__ import annotations

from typing import Dict, Mapping


def position_flops(config: Mapping) -> float:
    """Matmul operations of one position through every layer, attention's
    score and value products left out."""
    d, heads = config["hidden_size"], config["num_attention_heads"]
    nope, rope = config["qk_nope_head_dim"], config["qk_rope_head_dim"]
    v, rank = config["v_head_dim"], config["kv_lora_rank"]
    mla = 2 * (d * heads * (nope + rope) + d * (rank + rope)
               + rank * heads * (nope + v) + heads * v * d)
    dense = 6 * d * config["intermediate_size"]
    width = config["moe_intermediate_size"]
    routed = (config["num_experts_per_tok"] * 6 * d * width
              + 6 * d * config["n_shared_experts"] * width
              + 2 * d * config["n_routed_experts"])
    layers = config["num_hidden_layers"]
    n_dense = min(config["first_k_dense_replace"], layers)
    return float(layers * mla + n_dense * dense + (layers - n_dense) * routed)


def pair_flops(config: Mapping) -> float:
    """Operations of one (query, key) pair through every layer's attention:
    the score over ``nope + rope`` and the weighted value over ``v``."""
    per_head = (config["qk_nope_head_dim"] + config["qk_rope_head_dim"]
                + config["v_head_dim"])
    return float(config["num_hidden_layers"] * 2
                 * config["num_attention_heads"] * per_head)


def head_flops(config: Mapping) -> float:
    return float(2 * config["hidden_size"] * config["vocab_size"])


def step_counts(step: Mapping) -> Dict[str, int]:
    """Positions, causal pairs and head positions of one step from what the
    program recorded on its ``compute`` span: ``rows``, ``tokens_real``
    (prompt tokens), ``token_pairs`` (sum over rows of ``n*(n+1)/2``),
    ``label_positions_real`` (label tokens a row whose forward is read)."""
    rows, tokens = int(step["rows"]), int(step["tokens_real"])
    labels = int(step["label_positions_real"])
    # A label token attends the row's prompt and the label tokens up to
    # itself: counted as one label of ``labels`` tokens a row, which is a
    # few pairs a row above several short labels.
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


def step_bytes(config: Mapping, step: Mapping, weight_bytes: int = 2,
               act_bytes: int = 2) -> float:
    """Bytes one step must move at the least: every layer's weights once
    (at a thousand assignments an expert a step every expert is read), the
    head once, the embedding rows of the real tokens, and each layer's input
    and output activations once."""
    d = config["hidden_size"]
    layers = config["num_hidden_layers"]
    n_dense = min(config["first_k_dense_replace"], layers)
    heads = config["num_attention_heads"]
    nope, rope = config["qk_nope_head_dim"], config["qk_rope_head_dim"]
    v, rank = config["v_head_dim"], config["kv_lora_rank"]
    mla = (d * heads * (nope + rope) + d * (rank + rope)
           + rank * heads * (nope + v) + heads * v * d)
    width = config["moe_intermediate_size"]
    routed = (3 * d * width * (config["n_routed_experts"]
                               + config["n_shared_experts"])
              + d * config["n_routed_experts"])
    weights = (layers * mla + n_dense * 3 * d * config["intermediate_size"]
               + (layers - n_dense) * routed + d * config["vocab_size"])
    positions = step_counts(step)["positions"]
    # each layer's input and output, the head's input, the embedding rows
    activations = (layers * 2 + 2) * positions * d
    return float(weights * weight_bytes + activations * act_bytes)
