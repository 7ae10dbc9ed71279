"""Operations and least bytes of one scoring step of the decoder whose
attention layers differ in kind (``models/llama.py`` ``_score_labels`` on a
``laguna`` configuration: one prompt prefill a row through full and
sliding-window grouped-query layers, then the label continuations), computed
from the configuration file and the **real** counts of the step, whatever
implements it: padding and filler slots, the pairs outside the causal
triangle or behind a window, the kernel's tiles and how the experts are
grouped do not enter.  A multiply-add is two operations; only matrix
multiplications are counted (norms, RoPE, SiLU, softplus, softmax, the
router's top-k, the sort and the embedding lookup are left out; under 1% at
these widths).

Per position that goes through the layers (a prompt token, or a label token
whose forward pass is read), with ``D`` hidden, ``H_kind | H_kv`` heads of
``d``:

* an attention layer of a kind, projections and gate: ``2 * (D * (H_kind + 2
  H_kv) * d + H_kind * d * D) + 2 * D * H_kind``; attention ``2 * H_kind * 2 *
  d`` per (query, key) pair INSIDE the layer's mask: causal on a full layer,
  of those the pairs inside the window on a sliding one (the span's
  ``token_pairs_full`` / ``token_pairs_window`` of one layer of each, and
  ``label_pairs_*`` of the label positions)
* a dense layer's SwiGLU: ``6 * D * intermediate_size``
* a routed layer: ``6 * D * moe_intermediate_size`` an assignment to an
  expert HELD here (the span's ``assignments_held`` and
  ``label_assignments_held``: what the absent experts would run is the other
  chip's work) ``+ 6 * D * shared_expert_intermediate_size + 2 * D * E`` a
  position (the shared expert; the router over all ``E`` =
  ``published.num_experts``)

and the head, ``2 * D * vocabulary`` (the slice held), once per position
whose logits are read: the prompt's last, and each label token but the
label's last.

Hand count at the published widths (D 3,072, d 128, 8 key/value heads, 48
query heads on a full layer and 72 on a sliding one; layers full-dense,
sliding, sliding, sliding, full; SwiGLU 12,288; experts of 1,024, shared
1,024, E 256; vocabulary 50,176): full projections ``2 * (3072 * 64 * 128 + 48
* 128 * 3072) + 2 * 3072 * 48 = 88.375`` MFLOP, sliding ``2 * (3072 * 88 * 128
+ 72 * 128 * 3072) + 2 * 3072 * 72 = 126.271``; dense SwiGLU ``226.492``; a
routed layer's shared expert and router ``18.874 + 1.573 = 20.447``; an
assignment ``18.874``.  A position costs ``2 * 88.375 + 3 * 126.271 + 226.492
+ 4 * 20.447 = 863.846`` MFLOP before its pairs and assignments, a pair
``24,576`` FLOP on a full layer and ``36,864`` on a sliding one, a head
position ``308.281`` MFLOP.  One row of 700 prompt tokens and three two-token
labels with 5 of 10 assignments held in every routed layer: positions
``703``, assignments ``703 * 4 * 5 = 14,060``, a full layer's pairs ``700 *
701 / 2 + 3 * 701 = 247,453``, a sliding layer's ``512 * 513 / 2 + 188 * 512 +
3 * 512 = 229,120``, head positions ``4``: ``703 * 863.846 + 14,060 * 18.874
+ 2 * 247,453 * 0.024576 + 3 * 229,120 * 0.036864 + 4 * 308.281 = 911.39``
GFLOP.
"""

from __future__ import annotations

from typing import Dict, Mapping, Tuple


def kinds(config: Mapping) -> Dict[str, Tuple[int, int]]:
    """``{kind: (layers of it held here, its query heads)}``."""
    layers = config["num_hidden_layers"]
    heads = config["num_attention_heads_per_layer"][:layers]
    out: Dict[str, Tuple[int, int]] = {}
    for kind, n_heads in zip(config["layer_types"], heads):
        out[kind] = (out.get(kind, (0, n_heads))[0] + 1, n_heads)
    return out


def _ffn_layers(config: Mapping) -> Tuple[int, int]:
    """``(dense layers, routed layers)`` held here."""
    layers = config["num_hidden_layers"]
    dense = sum(i < layers for i in config["mlp_only_layers"])
    return dense, layers - dense


def attention_projection_flops(config: Mapping, n_heads: int) -> float:
    """q, k, v, o and the gate of one layer with ``n_heads`` query heads."""
    d_model, d = config["hidden_size"], config["head_dim"]
    kv = config["num_key_value_heads"]
    return float(2 * (d_model * (n_heads + 2 * kv) * d + n_heads * d * d_model)
                 + 2 * d_model * n_heads)


def position_flops(config: Mapping) -> float:
    """Operations of one position through every layer, attention's score
    and value products and the routed experts' assignments left out."""
    d = config["hidden_size"]
    dense, routed = _ffn_layers(config)
    router_width = (config.get("published") or {}).get(
        "num_experts", config["num_experts"])
    a_routed_layer = (6 * d * config["shared_expert_intermediate_size"]
                      + 2 * d * router_width)
    return (sum(n * attention_projection_flops(config, heads)
                for n, heads in kinds(config).values())
            + dense * 6 * d * config["intermediate_size"]
            + routed * a_routed_layer)


def assignment_flops(config: Mapping) -> float:
    return float(6 * config["hidden_size"] * config["moe_intermediate_size"])


def pair_flops(config: Mapping, n_heads: int) -> float:
    """Operations of one (query, key) pair through one layer with
    ``n_heads`` query heads: ``q k`` and ``p v``, a multiply-add a channel
    each."""
    return float(2 * n_heads * 2 * config["head_dim"])


def head_flops(config: Mapping) -> float:
    return float(2 * config["hidden_size"] * config["vocab_size"])


def _pairs_by_kind(step: Mapping, labels: bool) -> Dict[str, int]:
    """One layer's real in-mask pairs by kind, from the span."""
    out = {"full_attention": int(step["token_pairs_full"]),
           "sliding_attention": int(step["token_pairs_window"])}
    if labels:
        out["full_attention"] += int(step["label_pairs_full"])
        out["sliding_attention"] += int(step["label_pairs_window"])
    return out


def attention_flops(config: Mapping, step: Mapping,
                    labels: bool = True) -> float:
    """Score and value products of the real in-mask pairs of every layer
    (``labels`` false: the prefill's alone, what the kernel is given)."""
    pairs = _pairs_by_kind(step, labels)
    return sum(n * pair_flops(config, heads) * pairs[kind]
               for kind, (n, heads) in kinds(config).items())


def step_flops(config: Mapping, step: Mapping) -> float:
    rows, labels = int(step["rows"]), int(step["label_positions_real"])
    positions = int(step["tokens_real"]) + rows * labels
    assignments = (int(step["assignments_held"])
                   + int(step.get("label_assignments_held", 0)))
    return (positions * position_flops(config)
            + assignments * assignment_flops(config)
            + attention_flops(config, step)
            + rows * (1 + labels) * head_flops(config))


def attention_prefill_bytes(config: Mapping, step: Mapping,
                            act_bytes: int = 2) -> float:
    """Least bytes of the prefill's attention, all layers: a real
    position's queries and result (``H_kind`` heads) and keys and values
    (``H_kv`` heads), ``act_bytes`` a channel, each read or written once."""
    d, kv = config["head_dim"], config["num_key_value_heads"]
    a_token = sum(n * (2 * heads + 2 * kv) * d * act_bytes
                  for n, heads in kinds(config).values())
    return float(int(step["tokens_real"]) * a_token)
