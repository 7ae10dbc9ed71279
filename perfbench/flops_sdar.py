"""Operations and least bytes of one step of the block-diffusion decoder
(``models/block_diffusion.py``: the prefill of each row's whole prompt
blocks, then ``gen_blocks`` blocks of denoising passes and a commit pass),
computed from the configuration file and the **real** counts of the step,
whatever implements it: padding positions, the masked part of attention's
square and how the experts are grouped do not enter.  A multiply-add is two
operations; only matrix multiplications are counted (norms, RoPE, SiLU,
softmax, the router's softmax and top-k, the sort, the sampler and the
embedding lookup are left out; under 1% at these widths).

Per position that goes through the layers (a prefilled prompt token, or one
of a block's ``B`` positions in a denoising or commit pass), per layer, with
``D`` hidden, ``H`` | ``H_kv`` query | key heads of ``d``, ``E`` experts of
width ``W`` at ``k`` a token:

* attention projections: ``2 * (D*H*d + 2*D*H_kv*d + H*d*D)``
* attention: ``2 * H * 2d`` per (query, key) pair: the block-causal pairs of
  the prefill (a query sees its own block and those before) and ``B *
  (cached + B)`` a row a pass
* experts: ``k * 6 * D * W`` (the experts a token is sent to) ``+ 2 * D * E``
  (router); no shared expert, no dense layer

and the head, ``2 * D * vocabulary``, at the positions masked on entry to a
denoising pass and nowhere else (a clean position's logits are read by
nobody, a commit pass has no head, the prefill computes none).

Hand count at the published widths (D 2,048, 32 | 4 heads of 128, 128
experts of 768 at 8 a token, vocabulary 151,936; 7 layers): projections ``2
* 18,874,368 = 37.75`` MFLOP, experts ``75.50 + 0.52``; a position costs ``7
* 113.77 = 796.39`` MFLOP, a pair ``7 * 2 * 32 * 256 = 114,688`` FLOP, a head
position ``622.33`` MFLOP.  One row of 260 prompt tokens, 4 blocks of 4
denoised in 4 passes each: positions ``260 + 4 * 5 * 4 = 340``; pairs ``16 *
65 * 66 / 2 = 34,320`` in the prefill and ``5 * 4 * (264 + 268 + 272 + 276)
= 21,600`` in the passes; head positions ``4 * (4 + 3 + 2 + 1) = 40``: ``340
* 796.39 + 55,920 * 0.114688 + 40 * 622.33 = 302.08`` GFLOP.
"""

from __future__ import annotations

from typing import Dict, Mapping


def _attention_params(config: Mapping) -> int:
    d_model, d = config["hidden_size"], config["head_dim"]
    heads, kv_heads = (config["num_attention_heads"],
                       config["num_key_value_heads"])
    return d_model * heads * d + 2 * d_model * kv_heads * d + heads * d * d_model


def position_flops(config: Mapping) -> float:
    """Matmul operations of one position through every layer, attention's
    score and value products left out."""
    d_model = config["hidden_size"]
    experts = (config["num_experts_per_tok"] * 6 * d_model
               * config["moe_intermediate_size"]
               + 2 * d_model * config["num_experts"])
    return float(config["num_hidden_layers"]
                 * (2 * _attention_params(config) + experts))


def pair_flops(config: Mapping) -> float:
    """Operations of one (query, key) pair through every layer's attention:
    the score and the weighted value, each over ``head_dim``."""
    return float(config["num_hidden_layers"] * 2
                 * config["num_attention_heads"] * 2 * config["head_dim"])


def head_flops(config: Mapping) -> float:
    return float(2 * config["hidden_size"] * config["vocab_size"])


def step_counts(step: Mapping) -> Dict[str, int]:
    """Positions, pairs and head positions of one step from what the
    program recorded on its ``compute`` span: ``rows``, ``tokens_prefilled``
    (the prompts' whole blocks), ``token_pairs`` (their block-causal pairs),
    ``block_length``, ``denoise_passes`` and ``commit_passes`` (of the
    batch), ``pass_pairs`` (``B * (cached + B)`` a row a pass, summed) and
    ``positions_masked`` (masked on entry, summed over the passes)."""
    passes = int(step["denoise_passes"]) + int(step["commit_passes"])
    return {
        "positions": int(step["tokens_prefilled"])
        + int(step["rows"]) * int(step["block_length"]) * passes,
        "pairs": int(step["token_pairs"]) + int(step["pass_pairs"]),
        "head_positions": int(step["positions_masked"]),
    }


def step_flops(config: Mapping, step: Mapping) -> float:
    counts = step_counts(step)
    return (counts["positions"] * position_flops(config)
            + counts["pairs"] * pair_flops(config)
            + counts["head_positions"] * head_flops(config))


def step_bytes(config: Mapping, step: Mapping, weight_bytes: int = 2,
               act_bytes: int = 2) -> float:
    """Bytes one step must move at the least: every layer's weights once
    for the prefill and once more for every pass (at a thousand assignments
    a pass every expert is read), the head once a denoising pass, the cached
    keys and values each pass reads, the embedding rows, and each layer's
    input and output activations once."""
    d_model, layers = config["hidden_size"], config["num_hidden_layers"]
    layer = (_attention_params(config) + d_model * config["num_experts"]
             + 3 * d_model * config["moe_intermediate_size"]
             * config["num_experts"])
    passes = int(step["denoise_passes"]) + int(step["commit_passes"])
    head = d_model * config["vocab_size"]
    weights = (1 + passes) * layers * layer + int(step["denoise_passes"]) * head
    counts = step_counts(step)
    kv_width = 2 * config["num_key_value_heads"] * config["head_dim"]
    # a pass reads the keys and values of its pairs' cached side once
    cached = (int(step["pass_pairs"]) // int(step["block_length"])) * kv_width
    activations = (layers * 2 + 2) * counts["positions"] * d_model
    return float(weights * weight_bytes
                 + (cached * layers + activations) * act_bytes)


def block_causal_attention_flops(config: Mapping, step: Mapping) -> float:
    """Operations of the prefill's attention kernel in one step, all its
    layers: the real block-causal pairs."""
    return float(step["token_pairs"]) * pair_flops(config)


def block_causal_attention_bytes(config: Mapping, step: Mapping,
                                 act_bytes: int = 2) -> float:
    """Least bytes of the same: queries and outputs of every head, keys and
    values of every key head, of the prefilled positions, once a layer."""
    width = 2 * (config["num_attention_heads"]
                 + config["num_key_value_heads"]) * config["head_dim"]
    return float(config["num_hidden_layers"] * int(step["tokens_prefilled"])
                 * width * act_bytes)
