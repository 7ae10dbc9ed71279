"""Llama-3-style decoder LM with tensor-parallel sharding and KV cache.

The reference's "large model" path is a prompt to a remote Ollama server
(``scripts/sentiment_classifier.py:32-36,85-100``).  Here the LM is a
first-class on-device family: pre-norm GQA decoder blocks (RMSNorm, RoPE,
SwiGLU), weights laid out for ``tp`` sharding (``parallel/sharding.py``),
and an explicit KV cache whose head axis shards with the attention heads.

Zero-shot sentiment reuses the reference's exact prompt (PROMPT_TEMPLATE,
lyrics truncated to 4,000 chars) but replaces free-text generation +
normalization with *constrained label scoring*: one shared prompt prefill,
then teacher-forced log-likelihood of each candidate label continuation —
three tiny decode passes instead of an unbounded generation loop, which is
both deterministic and TPU-shaped (static shapes, no dynamic stopping).
A label's first token is scored by the prompt's last logits and token
``i > 0`` by the continuation's logits at position ``i - 1``, so a
continuation runs the label's tokens but the last, whose forward pass
nothing reads: ``max(label_lens) - 1`` positions (one for ``word + EOS``,
none where every label is one token), not a table padded to a fixed width.
A ``generate`` + ``normalise_label`` path (the reference's semantics,
empty-output crash fixed) is kept for API parity.
"""

from __future__ import annotations

import dataclasses
import os
from functools import partial
from typing import List, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np
from flax import linen as nn

from music_analyst_tpu.models.backend import ClassifierBackend, preset_files
from music_analyst_tpu.models.compact_stream import (
    feed_forward_positions,
    prefill_stream,
    runs_compact,
    step_capacity,
    stream_slots,
)
from music_analyst_tpu.models.layers import (
    MultiHeadAttention,
    RMSNorm,
    SwiGLU,
    causal_mask,
    padding_mask,
)
from music_analyst_tpu.models.tokenization import (
    ByteTokenizer,
    resolve_llama_tokenizer,
)
from music_analyst_tpu.ops.kv_cache import BlockCausalPrefill, KVCache
from music_analyst_tpu.profiling.compile import (
    note_traced_path,
    profiled_jit,
)
from music_analyst_tpu.utils.labels import SUPPORTED_LABELS, normalise_label

# Reference prompt, scripts/sentiment_classifier.py:32-36 (behavioral
# contract: same instruction, lyrics truncated to 4,000 characters).
PROMPT_TEMPLATE = (
    "You are an expert music analyst. Classify the overall sentiment of the "
    "following song lyrics as one of the following labels: Positive, "
    "Neutral, or Negative. Respond using only the label name with no "
    "explanations.\n\nLyrics:\n{lyrics}\n"
)
LYRICS_TRUNCATION = 4000


@dataclasses.dataclass(frozen=True)
class AttentionKind:
    """What a grouped-query layer of one kind (``layer_types``' name for
    it) has of its own where a model's attention layers differ: query
    heads, a sliding window (0 = none), and its RoPE: ``rope_theta``, the
    rotary part of the head (0 = all of it), YaRN's parameters (the
    group's items, hashable; ``None`` = plain RoPE)."""

    n_heads: int
    window: int = 0
    rope_theta: float = 10_000.0
    rotary_dim: int = 0
    yarn: Optional[tuple] = None


# ``layer_types`` names of grouped-query layers whose widths are answered by
# kind (``LlamaConfig.attention_kind``), beside "mamba", "mamba1" and
# "attention".
ATTENTION_KINDS = ("full_attention", "sliding_attention")


@dataclasses.dataclass(frozen=True)
class LlamaConfig:
    vocab_size: int = 128_256
    dim: int = 4096
    n_layers: int = 32
    n_heads: int = 32
    n_kv_heads: int = 8
    hidden_dim: int = 14_336
    rope_theta: float = 500_000.0
    max_seq_len: int = 8192
    dtype: str = "bfloat16"
    # > 0 replaces the dense SwiGLU with a routed mixture of experts whose
    # expert axis shards over the ``ep`` mesh axis (models/moe.py).
    n_experts: int = 0
    moe_top_k: int = 2
    # "sparse" = capacity-bounded token-choice dispatch (k*cf FLOPs/token);
    # "dense" = all-experts oracle (E× FLOPs).  See models/moe.py.
    moe_dispatch: str = "sparse"
    moe_capacity_factor: float = 1.25
    # "flash" uses the Pallas blocked-attention kernel on the no-cache
    # (prefill/training) path; seq len must divide its block size.
    attn_impl: str = "dense"
    # "int8" routes attention/MLP projections through the dynamic int8
    # matmul (ops/quant.py) — inference-only; see DistilBertConfig.quant.
    quant: str = "none"
    # "int8"/"int4" stores projection + lm_head kernels weight-quantized
    # (QuantizedParam leaves; ops/quant.py): the bf16 tree never exists,
    # which is what lets the 8B config fit one 16 GB chip.  Mutually
    # exclusive with the dynamic `quant` path (it subsumes the matmul).
    weight_quant: str = "none"
    # --- layer kinds beyond Llama's own, set from a published config file
    # (``from_hf_config``).  "mla" = multi-head latent attention with a
    # latent cache (models/mla.py); the four widths below are its.
    attention: str = "gqa"
    kv_lora_rank: int = 0
    qk_nope_head_dim: int = 0
    qk_rope_head_dim: int = 0
    v_head_dim: int = 0
    rope_interleave: bool = False
    rms_norm_eps: float = 1e-5
    # "sigmoid_noaux" = sigmoid scores, bias-corrected choice, shared
    # experts; "softmax_topk" = softmax over all experts, the top k
    # renormalised, nothing else: both are models/moe.RoutedMoE (experts of
    # width ``moe_hidden_dim``, no dropped token) with that router;
    # "softmax_capacity" = MoESwiGLU as above.
    moe_router: str = "softmax_capacity"
    moe_hidden_dim: int = 0
    n_shared_experts: int = 0
    routed_scaling_factor: float = 1.0
    norm_topk_prob: bool = True
    # Leading layers that keep the dense SwiGLU (``hidden_dim``) in a model
    # whose other layers are routed.
    first_k_dense_replace: int = 0
    # What float parameters are created and held in.  "bfloat16" also
    # makes the random init one jitted program a layer kind, on the device
    # (a float32 tree of a 4.4 B-parameter model is 17.7 GB).
    param_dtype: str = "float32"
    # Offline tokenizer: "byte", or "hash_word" (word ids hashed over the
    # whole vocabulary) for a vocabulary the bytes would barely touch.
    tokenizer: str = "byte"
    # The narrowest width a batch of prompts is trimmed to
    # (``_trim_prompt_pad``).  Every width is a compiled program: a preset
    # whose scoring step compiles for most of a minute sets it to its
    # prompt cap and runs one shape.
    prompt_width_floor: int = 64
    # Grouped-query attention beyond Llama's own: a head width that is not
    # ``dim / n_heads`` (0 = that), RMSNorm over it on queries and keys.
    head_dim: int = 0
    qk_norm: bool = False
    # How new tokens come: "autoregressive" (one a row a step) or
    # "block_diffusion": blocks of ``block_length`` positions, attention
    # bidirectional inside a block and causal across blocks, a block
    # denoised from ``mask_token_id`` by passes that unmask every position
    # whose confidence exceeds ``confidence_threshold`` and at least
    # ``block_length / denoising_steps`` of them
    # (``diffusion_prefill_program`` / ``diffusion_denoise_program``).
    generation: str = "autoregressive"
    block_length: int = 0
    denoising_steps: int = 0
    confidence_threshold: float = 1.0
    mask_token_id: int = -1
    # Two kinds of mixer in one model: with ``mixer_period`` p > 0 layer
    # ``i`` runs ``attention`` where ``(i + 1) % p == 0`` and Kimi Delta
    # Attention (models/kda.py: a recurrent state a row, not a cache)
    # everywhere else; 0 = ``attention`` in every layer.
    mixer_period: int = 0
    # The published indices of the layers kept, where a cut keeps others
    # than the first ``n_layers`` (a leading dense layer and a whole period
    # from further on): the period is counted on these.  ``None`` = 0, 1, ..
    layer_ids: Optional[tuple] = None
    kda_head_dim: int = 0
    kda_conv_kernel: int = 4
    kda_lower_bound: float = -5.0
    # One sigmoid gate a head on latent attention's output (models/mla.py).
    mla_output_gate: bool = False
    # Group-limited expert choice of the sigmoid router (models/moe.py).
    n_group: int = 1
    topk_group: int = 1
    # ``(first, count)``: the experts of each routed layer THIS chip holds
    # of the layer's ``n_experts`` (the router's width); ``None`` = all.
    experts_held: Optional[tuple] = None
    # The mixer a layer, as a published list (``"mamba"`` = a Mamba-2
    # state-space layer, models/mamba2.py: a recurrent state a row;
    # ``"mamba1"`` = a Mamba-1 selective-scan layer, models/mamba1.py: a
    # state a channel; ``"attention"`` = the ``attention`` kind;
    # ``"full_attention"`` / ``"sliding_attention"`` = grouped-query layers
    # whose heads, window and RoPE ``attention_kinds`` states by that name);
    # ``None`` = by ``mixer_period``.  Mamba-2's widths are the heads and
    # their width; Mamba-1's the inner width and the step's rank; both have
    # a state size and a convolution.
    layer_types: Optional[tuple] = None
    mamba_n_heads: int = 0
    mamba_head_dim: int = 0
    mamba_d_state: int = 0
    mamba_conv_kernel: int = 4
    mamba_inner_dim: int = 0
    mamba_dt_rank: int = 0
    # Grouped-query attention without rotary positions, and with a
    # published softmax scale (0 = ``head_dim ** -0.5``).
    use_rope: bool = True
    attention_scale: float = 0.0
    # Scalars on the embedding, on both residual branches of every layer
    # and under the logits (``logits / logits_scaling``); 1 = none.
    embedding_multiplier: float = 1.0
    residual_multiplier: float = 1.0
    logits_scaling: float = 1.0
    # The head is the embedding's transpose: no ``lm_head`` parameter.
    tie_embeddings: bool = False
    # Grouped-query layers of different kinds in one model:
    # ``((name, AttentionKind), ..)`` for the names ``layer_types`` uses
    # (:meth:`attention_kind` answers a layer's; a layer whose name is not
    # here has ``n_heads``, ``rope_theta``, no window, the whole head
    # rotated).
    attention_kinds: Optional[tuple] = None
    # One gate a query head on grouped-query attention's output, from the
    # layer's normed input (``models/layers.MultiHeadAttention``):
    # "softplus", "sigmoid" or "none".
    gqa_output_gate: str = "none"
    # A gate on the shared expert's output (a family whose configuration
    # names ``shared_expert_intermediate_size`` has one): not implemented;
    # a configuration that states it is refused.
    shared_expert_gate: bool = False

    def __post_init__(self):
        if self.attention not in ("gqa", "mla"):
            raise ValueError(f"unknown attention kind {self.attention!r}")
        if self.moe_router not in ("softmax_capacity", "sigmoid_noaux",
                                   "softmax_topk"):
            raise ValueError(f"unknown moe_router {self.moe_router!r}")
        if (self.attention == "mla" and self.n_experts > 0
                and not self.routed_experts):
            raise ValueError(
                "latent attention's expert layers are RoutedMoE: moe_router "
                "must be sigmoid_noaux or softmax_topk")
        if self.mixer_period and (self.attention != "mla"
                                  or self.kda_head_dim < 1
                                  or self.layer_types is not None):
            raise ValueError(
                "mixer_period puts KDA layers between latent-attention "
                "ones: attention must be mla, kda_head_dim set, and the "
                "layers' kinds not given as a list besides (layer_types)")
        for name in ("experts_held", "layer_ids"):  # lists in a preset file
            if getattr(self, name) is not None:
                object.__setattr__(
                    self, name, tuple(int(n) for n in getattr(self, name)))
        if self.layer_types is not None:
            object.__setattr__(self, "layer_types", tuple(self.layer_types))
            known = ("mamba", "mamba1", "attention") + ATTENTION_KINDS
            if (len(self.layer_types) != self.n_layers
                    or set(self.layer_types) - set(known)):
                raise ValueError(
                    "layer_types names n_layers layers, each one of "
                    + ", ".join(known))
            if self.attention != "gqa":
                raise ValueError(
                    "layer_types lists grouped-query layers and what stands "
                    "between them: attention must be gqa")
            if "mamba" in self.layer_types and min(
                    self.mamba_n_heads, self.mamba_head_dim,
                    self.mamba_d_state) < 1:
                raise ValueError(
                    "layer_types puts Mamba-2 layers between grouped-query "
                    "ones: the mamba widths must be set")
            if "mamba1" in self.layer_types and min(
                    self.mamba_inner_dim, self.mamba_dt_rank,
                    self.mamba_d_state) < 1:
                raise ValueError(
                    "layer_types puts Mamba-1 layers between grouped-query "
                    "ones: mamba_inner_dim, mamba_dt_rank and mamba_d_state "
                    "must be set")
        if self.attention_kinds is not None:
            kinds = tuple((str(name), kind)
                          for name, kind in self.attention_kinds)
            object.__setattr__(self, "attention_kinds", kinds)
            listed = set(self.layer_types or ())
            for name, kind in kinds:
                if name not in listed or not isinstance(kind, AttentionKind):
                    raise ValueError(
                        f"attention_kinds states {name!r}: an AttentionKind "
                        "a name that layer_types uses")
                if kind.n_heads % self.n_kv_heads or kind.window < 0:
                    raise ValueError(
                        f"attention kind {name!r}: {kind.n_heads} query "
                        f"heads over {self.n_kv_heads} key/value heads, "
                        f"window {kind.window}")
        if self.gqa_output_gate not in ("none", "softplus", "sigmoid"):
            raise ValueError(
                f"unknown gqa_output_gate {self.gqa_output_gate!r}")
        if self.shared_expert_gate:
            raise ValueError(
                "shared_expert_gate: a gate on the shared expert's output "
                "is not implemented (models/moe.RoutedMoE adds the shared "
                "experts ungated)")
        if self.tie_embeddings and self.weight_quant != "none":
            raise ValueError(
                "weight_quant stores a head of its own: no tied embeddings")
        if self.layer_ids is not None and len(self.layer_ids) != self.n_layers:
            raise ValueError("layer_ids does not name n_layers layers")
        if self.generation not in ("autoregressive", "block_diffusion"):
            raise ValueError(f"unknown generation kind {self.generation!r}")
        if self.block_diffusion and (
                self.attention != "gqa" or self.block_length < 1
                or self.denoising_steps < 1
                or self.block_length % self.denoising_steps
                or not 0 <= self.mask_token_id < self.vocab_size):
            raise ValueError(
                "block_diffusion needs grouped-query attention, a "
                "block_length that denoising_steps divides and a "
                "mask_token_id inside the vocabulary")
        if self.weight_quant not in ("none", "int8", "int4"):
            raise ValueError(
                f"weight_quant must be none/int8/int4, got "
                f"{self.weight_quant!r}"
            )
        if self.weight_quant != "none" and self.quant != "none":
            raise ValueError(
                "weight_quant and dynamic quant are mutually exclusive — "
                "the stored-weight path already runs the int8 MXU matmul"
            )
        if self.weight_quant != "none" and self.n_experts > 0:
            raise ValueError(
                "weight_quant does not cover the MoE expert stacks yet; "
                "use the dynamic quant='int8' path for MoE configs"
            )

    @property
    def latent_cache(self) -> bool:
        return self.attention == "mla"

    @property
    def block_diffusion(self) -> bool:
        return self.generation == "block_diffusion"

    def mixer(self, index: int) -> str:
        """Layer ``index``'s mixer: ``"kda"``, ``"mamba"``, ``"mamba1"``, or
        the ``attention`` kind.  The one place that answers, from the list
        (``layer_types``) or the period (``mixer_period``)."""
        if self.layer_types is not None:
            # "attention" and the kinds of ``ATTENTION_KINDS`` are all the
            # ``attention`` kind's mixer; what differs between them is
            # :meth:`attention_kind`'s to say
            kind = self.layer_types[index]
            return kind if kind in ("mamba", "mamba1") else self.attention
        if self.layer_ids is not None:
            index = self.layer_ids[index]
        if self.mixer_period and (index + 1) % self.mixer_period:
            return "kda"
        return self.attention

    def layer_kind(self, index: int) -> str:
        """Layer ``index``'s name in ``layer_types`` (its mixer's where the
        layers are not listed)."""
        return (self.layer_types[index] if self.layer_types is not None
                else self.mixer(index))

    def attention_kind(self, index: int) -> AttentionKind:
        """The widths grouped-query layer ``index`` has of its own: its
        kind's (``attention_kinds`` by the layer's name in ``layer_types``)
        or, for a model whose attention layers are all alike, the
        configuration's one ``n_heads`` and ``rope_theta``, no window, the
        whole head rotated.  The one place that answers."""
        if self.attention_kinds is not None:
            for name, kind in self.attention_kinds:
                if name == self.layer_types[index]:
                    return kind
        return AttentionKind(self.n_heads, rope_theta=self.rope_theta)

    @property
    def window_layers(self) -> int:
        """Grouped-query layers with a sliding window."""
        return sum(self.mixer(i) == "gqa" and self.attention_kind(i).window > 0
                   for i in range(self.n_layers))

    def _layers_of(self, mixer: str) -> int:
        return sum(self.mixer(i) == mixer for i in range(self.n_layers))

    @property
    def kda_layers(self) -> int:
        return self._layers_of("kda")

    @property
    def ssm_layers(self) -> int:
        return self._layers_of("mamba")

    @property
    def selective_layers(self) -> int:
        """Mamba-1 layers."""
        return self._layers_of("mamba1")

    @property
    def recurrent_state(self) -> bool:
        """Whether some layer carries a recurrent state a row
        (``models/kda.RecurrentState``, ``models/mamba2.SSMState`` or
        ``models/mamba1.SelectiveState``)."""
        return self.kda_layers + self.ssm_layers + self.selective_layers > 0

    @property
    def mixed_layers(self) -> bool:
        """Whether the layers differ in kind: mixers on a recurrent state
        between attention layers, or attention layers listed by kind.  A
        scoring step of such a model hands back what its prefill left in
        the caches of ``probe_rows`` (``score_labels_program``)."""
        return self.recurrent_state or self.layer_types is not None

    @property
    def compact_stream(self) -> bool:
        """Whether every block takes the compact token stream
        (``models/compact_stream.runs_compact``): the latent blocks, the
        blocks of a model whose layers are listed by kind (grouped-query
        blocks between state-space layers, full and sliding-window
        grouped-query blocks), and the grouped-query blocks of a
        block-diffusion prefill (its
        passes declare no lengths)."""
        return (self.attention == "mla" or self.layer_types is not None
                or self.block_diffusion)

    @property
    def experts_held_count(self) -> int:
        return (self.experts_held[1] if self.experts_held is not None
                else self.n_experts)

    @property
    def attn_head_dim(self) -> int:
        return self.head_dim or self.dim // self.n_heads

    @property
    def offline_vocab_size(self) -> int:
        """The ids an offline tokenizer may hash words over: a
        block-diffusion model's mask token (high in the vocabulary, with
        the other special tokens) and what lies behind it stay out."""
        return (min(self.vocab_size, self.mask_token_id)
                if self.block_diffusion else self.vocab_size)

    @property
    def routed_experts(self) -> bool:
        """Whether the expert layers are ``models/moe.RoutedMoE``."""
        return self.moe_router in ("sigmoid_noaux", "softmax_topk")

    def routed_layer(self, index: int) -> bool:
        """Whether layer ``index`` is a routed (expert) layer."""
        return self.n_experts > 0 and index >= self.first_k_dense_replace

    @classmethod
    def from_hf_config(cls, hf: dict, **overrides) -> "LlamaConfig":
        """The decoder a published ``config.json`` of ``model_type:
        deepseek_v3``, ``sdar_moe``, ``ling_hybrid``, ``granitemoehybrid``,
        ``laguna`` or ``jamba`` describes, key by key.  What this code cannot
        run is refused by name, not approximated."""
        if hf.get("model_type") == "sdar_moe":
            return cls._from_sdar_moe(hf, overrides)
        if hf.get("model_type") == "laguna":
            return cls._from_laguna(hf, overrides)
        if hf.get("model_type") == "granitemoehybrid":
            return cls._from_granitemoehybrid(hf, overrides)
        if hf.get("model_type") == "jamba":
            return cls._from_jamba(hf, overrides)
        if hf.get("model_type") == "ling_hybrid":
            return cls._from_ling_hybrid(hf, overrides)
        unsupported = {
            "model_type": hf.get("model_type") != "deepseek_v3",
            "q_lora_rank": hf.get("q_lora_rank") is not None,
            "scoring_func": hf.get("scoring_func") != "sigmoid",
            "topk_method": hf.get("topk_method") != "noaux_tc",
            "rope_scaling": hf.get("rope_scaling") is not None,
            "attention_bias": bool(hf.get("attention_bias", False)),
            "tie_word_embeddings": bool(hf.get("tie_word_embeddings", False)),
            "hidden_act": hf.get("hidden_act", "silu") != "silu",
            "moe_layer_freq": hf.get("moe_layer_freq", 1) != 1,
        }
        bad = sorted(k for k, v in unsupported.items() if v)
        if bad:
            raise ValueError(
                "this decoder does not implement the configuration's "
                + ", ".join(f"{k}={hf.get(k)!r}" for k in bad)
            )
        if hf["qk_head_dim"] != hf["qk_nope_head_dim"] + hf["qk_rope_head_dim"]:
            raise ValueError("qk_head_dim != qk_nope_head_dim + qk_rope_head_dim")
        fields = dict(
            vocab_size=hf["vocab_size"], dim=hf["hidden_size"],
            n_layers=hf["num_hidden_layers"],
            n_heads=hf["num_attention_heads"],
            n_kv_heads=hf["num_key_value_heads"],
            hidden_dim=hf["intermediate_size"],
            rope_theta=float(hf["rope_theta"]),
            max_seq_len=hf["max_position_embeddings"],
            attention="mla", kv_lora_rank=hf["kv_lora_rank"],
            qk_nope_head_dim=hf["qk_nope_head_dim"],
            qk_rope_head_dim=hf["qk_rope_head_dim"],
            v_head_dim=hf["v_head_dim"],
            rope_interleave=bool(hf["rope_interleave"]),
            rms_norm_eps=float(hf["rms_norm_eps"]),
            moe_router="sigmoid_noaux", n_experts=hf["n_routed_experts"],
            moe_top_k=hf["num_experts_per_tok"],
            moe_hidden_dim=hf["moe_intermediate_size"],
            n_shared_experts=hf["n_shared_experts"],
            routed_scaling_factor=float(hf["routed_scaling_factor"]),
            norm_topk_prob=bool(hf["norm_topk_prob"]),
            first_k_dense_replace=hf["first_k_dense_replace"],
            n_group=hf.get("n_group", 1), topk_group=hf.get("topk_group", 1),
        )
        fields.update(overrides)
        return cls(**fields)

    @classmethod
    def _from_ling_hybrid(cls, hf: dict, overrides: dict) -> "LlamaConfig":
        """``model_type: ling_hybrid`` (the name is the preset's own: the
        catalog's row of Ling-3.0-flash-VL's language model has none): a
        period of ``layer_group_size`` layers is KDA mixers then one latent
        attention with a head-wise output gate; leading dense layers, then
        ``num_experts`` sigmoid-routed experts chosen inside the best
        ``topk_group`` of ``n_group`` groups, one shared expert.  How many
        of the experts this chip holds is not the source's to say: the
        caller's ``overrides`` (``experts_held``) state it."""
        layers = hf["num_hidden_layers"]
        kept = overrides.get("layer_ids") or range(layers)

        def nonzero(key):
            limits = hf.get(key, ())
            return any(limits[i] for i in kept if i < len(limits))

        heads = hf["num_attention_heads"]
        unsupported = {
            "expert_swiglu_limit_list": nonzero("expert_swiglu_limit_list"),
            "share_expert_swiglu_limit_list": nonzero(
                "share_expert_swiglu_limit_list"),
            "use_nGPT": bool(hf.get("use_nGPT", False)),
            "value_norm": bool(hf.get("value_norm", False)),
            "up_proj_norm": bool(hf.get("up_proj_norm", False)),
            "scale_router_input": bool(hf.get("scale_router_input", False)),
            "use_kda_lora": bool(hf.get("use_kda_lora", False)),
            "no_kda_lora": not hf.get("no_kda_lora", True),
            "mtp_use_kda": bool(hf.get("mtp_use_kda", False)),
            "use_mla_nope": bool(hf.get("use_mla_nope", False)),
            "q_lora_rank": hf.get("q_lora_rank") is not None,
            "score_function": hf.get("score_function") != "sigmoid",
            "moe_router_enable_expert_bias": not hf.get(
                "moe_router_enable_expert_bias", False),
            "gated_attention_proj_granularity_type": hf.get(
                "gated_attention_proj_granularity_type") != "head_wise",
            "linear_silu": not hf.get("linear_silu", False),
            "kda_safe_gate": not hf.get("kda_safe_gate", False),
            "group_norm_size": hf.get("group_norm_size", 1) != 1,
            "num_kv_heads_for_linear_attn": hf.get(
                "num_kv_heads_for_linear_attn", 0) not in (0, heads),
            "use_qk_norm": not hf.get("use_qk_norm", False),
            "rotary_dim": hf.get("rotary_dim") != hf["qk_rope_head_dim"],
            "rope_scaling": hf.get("rope_scaling") is not None,
            "tie_word_embeddings": bool(hf.get("tie_word_embeddings", False)),
        }
        bad = sorted(k for k, v in unsupported.items() if v)
        if bad:
            raise ValueError(
                "this decoder does not implement the configuration's "
                + ", ".join(f"{k}={hf.get(k)!r}" for k in bad)
            )
        width = hf["moe_intermediate_size"]
        shared = hf["moe_shared_expert_intermediate_size"]
        if shared % width:
            raise ValueError(
                "moe_shared_expert_intermediate_size is not whole experts "
                "of moe_intermediate_size")
        fields = dict(
            vocab_size=hf["vocab_size"], dim=hf["hidden_size"],
            n_layers=layers, n_heads=heads,
            n_kv_heads=hf["num_key_value_heads"],
            hidden_dim=hf["intermediate_size"],
            rope_theta=float(hf["rope_theta"]),
            max_seq_len=hf["max_position_embeddings"],
            attention="mla", kv_lora_rank=hf["kv_lora_rank"],
            qk_nope_head_dim=hf["qk_nope_head_dim"],
            qk_rope_head_dim=hf["qk_rope_head_dim"],
            v_head_dim=hf["v_head_dim"],
            # the pairing of the existing latent path (``assumed``)
            rope_interleave=True, mla_output_gate=True,
            rms_norm_eps=float(hf["rms_norm_eps"]),
            mixer_period=hf["layer_group_size"],
            kda_head_dim=hf["head_dim"],
            kda_conv_kernel=hf["short_conv_kernel_size"],
            kda_lower_bound=float(hf["kda_lower_bound"]),
            moe_router="sigmoid_noaux", n_experts=hf["num_experts"],
            moe_top_k=hf["num_experts_per_tok"], moe_hidden_dim=width,
            n_shared_experts=shared // width,
            routed_scaling_factor=float(hf["routed_scaling_factor"]),
            norm_topk_prob=bool(hf["norm_topk_prob"]),
            first_k_dense_replace=hf["first_k_dense_replace"],
            n_group=hf["n_group"], topk_group=hf["topk_group"],
        )
        fields.update(overrides)
        return cls(**fields)

    @classmethod
    def _from_granitemoehybrid(cls, hf: dict,
                               overrides: dict) -> "LlamaConfig":
        """``model_type: granitemoehybrid``: ``layer_types`` says which
        layers are Mamba-2 and which grouped-query attention without
        positions (``position_embedding_type: "nope"``) under the published
        softmax scale ``attention_multiplier``; every layer's feed-forward
        half is ``num_local_experts`` softmax-routed experts of width
        ``intermediate_size`` (the softmax over the chosen
        ``num_experts_per_tok`` logits) plus one shared SwiGLU of
        ``shared_intermediate_size``; the embedding is multiplied by
        ``embedding_multiplier``, both residual branches by
        ``residual_multiplier``, the logits divided by ``logits_scaling``,
        and the head is the embedding's transpose.  How many of the experts
        this chip holds is not the source's to say: the caller's
        ``overrides`` (``experts_held``) state it."""
        width = hf["intermediate_size"]
        shared = hf["shared_intermediate_size"]
        unsupported = {
            "position_embedding_type": hf.get(
                "position_embedding_type") != "nope",
            "attention_bias": bool(hf.get("attention_bias", False)),
            "mamba_proj_bias": bool(hf.get("mamba_proj_bias", False)),
            "mamba_conv_bias": not hf.get("mamba_conv_bias", True),
            "mamba_n_groups": hf.get("mamba_n_groups", 1) != 1,
            "rope_scaling": hf.get("rope_scaling") is not None,
            "hidden_act": hf.get("hidden_act", "silu") != "silu",
            "normalization_function": hf.get(
                "normalization_function", "rmsnorm") != "rmsnorm",
            "mamba_expand": (hf["mamba_expand"] * hf["hidden_size"]
                             != hf["mamba_n_heads"] * hf["mamba_d_head"]),
            "shared_intermediate_size": bool(shared % width),
            "num_local_experts": hf.get("num_local_experts", 0) < 1,
        }
        bad = sorted(k for k, v in unsupported.items() if v)
        if bad:
            raise ValueError(
                "this decoder does not implement the configuration's "
                + ", ".join(f"{k}={hf.get(k)!r}" for k in bad)
            )
        fields = dict(
            vocab_size=hf["vocab_size"], dim=hf["hidden_size"],
            n_layers=hf["num_hidden_layers"],
            n_heads=hf["num_attention_heads"],
            n_kv_heads=hf["num_key_value_heads"],
            # no dense layer: the width is one expert's (the catalog's note)
            hidden_dim=width, moe_hidden_dim=width,
            rope_theta=float(hf.get("rope_theta", 10_000.0)),
            max_seq_len=hf["max_position_embeddings"],
            rms_norm_eps=float(hf["rms_norm_eps"]),
            layer_types=tuple(hf["layer_types"]),
            mamba_n_heads=hf["mamba_n_heads"],
            mamba_head_dim=hf["mamba_d_head"],
            mamba_d_state=hf["mamba_d_state"],
            mamba_conv_kernel=hf["mamba_d_conv"],
            use_rope=False,
            attention_scale=float(hf["attention_multiplier"]),
            embedding_multiplier=float(hf["embedding_multiplier"]),
            residual_multiplier=float(hf["residual_multiplier"]),
            logits_scaling=float(hf["logits_scaling"]),
            tie_embeddings=bool(hf.get("tie_word_embeddings", False)),
            # softmax over the chosen logits = softmax over all, the chosen
            # renormalised (``route_softmax_topk`` with ``norm_topk_prob``)
            moe_router="softmax_topk", norm_topk_prob=True,
            n_experts=hf["num_local_experts"],
            moe_top_k=hf["num_experts_per_tok"],
            n_shared_experts=shared // width,
        )
        fields.update(overrides)
        return cls(**fields)

    @classmethod
    def _from_jamba(cls, hf: dict, overrides: dict) -> "LlamaConfig":
        """``model_type: jamba``: layer ``i`` is grouped-query attention
        without positions where ``i % attn_layer_period ==
        attn_layer_offset`` (``JambaConfig.layers_block_type``), else
        Mamba-1 (inner width ``mamba_expand * hidden_size``, state
        ``mamba_d_state``, step rank ``mamba_dt_rank``, ``"auto"`` =
        ``ceil(hidden_size / 16)``); every layer's feed-forward half is
        the dense SwiGLU of ``intermediate_size`` (``num_experts`` 1: the
        expert layers of the routed variant are not this model's); the
        head is the embedding's transpose where ``tie_word_embeddings``."""
        rank = hf.get("mamba_dt_rank", "auto")
        if rank == "auto":
            rank = -(-hf["hidden_size"] // 16)
        period = hf.get("attn_layer_period", 8)
        offset = hf.get("attn_layer_offset", 4)
        unsupported = {
            "num_experts": hf.get("num_experts", 1) > 1,
            "mamba_proj_bias": bool(hf.get("mamba_proj_bias", False)),
            "mamba_conv_bias": not hf.get("mamba_conv_bias", True),
            "hidden_act": hf.get("hidden_act", "silu") != "silu",
            "sliding_window": hf.get("sliding_window") is not None,
            "attn_layer_offset": not 0 <= offset < period,
        }
        bad = sorted(k for k, v in unsupported.items() if v)
        if bad:
            raise ValueError(
                "this decoder does not implement the configuration's "
                + ", ".join(f"{k}={hf.get(k)!r}" for k in bad)
            )
        layers = hf["num_hidden_layers"]
        fields = dict(
            vocab_size=hf["vocab_size"], dim=hf["hidden_size"],
            n_layers=layers, n_heads=hf["num_attention_heads"],
            n_kv_heads=hf["num_key_value_heads"],
            hidden_dim=hf["intermediate_size"],
            max_seq_len=hf["max_position_embeddings"],
            rms_norm_eps=float(hf["rms_norm_eps"]),
            layer_types=tuple(
                "attention" if i % period == offset else "mamba1"
                for i in range(layers)),
            mamba_inner_dim=hf["mamba_expand"] * hf["hidden_size"],
            mamba_dt_rank=int(rank),
            mamba_d_state=hf["mamba_d_state"],
            mamba_conv_kernel=hf["mamba_d_conv"],
            use_rope=False,
            tie_embeddings=bool(hf.get("tie_word_embeddings", False)),
        )
        fields.update(overrides)
        return cls(**fields)

    @classmethod
    def _from_laguna(cls, hf: dict, overrides: dict) -> "LlamaConfig":
        """``model_type: laguna``: every layer grouped-query attention over
        ``num_key_value_heads`` heads of ``head_dim``, of the kind
        ``layer_types`` names: a kind has its own count of query heads
        (``num_attention_heads_per_layer``) and its own RoPE
        (``rope_parameters[kind]``: ``default`` or ``yarn``, on
        ``partial_rotary_factor`` of the head), and ``sliding_attention``
        sees the last ``sliding_window`` keys; one gate a head on the
        attention's output (``gating: "per-head"``); the leading
        ``mlp_only_layers`` keep a dense SwiGLU of ``intermediate_size``,
        the others route ``num_experts_per_tok`` of ``num_experts`` experts
        of ``moe_intermediate_size`` (renormalised, times
        ``moe_routed_scaling_factor``) beside shared experts of
        ``shared_expert_intermediate_size``; untied head.

        Four readings are of function and not of shape, and no key states
        them: the caller's ``overrides`` (a preset's ``runtime`` block) do,
        one field each: ``moe_router`` (how scores are made and chosen),
        ``gqa_output_gate`` (the gate's nonlinearity), ``qk_norm`` (RMSNorm
        a head on queries and keys before RoPE), ``shared_expert_gate``
        (whether the shared expert's output is gated).  How many of the
        experts this chip holds is the caller's to say too
        (``experts_held``)."""
        layers = hf["num_hidden_layers"]
        kinds = list(hf.get("layer_types") or ())
        heads_by_layer = list(hf.get("num_attention_heads_per_layer")
                              or [hf["num_attention_heads"]] * layers)
        gates = list(hf.get("gating_types") or ["per_head"] * layers)
        dense = sorted(hf.get("mlp_only_layers") or ())
        rope = hf.get("rope_parameters") or {}
        width, shared = (hf["moe_intermediate_size"],
                         hf["shared_expert_intermediate_size"])
        heads_of = {kind: {h for h, k in zip(heads_by_layer, kinds)
                           if k == kind} for kind in set(kinds)}
        ffn_kinds = hf.get("mlp_layer_types") or (
            ["dense"] * len(dense) + ["sparse"] * (layers - len(dense)))
        unsupported = {
            "attention_bias": bool(hf.get("attention_bias", False)),
            "moe_router_logit_softcapping": bool(
                hf.get("moe_router_logit_softcapping", 0)),
            "moe_apply_router_weight_on_input": bool(
                hf.get("moe_apply_router_weight_on_input", False)),
            "decoder_sparse_step": hf.get("decoder_sparse_step", 1) != 1,
            "mlp_only_layers": dense != list(range(len(dense))),
            "mlp_layer_types": list(ffn_kinds) != (
                ["dense"] * len(dense) + ["sparse"] * (layers - len(dense))),
            "layer_types": (len(kinds) != layers
                            or bool(set(kinds) - set(ATTENTION_KINDS))),
            "gating": hf.get("gating") != "per-head",
            "gating_types": (len(gates) != layers
                             or bool(set(gates) - {"per_head"})),
            "num_attention_heads_per_layer": (
                len(heads_by_layer) != layers
                or any(len(h) != 1 for h in heads_of.values())),
            "rope_parameters": any(
                (rope.get(kind) or {}).get("rope_type") not in (
                    "default", "yarn") for kind in set(kinds)),
            "sliding_window": ("sliding_attention" in kinds
                               and not hf.get("sliding_window")),
            "tie_word_embeddings": bool(hf.get("tie_word_embeddings", False)),
            "shared_expert_intermediate_size": bool(shared % width),
        }
        bad = sorted(k for k, v in unsupported.items() if v)
        if bad:
            raise ValueError(
                "this decoder does not implement the configuration's "
                + ", ".join(f"{k}={hf.get(k)!r}" for k in bad)
            )
        readings = ("moe_router", "gqa_output_gate", "qk_norm",
                    "shared_expert_gate")
        missing = [name for name in readings if name not in overrides]
        if missing:
            raise ValueError(
                "a laguna configuration states no " + ", ".join(missing)
                + ": the preset's runtime block has to")
        head_dim = hf["head_dim"]

        def kind_of(name: str) -> AttentionKind:
            group = rope[name]
            rotary = int(head_dim * group.get("partial_rotary_factor", 1))
            yarn = None
            if group["rope_type"] == "yarn":
                yarn = tuple(sorted(
                    (key, value) for key, value in group.items()
                    if key not in ("rope_type", "rope_theta",
                                   "partial_rotary_factor")))
            return AttentionKind(
                n_heads=heads_of[name].pop(),
                window=(hf["sliding_window"]
                        if name == "sliding_attention" else 0),
                rope_theta=float(group["rope_theta"]),
                rotary_dim=0 if rotary == head_dim else rotary, yarn=yarn)

        fields = dict(
            vocab_size=hf["vocab_size"], dim=hf["hidden_size"],
            n_layers=layers, n_heads=hf["num_attention_heads"],
            n_kv_heads=hf["num_key_value_heads"], head_dim=head_dim,
            hidden_dim=hf["intermediate_size"],
            max_seq_len=hf["max_position_embeddings"],
            rms_norm_eps=float(hf["rms_norm_eps"]),
            layer_types=tuple(kinds),
            attention_kinds=tuple(
                (name, kind_of(name)) for name in sorted(set(kinds))),
            first_k_dense_replace=len(dense),
            n_experts=hf["num_experts"],
            moe_top_k=hf["num_experts_per_tok"], moe_hidden_dim=width,
            n_shared_experts=shared // width,
            routed_scaling_factor=float(hf["moe_routed_scaling_factor"]),
            norm_topk_prob=bool(hf["norm_topk_prob"]),
            # every layer's own is its kind's; this is the first layer's
            rope_theta=float(rope[kinds[0]]["rope_theta"]),
        )
        fields.update(overrides)
        return cls(**fields)

    @classmethod
    def _from_sdar_moe(cls, hf: dict, overrides: dict) -> "LlamaConfig":
        """``model_type: sdar_moe``: a Qwen3-MoE-shaped layer (grouped-query
        attention with QK-norm, a published ``head_dim``, every layer
        softmax-routed experts) generating by diffusion over blocks.  The
        sampler's sizes (``block_length``, ``denoising_steps``,
        ``confidence_threshold``, ``mask_token_id``) are not in
        ``config.json``: the caller's ``overrides`` state them."""
        unsupported = {
            # the kernel and the cache view take a window beside the block
            # rule, but no test holds the two together to a reference
            "use_sliding_window": bool(hf.get("use_sliding_window", False)),
            "mlp_only_layers": bool(hf.get("mlp_only_layers")),
            "decoder_sparse_step": hf.get("decoder_sparse_step", 1) != 1,
            "rope_scaling": hf.get("rope_scaling") is not None,
            "attention_bias": bool(hf.get("attention_bias", False)),
            "tie_word_embeddings": bool(hf.get("tie_word_embeddings", False)),
            "hidden_act": hf.get("hidden_act", "silu") != "silu",
        }
        bad = sorted(k for k, v in unsupported.items() if v)
        if bad:
            raise ValueError(
                "this decoder does not implement the configuration's "
                + ", ".join(f"{k}={hf.get(k)!r}" for k in bad)
            )
        fields = dict(
            vocab_size=hf["vocab_size"], dim=hf["hidden_size"],
            n_layers=hf["num_hidden_layers"],
            n_heads=hf["num_attention_heads"],
            n_kv_heads=hf["num_key_value_heads"],
            head_dim=hf["head_dim"], qk_norm=True,
            # unused while every layer is routed (``mlp_only_layers`` [])
            hidden_dim=hf["intermediate_size"],
            rope_theta=float(hf["rope_theta"]),
            max_seq_len=hf["max_position_embeddings"],
            rms_norm_eps=float(hf["rms_norm_eps"]),
            moe_router="softmax_topk", n_experts=hf["num_experts"],
            moe_top_k=hf["num_experts_per_tok"],
            moe_hidden_dim=hf["moe_intermediate_size"],
            norm_topk_prob=bool(hf["norm_topk_prob"]),
            generation="block_diffusion",
        )
        fields.update(overrides)
        return cls(**fields)

    @classmethod
    def from_preset_file(cls, path: str) -> "LlamaConfig":
        """A file of ``models/presets/``: the published keys as run, and
        under ``runtime`` what the source does not state (dtypes, the
        offline tokenizer)."""
        import json

        with open(path, encoding="utf-8") as fh:
            hf = json.load(fh)
        return cls.from_hf_config(hf, **hf.get("runtime", {}))

    @classmethod
    def llama3_8b(cls) -> "LlamaConfig":
        return cls()

    @classmethod
    def tiny(cls) -> "LlamaConfig":
        """Byte-vocab smoke config: same topology, laptop-sized."""
        return cls(
            vocab_size=512, dim=128, n_layers=2, n_heads=8, n_kv_heads=4,
            hidden_dim=256, rope_theta=10_000.0, max_seq_len=2048,
        )


def presets() -> dict:
    """``{--model name: () -> LlamaConfig}``: the configurations written
    here and one a file of ``models/presets/`` (latent attention +
    sigmoid-routed experts: ``kanana-2-30b-a3b``, the published widths with
    the depth one chip holds, a pipeline stage; ``kanana-tiny``, the same
    kinds at test size).  A new file is a new preset."""
    return {
        "llama3": LlamaConfig.llama3_8b,
        "llama3-8b": LlamaConfig.llama3_8b,
        "llama3-tiny": LlamaConfig.tiny,
        "llama-tiny": LlamaConfig.tiny,
        **{name: partial(LlamaConfig.from_preset_file, path)
           for name, path in preset_files().items()},
    }


PRESETS = presets()

LATENT_CACHE_REFUSAL = (
    "this model's attention keeps a latent cache (one c_kv + k_rope vector "
    "a token, models/mla.LatentCache); the {runtime} runtime stores pages "
    "or slots of per-head keys and values and has no latent layout yet"
)


WINDOW_REFUSAL = (
    "some of this model's attention layers see a sliding window of keys "
    "(LlamaConfig.attention_kind: key position > query position - window); "
    "the {runtime} runtime's slots or pages are attended whole: its kernel "
    "masks by length alone and no page is released behind a window"
)


RECURRENT_STATE_REFUSAL = (
    "most of this model's layers carry a recurrent state a row "
    "(models/kda.RecurrentState, models/mamba2.SSMState or "
    "models/mamba1.SelectiveState: a float32 matrix a head or a channel and "
    "the convolutions' last inputs), not keys and values a "
    "token; the {runtime} runtime has slots or pages of per-head keys and "
    "values only"
)


class LlamaBlock(nn.Module):
    config: LlamaConfig
    # Only a configuration whose layers differ in kind reads it
    # (``first_k_dense_replace``).
    layer_index: int = 0

    @nn.compact
    def __call__(self, x, mask, positions, cache: Optional[KVCache],
                 lengths: Optional[jax.Array] = None,
                 segment_ids: Optional[jax.Array] = None,
                 prefill_lengths: Optional[jax.Array] = None,
                 prefill_capacity: Optional[int] = None,
                 packed=None, row_lengths: Optional[jax.Array] = None,
                 key_positions: Optional[jax.Array] = None):
        cfg = self.config
        if cfg.attention == "mla":
            return self._latent_block(x, mask, positions, cache,
                                      prefill_lengths, prefill_capacity,
                                      segment_ids, packed, row_lengths)
        if cfg.mixer(self.layer_index) in ("mamba", "mamba1"):
            return self._state_space_block(
                x, positions, cache, prefill_lengths, prefill_capacity,
                segment_ids, packed, row_lengths)
        if segment_ids is not None and (
            cache is not None or cfg.attn_impl != "flash"
        ):
            # Refuse rather than silently attend across documents: the
            # dense impl expresses packing as `causal & same-segment` in
            # the mask array (see tests/test_packed_decoder.py), and the
            # decode/cache path has no packed-document support.
            raise ValueError(
                "segment_ids is consumed by the flash prefill path only; "
                "fold the segment mask into `mask` for the dense impl"
            )
        dtype = jnp.dtype(cfg.dtype)
        # heads, window and RoPE are the layer's kind's (one kind in most
        # models: the configuration's own ``n_heads`` and ``rope_theta``)
        kind = cfg.attention_kind(self.layer_index)
        attn = MultiHeadAttention(
            n_heads=kind.n_heads,
            n_kv_heads=cfg.n_kv_heads,
            head_dim=cfg.attn_head_dim,
            use_rope=cfg.use_rope,
            rope_theta=kind.rope_theta,
            window=kind.window,
            rotary_dim=kind.rotary_dim,
            yarn=kind.yarn,
            output_gate=cfg.gqa_output_gate,
            max_positions=cfg.max_seq_len,
            dtype=dtype,
            attn_impl=cfg.attn_impl,
            flash_causal=True,
            quant=cfg.quant,
            weight_quant=cfg.weight_quant,
            qk_norm=cfg.qk_norm,
            norm_eps=cfg.rms_norm_eps,
            param_dtype=jnp.dtype(cfg.param_dtype),
            scale=cfg.attention_scale or None,
            name="attention",
        )
        h = RMSNorm(epsilon=cfg.rms_norm_eps, name="attention_norm")(x)
        with jax.named_scope("gqa"):
            if cache is not None:
                # A declared prefill (``prefill_lengths``: causal, from
                # position 0, the cache empty, one device) of a
                # configuration that asks for the flash kernel takes it
                # through the cache's causal view, which reads the lengths
                # in the mask's place; anything else is the masked form.
                view = (cfg.attn_impl == "flash"
                        and prefill_lengths is not None
                        and isinstance(cache, KVCache))
                if view:
                    cache = BlockCausalPrefill(
                        cache, prefill_lengths.astype(jnp.int32), 1,
                        scale=cfg.attention_scale or None,
                        window=kind.window)
                attn_out, new_cache = attn(
                    h, mask=mask, positions=positions, cache=cache,
                    packed=packed, key_positions=key_positions,
                )
                if view:
                    new_cache = new_cache.cache
            else:
                # Flash path: masking is fully described by
                # flash_causal=True + lengths (+ optional packed-document
                # segment_ids), so the (causal & padding) mask array stays
                # out.  Dense callers fold segment masking into the mask
                # array themselves.
                attn_out = attn(
                    h,
                    mask=None if cfg.attn_impl == "flash" else mask,
                    positions=positions,
                    lengths=lengths,
                    segment_ids=(segment_ids if cfg.attn_impl == "flash"
                                 else None),
                    packed=packed,
                )
                new_cache = None
        x = self._residual(x, attn_out)
        h = RMSNorm(epsilon=cfg.rms_norm_eps, name="ffn_norm")(x)
        if cfg.layer_types is not None or (
                cfg.n_experts > 0 and cfg.routed_experts):
            return (self._residual(x, self._feed_forward(
                h, prefill_lengths, prefill_capacity, packed)), new_cache)
        if cfg.n_experts > 0:
            from music_analyst_tpu.models.moe import MoESwiGLU

            # quant composes: the expert einsums (the bulk of MoE FLOPs)
            # run the per-expert int8 batched matmul alongside the
            # attention projections' int8 path, so an "int8" MoE model is
            # quantized where the FLOPs actually are.
            ffn = MoESwiGLU(
                cfg.n_experts, cfg.hidden_dim, top_k=cfg.moe_top_k,
                dtype=dtype, dispatch=cfg.moe_dispatch,
                capacity_factor=cfg.moe_capacity_factor,
                quant=cfg.quant,
                name="feed_forward_moe",
            )
        else:
            ffn = SwiGLU(cfg.hidden_dim, dtype=dtype, quant=cfg.quant,
                         weight_quant=cfg.weight_quant, name="feed_forward")
        x = self._residual(x, ffn(h))
        return x, new_cache

    def _residual(self, x, branch):
        """``x + residual_multiplier * branch`` (1 = the plain sum, and the
        program it always was)."""
        scale = self.config.residual_multiplier
        if scale == 1.0:
            return x + branch
        return x + (branch.astype(jnp.float32) * scale).astype(x.dtype)

    def _state_space_block(self, x, positions, cache, prefill_lengths,
                           prefill_capacity, segment_ids, packed,
                           row_lengths):
        """Pre-norm block whose mixer is Mamba-2 or Mamba-1 on a recurrent
        state in the cache's place (``models/mamba2.py``,
        ``models/mamba1.py``), then :meth:`_feed_forward`.  With ``packed``
        the stream ``x [1, C, D]`` is the real positions' compact set from
        norm to residual."""
        cfg = self.config
        if segment_ids is not None:
            raise ValueError("a state-space layer takes no segment_ids")
        kinds = dict(dtype=jnp.dtype(cfg.dtype),
                     param_dtype=jnp.dtype(cfg.param_dtype), name="attention")
        if cfg.mixer(self.layer_index) == "mamba1":
            from music_analyst_tpu.models.mamba1 import Mamba1Mixer

            mixer = Mamba1Mixer(
                inner=cfg.mamba_inner_dim, d_state=cfg.mamba_d_state,
                dt_rank=cfg.mamba_dt_rank, conv_kernel=cfg.mamba_conv_kernel,
                norm_eps=cfg.rms_norm_eps, **kinds)
        else:
            from music_analyst_tpu.models.mamba2 import Mamba2Mixer

            mixer = Mamba2Mixer(
                n_heads=cfg.mamba_n_heads, head_dim=cfg.mamba_head_dim,
                d_state=cfg.mamba_d_state, conv_kernel=cfg.mamba_conv_kernel,
                norm_eps=cfg.rms_norm_eps, **kinds)
        h = RMSNorm(epsilon=cfg.rms_norm_eps, name="attention_norm")(x)
        mixed = mixer(h, positions, cache, prefill_lengths, row_lengths,
                      packed)
        mixed, new_cache = mixed if cache is not None else (mixed, None)
        x = self._residual(x, mixed)
        h = RMSNorm(epsilon=cfg.rms_norm_eps, name="ffn_norm")(x)
        return (self._residual(x, self._feed_forward(
            h, prefill_lengths, prefill_capacity, packed)), new_cache)

    def _feed_forward(self, h, prefill_lengths, prefill_capacity,
                      packed=None):
        """The feed-forward half of a block whose expert layers are
        ``models/moe.RoutedMoE``, on the normed ``h [B, S, D]``: the dense
        SwiGLU in the leading layers, routed (+ shared) experts in the
        rest, with the router the configuration names.

        Where the caller's stream is the real positions' token set
        (``packed``: ``h [1, C, D]``) it is taken and returned as it is.
        On a ``[B, S]`` stream this half alone may run on the real
        positions (``models/compact_stream.feed_forward_positions``:
        gathered into ``prefill_capacity`` token slots, the result put back
        at their places); positions at or behind a row's length then
        receive zeros (the residual alone)."""
        from music_analyst_tpu.models.moe import RoutedMoE

        cfg = self.config
        dtype, param_dtype = jnp.dtype(cfg.dtype), jnp.dtype(cfg.param_dtype)
        compact = packed
        if packed is None:
            compact = feed_forward_positions(prefill_lengths, h.shape[1],
                                             prefill_capacity)
        if cfg.routed_layer(self.layer_index):
            ffn = RoutedMoE(
                cfg.n_experts, cfg.moe_hidden_dim, cfg.moe_top_k,
                n_shared=cfg.n_shared_experts,
                routed_scaling_factor=cfg.routed_scaling_factor,
                norm_topk_prob=cfg.norm_topk_prob, dtype=dtype,
                param_dtype=param_dtype,
                router=cfg.moe_router,
                n_group=cfg.n_group, topk_group=cfg.topk_group,
                experts_held=cfg.experts_held,
                name="feed_forward_moe",
            )
            # a prefill that declared its rows' lengths runs on one device
            # (a mesh withholds them): there its grouped matmuls may take
            # the kernel
            one_device = prefill_lengths is not None or packed is not None
            if compact is not None:  # it puts the experts it chose back too
                return ffn(h, compact, packed=packed is not None,
                           one_device=one_device)
            return ffn(h, one_device=one_device)
        else:
            ffn = SwiGLU(cfg.hidden_dim, dtype=dtype,
                         param_dtype=param_dtype, name="feed_forward")
        if compact is not None and packed is None:
            return compact.put_back(ffn(compact.gather(h)))
        return ffn(h)

    def _latent_block(self, x, mask, positions, cache, prefill_lengths,
                      prefill_capacity, segment_ids, packed=None,
                      row_lengths=None):
        """Pre-norm block of the ``mla`` kind: latent attention (or, in
        the layers ``LlamaConfig.mixer`` gives it, Kimi Delta Attention on a
        recurrent state in the cache's place), then :meth:`_feed_forward`.
        With ``packed`` the stream ``x [1, C, D]`` is the real positions'
        compact set from norm to residual."""
        from music_analyst_tpu.models.mla import MLAttention

        cfg = self.config
        if segment_ids is not None:
            raise ValueError("latent attention takes no segment_ids")
        dtype, param_dtype = jnp.dtype(cfg.dtype), jnp.dtype(cfg.param_dtype)
        if cfg.mixer(self.layer_index) == "kda":
            from music_analyst_tpu.models.kda import KimiDeltaAttention

            mixer = KimiDeltaAttention(
                n_heads=cfg.n_heads, head_dim=cfg.kda_head_dim,
                conv_kernel=cfg.kda_conv_kernel,
                lower_bound=cfg.kda_lower_bound, norm_eps=cfg.rms_norm_eps,
                dtype=dtype, param_dtype=param_dtype, name="attention",
            )
            h = RMSNorm(epsilon=cfg.rms_norm_eps, name="attention_norm")(x)
            mixed = mixer(h, positions, cache, prefill_lengths, row_lengths,
                          packed)
            mixed, new_cache = mixed if cache is not None else (mixed, None)
            x = x + mixed
            h = RMSNorm(epsilon=cfg.rms_norm_eps, name="ffn_norm")(x)
            return (x + self._feed_forward(
                h, prefill_lengths, prefill_capacity, packed), new_cache)
        attn = MLAttention(
            n_heads=cfg.n_heads, qk_nope_head_dim=cfg.qk_nope_head_dim,
            qk_rope_head_dim=cfg.qk_rope_head_dim,
            v_head_dim=cfg.v_head_dim, kv_lora_rank=cfg.kv_lora_rank,
            rope_theta=cfg.rope_theta, rope_interleave=cfg.rope_interleave,
            max_positions=cfg.max_seq_len, norm_eps=cfg.rms_norm_eps,
            dtype=dtype, param_dtype=param_dtype,
            output_gate=cfg.mla_output_gate, name="attention",
        )
        h = RMSNorm(epsilon=cfg.rms_norm_eps, name="attention_norm")(x)
        with jax.named_scope("mla"):
            if cache is not None:
                attn_out, new_cache = attn(h, mask, positions, cache,
                                           prefill_lengths, packed)
            else:
                attn_out = attn(h, mask, positions,
                                prefill_lengths=prefill_lengths,
                                packed=packed)
                new_cache = None
        x = x + attn_out
        h = RMSNorm(epsilon=cfg.rms_norm_eps, name="ffn_norm")(x)
        return (x + self._feed_forward(h, prefill_lengths, prefill_capacity,
                                       packed), new_cache)


class LlamaModel(nn.Module):
    config: LlamaConfig

    @nn.compact
    def __call__(
        self,
        token_ids: jax.Array,                      # [B, S]
        positions: jax.Array,                      # [B, S]
        mask: jax.Array,                           # broadcastable [B,H,S,KV]
        caches: Optional[List[KVCache]] = None,
        lengths: Optional[jax.Array] = None,       # [B] — flash path masks
        last_position: Optional[jax.Array] = None,  # [B] — see below
        segment_ids: Optional[jax.Array] = None,   # [B, S] — packed docs
        prefill_lengths: Optional[jax.Array] = None,  # [B] — see below
        prefill_capacity: Optional[int] = None,    # static — see below
        with_head: bool = True,  # False: no logits (``None`` in their place)
        row_lengths: Optional[jax.Array] = None,   # [B] — see below
        key_positions: Optional[jax.Array] = None,  # [B, KV] — see below
    ):
        # ``prefill_lengths`` is a promise about THIS call: a causal
        # prefill from position 0 on empty caches, ``mask`` = causal and
        # key padding by these lengths, one device, and nothing reads a
        # position at or behind its row's length.  With it the step may run
        # on the real positions alone, on ``prefill_capacity`` token slots
        # (static, ``None`` = rows x width): which form it takes is
        # ``models/compact_stream.py``'s answer.  On the compact stream
        # (``prefill_stream``) the hidden state is ``[1, capacity, dim]``
        # from the embedding of the real positions' ids to the position the
        # head reads; every layer takes it as it is and puts back at ``[B,
        # S]`` only what its cache holds, zeros at and behind a row's
        # length; logits without ``last_position`` are the head's of zeros
        # there.  Otherwise the feed-forward halves alone may gather the
        # real positions (``feed_forward_positions``).  Either way what the
        # layers return at or behind a row's length is neither computed as
        # the layer would nor defined.  ``row_lengths`` is read by the
        # layers that carry a recurrent state (``models/kda.py``) where
        # ``prefill_lengths`` is withheld: how many of this call's tokens
        # exist a row (a mask cannot tell a state when to stop), a fact
        # that promises nothing.  ``key_positions`` is read by
        # sliding-window layers on a cache: the position of the key each
        # slot holds where that is not the slot's index (a continuation
        # behind a padded prompt); a window layer adds its own rule (``key
        # position > query position - window``) to whatever ``mask``, the
        # lengths or the cache view let a query see, so ``mask`` stays one
        # array for all layers.  ``lengths`` keeps its one
        # meaning, the flash path's key padding:
        # CONTRACT: with cfg.attn_impl == "flash" (and no caches), the
        # `mask` argument is NOT applied — attention is causal + key-
        # padding-by-`lengths` + the layer's own sliding window + optional
        # same-segment (packed documents, ``segment_ids``; pair with
        # per-segment-restarted ``positions``).  Callers needing any other
        # mask (prefix-LM, cross-attention) must use the dense impl —
        # where `mask` is arbitrary, so packed-causal is expressed there
        # as ``causal & same-segment`` in the array; MultiHeadAttention
        # raises if a mask array reaches the flash branch directly.
        cfg = self.config
        dtype = jnp.dtype(cfg.dtype)
        param_dtype = jnp.dtype(cfg.param_dtype)
        with jax.named_scope("embed"):
            packed = prefill_stream(cfg, prefill_lengths,
                                    token_ids.shape[1], prefill_capacity)
            if packed is not None:
                token_ids = packed.gather(token_ids)[None]
                positions = packed.gather(positions)[None]
            embed = nn.Embed(cfg.vocab_size, cfg.dim, dtype=dtype,
                             param_dtype=param_dtype, name="tok_embeddings")
            x = embed(token_ids)
            if cfg.embedding_multiplier != 1.0:
                x = (x.astype(jnp.float32) * cfg.embedding_multiplier
                     ).astype(dtype)
        new_caches: List[KVCache] = []
        for i in range(cfg.n_layers):
            cache_i = caches[i] if caches is not None else None
            x, new_cache = LlamaBlock(cfg, i, name=f"layer_{i}")(
                x, mask, positions, cache_i, lengths,
                segment_ids=segment_ids, prefill_lengths=prefill_lengths,
                prefill_capacity=prefill_capacity, packed=packed,
                row_lengths=row_lengths, key_positions=key_positions,
            )
            if new_cache is not None:
                new_caches.append(new_cache)
        if not with_head:
            # a pass whose logits nobody reads (a diffusion prefill has no
            # next token; a commit pass only writes keys and values)
            return None, (new_caches if caches is not None else None)
        if packed is not None and last_position is not None:
            # the one slot a row's head reads, before the last norm
            x = x[0][packed.start + last_position.astype(jnp.int32),
                     None]
            last_position = None
        elif packed is not None:
            x = packed.put_back(x[0])
        x = RMSNorm(epsilon=cfg.rms_norm_eps, name="norm")(x)
        if last_position is not None:
            # Gather ONE position per row BEFORE the vocab projection:
            # prefill callers only consume the last prompt logits, and a
            # materialized [B, S, vocab] float32 tensor is the largest
            # array in the whole model (e.g. 33 GB at B=256, S=256,
            # V=128k — past a v5e's HBM on its own).  Returns [B, 1, V].
            x = jnp.take_along_axis(
                x, last_position[:, None, None].astype(jnp.int32), axis=1
            )
        if cfg.tie_embeddings:
            # the head is the embedding's transpose, as ``nn.Dense`` in
            # float32 would compute it from a kernel of its own
            note_traced_path("embeddings.tied")
            with jax.named_scope("lm_head"):
                logits = jax.lax.dot_general(
                    x.astype(jnp.float32),
                    embed.embedding.astype(jnp.float32),
                    (((x.ndim - 1,), (1,)), ((), ())))
        elif cfg.weight_quant != "none":
            from music_analyst_tpu.models.layers import WqDenseGeneral

            logits = WqDenseGeneral(
                features=cfg.vocab_size, axis=-1, use_bias=False,
                dtype=jnp.float32, name="lm_head",
            )(x)
        else:
            with jax.named_scope("lm_head"):
                logits = nn.Dense(cfg.vocab_size, use_bias=False,
                                  dtype=jnp.float32, param_dtype=param_dtype,
                                  name="lm_head")(x)
        if cfg.logits_scaling != 1.0:
            logits = logits / cfg.logits_scaling
        return logits, (new_caches if caches is not None else None)


def init_caches(
    cfg: LlamaConfig, batch: int, max_len: int, dtype=jnp.bfloat16
) -> List[KVCache]:
    """One empty cache a layer, of the layer's mixer kind: a ``KVCache``
    (grouped-query attention), a ``LatentCache`` (latent attention) or,
    for a layer that carries a recurrent state, that state (a KDA layer's
    ``RecurrentState``, a Mamba-2 layer's ``SSMState``, a Mamba-1 layer's
    ``SelectiveState``), which does not grow with ``max_len``.  The slot
    and paged decode runtimes hold the first kind alone
    (``LlamaZeroShotClassifier.decode_runtime_refusal``)."""

    def empty(mixer: str):
        if mixer == "kda":
            from music_analyst_tpu.models.kda import RecurrentState

            return RecurrentState.zeros(batch, cfg.n_heads, cfg.kda_head_dim,
                                        cfg.kda_conv_kernel, dtype)
        if mixer == "mamba":
            from music_analyst_tpu.models.mamba2 import SSMState

            return SSMState.zeros(
                batch, cfg.mamba_n_heads, cfg.mamba_head_dim,
                cfg.mamba_d_state, cfg.mamba_conv_kernel, dtype)
        if mixer == "mamba1":
            from music_analyst_tpu.models.mamba1 import SelectiveState

            return SelectiveState.zeros(
                batch, cfg.mamba_inner_dim, cfg.mamba_d_state,
                cfg.mamba_conv_kernel, dtype)
        if mixer == "mla":
            from music_analyst_tpu.models.mla import LatentCache

            return LatentCache.zeros(batch, max_len, cfg.kv_lora_rank,
                                     cfg.qk_rope_head_dim, dtype)
        return KVCache.zeros(batch, max_len, cfg.n_kv_heads,
                             cfg.attn_head_dim, dtype)

    return [empty(cfg.mixer(i)) for i in range(cfg.n_layers)]


def load_torch_state_dict(path: str, mmap: bool = False) -> dict:
    """Merge a ``pytorch_model.bin``-style file or a directory of shards
    (``pytorch_model*.bin`` / ``*.pt``) into one raw state dict.

    Shared by the Flax param mapper below and the validation harness's
    transformers oracle (``engines/validate.py``), so both sides of a
    label-agreement report read the checkpoint identically.

    ``mmap=True`` (the streaming quantize-on-load path) keeps tensor
    storage memory-mapped: pages materialize per-tensor as the per-unit
    iterator touches them, so peak host memory stays O(one layer) instead
    of O(checkpoint).  Falls back to an eager load for formats torch
    cannot mmap (legacy non-zip archives).
    """
    import torch

    if os.path.isdir(path):
        names = sorted(os.listdir(path))
        # HF Trainer dirs also hold training_args.bin / optimizer.pt etc.;
        # prefer the canonical weight-shard names when present.
        shards = [n for n in names
                  if n.startswith("pytorch_model") and n.endswith(".bin")]
        if not shards:
            shards = [n for n in names
                      if n.endswith((".bin", ".pt"))
                      and n not in ("training_args.bin", "optimizer.pt",
                                    "scheduler.pt", "rng_state.pth")]
        shards = [os.path.join(path, n) for n in shards]
        if not shards:
            raise FileNotFoundError(f"no *.bin/*.pt weight shards under {path}")
    else:
        shards = [path]
    sd = {}
    for shard in shards:
        try:
            if mmap:
                try:
                    loaded = torch.load(shard, map_location="cpu",
                                        weights_only=True, mmap=True)
                except (RuntimeError, ValueError):
                    loaded = torch.load(shard, map_location="cpu",
                                        weights_only=True)
            else:
                loaded = torch.load(shard, map_location="cpu",
                                    weights_only=True)
        except Exception as exc:
            # Never skip silently: a truncated weight shard skipped here
            # would surface as a confusing missing-key error (or worse,
            # a silent tied-embedding fallback) far from the cause.
            raise RuntimeError(f"failed to load shard {shard}") from exc
        if isinstance(loaded, dict):
            sd.update(loaded)
    if not sd:
        raise ValueError(
            f"no tensors found in {path} — not a torch state_dict?"
        )
    return sd


def iter_hf_param_units(params, path: str, mmap: bool = False):
    """Yield an HF ``LlamaForCausalLM`` checkpoint as per-unit leaf lists.

    The single definition of the torch→Flax mapping: torch Linear kernels
    ``[out, in]`` transpose to ``[in, out]``; attention projections reshape
    to ``[dim, heads, head_dim]``.  The RoPE convention needs no weight
    permutation: HF's ``rotate_half`` splits the head dim into contiguous
    halves, exactly as ``layers.apply_rope`` does.

    Yields ``(unit_name, [(tree_path, np.ndarray), …])`` one decoder layer
    (or embeddings / final norm / lm_head) at a time — the granularity the
    streaming quantize-on-load pipeline (``engines/checkpoint.py``)
    overlaps; with ``mmap=True`` only each unit's tensors are ever paged
    in.  ``params`` provides shapes only — ``ShapeDtypeStruct`` trees work.
    """
    import torch

    sd = load_torch_state_dict(path, mmap=mmap)
    # Tolerate both bare-model ("model.layers...") and prefixed keys.
    sd = { (k[len("model."):] if k.startswith("model.") else k): v
           for k, v in sd.items() }

    def t(name):
        return np.asarray(sd[name].to(torch.float32).numpy())

    dim = params["tok_embeddings"]["embedding"].shape[1]
    embed = t("embed_tokens.weight")
    want = tuple(params["tok_embeddings"]["embedding"].shape)
    if embed.shape != want:
        raise ValueError(
            f"checkpoint embed_tokens is {embed.shape} but the model config "
            f"expects {want} — config (vocab_size/dim) doesn't match the "
            "checkpoint"
        )
    yield "tok_embeddings", [("tok_embeddings/embedding", embed)]
    n_layers = sum(1 for k in params if k.startswith("layer_"))
    for i in range(n_layers):
        hf = f"layers.{i}"
        attn = params[f"layer_{i}"]["attention"]
        n_heads = attn["q_proj"]["kernel"].shape[1]
        n_kv = attn["k_proj"]["kernel"].shape[1]
        head_dim = attn["q_proj"]["kernel"].shape[2]
        pre = f"layer_{i}"
        leaves = [
            (f"{pre}/attention/q_proj/kernel",
             t(f"{hf}.self_attn.q_proj.weight").T.reshape(
                 dim, n_heads, head_dim)),
            (f"{pre}/attention/k_proj/kernel",
             t(f"{hf}.self_attn.k_proj.weight").T.reshape(
                 dim, n_kv, head_dim)),
            (f"{pre}/attention/v_proj/kernel",
             t(f"{hf}.self_attn.v_proj.weight").T.reshape(
                 dim, n_kv, head_dim)),
            (f"{pre}/attention/o_proj/kernel",
             t(f"{hf}.self_attn.o_proj.weight").T.reshape(
                 n_heads, head_dim, dim)),
            (f"{pre}/attention_norm/scale", t(f"{hf}.input_layernorm.weight")),
            (f"{pre}/ffn_norm/scale",
             t(f"{hf}.post_attention_layernorm.weight")),
            (f"{pre}/feed_forward/gate_proj/kernel",
             t(f"{hf}.mlp.gate_proj.weight").T),
            (f"{pre}/feed_forward/up_proj/kernel",
             t(f"{hf}.mlp.up_proj.weight").T),
            (f"{pre}/feed_forward/down_proj/kernel",
             t(f"{hf}.mlp.down_proj.weight").T),
        ]
        yield pre, leaves
    yield "norm", [("norm/scale", t("norm.weight"))]
    if "lm_head.weight" in sd:
        lm = t("lm_head.weight").T
    else:  # tied embeddings (Llama-3.2 style)
        lm = t("embed_tokens.weight").T
    yield "lm_head", [("lm_head/kernel", lm)]


def _set_tree_path(tree, path: str, leaf):
    parts = path.split("/")
    node = tree
    for part in parts[:-1]:
        node = node[part]
    node[parts[-1]] = leaf


def load_hf_torch_checkpoint(params, path: str):
    """Map an HF ``LlamaForCausalLM`` torch state_dict onto the Flax params.

    Eager wrapper over :func:`iter_hf_param_units` (one mapping
    definition; the streaming quantized loader consumes the iterator
    directly).  Replaces nothing in the reference — its large-model path
    is a remote Ollama server (``scripts/sentiment_classifier.py:85-100``);
    here the weights become first-class on-device arrays.
    """
    new = jax.tree_util.tree_map(lambda x: x, params)  # shallow copy
    for _, leaves in iter_hf_param_units(new, path):
        for tree_path, leaf in leaves:
            _set_tree_path(new, tree_path, leaf)
    return new


def _wq_group_size() -> int:
    """One group-size definition per family so the cache key, the loader,
    and the random-init quantizer can never disagree."""
    from music_analyst_tpu.ops.quant import WQ_DEFAULT_GROUP

    return WQ_DEFAULT_GROUP


def _sown_by_layer(sown, name: str) -> list:
    """What the routed layers sowed under ``name`` (``mutable=
    ["intermediates"]``), in layer order; empty for a model without them."""
    layers = sorted(
        (int(key.rsplit("_", 1)[1]), layer["feed_forward_moe"])
        for key, layer in sown.get("intermediates", {}).items()
        if "feed_forward_moe" in layer
    )
    return [moe[name][0] for _, moe in layers]


def _expert_id_dtype(n_experts: int):
    return jnp.uint8 if n_experts <= 256 else jnp.int32


def _expert_ids(chosen: jax.Array, n_experts: int) -> jax.Array:
    """Expert indices in the narrowest type that holds them."""
    return chosen.astype(_expert_id_dtype(n_experts))


def _routing_stats(sown, config: "LlamaConfig") -> dict:
    """The small device-side reductions of a prefill's routed layers that
    ride back with a step's result: ``expert_load_max`` / ``_mean``
    ``[routed layers]`` and ``chosen [layers, B, S, k]`` (which experts
    every position ran, for whoever compares against a reference); empty
    for a model without routed layers."""
    loads = _sown_by_layer(sown, "expert_load")
    if not loads:
        return {}
    load = jnp.stack(loads).astype(jnp.float32)  # [layers, E held]
    stats = {"expert_load_max": load.max(axis=-1),
             "expert_load_mean": load.mean(axis=-1),
             "chosen": _expert_ids(jnp.stack(_sown_by_layer(sown, "chosen")),
                                   config.n_experts)}
    if config.experts_held is not None:
        # the real positions' assignments, held here or not
        stats["assignments"] = jnp.stack(_sown_by_layer(sown, "assigned"))
    return stats


def init_params_by_layer(cfg: LlamaConfig, seed: int = 0):
    """Seeded random parameters, created on the device in
    ``cfg.param_dtype``: one jitted program a layer kind (the routed kind's
    is compiled once and run for every routed layer), one for the embedding
    and one for the final norm and the head.  The float32 tree never
    exists, and nothing is initialised op by op."""
    dtype = jnp.dtype(cfg.dtype)
    x = jnp.zeros((1, 8, cfg.dim), dtype)
    positions = jnp.zeros((1, 8), jnp.int32)
    mask = causal_mask(8, 8, 0)
    root = jax.random.key(seed)

    def program(module, *args):
        return jax.jit(lambda key: module.init(key, *args)["params"])

    inits = {}  # (routed?, the layer's kind) -> jitted init of that kind
    params = {}
    embed = nn.Embed(cfg.vocab_size, cfg.dim, dtype=dtype,
                     param_dtype=jnp.dtype(cfg.param_dtype))
    params["tok_embeddings"] = program(embed, positions)(
        jax.random.fold_in(root, 0))
    for i in range(cfg.n_layers):
        kind = (cfg.routed_layer(i), cfg.layer_kind(i))
        if kind not in inits:
            inits[kind] = program(LlamaBlock(cfg, i), x, mask, positions, None)
        params[f"layer_{i}"] = inits[kind](jax.random.fold_in(root, 1 + i))
    params["norm"] = RMSNorm(epsilon=cfg.rms_norm_eps).init(root, x)["params"]
    if cfg.tie_embeddings:
        return params
    head = nn.Dense(cfg.vocab_size, use_bias=False, dtype=jnp.float32,
                    param_dtype=jnp.dtype(cfg.param_dtype))
    params["lm_head"] = program(head, x)(
        jax.random.fold_in(root, 1 + cfg.n_layers))
    return params


def _partitioned(mesh) -> bool:
    return mesh is not None and mesh.size > 1


def _prefill_lengths(mesh, prompt_lens):
    """What a prefill from position 0 on empty caches hands the
    latent-attention layers beside the mask (``models/mla.py``: the
    kernel that reads lengths in its place): the prompts' lengths on
    one device, nothing under a mesh.  The kernel's call is opaque to
    the partitioner, which would gather its operands and give every
    chip all the work, where the XLA form is partitioned."""
    return None if _partitioned(mesh) else prompt_lens


def _prefill_capacity(config: LlamaConfig, mesh, prompt_lens,
                      shape) -> Optional[int]:
    """The static ``prefill_capacity`` of a step of ``shape`` (rows,
    width) whose rows have ``prompt_lens`` (host array):
    ``models/compact_stream.step_capacity`` where the blocks read
    ``prefill_lengths`` (one device); ``None`` under a mesh, where they
    are withheld, so such a step has one program."""
    if _partitioned(mesh):
        return None
    return step_capacity(config, prompt_lens, shape)


# The decoder's three programs, built from ``(model, config, …)``: whoever
# holds a model and its parameters can run them (the zero-shot classifier
# below does; a decode runtime need not go through it).  The traced
# functions' names are part of what a run records (a device trace finds
# the scoring step by ``score_labels``).

# The most tokens a label keeps, and the label slots every scoring step's
# caches have behind the prompt's ``S`` (a multiple of 8 keeps the cache's
# key axis one: 1,032 keys at a 1,024-wide step), whatever the table's width.
MAX_LABEL_TOKENS = 8


def _key_positions(config: LlamaConfig, prompt_lens, width: int,
                   kv_len: int):
    """``[B, kv_len]``: the position of the key each cache slot holds once
    tokens are written behind a ``width``-wide prompt: slot ``j < width``
    holds position ``j``, slot ``width + t`` position ``prompt_lens[row] +
    t``.  ``None`` for a model without sliding-window layers, whose masks
    say everything."""
    if not config.window_layers:
        return None
    slots = jnp.arange(kv_len)[None, :]
    return jnp.where(slots < width, slots,
                     prompt_lens[:, None] + slots - width)


def score_labels_program(model: LlamaModel, config: LlamaConfig, mesh=None):
    """The jitted scoring step: one prompt prefill a row, then the
    teacher-forced label continuations on its cache (``profiled_jit``
    name ``llama_score_labels``).

    A continuation runs the positions whose forward pass is read and no
    other.  Token 0 of a label is scored by the PROMPT's last logits and
    token ``i > 0`` by the continuation's logits at position ``i - 1``, so
    the forward of a label's last token (the EOS of ``word + EOS``) scores
    nothing: of a table ``L`` wide the continuation forwards
    ``label_ids[:, :L - 1]`` and gathers at ``label_ids[:, 1:]``.  ``L`` is
    a shape of the program (:func:`_label_table`: the longest label), so
    with one-token labels there is no continuation at all.  Attention is
    causal and a recurrent state runs forward: the positions left out
    never reached the ones that are read."""

    def _score_labels(params, prompt_ids, prompt_lens, label_ids,
                      label_lens, prefill_capacity=None, probe_rows=None):
        """Log-likelihood of each label continuation per batch row.

        prompt_ids [B, S]; label_ids [3, L], ``L <= MAX_LABEL_TOKENS``;
        ``prefill_capacity`` (static)
        the token slots the prefill runs, from
        ``models/compact_stream.compact_capacity`` of these lengths
        (``None``: ``B * S``).  Returns ``(scores [B, 3], stats)``:
        ``stats`` holds the small device-side reductions that ride back
        with the scores (``expert_load_max`` / ``expert_load_mean``
        ``[routed layers]`` of the prefill, for a model with routed
        experts; else empty; ``chosen_labels [3, layers, B, L, k]``: the
        experts the continuations' positions ran, ``-1`` at the last,
        which ran none).  ``probe_rows [P]`` (a model whose layers
        differ in kind, ``LlamaConfig.mixed_layers``): the rows whose
        caches after the prefill ride back
        too (``stats["probe"]``: every KDA or Mamba-2 layer's ``state
        [layers, P, H, ., .]`` or Mamba-1 layer's ``[layers, P, I, N]``,
        the Mamba layers' ``conv`` tails, every
        latent layer's ``latents`` and ``rope_keys`` ``[layers, P, S, .]``,
        every grouped-query layer's ``keys`` and ``values`` ``[layers, P,
        S, Hkv, D]``, the logits at the prompt's last position ``[P,
        V]``, and of a Mamba-1 model the first Mamba-1 layer's scan inputs
        ``scan_x`` / ``scan_r`` / ``scan_b`` / ``scan_c [P, S, .]``), for
        whoever compares them with a reference; the values choose rows,
        not a program.
        """
        B, S = prompt_ids.shape
        n_labels, L = label_ids.shape
        if L > MAX_LABEL_TOKENS:
            raise ValueError(
                f"a label table {L} wide: the caches hold "
                f"{MAX_LABEL_TOKENS} label slots")
        W = L - 1  # the positions a continuation runs
        with jax.named_scope("prefill"):
            # prompt_lens may arrive int16 (wire narrowing) — widen once
            # on device before the arithmetic/broadcast uses below.
            prompt_lens = prompt_lens.astype(jnp.int32)
            positions = jnp.arange(S)[None, :].repeat(B, 0)
            # kv length is the cache buffer's, S + MAX_LABEL_TOKENS
            # whatever the labels' width; the label slots are causally
            # unreachable during prefill and masked out anyway.
            kv_len = S + MAX_LABEL_TOKENS
            mask = causal_mask(S, kv_len, 0) & jnp.pad(
                padding_mask(prompt_lens, S),
                ((0, 0), (0, 0), (0, 0), (0, MAX_LABEL_TOKENS)),
            )
            caches = init_caches(config, B, kv_len)
            # last_position: only the final prompt logits are consumed, so
            # the [B,S,V] prefill logits are never materialized.
            (logits, caches), sown = model.apply(
                {"params": params}, prompt_ids, positions, mask, caches,
                last_position=prompt_lens - 1,
                prefill_lengths=_prefill_lengths(mesh, prompt_lens),
                prefill_capacity=prefill_capacity, row_lengths=prompt_lens,
                mutable=["intermediates"],
            )
            stats = _routing_stats(sown, config)
            if probe_rows is not None:
                stats["probe"] = _probe(config, caches, probe_rows, S)
                stats["probe"]["logits"] = logits[probe_rows, 0]
                stats["probe"].update(_scan_probe(sown, probe_rows))
            # Force every cache to report the true prompt length so
            # label positions line up even though the buffer was written
            # at 0..S.
            caches = [c.with_length(S) for c in caches]

        def score_one(label_row, label_len):
            lab = jnp.broadcast_to(label_row[None, :], (B, L))
            total = jnp.take_along_axis(first_logp, lab[:, :1], axis=1)[:, 0]
            label_chosen = None
            if W:
                # tokens i > 0 from the forward of the label's tokens
                # before them: positions 0 .. W-1, written at slots S ..
                steps = jnp.arange(W)
                pos = prompt_lens[:, None] + steps[None, :]
                # decode attends to the full prompt (masked by its length)
                # plus the causal prefix of the label tokens
                kv_pos = jnp.arange(kv_len)[None, None, None, :]
                prompt_part = kv_pos < prompt_lens[:, None, None, None]
                label_part = (kv_pos >= S) & (
                    kv_pos - S <= steps[None, None, :, None]
                )
                (logits2, _), sown2 = model.apply(
                    {"params": params}, lab[:, :-1], pos,
                    prompt_part | label_part, caches,
                    key_positions=_key_positions(config, prompt_lens, S,
                                                 kv_len),
                    mutable=["intermediates"],
                )
                rest_lp = jnp.take_along_axis(
                    jax.nn.log_softmax(logits2, axis=-1),
                    lab[:, 1:, None], axis=2,
                )[:, :, 0]
                total = total + jnp.where(
                    steps[None, :] < label_len - 1, rest_lp, 0.0).sum(axis=1)
                chosen2 = _sown_by_layer(sown2, "chosen")
                if chosen2:
                    # signed: the last position states no expert
                    label_chosen = jnp.pad(
                        jnp.stack(chosen2).astype(jnp.int32),
                        ((0, 0), (0, 0), (0, 1), (0, 0)),
                        constant_values=-1)
            # Length-normalize: summed log-probs otherwise favor the
            # shortest label ("Neutral" is one byte shorter than the
            # other two under the byte tokenizer).
            return (
                total / jnp.maximum(label_len.astype(jnp.float32), 1.0),
                label_chosen,
            )

        with jax.named_scope("labels"):
            # token 0 of every label is scored from the prompt's last
            # logits
            first_logp = jax.nn.log_softmax(logits[:, 0], axis=-1)  # [B, V]
            if config.recurrent_state:
                # One label after the other: each continuation advances its
                # own copy of every recurrent layer's state, and under ``vmap``
                # the copies of all labels and layers are made up front
                # (2.4 GB at 64 rows x 6 layers x 3 labels), beside the
                # prefill's temporaries.
                by_label, label_chosen = jax.lax.map(
                    lambda label: score_one(*label),
                    (label_ids, label_lens))
                scores = by_label.T
            else:
                scores, label_chosen = jax.vmap(
                    score_one, in_axes=(0, 0), out_axes=(1, 0)
                )(label_ids, label_lens)
            if label_chosen is None and "chosen" in stats:
                # no continuation ran: nothing stated at the one label
                # position
                layers, _, _, k = stats["chosen"].shape
                label_chosen = jnp.full((n_labels, layers, B, L, k), -1,
                                        jnp.int32)
            if label_chosen is not None:
                stats["chosen_labels"] = label_chosen  # [3, layers, B, L, k]
            if label_chosen is not None and config.experts_held is not None:
                # of the label positions whose forward is read, the
                # assignments to experts held here
                first, count = config.experts_held
                read = (jnp.arange(L)[None, :] < label_lens[:, None] - 1)
                here = (label_chosen >= first) & (
                    label_chosen < first + count)
                stats["label_assignments_held"] = jnp.sum(
                    here & read[:, None, None, :, None])
        return scores, stats  # [B, 3]

    return profiled_jit(_score_labels, name="llama_score_labels",
                        static_argnames=("prefill_capacity",))


def _probe(config: LlamaConfig, caches, rows, width: int) -> dict:
    """What ``rows`` of a prefill's caches hold, by mixer kind."""
    by_kind = {}
    for i, cache in enumerate(caches):
        by_kind.setdefault(config.mixer(i), []).append(cache)
    carried = [c for kind in ("kda", "mamba", "mamba1")
               for c in by_kind.get(kind, [])]
    tails = by_kind.get("mamba", []) + by_kind.get("mamba1", [])
    kept = {}
    if carried:
        kept["state"] = jnp.stack([c.state[rows] for c in carried])
    if tails:
        kept["conv"] = jnp.stack([c.conv[rows] for c in tails])
    if "mla" in by_kind:
        kept["latents"] = jnp.stack(
            [c.latents[rows, :width] for c in by_kind["mla"]])
        kept["rope_keys"] = jnp.stack(
            [c.rope_keys[rows, :width] for c in by_kind["mla"]])
    if "gqa" in by_kind:
        kept["keys"] = jnp.stack(
            [c.keys[rows, :width] for c in by_kind["gqa"]])
        kept["values"] = jnp.stack(
            [c.values[rows, :width] for c in by_kind["gqa"]])
    return kept


def _scan_probe(sown, rows) -> dict:
    """What the first Mamba-1 layer's recurrence read for ``rows``
    (``models/mamba1.Mamba1Mixer``'s ``scan_inputs``): ``scan_x``,
    ``scan_r``, ``scan_b``, ``scan_c [P, S, .]``, zero past a row's
    length; empty for a model without Mamba-1 layers."""
    layers = sorted(
        (int(key.rsplit("_", 1)[1]), layer["attention"]["scan_inputs"][0])
        for key, layer in sown.get("intermediates", {}).items()
        if "scan_inputs" in layer.get("attention", {}))
    if not layers:
        return {}
    read = layers[0][1]
    slot, real = read["slot"][rows], read["real"][rows]
    return {f"scan_{name}": jnp.where(real[..., None], read[name][slot], 0)
            for name in ("x", "r", "b", "c")}


def decode_step_program(model: LlamaModel):
    """One greedy token a row against the caches (the explicit step loop
    of :meth:`LlamaZeroShotClassifier.generate`)."""

    @jax.jit
    def _decode_step(params, token, position, caches):
        # a recurrent state has no length: the first cache that has one
        kv_len = next(c.max_len for c in caches if hasattr(c, "max_len"))
        kv_pos = jnp.arange(kv_len)[None, None, None, :]
        # a slot is its key's position here (``generate`` sets the caches'
        # length to the row's), which is what a window layer counts from
        mask = kv_pos <= position[:, None, None, None]
        logits, caches = model.apply(
            {"params": params}, token, position[:, None], mask, caches
        )
        return jnp.argmax(logits[:, -1], axis=-1), caches

    return _decode_step


def generate_scan_program(model: LlamaModel, config: LlamaConfig,
                          eos_id: int, mesh=None):
    """Prefill and every decode step of a batch as one jitted program."""

    @partial(jax.jit, static_argnames=("max_new_tokens", "early_exit",
                                       "prefill_capacity"))
    def _generate_scan(params, prompt_ids, prompt_lens, max_new_tokens,
                       early_exit=True, prefill_capacity=None):
        """Batched greedy decode as ONE compiled program.

        The reference's generation is a remote server call per song
        (``scripts/sentiment_classifier.py:94``); a naive on-device port
        would still pay one host→device round-trip per token.  Here
        prefill + every decode step run inside a single jit: the token
        loop is a ``lax.scan`` over the KV cache (static trip count,
        EOS handled by masking — XLA-shaped control flow, SURVEY.md
        §2.4 design notes).  With ``early_exit`` the scan is cut into
        fixed-size segments under a ``lax.while_loop`` whose predicate
        stops once every row has emitted EOS: the all-done tail of a
        short batch is skipped instead of decoded, and because the
        token buffer is pre-filled with EOS (exactly what the skipped
        steps would have emitted) the outputs are identical to the
        full scan.
        """
        B, S = prompt_ids.shape
        positions = jnp.arange(S)[None, :].repeat(B, 0)
        total = S + max_new_tokens
        mask = causal_mask(S, total, 0) & jnp.pad(
            padding_mask(prompt_lens, S),
            ((0, 0), (0, 0), (0, 0), (0, max_new_tokens)),
        )
        caches = init_caches(config, B, total)
        logits, caches = model.apply(
            {"params": params}, prompt_ids, positions, mask, caches,
            last_position=prompt_lens - 1,
            prefill_lengths=_prefill_lengths(mesh, prompt_lens),
            prefill_capacity=prefill_capacity, row_lengths=prompt_lens,
        )
        caches = [c.with_length(S) for c in caches]
        first = jnp.argmax(logits[:, 0], axis=-1)  # [B]
        eos = jnp.asarray(eos_id, jnp.int32)

        def step(carry, t):
            # Ragged prompts: row b's decode token t sits at *slot*
            # S + t (uniform, so one dynamic_update_slice serves the
            # whole batch) while its *position* is prompt_lens[b] + t
            # (per-row, for RoPE and the mask) — the same slot/position
            # split _score_labels uses.
            token, done, caches = carry
            pos = prompt_lens + t                              # [B]
            kv_pos = jnp.arange(total)[None, None, None, :]
            prompt_part = kv_pos < prompt_lens[:, None, None, None]
            decode_part = (kv_pos >= S) & (kv_pos - S <= t)
            step_mask = prompt_part | decode_part
            lg, caches = model.apply(
                {"params": params}, token[:, None], pos[:, None],
                step_mask, caches,
                key_positions=_key_positions(config, prompt_lens, S, total),
            )
            nxt = jnp.argmax(lg[:, -1], axis=-1).astype(jnp.int32)
            done = done | (token == eos)
            nxt = jnp.where(done, eos, nxt)
            return (nxt, done, caches), token

        init = (first.astype(jnp.int32), first == eos, caches)
        if not early_exit:
            (_, _, caches), tokens = jax.lax.scan(
                step, init, jnp.arange(max_new_tokens)
            )
            return tokens.T  # [B, max_new_tokens]

        # Early exit: fixed-size scan segments inside a while_loop with
        # an all-done predicate between segments.  Segment boundaries
        # keep the compiled-shape set O(1); the EOS-pre-filled buffer
        # makes a skipped tail byte-identical to a decoded one (post-
        # done steps emit exactly EOS).
        seg = min(8, max_new_tokens)
        n_seg = -(-max_new_tokens // seg)
        buf = jnp.full((n_seg * seg, B), eos, jnp.int32)

        def seg_cond(state):
            k, _, done, _, _ = state
            return (k < n_seg) & ~jnp.all(done)

        def seg_body(state):
            k, token, done, caches, buf = state
            (token, done, caches), seg_tokens = jax.lax.scan(
                step, (token, done, caches),
                k * seg + jnp.arange(seg),
            )
            buf = jax.lax.dynamic_update_slice(
                buf, seg_tokens, (k * seg, jnp.asarray(0, jnp.int32))
            )
            return (k + 1, token, done, caches, buf)

        state = (jnp.asarray(0, jnp.int32),) + init + (buf,)
        _, _, _, _, buf = jax.lax.while_loop(seg_cond, seg_body, state)
        return buf[:max_new_tokens].T  # [B, max_new_tokens]

    return _generate_scan


def build_params(model: LlamaModel, config: LlamaConfig,
                 checkpoint_path: Optional[str] = None, seed: int = 0,
                 mesh=None, wq_cache_dir: Optional[str] = None):
    """``(params, pretrained)``: the checkpoint's weights where a path is
    given (streamed through quantize-on-load under ``weight_quant``), else
    seeded random ones in the configuration's ``param_dtype``; not yet
    placed on ``mesh`` (``parallel/sharding.shard_params`` does that)."""
    dummy_ids = jnp.zeros((1, 8), jnp.int32)
    dummy_pos = jnp.zeros((1, 8), jnp.int32)
    dummy_mask = causal_mask(8, 8, 0)
    wq = config.weight_quant
    if checkpoint_path and wq != "none":
        # Streaming quantize-on-load: the float tree is never
        # materialized — shapes come from eval_shape, checkpoint
        # tensors stream through quantize→H2D one layer at a time,
        # and a warm wq-cache hit skips torch entirely.
        from music_analyst_tpu.engines import wq_cache
        from music_analyst_tpu.engines.checkpoint import (
            load_quantized_params,
        )

        params_shape = jax.eval_shape(
            model.init, jax.random.key(seed), dummy_ids,
            dummy_pos, dummy_mask,
        )["params"]
        cache_dir = wq_cache.resolve_cache_dir(wq_cache_dir)
        cache_key = (
            wq_cache.wq_key(checkpoint_path, "llama", wq,
                            _wq_group_size())
            if cache_dir else None
        )
        params = load_quantized_params(
            params_shape,
            lambda: iter_hf_param_units(
                params_shape, checkpoint_path, mmap=True
            ),
            wq,
            group_size=_wq_group_size(),
            mesh=mesh,
            cache_dir=cache_dir,
            cache_key=cache_key,
        )
        return params, True
    if config.param_dtype != "float32":
        if checkpoint_path:
            raise ValueError(
                "no checkpoint loader maps onto this configuration's "
                "layers yet; it runs seeded random weights"
            )
        return init_params_by_layer(config, seed), False
    params = model.init(
        jax.random.key(seed), dummy_ids, dummy_pos, dummy_mask
    )["params"]
    if checkpoint_path:
        params = load_hf_torch_checkpoint(params, checkpoint_path)
    if wq != "none":
        # Random-init WQ model (smoke/A-B runs): quantize the
        # just-initialized tree in place so the forward exercises
        # the exact stored-weight path a checkpoint load produces.
        from music_analyst_tpu.ops.quant import quantize_tree

        params = quantize_tree(params, wq, _wq_group_size())
    return params, bool(checkpoint_path)


def _label_table(tokenizer):
    """``(ids [3, L], lens [3])``: the label continuations scored
    teacher-forced after a shared prompt prefill, padded to the longest
    label's ``L`` tokens (at most ``MAX_LABEL_TOKENS``) so a single jitted
    function scores them as a batch dimension.  ``L`` is the tokenizer's
    own: 2 where a label is ``word + EOS``, 8 for the byte tokenizer's
    ``Positive`` / ``Negative``, 1 where every label is one token and the
    tokenizer closes none.  It is a host constant when the classifier is
    built and so a shape of the scoring program, which runs ``L - 1``
    positions a continuation (:func:`score_labels_program`: the last
    token's forward is never taken) and not a fixed 8."""
    bos_id = getattr(tokenizer, "bos_id", None)
    label_rows, label_lens = [], []
    for label in SUPPORTED_LABELS:
        row, n = tokenizer.encode(label, 16)
        # Drop the leading BOS only if this tokenizer actually adds one
        # (HF tokenizers with add_bos_token=False don't).
        skip = 1 if (n > 0 and bos_id is not None
                     and row[0] == bos_id) else 0
        if getattr(tokenizer, "closes_labels", False):
            # A word-level tokenizer gives every label one token, and
            # a one-token continuation never reads its own forward
            # pass: score "label, then stop" (EOS) as the answer.
            row = np.insert(row, n, tokenizer.eos_id)
            n += 1
        label_rows.append(row[skip:skip + MAX_LABEL_TOKENS])
        label_lens.append(min(n - skip, MAX_LABEL_TOKENS))
    width = max(1, *label_lens)
    return (np.stack([row[:width] for row in label_rows]),
            np.array(label_lens, dtype=np.int32))


def zero_shot_prompt(lyrics: str) -> str:
    """The reference's prompt around one song's lyrics."""
    return PROMPT_TEMPLATE.format(lyrics=lyrics.strip()[:LYRICS_TRUNCATION])


class LlamaZeroShotClassifier(ClassifierBackend):
    """Constrained-label zero-shot sentiment over the decoder LM.

    Holds what a decoder is made of — ``model``, ``params``, ``config``,
    ``tokenizer``, ``mesh``, ``max_prompt_len`` — and the prompt and label
    handling of the sentiment task; the programs it runs are built above,
    and the continuous decode runtimes are built from the same six
    attributes by ``serving/decode_runtime.py``.
    """

    name = "llama"

    def __init__(
        self,
        config: Optional[LlamaConfig] = None,
        checkpoint_path: Optional[str] = None,
        max_prompt_len: int = 1024,
        mesh=None,
        seed: int = 0,
        wq_cache_dir: Optional[str] = None,
    ) -> None:
        self.config = config or LlamaConfig.tiny()
        self.max_prompt_len = max_prompt_len
        self.tokenizer = resolve_llama_tokenizer(
            self.config.offline_vocab_size, kind=self.config.tokenizer)
        # Ids above vocab_size would be silently clamped by nn.Embed's
        # gather, producing garbage labels with no diagnostic.  With real
        # weights that's fatal; on random-weight smoke runs (labels are
        # garbage regardless) a warning keeps e.g. --model llama3-tiny
        # usable while MUSICAAL_LLAMA_TOKENIZER points at a full BPE dir.
        if self.tokenizer.vocab_size > self.config.vocab_size:
            message = (
                f"tokenizer vocab ({self.tokenizer.vocab_size}) exceeds "
                f"model vocab ({self.config.vocab_size})"
            )
            if checkpoint_path:
                raise ValueError(message)
            import warnings

            warnings.warn(message + "; out-of-range ids will clamp",
                          stacklevel=2)
        self.model = LlamaModel(self.config)
        self.params, self.pretrained = build_params(
            self.model, self.config, checkpoint_path, seed, mesh,
            wq_cache_dir)
        if self.pretrained and isinstance(self.tokenizer, ByteTokenizer):
            import warnings

            warnings.warn(
                "real checkpoint loaded but no matching tokenizer found "
                "— byte-level ids won't line up with the checkpoint's "
                "BPE vocabulary; set MUSICAAL_LLAMA_TOKENIZER to the "
                "checkpoint's tokenizer directory for meaningful labels",
                stacklevel=2,
            )
        self.mesh = mesh
        if mesh is not None:
            from music_analyst_tpu.parallel.sharding import shard_params

            self.params = shard_params(self.params, mesh)
        self._label_ids, self._label_lens = _label_table(self.tokenizer)
        # The rows of a step whose caches after the prefill ride back
        # with the scores (``_score_labels``' ``probe_rows``: a model whose
        # layers differ in kind alone); whoever compares them with a
        # reference sets the rows it sampled.  Values, not a program.
        self.probe_rows = np.arange(8, dtype=np.int32)
        self._score_labels = score_labels_program(
            self.model, self.config, mesh)
        self._decode_step = decode_step_program(self.model)
        self._generate_scan = generate_scan_program(
            self.model, self.config, self.tokenizer.eos_id, mesh)

    @property
    def decode_runtime_refusal(self) -> Optional[str]:
        """Why the continuous decode runtimes (``serving/
        decode_runtime.py``) cannot host this model, or ``None`` where
        they can: a latent cache, a step that yields a block
        (``models/block_diffusion.py``'s own), a recurrent state beside
        the cache, whatever the cache's kind, or a sliding window their
        kernels do not mask.  ``serve`` reads it to leave the ``generate``
        op off."""
        if self.config.recurrent_state:
            return RECURRENT_STATE_REFUSAL
        if self.config.window_layers:
            return WINDOW_REFUSAL
        return LATENT_CACHE_REFUSAL if self.config.latent_cache else None

    @classmethod
    def from_pretrained_or_random(cls, model: str, **kwargs):
        quant = "none"
        if model.endswith("-int8"):
            model, quant = model[: -len("-int8")], "int8"
        preset = PRESETS.get(model)
        if preset is None:
            raise ValueError(
                f"unknown llama preset {model!r}; options: {sorted(PRESETS)}"
            )
        config = kwargs.pop("config", None) or preset()
        if quant != "none":
            config = dataclasses.replace(config, quant=quant)
        weight_quant = kwargs.pop("weight_quant", "none") or "none"
        if weight_quant != "none":
            config = dataclasses.replace(config, weight_quant=weight_quant)
        ckpt = kwargs.pop("checkpoint_path", None) or os.environ.get(
            "MUSICAAL_LLAMA_CKPT"
        )
        if model in ("llama3", "llama3-8b") and not ckpt:
            raise RuntimeError(
                "llama3-8b needs a checkpoint (set MUSICAAL_LLAMA_CKPT) and "
                "a multi-chip mesh; use --model llama3-tiny for smoke runs "
                "or --mock for the keyword kernel"
            )
        if config.block_diffusion:
            from music_analyst_tpu.models.block_diffusion import (
                BlockDiffusionClassifier as cls,
            )
        return cls(config=config, checkpoint_path=ckpt, **kwargs)

    def _trim_prompt_pad(self, ids, lens):
        """Trim tokenizer padding to the smallest power-of-two width (floor
        ``config.prompt_width_floor``, 64 unless a preset says otherwise)
        that covers the batch's longest prompt, capped at
        ``max_prompt_len``.

        The decoder analogue of the encoder's length buckets: a
        short-lyric batch previously paid full ``max_prompt_len`` (1024)
        prefill FLOPs per row.  Rounding to powers of two keeps the
        compiled-shape set O(log max_prompt_len); no content is cut
        (width ≥ lens.max()), and padding columns are masked out of
        attention either way, so labels/generations are unchanged.
        """
        from music_analyst_tpu.utils.shapes import round_pow2

        longest = int(lens.max()) if len(lens) else 1
        width = min(round_pow2(longest, self.config.prompt_width_floor),
                    self.max_prompt_len)
        return ids[:, :width], lens

    def _encode_prompts(self, texts: Sequence[str]):
        ids, lens = self.tokenizer.encode_batch(
            [zero_shot_prompt(t) for t in texts], self.max_prompt_len)
        return self._trim_prompt_pad(ids, lens)

    # Staged hooks for the prefetch pipeline (engines/sentiment.py): a
    # step's read and tokenize overlap the device's work on the step
    # before.

    def prepare(self, texts: Sequence[str]):
        """Host phase: the prompts' token ids at the batch's trimmed
        width, lengths narrowed for the wire."""
        from music_analyst_tpu.runtime.wire import narrow_lengths

        prompt_ids, prompt_lens = self._encode_prompts(texts)
        # Prompt lengths cross the wire int16 (llama's 128k vocab keeps the
        # ids themselves int32); widened on device in _score_labels.
        return (texts, prompt_ids,
                narrow_lengths(prompt_lens, self.max_prompt_len))

    def transfer(self, prepared):
        from music_analyst_tpu.runtime.wire import count_h2d_bytes

        texts, prompt_ids, prompt_lens = prepared
        count_h2d_bytes([prompt_ids, prompt_lens])
        lens = prompt_lens.astype(np.int64)
        # real prompt tokens, their causal (query, key) pairs, the token
        # slots the prefill's feed-forward layers run for them, and, of a
        # model whose attention layers differ in kind, the pairs by kind
        capacity = _prefill_capacity(self.config, self.mesh, lens,
                                     prompt_ids.shape)
        real = (int(lens.sum()), int((lens * (lens + 1) // 2).sum()),
                capacity,
                self._attention_counts(lens, prompt_ids.shape, capacity))
        return (texts, jnp.asarray(prompt_ids), jnp.asarray(prompt_lens),
                real)

    def _attention_counts(self, lens, shape, capacity) -> Optional[dict]:
        """What the grouped-query layers of one scoring step of ``shape``
        (rows, width) attend to where they differ in kind (``None`` for
        every other model), from the rows' lengths on the host: layers with
        and without a window; the real (query, key) pairs inside the mask
        of ONE layer of each (``token_pairs_full``: causal;
        ``token_pairs_window``: of them those inside the window) and of the
        label positions whose forward is read (``label_pairs_*``);
        ``token_pairs``, their sum over the layers; and
        ``token_pairs_tiles``, the pairs a query head of the prefill
        computed, summed over the layers: whole tiles of the kernel's grid
        (``ops/kv_cache.prefill_tile_pairs``: the packed kernel's where the
        prefill ran on the compact stream of ``capacity`` slots,
        ``models/compact_stream.runs_compact``), every pair of the step
        where the masked form ran."""
        cfg = self.config
        if cfg.attention_kinds is None:
            return None
        from music_analyst_tpu.ops.kv_cache import prefill_tile_pairs

        width = shape[1]
        if not runs_compact(cfg, shape, capacity):
            capacity = None

        windows = [cfg.attention_kind(i).window for i in range(cfg.n_layers)
                   if cfg.mixer(i) == "gqa"]
        kernel = cfg.attn_impl == "flash" and not _partitioned(self.mesh)

        def pairs(window: int) -> int:
            causal = lens * (lens + 1) // 2
            if not window:
                return int(causal.sum())
            w = window
            return int(np.where(lens <= w, causal,
                                w * (w + 1) // 2 + (lens - w) * w).sum())

        def label_pairs(window: int) -> int:
            # the label token at offset t behind a prompt of n tokens sees
            # the prompt and the label's tokens up to its own
            total = 0
            for n_label in self._label_lens:
                for t in range(max(int(n_label) - 1, 0)):
                    seen = lens + t + 1
                    total += int((np.minimum(seen, window) if window
                                  else seen).sum())
            return total

        def tiles(window: int) -> int:
            if kernel:
                return prefill_tile_pairs(lens, width, window, capacity)
            return len(lens) * width * (width + MAX_LABEL_TOKENS)

        window = max(windows, default=0)
        n_window = sum(w > 0 for w in windows)
        n_full = len(windows) - n_window
        counts = {
            "attention_layers_full": n_full,
            "attention_layers_window": n_window,
            "token_pairs_full": pairs(0),
            "token_pairs_window": pairs(window),
            "label_pairs_full": label_pairs(0),
            "label_pairs_window": label_pairs(window),
        }
        counts["token_pairs"] = (n_full * counts["token_pairs_full"]
                                 + n_window * counts["token_pairs_window"])
        counts["token_pairs_tiles"] = sum(tiles(w) for w in windows)
        return counts

    def launch(self, transferred):
        """Dispatch the scoring program (JAX async dispatch: the handle
        holds device arrays, nothing blocks)."""
        texts, prompt_ids, prompt_lens, real = transferred
        extra = {}
        if self.config.mixed_layers:
            extra["probe_rows"] = jnp.asarray(
                np.minimum(self.probe_rows, prompt_ids.shape[0] - 1))
        scores, stats = self._score_labels(
            self.params, prompt_ids, prompt_lens,
            jnp.asarray(self._label_ids), jnp.asarray(self._label_lens),
            prefill_capacity=real[2], **extra,
        )
        return texts, scores, stats, prompt_ids.shape, real

    def submit(self, texts: Sequence[str]):
        return self.launch(self.transfer(self.prepare(texts)))

    def collect(self, handle) -> List[str]:
        texts, scores, stats, (rows, width), real = handle
        best = np.asarray(scores).argmax(axis=1)
        self._count_step(rows, width, real, stats)
        labels = []
        for text, idx in zip(texts, best):
            if not text.strip():
                labels.append("Neutral")  # reference empty-lyric rule
            else:
                labels.append(SUPPORTED_LABELS[int(idx)])
        return labels

    def _count_step(self, rows: int, width: int, real, stats) -> None:
        """What one scoring step computed, into the run's telemetry:
        ``decoder.tokens_real`` / ``decoder.tokens_computed`` (positions
        that went through the layers: the prompt's, and each label's
        tokens but its last, whose forward pass nothing reads and nothing
        runs; computed includes padding: a shorter label's positions up to
        the longest's, and of a prefill that ran on the compact token
        set its ``capacity`` slots in place of ``rows * width``), the
        routed layers' load, the latent cache's size as allocated, and the
        step's shape and real token counts on the span the engine has open
        (``compute``: ``label_positions`` a row ran, ``label_positions_real``
        of them read; equal where the labels are equally long).
        ``moe.assignments`` and the load count the real positions'
        assignments where the prefill ran compact (``moe_capacity`` token
        slots under ``rows * width``);
        ``moe.rows_computed`` counts the rows its grouped matmuls ran,
        fillers and padding included."""
        from music_analyst_tpu.telemetry import get_telemetry

        tel = get_telemetry()
        tokens_real, token_pairs, capacity, attention = real
        # a continuation runs the table's width less one: the positions
        # whose forward some label reads (``score_labels_program``)
        n_labels, label_width = self._label_ids.shape
        label_run = n_labels * (label_width - 1)
        label_real = int(np.maximum(self._label_lens - 1, 0).sum())
        tel.count("decoder.tokens_real", tokens_real + rows * label_real)
        tel.count("decoder.tokens_computed",
                  stream_slots(self.config, (rows, width), capacity)
                  + rows * label_run)
        attrs = dict(rows=rows, width=width, tokens_real=tokens_real,
                     token_pairs=token_pairs, label_positions=label_run,
                     label_positions_real=label_real)
        if "expert_load_max" in stats:
            slots = rows * width if capacity is None else capacity
            attrs.update(self._count_expert_load(stats, slots))
        cfg = self.config
        if attention is not None:
            # ``token_pairs`` of such a model is the sum over its layers,
            # each by its kind's rule (``_attention_counts``)
            attrs.update(attention)
            tel.count("attention.full_tokens",
                      tokens_real * attention["attention_layers_full"])
            tel.count("attention.window_tokens",
                      tokens_real * attention["attention_layers_window"])
            tel.gauge("kv_cache_bytes", int(
                rows * (width + MAX_LABEL_TOKENS) * cfg.n_layers
                * 2 * 2 * cfg.n_kv_heads * cfg.attn_head_dim))
        if cfg.latent_cache:
            tel.gauge("latent_cache_bytes", int(
                rows * (width + MAX_LABEL_TOKENS)
                * (cfg.n_layers - cfg.kda_layers) * 2
                * (cfg.kv_lora_rank + cfg.qk_rope_head_dim)))
        if cfg.kda_layers:
            kda = cfg.kda_layers
            # float32 state a head, and the convolutions' last inputs
            state_bytes = rows * kda * cfg.n_heads * cfg.kda_head_dim * (
                4 * cfg.kda_head_dim + 2 * 3 * (cfg.kda_conv_kernel - 1))
            tel.gauge("recurrent_state_bytes", state_bytes)
            tel.count("kda.tokens", tokens_real * kda)
            tel.count("kda.state_steps", rows * label_run * kda)
            attrs.update(kda_layers=kda, mla_layers=cfg.n_layers - kda,
                         state_bytes=state_bytes)
        if cfg.ssm_layers:
            ssm = cfg.ssm_layers
            inner = cfg.mamba_n_heads * cfg.mamba_head_dim
            # float32 state a head, and the convolution's last inputs
            state_bytes = rows * ssm * (
                4 * inner * cfg.mamba_d_state
                + 2 * (cfg.mamba_conv_kernel - 1)
                * (inner + 2 * cfg.mamba_d_state))
            tel.gauge("recurrent_state_bytes", state_bytes)
            tel.gauge("kv_cache_bytes", int(
                rows * (width + MAX_LABEL_TOKENS) * (cfg.n_layers - ssm)
                * 2 * 2 * cfg.n_kv_heads * cfg.attn_head_dim))
            tel.count("ssm.tokens", tokens_real * ssm)
            tel.count("ssm.state_steps", rows * label_run * ssm)
            attrs.update(ssm_layers=ssm, attention_layers=cfg.n_layers - ssm,
                         state_bytes=state_bytes)
        if cfg.selective_layers:
            sel = cfg.selective_layers
            inner = cfg.mamba_inner_dim
            # a float32 entry a channel and state, and the convolution's
            # last inputs
            state_bytes = rows * sel * inner * (
                4 * cfg.mamba_d_state + 2 * (cfg.mamba_conv_kernel - 1))
            tel.gauge("recurrent_state_bytes", state_bytes)
            tel.gauge("kv_cache_bytes", int(
                rows * (width + MAX_LABEL_TOKENS) * (cfg.n_layers - sel)
                * 2 * 2 * cfg.n_kv_heads * cfg.attn_head_dim))
            tel.count("mamba1.tokens", tokens_real * sel)
            tel.count("mamba1.state_steps", rows * label_run * sel)
            attrs.update(mamba1_layers=sel,
                         attention_layers=cfg.n_layers - sel,
                         slots=stream_slots(cfg, (rows, width), capacity),
                         state_bytes=state_bytes)
        tel.current_span().set(**attrs)

    def _count_expert_load(self, stats, slots: int,
                           pass_assignments: int = 0) -> dict:
        """The ``moe.*`` counters of one step whose prefill ran its expert
        layers on ``slots`` token slots (``stats``: its routed layers'
        load), ``pass_assignments`` more having run uncompacted behind it;
        returns what goes on the ``compute`` span.  ``moe.rows_kernel``
        counts the prefill's rows that went through the grouped matmul
        kernel (``models/moe.grouped_kernel_tiles``: one device, a shape in
        its regime), beside ``moe.rows_computed``."""
        from music_analyst_tpu.models.moe import (
            grouped_call_tokens,
            grouped_kernel_tiles,
        )
        from music_analyst_tpu.telemetry import get_telemetry

        tel, cfg = get_telemetry(), self.config
        load_max = np.asarray(stats["expert_load_max"], np.float64)
        load_mean = np.asarray(stats["expert_load_mean"], np.float64)
        # the load is the held experts' (all of them, but for a chip that
        # holds a share of each layer's)
        held = int(load_mean.sum() * self.config.experts_held_count)
        attrs = {"moe_capacity": slots, "expert_load_max_over_mean": [
            round(float(m / max(a, 1e-9)), 4)
            for m, a in zip(load_max, load_mean)]}
        if self.config.experts_held is None:
            assignments = held
        else:
            # every real position's choices, held here or not; of the label
            # continuations the positions whose forward is read
            assignments = int(np.asarray(stats["assignments"]).sum())
            held_labels = int(stats.get("label_assignments_held", 0))
            tel.count("moe.assignments_held", held + held_labels)
            attrs.update(assignments=assignments, assignments_held=held,
                         label_assignments_held=held_labels)
        tel.count("moe.assignments", assignments + pass_assignments)
        prefill_rows = slots * cfg.moe_top_k * len(load_max)
        tel.count("moe.rows_computed", prefill_rows + pass_assignments)
        kernel = not _partitioned(self.mesh) and grouped_kernel_tiles(
            grouped_call_tokens(slots, cfg.experts_held is not None),
            cfg.moe_top_k, cfg.experts_held_count, cfg.dim,
            cfg.moe_hidden_dim)
        tel.count("moe.rows_kernel", prefill_rows if kernel else 0)
        tel.count("moe.expert_load_max", int(load_max.sum()))
        tel.count("moe.expert_load_mean", int(load_mean.sum()))
        return attrs

    def classify_batch(self, texts: Sequence[str]) -> List[str]:
        return self.collect(self.submit(texts))

    def generate(
        self, prompt: str, max_new_tokens: int = 16
    ) -> str:
        """Greedy generation (API-parity path; label scoring is preferred)."""
        ids, lens = self.tokenizer.encode_batch([prompt], self.max_prompt_len)
        S = self.max_prompt_len
        caches = init_caches(self.config, 1, S + max_new_tokens)
        positions = jnp.arange(S)[None, :]
        mask = causal_mask(S, S + max_new_tokens, 0) & jnp.pad(
            padding_mask(jnp.asarray(lens), S),
            ((0, 0), (0, 0), (0, 0), (0, max_new_tokens)),
        )
        logits, caches = self.model.apply(
            {"params": self.params}, jnp.asarray(ids), positions, mask, caches,
            last_position=jnp.asarray(lens, jnp.int32) - 1,
            row_lengths=jnp.asarray(lens, jnp.int32),
        )
        caches = [c.with_length(int(lens[0])) for c in caches]
        token = jnp.argmax(logits[:, 0], axis=-1)
        out_tokens = []
        position = jnp.asarray([int(lens[0])], jnp.int32)
        for _ in range(max_new_tokens):
            out_tokens.append(int(token[0]))
            if out_tokens[-1] == getattr(self.tokenizer, "eos_id",
                                         ByteTokenizer.EOS):
                break
            token, caches = self._decode_step(
                self.params, token[:, None], position, caches
            )
            position = position + 1
        return self.tokenizer.decode(out_tokens)

    def generate_batch(
        self, prompts: Sequence[str], max_new_tokens: int = 16,
        early_exit: bool = True,
    ) -> List[str]:
        """Greedy generation for a whole batch in ONE compiled program.

        Prefill and all ``max_new_tokens`` decode steps run inside a single
        jit (``lax.scan`` over the KV cache) — no per-token host↔device
        round-trips, unlike :meth:`generate`'s explicit step loop (kept for
        API parity and as the differential oracle).  ``early_exit`` stops
        decoding once every row has emitted EOS (identical outputs either
        way; ``False`` keeps the always-``max_new_tokens`` scan as the
        equivalence oracle).  The continuous-batching sibling (slots that
        free mid-flight) is ``serving/decode_runtime.
        generate_batch_continuous``.
        """
        ids, lens = self.tokenizer.encode_batch(prompts, self.max_prompt_len)
        ids, lens = self._trim_prompt_pad(ids, lens)
        tokens = np.asarray(
            self._generate_scan(
                self.params, jnp.asarray(ids), jnp.asarray(lens),
                max_new_tokens, early_exit=early_exit,
                prefill_capacity=_prefill_capacity(
                    self.config, self.mesh, lens, ids.shape),
            )
        )
        eos = self.tokenizer.eos_id
        outs = []
        for row in tokens:
            ids_out = []
            for t in row:
                if t == eos:
                    break
                ids_out.append(int(t))
            outs.append(self.tokenizer.decode(ids_out))
        return outs

    def classify_by_generation(self, text: str) -> str:
        """Reference-semantics path: generate text, normalise first token."""
        return normalise_label(self.generate(zero_shot_prompt(text)))

    def classify_batch_by_generation(
        self, texts: Sequence[str]
    ) -> List[str]:
        """Reference generation semantics at batch speed: free-text decode
        (one scan-jitted program for the whole batch) then the shared label
        normalizer (``scripts/sentiment_classifier.py:102-108``, empty-
        output crash fixed)."""
        # Same token budget as generate()'s default so the batch path and
        # the single-song reference path yield identical labels.
        generations = self.generate_batch(
            [zero_shot_prompt(t) for t in texts], max_new_tokens=16)
        return [
            "Neutral" if not text.strip() else normalise_label(gen)
            for text, gen in zip(texts, generations)
        ]
