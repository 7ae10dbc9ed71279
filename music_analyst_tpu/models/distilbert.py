"""DistilBERT-sst2-style encoder classifier (BASELINE.json config[2]).

The batched on-device replacement for the reference's per-song HTTP loop
(``scripts/sentiment_classifier.py:85-100``): a 6-layer post-LN transformer
encoder with learned positions and a CLS head, matching the
``distilbert-base-uncased-finetuned-sst-2-english`` architecture so real
checkpoints drop in when available (``load_hf_torch_checkpoint``), while
random init keeps the pipeline, sharding, and benchmarks runnable in this
zero-egress environment.

Label contract: sst2 is 2-class (negative/positive).  The mapping onto the
reference's 3-label API (SURVEY.md §7 step 5 — "documented mapping") is
confidence-thresholded: ``max softmax prob < neutral_threshold`` →
``Neutral``, else argmax → ``Positive``/``Negative``.
"""

from __future__ import annotations

import dataclasses
import os
from typing import Any, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from flax import linen as nn

from music_analyst_tpu.models.backend import ClassifierBackend
from music_analyst_tpu.models.layers import (
    GeluMLP,
    MultiHeadAttention,
    padding_mask,
    segment_mask,
)
from music_analyst_tpu.models.tokenization import resolve_bert_tokenizer
from music_analyst_tpu.ops.whole_row_attention import whole_row_block_rows


# HF DistilBERT hardcodes nn.LayerNorm(eps=1e-12) (flax defaults to
# 1e-6); match it exactly so real checkpoints reproduce the reference
# forward — the oracle tests share this constant.
LN_EPS = 1e-12


@dataclasses.dataclass(frozen=True)
class DistilBertConfig:
    vocab_size: int = 30522
    dim: int = 768
    n_layers: int = 6
    n_heads: int = 12
    hidden_dim: int = 3072
    max_positions: int = 512
    n_classes: int = 2
    dtype: str = "bfloat16"
    # "flash" = Pallas blocked attention (padding-mask path); max_len must
    # divide the kernel block size.
    attn_impl: str = "dense"
    # "int8" = dynamic-quant projections/MLP on the MXU int8 path
    # (ops/quant.py; ~2.1x bf16 matmul throughput per the roofline suite).
    # Inference-only; small logit perturbation bounded by tests/test_quant.py.
    quant: str = "none"
    # "int8"/"int4" = stored weight-quantized projection/MLP kernels
    # (QuantizedParam leaves; ops/quant.py).  Embeddings, norms, and the
    # classifier heads stay float.  Mutually exclusive with `quant`.
    weight_quant: str = "none"

    def __post_init__(self):
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

    @classmethod
    def tiny(cls) -> "DistilBertConfig":
        return cls(vocab_size=1024, dim=64, n_layers=2, n_heads=4,
                   hidden_dim=128, max_positions=128)


class TransformerBlock(nn.Module):
    """Post-LN block: x → LN(x + attn(x)) → LN(· + mlp(·))."""

    config: DistilBertConfig
    mesh: Any = None

    @nn.compact
    def __call__(self, x, mask, lengths=None, segment_ids=None):
        cfg = self.config
        dtype = jnp.dtype(cfg.dtype)
        with jax.named_scope("encoder.attention"):
            attn_out = MultiHeadAttention(
                n_heads=cfg.n_heads, dtype=dtype, attn_impl=cfg.attn_impl,
                # HF DistilBERT q/k/v/out projections have biases
                use_bias=True,
                quant=cfg.quant, weight_quant=cfg.weight_quant,
                mesh=self.mesh, name="attention",
            )(x, mask=None if cfg.attn_impl == "flash" else mask,
              lengths=lengths,
              segment_ids=segment_ids if cfg.attn_impl == "flash" else None)
            x = nn.LayerNorm(
                name="sa_layer_norm", dtype=dtype, epsilon=LN_EPS
            )(x + attn_out)
        with jax.named_scope("encoder.ffn"):
            mlp_out = GeluMLP(cfg.hidden_dim, dtype=dtype, quant=cfg.quant,
                              weight_quant=cfg.weight_quant, name="ffn")(x)
            return nn.LayerNorm(
                name="output_layer_norm", dtype=dtype, epsilon=LN_EPS
            )(x + mlp_out)


class DistilBertEncoder(nn.Module):
    config: DistilBertConfig
    # The mesh of a sharded forward (None on one device); only the
    # whole-row attention kernel reads it, to run per shard.
    mesh: Any = None

    @nn.compact
    def __call__(self, token_ids, lengths, positions=None, segment_ids=None):
        """Encode ``[B, S]`` ids.

        Flat mode (``positions``/``segment_ids`` omitted): positions are
        ``0..S-1`` and masking is key-padding from ``lengths`` — the
        original single-lyric-per-row contract.

        Packed mode (SURVEY §7 "packed batching"): rows carry several
        lyrics back to back.  ``segment_ids`` ``[B, S]`` labels each token
        with its lyric (0 = padding) and attention is restricted to
        same-segment pairs, so lyrics sharing a row can never see each
        other; ``positions`` ``[B, S]`` restart at every segment boundary
        so each lyric receives exactly the position embeddings it would
        have gotten in its own row.
        """
        cfg = self.config
        dtype = jnp.dtype(cfg.dtype)
        with jax.named_scope("encoder.embed"):
            if positions is None:
                positions = jnp.arange(token_ids.shape[1])[None, :]
            tok = nn.Embed(cfg.vocab_size, cfg.dim, dtype=dtype,
                           name="word_embeddings")(token_ids)
            pos = nn.Embed(cfg.max_positions, cfg.dim, dtype=dtype,
                           name="position_embeddings")(positions)
            x = nn.LayerNorm(
                name="embed_layer_norm", dtype=dtype, epsilon=LN_EPS
            )(tok + pos)
        if segment_ids is not None:
            # Block-diagonal: token pairs attend iff same segment.  The
            # dense impl gets a mask array; the flash kernel takes the
            # segment ids natively (ops/flash_attention.py segment mode).
            # Padding (segment 0) forms its own group, so a fully padded
            # tail (or row) either softmaxes over uniform masked logits
            # (dense — finite fill keeps it NaN-free) or outputs zeros
            # (flash guarded denominator); it is never gathered by the
            # head either way.
            mask = (
                None if cfg.attn_impl == "flash"
                else segment_mask(segment_ids)
            )
        elif cfg.attn_impl == "dense" and whole_row_block_rows(
            token_ids.shape[1], cfg.n_heads, cfg.dim // cfg.n_heads, dtype,
            self.mesh,
        ):
            # Key padding only, no cache, S_q == S_kv, and a whole key row
            # of every head fits VMEM: `lengths` says all the mask would,
            # and without a mask array MultiHeadAttention's dense branch
            # runs the whole-row kernel (ops/whole_row_attention.py) — the
            # [B, H, S, S] scores never reach HBM.  The choice is made
            # here, from the shape, and nowhere else; longer rows build
            # the mask below and lower to the program they always did.
            mask = None
        else:
            mask = padding_mask(lengths, token_ids.shape[1])
        # CONTRACT: with cfg.attn_impl == "flash", attention masking is
        # derived from `lengths` + optional `segment_ids` (key padding +
        # block-diagonal); the mask array is only consumed by the dense
        # impl.
        for i in range(cfg.n_layers):
            x = TransformerBlock(cfg, self.mesh, name=f"layer_{i}")(
                x, mask, lengths, segment_ids=segment_ids
            )
        return x


class DistilBertForSentiment(nn.Module):
    """Encoder + CLS head → class logits.

    Flat mode returns ``[B, n_classes]`` from each row's position-0 CLS.
    Packed mode (``cls_index`` ``[B, K]`` = the CLS offset of each of up
    to K lyrics per row) returns ``[B, K, n_classes]`` — the head runs on
    every segment's own CLS vector; unused slots (index clamped into the
    row) produce garbage logits the caller masks out.
    """

    config: DistilBertConfig
    mesh: Any = None

    @nn.compact
    def __call__(self, token_ids, lengths, positions=None, segment_ids=None,
                 cls_index=None):
        cfg = self.config
        dtype = jnp.dtype(cfg.dtype)
        x = DistilBertEncoder(cfg, self.mesh, name="encoder")(
            token_ids, lengths, positions=positions, segment_ids=segment_ids
        )
        with jax.named_scope("encoder.head"):
            if cls_index is None:
                cls = x[:, 0]  # [CLS]
            else:
                cls = jnp.take_along_axis(
                    x, cls_index[:, :, None].astype(jnp.int32), axis=1
                )                                           # [B, K, D]
            h = nn.Dense(cfg.dim, dtype=dtype, name="pre_classifier")(cls)
            h = nn.relu(h)
            return nn.Dense(cfg.n_classes, dtype=jnp.float32,
                            name="classifier")(h)


def iter_hf_param_units(params, path: str, mmap: bool = False):
    """Stream an HF DistilBERT torch ``state_dict`` as layer-sized units.

    Yields ``(unit_name, [("/"-joined tree path, np.ndarray), …])`` —
    embeddings, then one unit per transformer layer, then the classifier
    head — in the layout ``load_quantized_params`` consumes, so the
    quantize-on-load path holds at most one unit of float tensors at a
    time.  Kernel matrices transpose (torch Linear stores ``[out, in]``),
    attention projections (weights AND biases) reshape to the
    ``[dim, heads, head_dim]`` head layout.  Every checkpoint tensor must
    be consumed — leftover keys raise at the end of the stream, so a
    checkpoint with unexpected structure can never silently half-load.
    ``params`` supplies shapes only; ``ShapeDtypeStruct`` trees work.
    """
    import torch

    try:
        sd = torch.load(path, map_location="cpu", weights_only=True,
                        mmap=mmap)
    except (RuntimeError, ValueError, TypeError):
        # Non-zipfile (legacy) serialization or older torch: mmap
        # unsupported — fall back to an eager read.
        sd = torch.load(path, map_location="cpu", weights_only=True)
    enc_shapes = params["encoder"]
    cfg_heads = enc_shapes["layer_0"]["attention"]["q_proj"]["kernel"].shape[1]
    dim = enc_shapes["word_embeddings"]["embedding"].shape[1]
    head_dim = dim // cfg_heads
    consumed = set()

    def t(name):
        consumed.add(name)
        return np.asarray(sd[name].numpy())

    yield "embeddings", [
        ("encoder/word_embeddings/embedding",
         t("distilbert.embeddings.word_embeddings.weight")),
        ("encoder/position_embeddings/embedding",
         t("distilbert.embeddings.position_embeddings.weight")),
        ("encoder/embed_layer_norm/scale",
         t("distilbert.embeddings.LayerNorm.weight")),
        ("encoder/embed_layer_norm/bias",
         t("distilbert.embeddings.LayerNorm.bias")),
    ]
    n_layers = sum(1 for k in enc_shapes if k.startswith("layer_"))
    for i in range(n_layers):
        hf = f"distilbert.transformer.layer.{i}"
        p = f"encoder/layer_{i}"
        leaves = []
        for ours, theirs in (("q_proj", "q_lin"), ("k_proj", "k_lin"),
                             ("v_proj", "v_lin")):
            w = t(f"{hf}.attention.{theirs}.weight").T  # [in, out]
            leaves.append((f"{p}/attention/{ours}/kernel",
                           w.reshape(dim, cfg_heads, head_dim)))
            leaves.append((f"{p}/attention/{ours}/bias",
                           t(f"{hf}.attention.{theirs}.bias").reshape(
                               cfg_heads, head_dim)))
        leaves.append((f"{p}/attention/o_proj/kernel",
                       t(f"{hf}.attention.out_lin.weight").T.reshape(
                           cfg_heads, head_dim, dim)))
        leaves.append((f"{p}/attention/o_proj/bias",
                       t(f"{hf}.attention.out_lin.bias")))
        leaves.append((f"{p}/sa_layer_norm/scale",
                       t(f"{hf}.sa_layer_norm.weight")))
        leaves.append((f"{p}/sa_layer_norm/bias",
                       t(f"{hf}.sa_layer_norm.bias")))
        leaves.append((f"{p}/ffn/lin1/kernel", t(f"{hf}.ffn.lin1.weight").T))
        leaves.append((f"{p}/ffn/lin1/bias", t(f"{hf}.ffn.lin1.bias")))
        leaves.append((f"{p}/ffn/lin2/kernel", t(f"{hf}.ffn.lin2.weight").T))
        leaves.append((f"{p}/ffn/lin2/bias", t(f"{hf}.ffn.lin2.bias")))
        leaves.append((f"{p}/output_layer_norm/scale",
                       t(f"{hf}.output_layer_norm.weight")))
        leaves.append((f"{p}/output_layer_norm/bias",
                       t(f"{hf}.output_layer_norm.bias")))
        yield f"layer_{i}", leaves
    yield "head", [
        ("pre_classifier/kernel", t("pre_classifier.weight").T),
        ("pre_classifier/bias", t("pre_classifier.bias")),
        ("classifier/kernel", t("classifier.weight").T),
        ("classifier/bias", t("classifier.bias")),
    ]
    # Non-parameter buffers some transformers versions serialize.
    ignorable = {k for k in sd if k.endswith("position_ids")}
    leftovers = set(sd) - consumed - ignorable
    if leftovers:
        raise ValueError(
            "checkpoint keys not consumed by the DistilBERT mapping: "
            + ", ".join(sorted(leftovers)[:8])
        )


def load_hf_torch_checkpoint(params, path: str):
    """Map an HF DistilBERT torch ``state_dict`` onto the Flax params.

    Eager wrapper over ``iter_hf_param_units`` — see it for the mapping
    contract (transposes, head-layout reshapes, consumed-keys check).
    """
    new = jax.tree_util.tree_map(lambda x: x, params)  # shallow copy
    for _, leaves in iter_hf_param_units(params, path):
        for tree_path, arr in leaves:
            parts = tree_path.split("/")
            node = new
            for part in parts[:-1]:
                node = node[part]
            node[parts[-1]] = arr
    return new


def derive_length_buckets(
    lengths,
    max_len: int,
    min_share: float = 0.05,
    floor: int = 16,
) -> Tuple[int, ...]:
    """Pick power-of-two sequence buckets from an observed length sample.

    Data-driven default for the SURVEY §7 "ragged lyrics" lever: each kept
    bucket must absorb at least ``min_share`` of the sampled rows — a bucket
    costs one compiled program per batch shape, and one holding few rows
    saves negligible FLOPs.  Rows skipped by a dropped bucket roll upward
    into the next candidate.  Returns ``()`` when the sample is dominated
    by full-length rows (real lyric corpora mostly are at ``max_len`` 128):
    the flat path is then already optimal, and auto mode stays flat.
    """
    lengths = np.asarray(lengths)
    out = []
    if lengths.size:
        prev = 0
        b = floor
        while b < max_len:
            share = float(((lengths > prev) & (lengths <= b)).mean())
            if share >= min_share:
                out.append(b)
                prev = b
            b <<= 1
    return tuple(out)


def pack_segments(
    lengths, capacity: int
) -> Tuple[np.ndarray, np.ndarray, np.ndarray, np.ndarray]:
    """Best-fit-decreasing bin packing of per-lyric token lengths.

    The SURVEY §7 "packed batching" lever: several short lyrics share one
    ``capacity``-wide row instead of each padding its own row out (the
    reference pads nothing because it classifies one song per blocking
    HTTP call, ``scripts/sentiment_classifier.py:144-154``; a batched
    device path pays for padding in real FLOPs).  Best-fit over the open
    rows' remaining capacities (binary search per lyric, ~11/9·OPT worst
    case) keeps host cost at O(n log n) for 8k-row batches.

    Returns ``(bin_of, slot_of, starts, row_len)``: input ``i`` becomes
    segment ``slot_of[i]`` of packed row ``bin_of[i]``; ``starts[p, k]``
    is the token offset of each row's ``k``-th segment (``capacity``
    sentinel for unused slots — never a valid offset); ``row_len[p]`` is
    the occupied prefix of each row.
    """
    import bisect

    lengths = np.asarray(lengths, dtype=np.int64)
    if lengths.size and (lengths <= 0).any():
        # A zero-length segment would collide with the sentinel (or with a
        # neighbor's offset) and gather another lyric's CLS as its own.
        # Unreachable via the classifier (every tokenizer emits ≥ 2 ids,
        # CLS+SEP), but the helper is public — enforce the precondition.
        raise ValueError("pack_segments requires every length > 0")
    if lengths.size and int(lengths.max()) > capacity:
        raise ValueError(
            f"segment length {int(lengths.max())} exceeds capacity "
            f"{capacity}"
        )
    n = int(lengths.size)
    bin_of = np.zeros(n, np.int64)
    slot_of = np.zeros(n, np.int64)
    rems: list = []       # open-row remaining capacities, ascending
    rem_bin: list = []    # parallel row ids
    rows: list = []       # input indices per row, placement order
    for i in np.argsort(-lengths, kind="stable"):
        need = int(lengths[i])
        j = bisect.bisect_left(rems, need)
        if j == len(rems):
            rem, b = capacity, len(rows)
            rows.append([])
        else:
            rem, b = rems.pop(j), rem_bin.pop(j)
        bin_of[i] = b
        slot_of[i] = len(rows[b])
        rows[b].append(int(i))
        rem -= need
        j = bisect.bisect_left(rems, rem)
        rems.insert(j, rem)
        rem_bin.insert(j, b)
    n_rows = len(rows)
    n_slots = max((len(r) for r in rows), default=0)
    starts = np.full((n_rows, n_slots), capacity, np.int64)
    row_len = np.zeros(n_rows, np.int64)
    for b, members in enumerate(rows):
        offset = 0
        for k, i in enumerate(members):
            starts[b, k] = offset
            offset += int(lengths[i])
        row_len[b] = offset
    return bin_of, slot_of, starts, row_len


class DistilBertClassifier(ClassifierBackend):
    """Batched data-parallel sentiment backend.

    ``neutral_threshold`` (default 0.6) is the 2→3-label calibration knob:
    the sst2 head is binary, so its max softmax prob is ≥ 0.5 by
    construction, and the band [0.5, threshold) — a logit margin under
    ``ln(threshold/(1-threshold))``, ≈0.405 at 0.6 — is mapped to
    ``Neutral``.  This mirrors the reference's behavior of bucketing every
    non-committal model answer into Neutral (``utils/labels.py`` /
    ``scripts/sentiment_classifier.py:101-107``): 0.6 keeps near-equipoise
    lyrics out of Positive/Negative while letting any clear sst2 verdict
    through.  It is a deployment knob, not a learned constant — the tested
    contract (``tests/test_models.py``) is monotonicity: threshold 0.5
    never yields Neutral on non-empty text, threshold 1.0 always does.
    """

    name = "distilbert"

    # sst2 head order in the HF checkpoint: [NEGATIVE, POSITIVE]
    _CLASS_LABELS = ("Negative", "Positive")

    def __init__(
        self,
        config: Optional[DistilBertConfig] = None,
        checkpoint_path: Optional[str] = None,
        max_len: int = 128,
        neutral_threshold: float = 0.6,
        mesh=None,
        seed: int = 0,
        vocab_path: Optional[str] = None,
        length_buckets: Optional[Sequence[int]] = None,
        packed: bool = False,
        wq_cache_dir: Optional[str] = None,
    ) -> None:
        self.config = config or DistilBertConfig()
        self.max_len = max_len
        self.neutral_threshold = neutral_threshold
        self.packed = bool(packed)
        if self.packed and length_buckets:
            # Packing already right-sizes padding within full-width rows;
            # composing the two would bucket *rows of several lyrics* by
            # the wrong lengths.  One lever at a time.  (Flash attention
            # DOES compose: the kernel takes segment ids natively.)
            raise ValueError(
                "packed=True cannot be combined with length_buckets"
            )
        # "auto" defers to the first submitted batch's length distribution
        # (resolved via derive_length_buckets); a sequence is validated now.
        if isinstance(length_buckets, str):
            if length_buckets != "auto":
                # Catch the CLI syntax leaking into the API: tuple("32,64")
                # would otherwise iterate characters and raise nonsense.
                raise ValueError(
                    "length_buckets must be 'auto' or a sequence of ints, "
                    f"got the string {length_buckets!r}"
                )
            self.length_buckets = "auto"
        else:
            self.length_buckets = self._check_buckets(length_buckets, max_len)
        self.tokenizer = resolve_bert_tokenizer(
            vocab_path, vocab_size=self.config.vocab_size
        )
        self.model = DistilBertForSentiment(self.config, mesh)
        # Parameters do not depend on the mesh, and the one-row dummy
        # batch cannot be split over it: initialise without.
        init = DistilBertForSentiment(self.config).init
        dummy = (
            jnp.zeros((1, max_len), jnp.int32),
            jnp.ones((1,), jnp.int32),
        )
        wq = self.config.weight_quant
        if checkpoint_path and wq != "none":
            # Streaming quantize-on-load: the float tree is never
            # materialized — only per-unit shapes via eval_shape, then the
            # layer-by-layer quantize→H2D pipeline (engines/checkpoint.py).
            from music_analyst_tpu.engines import wq_cache
            from music_analyst_tpu.engines.checkpoint import (
                load_quantized_params,
            )
            from music_analyst_tpu.ops.quant import WQ_DEFAULT_GROUP

            params_shape = jax.eval_shape(
                init, jax.random.key(seed), *dummy
            )["params"]
            cache_dir = wq_cache.resolve_cache_dir(wq_cache_dir)
            cache_key = (
                wq_cache.wq_key(checkpoint_path, "distilbert", wq,
                                WQ_DEFAULT_GROUP)
                if cache_dir else None
            )
            self.params = load_quantized_params(
                params_shape,
                lambda: iter_hf_param_units(
                    params_shape, checkpoint_path, mmap=True
                ),
                wq,
                group_size=WQ_DEFAULT_GROUP,
                mesh=mesh,
                cache_dir=cache_dir,
                cache_key=cache_key,
            )
            self.pretrained = True
        else:
            self.params = init(jax.random.key(seed), *dummy)["params"]
            self.pretrained = False
            if checkpoint_path:
                self.params = load_hf_torch_checkpoint(
                    self.params, checkpoint_path
                )
                self.pretrained = True
            if wq != "none":
                from music_analyst_tpu.ops.quant import (
                    WQ_DEFAULT_GROUP,
                    quantize_tree,
                )

                self.params = quantize_tree(self.params, wq, WQ_DEFAULT_GROUP)
        if mesh is not None:
            from jax.sharding import NamedSharding, PartitionSpec as P

            from music_analyst_tpu.parallel.sharding import shard_params

            # Megatron-style TP rules; axes absent from the mesh prune to
            # replication, so the same call serves dp-only and dp×tp.
            self.params = shard_params(self.params, mesh)
            self._data_sharding = NamedSharding(mesh, P("dp"))
        else:
            self._data_sharding = None
        self.mesh = mesh

        from music_analyst_tpu.profiling.compile import profiled_jit
        from music_analyst_tpu.runtime.wire import forward_donation_kwargs

        def _forward(params, token_ids, lengths):
            # ids/lengths may arrive int16 (see _wire_dtype/_index_dtype)
            # — widen on device.
            logits = self.model.apply(
                {"params": params},
                token_ids.astype(jnp.int32),
                lengths.astype(jnp.int32),
            )
            with jax.named_scope("encoder.head"):
                probs = jax.nn.softmax(logits, axis=-1)
                return jnp.argmax(logits, axis=-1), jnp.max(probs, axis=-1)

        # Steady-state forwards donate their input batch: the H2D staging
        # buffer is dead the moment the widened copy exists, so XLA may
        # reuse its space for temporaries instead of pinning ~depth+1
        # staged batches live across the step (no-op on the CPU test mesh,
        # see forward_donation_kwargs).
        self._forward = profiled_jit(
            _forward, name="distilbert_forward",
            **forward_donation_kwargs(1, 2),
        )

        def _forward_packed(params, token_ids, starts, row_len):
            """Packed rows: expand the compact per-segment wire format
            (``starts`` [P,K] with ``S`` sentinel + ``row_len`` [P]) into
            segment ids / restarted positions ON DEVICE — the host ships
            ~2 extra bytes per segment instead of 2 extra arrays of S
            bytes per row (what the H2D saving buys: not measured on
            this host)."""
            seq = token_ids.shape[1]
            ids = token_ids.astype(jnp.int32)
            st = starts.astype(jnp.int32)                    # [P, K]
            s_axis = jnp.arange(seq, dtype=jnp.int32)
            started = st[:, :, None] <= s_axis[None, None, :]  # [P, K, S]
            # Segment id = number of starts at or before s (starts[0] is
            # always 0, sentinel starts never fire) → 1..K; padding tail
            # (s ≥ row_len) and all-pad rows drop to segment 0, which
            # never equals a real segment in the block-diagonal mask.
            seg = started.sum(axis=1, dtype=jnp.int32)         # [P, S]
            valid = s_axis[None, :] < row_len[:, None].astype(jnp.int32)
            seg = jnp.where(valid, seg, 0)
            last_start = jnp.max(
                jnp.where(started, st[:, :, None], -1), axis=1
            )                                                  # [P, S]
            positions = s_axis[None, :] - jnp.maximum(last_start, 0)
            logits = self.model.apply(
                {"params": params},
                ids,
                row_len.astype(jnp.int32),
                positions=positions,
                segment_ids=seg,
                cls_index=jnp.minimum(st, seq - 1),
            )                                                  # [P, K, C]
            with jax.named_scope("encoder.head"):
                probs = jax.nn.softmax(logits, axis=-1)
                return jnp.argmax(logits, axis=-1), jnp.max(probs, axis=-1)

        self._forward_packed = profiled_jit(
            _forward_packed, name="distilbert_forward_packed",
            **forward_donation_kwargs(1, 2, 3),
        )
        # Token ids are the host→device payload, and every BERT-sized
        # vocab fits int16, halving the bytes on the wire (what that buys
        # end to end: not measured on this host).  Lossless: the cast
        # back to int32 happens on device inside the jit.
        # Sized from the TOKENIZER's id range, not the model config: a
        # supplied vocab.txt (MUSICAAL_BERT_VOCAB) can be larger than the
        # config vocab, and an int16 wire would silently wrap its ids.
        wire_vocab = max(self.config.vocab_size, self.tokenizer.vocab_size)
        self._wire_dtype = np.int16 if wire_vocab <= (1 << 15) else np.int32
        # Packed-row segment starts / row lengths are positions in
        # [0, max_len] (max_len itself is the empty-slot sentinel), so the
        # same wire-narrowing applies — conditioned on max_len, not the
        # vocab: a long-context config must not wrap its offsets.
        self._index_dtype = np.int16 if max_len < (1 << 15) else np.int32

    @classmethod
    def from_pretrained_or_random(cls, model: str, **kwargs):
        """Resolve ``--model distilbert[...]`` to a backend instance.

        Checkpoint lookup: explicit kwarg, else ``$MUSICAAL_DISTILBERT_CKPT``.
        Without a checkpoint the model runs with random weights (documented:
        throughput/sharding are exercised; accuracy needs real weights).
        """
        ckpt = kwargs.pop("checkpoint_path", None) or os.environ.get(
            "MUSICAAL_DISTILBERT_CKPT"
        )
        config = kwargs.pop("config", None)
        # Suffixes compose in any order (distilbert-tiny-int8-packed ==
        # distilbert-tiny-packed-int8): strip to fixpoint.
        quant, tiny = "none", False
        stripped = True
        while stripped:
            stripped = True
            if model.endswith("-packed"):
                model = model[: -len("-packed")]
                kwargs.setdefault("packed", True)
            elif model.endswith("-int8"):
                model, quant = model[: -len("-int8")], "int8"
            elif model.endswith("-tiny"):
                model, tiny = model[: -len("-tiny")], True
            else:
                stripped = False
        if tiny:
            config = config or DistilBertConfig.tiny()
        if quant != "none":
            config = dataclasses.replace(
                config or DistilBertConfig(), quant=quant
            )
        weight_quant = kwargs.pop("weight_quant", "none") or "none"
        if weight_quant != "none":
            config = dataclasses.replace(
                config or DistilBertConfig(), weight_quant=weight_quant
            )
        return cls(config=config, checkpoint_path=ckpt, **kwargs)

    @staticmethod
    def _check_buckets(
        buckets: Optional[Sequence[int]], max_len: int
    ) -> Optional[Tuple[int, ...]]:
        """Validate ascending sequence-length buckets; ``max_len`` is always
        the (implicit) last bucket so every row has a home."""
        if not buckets:
            return None
        out = sorted(set(int(b) for b in buckets) | {max_len})
        if out[0] < 8:
            raise ValueError(f"length bucket {out[0]} is below the floor of 8")
        if out[-1] > max_len:
            raise ValueError(
                f"length bucket {out[-1]} exceeds max_len={max_len}"
            )
        return tuple(out)

    @staticmethod
    def _round_rows(n: int) -> int:
        """Next power of two (≥16): bounds the number of compiled batch
        shapes per bucket while keeping row padding ≤ 2×."""
        from music_analyst_tpu.utils.shapes import round_pow2

        return round_pow2(n, 16)

    def _pad_batch(self, batch: np.ndarray, lengths: np.ndarray):
        """Pad the row count so the batch splits evenly over the dp axis."""
        if self.mesh is None:
            return batch, lengths, batch.shape[0]
        shards = self.mesh.shape.get("dp", 1)
        n = batch.shape[0]
        padded = -(-n // shards) * shards
        if padded != n:
            batch = np.pad(batch, ((0, padded - n), (0, 0)))
            lengths = np.pad(lengths, (0, padded - n), constant_values=1)
        return batch, lengths, n

    def _record_mesh_collectives(self, rows: int, seq: int) -> None:
        """Analytic per-step collective bytes for the sharded forward.

        Under tensor parallelism every encoder block ends its attention
        and MLP halves with a ``psum`` of the [rows/dp, seq, dim] bf16
        activations over the tp axis (Megatron pattern — 2 all-reduces
        per layer); the dp result gather moves each shard's class/
        confidence rows (~8 B/row) back together.  Pure estimate from
        shapes; measured collective time comes from a profiler trace.
        """
        if self.mesh is None:
            return
        from music_analyst_tpu.profiling.collectives import record_collective

        dp = self.mesh.shape.get("dp", 1)
        tp = self.mesh.shape.get("tp", 1)
        if tp > 1:
            act_bytes = (rows // max(dp, 1)) * seq * self.config.dim * 2
            record_collective(
                "sentiment.tp_allreduce", "psum",
                payload_bytes=act_bytes, n_devices=tp, axis="tp",
                count=2 * self.config.n_layers,
            )
        if dp > 1:
            record_collective(
                "sentiment.result_gather", "all_gather",
                payload_bytes=(rows // dp) * 8, n_devices=dp, axis="dp",
            )

    def _plan_flat(self, token_ids: np.ndarray, lengths: np.ndarray):
        """Host-side plan for one full-width forward: pad for the dp axis
        and cast to wire dtypes.  ``(gather, n, arrays)`` — no device."""
        from music_analyst_tpu.runtime.wire import narrow_lengths

        token_ids, lengths, n = self._pad_batch(token_ids, lengths)
        token_ids = np.asarray(token_ids, dtype=self._wire_dtype)
        lengths = narrow_lengths(lengths, self.max_len)
        return None, n, (token_ids, lengths)

    def _plan_packed(self, token_ids: np.ndarray, lengths: np.ndarray):
        """Host-side plan for packed rows: bin-pack lyrics into shared
        rows, cast the compact wire format.  Row and slot counts round to
        powers of two (shapes stay bounded); the plan carries the
        ``(bin_of, slot_of)`` gather map back to :meth:`collect`."""
        from music_analyst_tpu.runtime.wire import narrow_lengths
        from music_analyst_tpu.utils.shapes import round_pow2

        n = token_ids.shape[0]
        if n == 0:
            return []
        bin_of, slot_of, starts, row_len = pack_segments(lengths, self.max_len)
        n_rows, n_slots = starts.shape
        rows_padded = self._round_rows(n_rows)
        if self.mesh is not None:
            shards = self.mesh.shape.get("dp", 1)
            rows_padded = -(-rows_padded // shards) * shards
        slots_padded = round_pow2(max(n_slots, 1), 4)
        ids = np.zeros((rows_padded, self.max_len), token_ids.dtype)
        st = np.full((rows_padded, slots_padded), self.max_len, np.int64)
        st[:n_rows, :n_slots] = starts
        rl = np.zeros((rows_padded,), np.int64)
        rl[:n_rows] = row_len
        for i in range(n):
            offset = starts[bin_of[i], slot_of[i]]
            ids[bin_of[i], offset : offset + lengths[i]] = token_ids[
                i, : lengths[i]
            ]
        ids = np.asarray(ids, dtype=self._wire_dtype)
        st = narrow_lengths(st, self.max_len)
        rl = narrow_lengths(rl, self.max_len)
        return [((bin_of, slot_of), n, (ids, st, rl))]

    def prepare(self, texts: Sequence[str]):
        """Host phase: tokenize and plan the batch (no device work).

        With ``length_buckets`` set, rows group by token length and each
        group runs at the smallest sufficient sequence length (seq-32 rows
        cost ~1/4 the encoder FLOPs of seq-128 rows) — the SURVEY §7
        "ragged lyrics" lever.  With ``packed=True``, short lyrics instead
        share full-width rows behind a block-diagonal attention mask
        (:func:`pack_segments`) — same FLOP saving, but concentrated into
        fewer, fuller rows.  Row counts round up to powers of two so the
        compiled-shape set stays bounded; original order is restored in
        :meth:`collect`.

        Returns ``(texts, [(gather, n, host_arrays)...])`` — every array
        already padded and cast to its wire dtype, ready for
        :meth:`transfer`.
        """
        token_ids, lengths = self.tokenizer.encode_batch(texts, self.max_len)
        if self.packed:
            return texts, self._plan_packed(token_ids, lengths)
        if self.length_buckets == "auto" and lengths.size:
            # First non-empty batch is the sample: at production batch
            # sizes (4-8k rows) its length distribution is the corpus's.
            # (An empty batch leaves "auto" pending rather than silently
            # resolving to the flat path forever.)
            self.length_buckets = self._check_buckets(
                derive_length_buckets(lengths, self.max_len), self.max_len
            )
        if self.length_buckets == "auto":
            return texts, []
        if self.length_buckets is None:
            return texts, [self._plan_flat(token_ids, lengths)]
        parts = []
        remaining = np.arange(token_ids.shape[0])
        for bucket in self.length_buckets:
            in_bucket = lengths[remaining] <= bucket
            rows = remaining[in_bucket]
            remaining = remaining[~in_bucket]
            if rows.size == 0:
                continue
            padded_rows = self._round_rows(rows.size)
            ids_b = np.zeros((padded_rows, bucket), token_ids.dtype)
            len_b = np.ones((padded_rows,), lengths.dtype)
            ids_b[: rows.size] = token_ids[rows, :bucket]
            len_b[: rows.size] = lengths[rows]
            _, _, arrays = self._plan_flat(ids_b, len_b)
            parts.append((rows, rows.size, arrays))
        return texts, parts

    def transfer(self, prepared):
        """H2D phase: place every planned wire array on device.

        Runs in the pipeline's transfer stage so batch i+1 is copied
        host→device while batch i computes.  Bytes shipped (and saved
        vs an int32 wire) land in the ``pipeline.h2d_bytes*`` counters.
        """
        from music_analyst_tpu.runtime.wire import count_h2d_bytes

        texts, parts = prepared
        placed = []
        for gather, n, arrays in parts:
            count_h2d_bytes(arrays)
            arrays = tuple(
                jax.device_put(a, self._data_sharding) for a in arrays
            )
            placed.append((gather, n, arrays))
        return texts, placed

    def launch(self, transferred):
        """Dispatch phase: launch the jitted forwards (JAX async dispatch
        — returns handles, never blocks on results)."""
        texts, parts = transferred
        launched = []
        for gather, n, arrays in parts:
            if len(arrays) == 2:
                token_ids, lengths = arrays
                self._record_mesh_collectives(*token_ids.shape)
                classes, confidence = self._forward(
                    self.params, token_ids, lengths
                )
            else:
                ids, st, rl = arrays
                self._record_mesh_collectives(ids.shape[0], self.max_len)
                classes, confidence = self._forward_packed(
                    self.params, ids, st, rl
                )
            launched.append((gather, classes, confidence, n))
        return texts, launched

    def submit(self, texts: Sequence[str]):
        """Tokenize + dispatch without blocking: the staged hooks composed
        for direct submit/collect callers."""
        return self.launch(self.transfer(self.prepare(texts)))

    def collect(self, handle) -> List[str]:
        texts, parts = handle
        # Sentinel init + coverage check: every row must be written by
        # exactly one bucket part, or labels would silently be garbage.
        classes = np.full((len(texts),), -1, np.int64)
        confidence = np.empty((len(texts),), np.float64)
        for rows, part_classes, part_confidence, n in parts:
            if isinstance(rows, tuple):
                # Packed part: device results are [rows, slots]; gather
                # input i's segment via its (bin, slot) coordinates.
                bin_of, slot_of = rows
                classes[:n] = np.asarray(part_classes)[bin_of, slot_of]
                confidence[:n] = np.asarray(part_confidence)[bin_of, slot_of]
                continue
            if rows is None:
                rows = np.arange(len(texts))
            classes[rows] = np.asarray(part_classes)[:n]
            confidence[rows] = np.asarray(part_confidence)[:n]
        uncovered = np.flatnonzero(classes < 0)
        if uncovered.size:
            raise AssertionError(
                f"{uncovered.size} row(s) not covered by any length bucket "
                f"(first: {uncovered[0]})"
            )
        labels: List[str] = []
        for text, cls_id, conf in zip(texts, classes, confidence):
            if not text.strip():
                labels.append("Neutral")  # reference empty-lyric rule
            elif conf < self.neutral_threshold:
                labels.append("Neutral")
            else:
                labels.append(self._CLASS_LABELS[int(cls_id)])
        return labels

    def classify_batch(self, texts: Sequence[str]) -> List[str]:
        return self.collect(self.submit(texts))
