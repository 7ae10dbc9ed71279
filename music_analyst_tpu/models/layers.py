"""Shared Flax building blocks for the model families.

Written TPU-first: bfloat16 activations by default (MXU-native), static
shapes everywhere, fused residual blocks XLA can pipeline, and attention
formulated so heads can be sharded over the ``tp`` mesh axis (head counts
are kept divisible by the tp degree by construction in the model configs).
"""

from __future__ import annotations

import math
from typing import Any, Optional, Tuple

import jax
import jax.numpy as jnp
from flax import linen as nn

from music_analyst_tpu.ops.kv_cache import (
    KVCache,
    grouped_scores,
    grouped_values,
)
from music_analyst_tpu.profiling.compile import (
    note_attention_path,
    note_traced_path,
)


# The most positions a RoPE table is built over: a configuration that
# declares more (2**20 here: 268 MB a table of 64 frequencies, twice a layer
# kind) gets the same angles computed from the positions it is given
# (:func:`rope_at`; ``MultiHeadAttention`` decides by this number alone).
ROPE_TABLE_POSITIONS = 1 << 17


def rope_inverse_frequencies(
    head_dim: int, theta: float = 10_000.0, rotary_dim: int = 0, yarn=None
) -> Tuple[jax.Array, float]:
    """``(inv_freq [r / 2], factor)`` of a RoPE over the first ``r`` =
    ``rotary_dim`` dimensions of a head (0 = all ``head_dim``): ``theta **
    (-2j / r)``, and ``factor`` 1.  With ``yarn`` (a ``rope_parameters``
    group of ``rope_type: "yarn"``, as a dict or its items) the frequencies
    are YaRN's blend as ``transformers``' ``_compute_yarn_parameters``
    computes it with ``dim = r``, its defaults for the keys the group may
    lack (``beta_fast`` 32, ``beta_slow`` 1, ``attention_factor`` the
    paper's ``0.1 ln(factor) + 1``): ``ramp_j = clip((j - low) / (high -
    low), 0, 1)`` between the dimensions that turn ``beta_fast`` and
    ``beta_slow`` times over the original context, ``inv_freq_j = (f_j /
    factor) ramp_j + f_j (1 - ramp_j)``, and ``factor`` =
    ``attention_factor`` multiplies cos and sin."""
    r = rotary_dim or head_dim
    inv_freq = 1.0 / (theta ** (jnp.arange(0, r, 2, dtype=jnp.float32) / r))
    if yarn is None:
        return inv_freq, 1.0
    yarn = dict(yarn)
    factor = float(yarn["factor"])
    original = int(yarn["original_max_position_embeddings"])

    def correction_dim(rotations: float) -> float:
        return (r * math.log(original / (rotations * 2 * math.pi))
                / (2 * math.log(theta)))

    low = max(math.floor(correction_dim(yarn.get("beta_fast") or 32)), 0)
    high = min(math.ceil(correction_dim(yarn.get("beta_slow") or 1)), r - 1)
    if low == high:
        high += 0.001  # the source's guard against a ramp of no width
    ramp = jnp.clip(
        (jnp.arange(r // 2, dtype=jnp.float32) - low) / (high - low), 0, 1)
    attention_factor = yarn.get("attention_factor") or (
        0.1 * math.log(factor) + 1.0 if factor > 1 else 1.0)
    return (inv_freq / factor * ramp + inv_freq * (1 - ramp),
            float(attention_factor))


def rope_frequencies(
    head_dim: int, max_positions: int, theta: float = 10_000.0,
    rotary_dim: int = 0, yarn=None,
) -> Tuple[jax.Array, jax.Array]:
    """Precompute RoPE cos/sin tables ``[max_positions, r / 2]``, ``r`` the
    rotary part of the head (``rotary_dim``, 0 = ``head_dim``); with
    ``yarn`` YaRN's frequencies, cos and sin times its ``attention_factor``
    (:func:`rope_inverse_frequencies`)."""
    if not rotary_dim and yarn is None:
        inv_freq = 1.0 / (
            theta ** (jnp.arange(0, head_dim, 2, dtype=jnp.float32) / head_dim)
        )
        positions = jnp.arange(max_positions, dtype=jnp.float32)
        angles = jnp.outer(positions, inv_freq)
        return jnp.cos(angles), jnp.sin(angles)
    return rope_at(jnp.arange(max_positions), head_dim, theta, rotary_dim,
                   yarn)


def rope_at(
    positions: jax.Array, head_dim: int, theta: float = 10_000.0,
    rotary_dim: int = 0, yarn=None,
) -> Tuple[jax.Array, jax.Array]:
    """cos/sin ``[..., r / 2]`` at ``positions [...]``: the rows
    :func:`rope_frequencies`' tables hold there, without the table."""
    inv_freq, factor = rope_inverse_frequencies(
        head_dim, theta, rotary_dim, yarn)
    angles = positions.astype(jnp.float32)[..., None] * inv_freq
    if factor == 1.0:
        return jnp.cos(angles), jnp.sin(angles)
    return jnp.cos(angles) * factor, jnp.sin(angles) * factor


def apply_rope(
    x: jax.Array, cos: jax.Array, sin: jax.Array,
    positions: Optional[jax.Array]
) -> jax.Array:
    """Rotate ``x [B, S, H, D]`` by position-dependent angles.

    ``positions [B, S]`` indexes the precomputed tables, supporting both
    prefill (0..S) and decode (cache_len + step) without recompilation;
    ``None`` = cos and sin are ``[B, S, r / 2]`` already (:func:`rope_at`).
    Tables narrower than the head (``r < D``: a partial rotary part) turn
    the first ``r`` dimensions, half-split over those ``r``, and pass the
    rest as they are.
    """
    if positions is None:
        cos_p, sin_p = cos[:, :, None, :], sin[:, :, None, :]
    else:
        cos_p = cos[positions][:, :, None, :]  # [B, S, 1, r/2]
        sin_p = sin[positions][:, :, None, :]
    r = 2 * cos.shape[-1]
    if r < x.shape[-1]:
        note_traced_path("rope.partial")
        turned, passed = x[..., :r], x[..., r:]
    else:
        turned, passed = x, None
    x1, x2 = jnp.split(turned, 2, axis=-1)
    parts = (x1 * cos_p - x2 * sin_p, x2 * cos_p + x1 * sin_p)
    if passed is not None:
        parts += (passed,)
    return jnp.concatenate(parts, axis=-1).astype(x.dtype)


def apply_rope_interleaved(
    x: jax.Array, cos: jax.Array, sin: jax.Array, positions: jax.Array
) -> jax.Array:
    """:func:`apply_rope` for the interleaved layout (``rope_interleave``):
    the rotated pairs are ``(x[2i], x[2i+1])`` where the half-split form
    pairs ``(x[i], x[i + D/2])``.  ``x`` is ``[B, S, H, D]``; the result
    keeps the interleaved order."""
    cos_p = cos[positions][:, :, None, :]  # [B, S, 1, D/2]
    sin_p = sin[positions][:, :, None, :]
    x32 = x.astype(jnp.float32)
    pairs = x32.reshape(*x.shape[:-1], x.shape[-1] // 2, 2)
    x1, x2 = pairs[..., 0], pairs[..., 1]
    rotated = jnp.stack(
        (x1 * cos_p - x2 * sin_p, x2 * cos_p + x1 * sin_p), axis=-1
    )
    return rotated.reshape(x.shape).astype(x.dtype)


def fan_in_normal(fan_in: int):
    """N(0, 1/fan_in): lecun-normal on a kernel whose leading axes are not
    its receptive field (expert stacks, ``[rank, heads, dim]``)."""

    def init(key, shape, dtype):
        return (jax.random.normal(key, shape, jnp.float32)
                * fan_in ** -0.5).astype(dtype)

    return init


class RMSNorm(nn.Module):
    """Root-mean-square norm (no mean subtraction), fp32 accumulation."""

    epsilon: float = 1e-5

    @nn.compact
    def __call__(self, x: jax.Array) -> jax.Array:
        scale = self.param("scale", nn.initializers.ones, (x.shape[-1],))
        x32 = x.astype(jnp.float32)
        normed = x32 * jax.lax.rsqrt(
            jnp.mean(jnp.square(x32), axis=-1, keepdims=True) + self.epsilon
        )
        return (normed * scale).astype(x.dtype)


def dot_product_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    mask: Optional[jax.Array] = None,
    scale: Optional[float] = None,
) -> jax.Array:
    """Plain attention ``[B, S, H, D]``: float32 scores out of the matmul,
    float32 softmax, the result in ``q.dtype``.

    Grouped-query attention in place for every head ratio: ``k`` / ``v
    [B, KV, Hkv, D]`` meet each group of ``G = H // Hkv`` query heads as
    they are (``ops/kv_cache.grouped_scores`` / ``grouped_values``, traced
    path ``gqa.grouped`` where ``Hkv < H``), never repeated to ``H`` heads;
    ``G`` 1 is multi-head attention with a unit axis.  ``mask`` is
    broadcastable ``[B, H|1, S, KV]``.  ``scale`` multiplies the scores
    (``None`` = ``D ** -0.5``).
    """
    n_heads, n_kv = q.shape[2], k.shape[2]
    if n_kv < n_heads:
        note_traced_path("gqa.grouped")
    if scale is None:
        scale = q.shape[-1] ** -0.5
    scores = grouped_scores(q, k, scale)               # [B, Hkv, G, S, KV]
    if mask is not None:
        mask = (mask[:, :, None] if mask.shape[1] == 1 else mask.reshape(
            mask.shape[:1] + (n_kv, n_heads // n_kv) + mask.shape[2:]))
        scores = jnp.where(mask, scores, jnp.finfo(jnp.float32).min)
    probs = jax.nn.softmax(scores, axis=-1)
    return grouped_values(probs, v, q.dtype).astype(q.dtype)


class QuantDenseGeneral(nn.Module):
    """Drop-in for the two ``nn.DenseGeneral`` layouts with int8 compute.

    Parameter names/shapes are IDENTICAL to ``nn.DenseGeneral`` (`kernel`,
    `bias`), so checkpoint loaders, TP sharding rules, and params trained
    or initialized by the float modules apply unchanged — only the matmul
    runs through the dynamic int8 path (``ops/quant.py``).
    """

    features: Any          # int or tuple, as nn.DenseGeneral
    axis: Any = -1         # -1 or (-2, -1)
    use_bias: bool = True
    dtype: jnp.dtype = jnp.bfloat16

    @nn.compact
    def __call__(self, x: jax.Array) -> jax.Array:
        from music_analyst_tpu.ops.quant import (
            quant_dense_axis_last,
            quant_dense_axis_last2,
        )

        feat = (
            (self.features,)
            if isinstance(self.features, int)
            else tuple(self.features)
        )
        if self.axis == -1:
            kshape = (x.shape[-1],) + feat
            n_contract = 1
        elif not isinstance(self.axis, int) and tuple(self.axis) == (-2, -1):
            assert len(feat) == 1
            kshape = (x.shape[-2], x.shape[-1], feat[0])
            n_contract = 2
        else:
            raise ValueError(f"unsupported axis {self.axis!r}")

        def kernel_init(key, shape, dtype):
            # Match nn.DenseGeneral: initialize on the FLATTENED 2-D shape
            # (fan_in = prod of contracted axes) and reshape — raw
            # lecun_normal on a 3-D shape would treat the leading dim as a
            # conv receptive field and under-scale by sqrt(n_heads).
            import numpy as _np

            flat = (
                int(_np.prod(shape[:n_contract])),
                int(_np.prod(shape[n_contract:])),
            )
            return nn.initializers.lecun_normal()(key, flat, dtype).reshape(
                shape
            )

        kernel = self.param("kernel", kernel_init, kshape, jnp.float32)
        bias = (
            self.param("bias", nn.initializers.zeros, feat, jnp.float32)
            if self.use_bias
            else None
        )
        fn = quant_dense_axis_last if self.axis == -1 else quant_dense_axis_last2
        return fn(x, kernel, bias, out_dtype=self.dtype)


class WqDenseGeneral(nn.Module):
    """DenseGeneral over a *stored* weight-quantized kernel.

    Same two layouts (and identical param names, shapes, and init) as
    ``nn.DenseGeneral``/``QuantDenseGeneral``, but the ``kernel`` slot may
    hold a ``QuantizedParam`` (ops/quant.py): int8 or packed-int4 codes +
    scales, dequant fused into the matmul epilogue (w8/w4 stored,
    activations dynamically row-quantized inside the op).  With a plain
    float array in the slot (random init, bf16 A/B baselines) it computes
    the ordinary float contraction, so one module serves both.

    The kernel is read through ``scope.get_variable`` rather than
    ``self.param`` when a QuantizedParam is stored: packed int4 halves
    axis 0, which Flax's declared-shape check would (correctly) reject for
    a plain param — the quantized store is a different *representation* of
    the declared kernel, not a different kernel.
    """

    features: Any          # int or tuple, as nn.DenseGeneral
    axis: Any = -1         # -1 or (-2, -1)
    use_bias: bool = True
    dtype: jnp.dtype = jnp.bfloat16

    @nn.compact
    def __call__(self, x: jax.Array) -> jax.Array:
        from music_analyst_tpu.ops.quant import (
            QuantizedParam,
            wq_dense_axis_last,
            wq_dense_axis_last2,
        )

        feat = (
            (self.features,)
            if isinstance(self.features, int)
            else tuple(self.features)
        )
        if self.axis == -1:
            kshape = (x.shape[-1],) + feat
            n_contract = 1
        elif not isinstance(self.axis, int) and tuple(self.axis) == (-2, -1):
            assert len(feat) == 1
            kshape = (x.shape[-2], x.shape[-1], feat[0])
            n_contract = 2
        else:
            raise ValueError(f"unsupported axis {self.axis!r}")

        def kernel_init(key, shape, dtype):
            # Same flattened-fan-in init as QuantDenseGeneral (see above).
            import numpy as _np

            flat = (
                int(_np.prod(shape[:n_contract])),
                int(_np.prod(shape[n_contract:])),
            )
            return nn.initializers.lecun_normal()(key, flat, dtype).reshape(
                shape
            )

        kernel = None
        if self.scope is not None and self.scope.has_variable(
            "params", "kernel"
        ):
            stored = self.scope.get_variable("params", "kernel")
            if isinstance(stored, QuantizedParam):
                kernel = stored
        if kernel is None:
            kernel = self.param("kernel", kernel_init, kshape, jnp.float32)
        bias = (
            self.param("bias", nn.initializers.zeros, feat, jnp.float32)
            if self.use_bias
            else None
        )
        if isinstance(kernel, QuantizedParam):
            fn = (
                wq_dense_axis_last if self.axis == -1 else wq_dense_axis_last2
            )
            return fn(x, kernel, bias, out_dtype=self.dtype)
        # Float fallback: the contraction nn.DenseGeneral performs.
        xd = x.astype(self.dtype)
        kd = kernel.astype(self.dtype)
        contract = (
            ((xd.ndim - 1,), (0,))
            if n_contract == 1
            else ((xd.ndim - 2, xd.ndim - 1), (0, 1))
        )
        out = jax.lax.dot_general(xd, kd, (contract, ((), ())))
        if bias is not None:
            out = out + bias.astype(self.dtype)
        return out.astype(self.dtype)


def pick_dense_cls(weight_quant: str, quant: str):
    """One projection-class decision shared by every model family: stored
    weight-quant wins (it subsumes the matmul), then dynamic int8, then
    plain float."""
    if weight_quant != "none":
        return WqDenseGeneral
    if quant == "int8":
        return QuantDenseGeneral
    return nn.DenseGeneral


class MultiHeadAttention(nn.Module):
    """MHA/GQA with optional RoPE and optional KV cache.

    Projections use a single fused kernel per Q/K/V/O so each matmul is
    large enough to tile onto the MXU; head axes are laid out so a ``tp``
    sharding splits ``n_heads`` (and ``n_kv_heads``) without resharding.
    """

    n_heads: int
    n_kv_heads: Optional[int] = None
    head_dim: Optional[int] = None
    use_rope: bool = False
    rope_theta: float = 10_000.0
    max_positions: int = 4096
    dtype: jnp.dtype = jnp.bfloat16
    # "dense" materializes [B,H,S,KV] logits (any mask, any shape);
    # "flash" runs the Pallas blocked online-softmax kernel
    # (ops/flash_attention.py) — O(S·D) HBM, causal+lengths masks only,
    # seq len must divide the kernel block size.
    attn_impl: str = "dense"
    flash_causal: bool = False
    # BERT-family projections carry biases (HF q_lin/k_lin/v_lin/out_lin
    # each have one); Llama-family does not.
    use_bias: bool = False
    # "int8" routes the Q/K/V/O projections through the dynamic int8
    # matmul (ops/quant.py) — inference-only MXU throughput lever.
    quant: str = "none"
    # "int8"/"int4" stores the projection kernels weight-quantized
    # (QuantizedParam leaves; ops/quant.py) — takes precedence over the
    # dynamic `quant` path.
    weight_quant: str = "none"
    # The mesh a meshed forward runs under: a Pallas call is opaque to the
    # partitioner, so the whole-row kernel needs it to run per shard.
    mesh: Any = None
    # RMSNorm over ``head_dim`` on every query and key head before RoPE,
    # one learned scale of ``head_dim`` for all heads (QK-norm).
    qk_norm: bool = False
    norm_eps: float = 1e-6
    # What the float projection kernels are stored in.
    param_dtype: jnp.dtype = jnp.float32
    # What multiplies the scores before the softmax where a configuration
    # publishes its own; ``None`` = ``head_dim ** -0.5``.
    scale: Optional[float] = None
    # A sliding window: the query at position ``p`` sees the keys at
    # positions ``p - window + 1 .. p`` (``window`` keys with its own;
    # ``transformers``' ``sliding_window_overlay``: ``kv > q - window``) of
    # those the mask, the lengths or the cache view let it see; 0 = none.
    window: int = 0
    # RoPE on the first ``rotary_dim`` dimensions of a head (0 = all of
    # it), and YaRN's parameters (``rope_frequencies``; hashable: the
    # group's items); the defaults are plain RoPE over the head.
    rotary_dim: int = 0
    yarn: Any = None
    # One gate a query head on the attention's output, from the layer's
    # own input: ``o_h <- f(x W_g)_h * o_h`` with ``W_g [D, H]`` float32,
    # ``f`` = ``"softplus"`` or ``"sigmoid"``, before ``o_proj``; ``"none"``
    # = no gate and no parameter.
    output_gate: str = "none"

    @nn.compact
    def __call__(
        self,
        x: jax.Array,
        mask: Optional[jax.Array] = None,
        positions: Optional[jax.Array] = None,
        cache: Optional[KVCache] = None,
        lengths: Optional[jax.Array] = None,
        segment_ids: Optional[jax.Array] = None,
        packed=None,
        key_positions: Optional[jax.Array] = None,
    ):
        # ``packed`` (a ``models/compact_stream.RealPositions``): ``x [1, C,
        # D]`` is the compact token stream of a ``[B, S]`` step (traced
        # path ``gqa.compact``).  The projections, QK-norm, RoPE and the
        # output gate run on it.  A cache view that attends on the stream
        # itself (``ops/kv_cache.BlockCausalPrefill.attend_packed``) takes
        # queries, keys and values as they are and returns its result
        # there; only keys and values are put back at their ``[B, S]``
        # places, for the cache.  Otherwise all three are put back for the
        # cache and the attention this layer has (zeros at and behind a
        # row's length), and the result is gathered onto the stream again
        # before the gate and ``o_proj``.
        # ``key_positions [B, KV]`` (a window layer reads it): the position
        # of the key each cache slot holds, where a slot is not its
        # position (a continuation's slots lie behind the prompt's width,
        # its positions behind the row's length); ``None`` = the slot.
        # The scopes inside (``gqa.proj`` / ``.rope`` / ``.kernel`` /
        # ``.gate`` / ``.out``) stand under the caller's own (``gqa`` in a
        # decoder block, ``encoder.attention`` in the encoder).
        features = x.shape[-1]
        n_kv = self.n_kv_heads or self.n_heads
        head_dim = self.head_dim or features // self.n_heads
        dense_cls = pick_dense_cls(self.weight_quant, self.quant)
        # only the float class has a storage dtype to choose
        stored = ({"param_dtype": self.param_dtype}
                  if dense_cls is nn.DenseGeneral else {})
        dense = lambda feats, name: dense_cls(  # noqa: E731
            features=feats,
            axis=-1,
            use_bias=self.use_bias,
            dtype=self.dtype,
            name=name,
            **stored,
        )
        with jax.named_scope("gqa.proj"):
            q = dense((self.n_heads, head_dim), "q_proj")(x)
            k = dense((n_kv, head_dim), "k_proj")(x)
            v = dense((n_kv, head_dim), "v_proj")(x)
            if self.qk_norm:
                q = RMSNorm(epsilon=self.norm_eps, name="q_norm")(q)
                k = RMSNorm(epsilon=self.norm_eps, name="k_norm")(k)
        if self.output_gate not in ("none", "softplus", "sigmoid"):
            raise ValueError(f"unknown output_gate {self.output_gate!r}")
        gate = None
        if self.output_gate != "none":
            # float32 at the highest matmul precision, as the router's: a
            # sliver of the projections' cost, and one scalar scales a
            # whole head
            note_traced_path("gqa.output_gate")
            with jax.named_scope("gqa.gate"):
                gate_w = self.param("g_proj", fan_in_normal(features),
                                    (features, self.n_heads), jnp.float32)
                gate = jnp.dot(x.astype(jnp.float32), gate_w,
                               precision=jax.lax.Precision.HIGHEST)
                gate = (jax.nn.softplus(gate)
                        if self.output_gate == "softplus"
                        else jax.nn.sigmoid(gate))           # [B, S, H]

        if self.use_rope:
            if positions is None:
                positions = jnp.broadcast_to(
                    jnp.arange(x.shape[1]), x.shape[:2]
                )
            with jax.named_scope("gqa.rope"):
                if self.yarn is not None:
                    note_traced_path("rope.yarn")
                if self.max_positions > ROPE_TABLE_POSITIONS:
                    cos, sin = rope_at(positions, head_dim, self.rope_theta,
                                       self.rotary_dim, self.yarn)
                    at = None
                else:
                    cos, sin = rope_frequencies(
                        head_dim, self.max_positions, self.rope_theta,
                        self.rotary_dim, self.yarn)
                    at = positions
                q = apply_rope(q, cos, sin, at)
                k = apply_rope(k, cos, sin, at)

        on_stream = packed is not None and hasattr(cache, "attend_packed")
        if packed is not None:
            note_traced_path("gqa.compact")
            stream = (q[0], k[0], v[0])
            if on_stream:
                k, v = (packed.put_back(a) for a in stream[1:])
            else:
                q, k, v = (packed.put_back(a) for a in stream)

        new_cache = None
        paged = False
        if cache is not None:
            new_cache = cache.update(k, v)
            # A paged cache (ops/paged_attention.PagedAttnView) carries
            # the physical page pool, not a contiguous buffer: its
            # ``attend`` runs the fused gather+QK+softmax+V kernel, so
            # the contiguous k/v unpack below never happens for it.
            paged = hasattr(new_cache, "attend")
            if not paged:
                k, v = new_cache.keys, new_cache.values

        def no_scale(attention: str):
            if self.scale is not None:
                raise ValueError(
                    "a published softmax scale reaches the dense form, the "
                    f"flash kernel and a cache view that carries it; {attention} "
                    "has none")

        def no_window(attention: str):
            if self.window:
                raise ValueError(
                    "a sliding window reaches the dense form, the flash "
                    f"kernel and a cache view that carries it; {attention} "
                    "masks none")

        if self.window:
            note_traced_path("gqa.window")
        with jax.named_scope("gqa.kernel"):
            if paged:
                if getattr(new_cache, "scale", None) != self.scale:
                    no_scale("this cache view")
                if getattr(new_cache, "window", 0) != self.window:
                    no_window("this cache view")
                if on_stream:
                    out = new_cache.attend_packed(*stream, packed)[None]
                else:
                    out = new_cache.attend(q, mask)
            elif self.attn_impl == "flash" and cache is None:
                from music_analyst_tpu.ops.flash_attention import (
                    flash_attention,
                )

                # The flash kernel expresses masking ONLY via flash_causal
                # + lengths (+ window); an arbitrary `mask` array can't
                # reach it and would be silently dropped — refuse outright.
                # Callers on the flash path pass mask=None and encode
                # semantics in flash_causal / lengths (see LlamaBlock /
                # DistilBert TransformerBlock).
                if mask is not None:
                    raise ValueError(
                        "attn_impl='flash' cannot apply a mask array; pass "
                        "mask=None with lengths= (padding) and/or "
                        "flash_causal set, or use attn_impl='dense' for "
                        "arbitrary masks"
                    )
                out = flash_attention(
                    q, k, v, lengths=lengths, causal=self.flash_causal,
                    q_segment_ids=segment_ids, scale=self.scale,
                    window=self.window,
                )
            else:
                if segment_ids is not None:
                    raise ValueError(
                        "segment_ids is the flash path's masking "
                        "vocabulary; dense callers build the block-diagonal "
                        "mask array themselves (models/distilbert.py)"
                    )
                if mask is None and lengths is not None and cache is None:
                    # Key padding described by `lengths` alone: the caller
                    # built no mask array because the shape is one the
                    # whole-row kernel takes (models/distilbert.py decides).
                    from music_analyst_tpu.ops.whole_row_attention import (
                        whole_row_attention,
                    )

                    no_scale("the whole-row kernel")
                    no_window("the whole-row kernel")
                    note_attention_path("whole_row")
                    out = whole_row_attention(q, k, v, lengths,
                                              mesh=self.mesh)
                else:
                    if self.window:
                        mask = window_mask(
                            mask, self.window, q.shape[1], k.shape[1],
                            positions if packed is None else None,
                            key_positions)
                        note_attention_path("window_dense")
                    else:
                        note_attention_path("dense")
                    out = dot_product_attention(q, k, v, mask, self.scale)
        if packed is not None and not on_stream:
            out = packed.gather(out)[None]
        if gate is not None:
            with jax.named_scope("gqa.gate"):
                out = (out.astype(jnp.float32) * gate[..., None]
                       ).astype(out.dtype)
        with jax.named_scope("gqa.out"):
            out = dense_cls(
                features=features,
                axis=(-2, -1),
                use_bias=self.use_bias,
                dtype=self.dtype,
                name="o_proj",
                **stored,
            )(out)
        if cache is not None:
            return out, new_cache
        return out


class SwiGLU(nn.Module):
    """Llama-style gated MLP; hidden dim shards over ``tp``."""

    hidden_dim: int
    dtype: jnp.dtype = jnp.bfloat16
    quant: str = "none"
    weight_quant: str = "none"
    # What the float kernels are stored in (the quantized layouts own theirs).
    param_dtype: jnp.dtype = jnp.float32

    @nn.compact
    def __call__(self, x: jax.Array) -> jax.Array:
        features = x.shape[-1]
        if self.weight_quant != "none":
            dense = lambda feats, name: WqDenseGeneral(  # noqa: E731
                features=feats, use_bias=False, dtype=self.dtype, name=name
            )
        elif self.quant == "int8":
            dense = lambda feats, name: QuantDenseGeneral(  # noqa: E731
                features=feats, use_bias=False, dtype=self.dtype, name=name
            )
        else:
            dense = lambda feats, name: nn.Dense(  # noqa: E731
                feats, use_bias=False, dtype=self.dtype,
                param_dtype=self.param_dtype, name=name
            )
        gate = dense(self.hidden_dim, "gate_proj")(x)
        up = dense(self.hidden_dim, "up_proj")(x)
        return dense(features, "down_proj")(nn.silu(gate) * up)


class GeluMLP(nn.Module):
    """BERT-style 2-layer MLP with biases."""

    hidden_dim: int
    dtype: jnp.dtype = jnp.bfloat16
    quant: str = "none"
    weight_quant: str = "none"

    @nn.compact
    def __call__(self, x: jax.Array) -> jax.Array:
        features = x.shape[-1]
        if self.weight_quant != "none":
            dense = lambda feats, name: WqDenseGeneral(  # noqa: E731
                features=feats, dtype=self.dtype, name=name
            )
        elif self.quant == "int8":
            dense = lambda feats, name: QuantDenseGeneral(  # noqa: E731
                features=feats, dtype=self.dtype, name=name
            )
        else:
            dense = lambda feats, name: nn.Dense(  # noqa: E731
                feats, dtype=self.dtype, name=name
            )
        h = dense(self.hidden_dim, "lin1")(x)
        h = nn.gelu(h, approximate=False)
        return dense(features, "lin2")(h)


def causal_mask(q_len: int, kv_len: int, offset) -> jax.Array:
    """``[1, 1, q_len, kv_len]`` causal mask with a dynamic cache offset."""
    q_pos = jnp.arange(q_len)[:, None] + offset
    kv_pos = jnp.arange(kv_len)[None, :]
    return (kv_pos <= q_pos)[None, None, :, :]


def window_mask(mask: Optional[jax.Array], window: int, q_len: int,
                kv_len: int, positions: Optional[jax.Array] = None,
                key_positions: Optional[jax.Array] = None) -> jax.Array:
    """``mask`` (broadcastable ``[B, H, q_len, kv_len]``; a window layer's
    caller always has one: a window alone is not causal) and the sliding
    window ``key position > query position - window``.  ``positions [B,
    q_len]`` are the queries' (``None`` = ``0 .. q_len - 1``, a prefill
    from position 0); ``key_positions [B, kv_len]`` the position of the key
    a slot holds (``None`` = the slot's index)."""
    if mask is None:
        raise ValueError(
            "a sliding-window layer takes its causal mask from the caller")
    q_pos = (jnp.arange(q_len)[None, :] if positions is None
             else positions)[:, None, :, None]
    k_pos = (jnp.arange(kv_len)[None, :] if key_positions is None
             else key_positions)[:, None, None, :]
    return mask & (k_pos > q_pos - window)


def block_causal_mask(q_len: int, kv_len: int, block: int) -> jax.Array:
    """``[1, 1, q_len, kv_len]`` mask of a block-diffusion decoder: a query
    sees every key up to the end of its own block of ``block`` positions
    (bidirectional inside a block, causal across blocks)."""
    q_block = jnp.arange(q_len)[:, None] // block
    kv_block = jnp.arange(kv_len)[None, :] // block
    return (kv_block <= q_block)[None, None, :, :]


def padding_mask(lengths: jax.Array, max_len: int) -> jax.Array:
    """``[B, 1, 1, max_len]`` key-padding mask from per-row lengths."""
    return (jnp.arange(max_len)[None, :] < lengths[:, None])[:, None, None, :]


def segment_mask(segment_ids: jax.Array) -> jax.Array:
    """``[B, 1, S, S]`` block-diagonal mask from per-token segment ids.

    Token pairs attend iff they share a segment id (packed batches /
    packed documents).  The single definition shared by the encoder, the
    training loss, and tests, so packing semantics can't drift per site.
    """
    return segment_ids[:, None, :, None] == segment_ids[:, None, None, :]
