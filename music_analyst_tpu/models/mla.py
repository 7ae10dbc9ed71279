"""Multi-head latent attention (MLA) with a latent cache.

Keys and values are not projected from the hidden state directly: one
down-projection gives, per token, a latent ``c_kv`` (``kv_lora_rank``
values, RMS-normed) and one RoPE key ``k_rope`` shared by all heads; an
up-projection ``W_kvb`` expands the latent to every head's ``k_nope`` and
``v``.  Queries are ``[q_nope | q_rope]`` per head (no query low-rank here:
``q_lora_rank`` null), and a head's key is ``[k_nope | k_rope]``, so the
query/key width (``nope + rope``) differs from the value width.

Two forms of the same mathematics, chosen from the shapes:

* **expanded** — expand the latents to per-head keys and values and run
  ordinary attention.  Cheapest when many queries share the expansion
  (prefill).  A causal prefill from position 0 on one device, which its
  caller declares by giving the rows' ``prefill_lengths``, runs as one
  Pallas kernel (``ops/mla_prefill_attention.py``) that skips what lies
  above the diagonal or past a row's length and keeps no score outside
  VMEM; every other call takes :func:`blocked_attention`, the XLA form,
  where the scores of one block of queries at a time are live, never
  ``[B, H, S, S]``.
* **absorbed** — fold ``W_uk`` (the ``k_nope`` half of ``W_kvb``) into the
  query and ``W_uv`` (the ``v`` half) behind the weighted sum, so attention
  runs over the latents themselves:
  ``scores = (W_uk^T q_nope) . c_kv + q_rope . k_rope``,
  ``out = W_uv (P c_kv)``.  Cheapest for a few queries against a long cache
  (decode, label continuations): nothing per head is ever expanded.

The cache (:class:`LatentCache`) holds what both forms read: ``c_kv`` after
its norm and ``k_rope`` after RoPE, ``kv_lora_rank + rope`` values a token
where expanded keys and values are ``heads * (nope + rope + v)``.
"""

from __future__ import annotations

import dataclasses
import math
from typing import Callable, Optional

import jax
import jax.numpy as jnp
from flax import linen as nn

from music_analyst_tpu.models.layers import (
    RMSNorm,
    apply_rope,
    apply_rope_interleaved,
    fan_in_normal,
    rope_frequencies,
)
from music_analyst_tpu.ops.mla_prefill_attention import (
    mla_prefill_attention,
    mla_prefill_attention_packed,
    prefill_block,
)
from music_analyst_tpu.profiling.compile import (
    note_attention_path,
    note_traced_path,
)


@dataclasses.dataclass
class LatentCache:
    """Per-layer MLA cache: ``latents [B, max_len, kv_lora_rank]`` (after
    ``kv_a_norm``) and ``rope_keys [B, max_len, rope_dim]`` (after RoPE).
    ``length`` is the scalar write offset every row shares, as in the
    static-batch :class:`~music_analyst_tpu.ops.kv_cache.KVCache`."""

    latents: jax.Array
    rope_keys: jax.Array
    length: jax.Array

    @classmethod
    def zeros(cls, batch: int, max_len: int, kv_lora_rank: int,
              rope_dim: int, dtype=jnp.bfloat16) -> "LatentCache":
        return cls(
            latents=jnp.zeros((batch, max_len, kv_lora_rank), dtype),
            rope_keys=jnp.zeros((batch, max_len, rope_dim), dtype),
            length=jnp.zeros((), jnp.int32),
        )

    def update(self, c_new: jax.Array, r_new: jax.Array) -> "LatentCache":
        start = self.length
        latents = jax.lax.dynamic_update_slice(
            self.latents, c_new.astype(self.latents.dtype), (0, start, 0)
        )
        rope_keys = jax.lax.dynamic_update_slice(
            self.rope_keys, r_new.astype(self.rope_keys.dtype), (0, start, 0)
        )
        return LatentCache(latents, rope_keys, start + c_new.shape[1])

    @property
    def max_len(self) -> int:
        return self.latents.shape[1]

    def with_length(self, length) -> "LatentCache":
        """The same buffers reporting ``length`` filled positions."""
        return LatentCache(self.latents, self.rope_keys,
                           jnp.asarray(length, jnp.int32))


jax.tree_util.register_dataclass(
    LatentCache, data_fields=["latents", "rope_keys", "length"],
    meta_fields=[],
)


def dense_general_init(key, shape, dtype):
    """What ``nn.DenseGeneral`` draws for a kernel of ``shape`` contracted
    over its first axis: lecun-normal on the kernel flattened to 2-D.
    ``q_proj`` was such a module, and a seed keeps the weights it gave."""
    flat = nn.initializers.lecun_normal()(
        key, (shape[0], math.prod(shape[1:])), dtype)
    return flat.reshape(shape)


class Kernel(nn.Module):
    """A bare ``kernel`` leaf under a module name, for a projection that is
    applied in more than one contraction (``kv_b_proj``: expanded whole,
    absorbed by halves; ``q_proj``: 4-D, or 2-D by halves for the prefill
    kernel)."""

    shape: tuple
    fan_in: int
    param_dtype: jnp.dtype = jnp.float32
    init: Optional[Callable] = None      # default: N(0, 1 / fan_in)

    @nn.compact
    def __call__(self) -> jax.Array:
        return self.param("kernel", self.init or fan_in_normal(self.fan_in),
                          self.shape, self.param_dtype)


def blocked_attention(q_nope, q_rope, k_nope, k_rope, v, mask, scale: float,
                      block_q: int) -> jax.Array:
    """Attention with per-head keys ``[k_nope | k_rope]`` (``k_rope`` one
    vector a token, shared by the heads) over blocks of ``block_q``
    queries: one block's float32 scores are live at a time, the whole row
    of keys is computed and ``mask`` decides afterwards.

    The expanded form of every call the prefill kernel does not take:
    a caller that gives no ``prefill_lengths`` (``MLAttention`` says what
    they promise: ``init``, training, the layer tests, a continuation of
    more than ``absorb_max_queries`` tokens on a filled cache, any forward
    under a mesh, where XLA partitions this form and could not the
    kernel's call), and with them a number of queries outside
    ``ops/mla_prefill_attention.prefill_block`` (not whole kernel blocks,
    or fewer than two: prompt widths of 64 to 256).  The kernel's tests
    compare with it.

    ``q_nope [B,Sq,H,Dn]``, ``q_rope [B,Sq,H,Dr]``, ``k_nope [B,Sk,H,Dn]``,
    ``k_rope [B,Sk,Dr]``, ``v [B,Sk,H,Dv]``; ``mask`` broadcastable to
    ``[B,1,Sq,Sk]`` or ``None``.  Returns ``[B,Sq,H,Dv]``.
    """
    batch, n_q = q_nope.shape[:2]

    def attend(qn, qr, m):
        scores = jnp.einsum("bqhd,bkhd->bhqk", qn, k_nope,
                            preferred_element_type=jnp.float32)
        scores = scores + jnp.einsum("bqhd,bkd->bhqk", qr, k_rope,
                                     preferred_element_type=jnp.float32)
        scores = scores * scale
        if m is not None:
            scores = jnp.where(m, scores, jnp.finfo(jnp.float32).min)
        probs = jax.nn.softmax(scores, axis=-1).astype(v.dtype)
        return jnp.einsum("bhqk,bkhd->bqhd", probs, v)

    if n_q <= block_q or n_q % block_q:
        return attend(q_nope, q_rope, mask)
    n_blocks = n_q // block_q

    def blocks(x, axis):
        shape = x.shape[:axis] + (n_blocks, block_q) + x.shape[axis + 1:]
        return jnp.moveaxis(x.reshape(shape), axis, 0)

    operands = [blocks(q_nope, 1), blocks(q_rope, 1)]
    if mask is not None:
        mask = jnp.broadcast_to(
            mask, mask.shape[:2] + (n_q, mask.shape[-1]))
        operands.append(blocks(mask, 2))
    out = jax.lax.map(
        lambda args: attend(args[0], args[1],
                            args[2] if mask is not None else None),
        tuple(operands),
    )                                                   # [n, B, block, H, Dv]
    return jnp.moveaxis(out, 0, 1).reshape(batch, n_q, *out.shape[3:])


class MLAttention(nn.Module):
    """Latent attention block: projections, RoPE, both attention forms and
    the output projection.  Returns ``out`` without a cache, ``(out,
    new_cache)`` with one, as ``MultiHeadAttention`` does."""

    n_heads: int
    qk_nope_head_dim: int
    qk_rope_head_dim: int
    v_head_dim: int
    kv_lora_rank: int
    rope_theta: float = 10_000.0
    rope_interleave: bool = True
    max_positions: int = 4096
    norm_eps: float = 1e-6
    dtype: jnp.dtype = jnp.bfloat16
    param_dtype: jnp.dtype = jnp.float32
    # With a cache, up to this many new queries take the absorbed form and
    # more take the expanded one.  Absorbed costs each query
    # ``heads * (rank + rope + rank)`` multiply-adds a cached token where
    # expanded costs ``heads * (nope + rope + v)``, and expanded pays
    # ``rank * heads * (nope + v)`` once a cached token: they cross at
    # ``rank * (nope + v) / (2 * rank - nope - v)`` queries (171 at the
    # published 512 / 128 / 128).
    absorb_max_queries: int = 128
    block_q: int = 128
    # One sigmoid gate a head on the attention output, before ``o_proj``
    # (``gated_attention_proj_granularity_type: head_wise``): ``o_h <- o_h
    # * sigmoid(W_gate x)_h``, ``W_gate [dim, heads]``; the same in the
    # expanded and the absorbed form, which differ in how ``o_h`` is made.
    output_gate: bool = False

    @nn.compact
    def __call__(self, x, mask=None, positions=None,
                 cache: Optional[LatentCache] = None,
                 prefill_lengths: Optional[jax.Array] = None,
                 packed=None):
        """``prefill_lengths [B]`` is a promise only the caller can make
        (``cache.length`` is traced, nothing here can check it): this call
        is a causal prefill from position 0 (query ``i`` is key ``i``, on
        an empty cache if any), ``mask`` is the causal rule and key padding
        by these lengths, nothing reads the output at a padding position,
        and the call is not partitioned over a mesh.  The expanded form may
        then take the kernel that reads the lengths IN PLACE OF ``mask``
        (:func:`prefill_block` decides from the number of queries).  A
        continuation on a filled cache, a chunked prefill, any other mask,
        training (the kernel has no gradient) and a meshed forward withhold
        it, and ``mask`` is applied as given.

        ``packed`` (a ``models/moe.RealPositions`` of these lengths, from
        ``models/llama.LlamaModel`` alone) says ``x [1, C, dim]`` and
        ``positions [1, C]`` are that compact token set, each row's real
        positions one behind the other, and not ``[B, S, ...]``: the
        projections, RoPE, the latent expansion, the kernel (its packed
        form, which finds a row at its first slot) and ``o_proj`` run on
        the ``C`` slots and the result is ``[1, C, dim]``.  Only what the
        cache holds, ``latents`` and ``k_rope``, is put back at ``[B, S]``
        (zeros at and behind a row's length); a hidden state at a padding
        position does not exist."""
        dim = x.shape[-1]
        batch, n_q = x.shape[:2]
        heads, nope, rope = (self.n_heads, self.qk_nope_head_dim,
                             self.qk_rope_head_dim)
        rank, v_dim = self.kv_lora_rank, self.v_head_dim
        scale = (nope + rope) ** -0.5
        absorbed = (packed is None and cache is not None
                    and n_q <= self.absorb_max_queries)
        flash = packed is not None or (
            not absorbed and prefill_lengths is not None
            and bool(prefill_block(n_q)))

        x = x.astype(self.dtype)
        w_q = Kernel((dim, heads, nope + rope), dim, self.param_dtype,
                     dense_general_init, name="q_proj")().astype(self.dtype)
        kv_a = nn.Dense(
            rank + rope, use_bias=False, dtype=self.dtype,
            param_dtype=self.param_dtype, name="kv_a_proj",
        )(x)
        latents = RMSNorm(epsilon=self.norm_eps, name="kv_a_norm")(
            kv_a[..., :rank])
        w_kvb = Kernel((rank, heads, nope + v_dim), rank, self.param_dtype,
                       name="kv_b_proj")().astype(self.dtype)

        if positions is None:
            positions = jnp.broadcast_to(jnp.arange(n_q), (batch, n_q))
        cos, sin = rope_frequencies(rope, self.max_positions, self.rope_theta)
        rotate = apply_rope_interleaved if self.rope_interleave else apply_rope
        if flash:
            # The kernel reads ``[B, S, H*D]``, which is how XLA lays out a
            # contraction with a 2-D weight; fed from ``[B, S, H, D]`` the
            # call gets a slice and three transposing copies a layer
            # (tests/test_mosaic_aot.py pins this).
            q_nope = x @ w_q[..., :nope].reshape(dim, heads * nope)
            q_rope = (x @ w_q[..., nope:].reshape(dim, heads * rope)
                      ).reshape(batch, n_q, heads, rope)
        else:
            q = jnp.einsum("bsd,dhe->bshe", x, w_q)
            q_nope, q_rope = q[..., :nope], q[..., nope:]
        q_rope = rotate(q_rope, cos, sin, positions)
        k_rope = rotate(kv_a[..., None, rank:], cos, sin, positions)[:, :, 0]

        new_cache = None
        if cache is not None and packed is not None:
            # the kernel reads the compact set; the label passes read the
            # cache by row
            new_cache = cache.update(packed.put_back(latents[0]),
                                     packed.put_back(k_rope[0]))
        elif cache is not None:
            new_cache = cache.update(latents, k_rope)
            latents, k_rope = new_cache.latents, new_cache.rope_keys

        if absorbed:
            note_traced_path("mla.absorbed")
            q_abs = jnp.einsum("bqhd,rhd->bqhr", q_nope, w_kvb[..., :nope])
            scores = jnp.einsum("bqhr,bkr->bhqk", q_abs, latents,
                                preferred_element_type=jnp.float32)
            scores = scores + jnp.einsum(
                "bqhd,bkd->bhqk", q_rope, k_rope,
                preferred_element_type=jnp.float32)
            scores = scores * scale
            if mask is not None:
                scores = jnp.where(mask, scores, jnp.finfo(jnp.float32).min)
            probs = jax.nn.softmax(scores, axis=-1).astype(self.dtype)
            ctx = jnp.einsum("bhqk,bkr->bqhr", probs, latents)
            out = jnp.einsum("bqhr,rhd->bqhd", ctx, w_kvb[..., nope:])
        elif flash:
            note_traced_path("mla.expanded")
            kv = latents @ w_kvb.reshape(rank, heads * (nope + v_dim))
            q_rope = q_rope.reshape(batch, n_q, heads * rope)
            if packed is not None:
                note_traced_path("mla.compact")
                note_attention_path("mla_flash_packed")
                out = mla_prefill_attention_packed(
                    q_nope[0], q_rope[0], kv[0], k_rope[0], prefill_lengths,
                    packed.real.shape[1], heads, scale)
            else:
                note_attention_path("mla_flash")
                out = mla_prefill_attention(
                    q_nope, q_rope, kv, k_rope, prefill_lengths, heads,
                    scale)
            out = out.reshape(batch, n_q, heads, v_dim)
        else:
            note_traced_path("mla.expanded")
            note_attention_path("mla_blocked")
            kv = jnp.einsum("bkr,rhd->bkhd", latents, w_kvb)
            out = blocked_attention(
                q_nope, q_rope, kv[..., :nope], k_rope, kv[..., nope:],
                mask, scale, self.block_q,
            )
        if self.output_gate:
            note_traced_path("mla.gated")
            gate = nn.Dense(heads, use_bias=False, dtype=self.dtype,
                            param_dtype=self.param_dtype, name="gate_proj")(x)
            out = (out.astype(jnp.float32) * jax.nn.sigmoid(
                gate.astype(jnp.float32))[..., None]).astype(self.dtype)
        out = nn.DenseGeneral(
            features=dim, axis=(-2, -1), use_bias=False, dtype=self.dtype,
            param_dtype=self.param_dtype, name="o_proj",
        )(out)
        if cache is not None:
            return out, new_cache
        return out
