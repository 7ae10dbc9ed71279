"""The per-layer decode cache every cache layout here is built from.

Lives with the cache code (``ops/kv_slots.py``, ``ops/kv_pages.py``, which
construct it) and below the models, whose attention layers write through
it (``models/layers.MultiHeadAttention``).
"""

from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp


@dataclasses.dataclass
class KVCache:
    """Per-layer decode cache; keys/values ``[B, max_len, n_kv_heads, D]``.

    Replaces nothing in the reference (its LLM path is a remote Ollama
    server, ``scripts/sentiment_classifier.py:85-100``); on TPU the cache is
    an explicit on-device buffer whose head axis shards over ``tp`` so
    decode attention stays local to each chip.
    """

    keys: jax.Array
    values: jax.Array
    # int32 — filled positions.  A scalar means every row shares one write
    # offset (static batch decode); a ``[B]`` vector gives each row its own
    # offset (slot-indexed continuous decode, ops/kv_slots.py).
    length: jax.Array

    @classmethod
    def zeros(
        cls,
        batch: int,
        max_len: int,
        n_kv_heads: int,
        head_dim: int,
        dtype=jnp.bfloat16,
    ) -> "KVCache":
        shape = (batch, max_len, n_kv_heads, head_dim)
        return cls(
            keys=jnp.zeros(shape, dtype),
            values=jnp.zeros(shape, dtype),
            length=jnp.zeros((), jnp.int32),
        )

    def update(self, k_new: jax.Array, v_new: jax.Array) -> "KVCache":
        start = self.length
        k_new = k_new.astype(self.keys.dtype)
        v_new = v_new.astype(self.values.dtype)
        if start.ndim == 1:
            # Per-row offsets: each slot writes its new tokens at its own
            # fill level (dynamic_update_slice clamps, so callers must keep
            # every row's length strictly below max_len - new + 1).
            write = jax.vmap(
                lambda buf, new, s: jax.lax.dynamic_update_slice(
                    buf, new, (s, 0, 0)
                )
            )
            keys = write(self.keys, k_new, start)
            values = write(self.values, v_new, start)
        else:
            keys = jax.lax.dynamic_update_slice(
                self.keys, k_new, (0, start, 0, 0)
            )
            values = jax.lax.dynamic_update_slice(
                self.values, v_new, (0, start, 0, 0)
            )
        return KVCache(keys, values, start + k_new.shape[1])

    @property
    def max_len(self) -> int:
        return self.keys.shape[1]

    def with_length(self, length) -> "KVCache":
        """The same buffers reporting ``length`` filled positions."""
        return KVCache(self.keys, self.values, jnp.asarray(length, jnp.int32))


jax.tree_util.register_dataclass(
    KVCache, data_fields=["keys", "values", "length"], meta_fields=[]
)


# ---------------------------------------------------------------------------
# Block-diffusion views.  A block-diffusion decoder prefills its prompt's
# whole blocks under a block-causal mask, then runs one block of positions
# over the cache again and again (denoising: the cache is read, nothing is
# written) and once more to keep it (commit: the block's keys and values
# are written at each row's own offset).  Each of the three is a view the
# attention layer treats as a cache that attends for itself
# (``models/layers.MultiHeadAttention``: ``cache.update(k, v).attend(q,
# mask)``, the seam the paged cache uses; the ``mask`` argument is unread,
# a view knows its rule).

NEG_INF = -1e30


def block_causal_tile(n_queries: int) -> int:
    """The flash kernel's tile for a block-causal prefill of this many
    positions (``ops/flash_attention.py``), 0 = outside its regime: whole
    tiles of 512 or 256 positions; anything else keeps the masked XLA
    form.  The one place the limit is written down."""
    for tile in (512, 256):
        if n_queries % tile == 0:
            return tile
    return 0


def prefill_tile_pairs(lengths, width: int, window: int = 0) -> int:
    """(query, key) pairs a query head of a causal prefill view
    (:class:`BlockCausalPrefill` with ``block`` 1) computes for rows
    ``lengths`` long at ``width`` positions a row (host integers): whole
    tiles of the kernel's grid where the width admits the kernel
    (``ops/flash_attention.visited_pairs`` at :func:`block_causal_tile`),
    every pair of every row in the masked XLA form."""
    import numpy as np

    tile = block_causal_tile(width)
    if not tile:
        return int(np.size(lengths)) * width * width
    from music_analyst_tpu.ops.flash_attention import visited_pairs

    return visited_pairs(lengths, width, tile, tile, window)


# Grouped-query attention in place: each key/value head meets its group of
# ``G = H // Hkv`` query heads as it is, never repeated to ``H`` heads
# (query head ``h`` reads key head ``h // G``, as a repeat would give it).
# The one definition of the masked form's two contractions: the views below
# and ``models/layers.dot_product_attention`` (every other caller's) use it.

def grouped_scores(q, k, scale):
    """``q [B, n, H, D]`` against ``k [B, L, Hkv, D]`` without repeating
    the key heads: ``[B, Hkv, G, n, L]`` float32."""
    B, n, H, D = q.shape
    qg = q.reshape(B, n, k.shape[2], H // k.shape[2], D)
    return jnp.einsum("bqhgd,bkhd->bhgqk", qg, k,
                      preferred_element_type=jnp.float32) * scale


def grouped_values(p, v, dtype):
    """``p [B, Hkv, G, n, L]`` over ``v [B, L, Hkv, D]``: ``[B, n, H, D]``
    float32 (``p`` cast to ``dtype`` first)."""
    out = jnp.einsum("bhgqk,bkhd->bqhgd", p.astype(dtype), v,
                     preferred_element_type=jnp.float32)
    return out.reshape(out.shape[:2] + (-1, out.shape[-1]))


@dataclasses.dataclass
class BlockCausalPrefill:
    """View of an EMPTY cache for the prefill of ``lengths`` positions a
    row (whole blocks of ``block``): attention is self-attention of the
    new keys under the block-causal rule, by the flash kernel where the
    width admits it (``kernel``; :func:`block_causal_tile`) and by the
    masked XLA form elsewhere; ``update`` lays the new keys and values at
    the head of the cache's buffers and reports ``lengths`` filled.  What
    the view returns at or behind a row's length is not defined.

    ``block`` 1 is the causal rule itself: the view of an autoregressive
    decoder's declared prefill (``models/llama.LlamaBlock``), rows of any
    length.  ``scale`` multiplies the scores (``None`` = ``D ** -0.5``).
    ``window`` > 0 is a sliding-window layer's prefill: a query sees the
    ``window`` keys up to its own (``key > query - window``) of those the
    rule above lets it see, in the kernel (attention path
    ``window_causal``; tiles wholly behind the window skipped) and in the
    masked form (``window_causal_dense``) alike."""

    cache: KVCache
    lengths: jax.Array  # [B] int32, a multiple of ``block`` a row
    block: int
    kernel: bool = True
    scale: float | None = None
    window: int = 0
    k_new: jax.Array | None = None
    v_new: jax.Array | None = None

    def update(self, k_new: jax.Array, v_new: jax.Array):
        cache = self.cache
        keys = jax.lax.dynamic_update_slice(
            cache.keys, k_new.astype(cache.keys.dtype), (0, 0, 0, 0))
        values = jax.lax.dynamic_update_slice(
            cache.values, v_new.astype(cache.values.dtype), (0, 0, 0, 0))
        return dataclasses.replace(
            self, cache=KVCache(keys, values, self.lengths.astype(jnp.int32)),
            k_new=k_new, v_new=v_new)

    def attend(self, q: jax.Array, mask=None) -> jax.Array:
        from music_analyst_tpu.profiling.compile import note_attention_path

        n = q.shape[1]
        tile = block_causal_tile(n) if self.kernel else 0
        if tile:
            from music_analyst_tpu.ops.flash_attention import flash_attention

            note_attention_path(
                "window_causal" if self.window else "block_causal")
            return flash_attention(
                q, self.k_new, self.v_new, lengths=self.lengths,
                causal=True, block_causal=self.block, block_q=tile,
                block_kv=tile, scale=self.scale, window=self.window)
        note_attention_path(
            "window_causal_dense" if self.window else "block_causal_dense")
        scores = grouped_scores(
            q, self.k_new,
            q.shape[-1] ** -0.5 if self.scale is None else self.scale)
        pos = jnp.arange(n)
        seen = (pos[None, :] // self.block <= pos[:, None] // self.block)
        if self.window:
            seen = seen & (pos[None, :] > pos[:, None] - self.window)
        seen = seen[None] & (pos[None, None, :]
                             < self.lengths[:, None, None])    # [B, n, n]
        scores = jnp.where(seen[:, None, None], scores, NEG_INF)
        return grouped_values(jax.nn.softmax(scores, axis=-1), self.v_new,
                              q.dtype).astype(q.dtype)


@dataclasses.dataclass
class BlockPass:
    """View of a filled cache for one pass of one block: the block's
    queries see the row's cached keys (``j < filled[row]``, the cache's
    length before the pass) and the block's own, all of them
    (bidirectional), computed as two score parts under one softmax so the
    cache is never copied.  ``commit`` (static) says whether ``update``
    writes the block's keys and values at each row's own offset and
    advances its length (the last pass of a block) or leaves the cache as
    it is (a denoising pass)."""

    cache: KVCache  # ``length`` a ``[B]`` vector
    commit: bool = False
    filled: jax.Array | None = None
    k_new: jax.Array | None = None
    v_new: jax.Array | None = None

    def update(self, k_new: jax.Array, v_new: jax.Array):
        cache = self.cache.update(k_new, v_new) if self.commit else self.cache
        return dataclasses.replace(self, cache=cache,
                                   filled=self.cache.length, k_new=k_new,
                                   v_new=v_new)

    def attend(self, q: jax.Array, mask=None) -> jax.Array:
        from music_analyst_tpu.profiling.compile import note_attention_path

        note_attention_path("block_over_cache")
        scale = q.shape[-1] ** -0.5
        keys, values = self.cache.keys, self.cache.values
        cached = grouped_scores(q, keys, scale)            # [B,Hkv,G,n,L]
        seen = (jnp.arange(keys.shape[1])[None, :]
                < self.filled[:, None])[:, None, None, None, :]
        cached = jnp.where(seen, cached, NEG_INF)
        own = grouped_scores(q, self.k_new.astype(keys.dtype), scale)
        probs = jax.nn.softmax(
            jnp.concatenate([cached, own], axis=-1), axis=-1)
        split = keys.shape[1]
        out = (grouped_values(probs[..., :split], values, q.dtype)
               + grouped_values(probs[..., split:],
                                self.v_new.astype(values.dtype), q.dtype))
        return out.astype(q.dtype)


for _view, _data, _meta in (
    (BlockCausalPrefill, ["cache", "lengths", "k_new", "v_new"],
     ["block", "kernel", "scale", "window"]),
    (BlockPass, ["cache", "filled", "k_new", "v_new"], ["commit"]),
):
    jax.tree_util.register_dataclass(_view, data_fields=_data,
                                     meta_fields=_meta)
