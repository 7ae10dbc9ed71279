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
