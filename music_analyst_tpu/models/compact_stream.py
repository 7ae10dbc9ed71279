"""Which form a decoder's prefill takes, and on how many token slots: the
one place that decides it.

A prefill that declares its rows' lengths (``prefill_lengths``: causal,
from position 0, on empty caches, one device; a mesh withholds them) may
run on its real positions alone: the compact token stream
(:class:`RealPositions`), each row's real positions one behind the other
in a static number of slots, fillers behind the last row.

* **The stream** (:func:`runs_compact`): blocks that take it
  (``LlamaConfig.compact_stream``) at a kernel width
  (``ops/packed_stream.stream_tile``: rows of whole 256-slot tiles, at
  least two) keep the hidden state on the stream from the embedding to the
  head, full rows included (a step of full rows is a compact set with no
  filler).  Every layer there takes the stream as it is handed it: the
  packed latent and grouped-query kernels, the KDA and the Mamba-2 chunk
  kernels.
* **The feed-forward halves alone** (:func:`feed_forward_positions`): at a
  width the kernels refuse, a declared capacity under the step's positions
  still runs the feed-forward halves on the real positions.
* **Padded**: everything else (no lengths; blocks that do not take the
  stream, the plain grouped-query decoders).

The capacity (:func:`compact_capacity`) is the smallest eighth of the step
that holds the real tokens, whole tiles at a kernel width; a caller that
declares lengths and no capacity gets ``rows x width``.  The model
(``models/llama.LlamaModel``), its blocks' feed-forward halves and whoever
counts what a step computed (the classifier's counters,
``models/block_diffusion.py``) read this module; the layers below take the
stream they are handed.
"""

from __future__ import annotations

from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np

from music_analyst_tpu.ops.packed_stream import stream_tile

# A step shape has at most this many compiled programs: its capacities are
# eighths of its positions.
COMPACT_RUNGS = 8


def compact_capacity(real: int, rows: int, width: int) -> int:
    """Token slots a prefill of ``rows x width`` positions runs where
    ``real`` of them lie inside a row's length: the smallest multiple of
    an eighth of the step that holds them, rounded up to whole tiles at a
    kernel width (every rung of 16, 32 and 64 rows of 1,024 already is),
    so a step shape has at most :data:`COMPACT_RUNGS` compiled programs.
    Never more than the step's positions.  Host arithmetic on the lengths
    the caller already has."""
    positions = rows * width
    rung = -(-positions // COMPACT_RUNGS)
    capacity = min(positions, rung * max(1, -(-real // rung)))
    tile = stream_tile(width, positions)
    # the step's positions are whole tiles at such a width: never passed
    return -(-capacity // tile) * tile if tile else capacity


def runs_compact(config, shape, capacity: Optional[int]) -> bool:
    """Whether a prefill of ``shape`` (rows, width) that declares its rows'
    lengths and ``capacity`` slots keeps its hidden state on the compact
    token stream from the embedding to the head: blocks that take the
    stream and a width and a slot count the packed kernels take, under the
    block-diffusion rule where the model has one.  ``None`` = no capacity
    declared (a mesh withholds it): padded."""
    block = config.block_length if config.block_diffusion else 1
    return (config.compact_stream and capacity is not None
            and bool(stream_tile(int(shape[1]), capacity, block)))


def prefill_stream(config, lengths, width: int,
                   capacity: Optional[int]) -> Optional[RealPositions]:
    """The compact stream of a prefill that declares ``lengths`` (``[B]``,
    ``None`` = none declared) at ``width`` positions a row and ``capacity``
    slots (``None`` = ``B x width``), where :func:`runs_compact` says so;
    ``None`` = the ``[B, S]`` stream."""
    if lengths is None:
        return None
    if capacity is None:
        capacity = lengths.shape[0] * width
    if not runs_compact(config, (lengths.shape[0], width), capacity):
        return None
    return RealPositions.of(lengths, width, capacity)


def feed_forward_positions(lengths, width: int, capacity: Optional[int]
                           ) -> Optional[RealPositions]:
    """On a ``[B, S]`` stream (:func:`prefill_stream` said no: a width the
    kernels refuse), the real positions a block's feed-forward half alone
    runs on: a declared capacity under the step's positions; ``None`` =
    every position."""
    if (lengths is None or capacity is None
            or capacity >= lengths.shape[0] * width):
        return None
    return RealPositions.of(lengths, width, capacity)


def step_capacity(config, lengths, shape) -> Optional[int]:
    """The static capacity a step of ``shape`` (rows, width) whose rows
    have ``lengths`` (host integers) is handed: :func:`compact_capacity`
    where the blocks take the stream, ``None`` otherwise, so such a step
    has one program."""
    if not config.compact_stream:
        return None
    return compact_capacity(int(np.asarray(lengths, np.int64).sum()),
                            int(shape[0]), int(shape[1]))


def stream_slots(config, shape, capacity: Optional[int]) -> int:
    """Token slots a declared prefill of ``shape`` (rows, width) put
    through its layers: ``capacity`` on the stream, every position
    otherwise."""
    if runs_compact(config, shape, capacity):
        return capacity
    return int(shape[0]) * int(shape[1])


class RealPositions(NamedTuple):
    """The real positions (``pos < lengths[row]``) of a ``[B, S]`` step,
    in row-major order, as slots ``0 .. N-1`` of a compact token set of a
    static ``capacity >= N``; the ``capacity - N`` filler slots behind
    them hold a copy of the last real position and are never read back."""

    source: jax.Array  # [C] int32  flat position ``row * S + pos`` of a slot
    valid: jax.Array   # [C] bool   the slot holds a real position
    start: jax.Array   # [B] int32  a row's first slot
    slot: jax.Array    # [B, S] int32  a real position's slot (else clamped)
    real: jax.Array    # [B, S] bool   ``pos < lengths[row]``

    @classmethod
    def of(cls, lengths: jax.Array, width: int,
           capacity: int) -> "RealPositions":
        """From the rows' lengths by arithmetic: the rows' first slots are
        the exclusive running sum of the lengths, a slot's row is the
        number of rows that end at or before it, its position the rest.
        The caller promises ``sum(lengths) <= capacity``."""
        lengths = lengths.astype(jnp.int32)
        ends = jnp.cumsum(lengths)
        starts = ends - lengths
        slots = jnp.arange(capacity, dtype=jnp.int32)
        valid = slots < ends[-1]
        last = jnp.maximum(ends[-1] - 1, 0)
        held = jnp.where(valid, slots, last)
        row = jnp.searchsorted(ends, held, side="right",
                               method="compare_all").astype(jnp.int32)
        row = jnp.minimum(row, lengths.shape[0] - 1)
        pos = jnp.arange(width, dtype=jnp.int32)[None, :]
        real = pos < lengths[:, None]
        return cls(
            source=row * width + (held - starts[row]), valid=valid,
            start=starts,
            slot=jnp.where(real, starts[:, None] + pos, 0), real=real)

    def gather(self, x: jax.Array) -> jax.Array:
        """``x [B, S, ...]`` at the slots' positions: ``[C, ...]``."""
        return x.reshape((-1,) + x.shape[2:])[self.source]

    def put_back(self, y: jax.Array) -> jax.Array:
        """``y [C, ...]`` at its positions' places in ``[B, S, ...]``,
        zeros at and behind every row's length."""
        real = self.real.reshape(self.real.shape + (1,) * (y.ndim - 1))
        return jnp.where(real, y[self.slot], jnp.zeros((), y.dtype))
