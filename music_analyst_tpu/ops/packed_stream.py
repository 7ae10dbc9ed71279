"""What the packed prefill kernels share: rows laid one behind the other on
a compact token set (``models/compact_stream.RealPositions``), read a query
block at a time by the latent prefill's packed kernel
(``ops/mla_prefill_attention.mla_prefill_attention_packed``) and the
grouped-query one (``ops/flash_attention.flash_attention_packed``).

:data:`PACKED_TILE` is their tile and :func:`stream_tile` the shapes they
take; :func:`packed_tables` are the five scalar-prefetched tables that tell
a block's rows apart; :func:`last_live_block` the block both kernels' index
maps clamp to; :data:`MAX_FLOOR` and :func:`lanes` their running softmax
statistics, kept ``[rows, 128]`` with every lane equal.
"""

from __future__ import annotations

import jax.numpy as jnp

# The queries and keys of one grid step.  On a v5e at 32 rows x 1,024 x 32
# latent heads and the benchmark's prompt lengths (PERF.md): 128 -> 3.7 ms
# a call, 256 -> 2.7, 512 -> 2.7 (a larger tile runs nearer the MXU's rate
# and executes more of the masked square).
PACKED_TILE = 256


def stream_tile(width: int, capacity: int, block: int = 1) -> int:
    """The packed kernels' tile for rows of up to ``width`` positions laid
    one behind the other in ``capacity`` slots under the rule of ``block``
    (1: causal), 0 = outside their regime: rows of whole tiles and at least
    two of them (a single tile has nothing above the diagonal to skip), a
    token set of whole tiles, and a block that divides the tile.  The one
    place the regime is written down (``models/compact_stream.py`` reads
    it)."""
    tile = PACKED_TILE
    if (width % tile or width < 2 * tile or capacity % tile
            or tile % block):
        return 0
    return tile


# Where a packed kernel's running max starts: above every masked score,
# under every real one.  A query whose row starts inside a block meets key
# blocks in which it has no key: its max stays at the floor, ABOVE the
# masked scores, which then weigh exp(-1e30 + 1e20) = 0.
MAX_FLOOR = -1e20


def last_live_block(total, block: int):
    """The last query block that holds a real slot of ``total``: a block
    behind it holds on to its operands (no DMA) and runs nothing."""
    return jnp.maximum(total - 1, 0) // block


def packed_tables(lengths, n_blocks: int, block: int, window: int = 0,
                  xp=jnp):
    """What tells a query block's rows apart in a packed kernel (``xp``:
    ``jnp`` in the program, ``np`` on the host, the same arithmetic): the
    rows' first slots, the row of a block's first and of its last real
    slot, the first key block it reads (its first row's start; under a
    window no block wholly behind the window of its first query) and the
    real total, ``[1]``.  A block behind the last real slot holds the last
    live block's entries."""
    ends = xp.cumsum(lengths)
    starts = ends - lengths
    total = ends[-1]
    first_slot = xp.minimum(xp.arange(n_blocks),
                            xp.maximum(total - 1, 0) // block) * block
    last_slot = xp.minimum(first_slot + block, xp.maximum(total, 1)) - 1

    def row_of(slot):
        # the rows that end at or before a slot lie before it (an empty
        # row holds none)
        method = {"method": "compare_all"} if xp is jnp else {}
        row = xp.searchsorted(ends, slot, side="right", **method)
        return xp.minimum(row.astype(xp.int32), lengths.shape[0] - 1)

    first_row = row_of(first_slot)
    first_key = starts[first_row] // block
    if window:
        first_key = xp.maximum(
            first_key, xp.maximum(first_slot - window + 1, 0) // block)
    return starts, first_row, row_of(last_slot), first_key, total[None]


def lanes(stat, width: int):
    """A ``[rows, 128]`` statistic (every lane equal) at ``width`` lanes."""
    from jax.experimental.pallas import tpu as pltpu

    if width % 128:
        return stat[:, :1]      # under a lane tile: the ``*-tiny`` presets
    return stat if width == 128 else pltpu.repeat(stat, width // 128, axis=1)
