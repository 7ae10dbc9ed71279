"""The routed experts' grouped matrix multiplications over assignments
sorted by expert, as one Pallas TPU kernel: the prefill's form.

``x [A, K]`` holds the assignments of ``E`` groups one behind the other
(``group_sizes [E]``: group ``g`` is rows ``sum(sizes[:g]) ..`` of it),
rows behind the last group belong to none (a compact token set's fillers,
and the assignments to experts another chip holds).  Group ``g``'s rows
are multiplied by its weight block ``w[g] [K, N]``:

* :func:`grouped_swiglu` — ``silu(x @ gate[g]) * (x @ up[g])`` with both
  stacks read in the same grid step against one read of the row tile, the
  activation applied to the float32 accumulators, one bfloat16 ``[A, N]``
  written;
* :func:`grouped_matmul` — ``x @ w[g]``, the down projection.

Operands bfloat16, float32 accumulation, results in ``x``'s dtype, as
``jax.lax.ragged_dot`` gives them; rows that belong to no group come back
zero.

The grid walks the n-blocks of the weights outermost and, inside one, the
row tiles of ``x`` expert by expert (a scalar-prefetched table of visits
built on the device from ``group_sizes``).  A weight
block holds the whole contraction dim and its index stays unchanged
across all row tiles of its expert, so Pallas fetches each expert's block
once an n-block; a row tile that straddles two experts is visited for
each, consecutively (its block stays in VMEM), and each visit stores the
rows of its own expert alone.  Inside a visit the tile is computed
``STEP_ROWS`` rows at a time, and a step that holds no row of the expert
is skipped: what a straddling tile costs is one step's rows, not a
tile's.  Tiles behind the last group's end are visited once more, to
write their zeros, and fetch nothing.

XLA's own grouped matmul (``ragged-dot-none``) splits the contraction in
512s inside every row tile, so neither the expert's weights nor the row
tile stay resident: at the decoder cell's prefill it re-reads each row
tile for each of its three 256-wide n-blocks (PERF.md section 5).

Where the kernel runs is decided by the static shape alone
(:func:`kernel_tiles`): a call whose assignment rows a held expert reach a
row tile (a prefill's grouped matmuls) at widths of whole 128-lane tiles;
the calls of a label pass, a diffusion block and a decode step (a few rows
an expert) stay on ``ragged_dot``.  The tiles ask for no scoped VMEM
beyond the compiler's default (``VMEM_BUDGET``).
"""

from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# Rows of x a grid step fetches, in the order they are tried; a call takes
# the kernel where its rows a held expert reach the tile.  On a v5e at the
# decoder cell's prefill (PERF.md section 5) 256 rows with the whole hidden
# width a block ran 4.77 ms a layer, 512 rows, where half of it fits, 4.93,
# and 128 rows 5.18.
ROW_TILES = (256, 128)
# Rows the MXU runs at a time inside a tile; a step without a row of the
# visit's expert is skipped (128: 4.93 ms a layer there, 256: 5.36, a whole
# 512-row tile: 6.62).
STEP_ROWS = 128
# What a call's double-buffered blocks and a step's float32 products may
# take of the compiler's default 16 MiB of scoped VMEM: the most that
# Mosaic compiled for a v5e at these shapes (blocks of 16.4 MiB it
# refused).
VMEM_BUDGET = 31 * 2 ** 19


def vmem_bytes(row_tile: int, step_rows: int, k_dim: int, n_block: int,
               stacks: int, itemsize: int = 2) -> int:
    """VMEM a call at these tiles holds: two buffers of each input block
    (the row tile and ``stacks`` weight blocks) and of the output block,
    and a step's float32 products."""
    blocks = row_tile * k_dim + stacks * k_dim * n_block + row_tile * n_block
    return 2 * blocks * itemsize + stacks * step_rows * n_block * 4


def kernel_tiles(rows: int, experts: int, k_dim: int, n_dim: int,
                 stacks: int) -> Optional[Tuple[int, int]]:
    """``(row_tile, n_block)`` of the kernel for ``rows`` assignments over
    ``experts`` held weight stacks of ``[k_dim, n_dim]`` (``stacks`` read a
    step: 2 for :func:`grouped_swiglu`, 1 for :func:`grouped_matmul`), or
    ``None`` where the call stays on ``jax.lax.ragged_dot``.  Both widths
    are whole 128-lane tiles (narrower ones, the test presets', would pad
    every block to the lane tile).  The widest n-block (a multiple of 128
    that divides the n-dim) whose blocks fit ``VMEM_BUDGET`` at a row tile
    of :data:`ROW_TILES` that the call's rows fill a whole number of
    times, at least once a held expert: each n-block reads every row tile
    once more, and the weights are read once whatever the tile."""
    if k_dim % 128 or n_dim % 128:
        return None
    for n_block in range(n_dim, 0, -128):
        if n_dim % n_block:
            continue
        for tile in ROW_TILES:
            if (rows % tile == 0 and rows >= tile * experts
                    and vmem_bytes(tile, min(STEP_ROWS, tile), k_dim,
                                   n_block, stacks) <= VMEM_BUDGET):
                return tile, n_block
    return None


def _visits(group_sizes, tiles: int, tile: int):
    """The scalar-prefetched tables of one call: group offsets ``[E + 1]``,
    each visit's group and row tile (``[tiles + E - 1]``), the number of
    visits that compute and the number of row tiles that hold a grouped
    row (``[1]`` each), and the grid's length: the computing visits, then
    one a tile behind the last group's end.  A tile that ``n`` groups share
    is visited ``n`` times in a row; a visit behind the computing ones
    holds the last computing visit's group (no weight fetch)."""
    experts = group_sizes.shape[0]
    sizes = group_sizes.astype(jnp.int32)
    ends = jnp.cumsum(sizes)
    starts = ends - sizes
    first = starts // tile
    count = jnp.where(sizes > 0, (ends + tile - 1) // tile - first, 0)
    visit_end = jnp.cumsum(count)
    active = visit_end[-1]
    live = (ends[-1] + tile - 1) // tile
    i = jnp.arange(tiles + experts - 1, dtype=jnp.int32)
    group = jnp.minimum(
        jnp.searchsorted(visit_end, i, side="right", method="compare_all"),
        experts - 1).astype(jnp.int32)
    row_tile = first[group] + i - (visit_end[group] - count[group])
    tail = i >= active
    last = group[jnp.maximum(active - 1, 0)]
    group = jnp.where(tail, last, group)
    row_tile = jnp.minimum(jnp.where(tail, live + i - active, row_tile),
                           tiles - 1)
    offsets = jnp.concatenate([jnp.zeros((1,), jnp.int32), ends])
    return (offsets, group, row_tile, active[None], live[None],
            active + tiles - live)


def _kernel(offsets, group, row_tile, active, live, x_ref, *refs,
            experts: int, step_rows: int, swiglu: bool):
    w_refs, out_ref = refs[:-1], refs[-1]
    visit = pl.program_id(1)
    tile, n_block = out_ref.shape
    total = offsets[experts]

    @pl.when(visit < active[0])
    def _compute():
        g = group[visit]
        start, end = offsets[g], offsets[g + 1]
        base = row_tile[visit] * tile
        for step in range(tile // step_rows):
            lo = base + step * step_rows
            part = pl.ds(step * step_rows, step_rows)

            @pl.when((lo < end) & (lo + step_rows > start))
            def _rows():
                x = x_ref[part, :]
                products = [jnp.dot(x, w[...],
                                    preferred_element_type=jnp.float32)
                            for w in w_refs]
                y = (jax.nn.silu(products[0]) * products[1] if swiglu
                     else products[0])
                rows = lo + jax.lax.broadcasted_iota(
                    jnp.int32, (step_rows, n_block), 0)
                # the rows of another expert keep what their own visit
                # wrote or will write; rows of no group are zero
                kept = jnp.where(rows >= total, 0.0,
                                 out_ref[part, :].astype(jnp.float32))
                out_ref[part, :] = jnp.where(
                    (rows >= start) & (rows < end), y, kept
                ).astype(out_ref.dtype)

            @pl.when(lo >= total)
            def _none():
                out_ref[part, :] = jnp.zeros((step_rows, n_block),
                                             out_ref.dtype)

    @pl.when(visit >= active[0])
    def _behind():
        out_ref[...] = jnp.zeros(out_ref.shape, out_ref.dtype)


@functools.partial(jax.jit, static_argnames=("tile", "n_block", "step_rows",
                                             "interpret"))
def _grouped_call(x, stacks, group_sizes, tile: int, n_block: int,
                  step_rows: int, interpret: bool):
    """The ``pallas_call`` under one inner ``jit``: every routed layer of a
    program at one shape shares one trace and one Mosaic lowering.  Grid:
    n-blocks of the weights by visits (:func:`_visits`)."""
    rows, k_dim = x.shape
    experts, _, n_dim = stacks[0].shape
    tiles = rows // tile
    *tables, grid_len = _visits(group_sizes, tiles, tile)

    def x_map(j, i, offsets, group, row_tile, active, live):
        # a visit behind the last group holds the last tile it read
        return (jnp.minimum(row_tile[i], jnp.maximum(live[0] - 1, 0)), 0)

    def w_map(j, i, offsets, group, row_tile, active, live):
        return (group[i], 0, j)

    def out_map(j, i, offsets, group, row_tile, active, live):
        return (row_tile[i], j)

    weight_bytes = sum(w.size * w.dtype.itemsize for w in stacks)
    return pl.pallas_call(
        functools.partial(_kernel, experts=experts, step_rows=step_rows,
                          swiglu=len(stacks) == 2),
        out_shape=jax.ShapeDtypeStruct((rows, n_dim), x.dtype),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=5,
            grid=(n_dim // n_block, grid_len),
            in_specs=[pl.BlockSpec((tile, k_dim), x_map,
                                   memory_space=pltpu.VMEM)]
            + [pl.BlockSpec((None, k_dim, n_block), w_map,
                            memory_space=pltpu.VMEM) for _ in stacks],
            out_specs=pl.BlockSpec((tile, n_block), out_map,
                                   memory_space=pltpu.VMEM),
        ),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary")),
        cost_estimate=pl.CostEstimate(
            flops=2 * rows * k_dim * n_dim * len(stacks),
            transcendentals=rows * n_dim if len(stacks) == 2 else 0,
            bytes_accessed=(x.size * x.dtype.itemsize * (n_dim // n_block)
                            + weight_bytes + rows * n_dim
                            * x.dtype.itemsize)),
        interpret=interpret,
    )(*tables, x, *stacks)


def _call(x, stacks, group_sizes, tiles, interpret):
    from music_analyst_tpu.ops.flash_attention import interpret_default

    rows, k_dim = x.shape
    experts, w_k, n_dim = stacks[0].shape
    if (w_k != k_dim or group_sizes.shape != (experts,)
            or any(w.shape != stacks[0].shape for w in stacks)):
        raise ValueError(
            f"rows {x.shape}, stacks {[w.shape for w in stacks]} and group "
            f"sizes {group_sizes.shape} do not make a grouped matmul")
    if tiles is None:
        tiles = kernel_tiles(rows, experts, k_dim, n_dim, len(stacks))
    if tiles is None:
        raise ValueError(
            f"{rows} rows over {experts} experts of [{k_dim}, {n_dim}] are "
            "outside the grouped matmul kernel's regime (kernel_tiles)")
    tile, n_block = tiles
    step_rows = min(STEP_ROWS, tile)
    if rows % tile or n_dim % n_block or tile % step_rows:
        raise ValueError(f"tiles {tiles} do not divide [{rows}, {n_dim}]")
    if interpret is None:
        interpret = interpret_default()
    return _grouped_call(x, tuple(stacks), group_sizes.astype(jnp.int32),
                         tile=int(tile), n_block=int(n_block),
                         step_rows=step_rows, interpret=interpret)


def grouped_swiglu(x, gate_w, up_w, group_sizes, tiles=None,
                   interpret: Optional[bool] = None):
    """``silu(x @ gate_w[g]) * (x @ up_w[g])`` for the rows of each group
    ``g``, zeros on the rows behind the last group.  ``x [A, K]``, stacks
    ``[E, K, N]``, ``group_sizes [E]`` (the caller promises ``sum <= A``);
    returns ``[A, N]`` in ``x``'s dtype.  ``tiles`` = ``(row_tile,
    n_block)``, ``None`` = :func:`kernel_tiles`'s (a test passes smaller
    ones)."""
    return _call(x, (gate_w, up_w), group_sizes, tiles, interpret)


def grouped_matmul(x, w, group_sizes, tiles=None,
                   interpret: Optional[bool] = None):
    """``x @ w[g]`` for the rows of each group ``g``, zeros on the rows
    behind the last group: :func:`grouped_swiglu` with one stack and no
    activation."""
    return _call(x, (w,), group_sizes, tiles, interpret)
