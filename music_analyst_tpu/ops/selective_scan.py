"""The selective scan of Mamba-1 (arXiv:2312.00752) in two forms.

A row keeps a state ``S [I, N]`` (float32, zero at a row's start; ``I``
channels, ``N`` the state size): every channel has ``N`` entries, and every
entry decays at a rate of its own.  A token ``t`` brings the channels'
input ``x_t [I]``, the low-rank input of the step size ``r_t [R]`` and
``B_t``, ``C_t`` (``[N]``, shared by all channels); ``W_dt [R, I]``,
``b_dt [I]``, ``A = -exp(A_log) [I, N]`` and ``D [I]`` are the layer's::

    delta_t = softplus(r_t W_dt + b_dt)                  [I] float32
    S_t = exp(delta_t (x) A) o S_{t-1} + (delta_t o x_t) (x) B_t
    y_t = S_t C_t + D o x_t

(``(x)`` an outer product, ``o`` elementwise; the gate is the layer's,
``models/mamba1.py``).  ``r_t W_dt`` is a product of the operands' type
(bfloat16 in the model) with a float32 result; the step, its softplus, the
decays and the state are float32.  Unlike Mamba-2's scalar decay a head
(``ops/ssd_scan.py``), no matrix form of a chunk exists here: a chunk's
decays are ``[tokens, I, N]``, one number an entry a token.

* :func:`selective_scan_recurrent` — exactly those lines, a token a step of
  a ``lax.scan``.  The label continuations and ``generate`` run it from the
  prompt's final state (a continuation reads the state it is given and
  advances its own copy); so does every prefill that is not on the compact
  token stream, and the kernel is tested against it.
* :func:`selective_scan_chunked` — the same recurrence as one Pallas TPU
  kernel whose device operations carry ``_selective_scan`` in their names.
  It takes ``r``, not ``delta``: the step is computed a tile at a time in
  VMEM and never written out (``[tokens, I]`` float32 would be 0.5 GB at
  24,576 slots and 5,120 channels, a layer).  It takes the compact token
  stream (``models/compact_stream.RealPositions``: row ``b`` lies in slots
  ``[start_b, end_b)``, rows dense, nothing aligned).

The kernel.  Its grid is (row, chunk of that row): a step takes the aligned
``CHUNK``-slot block ``start_b // CHUNK + c`` of the stream with every
channel, and the row's state ``[I / 128, N, 128]`` stays in VMEM from one
chunk to the next (channels on the lanes, the ``N`` entries of a channel on
the sublanes).  Inside a step, a loop over lane tiles of ``LANE_TILE``
channels computes the tile's ``delta`` (an MXU product of the chunk's ``r``
with the tile's columns of ``W_dt``) and ``delta o x``, then runs the
recurrence serially over the chunk's tokens, eight at a time, from the
row's first slot in the block to its last: a token is an ``exp`` and a
multiply-add on ``[LANE_TILE / 128, N, 128]`` and a sum over the sublanes
for ``y``.  ``B_t`` and ``C_t`` are needed down the sublanes and the same on
every lane: two exact products with one-hot matrices lay them out so, once
a step.  Slots of the block outside ``[start_b, end_b)`` belong to a
neighbour or are fillers: their step is taken as zero (no decay, nothing
written) and their output rows are left as the neighbour's step wrote them
(a block that two rows share is visited by both, one after the other: the
output block stays in VMEM between the visits).  Rows run one after the
other; a v5e has one TensorCore, and the lanes and the entries of a tile
are the parallelism.  The kernel stays inside the compiler's default 16 MiB
of scoped VMEM (about 9 MiB at 5,120 channels), so that nothing is taken
from what XLA's memory-space assignment places around it.

Pallas is imported when a call is traced, not when this module is.
"""

from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

CHUNK = 64
LANE_TILE = 512
F32 = jnp.float32


def step_sizes(low, w_dt, b_dt):
    """``delta = softplus(low W_dt + b_dt)``, float32: ``low [.., R]`` and
    ``w_dt [R, I]`` in the operands' type (bfloat16 in the model), the
    product's result float32."""
    return jax.nn.softplus(
        jnp.dot(low, w_dt.astype(low.dtype), preferred_element_type=F32)
        + b_dt.astype(F32))


def decay_rates(a_log):
    """``A = -exp(A_log)``, float32, ``[I, N]``."""
    return -jnp.exp(a_log.astype(F32))


# ------------------------------------------------------------ token by token

def selective_scan_recurrent(x, low, w_dt, b_dt, a_log, b, c, skip, state,
                             valid=None):
    """The recurrence a token a step.  ``x [B, T, I]``, ``low [B, T, R]``,
    ``w_dt [R, I]``, ``b_dt [I]``, ``a_log [I, N]``, ``b, c [B, T, N]``,
    ``skip [I]`` (``D``), ``state [B, I, N]`` float32; ``valid [B, T]``
    (bool) marks the tokens that exist: the others leave the state as it
    is.  Returns ``(y [B, T, I] float32, final state)``."""
    delta = step_sizes(low, w_dt, b_dt)
    if valid is not None:
        delta = jnp.where(valid[..., None], delta, 0.0)
    a = decay_rates(a_log)
    xs = x.astype(F32)
    wrote = delta * xs

    def step(s, token):
        d_t, u_t, b_t, c_t = token
        s = jnp.exp(d_t[..., None] * a) * s + u_t[..., None] * b_t[:, None]
        return s, jnp.einsum("bin,bn->bi", s, c_t)

    with jax.default_matmul_precision("highest"):
        state, y = jax.lax.scan(
            step, state.astype(F32),
            tuple(jnp.moveaxis(v, 1, 0) for v in (
                delta, wrote, b.astype(F32), c.astype(F32))))
    return jnp.moveaxis(y, 0, 1) + skip.astype(F32) * xs, state


# ------------------------------------------------------------- Pallas kernel

def _block_of(start, end, step, chunk: int):
    """The stream block a (row, step) of the grid holds and whether it
    runs."""
    first = start // chunk
    last = jnp.maximum(end - 1, 0) // chunk
    return (jnp.minimum(first + step, last),
            (end > start) & (first + step <= last))


def lane_tile(inner: int) -> int:
    """Channels a pass of the kernel's lane loop takes."""
    return max(t for t in (128, 256, LANE_TILE) if inner % t == 0)


def _scan_kernel(starts_ref, ends_ref, x_ref, low_ref, b_ref, c_ref, w_ref,
                 bias_ref, a_ref, skip_ref, y_ref, s_out_ref, b_lanes_ref,
                 c_lanes_ref, delta_ref, wrote_ref, y_tile_ref, s_ref, *,
                 chunk: int, lanes: int):
    from jax.experimental import pallas as pl

    row_id, step = pl.program_id(0), pl.program_id(1)
    start, end = starts_ref[row_id], ends_ref[row_id]
    block, live = _block_of(start, end, step, chunk)
    n_state = b_ref.shape[1]
    groups = lanes // 128
    inner = x_ref.shape[1]

    @pl.when(step == 0)
    def _empty_state():
        s_ref[...] = jnp.zeros_like(s_ref)

    @pl.when(live)
    def _chunk():
        first = block * chunk
        slot = first + jax.lax.broadcasted_iota(jnp.int32, (chunk, 1), 0)
        own = (slot >= start) & (slot < end)                      # [C, 1]
        # the first step to touch this output block writes every row of it
        fresh = (step > 0) | (start % chunk == 0)
        # B and C a token down the sublanes, the same on every lane: row
        # ``t N + n`` of ``[C N, 128]`` holds entry ``n`` of token ``t``
        # (products with one-hot matrices: exact)
        spread_rows = chunk * n_state
        pick = (jax.lax.broadcasted_iota(jnp.int32, (spread_rows, chunk), 0)
                // n_state == jax.lax.broadcasted_iota(
                    jnp.int32, (spread_rows, chunk), 1)).astype(b_ref.dtype)
        entry = (jax.lax.broadcasted_iota(jnp.int32, (spread_rows, n_state), 0)
                 % n_state == jax.lax.broadcasted_iota(
                     jnp.int32, (spread_rows, n_state), 1))
        every_lane = jnp.ones((n_state, 128), b_ref.dtype)

        def spread(v):
            by_token = jnp.dot(pick, v, preferred_element_type=F32)
            return jnp.dot(jnp.where(entry, by_token, 0.0).astype(v.dtype),
                           every_lane, preferred_element_type=F32)

        b_lanes_ref[...] = spread(b_ref[...])
        c_lanes_ref[...] = spread(c_ref[...])
        # the eight-token groups that hold this row's slots of the block
        lo = jnp.maximum(start - first, 0) // 8
        hi = (jnp.minimum(end - first, chunk) + 7) // 8

        def tile(index, carry):
            at = pl.multiple_of(index * lanes, lanes)
            cols = pl.ds(at, lanes)
            delta = jax.nn.softplus(
                jnp.dot(low_ref[...], w_ref[:, cols],
                        preferred_element_type=F32) + bias_ref[:, cols])
            delta = jnp.where(own, delta, 0.0)        # no decay, no write
            xs = x_ref[:, cols].astype(F32)
            wrote = delta * xs
            for g in range(groups):
                delta_ref[g] = delta[:, g * 128:(g + 1) * 128]
                wrote_ref[g] = wrote[:, g * 128:(g + 1) * 128]
            rate = a_ref[pl.ds(index * groups, groups)]       # [G, N, 128]

            def eight(k, s):
                base = pl.multiple_of(k * 8, 8)
                d8 = delta_ref[:, pl.ds(base, 8), :]          # [G, 8, 128]
                u8 = wrote_ref[:, pl.ds(base, 8), :]
                at_b = pl.multiple_of(base * n_state, 8)
                b8 = b_lanes_ref[pl.ds(at_b, 8 * n_state), :]
                c8 = c_lanes_ref[pl.ds(at_b, 8 * n_state), :]
                rows = []
                for j in range(8):
                    b_j = b8[j * n_state:(j + 1) * n_state][None]
                    c_j = c8[j * n_state:(j + 1) * n_state][None]
                    s = (jnp.exp(d8[:, j:j + 1] * rate) * s
                         + u8[:, j:j + 1] * b_j)
                    rows.append(jnp.sum(s * c_j, axis=1, keepdims=True))
                y_tile_ref[:, pl.ds(base, 8), :] = jnp.concatenate(rows, 1)
                return s

            state = jax.lax.fori_loop(
                lo, hi, eight, s_ref[pl.ds(index * groups, groups)])
            s_ref[pl.ds(index * groups, groups)] = state
            y = jnp.concatenate([y_tile_ref[g] for g in range(groups)],
                                axis=1) + skip_ref[:, cols] * xs
            kept = jnp.where(fresh, 0.0, y_ref[:, cols].astype(F32))
            y_ref[:, cols] = jnp.where(own, y, kept).astype(y_ref.dtype)
            return carry

        jax.lax.fori_loop(0, inner // lanes, tile, 0)
        s_out_ref[0] = s_ref[...]


@functools.partial(jax.jit, static_argnames=("max_len", "chunk", "interpret"))
def _selective_scan_call(x, low, b, c, w_dt, b_dt, a_lanes, skip, starts,
                         ends, max_len: int, chunk: int, interpret: bool):
    """The ``pallas_call`` under one inner ``jit`` (every Mamba-1 layer of a
    forward shares one trace and one Mosaic lowering); its name is what a
    device trace finds the kernel by."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    slots, inner = x.shape
    rank, n_state = low.shape[1], b.shape[1]
    n_rows = starts.shape[0]
    lanes = lane_tile(inner)
    groups = inner // 128

    def block_of(row, step, starts, ends):
        return _block_of(starts[row], ends[row], step, chunk)[0]

    def down(width):  # tokens down the sublanes
        return pl.BlockSpec((chunk, width), lambda *a: (block_of(*a), 0),
                            memory_space=pltpu.VMEM)

    def whole(shape):  # the layer's own, every step
        return pl.BlockSpec(shape, lambda *a: (0,) * len(shape),
                            memory_space=pltpu.VMEM)

    return pl.pallas_call(
        functools.partial(_scan_kernel, chunk=chunk, lanes=lanes),
        out_shape=(
            jax.ShapeDtypeStruct((slots, inner), x.dtype),
            jax.ShapeDtypeStruct((n_rows, groups, n_state, 128), F32),
        ),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            # a row of max_len slots that starts inside a block ends
            # max_len / chunk blocks on
            grid=(n_rows, -(-max_len // chunk) + 1),
            in_specs=[down(inner), down(rank), down(n_state), down(n_state),
                      whole((rank, inner)), whole((1, inner)),
                      whole((groups, n_state, 128)), whole((1, inner))],
            out_specs=(
                down(inner),
                pl.BlockSpec((1, groups, n_state, 128),
                             lambda row, step, starts, ends: (row, 0, 0, 0),
                             memory_space=pltpu.VMEM),
            ),
            scratch_shapes=[
                pltpu.VMEM((chunk * n_state, 128), F32),
                pltpu.VMEM((chunk * n_state, 128), F32),
                pltpu.VMEM((lanes // 128, chunk, 128), F32),
                pltpu.VMEM((lanes // 128, chunk, 128), F32),
                pltpu.VMEM((lanes // 128, chunk, 128), F32),
                pltpu.VMEM((groups, n_state, 128), F32),
            ],
        ),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary")),
        interpret=interpret,
    )(starts, ends, x, low, b, c, w_dt, b_dt, a_lanes, skip)


def selective_scan_admits(n_slots: int, inner: int,
                          chunk: int = CHUNK) -> bool:
    """Whether the kernel takes a stream of ``n_slots`` slots of ``inner``
    channels: whole blocks, and channels on whole 128-lane tiles."""
    return n_slots % chunk == 0 and inner % 128 == 0


def selective_scan_chunked(x, low, w_dt, b_dt, a_log, b, c, skip, starts,
                           ends, valid, max_len: int, chunk: int = CHUNK,
                           interpret: Optional[bool] = None,
                           ) -> Tuple[jax.Array, jax.Array]:
    """The recurrence over a token stream whose row ``r`` lies in slots
    ``[starts[r], ends[r])`` (``ends - starts <= max_len``; rows in order,
    not overlapping), every row from a zero state.  ``x [T, I]``, ``low [T,
    R]``, ``b, c [T, N]`` (in ``x``'s dtype: the MXU's operands), ``w_dt [R,
    I]``, ``b_dt [I]``, ``a_log [I, N]``, ``skip [I]``; ``valid [T]`` (bool)
    = the slot lies in a row.  Returns ``(y [T, I]`` in ``x``'s dtype, zero
    off the rows' slots, ``states [R, I, N]`` float32, each row's after its
    last slot``)``.  Every operand has to be finite on every slot.  Only for
    streams :func:`selective_scan_admits`."""
    from music_analyst_tpu.ops.flash_attention import interpret_default

    n_slots, inner = x.shape
    n_state = a_log.shape[1]
    if not selective_scan_admits(n_slots, inner, chunk):
        raise ValueError(
            f"{n_slots} slots x {inner} channels are outside the kernel's "
            "regime (selective_scan_admits)")
    if interpret is None:
        interpret = interpret_default()
    # the decay rates with the channels on the lanes: [I / 128, N, 128]
    a_lanes = jnp.transpose(
        decay_rates(a_log).reshape(inner // 128, 128, n_state), (0, 2, 1))
    y, state = _selective_scan_call(
        x, low.astype(x.dtype), b.astype(x.dtype), c.astype(x.dtype),
        w_dt.astype(x.dtype), b_dt.astype(F32).reshape(1, inner), a_lanes,
        skip.astype(F32).reshape(1, inner), starts.astype(jnp.int32),
        ends.astype(jnp.int32), max_len=int(max_len), chunk=int(chunk),
        interpret=interpret)
    # blocks no row reaches are never written
    y = jnp.where(valid[:, None], y, jnp.zeros((), y.dtype))
    return y, jnp.transpose(state, (0, 1, 3, 2)).reshape(
        state.shape[0], inner, n_state)
