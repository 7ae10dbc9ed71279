"""The selective state-space recurrence of Mamba-2 (SSD, arXiv:2405.21060)
in three forms.

A head keeps a state ``S [P, N]`` (float32, zero at a row's start; ``P``
channels of the head, ``N`` the state size).  A token ``t`` brings the
head's input ``x_t [P]``, a step ``delta_t > 0`` (a scalar a head), and,
shared by every head of the one group, ``B_t`` and ``C_t`` (``[N]``); the
head's ``A < 0`` is a constant::

    S_t = exp(delta_t A) S_{t-1} + delta_t x_t B_t^T    (a scalar decay a head)
    y_t = S_t C_t

(the ``D x_t`` skip and the gate are the layer's, ``models/mamba2.py``).

* :func:`ssd_recurrent` — exactly those two lines, a token a step of a
  ``lax.scan``.  The label continuations and ``generate`` run it from the
  prompt's final state (a continuation reads the state it is given and
  advances its own copy), and the other two forms are tested against it.
* :func:`ssd_chunked_xla` — the chunked form as XLA programs, the state
  stepping from chunk to chunk in a ``lax.scan``: the path of a prefill whose
  rows are not declared (under a mesh the kernel's call would be opaque to
  the partitioner) and the kernel's oracle in the tests.
* :func:`ssd_chunked` — the same mathematics as one Pallas TPU kernel whose
  device operations carry ``_ssd_`` in their names.  A head's state stays in
  VMEM across a row's chunks; decays and state are float32, MXU operands
  bfloat16.  It takes the compact token stream
  (``models/compact_stream.RealPositions``: row ``b`` lies in slots
  ``[start_b, end_b)``, rows dense, nothing aligned) and, with ``start_b =
  b * S``, the padded ``[B, S]`` form: one kernel, two tables of row bounds
  (``ops/kda_attention.py``'s scheme).

The chunked form.  With ``a_t = delta_t A`` (the log-decay of a step, <= 0),
``G_i`` its running sum inside the chunk (inclusive), ``S_0`` the state on
entry and ``u_j = delta_j x_j`` what token ``j`` writes::

    Y = (exp(G) C) S_0^T + ((C B^T) o L) U,   L_ij = exp(G_i - G_j), j <= i
    S_C = exp(G_C) S_0 + (exp(G_C - G) U)^T B

``C B^T`` is one product for ALL heads (one group); a head differs by its
decays ``L`` and its ``U`` alone.  ``exp(G_i - G_j)`` is taken of the
difference, never as a quotient of two factors: a head may decay by ``e^-1.6``
a step (``delta`` up to 0.1, ``A`` down to -16 under the assumed
initialisation; the published model has no clamp either), so ``e^{-G_j}``
alone leaves float32 after 55 steps, and the difference is at most 0 wherever
it is used.  That costs ``CHUNK`` exponentials a head a token.  ``CHUNK`` is
128 (one MXU tile: the products above are whole tiles), not the published
256: the chunk length changes no value, only what the kernel pays for the
triangle against what it pays a grid step, and on the chip the two read
alike (6.90 against 6.55 ms a call of 11,348 tokens, ``bench.py
--suite=ssd_prefill``; PERF.md section 6, PR 37), so the smaller block of
VMEM stays.

The kernel's grid is (row, chunk of that row), as the KDA kernel's: a step
takes the aligned ``CHUNK``-slot block ``start_b // CHUNK + c`` of the stream
and ALL heads, ``128 / P`` heads at a time side by side on the lanes, so that
the products with the state are whole 128-lane tiles (a head's decayed
triangle times the lane tile of its neighbours, its own lanes kept).  Slots
of the block outside ``[start_b, end_b)`` belong to a neighbour or are
fillers: what they would write is taken as zero, and their output rows are
left as the neighbour's step wrote them (a block that two rows share is
visited by both, one after the other: the output block stays in VMEM between
the visits).  The running sum ``G`` is taken over ALL slots of an aligned
block, a neighbour's too, by the caller (a ``cumsum`` of ``[slots, H]``
float32, and its transpose, so that the kernel reads a head's sums both down
the sublanes and along the lanes): a shared block's rows read the same sums,
and only differences inside one row and the sum at the row's last slot are
used.  Fillers and neighbours have to be finite (they are multiplied by zero,
not skipped).

Pallas is imported when a call is traced, not when this module is.
"""

from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

CHUNK = 128
VMEM_LIMIT_BYTES = 64 * 1024 * 1024
F32 = jnp.float32


def _masked(dt, valid):
    """``dt`` with the steps of tokens that do not exist taken as zero: no
    decay and nothing written, the state passes them as it is."""
    return dt if valid is None else jnp.where(valid[..., None], dt, 0.0)


# ------------------------------------------------------------ token by token

def ssd_recurrent(x, dt, a, b, c, state, valid=None):
    """The recurrence a token a step.  ``x [B, T, H, P]``, ``dt [B, T, H]``
    (the step, > 0), ``a [H]`` (< 0), ``b, c [B, T, N]``, ``state [B, H, P,
    N]`` float32; ``valid [B, T]`` (bool) marks the tokens that exist: the
    others leave the state as it is.  Returns ``(y [B, T, H, P] float32,
    final state)``."""
    x, dt, b, c = (v.astype(F32) for v in (x, dt, b, c))
    dt = _masked(dt, valid)
    a = a.astype(F32)

    def step(s, token):
        x_t, dt_t, b_t, c_t = token
        s = s * jnp.exp(dt_t * a)[..., None, None] + jnp.einsum(
            "bhp,bn->bhpn", x_t * dt_t[..., None], b_t)
        return s, jnp.einsum("bhpn,bn->bhp", s, c_t)

    with jax.default_matmul_precision("highest"):
        state, y = jax.lax.scan(
            step, state.astype(F32),
            tuple(jnp.moveaxis(v, 1, 0) for v in (x, dt, b, c)))
    return jnp.moveaxis(y, 0, 1), state


# ------------------------------------------------------ chunked, XLA programs

def ssd_chunked_xla(x, dt, a, b, c, state, valid=None, chunk: int = CHUNK):
    """The chunked form as XLA programs; arguments and result as
    :func:`ssd_recurrent`, ``T`` a multiple of ``chunk``."""
    batch, n_tok, heads, width = x.shape
    if n_tok % chunk:
        raise ValueError(f"{n_tok} tokens are not whole chunks of {chunk}")
    x, dt, b, c = (v.astype(F32) for v in (x, dt, b, c))
    dt = _masked(dt, valid)
    n_chunks = n_tok // chunk

    def chunks(v):  # [B, T, ..] -> [n, B, chunk, ..]
        return jnp.moveaxis(
            v.reshape((batch, n_chunks, chunk) + v.shape[2:]), 1, 0)

    causal = (jnp.arange(chunk)[:, None] >= jnp.arange(chunk)[None, :])
    hi = jax.lax.Precision.HIGHEST

    def step(s, part):
        x_c, dt_c, b_c, c_c = part               # [B, C, H, P] [B, C, H] ..
        total = jnp.cumsum(dt_c * a.astype(F32), axis=1)        # [B, C, H]
        wrote = x_c * dt_c[..., None]
        pairs = jnp.einsum("bin,bjn->bij", c_c, b_c, precision=hi)
        decay = jnp.exp(jnp.minimum(
            total[:, :, None] - total[:, None, :], 0.0))     # [B, i, j, H]
        reach = jnp.where(causal[None, :, :, None],
                          pairs[..., None] * decay, 0.0)
        y = jnp.einsum("bijh,bjhp->bihp", reach, wrote, precision=hi)
        y = y + jnp.exp(total)[..., None] * jnp.einsum(
            "bin,bhpn->bihp", c_c, s, precision=hi)
        last = total[:, -1]                                       # [B, H]
        s = s * jnp.exp(last)[..., None, None] + jnp.einsum(
            "bjhp,bjn->bhpn",
            wrote * jnp.exp(last[:, None] - total)[..., None], b_c,
            precision=hi)
        return s, y

    state, y = jax.lax.scan(step, state.astype(F32),
                            tuple(chunks(v) for v in (x, dt, b, c)))
    return jnp.moveaxis(y, 0, 1).reshape(batch, n_tok, heads, width), state


# ------------------------------------------------------------- Pallas kernel

def _block_of(start, end, step, chunk: int):
    """The stream block a (row, step) of the grid holds and whether it
    runs."""
    first = start // chunk
    last = jnp.maximum(end - 1, 0) // chunk
    return (jnp.minimum(first + step, last),
            (end > start) & (first + step <= last))


def heads_a_tile(n_heads: int, head_dim: int) -> int:
    """Heads a kernel step lays side by side on one 128-lane tile."""
    return max(1, min(n_heads, 128 // head_dim))


def _ssd_kernel(starts_ref, ends_ref, u_ref, bt_ref, c_ref, sums_ref,
                sums_t_ref, y_ref, s_ref, *, n_heads: int, head_dim: int,
                chunk: int):
    from jax.experimental import pallas as pl

    row_id, step = pl.program_id(0), pl.program_id(1)
    start, end = starts_ref[row_id], ends_ref[row_id]
    block, live = _block_of(start, end, step, chunk)
    stack = heads_a_tile(n_heads, head_dim)
    lanes = stack * head_dim

    @pl.when(step == 0)
    def _empty_state():
        s_ref[...] = jnp.zeros_like(s_ref)

    @pl.when(live)
    def _chunk():
        slot = block * chunk + jax.lax.broadcasted_iota(
            jnp.int32, (chunk, 1), 0)
        own = (slot >= start) & (slot < end)                       # [C, 1]
        # the first step to touch this output block writes every row of it
        fresh = (step > 0) | (start % chunk == 0)
        at_last = slot == jnp.minimum(end, (block + 1) * chunk) - 1
        below = (jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 0)
                 >= jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 1))
        # C B^T once for all heads: the bfloat16 product the triangle of
        # every head is cut from
        pairs = jnp.where(below, jnp.dot(
            c_ref[...], bt_ref[...], preferred_element_type=F32), 0.0)
        sums = sums_ref[...]                                       # [C, H]
        sums_t = sums_t_ref[...]                                   # [H, C]
        head_lane = jax.lax.broadcasted_iota(jnp.int32, sums.shape, 1)
        head_row = jax.lax.broadcasted_iota(jnp.int32, sums_t.shape, 0)
        tile_head = jax.lax.broadcasted_iota(
            jnp.int32, (1, lanes), 1) // head_dim                  # [1, W]

        def group(index, carry):
            at = index * lanes
            if lanes % 128 == 0:
                at = pl.multiple_of(at, 128)
            tile = pl.ds(at, lanes)
            wrote = jnp.where(own, u_ref[:, tile], jnp.zeros((), u_ref.dtype))
            inner = jnp.zeros((chunk, lanes), F32)
            read_decay = jnp.zeros((chunk, lanes), F32)
            write_decay = jnp.zeros((chunk, lanes), F32)
            carry_decay = jnp.zeros((1, lanes), F32)
            for i in range(stack):
                head = index * stack + i
                # this head's running sums, down the sublanes and along
                # the lanes (a masked reduction: the head is dynamic)
                down = jnp.sum(jnp.where(head_lane == head, sums, 0.0),
                               axis=1, keepdims=True)              # [C, 1]
                along = jnp.sum(jnp.where(head_row == head, sums_t, 0.0),
                                axis=0, keepdims=True)             # [1, C]
                reach = (pairs * jnp.exp(jnp.minimum(down - along, 0.0))
                         ).astype(wrote.dtype)
                mine = tile_head == i
                inner = jnp.where(mine, jnp.dot(
                    reach, wrote, preferred_element_type=F32), inner)
                end_sum = jnp.sum(jnp.where(at_last, down, 0.0), axis=0,
                                  keepdims=True)                   # [1, 1]
                read_decay = jnp.where(mine, jnp.exp(down), read_decay)
                # own slots lie at or before the row's last: never above 0
                write_decay = jnp.where(
                    mine, jnp.exp(jnp.minimum(end_sum - down, 0.0)),
                    write_decay)
                carry_decay = jnp.where(mine, jnp.exp(end_sum), carry_decay)
            state = s_ref[0, index]                                # [N, W]
            out = inner + read_decay * jnp.dot(
                c_ref[...], state.astype(wrote.dtype),
                preferred_element_type=F32)
            kept = jnp.where(fresh, 0.0, y_ref[:, tile].astype(F32))
            y_ref[:, tile] = jnp.where(own, out, kept).astype(y_ref.dtype)
            s_ref[0, index] = state * carry_decay + jnp.dot(
                bt_ref[...],
                (wrote.astype(F32) * write_decay).astype(wrote.dtype),
                preferred_element_type=F32)
            return carry

        jax.lax.fori_loop(0, n_heads // stack, group, 0)


@functools.partial(jax.jit, static_argnames=(
    "n_heads", "max_len", "chunk", "interpret"))
def _ssd_chunk_call(u, b_t, c, sums, sums_t, starts, ends, n_heads: int,
                    max_len: int, chunk: int, interpret: bool):
    """The ``pallas_call`` under one inner ``jit`` (every Mamba-2 layer of a
    forward shares one trace and one Mosaic lowering); its name is what a
    device trace finds the kernel by."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    slots, width = u.shape
    head_dim = width // n_heads
    n_state = c.shape[1]
    n_rows = starts.shape[0]
    stack = heads_a_tile(n_heads, head_dim)

    def block_of(b, step, starts, ends):
        return _block_of(starts[b], ends[b], step, chunk)[0]

    def down(lanes):  # tokens down the sublanes
        return pl.BlockSpec(
            (chunk, lanes), lambda *a: (block_of(*a), 0),
            memory_space=pltpu.VMEM)

    def along(rows):  # tokens along the lanes
        return pl.BlockSpec(
            (rows, chunk), lambda *a: (0, block_of(*a)),
            memory_space=pltpu.VMEM)

    return pl.pallas_call(
        functools.partial(_ssd_kernel, n_heads=n_heads, head_dim=head_dim,
                          chunk=chunk),
        out_shape=(
            jax.ShapeDtypeStruct((slots, width), u.dtype),
            jax.ShapeDtypeStruct(
                (n_rows, n_heads // stack, n_state, stack * head_dim), F32),
        ),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            # a row of max_len slots that starts inside a block ends
            # max_len / chunk blocks on
            grid=(n_rows, -(-max_len // chunk) + 1),
            in_specs=[down(width), along(n_state), down(n_state),
                      down(n_heads), along(n_heads)],
            out_specs=(
                down(width),
                pl.BlockSpec(
                    (1, n_heads // stack, n_state, stack * head_dim),
                    lambda b, step, starts, ends: (b, 0, 0, 0),
                    memory_space=pltpu.VMEM),
            ),
        ),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
            vmem_limit_bytes=VMEM_LIMIT_BYTES,
        ),
        interpret=interpret,
    )(starts, ends, u, b_t, c, sums, sums_t)


def ssd_chunk_admits(n_slots: int, n_heads: int, head_dim: int,
                     chunk: int = CHUNK) -> bool:
    """Whether the kernel takes a stream of ``n_slots``: whole blocks, and
    heads that lie side by side on whole 128-lane tiles (or all of them on
    one narrower than that)."""
    stack = heads_a_tile(n_heads, head_dim)
    return (n_slots % chunk == 0 and n_heads % stack == 0
            and ((stack * head_dim) % 128 == 0 or stack == n_heads))


def ssd_chunked(x, dt, a, b, c, starts, ends, valid, n_heads: int,
                max_len: int, chunk: int = CHUNK,
                interpret: Optional[bool] = None,
                ) -> Tuple[jax.Array, jax.Array]:
    """The recurrence over a token stream whose row ``r`` lies in slots
    ``[starts[r], ends[r])`` (``ends - starts <= max_len``; rows in order,
    not overlapping), every row from a zero state.  Heads side by side on
    the last axis: ``x [T, H*P]``, ``dt [T, H]`` float32 (the step), ``a
    [H]``, ``b, c [T, N]``; ``valid [T]`` (bool) = the slot lies in a row.
    Returns ``(y [T, H*P]`` in ``x``'s dtype, zero off the rows' slots,
    ``states [R, H, P, N]`` float32, each row's after its last slot``)``.
    Every operand has to be finite on every slot.  Only for streams
    :func:`ssd_chunk_admits`."""
    from music_analyst_tpu.ops.flash_attention import interpret_default

    n_slots, width = x.shape
    head_dim = width // n_heads
    if not ssd_chunk_admits(n_slots, n_heads, head_dim, chunk):
        raise ValueError(
            f"{n_slots} slots x {n_heads} heads of {head_dim} are outside "
            "the kernel's regime (ssd_chunk_admits)")
    if interpret is None:
        interpret = interpret_default()
    dt = dt.astype(F32)
    # what a token writes, in the MXU's operand type, the step at every lane
    # of its head (a product of one term each: exact but for the two
    # roundings); the running sum of the log-decay inside each aligned block
    spread = jnp.repeat(jnp.eye(n_heads, dtype=x.dtype), head_dim, axis=1)
    wrote = x * (dt.astype(x.dtype) @ spread)
    sums = jnp.cumsum(
        (dt * a.astype(F32)).reshape(n_slots // chunk, chunk, n_heads),
        axis=1).reshape(n_slots, n_heads)
    y, state = _ssd_chunk_call(
        wrote, b.astype(x.dtype).T, c.astype(x.dtype), sums, sums.T,
        starts.astype(jnp.int32), ends.astype(jnp.int32), n_heads=n_heads,
        max_len=int(max_len), chunk=int(chunk), interpret=interpret)
    # blocks no row reaches are never written
    y = jnp.where(valid[:, None], y, jnp.zeros((), y.dtype))
    n_rows, groups, n_state, _ = state.shape
    state = state.reshape(n_rows, groups, n_state, n_heads // groups, head_dim)
    return y, jnp.transpose(state, (0, 1, 3, 4, 2)).reshape(
        n_rows, n_heads, head_dim, n_state)
