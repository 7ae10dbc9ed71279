"""Causal prefill attention of the latent-attention block as one Pallas TPU
kernel on the compact token stream, that does only the work under the
diagonal and inside each row.

The expanded form of ``models/mla.py`` gives every head a key
``[k_nope | k_rope]`` (``k_rope`` one vector a token, shared by the heads)
and a value of another width (192 | 128 at the published sizes).  Its XLA
formulation (``models/mla.blocked_attention``) computes the full square of
one block of queries against every key and masks afterwards: at a 32 x
1,024 step whose prompts have a median of 286 tokens, nine tenths of the
scores are masked away.  This kernel takes the rows laid one behind the
other on a compact token set (``models/compact_stream.RealPositions``):
operands ``[C, H*D]`` hold each row's real positions one behind the other,
row ``b`` from slot ``sum(lengths[:b])`` on, fillers behind the last row,
and the output is written the same way, ``[C, H*v]``.  A padding position
has no slot: it is neither read nor written, it does not exist.  Rows lie
DENSE (no row start is aligned to a tile), so the grid is the token set's
query tiles by the key tiles a row of the step's width can reach back
over, and small scalar-prefetched tables (``ops/packed_stream.py``: the
rows' first slots, a tile's first and last row, the key tile its first row
starts in, the number of real slots) tell a tile's rows apart: a query's
keys are the slots from its row's start up to itself.  Key tiles above the
diagonal or before the first row's start are skipped, and a skipped tile
costs no DMA either: the index maps are clamped from the tables, so the
pipeline sees the tile it already holds.  A query tile behind the last
real slot runs nothing, fetches nothing and writes zeros to its own slots,
which no live tile reads.  The capacity is the host's promise
(``sum(lengths) <= C``, ``models/compact_stream.compact_capacity``).  What
runs is an online softmax over key tiles: bfloat16 operands on the MXU,
float32 scores, running max / sum / accumulator in float32, the scores
scaled after the matmul (``ops/flash_attention.py`` says why),
probabilities cast to the value dtype before ``P.V`` as the XLA form does.

Layout.  Operands are ``[C, H*D]``: slots on sublanes, a head a static
lane slice (whole 128-lane tiles at the published widths).  That is the
layout XLA gives the output of a contraction with a 2-D weight, so
``models/mla.py`` writes the projections that feed the kernel that way and
nothing is copied or transposed around the call.  (Fed from ``[.., H,
D]`` arrays, the compiler keeps those with the head axis major and adds a
slice and three transposing copies of 0.13 to 0.54 GB a layer.)  One grid
step takes ALL heads of a tile, in a loop inside the kernel: a step costs
0.35 us whether it runs or not, and a grid over heads too would spend more
on skipped steps than the work takes.  Keys and values arrive as one array
(the latent expansion ``[.., H, nope + v]``), sliced by lanes in the
kernel.

Who runs it is decided in ``models/compact_stream.py``: a prefill that
declares its rows' lengths on one device at a width the kernel takes
(``ops/packed_stream.stream_tile``), full rows included.  Under a mesh the
call would be opaque to the partitioner, so meshed callers withhold the
lengths and keep the XLA form.  There is no gradient.  Pallas is imported
when a call is traced, not when this module is.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp

from music_analyst_tpu.ops.packed_stream import (
    MAX_FLOOR,
    lanes,
    last_live_block,
    packed_tables,
    stream_tile,
)

NEG_INF = -1e30
VMEM_LIMIT_BYTES = 64 * 1024 * 1024


def _head_loop(n_heads: int, nope: int, rope: int, v_dim: int):
    """``(each, group)``: how the kernel walks the heads.  Where a head is
    whole lane tiles the heads are a loop, ``group`` of them an iteration,
    whose rope queries fill one lane tile: one trace of the body
    (unrolled, 32 heads cost 4.7 s of tracing a process on the chip's host
    and ran 6% faster, 2.70 against 2.86 ms a call, PERF.md).  Narrower
    heads (``kanana-tiny``) are unrolled with static lane slices."""
    looped = (nope % 128 == 0 and v_dim % 128 == 0 and 128 % rope == 0
              and n_heads % (128 // rope) == 0)

    def each(count, body):
        if looped:
            jax.lax.fori_loop(0, count, lambda i, carry: body(i) or carry, 0)
        else:
            for i in range(count):
                body(i)

    return each, (128 // rope if looped else 1)


def _attend_tile(valid, qn_ref, qr_ref, kv_ref, kr_ref, acc_ref, m_ref,
                 l_ref, *, n_heads: int, nope: int, rope: int, v_dim: int,
                 scale: float):
    """One online-softmax update of every head's statistics by one ``block
    x block`` tile of scores, of which ``valid`` are kept."""
    block = valid.shape[0]
    each, group = _head_loop(n_heads, nope, rope, v_dim)
    k_rope = kr_ref[:]
    trans_b = (((1,), (1,)), ((), ()))
    # The running max is kept of the unscaled scores and the scale
    # rides in the exponent with log2(e): exp(scale * (s - m)) as one
    # multiply and one exp2 an element.
    to_exp2 = scale * 1.4426950408889634

    def one_head(h, q_rope):
        kv0 = h * (nope + v_dim)
        s = jax.lax.dot_general(
            qn_ref[:, _lane_slice(h * nope, nope)],
            kv_ref[:, _lane_slice(kv0, nope)], trans_b,
            preferred_element_type=jnp.float32)
        s = s + jax.lax.dot_general(
            q_rope, k_rope, trans_b, preferred_element_type=jnp.float32)
        s = jnp.where(valid, s, NEG_INF)            # [block, block]
        # Statistics stay [block, 128] with every lane equal, as they
        # are stored: a [block, 1] column costs as many vector
        # registers and a lane broadcast each time it meets the scores.
        m_prev, l_prev = m_ref[h], l_ref[h]
        m_next = jnp.maximum(m_prev, s.max(axis=1, keepdims=True))
        alpha = jnp.exp2((m_prev - m_next) * to_exp2)
        p = jnp.exp2((s - lanes(m_next, block)) * to_exp2)
        m_ref[h] = m_next
        l_ref[h] = alpha * l_prev + p.sum(axis=1, keepdims=True)
        values = kv_ref[:, _lane_slice(kv0 + nope, v_dim)]
        out = _lane_slice(h * v_dim, v_dim)
        acc_ref[:, out] = (
            acc_ref[:, out] * lanes(alpha, v_dim)
            + jax.lax.dot_general(
                p.astype(values.dtype), values, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32))

    def heads(g):
        tile = qr_ref[:, _lane_slice(g * group * rope, group * rope)]
        for i in range(group):
            one_head(g * group + i, tile[:, i * rope:(i + 1) * rope])

    each(n_heads // group, heads)


def _write_tile(o_ref, acc_ref, l_ref, *, n_heads: int, nope: int,
                rope: int, v_dim: int):
    """``acc / l`` of every head into the output block.  A query block
    that never ran holds the zeros its first step set, and zeros are what
    it writes."""
    each, _ = _head_loop(n_heads, nope, rope, v_dim)

    def one_head(h):
        out = _lane_slice(h * v_dim, v_dim)
        inv = 1.0 / jnp.maximum(l_ref[h], 1e-30)
        o_ref[:, out] = (
            acc_ref[:, out] * lanes(inv, v_dim)).astype(o_ref.dtype)

    each(n_heads, one_head)


def _packed_kernel(
    start_ref,      # SMEM [B] — a row's first slot (prefetched, all five)
    first_row_ref,  # SMEM [blocks] — the row of a query block's first slot
    last_row_ref,   # SMEM [blocks] — the row of its last real slot
    first_key_ref,  # SMEM [blocks] — the key block its first row starts in
    total_ref,      # SMEM [1] — real slots: the rows' lengths summed
    qn_ref,    # VMEM [block, H*nope]
    qr_ref,    # VMEM [block, H*rope]
    kv_ref,    # VMEM [block, H*(nope+v)]
    kr_ref,    # VMEM [block, rope]
    o_ref,     # VMEM [block, H*v]
    acc_ref,        # VMEM [block, H*v] float32
    m_ref,          # VMEM [H, block, 128] float32, every lane equal
    l_ref,
    *,
    scale: float,
    **dims,
):
    from jax.experimental import pallas as pl

    block = qn_ref.shape[0]
    qi, step = pl.program_id(0), pl.program_id(1)
    ki = first_key_ref[qi] + step

    @pl.when(step == 0)
    def _init():
        # A query whose row starts inside this block meets key blocks in
        # which it has no key at all.  Its running max then stays at the
        # floor, ABOVE the masked scores, so they weigh exp(-1e30 + 1e20)
        # = 0 and not exp(0); its first real score lowers nothing (alpha
        # = exp(floor - m) = 0 of sums that are zero).
        m_ref[...] = jnp.full_like(m_ref, MAX_FLOOR)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    @pl.when((qi * block < total_ref[0]) & (ki <= qi))
    def _compute():
        q_pos = qi * block + jax.lax.broadcasted_iota(
            jnp.int32, (block, block), 0)
        k_pos = ki * block + jax.lax.broadcasted_iota(
            jnp.int32, (block, block), 1)
        # A query's keys are its row's slots up to itself: rows lie one
        # behind the other, so ``row start <= key <= query`` is the causal
        # rule and the row's boundary in one.  The row start of every
        # query: the largest start at or before it among the rows this
        # block holds (fillers behind the last row attend like its tail).
        first = first_row_ref[qi]
        q_start = jax.lax.fori_loop(
            first + 1, last_row_ref[qi] + 1,
            lambda r, held: jnp.where(q_pos >= start_ref[r], start_ref[r],
                                      held),
            jnp.full((block, block), start_ref[first], jnp.int32))
        valid = (k_pos <= q_pos) & (k_pos >= q_start)
        _attend_tile(valid, qn_ref, qr_ref, kv_ref, kr_ref, acc_ref,
                     m_ref, l_ref, scale=scale, **dims)

    @pl.when(step == pl.num_programs(1) - 1)
    def _finalize():
        _write_tile(o_ref, acc_ref, l_ref, **dims)


def _lane_slice(start, width: int):
    """``width`` lanes from ``start``: a Python int (a static slice) or a
    traced multiple of 128 (a head of the loop over heads)."""
    from jax.experimental import pallas as pl

    if not isinstance(start, int):
        start = pl.multiple_of(start, 128)
    return pl.ds(start, width)


@functools.partial(
    jax.jit,
    static_argnames=("n_heads", "scale", "block", "max_len", "interpret"))
def _packed_prefill_call(q_nope, q_rope, kv, k_rope, lengths, n_heads: int,
                         scale: float, block: int, max_len: int,
                         interpret: bool):
    """The ``pallas_call`` under one inner ``jit`` (every layer of a forward
    that calls it at one shape shares one trace and one Mosaic lowering):
    the grid is the token set's query blocks by the most key blocks a row
    of ``max_len`` reaches back over, and five small tables say where each
    block's rows lie."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    capacity = q_nope.shape[0]
    nope = q_nope.shape[-1] // n_heads
    rope = k_rope.shape[-1]
    v_dim = kv.shape[-1] // n_heads - nope
    n_blocks = capacity // block

    tables = packed_tables(lengths, n_blocks, block)

    def q_map(qi, step, starts, first_row, last_row, first_key, total):
        # a block past the last real slot holds on to the last live block's
        # operands (no DMA) and runs nothing
        return (jnp.minimum(qi, last_live_block(total[0], block)), 0)

    def k_map(qi, step, starts, first_row, last_row, first_key, total):
        # nor is a key block past the diagonal fetched: the index stays
        # where the last used one left it
        return (jnp.minimum(
            first_key[qi] + step,
            jnp.minimum(qi, last_live_block(total[0], block))), 0)

    def spec(width, index_map):
        return pl.BlockSpec((block, width), index_map,
                            memory_space=pltpu.VMEM)

    return pl.pallas_call(
        functools.partial(
            _packed_kernel, n_heads=n_heads, nope=nope, rope=rope,
            v_dim=v_dim, scale=scale,
        ),
        out_shape=jax.ShapeDtypeStruct((capacity, n_heads * v_dim),
                                       q_nope.dtype),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=5,
            # a row of max_len slots that starts inside a block ends
            # max_len / block blocks on
            grid=(n_blocks, max_len // block + 1),
            in_specs=[
                spec(n_heads * nope, q_map),
                spec(n_heads * rope, q_map),
                spec(n_heads * (nope + v_dim), k_map),
                spec(rope, k_map),
            ],
            out_specs=spec(n_heads * v_dim, lambda qi, step, *_: (qi, 0)),
            scratch_shapes=[
                pltpu.VMEM((block, n_heads * v_dim), jnp.float32),
                pltpu.VMEM((n_heads, block, 128), jnp.float32),
                pltpu.VMEM((n_heads, block, 128), jnp.float32),
            ],
        ),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
            vmem_limit_bytes=VMEM_LIMIT_BYTES,
        ),
        interpret=interpret,
    )(*tables, q_nope, q_rope, kv, k_rope)


def mla_prefill_attention_packed(
    q_nope: jax.Array,
    q_rope: jax.Array,
    kv: jax.Array,
    k_rope: jax.Array,
    lengths: jax.Array,
    max_len: int,
    n_heads: int,
    scale: float,
    interpret: Optional[bool] = None,
) -> jax.Array:
    """Causal self-attention of rows laid one behind the other on a compact
    token set (``models/compact_stream.RealPositions``): row ``b``'s
    ``lengths[b] <= max_len`` positions are the slots from
    ``sum(lengths[:b])`` on, the ``capacity - sum(lengths)`` slots behind
    the last row are fillers.
    ``q_nope [C,H*Dn]``, ``q_rope [C,H*Dr]``, ``kv [C,H*(Dn+Dv)]``,
    ``k_rope [C,Dr]``; returns ``[C,H*Dv]`` with every real slot's
    attention over its own row's slots up to itself, finite values on
    fillers that share a block with a real slot, zeros on the blocks
    behind.  The caller promises ``sum(lengths) <= C`` and finite
    operands on every slot, fillers too (a masked key's value is
    multiplied by a probability of zero, not skipped).

    Only for shapes ``ops/packed_stream.stream_tile`` admits (it raises
    otherwise: the caller chooses, this function does not fall back)."""
    from music_analyst_tpu.ops.flash_attention import interpret_default

    capacity = q_nope.shape[0]
    block = stream_tile(max_len, capacity)
    if not block:
        raise ValueError(
            f"rows of {max_len} in {capacity} slots are outside the "
            "kernel's regime (stream_tile); use models/mla.blocked_attention")
    if kv.shape[0] != capacity or k_rope.shape[0] != capacity:
        raise ValueError(
            f"keys {kv.shape} / {k_rope.shape} are not the queries' "
            f"{capacity} slots")
    if interpret is None:
        interpret = interpret_default()
    return _packed_prefill_call(
        q_nope, q_rope, kv, k_rope, lengths.astype(jnp.int32),
        n_heads=n_heads, scale=float(scale), block=block,
        max_len=int(max_len), interpret=interpret,
    )
