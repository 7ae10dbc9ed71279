"""Causal prefill attention of the latent-attention block as one Pallas TPU
kernel that does only the work under the diagonal and inside each row's
length.

The expanded form of ``models/mla.py`` gives every head a key
``[k_nope | k_rope]`` (``k_rope`` one vector a token, shared by the heads)
and a value of another width (192 | 128 at the published sizes).  Its XLA
formulation (``models/mla.blocked_attention``) computes the full square of
one block of queries against every key and masks afterwards: at a 32 x
1,024 step whose prompts have a median of 286 tokens, nine tenths of the
scores are masked away.  This kernel takes the rows' ``lengths`` and the
causal rule in place of the mask and skips

* key blocks wholly above the diagonal,
* key blocks that start at or past ``lengths[row]``,
* query blocks that start at or past ``lengths[row]`` (written as zeros:
  nothing reads a padding position's attention output),

and a skipped block costs no DMA either: the index maps are clamped from
the scalar-prefetched lengths, so the pipeline sees the block it already
holds.  What runs is an online softmax over key blocks: bfloat16 operands
on the MXU, float32 scores, running max / sum / accumulator in float32,
the scores scaled after the matmul (``ops/flash_attention.py`` says why),
probabilities cast to the value dtype before ``P.V`` as the XLA form does.

Layout.  Operands are ``[B, S, H*D]``: positions on sublanes, a head a
static lane slice (whole 128-lane tiles at the published widths).  That
is the layout XLA gives the output of a contraction with a 2-D weight, so
``models/mla.py`` writes the projections that feed the kernel that way
and nothing is copied or transposed around the call.  (Fed from the
``[B, S, H, D]`` arrays of the XLA form, the compiler keeps those with
``S`` or ``H`` major and adds a slice and three transposing copies of
0.13 to 0.54 GB a layer.)  One grid step takes ALL heads of a ``BLOCK x
BLOCK`` tile, in a loop inside the kernel: a step costs 0.35 us whether
it runs or not, and a grid over heads too would spend more on skipped
steps than the work takes.  Keys and values arrive as one array (the
latent expansion ``[.., H, nope + v]``), sliced by lanes in the kernel,
and may be longer than the queries (the cache's buffer): only keys ``[0,
Sq)`` are ever indexed.

The packed form (:func:`mla_prefill_attention_packed`) is the same work on
a compact token set (``models/moe.RealPositions``): operands ``[C, H*D]``
hold each row's real positions one behind the other, row ``b`` from slot
``sum(lengths[:b])`` on, fillers behind the last row, and the output is
written the same way, ``[C, H*v]``.  A padding position has no slot: it is
neither read nor written, it does not exist.  Rows lie DENSE (no row
start is aligned to a block), so the grid is the token set's query blocks
by the key blocks a row of the step's width can reach back over, and small
scalar-prefetched tables (the rows' first slots, a block's first and last
row, the key block its first row starts in, the number of real slots)
tell a block's rows apart: a query's keys are the slots from its row's
start up to itself.  A query block behind the last real slot runs nothing,
fetches nothing and writes zeros to its own slots, which no live block
reads.  The capacity is the host's promise (``sum(lengths) <= C``,
``models/moe.compact_capacity``).

Who runs it is decided in ``models/mla.MLAttention``: a caller that
declares a prefill from position 0 on one device (``prefill_lengths``),
at a shape :func:`prefill_block` admits; the packed form where
``models/llama.LlamaModel`` keeps its stream compact
(``models/llama.runs_compact``).  Under a mesh the call would be
opaque to the partitioner, so meshed callers withhold the lengths and keep
the XLA form.  There is no gradient.  Pallas is imported when a call is
traced, not when this module is.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp

NEG_INF = -1e30
# Where the packed kernel's running max starts: above every masked score,
# under every real one (``_packed_kernel`` says why).
MAX_FLOOR = -1e20
# Queries and keys of one grid step.  On a v5e at 32 rows x 1,024 x 32 heads
# and the benchmark's prompt lengths (PERF.md): 128 -> 3.7 ms a call, 256 ->
# 2.7, 512 -> 2.7 (a larger block runs nearer the MXU's rate and executes
# more of the masked square); 256 queries x 512 keys 2.5.
BLOCK = 256
VMEM_LIMIT_BYTES = 64 * 1024 * 1024


def prefill_block(n_queries: int) -> int:
    """The kernel's block at this many queries; 0 = outside its regime
    (the caller keeps ``blocked_attention``).

    The one place the limit is written down: the queries are whole blocks
    and at least two of them (a single block has no key block to skip
    above the diagonal, and short prefills are the absorbed form's or too
    small to matter).
    """
    if n_queries % BLOCK or n_queries < 2 * BLOCK:
        return 0
    return BLOCK


def packed_prefill_block(max_len: int, capacity: int) -> int:
    """The packed kernel's block for rows of up to ``max_len`` queries laid
    one behind the other in ``capacity`` slots; 0 = outside its regime.
    Rows of a width :func:`prefill_block` admits, and a token set of whole
    blocks (``models/moe.compact_capacity`` gives eighths of the step)."""
    block = prefill_block(max_len)
    return block if block and capacity % block == 0 else 0


def _last_key_block(q_block, n_keys, block: int):
    """Index of the last key block a query block reads: the diagonal's, or
    the one holding the row's last real key if that comes first."""
    return jnp.minimum(q_block, jnp.maximum(n_keys - 1, 0) // block)


def _head_loop(n_heads: int, nope: int, rope: int, v_dim: int):
    """``(each, group)``: how the kernels walk the heads.  Where a head is
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


def _init_tile(acc_ref, m_ref, l_ref, floor: float):
    m_ref[...] = jnp.full_like(m_ref, floor)
    l_ref[...] = jnp.zeros_like(l_ref)
    acc_ref[...] = jnp.zeros_like(acc_ref)


def _attend_tile(valid, lead, qn_ref, qr_ref, kv_ref, kr_ref, acc_ref, m_ref,
                 l_ref, *, n_heads: int, nope: int, rope: int, v_dim: int,
                 scale: float):
    """One online-softmax update of every head's statistics by one ``block
    x block`` tile of scores, of which ``valid`` are kept.  ``lead``
    indexes the operands' leading axes (``(0,)`` for ``[1, block, W]``
    blocks, ``()`` for ``[block, W]``)."""
    block = valid.shape[0]
    each, group = _head_loop(n_heads, nope, rope, v_dim)
    rows = lead + (slice(None),)
    k_rope = kr_ref[rows]
    trans_b = (((1,), (1,)), ((), ()))
    # The running max is kept of the unscaled scores and the scale
    # rides in the exponent with log2(e): exp(scale * (s - m)) as one
    # multiply and one exp2 an element.
    to_exp2 = scale * 1.4426950408889634

    def one_head(h, q_rope):
        kv0 = h * (nope + v_dim)
        s = jax.lax.dot_general(
            qn_ref[rows + (_lane_slice(h * nope, nope),)],
            kv_ref[rows + (_lane_slice(kv0, nope),)], trans_b,
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
        p = jnp.exp2((s - _lanes(m_next, block)) * to_exp2)
        m_ref[h] = m_next
        l_ref[h] = alpha * l_prev + p.sum(axis=1, keepdims=True)
        values = kv_ref[rows + (_lane_slice(kv0 + nope, v_dim),)]
        out = _lane_slice(h * v_dim, v_dim)
        acc_ref[:, out] = (
            acc_ref[:, out] * _lanes(alpha, v_dim)
            + jax.lax.dot_general(
                p.astype(values.dtype), values, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32))

    def heads(g):
        tile = qr_ref[rows + (_lane_slice(g * group * rope, group * rope),)]
        for i in range(group):
            one_head(g * group + i, tile[:, i * rope:(i + 1) * rope])

    each(n_heads // group, heads)


def _write_tile(lead, o_ref, acc_ref, l_ref, *, n_heads: int, nope: int,
                rope: int, v_dim: int):
    """``acc / l`` of every head into the output block.  A query block
    that never ran holds the zeros of :func:`_init_tile`, and zeros are
    what it writes."""
    each, _ = _head_loop(n_heads, nope, rope, v_dim)
    rows = lead + (slice(None),)

    def one_head(h):
        out = _lane_slice(h * v_dim, v_dim)
        inv = 1.0 / jnp.maximum(l_ref[h], 1e-30)
        o_ref[rows + (out,)] = (
            acc_ref[:, out] * _lanes(inv, v_dim)).astype(o_ref.dtype)

    each(n_heads, one_head)


def _prefill_kernel(
    len_ref,   # SMEM [B] — real tokens per row (scalar prefetch)
    qn_ref,    # VMEM [1, block, H*nope]
    qr_ref,    # VMEM [1, block, H*rope]
    kv_ref,    # VMEM [1, block, H*(nope+v)] — per head [k_nope | v]
    kr_ref,    # VMEM [1, block, rope] — shared by the heads
    o_ref,     # VMEM [1, block, H*v]
    acc_ref,   # VMEM [block, H*v] float32
    m_ref,     # VMEM [H, block, 128] float32, every lane equal
    l_ref,
    *,
    scale: float,
    **dims,    # n_heads, nope, rope, v_dim
):
    from jax.experimental import pallas as pl

    block = qn_ref.shape[1]
    row, qi, ki = pl.program_id(0), pl.program_id(1), pl.program_id(2)
    n_keys = len_ref[row]

    @pl.when(ki == 0)
    def _init():
        _init_tile(acc_ref, m_ref, l_ref, NEG_INF)

    live = qi * block < n_keys
    run = live & (ki <= _last_key_block(qi, n_keys, block))

    @pl.when(run)
    def _compute():
        q_pos = qi * block + jax.lax.broadcasted_iota(
            jnp.int32, (block, block), 0)
        k_pos = ki * block + jax.lax.broadcasted_iota(
            jnp.int32, (block, block), 1)
        # Key 0 is real and under the diagonal for every query of a live
        # block, so after the first key block every running max is finite
        # and a wholly masked row of a later block contributes exp(-1e30
        # - m) = 0 without a guard.
        valid = (k_pos <= q_pos) & (k_pos < n_keys)
        _attend_tile(valid, (0,), qn_ref, qr_ref, kv_ref, kr_ref, acc_ref,
                     m_ref, l_ref, scale=scale, **dims)

    @pl.when(ki == pl.num_programs(2) - 1)
    def _finalize():
        _write_tile((0,), o_ref, acc_ref, l_ref, **dims)


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
    acc_ref, m_ref, l_ref,      # as _prefill_kernel's
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
        _init_tile(acc_ref, m_ref, l_ref, MAX_FLOOR)

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
        _attend_tile(valid, (), qn_ref, qr_ref, kv_ref, kr_ref, acc_ref,
                     m_ref, l_ref, scale=scale, **dims)

    @pl.when(step == pl.num_programs(1) - 1)
    def _finalize():
        _write_tile((), o_ref, acc_ref, l_ref, **dims)


def _lane_slice(start, width: int):
    """``width`` lanes from ``start``: a Python int (a static slice) or a
    traced multiple of 128 (a head of the loop over heads)."""
    from jax.experimental import pallas as pl

    if not isinstance(start, int):
        start = pl.multiple_of(start, 128)
    return pl.ds(start, width)


def _lanes(stat, width: int):
    """A ``[rows, 128]`` statistic (every lane equal) at ``width`` lanes."""
    from jax.experimental.pallas import tpu as pltpu

    if width % 128:
        return stat[:, :1]      # under a lane tile: ``kanana-tiny``
    return stat if width == 128 else pltpu.repeat(stat, width // 128, axis=1)


@functools.partial(
    jax.jit,
    static_argnames=("n_heads", "scale", "block", "interpret"))
def _prefill_call(q_nope, q_rope, kv, k_rope, lengths, n_heads: int,
                  scale: float, block: int, interpret: bool):
    """The ``pallas_call`` under one inner ``jit``: every layer of a forward
    that calls it at one shape shares one trace and one Mosaic lowering."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    batch, n_q, _ = q_nope.shape
    nope = q_nope.shape[-1] // n_heads
    rope = k_rope.shape[-1]
    v_dim = kv.shape[-1] // n_heads - nope

    def q_map(b, qi, ki, lens):
        # a query block past the row's length is not fetched: the index
        # stays on the last block that holds a real token
        return (b, jnp.minimum(qi, jnp.maximum(lens[b] - 1, 0) // block), 0)

    def k_map(b, qi, ki, lens):
        # nor is a key block the step will not use: the index stays where
        # the last used one left it
        return (b, jnp.minimum(ki, _last_key_block(qi, lens[b], block)), 0)

    def spec(width, index_map):
        return pl.BlockSpec((1, block, width), index_map,
                            memory_space=pltpu.VMEM)

    return pl.pallas_call(
        functools.partial(
            _prefill_kernel, n_heads=n_heads, nope=nope, rope=rope,
            v_dim=v_dim, scale=scale,
        ),
        out_shape=jax.ShapeDtypeStruct((batch, n_q, n_heads * v_dim),
                                       q_nope.dtype),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(batch, n_q // block, n_q // block),
            in_specs=[
                spec(n_heads * nope, q_map),
                spec(n_heads * rope, q_map),
                spec(n_heads * (nope + v_dim), k_map),
                spec(rope, k_map),
            ],
            out_specs=spec(n_heads * v_dim,
                           lambda b, qi, ki, lens: (b, qi, 0)),
            scratch_shapes=[
                pltpu.VMEM((block, n_heads * v_dim), jnp.float32),
                pltpu.VMEM((n_heads, block, 128), jnp.float32),
                pltpu.VMEM((n_heads, block, 128), jnp.float32),
            ],
        ),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
            vmem_limit_bytes=VMEM_LIMIT_BYTES,
        ),
        interpret=interpret,
    )(lengths, q_nope, q_rope, kv, k_rope)


def mla_prefill_attention(
    q_nope: jax.Array,
    q_rope: jax.Array,
    kv: jax.Array,
    k_rope: jax.Array,
    lengths: jax.Array,
    n_heads: int,
    scale: float,
    interpret: Optional[bool] = None,
) -> jax.Array:
    """Causal self-attention of a prefill with keys ``>= lengths[b]``
    masked: what ``models/mla.blocked_attention`` gives under the causal
    and key-padding mask on every real position; finite values on padding
    positions, zeros where a whole query block is padding.

    Heads lie side by side on the last axis: ``q_nope [B,Sq,H*Dn]``,
    ``q_rope [B,Sq,H*Dr]``, ``kv [B,Sk,H*(Dn+Dv)]`` (per head ``[k_nope |
    v]``), ``k_rope [B,Sk,Dr]``, ``lengths [B]``; ``Sk >= Sq`` and query
    ``i`` is key ``i`` (a prefill from position 0: keys past ``Sq`` are
    never read).  Returns ``[B,Sq,H*Dv]``.

    Only for shapes :func:`prefill_block` admits (it raises otherwise: the
    caller chooses, this function does not fall back).
    """
    from music_analyst_tpu.ops.flash_attention import interpret_default

    n_q = q_nope.shape[1]
    block = prefill_block(n_q)
    if not block:
        raise ValueError(
            f"{n_q} queries are outside the kernel's regime "
            "(prefill_block); use models/mla.blocked_attention")
    if kv.shape[1] < n_q or k_rope.shape[1] != kv.shape[1]:
        raise ValueError(
            f"keys {kv.shape} / {k_rope.shape} do not cover {n_q} queries")
    if interpret is None:
        interpret = interpret_default()
    return _prefill_call(
        q_nope, q_rope, kv, k_rope, lengths.astype(jnp.int32),
        n_heads=n_heads, scale=float(scale), block=block,
        interpret=interpret,
    )


@functools.partial(
    jax.jit,
    static_argnames=("n_heads", "scale", "block", "max_len", "interpret"))
def _packed_prefill_call(q_nope, q_rope, kv, k_rope, lengths, n_heads: int,
                         scale: float, block: int, max_len: int,
                         interpret: bool):
    """:func:`_prefill_call` for rows laid one behind the other: the grid is
    the token set's query blocks by the most key blocks a row of
    ``max_len`` reaches back over, and five small tables say where each
    block's rows lie."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    capacity = q_nope.shape[0]
    nope = q_nope.shape[-1] // n_heads
    rope = k_rope.shape[-1]
    v_dim = kv.shape[-1] // n_heads - nope
    n_blocks = capacity // block

    ends = jnp.cumsum(lengths)
    starts = ends - lengths
    total = ends[-1]

    def last_live_block(total):
        return jnp.maximum(total - 1, 0) // block

    first_slot = jnp.minimum(jnp.arange(n_blocks),
                             last_live_block(total)) * block
    last_slot = jnp.minimum(first_slot + block, jnp.maximum(total, 1)) - 1

    def row_of(slot):
        # the rows that end at or before a slot lie before it (an empty
        # row holds none)
        row = jnp.searchsorted(ends, slot, side="right",
                               method="compare_all").astype(jnp.int32)
        return jnp.minimum(row, lengths.shape[0] - 1)

    first_row = row_of(first_slot)
    # a block past the last real slot holds on to the last live block's
    # operands (no DMA) and runs nothing
    first_key = starts[first_row] // block

    def q_map(qi, step, starts, first_row, last_row, first_key, total):
        return (jnp.minimum(qi, last_live_block(total[0])), 0)

    def k_map(qi, step, starts, first_row, last_row, first_key, total):
        # nor is a key block past the diagonal fetched: the index stays
        # where the last used one left it
        return (jnp.minimum(
            first_key[qi] + step,
            jnp.minimum(qi, last_live_block(total[0]))), 0)

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
    )(starts, first_row, row_of(last_slot), first_key, total[None],
      q_nope, q_rope, kv, k_rope)


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
    """:func:`mla_prefill_attention` on a compact token set
    (``models/moe.RealPositions``): row ``b``'s ``lengths[b] <= max_len``
    positions are the slots from ``sum(lengths[:b])`` on, the
    ``capacity - sum(lengths)`` slots behind the last row are fillers.
    ``q_nope [C,H*Dn]``, ``q_rope [C,H*Dr]``, ``kv [C,H*(Dn+Dv)]``,
    ``k_rope [C,Dr]``; returns ``[C,H*Dv]`` with every real slot's
    attention over its own row's slots up to itself, finite values on
    fillers that share a block with a real slot, zeros on the blocks
    behind.  The caller promises ``sum(lengths) <= C`` and finite
    operands on every slot, fillers too (a masked key's value is
    multiplied by a probability of zero, not skipped).

    Only for shapes :func:`packed_prefill_block` admits (it raises
    otherwise)."""
    from music_analyst_tpu.ops.flash_attention import interpret_default

    capacity = q_nope.shape[0]
    block = packed_prefill_block(max_len, capacity)
    if not block:
        raise ValueError(
            f"rows of {max_len} in {capacity} slots are outside the "
            "kernel's regime (packed_prefill_block)")
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
