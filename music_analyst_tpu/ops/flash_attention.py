"""Blocked online-softmax attention as a Pallas TPU kernel.

The dense formulation (``models/layers.py:dot_product_attention``)
materializes the full ``[B, H, S, KV]`` logit tensor in HBM — fine at the
classifier's seq 128, quadratic-memory at long context.  This kernel never
materializes logits: one query block is staged in VMEM, key/value blocks
stream past it, and the softmax runs online (running max ``m``, running
denominator ``l``, rescaled accumulator) so HBM traffic is O(S·D) instead
of O(S²).

Replaces nothing in the reference (its longest "sequence" concern is
truncating lyrics to 4,000 chars, ``scripts/sentiment_classifier.py:90``);
this is the long-context path SURVEY.md §5 calls out as the TPU-era
requirement, and composes with the ring schedule in
``ops/ring_attention.py`` (each ring hop's local attention is exactly one
of these kernels).

Grid ``(B, H, q_blocks, kv_blocks)``; the kv dimension is innermost and
sequential ("arbitrary"), with the running state in VMEM scratch that
persists across kv steps.  GQA maps query head ``h`` to kv head
``h // group`` in the BlockSpec index map — no ``jnp.repeat`` of K/V.
Masking vocabulary: ``causal`` (with block skipping), a sliding ``window``
behind the diagonal (with block skipping on that side too), per-row
``lengths`` (key padding), ``block_causal`` (a block-diffusion prefill's
rule) and per-token ``segment_ids`` (block-diagonal, for packed batches) —
all composable in one pass.  :func:`tile_runs` is the one rule for which
tiles of the grid compute: the kernel reads it, and so does whoever counts
what the kernel computed (:func:`visited_pairs`).

The packed form (:func:`flash_attention_packed`) is the causal prefill of
rows laid one behind the other on a compact token set
(``models/compact_stream.RealPositions``), the latent prefill's packed
kernel (``ops/mla_prefill_attention.py``) for grouped-query heads: ``q [C,
H*D]``, ``k``/``v [C, Hkv*D]`` read from the stream and ``[C, H*D]``
written back to it, the query heads of whole key/value groups in one grid step (a loop
inside the kernel; each key/value head read in place by its group;
:func:`packed_kv_heads`), bfloat16 MXU operands with float32 statistics,
and tiles above the diagonal, before the first row's start or wholly
behind the window neither computed nor fetched.  :func:`packed_step_runs`
is its one rule for which tiles compute, read by the kernel and by
:func:`packed_visited_pairs`.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from music_analyst_tpu.ops.packed_stream import (
    MAX_FLOOR,
    lanes,
    last_live_block,
    packed_tables,
)

NEG_INF = -1e30
# What one grid step of the packed kernel may hold in VMEM
# (:func:`packed_kv_heads`): under the compiler's default scope of 16 MiB,
# so the kernel asks for no scoped VMEM of its own.
PACKED_VMEM_BYTES = 12 * 1024 * 1024


def interpret_default() -> bool:
    """Whether this package's Pallas kernels run under the interpreter.

    The one place that decides: on a TPU backend every kernel is compiled
    by Mosaic; anywhere else (the CPU test mesh) it is interpreted.  An
    explicit ``interpret=`` argument is a test facility, not a fallback —
    a kernel Mosaic refuses fails the run.
    """
    return jax.default_backend() != "tpu"


def tile_runs(qi, ki, block_q: int, block_kv: int, kv_len, causal: bool,
              block_causal: int = 0, window: int = 0, q_off=0, kv_off=0):
    """Whether tile ``(qi, ki)`` of the grid (queries ``q_off + qi *
    block_q ..``, keys ``kv_off + ki * block_kv ..``, global positions)
    computes, or is skipped because no pair in it can be inside the mask.
    Arithmetic and comparisons alone: the kernel calls it on its program
    ids, a host count on arrays of tile indices."""
    if causal:
        # Skip kv blocks whose every (offset-adjusted) position is above
        # the diagonal: they can't contribute to the online softmax.  (A
        # ``block_causal`` length divides both tile sizes, so the last
        # query of a tile also ends its block and the rule is the same.)
        run = kv_off + ki * block_kv <= q_off + qi * block_q + block_q - 1
    else:
        run = ki >= 0
    if window:
        # ... and those wholly behind the window of the tile's FIRST
        # query (the furthest back any query of the tile sees): a key at
        # ``k`` is seen by the query at ``q`` iff ``k > q - window``.
        run = run & (kv_off + ki * block_kv + block_kv - 1
                     > q_off + qi * block_q - window)
    if block_causal:
        # A block-causal prefill is self-attention of rows ``kv_len``
        # long: key tiles at or past a row's length add nothing, and
        # query tiles there are read by nobody (written as zeros).
        run = run & (kv_off + ki * block_kv < kv_len) & (
            q_off + qi * block_q < kv_len)
    return run


def visited_pairs(lengths, width: int, block_q: int, block_kv: int,
                  window: int = 0, block_causal: int = 1) -> int:
    """(query, key) pairs in the tiles a causal self-attention call of
    ``width`` positions a row computes for rows ``lengths`` long (host
    integers): the grid's tiles :func:`tile_runs` lets run, whole, for one
    query head.  Against the real pairs inside the mask it says what the
    kernel computes outside it."""
    lengths = np.asarray(lengths, np.int64).reshape(-1, 1, 1)
    qi = np.arange(width // block_q, dtype=np.int64)[None, :, None]
    ki = np.arange(width // block_kv, dtype=np.int64)[None, None, :]
    runs = tile_runs(qi, ki, block_q, block_kv, lengths, True, block_causal,
                     window)
    return int(np.sum(runs)) * block_q * block_kv


def _flash_kernel(
    len_ref,  # SMEM [B] — kv valid length per batch row
    off_ref,  # SMEM [2] — (q_offset, kv_offset) global position offsets
    *refs,    # [qseg, kvseg,] q, k, v, o [, m_out, l_out], scratch...
    causal: bool,
    block_q: int,
    block_kv: int,
    kv_blocks: int,
    scale: float,
    residuals: bool,
    segmented: bool,
    block_causal: int = 0,
    window: int = 0,
):
    if segmented:
        # VMEM [1, bq, 1] / [1, 1, bkv] — per-token segment ids (block-
        # diagonal attention for packed batches, models/distilbert.py).
        # Query ids ride the sublane axis and key ids the lane axis, so
        # the [bq, bkv] comparison is a plain broadcast: Mosaic accepts
        # neither a (1, bq) block of a [B, S] array with B > 1 nor the
        # lane→sublane transpose a 1-D id vector would need.
        qseg_ref, kvseg_ref, *refs = refs
    q_ref, k_ref, v_ref, o_ref, *rest = refs
    if residuals:
        m_out_ref, l_out_ref, acc_ref, m_ref, l_ref = rest
    else:
        acc_ref, m_ref, l_ref = rest
    bi = pl.program_id(0)
    qi = pl.program_id(2)
    ki = pl.program_id(3)
    kv_len = len_ref[bi]
    q_off = off_ref[0]
    kv_off = off_ref[1]

    @pl.when(ki == 0)
    def _init():
        m_ref[:] = jnp.full_like(m_ref, NEG_INF)
        l_ref[:] = jnp.zeros_like(l_ref)
        acc_ref[:] = jnp.zeros_like(acc_ref)

    run = tile_runs(qi, ki, block_q, block_kv, kv_len, causal, block_causal,
                    window, q_off, kv_off)

    @pl.when(run)
    def _compute():
        q = q_ref[0, 0].astype(jnp.float32)
        k = k_ref[0, 0].astype(jnp.float32)
        v = v_ref[0, 0].astype(jnp.float32)
        # Scale the scores, not the queries: the MXU takes its operands
        # in bf16 passes, so bf16 inputs contract exactly — a pre-scaled
        # q no longer fits bf16 and costs ~0.4% of every logit (measured
        # on a v5e against the f32 reference, chip_smoke.py; the CPU
        # interpreter's exact f32 matmul cannot show it).
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        ) * scale  # [bq, bkv]

        kv_pos = kv_off + ki * block_kv + jax.lax.broadcasted_iota(
            jnp.int32, s.shape, dimension=1
        )
        valid = kv_pos < kv_len
        if causal:
            q_pos = q_off + qi * block_q + jax.lax.broadcasted_iota(
                jnp.int32, s.shape, dimension=0
            )
            if block_causal:
                # a query sees every key up to the end of its own block
                q_pos = q_pos // block_causal * block_causal + (
                    block_causal - 1)
            valid = valid & (kv_pos <= q_pos)
        if window:
            q_at = q_off + qi * block_q + jax.lax.broadcasted_iota(
                jnp.int32, s.shape, dimension=0
            )
            valid = valid & (kv_pos > q_at - window)
        if segmented:
            valid = valid & (qseg_ref[0] == kvseg_ref[0])  # [bq,1]==[1,bkv]
        s = jnp.where(valid, s, NEG_INF)

        m_prev = m_ref[:, :1]                                  # [bq, 1]
        l_prev = l_ref[:, :1]
        m_cur = jnp.maximum(m_prev, s.max(axis=1, keepdims=True))
        # Guard fully-masked blocks: exp(NEG_INF - NEG_INF) would be 1.
        p = jnp.where(s > NEG_INF / 2, jnp.exp(s - m_cur), 0.0)
        alpha = jnp.exp(jnp.minimum(m_prev - m_cur, 0.0))
        l_cur = alpha * l_prev + p.sum(axis=1, keepdims=True)
        acc_ref[:] = acc_ref[:] * alpha + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32
        )
        m_ref[:] = jnp.broadcast_to(m_cur, m_ref.shape)
        l_ref[:] = jnp.broadcast_to(l_cur, l_ref.shape)

    @pl.when(ki == kv_blocks - 1)
    def _finalize():
        if residuals:
            # Unnormalized accumulator + running stats: hop-combinable
            # (ring attention merges partials across devices).
            o_ref[0, 0] = acc_ref[:].astype(o_ref.dtype)
            m_out_ref[0, 0] = m_ref[:]
            l_out_ref[0, 0] = l_ref[:]
        else:
            denom = jnp.maximum(l_ref[:, :1], 1e-30)
            o_ref[0, 0] = (acc_ref[:] / denom).astype(o_ref.dtype)


@functools.partial(
    jax.jit,
    static_argnames=("causal", "block_q", "block_kv", "interpret",
                     "residuals", "block_causal", "scale", "window"),
)
def _flash_call(
    q: jax.Array,       # [B, S, H, D]
    k: jax.Array,       # [B, KV, Hkv, D]
    v: jax.Array,
    lengths: jax.Array,  # [B] int32 — valid kv length per row
    offsets: jax.Array,  # [2] int32 — (q_offset, kv_offset)
    causal: bool,
    block_q: int,
    block_kv: int,
    interpret: bool,
    residuals: bool,
    q_seg: jax.Array | None = None,   # [B, S] int32 segment ids
    kv_seg: jax.Array | None = None,  # [B, KV]
    block_causal: int = 0,
    scale: float | None = None,
    window: int = 0,
):
    B, S, H, D = q.shape
    KV = k.shape[1]
    Hkv = k.shape[2]
    # Head-major layout so every VMEM block is (1, 1, seq_block, D): the
    # sublane/lane dims are then (seq_block, D), which tile cleanly.
    q = q.transpose(0, 2, 1, 3)  # [B, H, S, D]
    k = k.transpose(0, 2, 1, 3)
    v = v.transpose(0, 2, 1, 3)
    group = H // Hkv
    q_blocks = S // block_q
    kv_blocks = KV // block_kv
    if scale is None:
        scale = D ** -0.5

    segmented = q_seg is not None
    kernel = functools.partial(
        _flash_kernel,
        causal=causal,
        block_q=block_q,
        block_kv=block_kv,
        kv_blocks=kv_blocks,
        scale=scale,
        residuals=residuals,
        segmented=segmented,
        block_causal=block_causal,
        window=window,
    )
    qblock_spec = pl.BlockSpec(
        (1, 1, block_q, D),
        lambda b, h, qi, ki: (b, h, qi, 0),
        memory_space=pltpu.VMEM,
    )
    kvblock_spec = pl.BlockSpec(
        (1, 1, block_kv, D),
        lambda b, h, qi, ki: (b, h // group, ki, 0),
        memory_space=pltpu.VMEM,
    )
    stat_spec = pl.BlockSpec(
        (1, 1, block_q, 128),
        lambda b, h, qi, ki: (b, h, qi, 0),
        memory_space=pltpu.VMEM,
    )
    if residuals:
        out_shape = (
            jax.ShapeDtypeStruct((B, H, S, D), jnp.float32),
            jax.ShapeDtypeStruct((B, H, S, 128), jnp.float32),
            jax.ShapeDtypeStruct((B, H, S, 128), jnp.float32),
        )
        out_specs = (qblock_spec, stat_spec, stat_spec)
    else:
        out_shape = jax.ShapeDtypeStruct((B, H, S, D), q.dtype)
        out_specs = qblock_spec
    in_specs = [
        pl.BlockSpec(memory_space=pltpu.SMEM),  # lengths, whole [B]
        pl.BlockSpec(memory_space=pltpu.SMEM),  # offsets [2]
    ]
    inputs = [lengths, offsets]
    if segmented:
        in_specs.append(pl.BlockSpec(
            (1, block_q, 1), lambda b, h, qi, ki: (b, qi, 0),
            memory_space=pltpu.VMEM,
        ))
        in_specs.append(pl.BlockSpec(
            (1, 1, block_kv), lambda b, h, qi, ki: (b, 0, ki),
            memory_space=pltpu.VMEM,
        ))
        inputs += [q_seg[:, :, None], kv_seg[:, None, :]]
    in_specs += [qblock_spec, kvblock_spec, kvblock_spec]
    inputs += [q, k, v]
    out = pl.pallas_call(
        kernel,
        out_shape=out_shape,
        grid=(B, H, q_blocks, kv_blocks),
        in_specs=in_specs,
        out_specs=out_specs,
        scratch_shapes=[
            pltpu.VMEM((block_q, D), jnp.float32),
            pltpu.VMEM((block_q, 128), jnp.float32),
            pltpu.VMEM((block_q, 128), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel",
                                 "arbitrary"),
        ),
        interpret=interpret,
    )(*inputs)
    if residuals:
        o, m, l = out
        # o unnormalized [B,H,S,D] f32; stats collapse their broadcast lane.
        return o.transpose(0, 2, 1, 3), m[..., 0], l[..., 0]
    return out.transpose(0, 2, 1, 3)  # back to [B, S, H, D]


def _fit_block(requested: int, seq: int) -> int:
    """Largest tile-aligned divisor of ``seq`` that is ≤ ``requested``.

    Divisibility is required by the kernel's grid, but an over-large
    request (e.g. the default 512 against S=768, or a ring shard that is
    not a power of two) should degrade to a legal smaller block rather
    than raise.  Only multiples of the 8-row TPU sublane tile qualify —
    an unaligned block may not lower on real hardware and a tiny one is a
    silent perf cliff — so genuinely awkward lengths still raise with the
    remedy (sequences ≤ 8 pass through whole; they already fit one tile).
    """
    if seq <= 8:
        return min(requested, seq)
    for cand in range(min(requested, seq) // 8 * 8, 0, -8):
        if seq % cand == 0:
            return cand
    raise ValueError(
        f"no tile-aligned block ≤ {requested} divides sequence length "
        f"{seq}; pad the sequence to a multiple of 8"
    )


def flash_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    lengths: jax.Array | None = None,
    causal: bool = False,
    block_q: int = 512,
    block_kv: int = 1024,
    interpret: bool | None = None,
    q_offset: jax.Array | int = 0,
    kv_offset: jax.Array | int = 0,
    return_residuals: bool = False,
    q_segment_ids: jax.Array | None = None,
    kv_segment_ids: jax.Array | None = None,
    block_causal: int = 0,
    scale: float | None = None,
    window: int = 0,
):
    """Attention over ``[B, S, H, D]`` without materializing logits.

    ``window`` = ``w`` > 0 adds a sliding window: the query at (global)
    position ``i`` sees key ``j`` only where ``j > i - w`` (``w`` keys with
    its own under ``causal``).  Key tiles wholly behind the window of a
    query tile's first query are skipped as tiles above the diagonal are
    (the compute; the index map does not see the runtime offsets, so the
    fetch is not).  Under ``block_causal`` a query's window still counts
    from its own position.

    ``scale`` multiplies the scores before the softmax: ``D ** -0.5``
    unless a configuration publishes its own (``None`` = that default, and
    the program every caller without one had).

    ``lengths`` masks keys/values past each row's valid length (encoder
    padding); ``causal`` adds the autoregressive mask.  GQA is supported
    when ``k``/``v`` carry fewer heads.  ``block_q``/``block_kv`` are upper
    bounds: each is lowered to the largest divisor of its sequence length
    (tile-aligned when possible), so non-power-of-two shards (e.g. ring
    attention's per-device slices) pick a legal block instead of raising.
    Off-TPU the kernel runs in interpreter mode
    so CPU test meshes exercise the same code path
    (:func:`interpret_default`).

    ``q_segment_ids`` ``[B, S]`` / ``kv_segment_ids`` ``[B, KV]`` add
    block-diagonal masking: a query attends only to keys with the SAME
    segment id (packed batches, ``models/distilbert.py:pack_segments``).
    ``kv_segment_ids`` defaults to ``q_segment_ids`` for self-attention.
    Composes with ``lengths``/``causal``; a query whose segment has no
    valid key outputs zeros (guarded denominator), matching the dense
    formulation's uniform-over-masked behavior in effect (neither is ever
    gathered).

    ``block_causal`` = ``n`` > 0 (with ``causal``) is the block-diffusion
    prefill's rule: a query at position ``i`` sees key ``j`` iff ``j <
    lengths[row]`` and ``j // n <= i // n`` (bidirectional inside a block
    of ``n`` positions, causal across blocks).  ``n`` must divide both
    tiles, so tile skipping stays the causal rule; it declares
    self-attention of rows ``lengths`` long, so tiles of keys AND of
    queries at or past a row's length are skipped and such queries come
    back as zeros.

    ``q_offset``/``kv_offset`` shift the global positions used by the
    causal/length masks — the hook that lets a sequence-parallel caller
    (ring attention) run this kernel on one K/V shard at a time.  With
    ``return_residuals=True`` the call returns ``(o_unnormalized, m, l)``
    (``[B,S,H,D]`` f32, ``[B,H,S]``, ``[B,H,S]``) for cross-shard online
    combination instead of the normalized output.
    """
    B, S, H, D = q.shape
    KV = k.shape[1]
    block_q = _fit_block(block_q, S)
    block_kv = _fit_block(block_kv, KV)
    if H % k.shape[2]:
        raise ValueError(f"q heads {H} not a multiple of kv heads {k.shape[2]}")
    if block_causal and (not causal or block_q % block_causal
                         or block_kv % block_causal):
        raise ValueError(
            f"block_causal={block_causal} needs causal=True and a length "
            f"that divides the tiles ({block_q} x {block_kv})")
    if window < 0:
        raise ValueError(f"window={window} is not a number of keys")
    if lengths is None:
        # Lengths are *global* positions: with a kv_offset the local shard
        # covers [kv_offset, kv_offset + KV).
        lengths = jnp.full((B,), KV, jnp.int32) + jnp.asarray(
            kv_offset, jnp.int32
        )
    if interpret is None:
        interpret = interpret_default()
    offsets = jnp.stack(
        [jnp.asarray(q_offset, jnp.int32), jnp.asarray(kv_offset, jnp.int32)]
    )
    q_seg = kv_seg = None
    if q_segment_ids is not None:
        if kv_segment_ids is None:
            if KV != S:
                raise ValueError(
                    "kv_segment_ids is required when KV length differs "
                    "from the query length"
                )
            kv_segment_ids = q_segment_ids
        if q_segment_ids.shape != (B, S):
            raise ValueError(
                f"q_segment_ids must be [B, S]={B, S}, "
                f"got {q_segment_ids.shape}"
            )
        if kv_segment_ids.shape != (B, KV):
            raise ValueError(
                f"kv_segment_ids must be [B, KV]={B, KV}, "
                f"got {kv_segment_ids.shape}"
            )
        q_seg = q_segment_ids.astype(jnp.int32)
        kv_seg = kv_segment_ids.astype(jnp.int32)
    elif kv_segment_ids is not None:
        raise ValueError("kv_segment_ids given without q_segment_ids")
    return _flash_call(
        q, k, v, lengths.astype(jnp.int32), offsets, causal, block_q,
        block_kv, interpret, return_residuals, q_seg=q_seg, kv_seg=kv_seg,
        block_causal=block_causal,
        scale=None if scale is None else float(scale),
        window=int(window),
    )


# ---------------------------------------------------------------------------
# The packed form: rows one behind the other on a compact token set.

def packed_steps(max_len: int, block: int, window: int = 0) -> int:
    """The packed grid's key steps a query block takes: the most key blocks
    a row of ``max_len`` slots that starts inside a block reaches back
    over, plus one, and no more than a window of ``window`` keys spans."""
    steps = max_len // block + 1
    if window:
        steps = min(steps, -(-(window - 1) // block) + 1)
    return steps


def packed_step_runs(qi, step, first_key, total, block: int):
    """Whether step ``step`` of query block ``qi`` computes (key block
    ``first_key + step``): the block holds a real slot and the key block
    is not above the diagonal.  Key blocks before ``first_key`` are never
    stepped on.  The kernel calls it on its program ids, a host count on
    arrays (:func:`packed_visited_pairs`)."""
    return (qi * block < total) & (first_key + step <= qi)


def packed_visited_pairs(lengths, capacity: int, max_len: int, block: int,
                         window: int = 0) -> int:
    """(query, key) pairs in the tiles the packed kernel computes for one
    query head, rows ``lengths`` long (host integers) in ``capacity``
    slots: the grid's tiles :func:`packed_step_runs` lets run, whole."""
    lengths = np.asarray(lengths, np.int64)
    n_blocks = capacity // block
    _, _, _, first_key, total = packed_tables(lengths, n_blocks, block,
                                              window, np)
    qi = np.arange(n_blocks)[:, None]
    step = np.arange(packed_steps(max_len, block, window))[None, :]
    runs = packed_step_runs(qi, step, first_key[:, None], total[0], block)
    return int(np.sum(runs)) * block * block


def _packed_flash_kernel(
    start_ref,      # SMEM [B] — a row's first slot (prefetched, all five)
    first_row_ref,  # SMEM [blocks] — the row of a query block's first slot
    last_row_ref,   # SMEM [blocks] — the row of its last real slot
    first_key_ref,  # SMEM [blocks] — the first key block it reads
    total_ref,      # SMEM [1] — real slots: the rows' lengths summed
    q_ref,          # VMEM [h*D, block] — features on sublanes, slots on lanes
    k_ref,          # VMEM [hkv*D, block]
    v_ref,          # VMEM [hkv*D, block]
    o_ref,          # VMEM [block, h*D] — slots on sublanes
    q_rows_ref,     # VMEM [block, h*D] — the query block turned, once
    acc_ref,        # VMEM [block, h*D] float32
    m_ref,          # VMEM [h, block, 128] float32, every lane equal
    l_ref,
    *,
    n_heads: int,   # h: the query heads of one grid step
    n_kv: int,      # hkv: the key/value heads they read
    scale: float,
    block_causal: int,
    window: int,
):
    block = q_ref.shape[1]
    dim = q_ref.shape[0] // n_heads
    group = n_heads // n_kv
    qi, step = pl.program_id(0), pl.program_id(2)
    first_key = first_key_ref[qi]
    ki = first_key + step
    # Heads of whole tiles are loops (one trace of the body; the latent
    # kernel's 32 unrolled heads cost 4.7 s of tracing a process);
    # narrower ones (the ``*-tiny`` presets) unrolled with static slices.
    looped = dim % 128 == 0

    def each(count, body):
        if looped:
            jax.lax.fori_loop(0, count, lambda i, carry: body(i) or carry, 0)
        else:
            for i in range(count):
                body(i)

    def head(h):
        return pl.ds(pl.multiple_of(h * dim, 128) if looped else h * dim,
                     dim)

    @pl.when(step == 0)
    def _init():
        # A query whose row starts inside this block meets key blocks in
        # which it has no key: its running max stays at the floor, ABOVE
        # the masked scores, which then weigh exp(-1e30 + 1e20) = 0.
        m_ref[...] = jnp.full_like(m_ref, MAX_FLOOR)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

        def turn(h):
            q_rows_ref[:, head(h)] = q_ref[head(h), :].T

        each(n_heads, turn)

    @pl.when(packed_step_runs(qi, step, first_key, total_ref[0], block))
    def _compute():
        q_pos = qi * block + jax.lax.broadcasted_iota(
            jnp.int32, (block, block), 0)
        k_pos = ki * block + jax.lax.broadcasted_iota(
            jnp.int32, (block, block), 1)
        # A query's keys are its row's slots from the row's start: rows lie
        # one behind the other, so ``row start <= key`` is the row's
        # boundary.  The row start of every query is the largest start at
        # or before it among the rows this block holds (fillers behind the
        # last row attend like its tail).
        first = first_row_ref[qi]
        q_start = jax.lax.fori_loop(
            first + 1, last_row_ref[qi] + 1,
            lambda r, held: jnp.where(q_pos >= start_ref[r], start_ref[r],
                                      held),
            jnp.full((block, block), start_ref[first], jnp.int32))
        # Under the block-causal rule a query sees its own block of
        # ``block_causal`` positions whole: rows are whole blocks, so a
        # row's start is a multiple and the rule reads the slots alike.
        last = (q_pos if block_causal == 1
                else q_pos // block_causal * block_causal + block_causal - 1)
        valid = (k_pos <= last) & (k_pos >= q_start)
        if window:
            valid = valid & (k_pos > q_pos - window)
        # the running max is of the unscaled scores; the scale rides in
        # the exponent with log2(e) (scaled after the matmul:
        # ``_flash_kernel`` says why)
        to_exp2 = scale * 1.4426950408889634

        def kv_head(g):
            # a key/value head is read in place by its group of query
            # heads: keys as the scores' right operand, values as P.V's
            # (transposed) one
            keys, values = k_ref[head(g), :], v_ref[head(g), :]  # [D, block]

            def one_head(i):
                h = g * group + i
                s = jax.lax.dot_general(
                    q_rows_ref[:, head(h)], keys, (((1,), (0,)), ((), ())),
                    preferred_element_type=jnp.float32)     # [q, keys]
                s = jnp.where(valid, s, NEG_INF)
                # statistics stay [block, 128] with every lane equal, as
                # they are stored (``ops/mla_prefill_attention.py``)
                m_prev, l_prev = m_ref[h], l_ref[h]
                m_next = jnp.maximum(m_prev, s.max(axis=1, keepdims=True))
                alpha = jnp.exp2((m_prev - m_next) * to_exp2)
                p = jnp.exp2((s - lanes(m_next, block)) * to_exp2)
                m_ref[h] = m_next
                l_ref[h] = alpha * l_prev + p.sum(axis=1, keepdims=True)
                acc_ref[:, head(h)] = (
                    acc_ref[:, head(h)] * lanes(alpha, dim)
                    + jax.lax.dot_general(
                        p.astype(values.dtype), values,
                        (((1,), (1,)), ((), ())),
                        preferred_element_type=jnp.float32))  # [q, D]

            each(group, one_head)

        each(n_kv, kv_head)

    @pl.when(step == pl.num_programs(2) - 1)
    def _finalize():
        # a query block that never ran holds the zeros of ``_init``
        def one_head(h):
            inv = 1.0 / jnp.maximum(l_ref[h], 1e-30)
            o_ref[:, head(h)] = (
                acc_ref[:, head(h)] * lanes(inv, dim)).astype(o_ref.dtype)

        each(n_heads, one_head)


def packed_kv_heads(n_heads: int, n_kv: int, dim: int, block: int) -> int:
    """Key/value heads (with their groups of query heads) one grid step of
    the packed kernel takes: the most that divide ``n_kv``, fill whole
    128-lane tiles of the result (or are all of them) and keep the step's
    VMEM within :data:`PACKED_VMEM_BYTES`.
    Scoped VMEM a kernel asks for beyond the compiler's default is held
    back from the WHOLE program, out of what XLA's memory-space assignment
    places there: a step of all 72 heads of 128 at 256 slots asked for 64
    MiB, and the window cell's feed-forward then lost the VMEM its 72 MiB
    normed input and gathered rows had held (PERF.md section 5, PR 41)."""
    group = n_heads // n_kv
    # per key/value head: its queries (in, double-buffered; turned), the
    # float32 accumulator, two [block, 128] statistics a query head and the
    # result (out, double-buffered), bfloat16; its keys and values
    per_kv = group * block * (dim * (4 + 2 + 4 + 4) + 2 * 128 * 4) + (
        2 * 2 * 2 * dim * block)
    fits = [n for n in range(1, n_kv + 1)
            if n_kv % n == 0 and (n == n_kv or n * group * dim % 128 == 0)
            and n * per_kv <= PACKED_VMEM_BYTES]
    return max(fits, default=1)


@functools.partial(
    jax.jit,
    static_argnames=("n_heads", "max_len", "block", "block_causal", "scale",
                     "window", "interpret"))
def _packed_flash_call(q, k, v, lengths, n_heads: int, max_len: int,
                       block: int, block_causal: int, scale: float,
                       window: int, interpret: bool):
    """The ``pallas_call`` under one inner ``jit``: every layer of a forward
    that calls it at one shape shares one trace and one Mosaic lowering.
    Grid: the token set's query blocks by the groups of heads
    (:func:`packed_kv_heads`) by :func:`packed_steps` key blocks, five
    scalar-prefetched tables (:func:`packed_tables`).

    Layout.  On the chip XLA keeps the stream's projections, QK-norm and
    RoPE with the slots minor (physically ``[H*D, C]``) and wants
    ``o_proj``'s operand with the slots major: the kernel reads ``[H*D,
    C]`` blocks (the transposes written before the call are bitcasts in
    the compiled program; on ``[C, H*D]`` blocks each of q, k and v reached
    it through a transposing copy), turns a query block once, and writes
    ``[C, H*D]`` (written ``[H*D, C]``, the result went through a float32
    transposing copy on its way to ``o_proj``)."""
    capacity, width = q.shape
    kv_width = k.shape[1]
    dim = width // n_heads
    n_kv = kv_width // dim
    kv_step = packed_kv_heads(n_heads, n_kv, dim, block)
    heads_step = kv_step * (n_heads // n_kv)
    n_blocks = capacity // block
    tables = packed_tables(lengths, n_blocks, block, window)

    def q_map(qi, g, step, starts, first_row, last_row, first_key, total):
        # a block past the last real slot holds on to the last live
        # block's operands (no DMA) and runs nothing
        return (g, jnp.minimum(qi, last_live_block(total[0], block)))

    def k_map(qi, g, step, starts, first_row, last_row, first_key, total):
        # nor is a key block past the diagonal fetched: the index stays
        # where the last used one left it
        return (g, jnp.minimum(
            first_key[qi] + step,
            jnp.minimum(qi, last_live_block(total[0], block))))

    def feature_major(rows, index_map):
        return pl.BlockSpec((rows, block), index_map,
                            memory_space=pltpu.VMEM)

    return pl.pallas_call(
        functools.partial(
            _packed_flash_kernel, n_heads=heads_step, n_kv=kv_step,
            scale=scale, block_causal=block_causal, window=window),
        out_shape=jax.ShapeDtypeStruct((capacity, width), q.dtype),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=5,
            grid=(n_blocks, n_kv // kv_step,
                  packed_steps(max_len, block, window)),
            in_specs=[feature_major(heads_step * dim, q_map),
                      feature_major(kv_step * dim, k_map),
                      feature_major(kv_step * dim, k_map)],
            out_specs=pl.BlockSpec((block, heads_step * dim),
                                   lambda qi, g, step, *_: (qi, g),
                                   memory_space=pltpu.VMEM),
            scratch_shapes=[
                pltpu.VMEM((block, heads_step * dim), q.dtype),
                pltpu.VMEM((block, heads_step * dim), jnp.float32),
                pltpu.VMEM((heads_step, block, 128), jnp.float32),
                pltpu.VMEM((heads_step, block, 128), jnp.float32),
            ],
        ),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(*tables, q.T, k.T, v.T)


def flash_attention_packed(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    lengths: jax.Array,
    max_len: int,
    n_heads: int,
    block: int,
    block_causal: int = 1,
    scale: float | None = None,
    window: int = 0,
    interpret: bool | None = None,
) -> jax.Array:
    """Causal self-attention of rows laid one behind the other on a compact
    token set (``models/compact_stream.RealPositions``): row ``b``'s
    ``lengths[b] <= max_len`` positions are the slots from
    ``sum(lengths[:b])`` on, the slots behind the last row are fillers.
    ``q [C, H*D]``, ``k``/``v [C, Hkv*D]`` (query head ``h`` reads
    key/value head ``h // (H // Hkv)``); returns ``[C, H*D]``: every real
    slot's attention over its own row's slots up to itself (``block_causal`` = ``n`` > 1: up to the end of its
    block of ``n`` positions; rows are then whole blocks), of those the
    ``window`` up to its own where ``window`` > 0, with the scores times
    ``scale`` (``None`` = ``D ** -0.5``); finite values on fillers that share
    a ``block`` with a real slot, zeros on the blocks behind.  The caller
    promises ``sum(lengths) <= C`` and finite operands on every slot.
    ``block`` divides ``C`` and ``max_len``, and ``block_causal`` divides
    ``block``; anything else is refused, not served."""
    capacity, width = q.shape
    if (width % n_heads or capacity % block or max_len % block
            or block % block_causal or block % 8):
        raise ValueError(
            f"rows of {max_len} in {capacity} slots at block {block} "
            f"(block_causal {block_causal}, {n_heads} heads over {width} "
            "lanes) are outside the packed kernel's regime")
    dim = width // n_heads
    if (k.shape != v.shape or k.shape[0] != capacity or k.shape[1] % dim
            or n_heads % (k.shape[1] // dim)):
        raise ValueError(
            f"keys {k.shape} / values {v.shape} are not {capacity} slots of "
            f"key/value heads that divide {n_heads} query heads of {dim}")
    if window < 0:
        raise ValueError(f"window={window} is not a number of keys")
    if interpret is None:
        interpret = interpret_default()
    return _packed_flash_call(
        q, k, v, lengths.astype(jnp.int32), n_heads=n_heads,
        max_len=int(max_len), block=int(block),
        block_causal=int(block_causal),
        scale=float(dim ** -0.5 if scale is None else scale),
        window=int(window), interpret=interpret)
