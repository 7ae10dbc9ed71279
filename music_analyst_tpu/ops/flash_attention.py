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
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


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
