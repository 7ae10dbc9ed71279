"""Key-padded self-attention for short sequences as one Pallas TPU kernel.

The dense formulation (``models/layers.py:dot_product_attention``) writes
the ``[B, H, S, S]`` float32 scores to HBM, reads them back for the
softmax, writes the probabilities and reads those for ``P·V``: at the
classifier's 4,096 x 12 x 128 x 128 that is 3.2 GB of scores and more than
10 GB of traffic a layer for 0.2 TFLOP of matmul.  The blocked kernel
(``ops/flash_attention.py``) is built for long rows — its grid at this
shape is 49,152 steps of ``[128, 64]`` tiles.  When the whole key row of
every head fits on the chip neither is needed: one grid step takes a block
of rows with *all* heads, computes ``Q·Kᵀ·scale`` in float32, masks keys
``>= lengths[row]``, takes the exact softmax over the row that is there
(no running max, no rescaling), casts to the input dtype as the dense path
does and writes ``P·V`` — Q, K, V and O cross HBM once and the scores
never leave VMEM.

Layout.  On the chip XLA keeps the projections' ``[B, S, H, D]`` outputs
with ``S`` minor when ``S`` fills the 128 lanes (physically ``[B, H, D,
S]``), and wants the attention output the same way for ``o_proj``.  The
kernel therefore works on ``[B, H*D, S]`` blocks: the transposes written
around the call are bitcasts in the compiled program, a head is a whole-
tile sublane slice, and nothing is shuffled across lanes.  (A kernel on
``[B, S, H*D]`` blocks compiled to four transposing copies of 0.8 GB a
layer around the call.)

The mathematics and the precision are the dense path's: on a v5e the
outputs are bit-equal to it (the compiler keeps the dense einsum's scores
in float32 too, and both reduce over the key axis in the same order).

Who runs it is decided where the mask is built (``models/distilbert.py``)
from what :func:`whole_row_block_rows` says of the shape.  Pallas is
imported when a call is traced, not when this module is.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp

# What one call may hold in VMEM (a v5e core has 128 MiB; the compiler's
# default scope is 16 MiB): the double-buffered Q/K/V/O blocks plus the
# float32 scores of the heads in flight.
VMEM_LIMIT_BYTES = 32 * 1024 * 1024
# Rows of one grid step.  More do not help: blocks of 2 to 32 rows ran
# within 0.3% of each other on a v5e (PERF.md) — the matmuls set the
# pace, not the 0.35 us of step overhead.
MAX_BLOCK_ROWS = 8
# [H, S, S] float32 temporaries of a row the compiler keeps in VMEM
# (scores, their exponentials, and the probabilities at half the size).
_SCORE_COPIES = 4


def whole_row_block_rows(
    seq: int, n_heads: int, head_dim: int, dtype, mesh=None
) -> int:
    """Rows a grid step of the kernel takes at this shape; 0 = the shape is
    outside the kernel's regime and keeps the dense path.

    The one place the limit is written down.  Positions ride the 128 lanes
    (the layout XLA gives the projections when ``S`` fills them), so ``S``
    is a multiple of 128: a shorter row would leave lanes empty in HBM as
    in VMEM (at ``S = 32`` the step's temporaries grew from 8.7 to 15.3
    GB, PERF.md).  A head is whole sublane tiles of its dtype.  And one
    row's double-buffered Q/K/V/O blocks plus the ``[H, S, S]`` float32
    scores must fit :data:`VMEM_LIMIT_BYTES`: at DistilBERT's 12 x 64
    heads in bfloat16 that is ``S <= 384``.  Under a mesh the shape is the
    per-shard one (heads split over ``tp``).
    """
    if mesh is not None:
        tp = mesh.shape.get("tp", 1)
        if n_heads % tp:
            return 0
        n_heads //= tp
    itemsize = jnp.dtype(dtype).itemsize
    if seq % 128 or head_dim % (32 // itemsize):
        return 0
    per_row = 4 * 2 * seq * n_heads * head_dim * itemsize
    scores = _SCORE_COPIES * n_heads * seq * seq * 4
    return max(0, min(MAX_BLOCK_ROWS, (VMEM_LIMIT_BYTES - scores) // per_row))


def _whole_row_kernel(
    len_ref,  # SMEM [B] — valid keys per row (scalar prefetch)
    q_ref,    # VMEM [rows, H*D, S] — features on sublanes, positions on lanes
    k_ref,
    v_ref,
    o_ref,
    *,
    total_rows: int,
    head_dim: int,
    scale: float,
):
    from jax.experimental import pallas as pl

    rows, width, seq = q_ref.shape
    n_heads = width // head_dim
    base = pl.program_id(0) * rows
    # Scores are kept keys-by-queries: the softmax then reduces over
    # sublanes (elementwise across vregs) and P·V needs no transpose.
    key_pos = jax.lax.broadcasted_iota(jnp.int32, (1, seq, seq), 1)
    floor = jnp.finfo(jnp.float32).min  # the dense path's fill

    def one_row(r, carry):
        # The last block may hang over the end of the batch: its rows are
        # computed on whatever the block holds and never written back;
        # only the scalar read needs an index that exists.
        n_keys = len_ref[jnp.minimum(base + r, total_rows - 1)]
        # All heads of the row in one batched matmul each way: as twelve
        # matmuls in a row the compiler spreads them over the MXUs, as
        # twelve matmul-softmax-matmul chains it ran them one behind the
        # other (8.2 ms a call against 4.9 at 4,096 x 12 x 128 x 64).
        q = q_ref[r].reshape(n_heads, head_dim, seq)
        k = k_ref[r].reshape(n_heads, head_dim, seq)
        v = v_ref[r].reshape(n_heads, head_dim, seq)
        s = jax.lax.dot_general(
            k, q, (((1,), (1,)), ((0,), (0,))),
            preferred_element_type=jnp.float32,
        ) * scale  # [H, S_k, S_q]; scaled after the matmul, see flash
        s = jnp.where(key_pos < n_keys, s, floor)
        p = jnp.exp(s - s.max(axis=1, keepdims=True))
        p = (p * (1.0 / p.sum(axis=1, keepdims=True))).astype(v.dtype)
        out = jax.lax.dot_general(
            v, p, (((2,), (1,)), ((0,), (0,))),
            preferred_element_type=jnp.float32,
        )  # [H, D, S_q]
        o_ref[r] = out.reshape(width, seq).astype(o_ref.dtype)
        return carry

    jax.lax.fori_loop(0, rows, one_row, 0)


@functools.partial(jax.jit, static_argnames=("block_rows", "interpret"))
def _whole_row_call(q, k, v, lengths, block_rows: int, interpret: bool):
    """The ``pallas_call`` under one inner ``jit``: every layer of a forward
    that calls it at one shape shares one trace and one Mosaic lowering."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    batch, seq, n_heads, head_dim = q.shape
    width = n_heads * head_dim
    rows = min(block_rows, batch)
    block = pl.BlockSpec(
        (rows, width, seq), lambda i, lens: (i, 0, 0),
        memory_space=pltpu.VMEM,
    )

    def feature_major(x):  # [B, S, H, D] -> [B, H*D, S]
        return x.transpose(0, 2, 3, 1).reshape(batch, width, seq)

    out = pl.pallas_call(
        functools.partial(
            _whole_row_kernel, total_rows=batch, head_dim=head_dim,
            scale=head_dim ** -0.5,
        ),
        out_shape=jax.ShapeDtypeStruct((batch, width, seq), q.dtype),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(pl.cdiv(batch, rows),),
            in_specs=[block, block, block],
            out_specs=block,
        ),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",),
            vmem_limit_bytes=VMEM_LIMIT_BYTES,
        ),
        interpret=interpret,
    )(lengths, feature_major(q), feature_major(k), feature_major(v))
    return out.reshape(batch, n_heads, head_dim, seq).transpose(0, 3, 1, 2)


def whole_row_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    lengths: jax.Array,
    mesh=None,
    interpret: Optional[bool] = None,
) -> jax.Array:
    """Self-attention over ``[B, S, H, D]`` with keys ``>= lengths[b]``
    masked: ``dot_product_attention(q, k, v, padding_mask(lengths, S))``
    without the scores in HBM.

    Only for shapes :func:`whole_row_block_rows` admits (it raises
    otherwise: the caller chooses, this function does not fall back).  Any
    row count is served by one call — a last block that hangs over the end
    is clipped on the way out.  Under a ``mesh`` the call is opaque to the
    partitioner, so it runs per shard: rows split over ``dp``, heads over
    ``tp``, as the projections leave them; nothing is gathered.
    """
    from music_analyst_tpu.ops.flash_attention import interpret_default

    if q.shape != k.shape or q.shape != v.shape:
        raise ValueError(
            "whole_row_attention is self-attention without a cache or "
            f"grouped heads: q {q.shape}, k {k.shape}, v {v.shape}"
        )
    _, seq, n_heads, head_dim = q.shape
    block_rows = whole_row_block_rows(seq, n_heads, head_dim, q.dtype, mesh)
    if not block_rows:
        raise ValueError(
            f"no whole row of S={seq}, H={n_heads}, D={head_dim}, "
            f"{q.dtype} fits the kernel (whole_row_block_rows); use "
            "dot_product_attention with padding_mask"
        )
    if interpret is None:
        interpret = interpret_default()
    call = functools.partial(
        _whole_row_call, block_rows=block_rows, interpret=interpret
    )
    lengths = lengths.astype(jnp.int32)
    if mesh is None:
        return call(q, k, v, lengths)
    from jax import shard_map
    from jax.sharding import PartitionSpec as P

    dp = "dp" if "dp" in mesh.axis_names else None
    tp = "tp" if "tp" in mesh.axis_names else None
    heads = P(dp, None, tp, None)
    return shard_map(
        call, mesh=mesh, in_specs=(heads, heads, heads, P(dp)),
        out_specs=heads, check_vma=False,
    )(q, k, v, lengths)
