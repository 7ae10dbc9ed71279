"""Fused Pallas paged-attention decode kernel over the shared page pool.

``ops/kv_pages.py`` originally ran decode by materializing a contiguous
``[n_slots, max_total]`` copy of every slot's KV through the page table
(gather), running dense attention over the copy, and scattering the
touched pages back — three extra HBM passes over the whole resident KV
per decode dispatch.  This module removes the copy: one fused kernel
reads the ``(n_slots, pages_per_slot)`` int32 page table *inside* the
program, streams KV pages through VMEM, and reduces — gather + QK +
softmax + V in a single ``pallas_call``, so no contiguous view is ever
materialized.

Two kernel bodies, chosen statically by backend
(``ops/flash_attention.interpret_default`` is the one place that decides):

* **exact batched body** (interpret mode / the CPU-emulated test mesh):
  one program over the whole batch; the in-kernel take-gather feeds the
  *verbatim* ops of the dense reference
  (``models/layers.dot_product_attention`` over the gathered view) —
  the same grouped contractions (``ops/kv_cache.grouped_scores`` /
  ``grouped_values``), the same cast/scale order — so interpret-mode
  lowering is **bitwise** identical to the retired gather path.  (Any
  other form is mathematically equal but reassociates, and a 1-ulp
  logit difference flips greedy argmax near-ties.)
* **streaming body** (real TPU, compiled by Mosaic): grid ``(n_slots,
  pages_per_slot)``; the table rides in SMEM as a scalar-prefetch
  operand and the K/V page BlockSpecs index the pool *through* it, so
  the Pallas pipeline DMAs (and double-buffers) one physical page per
  grid step.  Each step folds its page into an online-softmax
  accumulator held in VMEM scratch across the page axis (running max /
  normalizer / weighted-V, masked lanes contribute exact zeros) —
  O(page) VMEM regardless of context length.  Everything in the body is
  a lane-dense 2-D tile: the page ``[P, n_kv, D]`` is viewed as
  ``[P * n_kv, D]`` and contracted against *all* ``H`` query heads at
  once; the ``[H, P * n_kv]`` score tile then keeps only the columns
  whose KV head matches the row's query group (an iota compare), which
  is GQA without the mid-axis batched einsum, strided head slices or
  sub-128 lane slices Mosaic refuses.  The extra ``n_kv``× MXU work is
  free where decode attention is bound by page bytes.

int8 KV pages (``ops/quant.quantize_kv_page``): both bodies accept
optional per-(page, row) f32 scale pools.  The exact body dequantizes
right after the gather (``int8 → f32 × scale → bf16``); the streaming
body applies the K scale to the score columns and the V scale to the
probabilities (the scale is constant over ``(n_kv, D)``, so this is the
same product with the codes contracted exactly).  The fp16/bf16 path
under interpret stays byte-identical to the retired gather runtime;
int8 and the streaming body carry a bounded-error contract instead
(``tests/test_paged_attention.py``; on the chip, ``chip_smoke.py``).

:class:`PagedAttnView` is the cache-shaped adapter: a registered
dataclass carrying (pool, scales, table, write offsets) that duck-types
``ops/kv_cache.KVCache`` — ``update`` writes the new token's KV row
directly into its physical page (quantizing per-row for int8) and
``attend`` invokes the kernel — so the paged decode runtime passes it
through the unmodified model stack and the whole decode span runs with
no per-dispatch gather/pad/scatter.
"""

from __future__ import annotations

import dataclasses
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from music_analyst_tpu.ops.flash_attention import interpret_default
from music_analyst_tpu.ops.kv_cache import grouped_scores, grouped_values

# Masked logit value.  The exact body uses finfo.min to match the dense
# reference bitwise; the streaming body's running max starts here and
# masked lanes are zeroed explicitly after the exp.
_NEG_INF = -1e30


def _geometry(q, key_pages, table, mask):
    n, q_len, H, D = q.shape
    if q_len != 1:
        raise ValueError(
            f"paged_attention is a decode kernel (q_len == 1), got {q_len}"
        )
    P, n_kv = key_pages.shape[1], key_pages.shape[2]
    if key_pages.shape[3] != D:
        raise ValueError(
            f"head_dim mismatch: q has {D}, pages have {key_pages.shape[3]}"
        )
    if H % n_kv:
        raise ValueError(f"n_heads ({H}) not divisible by n_kv ({n_kv})")
    pps = table.shape[1]
    total = mask.shape[-1]
    if total > pps * P:
        raise ValueError(
            f"mask width ({total}) exceeds slot span ({pps * P})"
        )
    return n, H, n_kv, D, P, pps, total


def _dequant(codes, scale, dtype):
    """int8 codes → compute dtype, scale broadcast over (n_kv, head_dim)."""
    return (codes.astype(jnp.float32) * scale[..., None, None]).astype(dtype)


def _exact_body(n, H, n_kv, D, P, pps, total, quantized, dtype):
    """One program, whole batch: in-kernel gather + the dense reference.

    Bitwise-identical to dense attention over the gathered contiguous
    view (tests/test_paged_attention.py pins this at page sizes 8 and
    16): after the gather, the ops ARE ``dot_product_attention``'s —
    the same grouped contractions (``ops/kv_cache.grouped_scores`` /
    ``grouped_values``: no repeat of the key heads, float32 scores out of
    the matmul times ``D**-0.5``), ``finfo.min`` masking, the float32
    softmax cast to ``q.dtype`` for the values.  Any other association of
    the multiply-adds (repeating the key heads among them) can move a
    logit by an ulp, and a 1-ulp logit difference flips greedy argmax
    near-ties — the byte-identity contract forbids it.
    """
    span = pps * P
    att_scale = D ** -0.5

    def body(table_ref, mask_ref, q_ref, kp_ref, vp_ref, *rest):
        if quantized:
            ks_ref, vs_ref, o_ref = rest
        else:
            (o_ref,) = rest
        k = jnp.take(kp_ref[:], table_ref[:], axis=0)  # [n, pps, P, kv, D]
        v = jnp.take(vp_ref[:], table_ref[:], axis=0)
        if quantized:
            sk = jnp.take(ks_ref[:], table_ref[:], axis=0)  # [n, pps, P]
            sv = jnp.take(vs_ref[:], table_ref[:], axis=0)
            k = _dequant(k, sk, dtype)
            v = _dequant(v, sv, dtype)
        k = k.reshape(n, span, n_kv, D)[:, :total]
        v = v.reshape(n, span, n_kv, D)[:, :total]
        s = grouped_scores(q_ref[:], k, att_scale)     # [n, kv, G, 1, total]
        s = jnp.where(
            mask_ref[:][:, None, None, None, :total], s,
            jnp.finfo(jnp.float32).min,
        )
        p = jax.nn.softmax(s, axis=-1)
        o_ref[:] = grouped_values(p, v, dtype).astype(dtype)

    return body


def check_stream_geometry(n_kv: int, head_dim: int) -> None:
    """Raise unless Mosaic can lower the streaming body at this geometry.

    The body views a ``[P, n_kv, D]`` page as ``[P * n_kv, D]``; Mosaic
    (libtpu 0.0.34) folds the KV-head axis into sublanes only when it is
    a power of two or the minor dim fills whole 128-lane tiles.
    """
    if n_kv & (n_kv - 1) and head_dim % 128:
        raise ValueError(
            f"paged attention cannot be compiled for n_kv_heads={n_kv}, "
            f"head_dim={head_dim}: the TPU kernel needs a power-of-two "
            "KV-head count or a head_dim that is a multiple of 128"
        )


def _stream_body(H, n_kv, D, P, pps, quantized, dtype):
    """Per-(slot, page) program: fold one page into the online softmax.

    The grid's page axis is sequential; (max, normalizer, weighted-V)
    live in VMEM scratch across it and the output block is written on
    the slot's last page.  The ``[H, P * n_kv]`` score tile holds every
    (query head, KV head) pair; columns of a foreign KV head are masked
    exactly like invalid positions.  Masked lanes are zeroed *after* the
    exp, so fully-masked pages (the slack tail past ``total``, a free
    slot's trash pages) contribute exactly nothing.
    """
    G = H // n_kv
    W = P * n_kv
    att_scale = D ** -0.5

    def body(table_ref, mask_ref, *rest):
        del table_ref  # consumed by the page BlockSpecs' index maps
        if quantized:
            ks_ref, vs_ref, *rest = rest
        q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref = rest
        lp = pl.program_id(1)

        @pl.when(lp == 0)
        def _init():
            m_ref[...] = jnp.full(m_ref.shape, _NEG_INF, jnp.float32)
            l_ref[...] = jnp.zeros(l_ref.shape, jnp.float32)
            acc_ref[...] = jnp.zeros(acc_ref.shape, jnp.float32)

        q = q_ref[0, 0]                                    # [H, D]
        k = k_ref[0].astype(dtype).reshape(W, D)           # int8 → bf16 exact
        v = v_ref[0].astype(dtype).reshape(W, D)
        row_kv = jax.lax.broadcasted_iota(jnp.int32, (H, W), 0) // G
        col_kv = jax.lax.broadcasted_iota(jnp.int32, (H, W), 1) % n_kv
        valid = (mask_ref[0, 0] > 0) & (row_kv == col_kv)  # [H, W]
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        ) * att_scale
        if quantized:
            s = s * ks_ref[0, 0]
        s = jnp.where(valid, s, _NEG_INF)
        m = m_ref[...]
        m_new = jnp.maximum(m, jnp.max(s, axis=-1, keepdims=True))
        p = jnp.where(valid, jnp.exp(s - m_new), 0.0)      # exact zeros
        corr = jnp.exp(m - m_new)
        l_ref[...] = l_ref[...] * corr + jnp.sum(p, axis=-1, keepdims=True)
        if quantized:
            p = p * vs_ref[0, 0]
        acc_ref[...] = acc_ref[...] * corr + jnp.dot(
            p.astype(dtype), v, preferred_element_type=jnp.float32
        )
        m_ref[...] = m_new

        @pl.when(lp == pps - 1)
        def _finalize():
            l = l_ref[...]
            l = jnp.where(l == 0.0, 1.0, l)                # all-masked rows
            o_ref[0, 0] = (acc_ref[...] / l).astype(dtype)

    return body


def paged_attention(
    q: jax.Array,
    key_pages: jax.Array,
    value_pages: jax.Array,
    table: jax.Array,
    mask: jax.Array,
    *,
    key_scale: Optional[jax.Array] = None,
    value_scale: Optional[jax.Array] = None,
    interpret: Optional[bool] = None,
    stream: Optional[bool] = None,
) -> jax.Array:
    """Fused paged decode attention: gather + QK + softmax + V, one call.

    Args:
      q: ``[n_slots, 1, n_heads, head_dim]`` decode queries.
      key_pages / value_pages: the physical pool,
        ``[n_pages + 1, page_size, n_kv_heads, head_dim]`` (bf16/fp16, or
        int8 codes when scales are passed; the +1 row is the trash page).
      table: ``[n_slots, pages_per_slot]`` int32 physical page indices.
      mask: ``[n_slots, total]`` bool — True at attendable positions
        (``total`` fixes the softmax width, exactly as the retired
        gathered view's ``[:, :total]`` slice did).
      key_scale / value_scale: optional ``[n_pages + 1, page_size]`` f32
        per-(page, row) symmetric dequant scales; passing them selects
        the int8 path with dequant fused after the KV load.
      interpret: run the Pallas interpreter (defaults to "not on TPU" —
        the CPU-emulated test mesh always interprets; on a TPU the
        kernel is compiled by Mosaic).
      stream: pick the page-streaming online-softmax body (defaults to
        the exact batched body under interpret, streaming on TPU; tests
        force ``stream=True`` under interpret to cover the TPU body).

    Returns ``[n_slots, 1, n_heads, head_dim]`` in ``q.dtype``.
    """
    if interpret is None:
        interpret = interpret_default()
    if stream is None:
        stream = not interpret
    quantized = key_scale is not None
    if quantized != (value_scale is not None):
        raise ValueError("key_scale and value_scale must be passed together")
    n, H, n_kv, D, P, pps, total = _geometry(q, key_pages, table, mask)
    dtype = q.dtype
    if not stream:
        operands = [table, mask, q, key_pages, value_pages]
        pool_specs = [pl.BlockSpec(memory_space=pl.ANY)] * 2
        if quantized:
            operands += [key_scale, value_scale]
            pool_specs += [pl.BlockSpec(memory_space=pl.ANY)] * 2
        body = _exact_body(n, H, n_kv, D, P, pps, total, quantized, dtype)
        return pl.pallas_call(
            body,
            out_shape=jax.ShapeDtypeStruct((n, 1, H, D), dtype),
            in_specs=[
                pl.BlockSpec(memory_space=pltpu.SMEM),   # table
                pl.BlockSpec(memory_space=pltpu.VMEM),   # mask
                pl.BlockSpec(memory_space=pltpu.VMEM),   # q
                *pool_specs,
            ],
            out_specs=pl.BlockSpec(memory_space=pltpu.VMEM),
            interpret=interpret,
        )(*operands)
    check_stream_geometry(n_kv, D)
    span = pps * P
    W = P * n_kv

    def per_column(plane):
        """``[n, span]`` per-token plane → ``[n, pps, 1, P * n_kv]``: one
        lane-dense row per page, each token repeated over the KV heads
        its page rows fan out to in the ``[H, P * n_kv]`` score tile."""
        return jnp.repeat(plane, n_kv, axis=-1).reshape(n, pps, 1, W)

    column_spec = pl.BlockSpec(
        (1, 1, 1, W), lambda i, lp, tbl: (i, lp, 0, 0)
    )
    head_spec = pl.BlockSpec((1, 1, H, D), lambda i, lp, tbl: (i, 0, 0, 0))
    page_spec = pl.BlockSpec(
        (1, P, n_kv, D), lambda i, lp, tbl: (tbl[i * pps + lp], 0, 0, 0)
    )

    # The body walks whole pages; the slack tail past ``total`` is just
    # more masked lanes.  An integer mask: Mosaic has no bool VMEM blocks.
    operands = [
        per_column(
            jnp.pad(mask, ((0, 0), (0, span - total))).astype(jnp.int32)
        )
    ]
    in_specs = [column_spec]
    if quantized:
        # Scales are 1/(n_kv * D) of the page bytes: gather the slot's
        # rows here and hand them to the kernel as score-column planes.
        for scale in (key_scale, value_scale):
            operands.append(
                per_column(jnp.take(scale, table, axis=0).reshape(n, span))
            )
            in_specs.append(column_spec)
    operands += [q, key_pages, value_pages]
    in_specs += [head_spec, page_spec, page_spec]
    return pl.pallas_call(
        _stream_body(H, n_kv, D, P, pps, quantized, dtype),
        out_shape=jax.ShapeDtypeStruct((n, 1, H, D), dtype),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(n, pps),
            in_specs=in_specs,
            out_specs=head_spec,
            scratch_shapes=[
                pltpu.VMEM((H, 1), jnp.float32),     # running max
                pltpu.VMEM((H, 1), jnp.float32),     # normalizer
                pltpu.VMEM((H, D), jnp.float32),     # weighted V
            ],
        ),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
        ),
        interpret=interpret,
    )(table.reshape(-1), *operands)


def paged_attention_reference(
    q, key_pages, value_pages, table, mask, key_scale=None, value_scale=None
):
    """Naive f32 oracle: gather pool rows through the table, dequantize,
    broadcast KV heads over query groups, full-precision softmax.  The
    property tests (``tests/test_paged_attention.py``) compare both
    kernel bodies against this across page sizes, odd valid lengths,
    and trash-page table rows."""
    n, H, n_kv, D, P, pps, total = _geometry(q, key_pages, table, mask)
    span = pps * P
    k = jnp.take(key_pages, table, axis=0)
    v = jnp.take(value_pages, table, axis=0)
    if key_scale is not None:
        k = _dequant(k, jnp.take(key_scale, table, axis=0), jnp.float32)
        v = _dequant(v, jnp.take(value_scale, table, axis=0), jnp.float32)
    k = k.reshape(n, span, n_kv, D)[:, :total].astype(jnp.float32)
    v = v.reshape(n, span, n_kv, D)[:, :total].astype(jnp.float32)
    group = H // n_kv
    k = jnp.repeat(k, group, axis=2)
    v = jnp.repeat(v, group, axis=2)
    q32 = q.astype(jnp.float32)
    logits = jnp.einsum("bqhd,bkhd->bhqk", q32, k) * (D ** -0.5)
    logits = jnp.where(
        mask[:, None, None, :], logits, jnp.finfo(jnp.float32).min
    )
    probs = jax.nn.softmax(logits, axis=-1)
    return jnp.einsum("bhqk,bkhd->bqhd", probs, v)


@dataclasses.dataclass
class PagedAttnView:
    """KVCache-shaped adapter binding one decode step to the page pool.

    Carries the physical pool (codes + scales for int8), the page table,
    and per-slot write offsets; duck-types ``ops/kv_cache.KVCache`` so
    the unmodified model stack drives the fused kernel: ``update`` lands
    the step's new KV row directly in its physical page (``off // P``
    within the slot's row, quantized per-row for int8) and ``attend``
    runs :func:`paged_attention` — the pool IS the cache, so the decode
    scan carries it and the runtime never gathers or scatters a view.
    """

    keys: jax.Array                      # [n_pages + 1, P, n_kv, D]
    values: jax.Array
    key_scale: Optional[jax.Array]       # [n_pages + 1, P] f32, int8 only
    value_scale: Optional[jax.Array]
    table: jax.Array                     # [n_slots, pages_per_slot] int32
    length: jax.Array                    # [n_slots] int32 write offsets
    page_size: int = 16
    total: int = 0

    def update(self, k_new: jax.Array, v_new: jax.Array) -> "PagedAttnView":
        if k_new.shape[1] != 1:
            raise ValueError(
                "PagedAttnView writes one decode token per step "
                f"(got {k_new.shape[1]}); chunked prefill stays on the "
                "gather/scatter path (ops/kv_pages.py)"
            )
        P = self.page_size
        rows = jnp.arange(self.table.shape[0])
        off = self.length
        lp = off // P
        r = off % P
        # Free slots' rows all point at the trash page; their duplicate
        # writes race benignly (the page is never read through an active
        # mask).  Decode offsets sit at or past prompt_region, so lp
        # lands in the decode pages and shared prompt pages are never
        # written (the invariant the retired scatter clamped for).
        phys = self.table[rows, lp]
        if self.key_scale is None:
            keys = self.keys.at[phys, r].set(
                k_new[:, 0].astype(self.keys.dtype)
            )
            values = self.values.at[phys, r].set(
                v_new[:, 0].astype(self.values.dtype)
            )
            key_scale = value_scale = None
        else:
            from music_analyst_tpu.ops.quant import quantize_kv_page

            qk, sk = quantize_kv_page(k_new[:, 0])
            qv, sv = quantize_kv_page(v_new[:, 0])
            keys = self.keys.at[phys, r].set(qk)
            values = self.values.at[phys, r].set(qv)
            key_scale = self.key_scale.at[phys, r].set(sk)
            value_scale = self.value_scale.at[phys, r].set(sv)
        return dataclasses.replace(
            self, keys=keys, values=values,
            key_scale=key_scale, value_scale=value_scale, length=off + 1,
        )

    def attend(self, q: jax.Array, mask: jax.Array) -> jax.Array:
        """Decode attention for ``q [n, 1, H, D]`` under ``mask
        [n, 1, 1, total]`` — the fused kernel, no materialized view."""
        return paged_attention(
            q, self.keys, self.values, self.table, mask[:, 0, 0, :],
            key_scale=self.key_scale, value_scale=self.value_scale,
        )


jax.tree_util.register_dataclass(
    PagedAttnView,
    data_fields=[
        "keys", "values", "key_scale", "value_scale", "table", "length"
    ],
    meta_fields=["page_size", "total"],
)
