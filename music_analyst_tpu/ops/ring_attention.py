"""Ring attention: sequence-parallel attention over the ``sp`` mesh axis.

Long-context support is first-class in this framework even though the
reference's longest "sequence" is a 4,000-char prompt truncation
(``scripts/sentiment_classifier.py:90``): lyrics corpora batch into long
packed sequences, and the decoder family must scale past a single chip's
HBM.

Design (blockwise/flash formulation, cf. PAPERS.md ring-attention entry):
queries stay resident; K/V blocks rotate around the ring via ``ppermute``
while each device accumulates its queries' attention with an online-softmax
(running max / normalizer / weighted accumulator).  After ``sp`` steps every
query has seen every key with only neighbor ICI traffic — no all-gather of
the full sequence anywhere.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.lax import pcast
from jax.sharding import Mesh, PartitionSpec as P

_NEG_INF = -1e30


def _block_attn_update(q, k_blk, v_blk, q_pos, kv_pos, causal, m, l, o,
                       q_seg=None, kv_seg=None):
    """One online-softmax accumulation step against a K/V block."""
    scale = q.shape[-1] ** -0.5
    logits = (
        jnp.einsum("bqhd,bkhd->bhqk", q, k_blk).astype(jnp.float32) * scale
    )
    allowed = None
    if causal:
        allowed = kv_pos[None, None, None, :] <= q_pos[None, None, :, None]
    if q_seg is not None:
        # Block-diagonal over packed documents: same-segment pairs only.
        seg_ok = q_seg[:, None, :, None] == kv_seg[:, None, None, :]
        allowed = seg_ok if allowed is None else (allowed & seg_ok)
    if allowed is not None:
        logits = jnp.where(allowed, logits, _NEG_INF)
    block_max = jnp.max(logits, axis=-1)                      # [B,H,Q]
    new_m = jnp.maximum(m, block_max)
    correction = jnp.exp(m - new_m)
    p = jnp.exp(logits - new_m[..., None])                    # [B,H,Q,K]
    if allowed is not None:
        # _NEG_INF is finite, so a fully-masked row's exp() is 1, not 0 —
        # re-zero the masked probabilities explicitly.
        p = jnp.where(allowed, p, 0.0)
    new_l = l * correction + p.sum(axis=-1)
    new_o = o * correction[..., None] + jnp.einsum(
        "bhqk,bkhd->bhqd", p, v_blk.astype(jnp.float32)
    )
    return new_m, new_l, new_o


def ring_attention_local(q, k, v, segment_ids=None, *, axis_name: str,
                         causal: bool = False, use_flash: bool = False):
    """Per-device body; call under ``shard_map`` with sequence sharded.

    Shapes per device: ``q,k,v [B, S/n, H, D]``.  Returns ``[B, S/n, H, D]``.

    ``use_flash=True`` computes each hop's local attention with the Pallas
    blocked kernel (``ops/flash_attention.py``) via its offset + residual
    hooks, then merges the per-hop ``(o, m, l)`` partials with the same
    online-softmax algebra — VMEM-blocked compute inside each hop, ICI
    ``ppermute`` between hops.

    ``segment_ids`` ``[B, S/n]`` (sequence-sharded like ``q``) restricts
    attention to same-segment pairs — the packed-documents long-context
    pattern.  The local segment shard rotates around the ring with its
    K/V block, so cross-device segment boundaries mask exactly.
    """
    n = jax.lax.psum(1, axis_name)
    idx = jax.lax.axis_index(axis_name)
    B, S_loc, H, D = q.shape
    # GQA: the blocks that ROTATE stay at their compact n_kv_heads size
    # (ring ICI traffic is the scarce resource); the dense path broadcasts
    # to the query-head count only transiently inside each hop, and the
    # flash kernel maps query head -> kv head in its index map.
    group = H // k.shape[2]
    q_pos = idx * S_loc + jnp.arange(S_loc)

    m = jnp.full((B, H, S_loc), _NEG_INF, jnp.float32)
    l = jnp.zeros((B, H, S_loc), jnp.float32)
    o = jnp.zeros((B, H, S_loc, D), jnp.float32)
    # The accumulators become device-varying inside the ring loop; mark the
    # initial values as varying over the axis so the carry types line up.
    m, l, o = (pcast(x, (axis_name,), to="varying") for x in (m, l, o))

    segmented = segment_ids is not None
    # This device's own (query-side) segment shard never rotates; only the
    # kv-side copy travels around the ring in the carry.
    q_seg_loc = segment_ids.astype(jnp.int32) if segmented else None

    def body(step, carry):
        # The segment shard joins the carry ONLY when segmented (the bool
        # is trace-static): unsegmented calls keep the original 5-tuple
        # and pay zero extra ppermute traffic.
        if segmented:
            k_blk, v_blk, seg_blk, m, l, o = carry
        else:
            k_blk, v_blk, m, l, o = carry
            seg_blk = None
        # After `step` rotations (each device passes K/V to the next ring
        # neighbor), this device holds the block originally owned by
        # idx - step.
        owner = (idx - step) % n
        if use_flash:
            from music_analyst_tpu.ops.flash_attention import flash_attention

            o_i, m_i, l_i = flash_attention(
                q, k_blk, v_blk, causal=causal,
                q_offset=idx * S_loc, kv_offset=owner * S_loc,
                return_residuals=True,
                q_segment_ids=q_seg_loc,
                kv_segment_ids=seg_blk,
            )
            o_i = jnp.transpose(o_i, (0, 2, 1, 3))     # [B,H,Q,D]
            m_new = jnp.maximum(m, m_i)
            c_prev = jnp.exp(m - m_new)
            c_hop = jnp.exp(jnp.where(m_i > _NEG_INF / 2, m_i - m_new,
                                      -jnp.inf))
            l = l * c_prev + l_i * c_hop
            o = o * c_prev[..., None] + o_i * c_hop[..., None]
            m = m_new
        else:
            kv_pos = owner * S_loc + jnp.arange(S_loc)
            if group > 1:
                k_use = jnp.repeat(k_blk, group, axis=2)
                v_use = jnp.repeat(v_blk, group, axis=2)
            else:
                k_use, v_use = k_blk, v_blk
            m, l, o = _block_attn_update(
                q, k_use, v_use, q_pos, kv_pos, causal, m, l, o,
                q_seg=q_seg_loc,
                kv_seg=seg_blk,
            )
        perm = [(i, (i + 1) % n) for i in range(n)]
        k_blk = jax.lax.ppermute(k_blk, axis_name, perm)
        v_blk = jax.lax.ppermute(v_blk, axis_name, perm)
        if segmented:
            # The segment shard travels WITH its K/V block so cross-device
            # segment boundaries mask exactly on every hop.
            seg_blk = jax.lax.ppermute(seg_blk, axis_name, perm)
            return k_blk, v_blk, seg_blk, m, l, o
        return k_blk, v_blk, m, l, o

    if segmented:
        init = (k, v, q_seg_loc, m, l, o)
    else:
        init = (k, v, m, l, o)
    *_, m, l, o = jax.lax.fori_loop(0, n, body, init)
    out = o / jnp.maximum(l, 1e-30)[..., None]                # [B,H,Q,D]
    return jnp.transpose(out, (0, 2, 1, 3)).astype(q.dtype)   # [B,Q,H,D]


def ring_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    mesh: Mesh,
    axis: str = "sp",
    causal: bool = False,
    use_flash: bool = False,
    segment_ids: jax.Array | None = None,
) -> jax.Array:
    """Sequence-parallel attention: ``[B, S, H, D]`` sharded on S over ``axis``.

    ``segment_ids`` ``[B, S]`` adds block-diagonal masking over packed
    documents; the ids shard over ``axis`` with the sequence and rotate
    with the K/V blocks, so segments spanning device boundaries mask
    exactly (composable with ``causal``).
    """
    body = partial(ring_attention_local, axis_name=axis, causal=causal,
                   use_flash=use_flash)
    n_in = 3 if segment_ids is None else 4
    fn = jax.jit(
        shard_map(
            body,
            mesh=mesh,
            in_specs=(P(None, axis),) * n_in,
            out_specs=P(None, axis),
            # pallas_call outputs carry no varying-mesh-axis annotation;
            # skip the vma check on the flash path.
            check_vma=not use_flash,
        )
    )
    if segment_ids is None:
        return fn(q, k, v)
    return fn(q, k, v, segment_ids)
