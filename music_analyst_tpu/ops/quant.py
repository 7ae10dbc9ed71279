"""Dynamic int8 quantized matmul for inference (w8a8, int32 accumulate).

The roofline suite measures the v5e MXU at ~2.1× bf16 throughput for
int8×int8→int32 chains (``benchmarks/results/roofline.json``), and the
headline DistilBERT forward already runs at ~93% of the bf16 roofline —
so int8 is the remaining large FLOP lever.  This op quantizes on the fly:

* weights: symmetric per-output-channel, ``s_w[c] = max|w[:,c]| / 127`` —
  computed inside the jitted forward from the ordinary float params, so
  the param tree, checkpoint loaders, and sharding rules are untouched;
* activations: symmetric per-token (row-wise) dynamic,
  ``s_x[t] = max|x[t,:]| / 127`` — one outlier token costs only its own
  row's resolution, not the whole batch's (the per-tensor variant loses
  ~all precision on every other row once one activation spikes;
  ``tests/test_quant.py::test_outlier_token_does_not_poison_batch``);
* accumulation in int32 on the MXU, dequant ``acc · s_x[t] · s_w[c]``
  fused into the epilogue by XLA.

Accuracy contract: quantization error is bounded by the symmetric-int8
resolution (~0.8% of the dynamic range per operand); the classifier's
2→3-label thresholding absorbs small logit shifts, and
``tests/test_quant.py`` pins both the op-level error and end-to-end label
agreement.  No reference analogue (the reference's model lives behind an
HTTP API); this is a TPU-hardware play, default OFF (``quant="none"``).
"""

from __future__ import annotations

import dataclasses
import math
import re
from typing import Any, Tuple

import jax
import jax.numpy as jnp
import numpy as np


def _symmetric_scale(value: jax.Array, axis, keepdims: bool = True):
    amax = jnp.max(jnp.abs(value), axis=axis, keepdims=keepdims)
    return jnp.maximum(amax, 1e-8) / 127.0


def quant_matmul(x: jax.Array, w: jax.Array) -> jax.Array:
    """``x @ w`` via dynamic int8: x ``[..., K]`` f32/bf16, w ``[K, N]``.

    Returns f32 ``[..., N]``.
    """
    x32 = x.astype(jnp.float32)
    w32 = w.astype(jnp.float32)
    s_x = _symmetric_scale(x32, axis=-1)  # [..., 1] per token
    s_w = _symmetric_scale(w32, axis=0)   # [1, N] per channel
    qx = jnp.round(x32 / s_x).astype(jnp.int8)
    qw = jnp.round(w32 / s_w).astype(jnp.int8)
    acc = jax.lax.dot_general(
        qx, qw,
        (((qx.ndim - 1,), (0,)), ((), ())),
        preferred_element_type=jnp.int32,
    )
    return acc.astype(jnp.float32) * s_x * s_w.reshape(1, -1)


def quant_batched_matmul(x: jax.Array, w: jax.Array) -> jax.Array:
    """Per-expert ``x[e] @ w[e]``: x ``[E, C, K]``, w ``[E, K, N]`` → f32
    ``[E, C, N]``.

    The MoE expert einsums (``models/moe.py``) are batched matmuls with a
    leading expert axis; scales follow the same symmetric scheme as
    :func:`quant_matmul`, kept **per expert**: activations per ``(e, row)``
    (one hot expert's buffer rows can't poison another's resolution),
    weights per ``(e, out-channel)``.  Accumulation is int32 on the MXU
    with the dequant fused into the epilogue; the expert batch axis maps
    onto dot_general batch dims, so an ``ep``-sharded weight stack shards
    the quantized compute identically to the float path.
    """
    x32 = x.astype(jnp.float32)
    w32 = w.astype(jnp.float32)
    s_x = _symmetric_scale(x32, axis=-1)  # [E, C, 1] per expert-row
    s_w = _symmetric_scale(w32, axis=1)   # [E, 1, N] per expert-channel
    qx = jnp.round(x32 / s_x).astype(jnp.int8)
    qw = jnp.round(w32 / s_w).astype(jnp.int8)
    acc = jax.lax.dot_general(
        qx, qw,
        (((2,), (1,)), ((0,), (0,))),     # contract K, batch over E
        preferred_element_type=jnp.int32,
    )
    return acc.astype(jnp.float32) * s_x * s_w


def quant_dense_axis_last(x, kernel, bias=None, out_dtype=None):
    """DenseGeneral(axis=-1): x ``[..., K]``, kernel ``[K, *F]`` → ``[..., *F]``."""
    feat_shape = kernel.shape[1:]
    out = quant_matmul(x, kernel.reshape(kernel.shape[0], -1))
    out = out.reshape(x.shape[:-1] + feat_shape)
    if bias is not None:
        out = out + bias.astype(jnp.float32)
    return out.astype(out_dtype or x.dtype)


def quant_dense_axis_last2(x, kernel, bias=None, out_dtype=None):
    """DenseGeneral(axis=(-2,-1)): x ``[..., H, D]``, kernel ``[H, D, N]``."""
    H, D, N = kernel.shape
    out = quant_matmul(x.reshape(x.shape[:-2] + (H * D,)), kernel.reshape(H * D, N))
    if bias is not None:
        out = out + bias.astype(jnp.float32)
    return out.astype(out_dtype or x.dtype)


# ---------------------------------------------------------------------------
# int8 KV-page quantization (paged decode cache, ops/paged_attention.py)
# ---------------------------------------------------------------------------
#
# KV rows are quantized symmetrically **per (page, row)**: one f32 scale
# covers a single token's (n_kv_heads, head_dim) K or V block.  Per-row
# granularity is what makes incremental decode exact — each new token's
# row is quantized once, in isolation, when it is written, so committing
# a token never re-scales (and never perturbs) any previously-written
# row, and copy-on-write / checkpoint / pin-transfer paths can move
# pages plus their scale rows without ever recomputing anything.  The
# dequant (codes · scale) is fused into the paged-attention kernel's
# KV-load epilogue; the scale layout alongside the pool is
# ``[n_pages + 1, page_size]`` per layer, for K and V each.


def quantize_kv_page(x: jax.Array) -> Tuple[jax.Array, jax.Array]:
    """Symmetric int8 over the trailing ``(n_kv_heads, head_dim)`` axes.

    ``x [..., n_kv, D]`` → ``(codes int8 [..., n_kv, D], scale f32
    [...])`` with ``scale = max(|row|, 1e-8) / 127`` — the same scheme as
    the matmul paths above, at per-token granularity.  Round-trip
    contract (pinned by tests/test_paged_attention.py): quantizing a row
    dequantized to f32 reproduces the codes exactly (the scale
    reconstructs to within 1 ulp and ``127 · 2^-24 ≪ 0.5``); through the
    bf16 compute dtype the reconstruction error reaches ``127 · 2^-8 ≈
    0.5``, so a code can shift by at most ±1 on the first round-trip and
    the result is a fixed point of further round-trips.  The paged
    prefill's recompute-and-rescatter of a boundary page therefore
    perturbs already-written rows by ≤ 1 code once — inside the int8
    path's bounded-error budget (the byte-identity contract covers only
    the unquantized pools).
    """
    x32 = x.astype(jnp.float32)
    amax = jnp.max(jnp.abs(x32), axis=(-2, -1))
    scale = jnp.maximum(amax, 1e-8) / 127.0
    q = jnp.clip(
        jnp.round(x32 / scale[..., None, None]), -127, 127
    ).astype(jnp.int8)
    return q, scale


def dequantize_kv_page(
    q: jax.Array, scale: jax.Array, dtype=jnp.bfloat16
) -> jax.Array:
    """Inverse of :func:`quantize_kv_page`: ``codes [..., n_kv, D]`` ×
    ``scale [...]`` → ``dtype`` rows (the representation the model's
    attention math runs on everywhere else)."""
    return (q.astype(jnp.float32) * scale[..., None, None]).astype(dtype)


# ---------------------------------------------------------------------------
# Weight-only quantized parameter store (stored int8 / packed int4 weights)
# ---------------------------------------------------------------------------
#
# The dynamic path above re-derives int8 weights from a *float* param tree
# inside every forward — the bf16 tree must still exist on host and in HBM.
# For the 8B decoder that tree is ~16 GB: it does not fit one v5e chip
# (16 GB HBM), and it is twice the bytes to copy host→device.  The
# weight-only store below quantizes ONCE (on host, at load) and keeps only
# the integer codes + scales resident:
#
# * ``int8``: symmetric per-output-channel, q keeps the float kernel's
#   shape, ``scale[(1,), *feat]`` — the matmul is the existing
#   int8×int8→int32 MXU formulation with the dequant in the epilogue;
# * ``int4``: symmetric per-channel-*group* over the contracted axis
#   (default group 128; falls back to one group when the contraction dim
#   isn't divisible), two codes packed per int8 byte along axis 0
#   (element 2i → low nibble, 2i+1 → high nibble, arithmetic-shift
#   unpack), ``scale[(G,), *feat]`` — grouped int32 dots, per-group
#   dequant, summed over groups.
#
# Activations stay float at the API boundary and are dynamically
# row-quantized inside the op (same rationale as ``quant_matmul``: one
# outlier token costs only its own row).  ``QuantizedParam`` is a
# registered pytree whose scheme metadata is hashable, so quantized trees
# flow through ``jax.jit``, ``jax.eval_shape``, sharding rules
# (``parallel/sharding.py``) and donation exactly like float trees.

WQ_SCHEMES = ("int8", "int4")
WQ_DEFAULT_GROUP = 128

# (path regex, n_contract) — which param-tree leaves are weight-quantized.
# Matmul kernels only: embeddings (gathers, not matmuls), norm scales,
# biases, and the tiny classifier heads stay float.  o_proj contracts its
# leading TWO axes (DenseGeneral(axis=(-2,-1))); everything else one.
WQ_PATH_RULES: Tuple[Tuple[str, int], ...] = (
    (r".*(q_proj|k_proj|v_proj)/kernel$", 1),
    (r".*o_proj/kernel$", 2),
    (r".*(gate_proj|up_proj|down_proj)/kernel$", 1),
    (r".*ffn/(lin1|lin2)/kernel$", 1),
    (r".*lm_head/kernel$", 1),
)


@dataclasses.dataclass
class QuantizedParam:
    """A stored weight-quantized kernel: integer codes + dequant scales.

    ``q``/``scale`` are the data leaves (arrays, shardings, or
    ``ShapeDtypeStruct``s — whatever the surrounding transform carries);
    the scheme metadata is static aux data, so two params quantized the
    same way are structure-equal and jit caches on the metadata.
    """

    q: Any                      # int8 codes ([*shape] or packed [s0/2, ...])
    scale: Any                  # f32 [(1|G,), *shape[n_contract:]]
    scheme: str = "int8"        # "int8" | "int4"
    shape: Tuple[int, ...] = ()  # original float kernel shape
    n_contract: int = 1         # leading axes contracted by the matmul
    group_size: int = 0         # int4 group length over flattened K; 0=int8

    @property
    def feat_shape(self) -> Tuple[int, ...]:
        return self.shape[self.n_contract:]


jax.tree_util.register_dataclass(
    QuantizedParam,
    data_fields=["q", "scale"],
    meta_fields=["scheme", "shape", "n_contract", "group_size"],
)


def _xp(value):
    """numpy for host arrays (no accidental device_put during streaming
    load), jnp for device arrays / tracers."""
    return np if isinstance(value, np.ndarray) else jnp


def wq_group_size(K: int, group_size: int = WQ_DEFAULT_GROUP) -> int:
    """Effective int4 group: the requested size when it divides the
    flattened contraction dim, else one group spanning all of K
    (degrades to per-channel, still valid)."""
    return group_size if group_size > 0 and K % group_size == 0 else K


def quantize_array(
    w,
    scheme: str,
    n_contract: int = 1,
    group_size: int = WQ_DEFAULT_GROUP,
) -> QuantizedParam:
    """Symmetric weight-only quantization of one kernel.

    Works on numpy arrays (host streaming load), jax arrays (quantizing an
    already-materialized tree), and under ``jax.eval_shape`` (abstract
    byte-budget accounting — ``tests/test_8b_lowering.py``).
    """
    if scheme not in WQ_SCHEMES:
        raise ValueError(f"scheme must be one of {WQ_SCHEMES}, got {scheme!r}")
    xp = _xp(w)
    shape = tuple(int(s) for s in w.shape)
    K = int(math.prod(shape[:n_contract]))
    F = int(math.prod(shape[n_contract:]))
    w2 = xp.reshape(xp.asarray(w, dtype=xp.float32), (K, F))
    if scheme == "int8":
        amax = xp.max(xp.abs(w2), axis=0, keepdims=True)         # [1, F]
        scale = xp.maximum(amax, 1e-8) / 127.0
        q = xp.clip(xp.round(w2 / scale), -127, 127).astype(xp.int8)
        return QuantizedParam(
            q=q.reshape(shape),
            scale=scale.reshape((1,) + shape[n_contract:]),
            scheme="int8", shape=shape, n_contract=n_contract, group_size=0,
        )
    if shape[0] % 2:
        raise ValueError(
            f"int4 packing pairs elements along axis 0, which must be even "
            f"(kernel shape {shape})"
        )
    g = wq_group_size(K, group_size)
    G = K // g
    w3 = w2.reshape(G, g, F)
    amax = xp.max(xp.abs(w3), axis=1, keepdims=True)             # [G, 1, F]
    scale = xp.maximum(amax, 1e-8) / 7.0
    q = xp.clip(xp.round(w3 / scale), -7, 7).astype(xp.int8).reshape(shape)
    # Two codes per byte along axis 0: 2i → low nibble, 2i+1 → high.
    lo = q[0::2]
    hi = q[1::2]
    packed = xp.bitwise_or(
        xp.left_shift(hi, 4), xp.bitwise_and(lo, xp.int8(0x0F))
    ).astype(xp.int8)
    return QuantizedParam(
        q=packed,
        scale=scale.reshape((G,) + shape[n_contract:]).astype(xp.float32),
        scheme="int4", shape=shape, n_contract=n_contract, group_size=g,
    )


def _unpack_int4(packed, xp=jnp):
    """Inverse of the axis-0 nibble packing; arithmetic shifts sign-extend."""
    lo = xp.right_shift(xp.left_shift(packed, 4), 4)
    hi = xp.right_shift(packed, 4)
    stacked = xp.stack([lo, hi], axis=1)        # [s0/2, 2, ...]
    return stacked.reshape((packed.shape[0] * 2,) + tuple(packed.shape[1:]))


def dequantize_param(qp: QuantizedParam):
    """Float32 kernel of the original shape — the test oracle, and the
    definition of the 'dequant-transient' bytes the profiling breakdown
    accounts (``profiling/compile.py``)."""
    xp = _xp(qp.q)
    K = int(math.prod(qp.shape[:qp.n_contract]))
    F = int(math.prod(qp.feat_shape))
    if qp.scheme == "int8":
        w2 = qp.q.reshape(K, F).astype(xp.float32) * qp.scale.reshape(1, F)
        return w2.reshape(qp.shape)
    q = _unpack_int4(qp.q, xp).reshape(K, F)
    G = K // qp.group_size
    w3 = q.reshape(G, qp.group_size, F).astype(xp.float32)
    w3 = w3 * qp.scale.reshape(G, 1, F)
    return w3.reshape(qp.shape)


def wq_matmul(x: jax.Array, qp: QuantizedParam) -> jax.Array:
    """``x @ dequant(qp)`` with the dequant fused into the epilogue.

    x ``[..., K]`` float (K = flattened contraction dim); returns f32
    ``[..., F]``.  Activations are dynamically row-quantized to int8 so
    both schemes ride the int8×int8→int32 MXU path.
    """
    K = int(math.prod(qp.shape[:qp.n_contract]))
    F = int(math.prod(qp.feat_shape))
    x32 = x.astype(jnp.float32)
    s_x = _symmetric_scale(x32, axis=-1)                     # [..., 1]
    qx = jnp.round(x32 / s_x).astype(jnp.int8)
    if qp.scheme == "int8":
        acc = jax.lax.dot_general(
            qx, qp.q.reshape(K, F),
            (((qx.ndim - 1,), (0,)), ((), ())),
            preferred_element_type=jnp.int32,
        )
        return acc.astype(jnp.float32) * s_x * qp.scale.reshape(1, F)
    g = qp.group_size
    G = K // g
    lead = x.shape[:-1]
    qw = _unpack_int4(qp.q, jnp).reshape(G, g, F)
    qx3 = qx.reshape((-1, G, g))                             # [T, G, g]
    acc = jax.lax.dot_general(
        qx3, qw,
        (((2,), (1,)), ((1,), (0,))),                        # → [G, T, F]
        preferred_element_type=jnp.int32,
    )
    out = (acc.astype(jnp.float32) * qp.scale.reshape(G, 1, F)).sum(axis=0)
    out = out * s_x.reshape(-1, 1)
    return out.reshape(lead + (F,))


def wq_dense_axis_last(x, qp: QuantizedParam, bias=None, out_dtype=None):
    """DenseGeneral(axis=-1) over a stored-quantized kernel ``[K, *F]``."""
    out = wq_matmul(x, qp).reshape(x.shape[:-1] + qp.feat_shape)
    if bias is not None:
        out = out + bias.astype(jnp.float32)
    return out.astype(out_dtype or x.dtype)


def wq_dense_axis_last2(x, qp: QuantizedParam, bias=None, out_dtype=None):
    """DenseGeneral(axis=(-2,-1)) over a stored-quantized ``[H, D, N]``."""
    H, D = qp.shape[0], qp.shape[1]
    out = wq_matmul(x.reshape(x.shape[:-2] + (H * D,)), qp)
    if bias is not None:
        out = out + bias.astype(jnp.float32)
    return out.astype(out_dtype or x.dtype)


def wq_rule_for_path(path: str):
    """``n_contract`` when the "/"-joined tree path names a weight-quantized
    kernel, else ``None``."""
    for pattern, n_contract in WQ_PATH_RULES:
        if re.match(pattern, path):
            return n_contract
    return None


def _tree_path_str(path) -> str:
    parts = []
    for p in path:
        part = getattr(p, "key", None)
        if part is None:
            part = getattr(p, "idx", None)
        if part is None:
            part = getattr(p, "name", None)
        parts.append(str(p if part is None else part))
    return "/".join(parts)


def quantize_tree(
    params, scheme: str, group_size: int = WQ_DEFAULT_GROUP
):
    """Quantize every rule-matched kernel in a param tree.

    Leaves that match no rule pass through untouched; the result is the
    tree the WQ model modules (``models/layers.py``) expect.  Usable on
    host (numpy), on device (jnp), and under ``jax.eval_shape``.
    """
    def _leaf(path, leaf):
        n_contract = wq_rule_for_path(_tree_path_str(path))
        if n_contract is None:
            return leaf
        return quantize_array(leaf, scheme, n_contract, group_size)

    return jax.tree_util.tree_map_with_path(_leaf, params)


def _leaf_nbytes(leaf) -> int:
    return int(math.prod(leaf.shape)) * np.dtype(leaf.dtype).itemsize


def param_tree_bytes(tree) -> dict:
    """Byte accounting for a (possibly quantized) param tree.

    ``stored_bytes`` is what actually lives in HBM (codes + scales +
    untouched float leaves); ``dequant_transient_bytes`` is the LARGEST
    would-be float kernel among quantized leaves — the epilogue-fused
    matmul never materializes more than one.  Works on arrays and
    ``ShapeDtypeStruct`` trees alike (the 8B budget test is abstract).
    """
    stored = quantized = float_bytes = 0
    transient = 0
    n_q = n_f = 0
    is_qp = lambda x: isinstance(x, QuantizedParam)  # noqa: E731
    for leaf in jax.tree_util.tree_leaves(tree, is_leaf=is_qp):
        if is_qp(leaf):
            n_q += 1
            b = _leaf_nbytes(leaf.q) + _leaf_nbytes(leaf.scale)
            quantized += b
            stored += b
            transient = max(
                transient, int(math.prod(leaf.shape)) * 4
            )
        else:
            n_f += 1
            b = _leaf_nbytes(leaf)
            float_bytes += b
            stored += b
    return {
        "stored_bytes": stored,
        "quantized_bytes": quantized,
        "float_bytes": float_bytes,
        "dequant_transient_bytes": transient,
        "n_quantized_leaves": n_q,
        "n_float_leaves": n_f,
    }
