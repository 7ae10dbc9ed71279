"""The gated delta rule of Kimi Delta Attention (KDA) in three forms.

A head keeps a state ``S [d_k, d_v]`` (float32, zero at a row's start).  A
token ``t`` brings a normalised key ``k_t`` and query ``q_t`` (``[d_k]``), a
value ``v_t [d_v]``, a write strength ``beta_t`` in (0, 1) and a log-decay
a key channel ``g_t [d_k]`` in ``[lower_bound, 0)``::

    S' = Diag(exp(g_t)) S_{t-1}                  decay, a channel each
    S_t = S' + beta_t k_t (v_t - k_t^T S')^T     read k_t^T S', write the rest
    o_t = S_t^T q_t                              read with the query

* :func:`kda_recurrent` — exactly those three lines, a token a step of a
  ``lax.scan``.  The label continuations run it from the prompt's final
  state (a continuation reads the state it is given and advances its own
  copy), and it is what the other two forms are tested against.
* :func:`kda_chunked_xla` — the chunked form as XLA programs: the tokens of
  a chunk of ``CHUNK`` are solved together (below), the state steps from
  chunk to chunk in a ``lax.scan``.  The path of a prefill whose rows are
  not declared (under a mesh the kernel's call would be opaque to the
  partitioner) and the kernel's oracle in the tests.
* :func:`kda_chunked` — the same mathematics as one Pallas TPU kernel whose
  device operations carry ``_kda_`` in their names.  A head's state stays
  in VMEM across a row's chunks; state and accumulations are float32, MXU
  operands bfloat16.  It takes the compact token stream
  (``models/moe.RealPositions``: row ``b`` lies in slots ``[start_b,
  end_b)``, rows dense, nothing aligned) and, with ``start_b = b * S``, the
  padded ``[B, S]`` form: one kernel, two tables of row bounds.

The chunked form.  With ``G_i`` the running sum of ``g`` inside the chunk
(inclusive), ``S_0`` the state on entry and ``u_i = beta_i (v_i - k_i^T
S'_i)`` what token ``i`` writes::

    (I + L) U = beta (V - (K e^G) S_0),  L_ij = beta_i (k_i . k_j e^{G_i - G_j}), j < i
    O = (Q e^G) S_0 + tril(P) U,         P_ij = q_i . k_j e^{G_i - G_j}, j <= i
    S_C = Diag(e^{G_C}) S_0 + (K e^{G_C - G})^T U

``e^{G_i - G_j}`` is at most 1, but its two factors are not: a channel may
decay by ``e^{lower_bound}`` a step (``e^-5``), so ``e^{-G_j}`` alone
leaves float32 after 17 steps.  The factors are therefore taken about the
chunk's middle ``m = G_{CHUNK/2 - 1}``: ``e^{G_i - m}`` and ``e^{m - G_j}``
are each at most ``e^{5 * CHUNK/2}`` = ``e^80`` at ``CHUNK`` 32, inside
float32 (and bfloat16, whose exponent is the same), and every product that
is used (``j <= i``) is at most 1.  That is what ``kda_lower_bound`` -5 is
for, and why ``CHUNK`` is 32 and not larger.

``(I + L)^-1`` is built by blocks, never by the series ``I - L + L^2 -
...`` over the whole chunk: a lyric that repeats a word gives keys with
``k_i . k_j`` near 1, the powers of ``L`` then grow like binomial
coefficients (``3 * 10^8`` at 32) and cancel in the sum, which float32 does
not survive.  Inside 8 x 8 diagonal blocks the series is exact after three
factors and its terms stay under 70; the blocks are then merged pairwise
(``[[A, 0], [C, B]]^-1 = [[A^-1, 0], [-B^-1 C A^-1, B^-1]]``), products of
bounded matrices with nothing to cancel.  These small products run as three
bfloat16 passes (``_dot3``: both operands split in two), 16 bits.

The kernel's grid is (row, chunk of that row): a step takes the aligned
32-slot block ``start_b // 32 + c`` of the stream and ALL heads, four heads
at a time stacked on the sublanes so that every product is a whole 128 x 128
MXU tile (the four heads' ``L`` and ``P`` come out of one product as the
diagonal blocks; the rest is masked).  Slots of the block outside ``[start_b,
end_b)`` belong to a neighbour or are fillers: their ``beta`` is taken as
zero, so they write nothing, and their output rows are left as the
neighbour's step wrote them (a block that two rows share is visited by both,
one after the other: the output block stays in VMEM between the two visits).
Nothing is aligned, so nothing is copied to align it.  Steps past a row's
last block run nothing and fetch nothing (index maps clamped from the
scalar-prefetched bounds).  The running sum ``G`` is taken inside each
aligned block over ALL its slots, a neighbour's too (a triangular matrix of
ones times ``g`` split in three exact bfloat16 pieces): a shared block's two
rows read the same sums, and only differences ``G_i - G_j`` inside one row
and the sum at the row's last slot are used.

Fillers and neighbours have to be finite (they are multiplied by zero, not
skipped), and a filler's output is whatever reading the last row's state
gives: finite, and read by nobody.

Pallas is imported when a call is traced, not when this module is.
"""

from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

CHUNK = 32
_SHIFT = 5  # log2(CHUNK)
# heads a kernel step stacks on the sublanes: 4 x 32 rows = one MXU tile
_STACK = 128 // CHUNK
VMEM_LIMIT_BYTES = 64 * 1024 * 1024
F32 = jnp.float32


# ------------------------------------------------------------ token by token

def kda_recurrent(q, k, v, g, beta, state, valid=None):
    """The recurrence a token a step.  ``q, k [B, T, H, dk]``, ``v [B, T, H,
    dv]``, ``g [B, T, H, dk]`` (log-decay, <= 0), ``beta [B, T, H]``,
    ``state [B, H, dk, dv]`` float32; ``valid [B, T]`` (bool) marks the
    tokens that exist: the others leave the state as it is.  Returns ``(o
    [B, T, H, dv] float32, final state)``."""
    q, k, v, g, beta = (x.astype(F32) for x in (q, k, v, g, beta))
    if valid is not None:
        beta = jnp.where(valid[..., None], beta, 0.0)
        g = jnp.where(valid[..., None, None], g, 0.0)

    def step(s, token):
        q_t, k_t, v_t, g_t, b_t = token
        s = s * jnp.exp(g_t)[..., None]
        read = jnp.einsum("bhk,bhkv->bhv", k_t, s)
        s = s + jnp.einsum("bhk,bhv->bhkv", k_t,
                           b_t[..., None] * (v_t - read))
        return s, jnp.einsum("bhk,bhkv->bhv", q_t, s)

    with jax.default_matmul_precision("highest"):
        state, o = jax.lax.scan(
            step, state.astype(F32),
            tuple(jnp.moveaxis(x, 1, 0) for x in (q, k, v, g, beta)))
    return jnp.moveaxis(o, 0, 1), state


# ----------------------------------------------------- (I + L)^-1 by blocks

def _dot3(a, b):
    """``a @ b`` of float32 matrices to about 16 bits on bfloat16 MXU
    passes: both split in two, the low x low term dropped."""
    a_hi = a.astype(jnp.bfloat16)
    b_hi = b.astype(jnp.bfloat16)
    a_lo = (a - a_hi.astype(F32)).astype(jnp.bfloat16)
    b_lo = (b - b_hi.astype(F32)).astype(jnp.bfloat16)

    def mm(x, y):
        return jnp.matmul(x, y, preferred_element_type=F32)

    return mm(a_hi, b_hi) + (mm(a_hi, b_lo) + mm(a_lo, b_hi))


def _unit_lower_inverse(lower, row, col, dot=_dot3):
    """``(I + lower)^-1`` for ``lower [n, n]`` strictly lower triangular
    inside diagonal blocks of ``CHUNK`` (and zero elsewhere); ``row`` /
    ``col`` are the index grids (``[n, n]`` int32).  See the module's
    docstring for why by blocks."""
    eye = (row == col).astype(F32)
    same8 = (row >> 3) == (col >> 3)
    base = jnp.where(same8, lower, 0.0)
    sq = dot(base, base)
    inv = dot(dot(eye - base, eye + sq), eye + dot(sq, sq))
    width = 8
    while width < CHUNK:
        shift = width.bit_length()  # log2(2 * width)
        same = (row >> shift) == (col >> shift)
        inner = (row >> (shift - 1)) == (col >> (shift - 1))
        off = jnp.where(same & ~inner, lower, 0.0)
        inv = inv - dot(inv, dot(off, inv))
        width *= 2
    return inv


# ------------------------------------------------------ chunked, XLA programs

def kda_chunked_xla(q, k, v, g, beta, state, valid=None):
    """The chunked form as XLA programs; arguments and result as
    :func:`kda_recurrent`, ``T`` a multiple of ``CHUNK``."""
    batch, n_tok, heads, dk = q.shape
    if n_tok % CHUNK:
        raise ValueError(f"{n_tok} tokens are not whole chunks of {CHUNK}")
    q, k, v, g, beta = (x.astype(F32) for x in (q, k, v, g, beta))
    if valid is not None:
        beta = jnp.where(valid[..., None], beta, 0.0)
        g = jnp.where(valid[..., None, None], g, 0.0)
    n_chunks = n_tok // CHUNK

    def chunks(x):  # [B, T, H, ..] -> [n, B, H, CHUNK, ..]
        x = x.reshape((batch, n_chunks, CHUNK) + x.shape[2:])
        return jnp.swapaxes(jnp.moveaxis(x, 1, 0), 2, 3)

    row = jax.lax.broadcasted_iota(jnp.int32, (CHUNK, CHUNK), 0)
    col = jax.lax.broadcasted_iota(jnp.int32, (CHUNK, CHUNK), 1)
    hi = functools.partial(jnp.matmul, precision=jax.lax.Precision.HIGHEST)

    def step(s, chunk):
        q_c, k_c, v_c, g_c, b_c = chunk          # [B, H, C, ..]
        total = jnp.cumsum(g_c, axis=2)
        mid = total[:, :, CHUNK // 2 - 1:CHUNK // 2]
        k_col = k_c * jnp.exp(mid - total)
        pairs_k = hi(k_c * jnp.exp(total - mid), jnp.swapaxes(k_col, 2, 3))
        pairs_q = hi(q_c * jnp.exp(total - mid), jnp.swapaxes(k_col, 2, 3))
        lower = jnp.where(row > col, pairs_k, 0.0) * b_c[..., None]
        decayed = jnp.exp(total)
        rest = b_c[..., None] * (v_c - hi(k_c * decayed, s))
        wrote = hi(_unit_lower_inverse(lower, row, col, hi), rest)
        o = hi(q_c * decayed, s) + hi(jnp.where(row >= col, pairs_q, 0.0),
                                      wrote)
        last = total[:, :, -1:]
        s = s * jnp.swapaxes(jnp.exp(last), 2, 3) + hi(
            jnp.swapaxes(k_c * jnp.exp(last - total), 2, 3), wrote)
        return s, o

    state, o = jax.lax.scan(
        step, state.astype(F32),
        tuple(chunks(x) for x in (q, k, v, g)) + (chunks(beta),))
    o = jnp.moveaxis(jnp.moveaxis(o, 2, 3), 0, 1)   # [B, n, C, H, dv]
    return o.reshape(batch, n_tok, heads, -1), state


# ------------------------------------------------------------- Pallas kernel

def _block_of(start, end, step):
    """The stream block a (row, step) of the grid holds, whether it runs,
    and the row's first block."""
    first = start // CHUNK
    last = jnp.maximum(end - 1, 0) // CHUNK
    return (jnp.minimum(first + step, last),
            (end > start) & (first + step <= last), first)


def _kda_kernel(starts_ref, ends_ref, q_ref, k_ref, v_ref, g_ref, beta_ref,
                o_ref, s_ref, *, n_heads: int, dk: int, dv: int,
                normalize: bool, out_norm_eps):
    from jax.experimental import pallas as pl

    b, step = pl.program_id(0), pl.program_id(1)
    start, end = starts_ref[b], ends_ref[b]
    block, live, _ = _block_of(start, end, step)
    stack = min(_STACK, n_heads)
    rows = stack * CHUNK

    @pl.when(step == 0)
    def _empty_state():
        s_ref[...] = jnp.zeros_like(s_ref)

    @pl.when(live)
    def _chunk():
        slot = block * CHUNK + jax.lax.broadcasted_iota(
            jnp.int32, (CHUNK, 1), 0)
        own = (slot >= start) & (slot < end)                       # [C, 1]
        # the first step to touch this output block writes every row of it
        fresh = (step > 0) | (start % CHUNK == 0)
        # G at the row's last slot inside this block
        at_last = slot == jnp.minimum(end, (block + 1) * CHUNK) - 1
        row = jax.lax.broadcasted_iota(jnp.int32, (rows, rows), 0)
        col = jax.lax.broadcasted_iota(jnp.int32, (rows, rows), 1)
        same_head = (row >> _SHIFT) == (col >> _SHIFT)
        strict = same_head & (row > col)
        causal = same_head & (row >= col)
        ones_below = (
            jax.lax.broadcasted_iota(jnp.int32, (CHUNK, CHUNK), 0)
            >= jax.lax.broadcasted_iota(jnp.int32, (CHUNK, CHUNK), 1)
        ).astype(jnp.bfloat16)

        def lanes(head, width):
            at = head * width
            if not isinstance(at, int) and width % 128 == 0:
                at = pl.multiple_of(at, 128)
            return pl.ds(at, width)

        def group(index, carry):
            heads = [index * stack + i for i in range(stack)]
            kb_rows, q_rows, k_cols, k_ends, reads, rests, end_sums = (
                [] for _ in range(7))
            # the running sum of the log-decay inside the block, the four
            # heads at once: a lower-triangular matrix of ones times ``g``
            # in three exact bfloat16 pieces (24 bits), float32 sums
            g_all = g_ref[:, lanes(index, stack * dk)]
            sums = jnp.zeros_like(g_all)
            for _ in range(3):
                piece = g_all.astype(jnp.bfloat16)
                sums = sums + jnp.dot(ones_below, piece,
                                      preferred_element_type=F32)
                g_all = g_all - piece.astype(F32)
            for i, head in enumerate(heads):
                at = lanes(head, dk)
                total = sums[:, i * dk:(i + 1) * dk]               # [C, dk]
                mid = total[CHUNK // 2 - 1:CHUNK // 2]
                end_sum = jnp.sum(jnp.where(at_last, total, 0.0), axis=0,
                                  keepdims=True)
                end_sums.append(end_sum)
                k_h = k_ref[:, at].astype(F32)
                q_h = q_ref[:, at].astype(F32)
                if normalize:
                    k_h = k_h * jax.lax.rsqrt(
                        jnp.sum(k_h * k_h, axis=1, keepdims=True) + 1e-6)
                    q_h = q_h * (jax.lax.rsqrt(
                        jnp.sum(q_h * q_h, axis=1, keepdims=True) + 1e-6)
                        * dk ** -0.5)
                # a slot outside the row writes nothing: beta as zero
                beta_h = jnp.where(own, beta_ref[:, at].astype(F32), 0.0)
                kb_h = k_h * beta_h
                vb_h = v_ref[:, lanes(head, dv)].astype(F32) * (
                    beta_h if dv == dk else beta_h[:, :1])
                about = jnp.exp(total - mid)
                decayed = jnp.exp(total)
                state_t = s_ref[0, head].astype(jnp.bfloat16)     # [dv, dk]
                nt = (((1,), (1,)), ((), ()))
                both = jnp.concatenate(
                    [kb_h * decayed, q_h * decayed]).astype(jnp.bfloat16)
                from_state = jax.lax.dot_general(
                    both, state_t, nt, preferred_element_type=F32)
                rests.append(vb_h - from_state[:CHUNK])
                reads.append(from_state[CHUNK:])
                kb_rows.append(kb_h * about)
                q_rows.append(q_h * about)
                k_cols.append(k_h * jnp.exp(mid - total))
                # own slots lie at or before the row's last: never above 0
                k_ends.append(k_h * jnp.exp(jnp.minimum(end_sum - total, 0.0)))
            stacked = lambda parts: jnp.concatenate(parts, axis=0)  # noqa: E731
            pairs = jax.lax.dot_general(
                stacked(kb_rows + q_rows).astype(jnp.bfloat16),
                stacked(k_cols).astype(jnp.bfloat16),
                (((1,), (1,)), ((), ())), preferred_element_type=F32)
            lower = jnp.where(strict, pairs[:rows], 0.0)
            reach = jnp.where(causal, pairs[rows:], 0.0)
            inverse = _unit_lower_inverse(lower, row, col)
            wrote = jnp.dot(inverse.astype(jnp.bfloat16),
                            stacked(rests).astype(jnp.bfloat16),
                            preferred_element_type=F32)           # [rows, dv]
            out = stacked(reads) + jnp.dot(
                reach.astype(jnp.bfloat16), wrote.astype(jnp.bfloat16),
                preferred_element_type=F32)
            wrote_t = wrote.T.astype(jnp.bfloat16)                # [dv, rows]
            k_end_rows = stacked(k_ends)
            head_of_row = jax.lax.broadcasted_iota(
                jnp.int32, (rows, 1), 0) >> _SHIFT
            for i, head in enumerate(heads):
                at = lanes(head, dv)
                mine = out[i * CHUNK:(i + 1) * CHUNK]
                if out_norm_eps is not None:
                    mine = mine * jax.lax.rsqrt(
                        jnp.mean(mine * mine, axis=1, keepdims=True)
                        + out_norm_eps)
                kept = jnp.where(fresh, 0.0, o_ref[:, at].astype(F32))
                o_ref[:, at] = jnp.where(own, mine, kept).astype(o_ref.dtype)
                s_ref[0, head] = s_ref[0, head] * jnp.exp(end_sums[i]) + jnp.dot(
                    wrote_t,
                    jnp.where(head_of_row == i, k_end_rows,
                              0.0).astype(jnp.bfloat16),
                    preferred_element_type=F32)
            return carry

        jax.lax.fori_loop(0, n_heads // stack, group, 0)


@functools.partial(jax.jit, static_argnames=(
    "n_heads", "max_len", "normalize", "out_norm_eps", "interpret"))
def _kda_chunk_call(q, k, v, g, beta, starts, ends, n_heads: int,
                    max_len: int, normalize: bool, out_norm_eps,
                    interpret: bool):
    """The ``pallas_call`` under one inner ``jit`` (every KDA layer of a
    forward shares one trace and one Mosaic lowering); its name is what a
    device trace finds the kernel by."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    slots = q.shape[0]
    dk = q.shape[1] // n_heads
    dv = v.shape[1] // n_heads
    n_rows = starts.shape[0]

    def token_map(b, step, starts, ends):
        return (_block_of(starts[b], ends[b], step)[0], 0)

    def spec(width):
        return pl.BlockSpec((CHUNK, width), token_map,
                            memory_space=pltpu.VMEM)

    return pl.pallas_call(
        functools.partial(_kda_kernel, n_heads=n_heads, dk=dk, dv=dv,
                          normalize=normalize, out_norm_eps=out_norm_eps),
        out_shape=(
            jax.ShapeDtypeStruct((slots, n_heads * dv), q.dtype),
            jax.ShapeDtypeStruct((n_rows, n_heads, dv, dk), F32),
        ),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            # a row of max_len slots that starts inside a block ends
            # max_len / CHUNK blocks on
            grid=(n_rows, max_len // CHUNK + 1),
            in_specs=[spec(n_heads * dk), spec(n_heads * dk),
                      spec(n_heads * dv), spec(n_heads * dk),
                      spec(n_heads * dk)],
            out_specs=(
                spec(n_heads * dv),
                pl.BlockSpec((1, n_heads, dv, dk),
                             lambda b, step, starts, ends: (b, 0, 0, 0),
                             memory_space=pltpu.VMEM),
            ),
        ),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
            vmem_limit_bytes=VMEM_LIMIT_BYTES,
        ),
        interpret=interpret,
    )(starts, ends, q, k, v, g, beta)


def kda_chunk_admits(n_slots: int, n_heads: int) -> bool:
    """Whether the kernel takes a stream of ``n_slots``: whole blocks, and
    heads that stack to whole MXU tiles (or fewer heads than a stack)."""
    return n_slots % CHUNK == 0 and (
        n_heads % _STACK == 0 or n_heads < _STACK)


def kda_chunked(q, k, v, g, beta, starts, ends, valid, n_heads: int,
                max_len: int, normalize: bool = False,
                out_norm_eps: Optional[float] = None,
                interpret: Optional[bool] = None,
                ) -> Tuple[jax.Array, jax.Array]:
    """The recurrence over a token stream whose row ``b`` lies in slots
    ``[starts[b], ends[b])`` (``ends - starts <= max_len``; rows in order,
    not overlapping), every row from a zero state.  Heads side by side on
    the last axis: ``q, k, g [N, H*dk]`` (``g`` float32), ``v [N, H*dv]``,
    ``beta [N, H]``; ``valid [N]`` (bool) = the slot lies in a row.  Returns
    ``(o [N, H*dv]`` in ``q``'s dtype, zero off the rows' slots, ``states
    [B, H, dk, dv]`` float32, each row's after its last slot``)``.  Every
    operand has to be finite on every slot.  Only for streams
    :func:`kda_chunk_admits`.

    What a KDA layer does a head at a time on either side of the recurrence
    can ride in the kernel, where a head is a lane tile already (as XLA
    programs on ``[N, H, d]`` they cost a re-tiling copy of 0.4 GB each at
    24,576 slots): ``normalize`` = ``q`` and ``k`` arrive unnormalised and
    are divided by their norm over a head's channels (``x * rsqrt(sum x^2 +
    1e-6)``), ``q`` scaled by ``dk^-0.5`` besides; ``out_norm_eps`` = each
    head's output leaves RMS-normalised over its channels (no scale)."""
    from music_analyst_tpu.ops.flash_attention import interpret_default

    n_slots = q.shape[0]
    if not kda_chunk_admits(n_slots, n_heads):
        raise ValueError(
            f"{n_slots} slots x {n_heads} heads are outside the kernel's "
            "regime (kda_chunk_admits)")
    if interpret is None:
        interpret = interpret_default()
    dk = q.shape[1] // n_heads
    # beta at every lane of its head (one term a product: exact)
    spread = jnp.repeat(jnp.eye(n_heads, dtype=q.dtype), dk, axis=1)
    o, state_t = _kda_chunk_call(
        q, k.astype(q.dtype), v.astype(q.dtype), g.astype(F32),
        beta.astype(q.dtype) @ spread, starts.astype(jnp.int32),
        ends.astype(jnp.int32), n_heads=n_heads, max_len=int(max_len),
        normalize=bool(normalize),
        out_norm_eps=None if out_norm_eps is None else float(out_norm_eps),
        interpret=interpret)
    # blocks no row reaches are never written
    o = jnp.where(valid[:, None], o, jnp.zeros((), o.dtype))
    return o, jnp.swapaxes(state_t, 2, 3)
