"""Kimi Delta Attention (KDA): a linear-attention mixer whose memory is a
fixed-size recurrent state, not a cache that grows with the tokens.

Per token ``x [D]`` (``H`` heads of ``d`` key and value channels; Kimi
Linear, arXiv:2510.26692):

1. ``q~, k~, v~ = W_q x, W_k x, W_v x``, each ``[H*d]``.
2. ``q, k, v = SiLU(conv(.))``: a depthwise causal convolution over time,
   kernel ``conv_kernel`` (4), no bias.  Its state is the last
   ``conv_kernel - 1`` inputs of each, before the convolution.
3. ``q, k`` L2-normalised over each head's ``d`` (``x * rsqrt(sum x^2 +
   1e-6)``), ``q`` scaled by ``d^-0.5``.
4. ``beta = sigmoid(W_b x) [H]``.
5. The log-decay, a channel each: ``g = lower_bound * sigmoid(exp(A_log_h)
   * (W_f x + dt_bias))`` in ``(lower_bound, 0)``; ``A_log [H]``, ``dt_bias
   [H*d]`` float32.
6. The gated delta rule on the head's state ``S [d, d]`` (float32, zero at
   a row's start): ``ops/kda_attention.py`` has the equations and their
   three forms.
7. ``o <- RMSNorm_d(o) * w_norm * sigmoid(W_g x)`` (a norm a head, the gate
   elementwise), ``y = W_o o``.

No position enters: the recurrence orders the tokens, there is no RoPE.

What a call is decides the form of step 6, as in ``models/mla.py``:

* a prefill that declares its rows (``prefill_lengths``: from position 0, a
  zero state, one device) runs the Pallas kernel (``kda_chunked``) — on the
  compact token stream where ``packed`` says ``x [1, C, D]`` is one
  (``models/llama.LlamaModel``: a row starts at its own slot, state and
  convolution restart there), else on the ``[B, S]`` rows;
* every other call continues from the ``state`` it is given (zeros without
  one), token by token (``kda_recurrent``: the label continuations, eight
  positions from the prompt's final state) or, for whole chunks of more than
  one, by the chunked XLA form (``kda_chunked_xla``: a prefill under a mesh,
  which gives ``row_lengths`` so that padding leaves the state alone).

:class:`RecurrentState` is what a row carries from call to call.  A call
returns a new one and leaves the one it read as it was, so three label
continuations fork one prompt state.
"""

from __future__ import annotations

import dataclasses
import math
from typing import Optional

import jax
import jax.numpy as jnp
from flax import linen as nn

from music_analyst_tpu.models.layers import fan_in_normal
from music_analyst_tpu.ops.kda_attention import (
    CHUNK,
    kda_chunk_admits,
    kda_chunked,
    kda_chunked_xla,
    kda_recurrent,
)
from music_analyst_tpu.profiling.compile import (
    note_attention_path,
    note_traced_path,
)


@dataclasses.dataclass
class RecurrentState:
    """Per-layer KDA state of a batch of rows: ``state [B, H, dk, dv]``
    float32 (after each row's last token) and ``conv [B, K-1, 3*H*d]``, the
    last ``K - 1`` pre-convolution inputs ``[q~ | k~ | v~]`` of each row
    (zeros before a row's start)."""

    state: jax.Array
    conv: jax.Array

    @classmethod
    def zeros(cls, batch: int, n_heads: int, head_dim: int,
              conv_kernel: int = 4, dtype=jnp.bfloat16) -> "RecurrentState":
        return cls(
            state=jnp.zeros((batch, n_heads, head_dim, head_dim),
                            jnp.float32),
            conv=jnp.zeros((batch, conv_kernel - 1, 3 * n_heads * head_dim),
                           dtype),
        )

    def with_length(self, length) -> "RecurrentState":
        """A state has no write offset: what the caches' callers set on
        every layer's cache leaves it as it is."""
        return self


jax.tree_util.register_dataclass(
    RecurrentState, data_fields=["state", "conv"], meta_fields=[])


def _log_uniform(low: float, high: float):
    def init(key, shape, dtype):
        return jnp.exp(jax.random.uniform(
            key, shape, jnp.float32, math.log(low), math.log(high))
        ).astype(dtype)

    return init


def _a_log_init(key, shape, dtype):
    """``A_log = log U(1, 16)``."""
    return jnp.log(jax.random.uniform(key, shape, jnp.float32, 1.0, 16.0)
                   ).astype(dtype)


def _dt_bias_init(key, shape, dtype):
    """``dt = exp U(log 1e-3, log 1e-1)``, stored as its inverse softplus."""
    dt = _log_uniform(1e-3, 1e-1)(key, shape, jnp.float32)
    return (dt + jnp.log(-jnp.expm1(-dt))).astype(dtype)


def _conv_init(key, shape, dtype):
    """A depthwise ``Conv1d``'s default: ``U(-1/sqrt(K), 1/sqrt(K))``."""
    bound = shape[0] ** -0.5
    return jax.random.uniform(key, shape, jnp.float32, -bound, bound
                              ).astype(dtype)


def causal_conv(u, weight, history=None, positions=None):
    """Depthwise causal convolution over time: ``y_t = sum_i weight[i] *
    u_{t - (K-1) + i}``.  ``u [B, T, W]``, ``weight [K, W]``.  ``history
    [B, K-1, W]`` holds the inputs before ``u``'s first (zeros without);
    with ``positions [B, T]`` (a token's index in its own row, on a stream
    that lays rows one behind the other) an input from before the token's
    row start counts as zero.  Float32 result."""
    taps = weight.shape[0]
    n_tok = u.shape[1]
    out = 0.0
    for i in range(taps):
        back = taps - 1 - i                       # how far behind ``t``
        # ``u`` moved ``back`` tokens on, its own head cut off
        if not back:
            moved = u
        elif positions is not None and history is None:
            # Rows of the stream taken by index, not a slice behind a pad:
            # fed straight into the kernel's call, XLA's slice-and-pad
            # fusion (jax 0.9.0's TPU compiler) came out wrong for the rows
            # that straddle a multiple of 1,024 slots of a 24,576-slot
            # stream (states 5-28% off in exactly those rows, right as a
            # program output: my chip runs, PR 33).  What lies before the
            # stream's first slot is masked below (its positions are 0-2).
            moved = jnp.take(
                u, jnp.maximum(jnp.arange(n_tok) - back, 0), axis=1)
        else:
            ahead = (history[:, i:] if history is not None
                     else jnp.zeros((u.shape[0], back, u.shape[2]), u.dtype))
            moved = jnp.concatenate(
                [ahead.astype(u.dtype), u[:, :n_tok - back]], axis=1
            )[:, :n_tok]
        term = moved.astype(jnp.float32) * weight[i].astype(jnp.float32)
        if positions is not None and back:
            term = jnp.where((positions >= back)[..., None], term, 0.0)
        out = out + term
    return out


def conv_tails(before, history, lengths, packed, keep: int):
    """The last ``keep`` pre-convolution inputs of every row after a call's
    tokens, the streams of ``before`` (a list of ``[B, T, W]``, or ``[1, C,
    W]`` on the compact stream ``packed``) side by side on the last axis;
    ``history [B, keep, sum W]`` holds what came before the call (zeros
    without), ``lengths [B]`` how many of the call's tokens exist a row
    (all of them without)."""
    back = jnp.arange(keep, dtype=jnp.int32)
    if packed is not None:
        lens = lengths.astype(jnp.int32)
        offset = lens[:, None] - keep + back[None, :]       # [B, K-1]
        slot = jnp.clip(packed.start[:, None] + offset, 0,
                        before[0].shape[1] - 1)
        tails = jnp.concatenate([u[0][slot] for u in before], axis=-1)
        return jnp.where((offset >= 0)[..., None], tails,
                         jnp.zeros((), tails.dtype))
    batch, n_tok = before[0].shape[:2]
    count = (jnp.full((batch,), n_tok, jnp.int32) if lengths is None
             else lengths.astype(jnp.int32))
    at = (count[:, None] + back[None, :])[..., None]
    width = before[0].shape[2]
    tails = []
    for i, u in enumerate(before):
        past = (jnp.zeros((batch, keep, width), u.dtype)
                if history is None
                else history[..., i * width:(i + 1) * width
                             ].astype(u.dtype))
        tails.append(jnp.take_along_axis(
            jnp.concatenate([past, u], axis=1), at, axis=1))
    return jnp.concatenate(tails, axis=-1)


class KimiDeltaAttention(nn.Module):
    """The KDA mixer.  Returns ``out`` without a state, ``(out, new_state)``
    with one, as ``MLAttention`` does with its cache."""

    n_heads: int
    head_dim: int
    conv_kernel: int = 4
    lower_bound: float = -5.0
    norm_eps: float = 1e-6
    dtype: jnp.dtype = jnp.bfloat16
    param_dtype: jnp.dtype = jnp.float32

    @nn.compact
    def __call__(self, x, positions=None,
                 state: Optional[RecurrentState] = None,
                 prefill_lengths: Optional[jax.Array] = None,
                 row_lengths: Optional[jax.Array] = None,
                 packed=None):
        """``prefill_lengths [B]`` is ``MLAttention``'s promise (a prefill
        from position 0 on one device, every row from a zero state whatever
        ``state`` holds) and lets the kernel run; ``row_lengths [B]`` only
        says how many of this call's tokens exist a row (the rest leave the
        state alone) and promises nothing.  ``packed`` (a
        ``models/moe.RealPositions`` of ``prefill_lengths``) says ``x [1,
        C, D]`` and ``positions [1, C]`` are the compact token stream."""
        dim = x.shape[-1]
        heads, d = self.n_heads, self.head_dim
        width = heads * d
        x = x.astype(self.dtype)

        def matrix(name, features, fan_in=dim):
            return self.param(name, fan_in_normal(fan_in),
                              (fan_in, features), self.param_dtype
                              ).astype(self.dtype)

        with jax.named_scope("kda.proj"):
            before = [x @ matrix(f"{n}_proj", width) for n in "qkv"]
        conv_w = [self.param(f"{n}_conv", _conv_init,
                             (self.conv_kernel, width), self.param_dtype)
                  for n in "qkv"]
        a_log = self.param("A_log", _a_log_init, (heads,), jnp.float32)
        dt_bias = self.param("dt_bias", _dt_bias_init, (width,), jnp.float32)
        w_f, w_b = matrix("f_proj", width), matrix("b_proj", heads)
        w_g, w_o = matrix("g_proj", width), matrix("o_proj", dim, width)
        norm_scale = self.param("o_norm", nn.initializers.ones, (d,),
                                jnp.float32)

        declared = prefill_lengths is not None
        lengths = prefill_lengths if declared else row_lengths
        batch, n_tok = x.shape[:2]
        rows = batch if packed is None else packed.real.shape[0]
        if declared or state is None:
            history = None
            start_state = jnp.zeros((rows, heads, d, d), jnp.float32)
        else:
            history, start_state = state.conv, state.state

        kernel = declared and (packed is not None or (
            n_tok % CHUNK == 0 and kda_chunk_admits(batch * n_tok, heads)))
        with jax.named_scope("kda.conv"):
            # one of q, k, v at a time, and never as [.., H, d] where the
            # kernel runs (it takes a head's norms itself): the three
            # together in float32 are 1.2 GB at 24,576 slots
            q, k, v = (nn.silu(causal_conv(
                u, w, None if history is None
                else history[..., i * width:(i + 1) * width],
                positions if packed is not None else None))
                for i, (u, w) in enumerate(zip(before, conv_w)))
        with jax.named_scope("kda.gate"):
            beta = jax.nn.sigmoid(jnp.dot(
                x, w_b, preferred_element_type=jnp.float32))
            decay_in = jnp.dot(x, w_f, preferred_element_type=jnp.float32)
            g = self.lower_bound * jax.nn.sigmoid(
                jnp.repeat(jnp.exp(a_log), d) * (decay_in + dt_bias))

        with jax.named_scope("kda.chunk"):
            if kernel:
                note_attention_path("kda_chunked")
                lens = prefill_lengths.astype(jnp.int32)
                if packed is not None:
                    note_traced_path("kda.compact")
                    starts, valid, max_len = (
                        packed.start, packed.valid, packed.real.shape[1])
                else:
                    starts = jnp.arange(batch, dtype=jnp.int32) * n_tok
                    valid = (jnp.arange(n_tok)[None, :]
                             < lens[:, None]).reshape(-1)
                    max_len = n_tok
                flat = lambda a: a.reshape(batch * n_tok, -1)  # noqa: E731
                o, new_state = kda_chunked(
                    flat(q).astype(self.dtype), flat(k).astype(self.dtype),
                    flat(v).astype(self.dtype), flat(g), flat(beta),
                    starts, starts + lens, valid, heads, max_len,
                    normalize=True, out_norm_eps=self.norm_eps)
                o = o.reshape(batch, n_tok, width).astype(jnp.float32)
            else:
                valid = None
                if lengths is not None:
                    valid = (jnp.arange(n_tok)[None, :]
                             < lengths.astype(jnp.int32)[:, None])
                # a continuation of up to a chunk runs a token a step
                if n_tok <= CHUNK or n_tok % CHUNK:
                    note_attention_path("kda_recurrent")
                    form = kda_recurrent
                else:
                    note_attention_path("kda_chunked_xla")
                    form = kda_chunked_xla
                by_head = lambda a: a.reshape(  # noqa: E731
                    batch, n_tok, heads, d)
                q, k = by_head(q), by_head(k)
                q = q * jax.lax.rsqrt(
                    jnp.sum(q * q, -1, keepdims=True) + 1e-6) * d ** -0.5
                k = k * jax.lax.rsqrt(
                    jnp.sum(k * k, -1, keepdims=True) + 1e-6)
                o, new_state = form(q, k, by_head(v), by_head(g), beta,
                                    start_state, valid)
                o = o * jax.lax.rsqrt(
                    jnp.mean(o * o, -1, keepdims=True) + self.norm_eps)
                o = o.reshape(batch, n_tok, width)

        with jax.named_scope("kda.out"):
            gate = jax.nn.sigmoid((x @ w_g).astype(jnp.float32))
            o = o * jnp.tile(norm_scale, heads) * gate
            out = o.astype(self.dtype) @ w_o
        if state is None:
            return out
        return out, RecurrentState(
            new_state, conv_tails(before, history, lengths, packed,
                                  self.conv_kernel - 1))
