"""Mamba-2: a state-space mixer whose memory is a fixed-size recurrent
state, not a cache that grows with the tokens.

Per token ``u [D]`` (``H`` heads of ``P`` channels, inner width ``I = H *
P``, state size ``N``, one group of ``B`` and ``C`` for all heads; SSD,
arXiv:2405.21060, as ``transformers``' ``GraniteMoeHybridMambaLayer`` orders
it):

1. ``[z | xBC] = W_in u`` (``I | I + 2N``) and ``dt = W_dt u [H]``, no
   bias.  The source stores one matrix ``[z | xBC | dt]``; here the ``dt``
   columns are a parameter of their own so that its product leaves in
   float32 (a checkpoint's matrix splits at column ``2I + 2N``).
2. ``xBC <- SiLU(conv(xBC) + b_conv)``: a depthwise causal convolution over
   time, kernel ``conv_kernel`` (4), with bias.  Its state is the last
   ``conv_kernel - 1`` inputs, before the convolution.  Split ``x [H, P]``,
   ``B [N]``, ``C [N]``.
3. ``delta = softplus(dt + dt_bias) [H]`` (no clamp), ``A = -exp(A_log)
   [H]``: one scalar decay ``exp(delta A)`` a head a token.
4. The recurrence on the head's state ``S [P, N]`` (float32, zero at a
   row's start) and ``y = S C + D x``: ``ops/ssd_scan.py`` has the equations
   and their three forms.
5. ``y <- RMSNorm_I(y * SiLU(z)) * w`` (the gate first, then one norm over
   all ``I`` channels), ``out = W_out y``.

No position enters: the recurrence orders the tokens.

What a call is decides the form of step 4, as in ``models/kda.py``:

* a prefill that declares its rows (``prefill_lengths``: from position 0, a
  zero state, one device) runs the Pallas kernel (``ssd_chunked``) — on the
  compact token stream where ``packed`` says ``x [1, C, D]`` is one
  (``models/llama.LlamaModel``: a row starts at its own slot, state and
  convolution restart there), else on the ``[B, S]`` rows;
* every other call continues from the ``state`` it is given (zeros without
  one), token by token (``ssd_recurrent``: the label continuations and
  ``generate``, from the prompt's final state) or, for whole chunks of more
  than one, by the chunked XLA form (``ssd_chunked_xla``: a prefill under a
  mesh, which gives ``row_lengths`` so that padding leaves the state alone).

:class:`SSMState` is what a row carries from call to call.  A call returns a
new one and leaves the one it read as it was, so three label continuations
fork one prompt state.
"""

from __future__ import annotations

import dataclasses
from typing import Optional

import jax
import jax.numpy as jnp
from flax import linen as nn

from music_analyst_tpu.models.kda import (
    _a_log_init,
    _conv_init,
    _dt_bias_init,
    causal_conv,
    conv_tails,
)
from music_analyst_tpu.models.layers import fan_in_normal
from music_analyst_tpu.ops.ssd_scan import (
    CHUNK,
    ssd_chunk_admits,
    ssd_chunked,
    ssd_chunked_xla,
    ssd_recurrent,
)
from music_analyst_tpu.profiling.compile import (
    note_attention_path,
    note_traced_path,
)


@dataclasses.dataclass
class SSMState:
    """Per-layer Mamba-2 state of a batch of rows: ``state [B, H, P, N]``
    float32 (after each row's last token) and ``conv [B, K-1, I + 2N]``, the
    last ``K - 1`` pre-convolution inputs ``[x | B | C]`` of each row (zeros
    before a row's start)."""

    state: jax.Array
    conv: jax.Array

    @classmethod
    def zeros(cls, batch: int, n_heads: int, head_dim: int, d_state: int,
              conv_kernel: int = 4, dtype=jnp.bfloat16) -> "SSMState":
        return cls(
            state=jnp.zeros((batch, n_heads, head_dim, d_state),
                            jnp.float32),
            conv=jnp.zeros(
                (batch, conv_kernel - 1, n_heads * head_dim + 2 * d_state),
                dtype),
        )

    def with_length(self, length) -> "SSMState":
        """A state has no write offset: what the caches' callers set on
        every layer's cache leaves it as it is."""
        return self


jax.tree_util.register_dataclass(
    SSMState, data_fields=["state", "conv"], meta_fields=[])


def _conv_bias_init(key, shape, dtype):
    return (0.01 * jax.random.normal(key, shape, jnp.float32)).astype(dtype)


class Mamba2Mixer(nn.Module):
    """The Mamba-2 mixer.  Returns ``out`` without a state, ``(out,
    new_state)`` with one, as ``KimiDeltaAttention`` does."""

    n_heads: int
    head_dim: int
    d_state: int
    conv_kernel: int = 4
    norm_eps: float = 1e-5
    dtype: jnp.dtype = jnp.bfloat16
    param_dtype: jnp.dtype = jnp.float32

    @nn.compact
    def __call__(self, x, positions=None,
                 state: Optional[SSMState] = None,
                 prefill_lengths: Optional[jax.Array] = None,
                 row_lengths: Optional[jax.Array] = None,
                 packed=None):
        """``prefill_lengths``, ``row_lengths`` and ``packed`` mean what
        they mean to ``models/kda.KimiDeltaAttention``."""
        dim = x.shape[-1]
        heads, p, n = self.n_heads, self.head_dim, self.d_state
        inner = heads * p
        x = x.astype(self.dtype)

        def matrix(name, features, fan_in=dim):
            return self.param(name, fan_in_normal(fan_in),
                              (fan_in, features), self.param_dtype
                              ).astype(self.dtype)

        with jax.named_scope("mamba.proj"):
            both = x @ matrix("in_proj", 2 * inner + 2 * n)
            gate_in, before = both[..., :inner], both[..., inner:]
            dt = jnp.dot(x, matrix("dt_proj", heads),
                         preferred_element_type=jnp.float32)
        conv_w = self.param("conv", _conv_init,
                            (self.conv_kernel, inner + 2 * n),
                            self.param_dtype)
        conv_b = self.param("conv_bias", _conv_bias_init, (inner + 2 * n,),
                            self.param_dtype)
        a = -jnp.exp(self.param("A_log", _a_log_init, (heads,), jnp.float32))
        dt_bias = self.param("dt_bias", _dt_bias_init, (heads,), jnp.float32)
        skip = self.param("D", nn.initializers.ones, (heads,), jnp.float32)
        norm_scale = self.param("norm", nn.initializers.ones, (inner,),
                                jnp.float32)
        w_out = matrix("out_proj", dim, inner)

        declared = prefill_lengths is not None
        lengths = prefill_lengths if declared else row_lengths
        batch, n_tok = x.shape[:2]
        rows = batch if packed is None else packed.real.shape[0]
        if declared or state is None:
            history = None
            start_state = jnp.zeros((rows, heads, p, n), jnp.float32)
        else:
            history, start_state = state.conv, state.state

        kernel = declared and (packed is not None or (
            n_tok % CHUNK == 0
            and ssd_chunk_admits(batch * n_tok, heads, p)))
        with jax.named_scope("mamba.conv"):
            # (left in the stream's type: the float32 sum of the taps is
            # 0.4 GB a layer at 12,288 slots if it outlives its fusion)
            mixed = nn.silu(causal_conv(
                before, conv_w, history,
                positions if packed is not None else None)
                + conv_b.astype(jnp.float32)).astype(self.dtype)
            xs, b, c = (mixed[..., :inner], mixed[..., inner:inner + n],
                        mixed[..., inner + n:])
            delta = jax.nn.softplus(dt + dt_bias)

        with jax.named_scope("mamba.scan"):
            if kernel:
                note_attention_path("ssd_chunked")
                lens = prefill_lengths.astype(jnp.int32)
                if packed is not None:
                    note_traced_path("ssm.compact")
                    starts, valid, max_len = (
                        packed.start, packed.valid, packed.real.shape[1])
                else:
                    starts = jnp.arange(batch, dtype=jnp.int32) * n_tok
                    valid = (jnp.arange(n_tok)[None, :]
                             < lens[:, None]).reshape(-1)
                    max_len = n_tok
                flat = lambda v: v.reshape(batch * n_tok, -1)  # noqa: E731
                y, new_state = ssd_chunked(
                    flat(xs), flat(delta), a, flat(b), flat(c), starts,
                    starts + lens, valid, heads, max_len)
                y = y.reshape(batch, n_tok, inner).astype(jnp.float32)
            else:
                valid = None
                if lengths is not None:
                    valid = (jnp.arange(n_tok)[None, :]
                             < lengths.astype(jnp.int32)[:, None])
                # a continuation of up to a chunk runs a token a step
                if n_tok <= CHUNK or n_tok % CHUNK:
                    note_attention_path("ssd_recurrent")
                    form = ssd_recurrent
                else:
                    note_attention_path("ssd_chunked_xla")
                    form = ssd_chunked_xla
                y, new_state = form(
                    xs.reshape(batch, n_tok, heads, p), delta, a, b, c,
                    start_state, valid)
                y = y.reshape(batch, n_tok, inner)

        with jax.named_scope("mamba.gate"):
            y = y + jnp.repeat(skip, p) * xs.astype(jnp.float32)
            y = y * nn.silu(gate_in.astype(jnp.float32))
            y = y * jax.lax.rsqrt(
                jnp.mean(y * y, -1, keepdims=True) + self.norm_eps
            ) * norm_scale
        with jax.named_scope("mamba.out"):
            out = y.astype(self.dtype) @ w_out
        if state is None:
            return out
        return out, SSMState(new_state, conv_tails(
            [before], history, lengths, packed, self.conv_kernel - 1))
