"""Mamba-1: a selective state-space mixer whose memory is a fixed-size
recurrent state, a matrix a row, not a cache that grows with the tokens.

Per token ``u [D]`` (inner width ``I``, state size ``N``, step rank ``R``;
Mamba, arXiv:2312.00752, with Jamba's three norms, as ``transformers``'
``JambaMambaMixer`` orders it):

1. ``[x | z] = u W_in`` (``I | I``), no bias.
2. ``x <- SiLU(conv(x) + b_conv)``: a depthwise causal convolution over
   time, kernel ``conv_kernel`` (4), with bias.  Its state is the last
   ``conv_kernel - 1`` inputs, before the convolution.
3. ``[r | B | C] = x W_x`` (``R | N | N``), no bias; ``r``, ``B`` and ``C``
   each RMS-normed with a weight of its own (Jamba's ``dt_layernorm``,
   ``b_layernorm``, ``c_layernorm``).
4. The recurrence on the row's state ``S [I, N]`` (float32, zero at a
   row's start), with ``delta = softplus(r W_dt + b_dt) [I]`` and ``A =
   -exp(A_log) [I, N]``: a decay a channel and entry a token, and ``y = S C
   + D x``.  ``ops/selective_scan.py`` has the equations and their two
   forms; ``r W_dt`` is computed there, so ``delta`` never leaves the
   kernel.
5. ``out = (y * SiLU(z)) W_out``: no norm on the gated output.

No position enters: the recurrence orders the tokens.

What a call is decides the form of step 4, as in ``models/mamba2.py``:

* a prefill on the compact token stream (``prefill_lengths`` declared and
  ``packed`` saying ``x [1, C, D]`` is the real positions' set,
  ``models/llama.LlamaModel``: a row starts at its own slot, state and
  convolution restart there) runs the Pallas kernel
  (``selective_scan_chunked``);
* every other call runs token by token (``selective_scan_recurrent``): the
  label continuations and ``generate``, from the prompt's final state; a
  declared prefill on ``[B, S]`` rows (below the stream's kernel widths) or
  under a mesh, which gives ``row_lengths`` so that padding leaves the
  state alone, from zeros.

A prefill from a zero state sows what its recurrence reads (``x``, ``r``,
``B`` and ``C``, under ``intermediates`` / ``scan_inputs``, with each row's
slots) for a caller that asks (``models/llama.score_labels_program``'s
probe: a reference reruns the first layer's recurrence on them).

Projections run in the layer's dtype (bfloat16); the step, its softplus,
the decays, the state, ``A_log``, ``D``, ``b_dt`` and the three small norms'
weights are float32.

:class:`SelectiveState` is what a row carries from call to call.  A call
returns a new one and leaves the one it read as it was, so three label
continuations fork one prompt state.
"""

from __future__ import annotations

import dataclasses
from typing import Optional

import jax
import jax.numpy as jnp
from flax import linen as nn

from music_analyst_tpu.models.kda import (
    _conv_init,
    _dt_bias_init,
    causal_conv,
    conv_tails,
)
from music_analyst_tpu.models.layers import fan_in_normal
from music_analyst_tpu.models.mamba2 import _conv_bias_init
from music_analyst_tpu.ops.selective_scan import (
    selective_scan_chunked,
    selective_scan_recurrent,
)
from music_analyst_tpu.profiling.compile import (
    note_attention_path,
    note_traced_path,
)


@dataclasses.dataclass
class SelectiveState:
    """Per-layer Mamba-1 state of a batch of rows: ``state [B, I, N]``
    float32 (after each row's last token) and ``conv [B, K-1, I]``, the last
    ``K - 1`` pre-convolution inputs ``x`` of each row (zeros before a row's
    start)."""

    state: jax.Array
    conv: jax.Array

    @classmethod
    def zeros(cls, batch: int, inner: int, d_state: int,
              conv_kernel: int = 4, dtype=jnp.bfloat16) -> "SelectiveState":
        return cls(
            state=jnp.zeros((batch, inner, d_state), jnp.float32),
            conv=jnp.zeros((batch, conv_kernel - 1, inner), dtype),
        )

    def with_length(self, length) -> "SelectiveState":
        """A state has no write offset: what the caches' callers set on
        every layer's cache leaves it as it is."""
        return self


jax.tree_util.register_dataclass(
    SelectiveState, data_fields=["state", "conv"], meta_fields=[])


def _a_log_init(key, shape, dtype):
    """Mamba-1's published ``A_log = log [1 .. N]``, every channel alike."""
    del key
    return jnp.broadcast_to(
        jnp.log(jnp.arange(1, shape[1] + 1, dtype=jnp.float32)), shape
    ).astype(dtype)


def _rms(v, weight, eps: float):
    v = v.astype(jnp.float32)
    return v * jax.lax.rsqrt(jnp.mean(v * v, -1, keepdims=True) + eps) * weight


class Mamba1Mixer(nn.Module):
    """The Mamba-1 mixer.  Returns ``out`` without a state, ``(out,
    new_state)`` with one, as ``Mamba2Mixer`` does."""

    inner: int
    d_state: int
    dt_rank: int
    conv_kernel: int = 4
    norm_eps: float = 1e-6
    dtype: jnp.dtype = jnp.bfloat16
    param_dtype: jnp.dtype = jnp.float32

    @nn.compact
    def __call__(self, x, positions=None,
                 state: Optional[SelectiveState] = None,
                 prefill_lengths: Optional[jax.Array] = None,
                 row_lengths: Optional[jax.Array] = None,
                 packed=None):
        """``prefill_lengths``, ``row_lengths`` and ``packed`` mean what
        they mean to ``models/kda.KimiDeltaAttention``."""
        dim = x.shape[-1]
        inner, n, rank = self.inner, self.d_state, self.dt_rank
        x = x.astype(self.dtype)

        def matrix(name, features, fan_in=dim):
            return self.param(name, fan_in_normal(fan_in),
                              (fan_in, features), self.param_dtype
                              ).astype(self.dtype)

        def small(name, init, shape):
            return self.param(name, init, shape, jnp.float32)

        with jax.named_scope("mamba1.proj"):
            both = x @ matrix("in_proj", 2 * inner)
            before, gate_in = both[..., :inner], both[..., inner:]
        conv_w = self.param("conv", _conv_init, (self.conv_kernel, inner),
                            self.param_dtype)
        conv_b = self.param("conv_bias", _conv_bias_init, (inner,),
                            self.param_dtype)
        w_x = matrix("x_proj", rank + 2 * n, inner)
        w_dt = matrix("dt_proj", inner, rank)
        dt_bias = small("dt_bias", _dt_bias_init, (inner,))
        a_log = small("A_log", _a_log_init, (inner, n))
        skip = small("D", nn.initializers.ones, (inner,))
        norms = [small(name, nn.initializers.ones, (width,))
                 for name, width in (("dt_norm", rank), ("b_norm", n),
                                     ("c_norm", n))]
        w_out = matrix("out_proj", dim, inner)

        declared = prefill_lengths is not None
        lengths = prefill_lengths if declared else row_lengths
        batch, n_tok = x.shape[:2]
        rows = batch if packed is None else packed.real.shape[0]
        if declared or state is None:
            history = None
            start_state = jnp.zeros((rows, inner, n), jnp.float32)
        else:
            history, start_state = state.conv, state.state

        kernel = declared and packed is not None
        with jax.named_scope("mamba1.conv"):
            xs = nn.silu(causal_conv(
                before, conv_w, history,
                positions if packed is not None else None)
                + conv_b.astype(jnp.float32)).astype(self.dtype)
        with jax.named_scope("mamba1.xproj"):
            chosen = jnp.dot(xs, w_x, preferred_element_type=jnp.float32)
            low, b, c = (
                _rms(part, weight, self.norm_eps).astype(self.dtype)
                for part, weight in zip(
                    (chosen[..., :rank], chosen[..., rank:rank + n],
                     chosen[..., rank + n:]), norms))

        valid = None
        if lengths is not None and not kernel:
            valid = (jnp.arange(n_tok)[None, :]
                     < lengths.astype(jnp.int32)[:, None])
        if history is None:
            flat = lambda v: v.reshape(batch * n_tok, -1)  # noqa: E731
            if kernel:
                slot, real = packed.slot, packed.real
            else:
                slot = jnp.arange(batch * n_tok).reshape(batch, n_tok)
                real = (jnp.ones((batch, n_tok), bool) if valid is None
                        else valid)
            self.sow("intermediates", "scan_inputs", dict(
                x=flat(xs), r=flat(low), b=flat(b), c=flat(c), slot=slot,
                real=real))

        with jax.named_scope("mamba1.scan"):
            if kernel:
                note_attention_path("selective_scan_chunked")
                note_traced_path("mamba1.compact")
                starts = packed.start
                y, new_state = selective_scan_chunked(
                    xs[0], low[0], w_dt, dt_bias, a_log, b[0], c[0], skip,
                    starts, starts + prefill_lengths.astype(jnp.int32),
                    packed.valid, packed.real.shape[1])
                y = y[None]
            else:
                note_attention_path("selective_scan_recurrent")
                y, new_state = selective_scan_recurrent(
                    xs, low, w_dt, dt_bias, a_log, b, c, skip, start_state,
                    valid)

        with jax.named_scope("mamba1.gate"):
            y = y.astype(jnp.float32) * nn.silu(gate_in.astype(jnp.float32))
        with jax.named_scope("mamba1.out"):
            out = y.astype(self.dtype) @ w_out
        if state is None:
            return out
        return out, SelectiveState(new_state, conv_tails(
            [before], history, lengths, packed, self.conv_kernel - 1))
