"""The Mamba-2 prefill kernel (``ops/ssd_scan.py``) against its two other
forms, at the state-space cell's step.

32 rows x 1,024 slots x 128 heads of 64, state 128, one group, bfloat16
operands, float32 states, with the prompt lengths of the benchmark's own
corpus (as ``benchmarks/mla_prefill.py`` draws them): ``kernel_ms`` is one
call of the Pallas kernel on the rows laid one behind the other in
``models/compact_stream.compact_capacity`` slots (what a compact prefill's
Mamba-2 layer calls), at each chunk length of ``chunks``
(``ops/ssd_scan.CHUNK`` is the program's); ``kernel_padded_ms`` the same
rows at ``[B, S]`` (a row a 1,024-slot stretch), ``xla_ms`` the chunked
XLA form on four of the padded rows (its ``[chunk, chunk, heads]`` decays
are 0.5 GB a row block in float32).  The errors are against the
token-by-token recurrence in float32 on the same bfloat16 operands, with
steps and decays drawn as the assumed initialisation gives them (``delta``
in ``[1e-3, 1e-1]``, ``A`` in ``[-16, -1]``) and, in ``errors_fast_decay``,
at ``delta A = -8`` a step on every head.  ``tflops`` counts
``perfbench/flops_granite.ssd_flops`` a token.

    chiprun -- python3 bench.py --suite=ssd_prefill
"""

from __future__ import annotations

import numpy as np

from benchmarks import suite
from benchmarks._util import device_info, smoke, timed


def _operands(key, rows, seq, heads, dim, n_state, fast=False):
    import jax
    import jax.numpy as jnp

    ks = jax.random.split(key, 5)
    x = jax.random.normal(ks[0], (rows, seq, heads * dim))
    b = jax.random.normal(ks[1], (rows, seq, n_state))
    c = jax.random.normal(ks[2], (rows, seq, n_state))
    if fast:
        dt = jnp.full((rows, seq, heads), 0.5)
        a = jnp.full((heads,), -16.0)
    else:
        dt = jnp.exp(jax.random.uniform(
            ks[3], (rows, seq, heads), minval=np.log(1e-3),
            maxval=np.log(1e-1)))
        a = -jax.random.uniform(ks[4], (heads,), minval=1.0, maxval=16.0)
    bf = jnp.bfloat16
    return x.astype(bf), dt, a, b.astype(bf), c.astype(bf)


@suite("ssd_prefill")
def run() -> dict:
    import jax
    import jax.numpy as jnp

    from benchmarks.mla_prefill import _prompt_lengths
    from music_analyst_tpu.models.compact_stream import (
        RealPositions,
        compact_capacity,
    )
    from music_analyst_tpu.ops.ssd_scan import (
        CHUNK,
        ssd_chunked,
        ssd_chunked_xla,
        ssd_recurrent,
    )

    if smoke():
        rows, seq, heads, dim, n_state = 4, 256, 8, 16, 32
        lens = np.asarray([200, 41, 256, 3])
        chunks = (CHUNK,)
    else:
        rows, seq, heads, dim, n_state = 32, 1024, 128, 64, 128
        lens = _prompt_lengths(seq)[:rows]
        chunks = (128, 256)
    few = min(rows, 4)
    lens_d = jnp.asarray(lens, jnp.int32)
    valid = jnp.arange(seq)[None, :] < lens_d[:, None]
    capacity = compact_capacity(int(lens.sum()), rows, seq)
    packed = RealPositions.of(lens_d, seq, capacity)

    def flat(v):
        return v.reshape(rows * seq, -1)

    def by_head(x):
        return x.reshape(x.shape[:2] + (heads, dim))

    @jax.jit
    def kernel_padded(x, dt, a, b, c):
        starts = jnp.arange(rows, dtype=jnp.int32) * seq
        return ssd_chunked(flat(x), flat(dt), a, flat(b), flat(c), starts,
                           starts + lens_d, valid.reshape(-1), heads, seq)

    def gather(x, dt, a, b, c):
        return (packed.gather(x), packed.gather(dt), a, packed.gather(b),
                packed.gather(c))

    def kernel_at(chunk):
        return jax.jit(lambda x, dt, a, b, c: ssd_chunked(
            x, dt, a, b, c, packed.start, packed.start + lens_d,
            packed.valid, heads, seq, chunk=chunk))

    kernel = kernel_at(CHUNK)
    zeros = jnp.zeros((rows, heads, dim, n_state), jnp.float32)
    xla = jax.jit(lambda x, dt, a, b, c: ssd_chunked_xla(
        by_head(x[:few]), dt[:few], a, b[:few], c[:few], zeros[:few],
        valid[:few]))
    exact = jax.jit(lambda x, dt, a, b, c: ssd_recurrent(
        by_head(x), dt, a, b, c, zeros, valid))

    def errors(operands):
        want_y, want_s = exact(*operands)
        got_y, got_s = kernel(*gather(*operands))
        got_y = by_head(packed.put_back(got_y.astype(jnp.float32)))
        pad_y, pad_s = kernel_padded(*operands)
        pad_y = by_head(pad_y.astype(jnp.float32).reshape(rows, seq, -1))
        xla_y, xla_s = xla(*operands)
        real = valid[..., None, None]
        return {
            "y": float(jnp.abs(jnp.where(real, got_y - want_y, 0)).max()),
            "state": float(jnp.abs(got_s - want_s).max()),
            "y_padded": float(
                jnp.abs(jnp.where(real, pad_y - want_y, 0)).max()),
            "state_padded": float(jnp.abs(pad_s - want_s).max()),
            "y_xla": float(jnp.abs(jnp.where(
                real[:few], xla_y - want_y[:few], 0)).max()),
            "state_xla": float(jnp.abs(xla_s - want_s[:few]).max()),
            "y_scale": float(jnp.abs(want_y).max()),
            "state_scale": float(jnp.abs(want_s).max()),
            "finite": bool(jnp.isfinite(got_y).all()
                           & jnp.isfinite(got_s).all()),
        }

    operands = _operands(jax.random.key(0), rows, seq, heads, dim, n_state)
    fast = _operands(jax.random.key(1), rows, seq, heads, dim, n_state, True)

    def ms(fn, args):
        def go():
            out = fn(*args)
            return out[0].reshape(-1)[:8] + out[1].reshape(-1)[:8]
        go()
        return timed(go)[0] * 1e3

    gathered = gather(*operands)
    kernel_ms = {str(chunk): round(ms(kernel_at(chunk), gathered), 3)
                 for chunk in chunks}
    tokens = int(lens.sum())
    # perfbench/flops_granite.ssd_flops at the published chunk of 256
    a_token = (heads * (2 * dim * 128.5 + 4 * n_state * dim)
               + 2 * n_state * 128.5)
    return {
        "suite": "ssd_prefill",
        "smoke": smoke(),
        "device": device_info(),
        "shape": {"rows": rows, "slots_a_row": seq, "heads": heads,
                  "head_dim": dim, "state": n_state, "capacity": capacity,
                  "tokens": tokens, "chunk": CHUNK},
        "kernel_ms": kernel_ms,
        "kernel_padded_ms": round(ms(kernel_padded, operands), 3),
        "xla_ms_of_4_rows": round(ms(xla, operands), 3),
        "kernel_tflops": round(
            tokens * a_token / kernel_ms[str(CHUNK)] / 1e9, 3),
        "errors": errors(operands),
        "errors_fast_decay": errors(fast),
    }
