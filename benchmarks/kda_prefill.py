"""The KDA prefill kernel (``ops/kda_attention.py``) against its two other
forms, at the hybrid cell's step.

64 rows x 1,024 slots x 32 heads of 128 | 128, bfloat16 operands, float32
states, with the prompt lengths of the benchmark's own corpus (as
``benchmarks/mla_prefill.py`` draws them): ``kernel_ms`` is one call of the
Pallas kernel on the rows laid one behind the other in
``models/moe.compact_capacity`` slots (what a compact prefill's KDA layer
calls), ``kernel_padded_ms`` the same rows at ``[B, S]`` (a row a 1,024-slot
stretch), ``xla_ms`` the chunked XLA form on the padded rows.  The errors
are against the token-by-token recurrence in float32 on the same bfloat16
operands, at log-decays drawn down to the lower bound of -5 a step (and, in
``max_abs_error_repeated_keys``, with every key of a row nearly the same: the
case the block-wise inverse exists for).  ``tflops`` counts ``6 * dk * dv`` a
token a head, the recurrence's own operations (``perfbench/flops_ling.py``).

    chiprun -- python3 bench.py --suite=kda_prefill
"""

from __future__ import annotations

import numpy as np

from benchmarks import suite
from benchmarks._util import device_info, smoke, timed


def _operands(key, rows, seq, heads, dim, repeated=False):
    import jax
    import jax.numpy as jnp

    ks = jax.random.split(key, 5)
    shape = (rows, seq, heads, dim)
    q = jax.random.normal(ks[0], shape)
    k = jax.random.normal(ks[1], shape)
    if repeated:
        k = k[:, :1] + 0.01 * k
    q = q / jnp.linalg.norm(q, axis=-1, keepdims=True) * dim ** -0.5
    k = k / jnp.linalg.norm(k, axis=-1, keepdims=True)
    v = jax.random.normal(ks[2], shape)
    # log-decays over the whole range: a third of the channels near 0, a
    # third near the lower bound
    g = -5.0 * jax.nn.sigmoid(4.0 * jax.random.normal(ks[3], shape))
    beta = jax.nn.sigmoid(jax.random.normal(ks[4], shape[:3])
                          + (2.0 if repeated else 0.0))
    bf = jnp.bfloat16
    return q.astype(bf), k.astype(bf), v.astype(bf), g, beta


@suite("kda_prefill")
def run() -> dict:
    import jax
    import jax.numpy as jnp

    from benchmarks.mla_prefill import _prompt_lengths
    from music_analyst_tpu.models.moe import RealPositions, compact_capacity
    from music_analyst_tpu.ops.kda_attention import (
        kda_chunked,
        kda_chunked_xla,
        kda_recurrent,
    )

    if smoke():
        rows, seq, heads, dim = 4, 128, 4, 16
        lens = np.asarray([100, 41, 128, 3])
    else:
        rows, seq, heads, dim = 64, 1024, 32, 128
        lens = _prompt_lengths(seq)[:rows]
    lens_d = jnp.asarray(lens, jnp.int32)
    valid = jnp.arange(seq)[None, :] < lens_d[:, None]
    capacity = compact_capacity(int(lens.sum()), rows * seq)
    packed = RealPositions.of(lens_d, seq, capacity)

    def flat(x):
        return x.reshape(rows * seq, -1)

    @jax.jit
    def kernel_padded(q, k, v, g, beta):
        starts = jnp.arange(rows, dtype=jnp.int32) * seq
        return kda_chunked(flat(q), flat(k), flat(v), flat(g), flat(beta),
                           starts, starts + lens_d, valid.reshape(-1), heads,
                           seq)

    def gather(q, k, v, g, beta):
        keep = packed.valid[:, None]
        return (packed.gather(q.reshape(rows, seq, -1)),
                packed.gather(k.reshape(rows, seq, -1)),
                packed.gather(v.reshape(rows, seq, -1)),
                jnp.where(keep, packed.gather(g.reshape(rows, seq, -1)), 0.0),
                jnp.where(keep, packed.gather(beta), 0.0))

    @jax.jit
    def kernel(q, k, v, g, beta):
        return kda_chunked(q, k, v, g, beta, packed.start,
                           packed.start + lens_d, packed.valid, heads, seq)

    zeros = jnp.zeros((rows, heads, dim, dim), jnp.float32)
    xla = jax.jit(lambda *a: kda_chunked_xla(*a, zeros, valid))
    exact = jax.jit(lambda *a: kda_recurrent(*a, zeros, valid))

    def errors(operands):
        want_o, want_s = exact(*operands)
        got_o, got_s = kernel(*gather(*operands))
        got_o = packed.put_back(got_o.astype(jnp.float32)).reshape(
            want_o.shape)
        pad_o, pad_s = kernel_padded(*operands)
        pad_o = pad_o.astype(jnp.float32).reshape(want_o.shape)
        real = valid[..., None, None]
        return {
            "o": float(jnp.abs(jnp.where(real, got_o - want_o, 0)).max()),
            "state": float(jnp.abs(got_s - want_s).max()),
            "o_padded": float(
                jnp.abs(jnp.where(real, pad_o - want_o, 0)).max()),
            "state_padded": float(jnp.abs(pad_s - want_s).max()),
            "o_scale": float(jnp.abs(want_o).max()),
            "state_scale": float(jnp.abs(want_s).max()),
            "finite": bool(jnp.isfinite(got_o).all()
                           & jnp.isfinite(got_s).all()),
        }

    operands = _operands(jax.random.key(0), rows, seq, heads, dim)
    repeated = _operands(jax.random.key(1), rows, seq, heads, dim, True)

    def ms(fn, args):
        def go():
            out = fn(*args)
            return out[0].reshape(-1)[:8] + out[1].reshape(-1)[:8]
        go()
        return timed(go)[0] * 1e3

    gathered = gather(*operands)
    kernel_ms = ms(kernel, gathered)
    tokens = int(lens.sum())
    return {
        "suite": "kda_prefill",
        "smoke": smoke(),
        "device": device_info(),
        "shape": {"rows": rows, "slots_a_row": seq, "heads": heads,
                  "widths": f"{dim}|{dim}", "capacity": capacity,
                  "tokens": tokens},
        "kernel_ms": round(kernel_ms, 3),
        "kernel_padded_ms": round(ms(kernel_padded, operands), 3),
        "xla_ms": round(ms(xla, operands), 3),
        "kernel_tflops": round(
            tokens * heads * 6 * dim * dim / kernel_ms / 1e9, 3),
        "errors": errors(operands),
        "errors_repeated_keys": errors(repeated),
    }
