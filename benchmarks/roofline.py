"""Chip roofline: bf16/int8 matmul throughput + host→device bandwidth.

Backs the "Chip roofline" table in PERFORMANCE.md.  Three measurements:

* bf16 matmul chain — ``k`` dependent ``[M, 768] × [768, 3072] × [3072,
  768]`` pairs inside one jit, reduced to a scalar on device; TFLOP/s is
  the practical MXU ceiling every model forward is judged against.
* int8 matmul chain — same shapes with int8 operands and int32
  accumulation (requantize between steps).
* host→device transfer — ``device_put`` of 2 MB batches, the bandwidth
  every batch's wire bytes are judged against.
"""

from __future__ import annotations

import functools

from benchmarks import suite
from benchmarks._util import device_info, smoke, timed


@suite("roofline")
def run() -> dict:
    import time

    import jax
    import jax.numpy as jnp
    import numpy as np

    M = 4096 if smoke() else 1 << 19
    K, N = 768, 3072
    steps = 2 if smoke() else 8

    @functools.partial(jax.jit, static_argnames=("n_steps",))
    def bf16_chain(x, w1, w2, n_steps):
        def body(x, _):
            return jnp.tanh(x @ w1) @ w2, None

        out, _ = jax.lax.scan(body, x, None, length=n_steps)
        return jnp.sum(out.astype(jnp.float32))

    key = jax.random.key(0)
    x = jax.random.normal(key, (M, K), jnp.bfloat16)
    w1 = jax.random.normal(key, (K, N), jnp.bfloat16)
    w2 = jax.random.normal(key, (N, K), jnp.bfloat16)
    bf16_chain(x, w1, w2, steps)  # compile
    bf16_s, _ = timed(lambda: bf16_chain(x, w1, w2, steps))
    flops = 2 * M * K * N * 2 * steps  # 2 matmuls per step
    bf16_tflops = flops / bf16_s / 1e12

    @functools.partial(jax.jit, static_argnames=("n_steps",))
    def int8_chain(x, w1, w2, n_steps):
        def body(x, _):
            acc = jax.lax.dot(
                x, w1, preferred_element_type=jnp.int32
            )
            # crude requant back to int8 range
            q = (acc >> 8).astype(jnp.int8)
            acc2 = jax.lax.dot(q, w2, preferred_element_type=jnp.int32)
            return (acc2 >> 8).astype(jnp.int8), None

        out, _ = jax.lax.scan(body, x, None, length=n_steps)
        return jnp.sum(out.astype(jnp.int32))

    rng = np.random.default_rng(0)
    xi = jnp.asarray(rng.integers(-127, 127, (M, K)), jnp.int8)
    w1i = jnp.asarray(rng.integers(-127, 127, (K, N)), jnp.int8)
    w2i = jnp.asarray(rng.integers(-127, 127, (N, K)), jnp.int8)
    int8_chain(xi, w1i, w2i, steps)
    int8_s, _ = timed(lambda: int8_chain(xi, w1i, w2i, steps))
    int8_tops = flops / int8_s / 1e12

    # Host→device: 4 × 2 MB int8 batches, timed with a device-side touch.
    chunk = np.zeros((4, 1 << 21), dtype=np.int8)
    touch = jax.jit(lambda t: t.reshape(-1)[::1 << 20].sum())
    start = time.perf_counter()
    for row in chunk:
        np.asarray(touch(jax.device_put(row)))
    h2d_s = time.perf_counter() - start
    h2d_mbps = chunk.nbytes / (1 << 20) / h2d_s

    return {
        "suite": "roofline",
        **device_info(),
        "smoke": smoke(),
        "matmul_shapes": f"[{M},{K}]x[{K},{N}]x[{N},{K}] x{steps} steps",
        "bf16_tflops": round(bf16_tflops, 1),
        "bf16_seconds": round(bf16_s, 4),
        "int8_tops": round(int8_tops, 1),
        "int8_over_bf16": round(int8_tops / bf16_tflops, 3),
        "host_to_device_mb_per_s": round(h2d_mbps, 1),
    }
