"""The routed experts' grouped matmuls (``ops/grouped_matmul.py``) against
XLA's own (``jax.lax.ragged_dot``, three calls and the activation between)
at each expert cell's prefill shape, against megablox's ``gmm`` (three
calls, the contraction whole in a block as the kernel's) at the decoder
cell's, and, at the decoder cell's widths, at fewer rows an expert down to
the diffusion block loop's 8: where the kernel overtakes ``ragged_dot``.

A shape is ``A`` assignment rows over ``E`` held experts of ``D x H``, of
which ``real`` belong to a group (the others: fillers, and assignments to
experts another chip holds); group sizes are a multinomial draw over
experts whose shares vary as a routed layer's load does
(``expert_load_max_over_mean`` about 2).  ``*_ms`` is one layer's three
projections (gate, up, down) and the SwiGLU between; ``tflops`` counts the
real rows' ``6 * real * D * H``.  ``err`` is the largest difference of the
kernel's result from ``ragged_dot``'s on the grouped rows, against the
largest value there; ``finite`` covers every row.

    python3 bench.py --suite=grouped_matmul      # on a host with a TPU
"""

from __future__ import annotations

import time

import numpy as np

from benchmarks import suite
from benchmarks._util import device_info, smoke

# name: (rows A, held experts E, D, H, real rows)
CELL_SHAPES = {
    # 12,288 slots x 6; about 10,300 real tokens
    "llm_sentiment_releases": (73728, 128, 2048, 768, 61800),
    # 12,288 slots x 8
    "diffusion_sentiment_releases": (98304, 128, 2048, 768, 82400),
    # a stretch of 8,192 slots x 8; a quarter of the experts held
    "hybrid_sentiment_releases": (65536, 128, 2560, 768, 14000),
    # 12,288 slots x 10; half the experts held
    "ssm_sentiment_releases": (122880, 36, 4096, 768, 51500),
    "window_sentiment_long_lyrics": (122880, 128, 3072, 1024, 55000),
}
# the decoder cell's widths at fewer rows an expert (A / E)
CROSSOVER = (8, 64, 128, 256, 512)
# megablox's tiles at the decoder cell's (gate and up, down): 512 rows, the
# whole contraction, the kernel's n-blocks
MEGABLOX_TILES = ((512, 2048, 384), (512, 768, 1024))


def _operands(key, rows, experts, dim, hidden, real):
    import jax
    import jax.numpy as jnp

    ks = jax.random.split(key, 5)
    share = np.asarray(jax.nn.softmax(
        0.6 * jax.random.normal(ks[0], (experts,))), np.float64)
    sizes = np.random.default_rng(0).multinomial(real, share / share.sum())
    bf = jnp.bfloat16
    x = jax.random.normal(ks[1], (rows, dim), bf)
    gate, up, down = (
        (0.03 * jax.random.normal(k, shape)).astype(bf)
        for k, shape in zip(ks[2:], ((experts, dim, hidden),) * 2
                            + ((experts, hidden, dim),)))
    return x, gate, up, down, jnp.asarray(sizes, jnp.int32)


def forms(tiles=None, megablox_tiles=None):
    """``{name: fn(x, gate, up, down, sizes) -> [A, D]}``: ``ragged_dot``,
    the kernel (``tiles``: ``(swiglu, down)`` tile pairs, ``None`` =
    ``kernel_tiles``'), and megablox at ``megablox_tiles`` (``(tm, tk, tn)``
    of each projection) where given."""
    import jax
    import jax.numpy as jnp

    from music_analyst_tpu.ops.grouped_matmul import (
        grouped_matmul,
        grouped_swiglu,
    )

    def ragged(x, gate, up, down, sizes):
        g = jax.lax.ragged_dot(x, gate, sizes)
        u = jax.lax.ragged_dot(x, up, sizes)
        return jax.lax.ragged_dot(jax.nn.silu(g) * u, down, sizes)

    swiglu_tiles, down_tiles = tiles or (None, None)

    def kernel(x, gate, up, down, sizes):
        h = grouped_swiglu(x, gate, up, sizes, tiles=swiglu_tiles)
        return grouped_matmul(h, down, sizes, tiles=down_tiles)

    out = {"ragged_dot": jax.jit(ragged), "kernel": jax.jit(kernel)}
    if megablox_tiles:
        from jax.experimental.pallas.ops.tpu.megablox import gmm

        up_tiles, down_m = megablox_tiles

        def megablox(x, gate, up, down, sizes):
            g = gmm(x, gate, sizes, preferred_element_type=jnp.bfloat16,
                    tiling=up_tiles)
            u = gmm(x, up, sizes, preferred_element_type=jnp.bfloat16,
                    tiling=up_tiles)
            return gmm(jax.nn.silu(g) * u, down, sizes,
                       preferred_element_type=jnp.bfloat16, tiling=down_m)

        out["megablox"] = jax.jit(megablox)
    return out


def device_ms(fn, args, calls: int = 10) -> float:
    """Milliseconds a call, ``calls`` dispatched back to back after a
    warm-up and waited for once (the device is the bound)."""
    fn(*args).block_until_ready()
    start = time.perf_counter()
    for _ in range(calls):
        out = fn(*args)
    out.block_until_ready()
    return (time.perf_counter() - start) / calls * 1e3


def measure(shape, fns, key=None, calls: int = 10) -> dict:
    """One shape's row of the table: each form's time and the kernel's
    error against ``ragged_dot``."""
    import jax
    import jax.numpy as jnp

    rows, experts, dim, hidden, real = shape
    args = _operands(key if key is not None else jax.random.key(0),
                     rows, experts, dim, hidden, real)
    row = {"shape": {"rows": rows, "experts": experts, "dim": dim,
                     "hidden": hidden, "real": real}}
    flops = 6 * real * dim * hidden
    want = fns["ragged_dot"](*args).astype(jnp.float32)[:real]
    for name, fn in fns.items():
        ms = device_ms(fn, args, calls)
        row[f"{name}_ms"] = round(ms, 4)
        row[f"{name}_tflops"] = round(flops / ms / 1e9, 2)
        if name != "ragged_dot":
            got = fn(*args).astype(jnp.float32)
            row[f"{name}_err"] = float(
                jnp.abs(got[:real] - want).max() / jnp.abs(want).max())
            row[f"{name}_finite"] = bool(jnp.isfinite(got).all())
    return row


@suite("grouped_matmul")
def run() -> dict:
    import jax

    from music_analyst_tpu.ops.grouped_matmul import kernel_tiles

    if smoke():
        shapes = {"tiny": (512, 4, 128, 128, 400)}
        crossover, calls = (), 1
    else:
        shapes, crossover, calls = CELL_SHAPES, CROSSOVER, 10
    table = {}
    for name, shape in shapes.items():
        rows, experts, dim, hidden, _ = shape
        tiles = megablox = None
        if smoke():
            tiles = ((128, 128), (128, 128))
        elif name == "llm_sentiment_releases":
            megablox = MEGABLOX_TILES
        table[name] = measure(shape, forms(tiles, megablox), calls=calls)
        table[name]["tiles"] = [
            kernel_tiles(rows, experts, dim, hidden, 2),
            kernel_tiles(rows, experts, hidden, dim, 1)]
    for per_expert in crossover:
        rows, experts, dim, hidden = 128 * per_expert, 128, 2048, 768
        # the kernel at the row tile that these rows allow
        tile = min(512, max(128, per_expert))
        fns = forms(((tile, 384), (tile, 1024)))
        table[f"rows_an_expert_{per_expert}"] = measure(
            (rows, experts, dim, hidden, rows), fns, calls=calls)
    return {
        "suite": "grouped_matmul",
        "smoke": smoke(),
        "device": device_info(),
        "jax": jax.__version__,
        "table": table,
    }
