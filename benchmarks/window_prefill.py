"""The flash kernel with and without a sliding window (``ops/
flash_attention.py`` through the cache's causal view, ``ops/kv_cache.
BlockCausalPrefill``) against the masked XLA form, at the window cell's step.

32 rows x 1,024 positions, 8 key/value heads of 128, 72 query heads (a
sliding layer's; ``heads`` also times 48, a full layer's), bfloat16, with the
prompt lengths of a long-lyrics corpus (a row about 770 tokens; as
``benchmarks/mla_prefill.py`` draws them where the harness's corpus is at
hand): ``kernel_ms`` is one call of the view's ``attend`` with the window of
512 and ``causal_ms`` without one (what a full layer calls), each at the
tile the view takes at this width; ``kernel_ms_at_tile`` the windowed call at
other tiles (a smaller tile lets more key tiles lie wholly behind the
window: ``visited_share`` is the pairs in the tiles that ran over all of the
step's pairs, ``real_share`` the pairs inside the mask over the same);
``xla_ms_of_4_rows`` the masked XLA form on four rows.  The errors are of the
windowed kernel against the masked form in float32 on the same bfloat16
operands, on the real positions.

    chiprun -- python3 bench.py --suite=window_prefill
"""

from __future__ import annotations

import numpy as np

from benchmarks import suite
from benchmarks._util import device_info, smoke, timed


@suite("window_prefill")
def run() -> dict:
    import jax
    import jax.numpy as jnp

    from music_analyst_tpu.ops.flash_attention import (
        flash_attention,
        visited_pairs,
    )
    from music_analyst_tpu.ops.kv_cache import (
        BlockCausalPrefill,
        KVCache,
        block_causal_tile,
    )

    if smoke():
        rows, seq, kv_heads, dim, window = 4, 256, 2, 16, 64
        heads_of = {"sliding": 6}
        lens = np.asarray([256, 41, 200, 3])
        tiles = (128,)
    else:
        rows, seq, kv_heads, dim, window = 32, 1024, 8, 128, 512
        heads_of = {"sliding": 72, "full": 48}
        rng = np.random.default_rng(0)
        lens = np.clip(rng.lognormal(np.log(760), 0.25, rows), 60,
                       seq).astype(np.int64)
        tiles = (128, 256)
    few = min(rows, 4)
    lens_d = jnp.asarray(lens, jnp.int32)
    tile = block_causal_tile(seq)

    def operands(heads):
        ks = jax.random.split(jax.random.key(heads), 3)
        bf = jnp.bfloat16
        return (jax.random.normal(ks[0], (rows, seq, heads, dim)).astype(bf),
                jax.random.normal(ks[1], (rows, seq, kv_heads, dim)
                                  ).astype(bf),
                jax.random.normal(ks[2], (rows, seq, kv_heads, dim)
                                  ).astype(bf))

    def view(win, kernel=True, n=rows, dtype=jnp.bfloat16):
        def attend(q, k, v):
            cache = KVCache.zeros(n, seq, kv_heads, dim, dtype)
            return BlockCausalPrefill(
                cache, lens_d[:n], 1, kernel=kernel, window=win,
            ).update(k[:n], v[:n]).attend(q[:n])
        return jax.jit(attend)

    def at_tile(win, t):
        return jax.jit(lambda q, k, v: flash_attention(
            q, k, v, lengths=lens_d, causal=True, block_causal=1,
            block_q=t, block_kv=t, window=win))

    def ms(fn, args):
        def go():
            return fn(*args).reshape(-1)[:8]
        go()
        return timed(go)[0] * 1e3

    real = jnp.arange(seq)[None, :] < lens_d[:, None]
    n = lens.astype(np.int64)
    causal_pairs = int((n * (n + 1) // 2).sum())
    inside = int(np.where(n <= window, n * (n + 1) // 2,
                          window * (window + 1) // 2
                          + (n - window) * window).sum())
    out = {
        "suite": "window_prefill",
        "smoke": smoke(),
        "device": device_info(),
        "shape": {"rows": rows, "width": seq, "kv_heads": kv_heads,
                  "head_dim": dim, "window": window, "tile": tile,
                  "tokens": int(lens.sum())},
        "kernel_ms": {}, "causal_ms": {}, "kernel_ms_at_tile": {},
        "visited_share": {}, "real_share": {
            "window": inside / (rows * seq * seq),
            "causal": causal_pairs / (rows * seq * seq)},
    }
    for name, heads in heads_of.items():
        args = operands(heads)
        out["kernel_ms"][name] = round(ms(view(window), args), 3)
        out["causal_ms"][name] = round(ms(view(0), args), 3)
    sliding = operands(heads_of["sliding"])
    for t in tiles:
        out["kernel_ms_at_tile"][str(t)] = round(
            ms(at_tile(window, t), sliding), 3)
        out["visited_share"][str(t)] = visited_pairs(
            lens, seq, t, t, window) / (rows * seq * seq)
    out["visited_share"][str(tile)] = visited_pairs(
        lens, seq, tile, tile, window) / (rows * seq * seq)
    out["xla_ms_of_4_rows"] = round(
        ms(view(window, kernel=False, n=few), sliding), 3)
    f32 = tuple(a.astype(jnp.float32) for a in sliding)
    want = view(window, kernel=False, n=few, dtype=jnp.float32)(*f32)
    got = view(window)(*sliding)[:few].astype(jnp.float32)
    err = jnp.where(real[:few, :, None, None], got - want, 0.0)
    out["errors"] = {"max": float(jnp.abs(err).max()),
                     "scale": float(jnp.abs(want).max()),
                     "finite": bool(jnp.isfinite(got).all())}
    pairs = inside * heads_of["sliding"]
    out["kernel_tflops"] = round(
        pairs * 4 * dim / out["kernel_ms"]["sliding"] / 1e9, 3)
    return out
