"""The flash kernel with and without a sliding window, in its ``[B, S]``
form (``ops/flash_attention.py`` through the cache's causal view,
``ops/kv_cache.BlockCausalPrefill``) and its packed form on the compact
token stream (``flash_attention_packed``, the view's ``attend_packed``),
against the masked XLA form, at the window cell's step.

16 rows x 1,024 positions on the compact rung their lengths meet (12,288
slots at the cell's lengths), 8 key/value heads of 128, 72 query heads (a
sliding layer's; ``heads`` also times 48, a full layer's), bfloat16, with
the prompt lengths of a long-lyrics corpus (a row about 770 tokens; as
``benchmarks/mla_prefill.py`` draws them where the harness's corpus is at
hand).  ``kernel_ms`` is one call of the view's ``[B, S]`` ``attend`` with
the window of 512 and ``causal_ms`` without one (what a full layer calls),
each at the tile the view takes at this width; ``packed_ms`` /
``packed_causal_ms`` the packed kernel on the stream at each ``blocks``
size; ``block_causal_ms`` the packed kernel under the block-diffusion
prefill's rule at that cell's step (32 rows of a median 286 tokens, 32
query heads on 4 key/value heads, blocks of 4) beside the ``[B, S]`` call.
``visited_share`` is the pairs in the tiles that ran over all of the
``[B, S]`` step's pairs (``bs`` at the view's tile, ``packed`` at each
block), ``real_share`` the pairs inside the mask over the same;
``xla_ms_of_4_rows`` the masked XLA form on four rows.  The errors are of
the windowed kernels against the masked form in float32 on the same
bfloat16 operands, on the real positions.

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

    from music_analyst_tpu.models.compact_stream import (
        RealPositions,
        compact_capacity,
    )
    from music_analyst_tpu.ops.flash_attention import (
        flash_attention_packed,
        packed_visited_pairs,
        visited_pairs,
    )
    from music_analyst_tpu.ops.kv_cache import (
        BlockCausalPrefill,
        KVCache,
        block_causal_tile,
    )

    rng = np.random.default_rng(0)
    if smoke():
        rows, seq, kv_heads, dim, window = 4, 256, 2, 16, 64
        heads_of = {"sliding": 6}
        lens = np.asarray([256, 41, 200, 3])
        blocks = (128,)
        diffusion = (4, 256, 4, 2, np.asarray([256, 40, 0, 100]))
    else:
        rows, seq, kv_heads, dim, window = 16, 1024, 8, 128, 512
        heads_of = {"sliding": 72, "full": 48}
        lens = np.clip(rng.lognormal(np.log(760), 0.25, rows), 60,
                       seq).astype(np.int64)
        blocks = (128, 256)
        diffusion = (32, 1024, 32, 4, np.clip(
            rng.lognormal(np.log(286), 0.6, 32), 20, 1024).astype(np.int64)
            // 4 * 4)
    few = min(rows, 4)
    lens_d = jnp.asarray(lens, jnp.int32)
    tile = block_causal_tile(seq)
    capacity = compact_capacity(int(lens.sum()), rows, seq)
    stream = RealPositions.of(lens_d, seq, capacity)

    def operands(heads, n_rows=rows, n_kv=kv_heads, width=seq):
        ks = jax.random.split(jax.random.key(heads), 3)
        bf = jnp.bfloat16
        return (jax.random.normal(ks[0], (n_rows, width, heads, dim)
                                  ).astype(bf),
                jax.random.normal(ks[1], (n_rows, width, n_kv, dim)
                                  ).astype(bf),
                jax.random.normal(ks[2], (n_rows, width, n_kv, dim)
                                  ).astype(bf))

    def view(win, kernel=True, n=rows, dtype=jnp.bfloat16, lengths=lens_d,
             block=1):
        def attend(q, k, v):
            cache = KVCache.zeros(n, q.shape[1], k.shape[2], dim, dtype)
            return BlockCausalPrefill(
                cache, lengths[:n], block, kernel=kernel, window=win,
            ).update(k[:n], v[:n]).attend(q[:n])
        return jax.jit(attend)

    def packed(win, block, heads, positions=stream, lengths=lens_d, n=1):
        width = positions.real.shape[1]
        return jax.jit(lambda q, k, v: flash_attention_packed(
            q, k, v, lengths, width, heads, block, block_causal=n,
            window=win))

    def on_stream(args, positions=stream):
        c = positions.source.shape[0]
        return tuple(positions.gather(a).reshape(c, -1) for a in args)

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
    square = rows * seq * seq
    out = {
        "suite": "window_prefill",
        "smoke": smoke(),
        "device": device_info(),
        "shape": {"rows": rows, "width": seq, "kv_heads": kv_heads,
                  "head_dim": dim, "window": window, "tile": tile,
                  "tokens": int(lens.sum()), "capacity": capacity},
        "kernel_ms": {}, "causal_ms": {}, "packed_ms": {},
        "packed_causal_ms": {},
        "visited_share": {"bs": visited_pairs(
            lens, seq, tile, tile, window) / square, "packed": {}},
        "real_share": {"window": inside / square,
                       "causal": causal_pairs / square},
    }
    for name, heads in heads_of.items():
        args = operands(heads)
        out["kernel_ms"][name] = round(ms(view(window), args), 3)
        out["causal_ms"][name] = round(ms(view(0), args), 3)
        flat = on_stream(args)
        for b in blocks:
            out["packed_ms"].setdefault(name, {})[str(b)] = round(
                ms(packed(window, b, heads), flat), 3)
            out["packed_causal_ms"].setdefault(name, {})[str(b)] = round(
                ms(packed(0, b, heads), flat), 3)
    for b in blocks:
        out["visited_share"]["packed"][str(b)] = packed_visited_pairs(
            lens, capacity, seq, b, window) / square
    sliding = operands(heads_of["sliding"])
    out["xla_ms_of_4_rows"] = round(
        ms(view(window, kernel=False, n=few), sliding), 3)
    f32 = tuple(a.astype(jnp.float32) for a in sliding)
    want = view(window, kernel=False, n=few, dtype=jnp.float32)(*f32)
    scale = float(jnp.abs(want).max())

    def errors(got):
        got = got[:few].astype(jnp.float32)
        err = jnp.where(real[:few, :, None, None], got - want, 0.0)
        return {"max": float(jnp.abs(err).max()), "scale": scale,
                "finite": bool(jnp.isfinite(got).all())}

    out["errors"] = {"bs": errors(view(window)(*sliding))}
    flat = on_stream(sliding)
    for b in blocks:
        got = packed(window, b, heads_of["sliding"])(*flat)
        out["errors"][f"packed_{b}"] = errors(stream.put_back(
            got.reshape(capacity, heads_of["sliding"], dim)))
    pairs = inside * heads_of["sliding"]
    out["kernel_tflops"] = {"bs": round(
        pairs * 4 * dim / out["kernel_ms"]["sliding"] / 1e9, 3)}
    for b in blocks:
        out["kernel_tflops"][f"packed_{b}"] = round(
            pairs * 4 * dim / out["packed_ms"]["sliding"][str(b)] / 1e9, 3)

    # the block-diffusion prefill's rule at that cell's step
    d_rows, d_seq, d_heads, d_kv, whole = diffusion
    d_lens = jnp.asarray(whole, jnp.int32)
    d_cap = compact_capacity(int(whole.sum()), d_rows, d_seq)
    d_stream = RealPositions.of(d_lens, d_seq, d_cap)
    d_args = operands(d_heads, d_rows, d_kv, d_seq)
    out["block_causal_ms"] = {"bs": round(ms(view(
        0, n=d_rows, lengths=d_lens, block=4), d_args), 3)}
    for b in blocks:
        out["block_causal_ms"][f"packed_{b}"] = round(ms(packed(
            0, b, d_heads, d_stream, d_lens, 4), on_stream(
                d_args, d_stream)), 3)
    out["block_causal_shape"] = {"rows": d_rows, "width": d_seq,
                                 "heads": d_heads, "kv_heads": d_kv,
                                 "tokens": int(whole.sum()),
                                 "capacity": d_cap}
    return out
