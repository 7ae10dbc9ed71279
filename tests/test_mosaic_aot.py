"""Mosaic accepts the Pallas kernels — checked without a chip.

libtpu can describe a TPU topology and compile for it ahead of time on a
host that has no TPU, so the sandbox can answer "does Mosaic lower this
kernel at this geometry?" — the question every other test (all of which
run the kernels under ``interpret=True``) cannot.  Compiling is not
running: numerics on the chip are ``chip_smoke.py``'s kernels leg.

Geometries are the ones ``chip_smoke.py`` runs: Llama-3-8B heads
(32 Q / 8 KV x 128) and the ``llama3-tiny`` shape ``serve`` really
builds (8 Q / 4 KV x 16), page 16, bf16 and int8 pools; flash attention
at GQA + causal with and without segment ids; the whole-row kernel at the
corpus job's two batch shapes (4,096 and the 306-row tail x 12 x 128 x 64),
at the longest rows its VMEM arithmetic admits, and at ``distilbert-tiny``.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from music_analyst_tpu.ops.flash_attention import flash_attention
from music_analyst_tpu.ops.paged_attention import (
    check_stream_geometry,
    paged_attention,
)
from music_analyst_tpu.ops.whole_row_attention import (
    whole_row_attention,
    whole_row_block_rows,
)


@pytest.fixture(scope="module")
def tpu_sharding():
    from jax.experimental import topologies

    try:
        topology = topologies.get_topology_desc("v5e:2x2", "tpu")
    except Exception as exc:  # no libtpu on this host
        pytest.skip(f"no TPU topology for ahead-of-time compiles: {exc}")
    return SingleDeviceSharding(topology.devices[0])


def _compile_for_tpu(fn, sharding, *shapes):
    args = [
        jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)
        for shape, dtype in shapes
    ]
    lowered = jax.jit(fn).trace(*args).lower(lowering_platforms=("tpu",))
    assert "tpu_custom_call" in lowered.as_text()  # Mosaic, not interpreted
    return lowered.compile()


@pytest.mark.parametrize("quantized", [False, True], ids=["bf16", "int8"])
@pytest.mark.parametrize(
    "heads,kv_heads,head_dim,pages_per_slot",
    [(32, 8, 128, 64), (8, 4, 16, 65)],
    ids=["llama3-8b", "llama3-tiny"],
)
def test_paged_stream_body_compiles_under_mosaic(
    tpu_sharding, heads, kv_heads, head_dim, pages_per_slot, quantized
):
    slots, page = 8, 16
    pool = (slots * pages_per_slot + 1, page, kv_heads, head_dim)
    pool_dtype = jnp.int8 if quantized else jnp.bfloat16
    shapes = [
        ((slots, 1, heads, head_dim), jnp.bfloat16),
        (pool, pool_dtype),
        (pool, pool_dtype),
        ((slots, pages_per_slot), jnp.int32),
        ((slots, pages_per_slot * page - 3), jnp.bool_),
    ]
    if quantized:
        shapes += [(pool[:2], jnp.float32)] * 2

    def fn(q, k, v, table, mask, key_scale=None, value_scale=None):
        return paged_attention(
            q, k, v, table, mask, key_scale=key_scale,
            value_scale=value_scale, interpret=False, stream=True,
        )

    _compile_for_tpu(fn, tpu_sharding, *shapes)


@pytest.mark.parametrize("segmented", [False, True], ids=["plain", "segments"])
def test_flash_attention_compiles_under_mosaic(tpu_sharding, segmented):
    batch, seq, heads, kv_heads, head_dim = 2, 4096, 8, 2, 128
    shapes = [
        ((batch, seq, heads, head_dim), jnp.bfloat16),
        ((batch, seq, kv_heads, head_dim), jnp.bfloat16),
        ((batch, seq, kv_heads, head_dim), jnp.bfloat16),
    ]
    if segmented:
        shapes.append(((batch, seq), jnp.int32))

    def fn(q, k, v, segment_ids=None):
        return flash_attention(
            q, k, v, causal=True, interpret=False,
            q_segment_ids=segment_ids,
        )

    _compile_for_tpu(fn, tpu_sharding, *shapes)


@pytest.mark.parametrize(
    "rows,seq,heads,head_dim,dtype",
    [
        (4096, 128, 12, 64, jnp.bfloat16),  # the corpus job's full batch
        (306, 128, 12, 64, jnp.bfloat16),   # its tail: 38 blocks of 8 and 2
        (1, 128, 12, 64, jnp.bfloat16),     # the one-row init / serve batch
        (16, 256, 12, 64, jnp.bfloat16),
        (16, 384, 12, 64, jnp.bfloat16),    # the longest row that fits: 1 a step
        (16, 256, 12, 64, jnp.float32),
        (64, 128, 4, 16, jnp.bfloat16),     # distilbert-tiny
        (64, 128, 3, 64, jnp.bfloat16),     # 12 heads split over tp=4
    ],
    ids=lambda x: getattr(x, "__name__", str(x)),
)
def test_whole_row_attention_compiles_under_mosaic(
    tpu_sharding, rows, seq, heads, head_dim, dtype
):
    """Wherever the kernel's own arithmetic admits a shape, Mosaic takes
    it inside the VMEM the call asks for."""
    assert whole_row_block_rows(seq, heads, head_dim, dtype)
    shape = ((rows, seq, heads, head_dim), dtype)

    def fn(q, k, v, lengths):
        return whole_row_attention(q, k, v, lengths, interpret=False)

    _compile_for_tpu(
        fn, tpu_sharding, shape, shape, shape, ((rows,), jnp.int32)
    )


def test_unservable_geometry_is_refused_by_name():
    """What Mosaic cannot lower is refused when the kernel (or the decode
    runtime, on a TPU) is built — not discovered at the first dispatch."""
    check_stream_geometry(8, 128)
    check_stream_geometry(4, 16)
    check_stream_geometry(3, 128)   # odd KV heads are fine at full lanes
    with pytest.raises(ValueError, match="power-of-two"):
        check_stream_geometry(3, 64)
