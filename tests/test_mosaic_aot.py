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
at the longest rows its VMEM arithmetic admits, and at ``distilbert-tiny``;
the latent-attention prefill kernel at the decoder cell's step (32 x 1,024,
32 heads of 192 | 128, the cache's 1,032-key buffer), at its smallest
admitted width and at ``kanana-tiny``'s widths, and its packed form on the
compact token sets of those steps; the whole scoring step of that cell at
the rungs its compact prefill meets; the KDA prefill kernel at the hybrid
cell's step (64 x 1,024, 32 heads of 128 | 128; compact and padded) and the
whole scoring step of that cell; flash attention
under the block-causal rule at the diffusion cell's prefill (32 x 1,024, 32
query heads on 4 key heads of 128) and both programs of that cell's step;
flash attention with a sliding window at the window cell's prefill (32 x
1,024, 72 and 48 query heads on 8 key heads of 128, window 512).
One compile holds no kernel of ours: the grouped experts of a routed layer
(``models/moe.grouped_experts``, XLA's own grouped matmul) at the decoder
cells' compact token set, with 6 and with 8 choices a token, for what XLA
puts between the kernels' result and the weighted sum.
"""

from __future__ import annotations

import math
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from music_analyst_tpu.ops.flash_attention import flash_attention
from music_analyst_tpu.ops.mla_prefill_attention import (
    mla_prefill_attention,
    mla_prefill_attention_packed,
    packed_prefill_block,
    prefill_block,
)
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


def _compile_for_tpu(fn, sharding, *shapes, mosaic=True):
    args = [
        jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)
        for shape, dtype in shapes
    ]
    lowered = jax.jit(fn).trace(*args).lower(lowering_platforms=("tpu",))
    if mosaic:  # a kernel of ours: Mosaic, not interpreted
        assert "tpu_custom_call" in lowered.as_text()
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


@pytest.mark.parametrize(
    "rows,seq,heads,nope,rope,v_dim",
    [
        (32, 1024, 32, 128, 64, 128),   # kanana-2-30b-a3b, the cell's step
        (4, 512, 32, 128, 64, 128),     # two blocks, the smallest admitted
        (8, 512, 4, 16, 8, 16),         # kanana-tiny: heads inside a lane tile
    ],
)
def test_mla_prefill_attention_compiles_under_mosaic(
    tpu_sharding, rows, seq, heads, nope, rope, v_dim
):
    """The loop over heads with lane slices at traced offsets, and the
    unrolled form where a head is narrower than a lane tile, inside the
    VMEM the call asks for; keys are the cache's buffer, 8 past the
    queries."""
    assert prefill_block(seq)

    def fn(q_nope, q_rope, kv, k_rope, lengths):
        return mla_prefill_attention(
            q_nope, q_rope, kv, k_rope, lengths, heads,
            (nope + rope) ** -0.5, interpret=False)

    _compile_for_tpu(
        fn, tpu_sharding,
        ((rows, seq, heads * nope), jnp.bfloat16),
        ((rows, seq, heads * rope), jnp.bfloat16),
        ((rows, seq + 8, heads * (nope + v_dim)), jnp.bfloat16),
        ((rows, seq + 8, rope), jnp.bfloat16),
        ((rows,), jnp.int32),
    )


@pytest.mark.parametrize(
    "capacity,rows,seq,heads,nope,rope,v_dim",
    [
        (12288, 32, 1024, 32, 128, 64, 128),  # the cell's usual rung
        (16384, 32, 1024, 32, 128, 64, 128),  # and the one above
        (1024, 4, 512, 32, 128, 64, 128),     # the smallest admitted width
        (2048, 8, 512, 4, 16, 8, 16),         # kanana-tiny's widths
    ],
)
def test_packed_mla_prefill_attention_compiles_under_mosaic(
    tpu_sharding, capacity, rows, seq, heads, nope, rope, v_dim
):
    """The packed form: ``[capacity, H*D]`` operands, five scalar-prefetched
    tables, a loop over a block's rows with traced bounds."""
    assert packed_prefill_block(seq, capacity)

    def fn(q_nope, q_rope, kv, k_rope, lengths):
        return mla_prefill_attention_packed(
            q_nope, q_rope, kv, k_rope, lengths, seq, heads,
            (nope + rope) ** -0.5, interpret=False)

    _compile_for_tpu(
        fn, tpu_sharding,
        ((capacity, heads * nope), jnp.bfloat16),
        ((capacity, heads * rope), jnp.bfloat16),
        ((capacity, heads * (nope + v_dim)), jnp.bfloat16),
        ((capacity, rope), jnp.bfloat16),
        ((rows,), jnp.int32),
    )


def _opcode(program: str, name: str) -> str:
    """Opcode of the instruction ``%name`` in a compiled program's text."""
    (line,) = re.findall(rf"^\s*(?:ROOT )?%{re.escape(name)} = .*$", program,
                         re.MULTILINE)
    return re.search(r"[\])}] ([a-z-]+)\(", line).group(1)


def test_projections_feed_the_prefill_kernel_without_a_copy(
    tpu_sharding, monkeypatch
):
    """One latent-attention layer at the published widths, compiled for a
    v5e: the kernel's large operands, ``q_nope`` and the expanded ``[k_nope
    | v]`` (nine tenths of what it reads), are the projections' own fusions
    and its result reaches ``o_proj`` as a bitcast.  ``MLAttention`` writes
    those projections as contractions with 2-D weights for this; from
    ``[B, S, H, D]`` einsums each got a transposing copy.  The rotated
    ``q_rope`` still comes through one (RoPE works on ``[B, S, H, D]``)."""
    from music_analyst_tpu.models.layers import causal_mask
    from music_analyst_tpu.models.mla import LatentCache, MLAttention
    from music_analyst_tpu.ops import flash_attention

    monkeypatch.setattr(flash_attention, "interpret_default", lambda: False)
    rows, seq, labels, dim, rank, rope = 4, 512, 8, 2048, 512, 64
    attention = MLAttention(
        n_heads=32, qk_nope_head_dim=128, qk_rope_head_dim=rope,
        v_head_dim=128, kv_lora_rank=rank, param_dtype=jnp.bfloat16)

    def forward(params, x, lens):
        cache = LatentCache.zeros(rows, seq + labels, rank, rope,
                                  jnp.bfloat16)
        return attention.apply(
            params, x, causal_mask(seq, seq + labels, 0), None, cache,
            prefill_lengths=lens)

    def placed(tree):
        return jax.tree_util.tree_map(
            lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype,
                                           sharding=tpu_sharding), tree)

    params = jax.eval_shape(
        lambda: attention.init(jax.random.key(0),
                               jnp.zeros((1, 8, dim), jnp.bfloat16)))
    program = jax.jit(forward).trace(
        placed(params), placed(jnp.zeros((rows, seq, dim), jnp.bfloat16)),
        placed(jnp.zeros((rows,), jnp.int32)),
    ).lower(lowering_platforms=("tpu",)).compile().as_text()

    (call,) = re.findall(
        r"^\s*%(_prefill_call[.\d]*) = \S+ custom-call\(([^)]*)\)", program,
        re.MULTILINE)
    name, operands = call[0], re.findall(r"%([\w.-]+)", call[1])
    lengths, q_nope, q_rope, kv, k_rope = operands
    assert _opcode(program, q_nope) == "fusion"
    assert _opcode(program, kv) == "fusion"
    assert _opcode(program, q_rope) in ("copy", "fusion")
    readers = re.findall(
        rf"^\s*(?:ROOT )?%[\w.-]+ = \S+ ([a-z-]+)\([^)]*%{re.escape(name)}[,)]",
        program, re.MULTILINE)
    assert readers and set(readers) <= {"bitcast", "fusion"}, readers


def _largest_float32(program: str) -> int:
    """Elements of the largest float32 array the compiled text names,
    a fusion's inner values among them."""
    return max(math.prod(map(int, dims.split(",")))
               for dims in re.findall(r"\bf32\[([\d,]+)\]", program))


@pytest.mark.parametrize("top_k", [6, 8])
def test_grouped_results_return_to_token_order_with_no_float32_copy(
    tpu_sharding, top_k
):
    """``models/moe.grouped_experts`` at a routed layer of the decoder
    cells' prefill (12,288 token slots, 128 experts of 2,048 x 768; 6
    choices a token as ``kanana-2-30b-a3b``, 8 as ``sdar-30b-a3b-chat``)
    compiled for a v5e.  The down projection's rows come back one choice
    at a time, ``top_k`` bfloat16 gathers of ``[T, D]``, and one fusion
    sums them in float32: no float32 array of ``T x top_k x D`` elements
    exists (at 6 choices the TPU padded it to 8 and laid it out anew,
    0.8 GB a layer), and the temporaries are the sorted rows in bfloat16
    twice over (the kernels' result and its gathered copy), 0.55 GB at 6
    choices where that array made them 1.1 GB."""
    from music_analyst_tpu.models.moe import grouped_experts

    tokens, dim, hidden, experts = 12288, 2048, 768, 128
    compiled = _compile_for_tpu(
        grouped_experts, tpu_sharding,
        ((tokens, dim), jnp.bfloat16), ((tokens, top_k), jnp.int32),
        ((tokens, top_k), jnp.float32),
        ((experts, dim, hidden), jnp.bfloat16),
        ((experts, dim, hidden), jnp.bfloat16),
        ((experts, hidden, dim), jnp.bfloat16), mosaic=False)
    text = compiled.as_text()
    assert "ragged-dot-none" in text            # XLA's grouped matmul
    # not even inside a fusion (the widest is the activation's, which
    # never leaves its fusion: ``tokens x top_k x hidden``)
    assert _largest_float32(text) < tokens * top_k * dim
    back = re.findall(
        r"= bf16\[(\d+),(\d+)\]\S* fusion\(.*moe\.combine/gather", text)
    assert back == [(str(tokens), str(dim))] * top_k, back
    sorted_rows = tokens * top_k * dim * 2      # bfloat16
    assert compiled.memory_analysis().temp_size_in_bytes < (
        2 * sorted_rows + 64 * 2 ** 20)


def _traced(fn):
    """``{path: layers}`` the traces under ``fn`` noted
    (``profiling.compile.note_traced_path``)."""
    from music_analyst_tpu.telemetry import get_telemetry

    tel = get_telemetry()
    before = dict(tel.counters)
    fn()
    return {name[len("traced."):]: count - before.get(name, 0)
            for name, count in tel.counters.items()
            if name.startswith("traced.") and count > before.get(name, 0)}


@pytest.mark.parametrize("capacity", [12288, 16384])
def test_compact_scoring_step_keeps_the_kernel_fed_and_no_padded_position(
    tpu_sharding, monkeypatch, capacity
):
    """``llama_score_labels`` at the decoder cell's step (32 x 1,024, the
    published widths, abstract parameters) compiled for a v5e at the rungs
    the cell's jobs meet (``models/moe.compact_capacity`` of about 10.3k
    real tokens a step).  The prefill runs on ``capacity`` token slots
    from the embedding to the last norm: nothing of the 196,608
    assignments of the padded step (32 x 1,024 x 6) is left, in any
    array, and no array of the step's 32 x 1,024 positions wider than the
    latent cache's (``latents`` 512, ``k_rope`` 64).  The packed prefill
    kernel's large operands are the projections' own ``[capacity, H*D]``
    fusions in every one of the seven layers, with no copy, slice or
    transpose between, and its result reaches ``o_proj`` as a bitcast.
    The trace says which form it took; a program that declares no
    capacity takes neither compact path."""
    from music_analyst_tpu.models import llama
    from music_analyst_tpu.ops import flash_attention

    monkeypatch.setattr(flash_attention, "interpret_default", lambda: False)
    config = llama.PRESETS["kanana-2-30b-a3b"]()
    rows, width, top_k = 32, 1024, config.moe_top_k
    assert (config.n_layers, config.dim, top_k) == (7, 2048, 6)
    program = llama.score_labels_program(llama.LlamaModel(config), config)

    def placed(tree):
        return jax.tree_util.tree_map(
            lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype,
                                           sharding=tpu_sharding), tree)

    params = placed(jax.eval_shape(
        lambda: llama.init_params_by_layer(config)))

    def trace(**static):
        return program.trace(
            params, placed(jnp.zeros((rows, width), jnp.int32)),
            placed(jnp.zeros((rows,), jnp.int16)),
            placed(jnp.zeros((3, 2), jnp.int32)),   # word + EOS
            placed(jnp.zeros((3,), jnp.int32)), **static)

    traced = []
    paths = _traced(lambda: traced.append(trace(prefill_capacity=capacity)))
    assert (paths["mla.compact"], paths["moe.compact"]) == (7, 6)
    if capacity == 12288:
        padded = _traced(trace)       # lengths, no capacity
        assert padded["mla.expanded"] == 7
        assert not {"mla.compact", "moe.compact"} & set(padded)
    compiled = traced[0].lower(lowering_platforms=("tpu",)).compile()
    text = compiled.as_text()

    # as an array's dimension (a reshape's ``integer_config`` may hold the
    # number for its own reasons: 12,288 x 16 is the same)
    assert not re.search(rf"[\[,]{rows * width * top_k}[,\]]", text)
    # the widest array over the step's positions is the cache's latents
    # (the chosen experts and the mask of real positions are narrower
    # still); the residual stream alone is 2,048 wide
    by_position = re.findall(
        rf"\[{rows},10\d\d,(\d+)\]|\w\[{rows * width},(\d+)\]", text)
    assert max(int(a or b) for a, b in by_position) == 512
    shapes = set(re.findall(
        r"%ragged-dot-none[.\d]* = (\w+\[\d+,\d+\])", text))
    # the prefill's grouped matmuls at capacity * top_k rows, the label
    # continuations' at theirs: 3 x 32 rows x the ONE position whose
    # forward is read (the label word's; not a table padded to 8)
    assert shapes == {
        f"bf16[{capacity * top_k},{n}]" for n in (768, 2048)} | {
        f"bf16[{3 * rows * top_k},{n}]" for n in (768, 2048)}, shapes
    # the experts' results return to token order with no float32 copy of
    # the ``capacity x top_k`` sorted rows, in a fusion or out of one
    assert _largest_float32(text) < capacity * top_k * config.dim
    # 0.99 and 1.29 GB of temporaries (1.45 and 1.8 with that copy; the
    # padded step has 3.4)
    assert compiled.memory_analysis().temp_size_in_bytes < 1.5e9

    calls = re.findall(
        r"^\s*%(_packed_prefill_call[.\d]*) = \S+ custom-call\(([^)]*)\)",
        text, re.MULTILINE)
    assert len(calls) == config.n_layers
    assert "%_prefill_call" not in text
    for name, operands in calls:
        *tables, q_nope, q_rope, kv, k_rope = re.findall(r"%([\w.-]+)",
                                                         operands)
        assert len(tables) == 5
        assert _opcode(text, q_nope) == "fusion"
        assert _opcode(text, kv) == "fusion"
        assert _opcode(text, q_rope) in ("copy", "fusion")
        readers = re.findall(
            rf"^\s*(?:ROOT )?%[\w.-]+ = \S+ ([a-z-]+)\([^)]*"
            rf"%{re.escape(name)}[,)]", text, re.MULTILINE)
        assert readers and set(readers) <= {"bitcast", "fusion"}, readers


@pytest.mark.parametrize("rows,width,heads,kv_heads,head_dim", [
    (32, 1024, 32, 4, 128),  # the diffusion cell's prefill
    (8, 256, 4, 2, 32),      # sdar-tiny at its smallest admitted width
], ids=["sdar-30b-a3b-chat", "sdar-tiny"])
def test_block_causal_flash_attention_compiles_under_mosaic(
    tpu_sharding, rows, width, heads, kv_heads, head_dim
):
    from music_analyst_tpu.ops.kv_cache import block_causal_tile

    tile = block_causal_tile(width)
    assert tile in (256, 512)

    def fn(q, k, v, lengths):
        return flash_attention(
            q, k, v, lengths=lengths, causal=True, block_causal=4,
            block_q=tile, block_kv=tile, interpret=False)

    _compile_for_tpu(
        fn, tpu_sharding,
        ((rows, width, heads, head_dim), jnp.bfloat16),
        ((rows, width, kv_heads, head_dim), jnp.bfloat16),
        ((rows, width, kv_heads, head_dim), jnp.bfloat16),
        ((rows,), jnp.int32))


@pytest.mark.parametrize("heads,window", [(72, 512), (48, 0), (6, 8)],
                         ids=["sliding-72", "full-48", "laguna-tiny"])
def test_windowed_flash_attention_compiles_under_mosaic(
    tpu_sharding, monkeypatch, heads, window
):
    """The window cell's prefill kernel: 32 rows x 1,024, 72 query heads (a
    sliding layer's, window 512) and 48 (a full layer's) on 8 key/value
    heads of 128, through the cache's causal view at the tile it takes
    there; and ``laguna-tiny``'s heads at its smallest admitted width."""
    from music_analyst_tpu.ops.kv_cache import (
        BlockCausalPrefill,
        KVCache,
        block_causal_tile,
    )

    from music_analyst_tpu.ops import flash_attention as kernel_module

    monkeypatch.setattr(kernel_module, "interpret_default", lambda: False)
    rows, width, kv_heads, head_dim = (
        (32, 1024, 8, 128) if heads > 6 else (8, 256, 2, 16))
    assert block_causal_tile(width) in (256, 512)

    def fn(q, k, v, lengths):
        view = BlockCausalPrefill(
            KVCache.zeros(rows, width + 8, kv_heads, head_dim), lengths, 1,
            window=window).update(k, v)
        return view.attend(q), view.cache.keys

    compiled = _compile_for_tpu(
        fn, tpu_sharding,
        ((rows, width, heads, head_dim), jnp.bfloat16),
        ((rows, width, kv_heads, head_dim), jnp.bfloat16),
        ((rows, width, kv_heads, head_dim), jnp.bfloat16),
        ((rows,), jnp.int32))
    assert len(re.findall(r"%_flash_call[.\d]* = \S+ custom-call\(",
                          compiled.as_text())) == 1


@pytest.mark.parametrize("capacity", [12288, 16384])
def test_diffusion_step_compiles_for_the_chip_at_the_cells_shapes(
    tpu_sharding, monkeypatch, capacity
):
    """Both programs of ``sdar-30b-a3b-chat``'s step (32 x 1,024, the
    published widths, abstract parameters) compiled for a v5e at the rungs
    the cell's jobs meet: the prefill holds one block-causal kernel call a
    layer, and its hidden state and expert layers run ``capacity`` token
    slots, not the padded step's 32,768; the block loop's
    grouped matmuls run the 1,024 assignments of a pass, and the donated
    caches come back in place (aliased, not copied)."""
    from music_analyst_tpu.models import block_diffusion, llama
    from music_analyst_tpu.ops import flash_attention as flash

    monkeypatch.setattr(flash, "interpret_default", lambda: False)
    config = llama.PRESETS["sdar-30b-a3b-chat"]()
    rows, width, blocks = 32, 1024, 4
    top_k, n = config.moe_top_k, config.block_length
    assert (config.n_layers, config.dim, top_k, n) == (7, 2048, 8, 4)
    model = llama.LlamaModel(config)

    def placed(tree):
        return jax.tree_util.tree_map(
            lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype,
                                           sharding=tpu_sharding), tree)

    params = placed(jax.eval_shape(
        lambda: llama.init_params_by_layer(config)))
    ids = placed(jnp.zeros((rows, width), jnp.int32))
    lens = placed(jnp.zeros((rows,), jnp.int16))
    prefill = block_diffusion.diffusion_prefill_program(model, config).trace(
        params, ids, lens, gen_blocks=blocks, prefill_capacity=capacity,
    ).lower(lowering_platforms=("tpu",)).compile()
    text = prefill.as_text()
    assert set(re.findall(
        r"%ragged-dot-none[.\d]* = (\w+\[\d+,\d+\])", text)) == {
        f"bf16[{capacity * top_k},{w}]" for w in (768, 2048)}
    assert len(re.findall(r"%_flash_call[.\d]* = \S+ custom-call\(", text)) == (
        config.n_layers)
    # the hidden state lives on the ``capacity`` slots from the embedding
    # to the last layer: no array of the padded step's positions x dim
    assert f"[{rows},{width},{config.dim}]" not in text
    assert f"[{rows * width},{config.dim}]" not in text
    assert f"bf16[{capacity},{config.dim}]" in text
    assert prefill.memory_analysis().temp_size_in_bytes < 1.5e9

    caches = placed(jax.eval_shape(lambda: [
        llama.KVCache(c.keys, c.values, jnp.zeros((rows,), jnp.int32))
        for c in llama.init_caches(config, rows, width + blocks * n)]))
    denoise = block_diffusion.diffusion_denoise_program(model, config).trace(
        params, caches, ids, lens, gen_blocks=blocks,
    ).lower(lowering_platforms=("tpu",)).compile()
    text = denoise.as_text()
    assert set(re.findall(
        r"%ragged-dot-none[.\d]* = (\w+\[\d+,\d+\])", text)) == {
        f"bf16[{rows * n * top_k},{w}]" for w in (768, 2048)}
    memory = denoise.memory_analysis()
    cache_bytes = (2 * config.n_layers * rows * (width + blocks * n)
                   * config.n_kv_heads * config.attn_head_dim * 2)
    assert memory.alias_size_in_bytes >= cache_bytes
    assert memory.temp_size_in_bytes < 1.0e9


@pytest.mark.parametrize("slots,rows,width,heads,dim", [
    (24576, 64, 1024, 32, 128),   # the hybrid cell's step, compact
    (65536, 64, 1024, 32, 128),   # the same rows padded to [B, S]
    (768, 4, 512, 4, 16),         # ling-tiny's compact 512-wide step
], ids=["ling-compact", "ling-padded", "ling-tiny"])
def test_kda_chunked_compiles_under_mosaic(tpu_sharding, slots, rows, width,
                                           heads, dim):
    """The KDA prefill kernel (``ops/kda_attention.py``) at the hybrid
    cell's step (64 rows of up to 1,024 slots, 32 heads of 128 | 128) on
    the compact token stream and on padded rows, with the norms it takes a
    head at a time, and at the test size."""
    from music_analyst_tpu.ops.kda_attention import kda_chunked

    def fn(q, k, v, g, beta, starts, ends, valid):
        return kda_chunked(q, k, v, g, beta, starts, ends, valid, heads,
                           width, normalize=True, out_norm_eps=1e-6,
                           interpret=False)

    wide = (slots, heads * dim)
    compiled = _compile_for_tpu(
        fn, tpu_sharding, (wide, jnp.bfloat16), (wide, jnp.bfloat16),
        (wide, jnp.bfloat16), (wide, jnp.float32),
        ((slots, heads), jnp.float32), ((rows,), jnp.int32),
        ((rows,), jnp.int32), ((slots,), jnp.bool_))
    assert re.search(r"%_kda_chunk_call[.\d]* = ", compiled.as_text())


def test_hybrid_scoring_step_compiles_for_the_chip_at_the_cells_shape(
    tpu_sharding, monkeypatch
):
    """``llama_score_labels`` of ``ling-3.0-flash-vl`` at the hybrid cell's
    step (64 x 1,024, the published widths, abstract parameters) compiled
    for a v5e at the rung its jobs meet (24,576 slots for about 21,000 real
    tokens): one KDA kernel call in each of the six KDA layers and one
    packed latent-attention call in the seventh, all on the compact stream;
    the held experts' grouped matmuls run a stretch of the tokens at a time
    (8,192 slots x 8 choices) over 128 expert stacks, not the router's 512;
    no KDA layer holds the stream as ``[slots, heads, 128]`` (a head's
    norms ride in the kernel), and the temporaries stay under what one chip
    has beside 10.5 GB of weights."""
    from music_analyst_tpu.models import llama
    from music_analyst_tpu.ops import flash_attention

    monkeypatch.setattr(flash_attention, "interpret_default", lambda: False)
    config = llama.PRESETS["ling-3.0-flash-vl"]()
    rows, width, capacity = 64, 1024, 24576
    assert (config.n_layers, config.kda_layers, config.moe_top_k) == (7, 6, 8)
    program = llama.score_labels_program(llama.LlamaModel(config), config)

    def placed(tree):
        return jax.tree_util.tree_map(
            lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype,
                                           sharding=tpu_sharding), tree)

    params = placed(jax.eval_shape(
        lambda: llama.init_params_by_layer(config)))
    assert params["layer_1"]["feed_forward_moe"]["gate_experts"].shape == (
        128, 2560, 768)
    assert params["layer_1"]["feed_forward_moe"]["router"].shape == (
        2560, 512)
    traced = []
    paths = _traced(lambda: traced.append(program.trace(
        params, placed(jnp.zeros((rows, width), jnp.int32)),
        placed(jnp.zeros((rows,), jnp.int16)),
        placed(jnp.zeros((3, 2), jnp.int32)),   # word + EOS
        placed(jnp.zeros((3,), jnp.int32)), prefill_capacity=capacity,
        probe_rows=placed(jnp.zeros((8,), jnp.int32)))))
    assert (paths["kda.compact"], paths["mla.compact"]) == (6, 1)
    assert paths["moe.experts_held"] == paths["moe.group_limited"] == 12
    compiled = traced[0].lower(lowering_platforms=("tpu",)).compile()
    text = compiled.as_text()
    # (two results a call: the outputs and the rows' final states)
    assert len(re.findall(
        r"%_kda_chunk_call[.\d]* = \([^=]*\) custom-call\(", text)) == 6
    assert len(re.findall(
        r"%_packed_prefill_call[.\d]* = \S+ custom-call\(", text)) == 1
    shapes = set(re.findall(
        r"%ragged-dot-none[.\d]* = (\w+\[\d+,\d+\])", text))
    # (a label's continuation at a time: 64 rows x the one position whose
    # forward is read x 8 choices)
    assert shapes == {f"bf16[{8192 * 8},{n}]" for n in (768, 2560)} | {
        f"bf16[{rows * 8},{n}]" for n in (768, 2560)}, shapes
    # a head's norms ride in the KDA kernel: no KDA layer holds the stream
    # as [slots, heads, 128] in float32 (0.4 GB and a re-tiling copy each)
    by_head = re.findall(
        rf'f32\[{capacity},32,128\][^\n]*op_name="([^"]*)"', text)
    assert by_head and not any("kda" in name for name in by_head)
    memory = compiled.memory_analysis()
    assert memory.argument_size_in_bytes < 10.6e9
    assert memory.temp_size_in_bytes < 3.6e9


@pytest.mark.parametrize("slots,rows,width,heads,dim,state", [
    (12288, 32, 1024, 128, 64, 128),   # the cell's rung, compact stream
    (32768, 32, 1024, 128, 64, 128),   # the same rows padded to [B, S]
    (768, 4, 512, 8, 16, 32),          # granite-tiny's compact 512-wide step
], ids=["granite-compact", "granite-padded", "granite-tiny"])
def test_ssd_chunked_compiles_under_mosaic(tpu_sharding, slots, rows, width,
                                           heads, dim, state):
    """The Mamba-2 prefill kernel (``ops/ssd_scan.py``) at the state-space
    cell's step (32 rows of up to 1,024 slots, 128 heads of 64, state 128)
    on the compact token stream and on padded rows, and at the test size."""
    from music_analyst_tpu.ops.ssd_scan import ssd_chunked

    def fn(x, dt, a, b, c, starts, ends, valid):
        return ssd_chunked(x, dt, a, b, c, starts, ends, valid, heads, width,
                           interpret=False)

    compiled = _compile_for_tpu(
        fn, tpu_sharding, ((slots, heads * dim), jnp.bfloat16),
        ((slots, heads), jnp.float32), ((heads,), jnp.float32),
        ((slots, state), jnp.bfloat16), ((slots, state), jnp.bfloat16),
        ((rows,), jnp.int32), ((rows,), jnp.int32), ((slots,), jnp.bool_))
    assert re.search(r"%_ssd_chunk_call[.\d]* = ", compiled.as_text())


def test_mamba_layer_feeds_the_ssd_kernel_without_a_copy(tpu_sharding,
                                                          monkeypatch):
    """One Mamba-2 mixer of ``granite-4.0-h-small`` on the cell's compact
    stream (12,288 slots of 32 rows, 4,096 wide, inner 8,192), compiled for a
    v5e: one ``_ssd_`` call, fed by a fusion (what a token writes, the step
    spread over its head's lanes inside the product), and no copy or
    transpose of a stream-sized array anywhere in the layer: nothing is laid
    out anew for the kernel, before it or behind it."""
    from music_analyst_tpu.models import llama
    from music_analyst_tpu.models.mamba2 import Mamba2Mixer, SSMState
    from music_analyst_tpu.models.moe import RealPositions
    from music_analyst_tpu.ops import flash_attention

    monkeypatch.setattr(flash_attention, "interpret_default", lambda: False)
    cfg = llama.PRESETS["granite-4.0-h-small"]()
    rows, width, capacity = 32, 1024, 12288
    mixer = Mamba2Mixer(
        n_heads=cfg.mamba_n_heads, head_dim=cfg.mamba_head_dim,
        d_state=cfg.mamba_d_state, conv_kernel=cfg.mamba_conv_kernel,
        norm_eps=cfg.rms_norm_eps, dtype=jnp.bfloat16,
        param_dtype=jnp.bfloat16)

    def placed(tree):
        return jax.tree_util.tree_map(
            lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype,
                                           sharding=tpu_sharding), tree)

    params = placed(jax.eval_shape(
        mixer.init, jax.random.key(0), jnp.zeros((1, 8, cfg.dim),
                                                 jnp.bfloat16)))

    def fn(params, x, lens):
        packed = RealPositions.of(lens, width, capacity)
        positions = packed.gather(
            jnp.broadcast_to(jnp.arange(width), (rows, width)))[None]
        state = SSMState.zeros(rows, cfg.mamba_n_heads, cfg.mamba_head_dim,
                               cfg.mamba_d_state)
        return mixer.apply(params, x, positions, state, lens, None, packed)

    lowered = jax.jit(fn).trace(
        params, placed(jnp.zeros((1, capacity, cfg.dim), jnp.bfloat16)),
        placed(jnp.zeros((rows,), jnp.int32))).lower(
            lowering_platforms=("tpu",))
    text = lowered.compile().as_text()
    calls = re.findall(
        r"%_ssd_chunk_call[.\d]* = \([^=]*\) custom-call\(([^)]*)\)", text)
    assert len(calls) == 1
    fed_by = [name.strip() for name in calls[0].split(",")]
    assert "fusion" in fed_by[2], fed_by        # what a token writes
    # (the entry computation's own operations: inside a fusion a transpose
    # over ``dimensions={0,1}`` is the gather's notation, not a movement)
    moved = re.findall(
        rf"= \w+\[(?:1,)?{capacity},(?:4096|8192|8448|16640)\]\S* "
        r"(?:copy|transpose)\(", text[text.index("ENTRY"):])
    assert not moved, moved


def test_unservable_geometry_is_refused_by_name():
    """What Mosaic cannot lower is refused when the kernel (or the decode
    runtime, on a TPU) is built — not discovered at the first dispatch."""
    check_stream_geometry(8, 128)
    check_stream_geometry(4, 16)
    check_stream_geometry(3, 128)   # odd KV heads are fine at full lanes
    with pytest.raises(ValueError, match="power-of-two"):
        check_stream_geometry(3, 64)
