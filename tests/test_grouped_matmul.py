"""The routed experts' grouped matmul kernel (``ops/grouped_matmul.py``)
under the interpreter, against ``jax.lax.ragged_dot`` and a float32
reference, and the rule that decides where it runs.

Small shapes: a row tile of 32 computed 8 rows a step (``STEP_ROWS``
patched down), so that groups smaller than a step, groups that straddle
steps and tiles, empty groups and rows behind the last group all occur in a
few hundred rows.  The real shapes compile for a v5e in
``tests/test_mosaic_aot.py``.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from music_analyst_tpu.models import moe
from music_analyst_tpu.ops import grouped_matmul as gm

ROWS, K_DIM, N_DIM = 128, 64, 256
TILES = (32, 128)                 # 4 row tiles, 2 n-blocks

LAYOUTS = {
    "uneven": [40, 3, 0, 57, 9, 2],
    "empty-between": [0, 30, 0, 0, 50, 0],
    "one-group-holds-every-row": [0, 0, ROWS, 0, 0, 0],
    "smaller-than-a-step": [5, 6, 7, 5, 6, 7],
    "all-empty": [0] * 6,
}


def _operands(sizes, seed=0):
    keys = jax.random.split(jax.random.key(seed), 4)
    experts = len(sizes)
    x = jax.random.normal(keys[0], (ROWS, K_DIM), jnp.bfloat16)
    # the rows behind the last group (fillers, absent experts) hold NaN:
    # nothing of them may reach a grouped row or the result
    x = jnp.where(jnp.arange(ROWS)[:, None] < sum(sizes), x, jnp.nan)
    stacks = [(0.2 * jax.random.normal(k, (experts, K_DIM, N_DIM))
               ).astype(jnp.bfloat16) for k in keys[1:3]]
    return x, stacks, jnp.asarray(sizes, jnp.int32)


def _float32_reference(x, stacks, sizes, swiglu):
    x = np.asarray(x, np.float32)
    out = np.zeros((x.shape[0], N_DIM), np.float32)
    start = 0
    for g, size in enumerate(np.asarray(sizes)):
        rows = x[start:start + size]
        y = rows @ np.asarray(stacks[0][g], np.float32)
        if swiglu:
            y = y / (1 + np.exp(-y)) * (
                rows @ np.asarray(stacks[1][g], np.float32))
        out[start:start + size] = y
        start += size
    return out


@pytest.mark.parametrize("swiglu", [True, False], ids=["swiglu", "down"])
@pytest.mark.parametrize("layout", sorted(LAYOUTS))
def test_kernel_matches_ragged_dot_and_float32(layout, swiglu, monkeypatch):
    """Every grouped row is its expert's product (with the SwiGLU on the
    float32 accumulators where fused), within a bfloat16 rounding of the
    float32 reference and of what ``ragged_dot`` gives; every row behind
    the last group is zero, and every row finite."""
    monkeypatch.setattr(gm, "STEP_ROWS", 8)
    sizes = LAYOUTS[layout]
    x, stacks, group_sizes = _operands(sizes)
    if swiglu:
        got = gm.grouped_swiglu(x, *stacks, group_sizes, tiles=TILES,
                                interpret=True)
        gate = jax.lax.ragged_dot(x, stacks[0], group_sizes)
        up = jax.lax.ragged_dot(x, stacks[1], group_sizes)
        ragged = jax.nn.silu(gate) * up
    else:
        got = gm.grouped_matmul(x, stacks[0], group_sizes, tiles=TILES,
                                interpret=True)
        ragged = jax.lax.ragged_dot(x, stacks[0], group_sizes)
    assert got.shape == (ROWS, N_DIM) and got.dtype == jnp.bfloat16
    got = np.asarray(got, np.float32)
    total = sum(sizes)
    assert np.isfinite(got).all()
    assert (got[total:] == 0).all()
    want = _float32_reference(x, stacks, group_sizes, swiglu)[:total]
    ragged = np.asarray(ragged, np.float32)[:total]
    scale = max(float(np.abs(want).max(initial=0.0)), 1.0)
    # one bfloat16 rounding of the result (2^-9 of it) and of a summand
    assert (np.abs(got[:total] - want)
            <= 2.0 ** -8 * np.abs(want) + 2.0 ** -9 * scale).all()
    assert (np.abs(got[:total] - ragged)
            <= 2.0 ** -7 * np.abs(want) + 2.0 ** -8 * scale).all()


def test_kernel_refuses_a_shape_outside_its_regime():
    x, stacks, sizes = _operands(LAYOUTS["uneven"])
    with pytest.raises(ValueError, match="regime"):
        gm.grouped_matmul(x, stacks[0], sizes, interpret=True)
    with pytest.raises(ValueError, match="divide"):
        gm.grouped_matmul(x, stacks[0], sizes, tiles=(48, 128),
                          interpret=True)


# (tokens of a grouped call, top_k, held experts, dim, hidden)
PREFILLS = {
    "kanana-2-30b-a3b": (12288, 6, 128, 2048, 768),
    "sdar-30b-a3b-chat": (12288, 8, 128, 2048, 768),
    "ling-3.0-flash-vl": (moe.HELD_CHUNK, 8, 128, 2560, 768),
    "granite-4.0-h-small": (12288, 10, 36, 4096, 768),
    "laguna-s-2.1": (12288, 10, 128, 3072, 1024),
}
RAGGED = {
    # a label continuation: 32 rows x the one position read x 3 labels
    "kanana-labels": (3 * 32, 6, 128, 2048, 768),
    # a pass of the diffusion block loop: 32 rows x a block of 4
    "sdar-block-loop": (32 * 4, 8, 128, 2048, 768),
    "ling-labels": (3 * 64, 8, 128, 2560, 768),
    "granite-labels": (3 * 32, 10, 36, 4096, 768),
    "laguna-labels": (3 * 16, 10, 128, 3072, 1024),
    # a decode step of ``generate``
    "kanana-decode": (1, 6, 128, 2048, 768),
    # a prefill of the test preset: hundreds of rows an expert, widths
    # under a lane tile
    "kanana-tiny-prefill": (3328, 2, 8, 64, 32),
}


@pytest.mark.parametrize("name", sorted(PREFILLS))
def test_prefill_shapes_take_the_kernel(name):
    """The prefills of the five expert configurations take the kernel,
    with blocks inside the VMEM budget and a row tile their rows an expert
    reach."""
    tokens, top_k, held, dim, hidden = PREFILLS[name]
    tiles = moe.grouped_kernel_tiles(tokens, top_k, held, dim, hidden)
    assert tiles is not None
    for (tile, n_block), (k_dim, n_dim, stacks) in zip(
            tiles, ((dim, hidden, 2), (hidden, dim, 1))):
        assert tokens * top_k // held >= tile
        assert n_dim % n_block == 0 and (n_block % 128 == 0
                                         or n_block == n_dim)
        assert gm.vmem_bytes(tile, min(gm.STEP_ROWS, tile), k_dim, n_block,
                             stacks) <= gm.VMEM_BUDGET


@pytest.mark.parametrize("name", sorted(RAGGED))
def test_pass_and_narrow_shapes_stay_on_ragged_dot(name):
    """A label pass, a diffusion block and a decode step have a few rows
    an expert, the test presets widths under a lane tile: ``ragged_dot``."""
    assert moe.grouped_kernel_tiles(*RAGGED[name]) is None


def _routed_operands(partial):
    """A grouped call in the kernel's regime at small widths: 1,024 tokens
    of 2 choices over 2 held expert stacks of 128 x 128 (512 rows an
    expert), 24 fillers and, with ``partial``, a third of the assignments
    to an absent expert."""
    tokens, experts, dim, hidden, fillers = 1024, 2, 128, 128, 24
    keys = jax.random.split(jax.random.key(11), 7)
    x = jax.random.normal(keys[0], (tokens, dim), jnp.bfloat16)
    stacks = [(0.1 * jax.random.normal(k, shape)).astype(jnp.bfloat16)
              for k, shape in zip(keys[1:4], ((experts, dim, hidden),) * 2
                                  + ((experts, hidden, dim),))]
    chosen = jax.random.randint(keys[4], (tokens, 2), 0, experts)
    if partial:
        absent = jax.random.uniform(keys[5], chosen.shape) < 0.35
        chosen = jnp.where(absent, experts, chosen)
    chosen = chosen.at[tokens - fillers:].set(experts).astype(jnp.int32)
    weights = jax.random.uniform(keys[6], (tokens, 2), jnp.float32, 0.05, 1)
    return (x, chosen, weights, *stacks), tokens - fillers


@pytest.mark.parametrize("partial", [False, True], ids=["whole", "held"])
def test_one_device_experts_take_the_kernel_and_match_ragged_dot(partial):
    """``grouped_experts*_one_device`` at a shape in the kernel's regime
    runs the kernel (a ``pallas_call`` where the other form has three
    ``ragged_dot``s) and gives the same weighted sums on every real token
    within bfloat16 roundings; an absent expert's assignment still counts
    as zero, and the fillers' rows come out finite."""
    args, real = _routed_operands(partial)
    one_device = (moe.grouped_experts_held_one_device if partial
                  else moe.grouped_experts_one_device)
    anywhere = moe.grouped_experts_held if partial else moe.grouped_experts
    kernel_text = str(jax.make_jaxpr(one_device)(*args))
    ragged_text = str(jax.make_jaxpr(anywhere)(*args))
    assert kernel_text.count("pallas_call[") == 2
    assert "ragged_dot" not in kernel_text
    assert ragged_text.count("ragged_dot_general[") == 3
    assert "pallas_call" not in ragged_text
    got = np.asarray(one_device(*args))
    want = np.asarray(anywhere(*args))
    assert np.isfinite(got).all()
    scale = np.abs(want[:real]).max()
    assert scale > 0.1
    assert np.abs(got[:real] - want[:real]).max() <= 2.0 ** -6 * scale


@pytest.mark.parametrize("packed", [False, True], ids=["gathered", "packed"])
def test_routed_layer_takes_the_kernel_only_on_one_device(packed):
    """``RoutedMoE`` in a prefill that declared its rows' lengths (one
    device), on their compact token set or on every position, at a shape in
    the kernel's regime notes ``moe.grouped_kernel`` and calls the kernel;
    the same layer where they are withheld (under a mesh) keeps
    ``ragged_dot``."""
    from music_analyst_tpu.models.compact_stream import RealPositions
    from music_analyst_tpu.telemetry import get_telemetry

    layer = moe.RoutedMoE(2, 128, 2, dtype=jnp.bfloat16,
                          param_dtype=jnp.bfloat16)
    rows, width = 2, 640
    lengths = jnp.asarray([600, 400], jnp.int32)
    x = jax.random.normal(jax.random.key(1), (rows, width, 128),
                          jnp.bfloat16)
    params = jax.eval_shape(layer.init, jax.random.key(0), x[:, :8])
    compact = RealPositions.of(lengths, width, 1024)
    stream = compact.gather(x)[None] if packed else x

    def traced(*args, **kwargs):
        """The program's text and the layers that noted the kernel."""
        counters = get_telemetry().counters
        before = counters.get("traced.moe.grouped_kernel", 0)
        text = str(jax.make_jaxpr(
            lambda p, h: layer.apply(p, h, *args, **kwargs))(params, stream))
        return text, counters.get("traced.moe.grouped_kernel", 0) - before

    text, noted = traced(compact, packed=packed, one_device=True)
    assert noted == 1
    assert text.count("pallas_call[") == 2 and "ragged_dot" not in text
    if not packed:
        text, noted = traced(one_device=True)       # every position
        assert noted == 1 and text.count("pallas_call[") == 2
        text, noted = traced(compact)
        assert noted == 0
        assert text.count("ragged_dot_general[") == 3
        assert "pallas_call" not in text
