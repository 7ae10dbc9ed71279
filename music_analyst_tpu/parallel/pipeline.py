"""Pipeline parallelism: GPipe-style microbatching over the ``pp`` mesh axis.

No reference analogue (the reference's only axis is data parallelism,
SURVEY.md §2.4); included so every classic parallelism axis is first-class.

Mechanics: decoder blocks are stacked ``[n_stages, layers_per_stage, ...]``
with the stage axis sharded over ``pp`` — each device owns one stage.
Inside ``shard_map`` a ``lax.scan`` runs ``n_micro + n_stages - 1`` ticks;
each tick every device ppermutes its previous activation to the next ring
neighbor, stage 0 injects the next microbatch, every stage applies its
layers (a ``lax.scan`` over the stage's stacked layer params), and the last
stage records finished microbatches.  Autodiff through scan + ppermute
yields the standard GPipe backward schedule for free — no hand-written
backward pass.
"""

from __future__ import annotations

from typing import Any, Callable

import jax
import jax.numpy as jnp
import numpy as np
from jax import shard_map
from jax.lax import pcast
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from music_analyst_tpu.profiling.collectives import record_collective


def stack_layer_params(params: dict, n_stages: int, prefix: str = "layer_"):
    """``{layer_0: t0, layer_1: t1, ...}`` → stacked ``[n_stages, k, ...]``.

    Returns ``(stacked_tree, n_layers)``; layer order is preserved, layers
    are split contiguously (layers ``[s*k, (s+1)*k)`` form stage ``s``).
    """
    layer_keys = sorted(
        (k for k in params if k.startswith(prefix)),
        key=lambda k: int(k[len(prefix):]),
    )
    n_layers = len(layer_keys)
    if n_layers % n_stages != 0:
        raise ValueError(
            f"{n_layers} layers do not split into {n_stages} stages"
        )
    trees = [params[k] for k in layer_keys]
    stacked_flat = jax.tree_util.tree_map(
        lambda *leaves: jnp.stack(leaves).reshape(
            (n_stages, n_layers // n_stages) + leaves[0].shape
        ),
        *trees,
    )
    return stacked_flat, n_layers


def unstack_layer_params(stacked, prefix: str = "layer_") -> dict:
    """Inverse of :func:`stack_layer_params` (host-side, for tests)."""
    leaves = jax.tree_util.tree_leaves(stacked)
    n_stages, k = leaves[0].shape[:2]
    out = {}
    for s in range(n_stages):
        for j in range(k):
            out[f"{prefix}{s * k + j}"] = jax.tree_util.tree_map(
                lambda leaf: leaf[s, j], stacked
            )
    return out


def pipeline_apply(
    stage_fn: Callable[[Any, jax.Array], jax.Array],
    stacked_params: Any,
    microbatches: jax.Array,
    mesh: Mesh,
    axis: str = "pp",
):
    """Run the microbatch pipeline; returns outputs shaped like the input.

    ``stage_fn(stage_params, x)`` applies one stage (its ``[k, ...]``
    stacked layers) to activations ``x``; ``microbatches`` is
    ``[n_micro, mb, ...]`` and is replicated (stage 0 injects from it).
    """
    n_stages = mesh.shape[axis]
    lead = jax.tree_util.tree_leaves(stacked_params)[0].shape[0]
    if lead != n_stages:
        raise ValueError(
            f"stacked params have {lead} stages but mesh axis "
            f"{axis!r} has {n_stages} devices"
        )

    def body(stage_params, mb):
        # stage_params leaves arrive as [1, k, ...] (this device's stage).
        stage_params = jax.tree_util.tree_map(
            lambda leaf: leaf[0], stage_params
        )
        idx = jax.lax.axis_index(axis)
        n = jax.lax.psum(1, axis)
        n_micro = mb.shape[0]
        ticks = n_micro + n - 1
        state = jnp.zeros_like(mb[0])
        state = pcast(state, (axis,), to="varying")
        outputs = jnp.zeros_like(mb)
        outputs = pcast(outputs, (axis,), to="varying")
        perm = [(i, (i + 1) % n) for i in range(n)]

        def tick(carry, t):
            state, outputs = carry
            incoming = jax.lax.ppermute(state, axis, perm)
            inject = mb[jnp.clip(t, 0, n_micro - 1)]
            x_in = jnp.where(idx == 0, inject, incoming)
            y = stage_fn(stage_params, x_in)
            mb_idx = t - (n - 1)
            is_last = idx == n - 1
            write = is_last & (mb_idx >= 0)
            slot = jnp.clip(mb_idx, 0, n_micro - 1)
            outputs = outputs.at[slot].set(
                jnp.where(write, y, outputs[slot])
            )
            return (y, outputs), None

        (_, outputs), _ = jax.lax.scan(
            tick, (state, outputs), jnp.arange(ticks)
        )
        # Only the last stage holds real outputs; broadcast them to all.
        outputs = jax.lax.psum(
            jnp.where(idx == n - 1, outputs, jnp.zeros_like(outputs)), axis
        )
        return outputs

    # Analytic wire accounting: one activation ppermute per tick (ticks =
    # n_micro + n_stages - 1), then the final psum that broadcasts the
    # last stage's [n_micro, mb, ...] outputs to every device.
    n_micro = microbatches.shape[0]
    act_bytes = int(
        np.prod(microbatches.shape[1:]) * microbatches.dtype.itemsize
    )
    record_collective(
        "pipeline.activation_shift", "ppermute",
        payload_bytes=act_bytes, n_devices=n_stages, axis=axis,
        count=n_micro + n_stages - 1,
    )
    record_collective(
        "pipeline.output_broadcast", "psum",
        payload_bytes=n_micro * act_bytes, n_devices=n_stages, axis=axis,
    )
    return shard_map(
        body,
        mesh=mesh,
        in_specs=(P(axis), P()),
        out_specs=P(),
    )(stacked_params, microbatches)
