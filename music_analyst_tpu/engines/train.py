"""Distributed training step for the decoder LM family.

The reference has no training at all; this engine exists because the
framework's model families must be trainable at scale (fine-tuning the
sentiment classifier, continued pretraining on lyrics).  The step is a
single jitted SPMD program over a named mesh:

* ``dp`` — batch axis of the token batch;
* ``sp`` — sequence axis of the token batch (GSPMD inserts the attention
  collectives from the shardings; the hand-rolled ring attention in
  ``ops/ring_attention.py`` is the ICI-optimal manual variant);
* ``tp`` — parameter/optimizer-state sharding via ``parallel/sharding.py``;
* ``ep`` — MoE expert stacks when the config enables experts.

Gradients reduce over ``dp``/``sp`` automatically (XLA derives the psums
from the shardings — the scaling-book recipe, not hand-written collectives).
"""

from __future__ import annotations

import dataclasses
from typing import Any, Optional, Tuple

import jax
import jax.numpy as jnp
import optax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from music_analyst_tpu.models.layers import causal_mask
from music_analyst_tpu.parallel.sharding import partition_specs


@dataclasses.dataclass
class TrainState:
    params: Any
    opt_state: Any
    step: jax.Array


jax.tree_util.register_dataclass(
    TrainState, data_fields=["params", "opt_state", "step"], meta_fields=[]
)


def causal_lm_loss(model, params, token_ids, lengths, segment_ids=None):
    """Next-token cross-entropy with padding masked out.

    ``segment_ids`` ``[B, S]`` (contiguous document ids per row, 0 = pad)
    turns a row into a *pack* of documents — the standard pretraining
    data-efficiency move: attention is restricted to same-document pairs,
    positions restart at every document boundary, and the loss skips the
    cross-document boundary target (token t never predicts another
    document's token t+1).  A packed row's per-token losses equal the
    per-document rows' exactly (``tests/test_packed_training.py``).
    """
    inputs = token_ids[:, :-1]
    targets = token_ids[:, 1:]
    S = inputs.shape[1]
    s_idx = jnp.arange(S)[None, :]
    flash = model.config.attn_impl == "flash"
    if segment_ids is None:
        positions = jnp.broadcast_to(s_idx, inputs.shape)
        logits, _ = model.apply(
            {"params": params}, inputs, positions, causal_mask(S, S, 0)
        )
    else:
        from music_analyst_tpu.models.layers import segment_mask

        seg = segment_ids[:, :-1].astype(jnp.int32)
        # Position = offset from the document's first token: cummax of
        # the segment-start indices (contiguous ids ⇒ a start is any
        # index whose left neighbor differs).
        is_start = jnp.concatenate(
            [jnp.ones((seg.shape[0], 1), bool), seg[:, 1:] != seg[:, :-1]],
            axis=1,
        )
        start_idx = jax.lax.cummax(jnp.where(is_start, s_idx, 0), axis=1)
        positions = s_idx - start_idx
        # The flash path discards mask arrays by contract (models/llama.py)
        # and takes the segment ids natively; the dense path folds them
        # into the mask array.  Routing by impl here keeps both honest —
        # tests pin packed ≡ separate on each.
        if flash:
            logits, _ = model.apply(
                {"params": params}, inputs, positions, None,
                segment_ids=seg,
            )
        else:
            logits, _ = model.apply(
                {"params": params}, inputs, positions,
                causal_mask(S, S, 0) & segment_mask(seg),
            )
    logp = jax.nn.log_softmax(logits.astype(jnp.float32), axis=-1)
    nll = -jnp.take_along_axis(logp, targets[..., None], axis=-1)[..., 0]
    valid = (s_idx < (lengths - 1)[:, None]).astype(jnp.float32)
    if segment_ids is not None:
        # Drop pad tokens and the last token of every document: its
        # "next token" belongs to a different document.
        same_doc = (segment_ids[:, :-1] == segment_ids[:, 1:])
        valid = valid * (same_doc & (segment_ids[:, :-1] > 0)).astype(
            jnp.float32
        )
    return jnp.sum(nll * valid) / jnp.maximum(jnp.sum(valid), 1.0)


def make_optimizer(
    learning_rate: float = 3e-4, weight_decay: float = 0.01
) -> optax.GradientTransformation:
    return optax.adamw(learning_rate, weight_decay=weight_decay)


def zero1_shard_opt_state(opt_state, mesh: Mesh):
    """Shard optimizer-state arrays over the ``dp`` axis (ZeRO stage 1).

    Data-parallel replicas don't need replicated Adam moments — each can
    own a slice of them (cross-replica sharding of the weight update,
    arXiv:2004.13336; PAPERS.md).  Each moment leaf gets ``dp`` assigned to
    its first divisible, still-unsharded dimension, composing with the
    tp/ep specs it inherited from the params.  GSPMD derives the
    reduce-scatter/all-gather pair around the update from the sharding
    mismatch — no hand-written collectives.
    """
    dp = mesh.shape.get("dp", 1)
    if dp <= 1:
        return opt_state

    def place(leaf):
        if not hasattr(leaf, "ndim") or leaf.ndim == 0:
            return leaf
        spec = list(getattr(getattr(leaf, "sharding", None), "spec", ()))
        spec += [None] * (leaf.ndim - len(spec))
        for i in range(leaf.ndim):
            if spec[i] is None and leaf.shape[i] % dp == 0:
                spec[i] = "dp"
                return jax.device_put(leaf, NamedSharding(mesh, P(*spec)))
        return leaf  # no divisible free axis — stays as-is

    return jax.tree_util.tree_map(place, opt_state)


def init_train_state(
    model,
    optimizer: optax.GradientTransformation,
    sample_batch: Tuple[jax.Array, jax.Array],
    seed: int = 0,
    mesh: Optional[Mesh] = None,
    zero1: bool = False,
) -> TrainState:
    """Initialize params + optimizer state, sharded over ``mesh`` if given.

    Parameters and every optimizer-state leaf that mirrors a parameter
    (Adam moments) share the same partition spec, so optimizer memory
    scales down with ``tp``/``ep`` exactly like the weights.  With
    ``zero1=True`` the moments additionally shard over ``dp``
    (:func:`zero1_shard_opt_state`).
    """
    token_ids, lengths = sample_batch
    S = token_ids.shape[1] - 1
    positions = jnp.zeros((1, S), jnp.int32)
    params = model.init(
        jax.random.key(seed),
        jnp.zeros((1, S), jnp.int32),
        positions,
        causal_mask(S, S, 0),
    )["params"]
    opt_state = optimizer.init(params)
    if mesh is not None:
        specs = partition_specs(params)
        axis_names = set(mesh.axis_names)

        def prune(spec: P) -> P:
            return P(*(a if a in axis_names else None for a in spec))

        def place_params(spec, leaf):
            return jax.device_put(leaf, NamedSharding(mesh, prune(spec)))

        params = jax.tree_util.tree_map(
            lambda spec, leaf: place_params(spec, leaf), specs, params
        )
        # Re-initializing from the sharded params makes every Adam moment
        # (zeros_like of a sharded leaf) inherit that leaf's sharding.
        opt_state = optimizer.init(params)
        if zero1:
            opt_state = zero1_shard_opt_state(opt_state, mesh)
    return TrainState(
        params=params, opt_state=opt_state, step=jnp.zeros((), jnp.int32)
    )


def _with_step_telemetry(step):
    """Wrap a (possibly jitted) train step with a telemetry span + counter.

    The span measures *dispatch* time: the jitted program is asynchronous,
    so the first call's duration includes trace+compile while steady-state
    calls are near-instant enqueues.  That asymmetry is exactly what makes
    the span useful — compile stalls show up as outlier ``train_step``
    spans next to the jax backend_compile events in the same log.
    """
    import functools

    from music_analyst_tpu.observability import watchdog
    from music_analyst_tpu.telemetry import get_telemetry

    @functools.wraps(step)
    def timed_step(state, token_ids, lengths, segment_ids=None):
        tel = get_telemetry()
        with tel.span("train_step"):
            # A dispatch that never returns is a device stall; the
            # watchdog names it instead of leaving a silent hang.
            with watchdog.watch("train.step", kind="device"):
                out = step(state, token_ids, lengths, segment_ids)
        tel.count("train_steps")
        return out

    return timed_step


def make_train_step(model, optimizer, mesh: Optional[Mesh] = None):
    """Build the jitted SPMD train step.

    With a mesh, the token batch shards ``P('dp', 'sp')`` (batch over data
    ranks, sequence over sequence ranks) and the output state is pinned to
    the *input* state's shardings (derived per distinct input sharding
    layout) — required for ZeRO-1, where the moments' dp-sharding must
    survive the update instead of being re-replicated by the compiler, and
    harmless otherwise.
    """

    def step_fn(state: TrainState, token_ids, lengths, segment_ids=None):
        loss, grads = jax.value_and_grad(
            lambda p: causal_lm_loss(model, p, token_ids, lengths,
                                     segment_ids=segment_ids)
        )(state.params)
        updates, new_opt = optimizer.update(
            grads, state.opt_state, state.params
        )
        new_params = optax.apply_updates(state.params, updates)
        return (
            TrainState(new_params, new_opt, state.step + 1),
            loss,
        )

    from music_analyst_tpu.profiling.compile import profiled_jit

    if mesh is None:
        # Donate the incoming state: state-in and state-out are the same
        # pytree of shapes, so params + both Adam moments update in place
        # instead of holding two full copies live across the step.  Callers
        # must reassign (`state, loss = step(state, ...)`) — every loop in
        # this repo does, and the donated buffers error loudly if reused.
        return _with_step_telemetry(
            profiled_jit(step_fn, name="train_step", donate_argnums=(0,))
        )

    data_axes = [a for a in ("dp", "sp") if a in mesh.axis_names]
    dp = data_axes[0] if data_axes else None
    sp = data_axes[1] if len(data_axes) > 1 else None
    batch_sharding = NamedSharding(mesh, P(dp, sp))
    lengths_sharding = NamedSharding(mesh, P(dp))

    def sharded_step(state, token_ids, lengths, segment_ids=None):
        token_ids = jax.lax.with_sharding_constraint(token_ids, batch_sharding)
        lengths = jax.lax.with_sharding_constraint(lengths, lengths_sharding)
        if segment_ids is not None:
            # Packed-document ids shard exactly like the tokens they label.
            segment_ids = jax.lax.with_sharding_constraint(
                segment_ids, batch_sharding
            )
        return step_fn(state, token_ids, lengths, segment_ids)

    def _shardings_of(state):
        return jax.tree_util.tree_map(
            lambda x: x.sharding
            if isinstance(getattr(x, "sharding", None), NamedSharding)
            else None,
            state,
        )

    # Output shardings derive from each call's concrete input state, keyed
    # by the state's sharding layout: init_train_state(zero1=True) is the
    # only knob, and a step function reused across differently-sharded
    # states (e.g. a plain smoke state, then a ZeRO-1 state) pins each
    # layout separately instead of freezing the first one seen.  The
    # common case — the caller feeding back the state this step returned —
    # is an identity check, so the steady-state loop never re-derives the
    # layout (NamedSharding is hashable, so the cold-path key is the
    # sharding tuple itself, no string formatting).
    import weakref

    jitted_by_layout = {}
    # Weakref so the cache never pins the caller's dropped TrainState
    # (params + both Adam moments) in device memory.
    last_out = [None, None]  # [weakref to output state, jitted fn]

    def pinned_step(state, token_ids, lengths, segment_ids=None):
        if last_out[0] is not None and last_out[0]() is state:
            jitted = last_out[1]
        else:
            shardings = _shardings_of(state)
            key = tuple(
                jax.tree_util.tree_leaves(
                    shardings, is_leaf=lambda x: x is None
                )
            )
            jitted = jitted_by_layout.get(key)
            if jitted is None:
                # donate_argnums=(0,): the output state is pinned to the
                # input state's shardings, so every leaf aliases exactly —
                # in-place update, halving peak optimizer memory.
                jitted = profiled_jit(
                    sharded_step, name="train_step_sharded",
                    out_shardings=(shardings, None),
                    donate_argnums=(0,),
                )
                jitted_by_layout[key] = jitted
        new_state, loss = jitted(state, token_ids, lengths, segment_ids)
        last_out[0], last_out[1] = weakref.ref(new_state), jitted
        return new_state, loss

    return _with_step_telemetry(pinned_step)


def prefetch_batches(batches, mesh: Optional[Mesh] = None, depth=None):
    """Device-put training batches up to ``depth`` ahead of the step loop.

    ``batches`` yields ``(token_ids, lengths)`` or ``(token_ids, lengths,
    segment_ids)`` host arrays; each comes back with lengths/segment ids
    narrowed to int16 where the sequence length allows (they widen inside
    the loss) and every array already placed — sharded ``P('dp','sp')``
    when a mesh is given — so the train loop's ``jitted(state, *batch)``
    never blocks on the host→device copy (H2D rate: not measured on
    this host).  The transfer overlaps the
    previous step's device time through the shared bounded pipeline
    (``runtime/prefetch.py``); stalls land in the manifest's ``pipeline``
    section under ``train_pipeline``.
    """
    from music_analyst_tpu.runtime import (
        PrefetchPipeline,
        Stage,
        resolve_prefetch_depth,
    )
    from music_analyst_tpu.runtime.wire import count_h2d_bytes, narrow_lengths

    depth = resolve_prefetch_depth(depth)
    if mesh is not None:
        data_axes = [a for a in ("dp", "sp") if a in mesh.axis_names]
        dp = data_axes[0] if data_axes else None
        sp = data_axes[1] if len(data_axes) > 1 else None
        batch_sharding = NamedSharding(mesh, P(dp, sp))
        lengths_sharding = NamedSharding(mesh, P(dp))
    else:
        batch_sharding = lengths_sharding = None

    def h2d(batch):
        token_ids, lengths, *rest = batch
        segment_ids = rest[0] if rest else None
        S = token_ids.shape[1]
        lengths = narrow_lengths(lengths, S)
        arrays = [token_ids, lengths]
        shardings = [batch_sharding, lengths_sharding]
        if segment_ids is not None:
            # Contiguous per-row document ids are bounded by S.
            arrays.append(narrow_lengths(segment_ids, S))
            shardings.append(batch_sharding)
        count_h2d_bytes(arrays, prefix="train_pipeline")
        placed = tuple(
            jax.device_put(a, s) for a, s in zip(arrays, shardings)
        )
        if segment_ids is None and rest:
            return (*placed, None)
        return placed

    pipe = PrefetchPipeline(
        [Stage("h2d", h2d)],
        depth=depth,
        name="train_pipeline",
        sink_name="step",
    )
    return pipe.run(iter(batches))
