"""Mixture-of-experts feed-forward with expert-parallel sharding.

No reference analogue (SURVEY.md §2.4 marks EP absent); present because the
framework treats every parallelism axis as first-class.  The expert weight
stacks carry a leading ``E`` axis sharded over the ``ep`` mesh axis
(``parallel/sharding.py``); the hidden axis additionally shards over ``tp``.

Dispatch is *sparse* (token-choice top-k with a capacity bound): each
token's top-k expert assignments scatter into a static ``[E, capacity]``
buffer (position = running count within the expert, computed by one
cumsum), the expert SwiGLUs run over the buffer, and results gather back
weighted by the router.  FLOPs are ``k × capacity_factor`` per token
instead of the dense path's ``E×``; shapes stay static so the whole thing
jits and shards.  Assignments beyond an expert's capacity are dropped —
the standard Switch/GShard trade; ``capacity_factor >= n_experts`` is
lossless and reproduces the dense path exactly, which is how the
differential test pins the implementation (``tests/test_moe.py``).

``dispatch="dense"`` keeps the exact all-experts compute as the oracle.

Sharding semantics under an ``ep`` mesh axis: the expert einsums — where
~all FLOPs live — partition over ``E`` (weights carry the sharded axis);
the routing/scatter/gather bookkeeping computes on replicated token
activations (O(T·(k+D)) elementwise work, no matmuls) and XLA slices the
buffer per shard at the einsum boundary.  An explicit all-to-all token
exchange only pays off once tokens themselves are ep-sharded across
hosts — the multi-host regime ``parallel/distributed.py`` owns.
"""

from __future__ import annotations

import math
from typing import Optional

import jax
import jax.numpy as jnp
from flax import linen as nn

from music_analyst_tpu.models.compact_stream import RealPositions


def moe_capacity(tokens: int, top_k: int, n_experts: int,
                 capacity_factor: float) -> int:
    """Buffer slots per expert: ``ceil(ceil(T*k/E) * capacity_factor)``.

    The outer ceil matters at decode-scale token counts: truncation would
    silently erase the headroom (ceil(8/4)*1.25 = 2.5 must give 3 slots,
    not 2 — 2 is capacity_factor 1.0 in disguise).
    """
    fair_share = -(-tokens * top_k // n_experts)
    return max(1, math.ceil(fair_share * capacity_factor))


class MoESwiGLU(nn.Module):
    """Top-k routed mixture of SwiGLU experts."""

    n_experts: int
    hidden_dim: int
    top_k: int = 2
    dtype: jnp.dtype = jnp.bfloat16
    # "sparse": capacity-bounded scatter/gather dispatch (production);
    # "dense": every expert computes every token, router mask combines
    # (exact; the differential oracle).
    dispatch: str = "sparse"
    # Buffer slots per expert = ceil(T*k/E) * capacity_factor.  1.25 keeps
    # drops rare under mild router imbalance; >= n_experts is lossless.
    capacity_factor: float = 1.25
    # "int8" routes the expert einsums — where ~all MoE FLOPs live —
    # through the dynamic per-expert int8 matmul
    # (``ops/quant.py:quant_batched_matmul``); the router stays f32 (a
    # [D,E] sliver of the FLOPs, and top-k index flips under quantization
    # would change *routing*, not just precision).  Same contract as the
    # dense layers' quant flag: inference-only, default OFF.
    quant: str = "none"

    def _expert_mm(self, x: jax.Array, w: jax.Array) -> jax.Array:
        """Batched per-expert matmul ``[E,C,K] @ [E,K,N]`` in self.dtype
        or via the int8 MXU path."""
        if self.quant == "int8":
            from music_analyst_tpu.ops.quant import quant_batched_matmul

            return quant_batched_matmul(x, w).astype(self.dtype)
        return jnp.einsum("eck,ekn->ecn", x, w.astype(self.dtype))

    @nn.compact
    def __call__(self, x: jax.Array) -> jax.Array:
        if self.dispatch not in ("sparse", "dense"):
            raise ValueError(f"unknown MoE dispatch {self.dispatch!r}")
        features = x.shape[-1]
        E, H = self.n_experts, self.hidden_dim
        k = min(self.top_k, E)
        init = nn.initializers.lecun_normal()
        gate_w = self.param("gate_experts", init, (E, features, H))
        up_w = self.param("up_experts", init, (E, features, H))
        down_w = self.param("down_experts", init, (E, H, features))

        router_logits = nn.Dense(
            E, use_bias=False, dtype=jnp.float32, name="router"
        )(x)                                                   # [B,S,E]
        top_vals, top_idx = jax.lax.top_k(router_logits, k)
        top_weights = jax.nn.softmax(top_vals, axis=-1)        # [B,S,k]

        if self.dispatch == "dense":
            return self._dense(
                x, gate_w, up_w, down_w, top_idx, top_weights
            )
        return self._sparse(x, gate_w, up_w, down_w, top_idx, top_weights)

    def _dense(self, x, gate_w, up_w, down_w, top_idx, top_weights):
        E = self.n_experts
        # scatter the top-k weights back to a dense [B,S,E] combine matrix
        combine = jnp.sum(
            jax.nn.one_hot(top_idx, E, dtype=jnp.float32)
            * top_weights[..., None],
            axis=-2,
        )
        if self.quant == "int8":
            # Same batched-matmul layout as the sparse path so both
            # dispatches quantize identically: broadcast the tokens to
            # every expert ([E,T,D] — the dense oracle already pays E×
            # FLOPs, the copy is not the cost driver).
            B, S, D = x.shape
            T = B * S
            xb = jnp.broadcast_to(
                x.reshape(T, D).astype(self.dtype), (E, T, D)
            )
            gate = self._expert_mm(xb, gate_w)
            up = self._expert_mm(xb, up_w)
            out = self._expert_mm(nn.silu(gate) * up, down_w)  # [E,T,D]
            out = jnp.einsum(
                "te,etd->td",
                combine.reshape(T, E).astype(jnp.float32),
                out.astype(jnp.float32),
            ).reshape(B, S, D)
            return out.astype(x.dtype)
        xc = x.astype(self.dtype)
        gate = jnp.einsum("bsd,edh->besh", xc, gate_w.astype(self.dtype))
        up = jnp.einsum("bsd,edh->besh", xc, up_w.astype(self.dtype))
        expert_out = jnp.einsum(
            "besh,ehd->besd", nn.silu(gate) * up, down_w.astype(self.dtype)
        )                                                      # [B,E,S,D]
        out = jnp.einsum(
            "bse,besd->bsd", combine.astype(self.dtype), expert_out
        )
        return out.astype(x.dtype)

    def _sparse(self, x, gate_w, up_w, down_w, top_idx, top_weights):
        B, S, D = x.shape
        E, k = self.n_experts, top_idx.shape[-1]
        T = B * S
        A = T * k  # assignments: token t's choices at flat ids t*k .. t*k+k-1
        capacity = moe_capacity(T, k, E, self.capacity_factor)

        xt = x.reshape(T, D).astype(self.dtype)
        flat_expert = top_idx.reshape(A)
        flat_weight = top_weights.reshape(A)
        flat_token = jnp.arange(A) // k

        # Position of each assignment within its expert: cumulative count
        # of earlier same-expert assignments (one cumsum over the one-hot).
        one_hot_e = jax.nn.one_hot(flat_expert, E, dtype=jnp.int32)  # [A,E]
        pos = jnp.sum(
            (jnp.cumsum(one_hot_e, axis=0) - 1) * one_hot_e, axis=-1
        )                                                            # [A]
        keep = pos < capacity
        # Dropped assignments target row `capacity`, one past the buffer:
        # scatter mode="drop" discards them; gathers clamp but are masked.
        safe_pos = jnp.where(keep, pos, capacity)

        buf = jnp.zeros((E, capacity, D), self.dtype)
        buf = buf.at[flat_expert, safe_pos].set(
            xt[flat_token], mode="drop"
        )

        gate = self._expert_mm(buf, gate_w)
        up = self._expert_mm(buf, up_w)
        out_buf = self._expert_mm(nn.silu(gate) * up, down_w)  # [E,C,D]

        gathered = out_buf[flat_expert, jnp.minimum(safe_pos, capacity - 1)]
        contrib = gathered.astype(jnp.float32) * (
            flat_weight * keep.astype(jnp.float32)
        )[:, None]
        out = (
            jnp.zeros((T, D), jnp.float32)
            .at[flat_token]
            .add(contrib)
            .reshape(B, S, D)
        )
        return out.astype(x.dtype)

    @staticmethod
    def load_balancing_loss(router_logits: jax.Array, top_idx: jax.Array,
                            n_experts: int) -> jax.Array:
        """Switch-style auxiliary loss (mean prob × mean dispatch per expert)."""
        probs = jax.nn.softmax(router_logits, axis=-1)
        mean_prob = probs.mean(axis=(0, 1))
        dispatch = jax.nn.one_hot(top_idx[..., 0], n_experts).mean(axis=(0, 1))
        return n_experts * jnp.sum(mean_prob * dispatch)


def route_sigmoid_noaux(
    logits: jax.Array, bias: jax.Array, top_k: int,
    routed_scaling_factor: float, norm_topk_prob: bool = True,
    n_group: int = 1, topk_group: int = 1,
):
    """``sigmoid`` scores with ``noaux_tc`` selection, group-limited where
    ``n_group > 1``.

    Choice: the ``top_k`` largest of ``s + bias`` where ``s =
    sigmoid(logits)`` and ``bias`` is the per-expert
    ``e_score_correction_bias``.  With groups the experts are ``n_group``
    runs of ``E / n_group`` neighbours, a group's score is the sum of its
    two largest corrected scores, the best ``topk_group`` groups are kept
    and the corrected scores of the others count as 0 in the choice.
    Weights: ``s`` itself (without the bias) at the chosen experts, divided
    by their sum, times ``routed_scaling_factor``.  ``logits [T, E]``
    float32; returns ``(indices [T, k] int32, weights [T, k] float32)``.
    """
    scores = jax.nn.sigmoid(logits.astype(jnp.float32))
    corrected = scores + bias.astype(jnp.float32)
    if n_group > 1:
        grouped = corrected.reshape(corrected.shape[:-1] + (n_group, -1))
        group_scores = jax.lax.top_k(grouped, 2)[0].sum(axis=-1)
        _, kept = jax.lax.top_k(group_scores, topk_group)
        keep = jax.nn.one_hot(kept, n_group, dtype=bool).any(axis=-2)
        corrected = jnp.where(keep[..., None], grouped, 0.0).reshape(
            corrected.shape)
    elif topk_group != 1:
        raise ValueError("topk_group without n_group")
    _, chosen = jax.lax.top_k(corrected, top_k)
    weights = jnp.take_along_axis(scores, chosen, axis=-1)
    if norm_topk_prob:
        weights = weights / (weights.sum(axis=-1, keepdims=True) + 1e-20)
    return chosen.astype(jnp.int32), weights * routed_scaling_factor


def route_softmax_topk(logits: jax.Array, top_k: int,
                       norm_topk_prob: bool = True):
    """``softmax`` scores over ALL experts, the ``top_k`` largest chosen,
    no bias, no scaling, no group stage.  Weights: the chosen
    probabilities, divided by their sum where ``norm_topk_prob``.
    ``logits [T, E]`` float32; returns ``(indices [T, k] int32, weights
    [T, k] float32)``."""
    probs = jax.nn.softmax(logits.astype(jnp.float32), axis=-1)
    weights, chosen = jax.lax.top_k(probs, top_k)
    if norm_topk_prob:
        weights = weights / weights.sum(axis=-1, keepdims=True)
    return chosen.astype(jnp.int32), weights


# The routers :class:`RoutedMoE` can be given, by the configuration's name
# for them (``LlamaConfig.moe_router``).
ROUTERS = ("sigmoid_noaux", "softmax_topk")


# Tokens a pass of a layer that holds a share of its experts
# (``RoutedMoE(experts_held=...)``) sorts to them at a time.
HELD_CHUNK = 8192


def grouped_call_tokens(tokens: int, share: bool) -> int:
    """Tokens one grouped call of a routed layer runs over ``tokens`` token
    slots: all of them, or where the layer holds a ``share`` of its experts
    and they are more than :data:`HELD_CHUNK` (and whole stretches of it),
    a stretch at a time."""
    if share and tokens > HELD_CHUNK and tokens % HELD_CHUNK == 0:
        return HELD_CHUNK
    return tokens


def grouped_kernel_tiles(tokens: int, top_k: int, held: int, dim: int,
                         hidden: int):
    """The grouped matmul kernel's tiles ``(swiglu, down)``
    (``ops/grouped_matmul.kernel_tiles``) for one grouped call over
    ``tokens`` tokens of ``top_k`` choices among ``held`` expert stacks of
    ``dim x hidden``, or ``None`` where the call stays on
    ``jax.lax.ragged_dot``: both projections take the kernel or neither.
    The static shape alone decides (a prefill's calls have hundreds of
    rows an expert, a label pass's, a diffusion block's and a decode
    step's a few); whoever counts the rows the kernel took asks it too."""
    from music_analyst_tpu.ops.grouped_matmul import kernel_tiles

    rows = tokens * top_k
    swiglu = kernel_tiles(rows, held, dim, hidden, 2)
    down = kernel_tiles(rows, held, hidden, dim, 1)
    return (swiglu, down) if swiglu and down else None


def _grouped_experts(xt, chosen, weights, gate_w, up_w, down_w,
                     partial: bool, one_device: bool):
    """``sum_k weights[t, k] * expert_{chosen[t, k]}(xt[t])`` for every
    token, no capacity: assignments sorted by expert, the grouped matmuls
    over the ragged groups, and the results back in token order one choice
    at a time: ``k`` gathers of the down projection's bfloat16 rows, each
    row converted to float32, multiplied by its float32 weight and added
    in float32 in ONE fusion behind the gathers (scopes ``moe.dispatch``,
    ``moe.matmul``, ``moe.combine``, inside the caller's ``moe.experts``).
    No ``[T, k, D]`` float32 array exists for any ``k``: it would be the
    step's largest by far (2 GB at 24,576 slots x 8 x 2,560), and where
    ``k`` is no multiple of the float32 tile's 8 rows the TPU pads it and
    lays it out anew besides (6 to 8: 0.8 GB written and read a layer at
    12,288 x 6 x 2,048).  ``xt [T, D]``, ``chosen``/``weights [T, k]``,
    expert stacks ``[E, D, H]`` / ``[E, H, D]``.  Returns ``[T, D]``
    float32.

    The grouped matmuls: on ``one_device`` at a shape
    :func:`grouped_kernel_tiles` admits, the Pallas kernel
    (``ops/grouped_matmul.py``: gate and up and the SwiGLU in one call, the
    down projection in another); otherwise one ``jax.lax.ragged_dot`` a
    projection (under a mesh the kernel's call would be opaque to the
    partitioner).

    A token whose ``chosen`` is the number of experts (one past the last)
    is a filler of a compact token set (:class:`RealPositions`): its
    assignments sort behind the last group and belong to none, so no
    expert multiplies them, and its row of the result is undefined.
    ``partial`` (:func:`grouped_experts_held`): single assignments may name
    that one-past-the-last expert too (an expert another chip holds); they
    go where a filler's go and the sum masks them (``chosen < E``): each
    counts as exactly zero in its token's sum, whatever its row holds."""
    from music_analyst_tpu.ops.grouped_matmul import (
        grouped_matmul,
        grouped_swiglu,
    )

    T, D = xt.shape
    E, _, H = gate_w.shape
    k = chosen.shape[-1]
    tiles = one_device and grouped_kernel_tiles(T, k, E, D, H)
    with jax.named_scope("moe.dispatch"):
        flat_expert = chosen.reshape(T * k)
        order = jnp.argsort(flat_expert, stable=True)
        group_sizes = jnp.zeros((E,), jnp.int32).at[
            flat_expert].add(1, mode="drop")
        xs = xt[order // k]                                       # [A, D]
    with jax.named_scope("moe.matmul"):
        if tiles:
            h = grouped_swiglu(xs, gate_w, up_w, group_sizes, tiles[0])
            ys = grouped_matmul(h, down_w, group_sizes, tiles[1])
        else:
            gate = jax.lax.ragged_dot(xs, gate_w, group_sizes)
            up = jax.lax.ragged_dot(xs, up_w, group_sizes)
            ys = jax.lax.ragged_dot(nn.silu(gate) * up, down_w,
                                    group_sizes)
    with jax.named_scope("moe.combine"):
        back = jnp.argsort(order).reshape(T, k)
        if partial:
            here = chosen < E

        def weighted(j):
            return (ys[back[:, j]].astype(jnp.float32)
                    * weights[:, j, None].astype(jnp.float32))

        out = jnp.zeros((T, D), jnp.float32)
        for j in range(k):
            if partial:
                out = out + jnp.where(here[:, j, None], weighted(j), 0.0)
            else:
                out = out + weighted(j)
        return out


def _batched_over_tokens(partial: bool, one_device: bool):
    """:func:`_grouped_experts` with its batching rule: tokens are
    independent, so a batch of token sets is one larger set (``ragged_dot``
    has no batching rule over its token axis): the label continuations of
    ``_score_labels`` run as one grouped matmul."""

    @jax.custom_batching.custom_vmap
    def grouped_experts(xt, chosen, weights, gate_w, up_w, down_w):
        return _grouped_experts(xt, chosen, weights, gate_w, up_w, down_w,
                                partial, one_device)

    grouped = grouped_experts

    @grouped.def_vmap
    def _vmap(axis_size, in_batched, xt, chosen, weights, gate_w, up_w,
              down_w):
        if any(in_batched[3:]):
            raise NotImplementedError(
                "grouped_experts: batched expert weights")

        def merge(x, batched):
            if not batched:
                x = jnp.broadcast_to(x[None], (axis_size,) + x.shape)
            return x.reshape((axis_size * x.shape[1],) + x.shape[2:])

        out = grouped(
            merge(xt, in_batched[0]), merge(chosen, in_batched[1]),
            merge(weights, in_batched[2]), gate_w, up_w, down_w)
        return out.reshape((axis_size, -1) + out.shape[1:]), True

    grouped.__doc__ = _grouped_experts.__doc__
    return grouped


grouped_experts = _batched_over_tokens(partial=False, one_device=False)
# The same over the experts THIS chip holds of a layer that has more
# (``RoutedMoE(experts_held=...)``): ``chosen`` in local ids, an absent
# expert's as one past the last.
grouped_experts_held = _batched_over_tokens(partial=True, one_device=False)
# Both on one device, where the grouped matmul kernel may run.
grouped_experts_one_device = _batched_over_tokens(partial=False,
                                                  one_device=True)
grouped_experts_held_one_device = _batched_over_tokens(partial=True,
                                                       one_device=True)


class RoutedMoE(nn.Module):
    """Routed experts with **no capacity and no dropped token**, plus the
    shared experts (if any) every token passes through.  ``router`` names
    the function that scores and chooses (:data:`ROUTERS`):
    ``sigmoid_noaux`` (:func:`route_sigmoid_noaux`, with its
    ``e_score_correction_bias`` parameter and ``routed_scaling_factor``) or
    ``softmax_topk`` (:func:`route_softmax_topk`, no parameter beside the
    router's matrix).

    The assignments (token, expert) are sorted by expert and each of the
    three projections is one grouped matrix multiplication over the ragged
    groups (``jax.lax.ragged_dot``: on the TPU XLA lowers it to its grouped
    matmul kernel, the work is ``top_k`` experts a token however uneven the
    routing).  On ``one_device`` (a prefill that declared its rows'
    lengths: a mesh withholds them, and the kernel's call would be opaque
    to the partitioner) at a shape in its regime
    (:func:`grouped_kernel_tiles`: a prefill's hundreds of rows an expert)
    they run on the Pallas kernel of ``ops/grouped_matmul.py`` instead,
    gate, up and the SwiGLU in one call (traced path
    ``moe.grouped_kernel``).  The results go back to token order one
    choice at a time (``top_k`` gathers of bfloat16 rows) and are combined
    with the router's weights in one fused float32 multiply-add: no ``[T,
    top_k, D]`` array lies between (:func:`grouped_experts`).
    ``n_shared`` shared experts are one SwiGLU of width ``n_shared *
    hidden_dim``.

    Given ``compact`` (a prefill that declared its rows' lengths:
    ``models/llama.LlamaBlock._feed_forward``), router, sort, grouped
    matmuls, weighted sum and shared experts run on the real positions
    alone, gathered into ``compact``'s token set, and the result is put
    back at their places: every real position is routed by the same scores
    to the same experts and summed with the same weights as without it,
    and positions at or behind a row's length are NOT computed; their
    output is zero and their ``chosen`` 0, not what the layer would give.
    With ``packed`` the caller's ``x [1, C, D]`` IS that token set (the
    compact stream of ``models/compact_stream.runs_compact``) and so is the
    result: nothing is gathered or put back but the sown ``chosen``, and a
    filler's routed output is zero (the shared experts' alone is its
    result).

    ``experts_held = (first, count)`` says this chip holds experts ``first
    .. first + count - 1`` of the layer's ``n_experts`` (expert parallelism:
    the others live on the chips that share the layer).  The router keeps
    its ``n_experts`` outputs and its ``top_k`` a token; ``count`` expert
    stacks exist; an assignment to an absent expert goes where a filler's
    goes (no group of the grouped matmul: zero, not computed); and the
    result is THIS chip's part of the layer's: the held experts' share of
    the weighted sum plus the shared experts, which every chip of the layer
    computes alike.  The exchange that would add the parts is not here, and
    nothing stands in for it.

    Sows ``expert_load`` (assignments each held expert received, ``[E]``
    int32; with ``compact`` those of real positions alone, fillers
    uncounted) and ``chosen`` (``[B, S, k]``, the router's ids, held or
    not) into the ``intermediates`` collection for callers that ask for it.
    """

    n_experts: int
    hidden_dim: int
    top_k: int
    n_shared: int = 0
    routed_scaling_factor: float = 1.0
    norm_topk_prob: bool = True
    dtype: jnp.dtype = jnp.bfloat16
    param_dtype: jnp.dtype = jnp.float32
    router: str = "sigmoid_noaux"
    # group-limited choice (``route_sigmoid_noaux``); 1, 1 = none
    n_group: int = 1
    topk_group: int = 1
    experts_held: Optional[tuple] = None

    @nn.compact
    def __call__(self, x: jax.Array,
                 compact: Optional[RealPositions] = None,
                 packed: bool = False, one_device: bool = False) -> jax.Array:
        from music_analyst_tpu.models.layers import SwiGLU, fan_in_normal
        from music_analyst_tpu.profiling.compile import note_traced_path

        D = x.shape[-1]
        B, S = x.shape[:2] if not packed else compact.real.shape
        E, H, k = self.n_experts, self.hidden_dim, self.top_k
        first, held = self.experts_held or (0, E)
        if not 0 <= first <= first + held <= E:
            raise ValueError(
                f"experts_held {self.experts_held} outside 0..{E}")
        gate_w = self.param("gate_experts", fan_in_normal(D), (held, D, H),
                            self.param_dtype)
        up_w = self.param("up_experts", fan_in_normal(D), (held, D, H),
                          self.param_dtype)
        down_w = self.param("down_experts", fan_in_normal(H), (held, H, D),
                            self.param_dtype)
        # The router stays float32 at the highest matmul precision: its
        # cost is a sliver, and a rounding there changes *which* experts
        # run, not how precisely.
        router_w = self.param("router", fan_in_normal(D), (D, E),
                              jnp.float32)
        if self.router not in ROUTERS:
            raise ValueError(f"unknown router {self.router!r}")
        if self.router == "sigmoid_noaux":
            bias = self.param(
                "e_score_correction_bias",
                lambda key, shape, dtype: 0.01 * jax.random.normal(
                    key, shape, dtype),
                (E,), jnp.float32,
            )
        if compact is None or packed:
            xt = x.reshape(-1, D).astype(self.dtype)
        else:
            xt = compact.gather(x).astype(self.dtype)

        with jax.named_scope("moe.route"):
            logits = jnp.dot(xt.astype(jnp.float32), router_w,
                             precision=jax.lax.Precision.HIGHEST)
            if self.router == "sigmoid_noaux" and self.n_group > 1:
                note_traced_path("moe.group_limited")
                chosen, weights = route_sigmoid_noaux(
                    logits, bias, k, self.routed_scaling_factor,
                    self.norm_topk_prob, self.n_group, self.topk_group)
            elif self.router == "sigmoid_noaux":
                chosen, weights = route_sigmoid_noaux(
                    logits, bias, k, self.routed_scaling_factor,
                    self.norm_topk_prob)
            else:
                note_traced_path("moe.softmax_topk")
                chosen, weights = route_softmax_topk(
                    logits, k, self.norm_topk_prob)

        with jax.named_scope("moe.experts"):
            note_traced_path("moe.grouped")
            placed = chosen
            if self.experts_held is not None:
                note_traced_path("moe.experts_held")
                # local ids; an expert another chip holds is one past the
                # last, as a filler's is (grouped_experts_held)
                local = chosen - first
                chosen = jnp.where((local >= 0) & (local < held), local,
                                   held)
            if compact is not None:
                note_traced_path("moe.compact")
                placed = compact.put_back(placed)
                # a filler belongs to no expert (grouped_experts)
                chosen = jnp.where(compact.valid[:, None], chosen, held)
            self.sow("intermediates", "expert_load",
                     jnp.zeros((held,), jnp.int32).at[
                         chosen.reshape(-1)].add(1, mode="drop"))
            self.sow("intermediates", "chosen", placed.reshape(B, S, k))
            if self.experts_held is not None:
                real = (compact.valid.sum() if compact is not None
                        else xt.shape[0])
                self.sow("intermediates", "assigned",
                         jnp.asarray(real * k, jnp.int32))
            stacks = (gate_w.astype(self.dtype), up_w.astype(self.dtype),
                      down_w.astype(self.dtype))
            n_tok = xt.shape[0]
            share = self.experts_held is not None
            call_tokens = grouped_call_tokens(n_tok, share)
            if one_device and grouped_kernel_tiles(call_tokens, k, held, D,
                                                   H):
                note_traced_path("moe.grouped_kernel")
            grouped = {
                (False, False): grouped_experts,
                (True, False): grouped_experts_held,
                (False, True): grouped_experts_one_device,
                (True, True): grouped_experts_held_one_device,
            }[share, one_device]
            if call_tokens < n_tok:
                # Every assignment is sorted and gathered, held or not
                # (how many are held is the router's to say, and no token
                # is dropped), so the sorted copies are sized by all of
                # them: a stretch of the tokens at a time.
                def stretch(a):
                    return a.reshape((-1, call_tokens) + a.shape[1:])

                out = jax.lax.map(
                    lambda part: grouped(*part, *stacks),
                    (stretch(xt), stretch(chosen), stretch(weights)),
                ).reshape(n_tok, D)
            else:
                out = grouped(xt, chosen, weights, *stacks)
            if packed:
                # The fillers stay in the caller's stream, and their rows
                # of the grouped matmuls belong to no group: undefined.
                # They have to be finite: the prefill kernel multiplies a
                # masked key's value by a probability of zero, and the
                # fillers share the last real slots' block.
                out = jnp.where(compact.valid[:, None], out, 0.0)

        if self.n_shared:
            with jax.named_scope("moe.shared"):
                out = out + SwiGLU(
                    self.n_shared * H, dtype=self.dtype,
                    param_dtype=self.param_dtype, name="shared_experts",
                )(xt).astype(jnp.float32)
        if compact is None or packed:
            return out.reshape(x.shape).astype(x.dtype)
        return compact.put_back(out.astype(x.dtype))


# The name the layer had while the sigmoid router was its only one.
SigmoidRoutedMoE = RoutedMoE
