"""Parameter partition rules: param-tree paths → ``PartitionSpec``.

The reference has no tensor parallelism at all (SURVEY.md §2.4: DP via
MPI byte-range sharding is its only axis); TP exists here for the
Llama-family sentiment config the north star requires.

The tensor-parallel layout follows the Megatron/scaling-book recipe: QKV
projections split the *head* axis over ``tp`` and the output projection
splits the *input* head axis (one all-reduce per attention block); MLP
up/gate split the hidden axis, down splits the input axis (one all-reduce
per MLP); embeddings and the LM head split the vocab axis.  Norm scales and
biases replicate.  XLA inserts the psums from these shardings — there is no
hand-written collective in the model code.
"""

from __future__ import annotations

import re
from typing import List, Tuple

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

# (path regex, spec) — first match wins.  Paths are "/"-joined param tree
# keys, e.g. "encoder/layer_0/attention/q_proj/kernel".
TP_RULES: List[Tuple[str, P]] = [
    # attention: kernel [dim, heads, head_dim] — shard heads
    (r".*(q_proj|k_proj|v_proj)/kernel$", P(None, "tp", None)),
    # attention bias [heads, head_dim] (BERT family) — shard heads to match
    (r".*(q_proj|k_proj|v_proj)/bias$", P("tp", None)),
    # latent attention: the up-projection [rank, heads, nope + v] shards
    # its heads like q_proj; the down-projection (kv_a_proj, one latent and
    # one RoPE key a token, shared by every head) matches no rule and
    # replicates, as does the latent cache it fills
    (r".*kv_b_proj/kernel$", P(None, "tp", None)),
    # output proj: kernel [heads, head_dim, dim] — shard input heads
    (r".*o_proj/kernel$", P("tp", None, None)),
    # gated MLP: [dim, hidden] / [hidden, dim]
    (r".*(gate_proj|up_proj)/kernel$", P(None, "tp")),
    (r".*down_proj/kernel$", P("tp", None)),
    # MoE expert stacks: [E, dim, hidden] / [E, hidden, dim] — expert axis
    # over ep, hidden over tp; router and e_score_correction_bias
    # replicated (match no rule); shared experts are a SwiGLU (rules above)
    (r".*(gate_experts|up_experts)$", P("ep", None, "tp")),
    (r".*down_experts$", P("ep", "tp", None)),
    # BERT-style MLP
    (r".*ffn/lin1/kernel$", P(None, "tp")),
    (r".*ffn/lin2/kernel$", P("tp", None)),
    (r".*ffn/lin1/bias$", P("tp")),
    # vocab-sharded embedding + LM head
    (r".*(word_embeddings|tok_embeddings)/embedding$", P("tp", None)),
    (r".*lm_head/kernel$", P(None, "tp")),
]


# Decode-runtime KV layout: logical axis name → mesh axis (the
# ``DEFAULT_RULES`` dict shape of megatron-style jax stacks).  Both KV
# layouts the serving stack compiles — the monolithic slot cache
# ``[n_slots, max_total, n_kv_heads, head_dim]`` and the paged pool
# ``[n_pages + 1, page_size, n_kv_heads, head_dim]`` — put the KV-head
# axis third, matching the q/k/v projections' head sharding above, so
# per-head attention never crosses the tp axis and the only decode-path
# collective stays the o_proj all-reduce the param rules already imply.
DECODE_KV_RULES = {
    "slots": None,      # slot / physical-page axis: every chip sees all slots
    "pages": None,
    "tokens": None,     # sequence axis: attention reduces over it per head
    "kv_heads": "tp",   # shard heads with the projections that feed them
    "head_dim": None,
    "lengths": None,    # per-slot write offsets: tiny, replicated
}


def kv_cache_spec(mesh: Mesh, n_kv_heads: int) -> Tuple[P, P]:
    """(keys/values spec, length spec) for a decode KV cache on ``mesh``.

    The head axis shards over ``tp`` only when the mesh has a tp axis
    that divides ``n_kv_heads`` — otherwise the cache replicates, so a
    dp-only mesh (or a tp size the head count can't split) degrades to
    the single-chip layout instead of failing placement.
    """
    head_axis = DECODE_KV_RULES["kv_heads"]
    axis_sizes = dict(zip(mesh.axis_names, mesh.devices.shape))
    tp = axis_sizes.get(head_axis, 1)
    if tp > 1 and n_kv_heads % tp == 0:
        kv = P(None, None, head_axis, None)
    else:
        kv = P()
    return kv, P()


def shard_kv_caches(caches, mesh: Mesh, n_kv_heads: int):
    """Place freshly-initialized decode KV caches on ``mesh`` per
    :data:`DECODE_KV_RULES` (keys/values head-sharded over tp, lengths
    replicated).  ``caches`` is the per-layer list of ``KVCache`` the
    runtimes' ``init_caches`` builds; the dataclass is rebuilt leaf by
    leaf so donated-buffer identity is preserved elsewhere."""
    import dataclasses

    kv_spec, len_spec = kv_cache_spec(mesh, n_kv_heads)
    kv_sh = NamedSharding(mesh, kv_spec)
    len_sh = NamedSharding(mesh, len_spec)
    out = []
    for c in caches:
        extra = {}
        if getattr(c, "key_scale", None) is not None:
            # int8 paged pools (ops/kv_pages.QuantizedKVPages) carry
            # per-(page, row) scale planes: no head axis, so they
            # replicate like the lengths.
            extra = dict(
                key_scale=jax.device_put(c.key_scale, len_sh),
                value_scale=jax.device_put(c.value_scale, len_sh),
            )
        out.append(
            dataclasses.replace(
                c,
                keys=jax.device_put(c.keys, kv_sh),
                values=jax.device_put(c.values, kv_sh),
                length=jax.device_put(c.length, len_sh),
                **extra,
            )
        )
    return out


def spec_for_path(path: str, rules=None) -> P:
    for pattern, spec in rules or TP_RULES:
        if re.match(pattern, path):
            return spec
    return P()  # replicate


def _path_str(path) -> str:
    parts = []
    for p in path:
        part = getattr(p, "key", None)
        if part is None:
            part = getattr(p, "idx", None)
        if part is None:
            # register_dataclass fields flatten with GetAttrKey(.name)
            part = getattr(p, "name", None)
        parts.append(str(p if part is None else part))
    return "/".join(parts)


def _is_quantized(leaf) -> bool:
    from music_analyst_tpu.ops.quant import QuantizedParam

    return isinstance(leaf, QuantizedParam)


def _quantized_specs(qp, base: P):
    """Spec-holding QuantizedParam for a stored-quantized kernel.

    ``q`` keeps the float kernel's rule (same rank — int4 halves axis 0
    but keeps head/hidden divisibility, e.g. 8B o_proj heads 32→16 still
    split by tp=4); ``scale`` replicates its leading group axis and
    inherits the kernel's *feature*-axis placement so the epilogue
    multiply needs no resharding.  Meta fields are preserved, so the spec
    tree stays structure-congruent with the param tree.
    """
    import dataclasses

    padded = tuple(base) + (None,) * (len(qp.shape) - len(tuple(base)))
    scale_spec = P(None, *padded[qp.n_contract:])
    return dataclasses.replace(qp, q=base, scale=scale_spec)


def partition_specs(params, rules=None):
    """PartitionSpec pytree matching ``params``.

    ``QuantizedParam`` leaves are resolved atomically — the rule lookup
    sees the kernel's tree path (".../kernel"), not the dataclass's inner
    ``q``/``scale`` fields — and come back as a QuantizedParam holding one
    spec per data field.
    """

    def _spec(path, leaf):
        spec = spec_for_path(_path_str(path), rules)
        if _is_quantized(leaf):
            return _quantized_specs(leaf, spec)
        return spec

    return jax.tree_util.tree_map_with_path(
        _spec, params, is_leaf=lambda x: _is_quantized(x)
    )


def prune_spec(spec: P, axis_names) -> P:
    """Drop axes absent from the mesh (so the same rules serve a dp-only
    mesh, a dp×tp mesh, etc.).  The single definition used by
    ``shard_params`` and by abstract-lowering tests, so test placement
    can't silently diverge from production placement."""
    return P(*(a if a in axis_names else None for a in spec))


def shard_params(params, mesh: Mesh, rules=None, drop_unused_axes: bool = True):
    """Place a param tree on ``mesh`` according to the rules.

    Axes named in a rule but absent from the mesh are dropped from the
    spec via :func:`prune_spec`.
    """
    axis_names = set(mesh.axis_names)

    def _place(path, leaf):
        spec = spec_for_path(_path_str(path), rules)
        if _is_quantized(leaf):
            import dataclasses

            specs = _quantized_specs(leaf, spec)
            if drop_unused_axes:
                specs = dataclasses.replace(
                    specs,
                    q=prune_spec(specs.q, axis_names),
                    scale=prune_spec(specs.scale, axis_names),
                )
            return dataclasses.replace(
                leaf,
                q=jax.device_put(leaf.q, NamedSharding(mesh, specs.q)),
                scale=jax.device_put(
                    leaf.scale, NamedSharding(mesh, specs.scale)
                ),
            )
        if drop_unused_axes:
            spec = prune_spec(spec, axis_names)
        return jax.device_put(leaf, NamedSharding(mesh, spec))

    return jax.tree_util.tree_map_with_path(
        _place, params, is_leaf=lambda x: _is_quantized(x)
    )
