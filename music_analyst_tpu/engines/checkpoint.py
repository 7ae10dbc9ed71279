"""Checkpoint / resume for training state (Orbax-backed) and the
streaming weight-quantized inference loader.

The reference has no checkpointing at all — every run recomputes from the
CSV (SURVEY.md §5 "Checkpoint/resume: none").  Training at framework scale
needs real save/restore: Orbax handles sharded arrays natively, so a
TrainState saved from a dp×tp mesh restores onto any mesh with the same
global shapes.

``load_quantized_params`` is the inference-side counterpart: HF torch
tensors are read layer-by-layer (the model families expose per-unit
iterators over mmap'd shards), quantized on host in numpy, and device-put
through the bounded-depth ``runtime/prefetch.py`` pipeline — H2D of layer
*k+1* overlaps quantization of layer *k*, and the full float tree never
exists (peak host staging is O(one layer); ``last_load_stats()`` exposes
the measured peak for the test that pins this).  Quantized leaves are
optionally persisted through the content-addressed ``engines/wq_cache.py``
so the quantize + transfer costs are paid once per (checkpoint, scheme).
"""

from __future__ import annotations

import math
import os
import threading
import time
from typing import Any, Callable, Dict, Iterable, List, Optional, Tuple

import jax
import numpy as np

from music_analyst_tpu.engines.train import TrainState
from music_analyst_tpu.resilience.faults import fault_point
from music_analyst_tpu.telemetry import register_manifest_section


def _checkpointer():
    import orbax.checkpoint as ocp

    return ocp.PyTreeCheckpointer()


def save_train_state(state: TrainState, path: str) -> str:
    """Save to ``path`` (absolute or cwd-relative); returns the path."""
    import orbax.checkpoint as ocp

    path = os.path.abspath(path)
    _checkpointer().save(
        path,
        {
            "params": state.params,
            "opt_state": state.opt_state,
            "step": np.asarray(state.step),
        },
        force=True,
    )
    return path


def restore_train_state(
    path: str,
    like: Optional[TrainState] = None,
) -> TrainState:
    """Restore; with ``like`` given, restores onto its shardings/structure."""
    import orbax.checkpoint as ocp

    path = os.path.abspath(path)
    if like is not None:
        template = {
            "params": like.params,
            "opt_state": like.opt_state,
            "step": np.asarray(like.step),
        }
        restored = _checkpointer().restore(path, item=template)
    else:
        restored = _checkpointer().restore(path)
    return TrainState(
        params=restored["params"],
        opt_state=restored["opt_state"],
        step=jax.numpy.asarray(restored["step"]),
    )


# ---------------------------------------------------------------------------
# Streaming weight-quantized load (quantize-on-load + bounded-depth H2D)
# ---------------------------------------------------------------------------

# Stats of the most recent load_quantized_params call in this process —
# read by tests (O(one layer) peak-staging assertion) and the wq_store
# bench suite.  Guarded by a lock only for the in-flight byte accounting;
# the snapshot is written once at the end of a load.
_LOAD_LOCK = threading.Lock()
_LAST_LOAD_STATS: Dict[str, Any] = {}


def last_load_stats() -> Dict[str, Any]:
    """Snapshot of the most recent quantized load (empty before any)."""
    with _LOAD_LOCK:
        return dict(_LAST_LOAD_STATS)


def _manifest_section() -> Dict[str, Any]:
    """Quantized-checkpoint cache hit/miss/stores/bytes-saved plus the
    most recent streaming load's peak-host-staging digest — only once the
    cache was consulted or a load ran."""
    from music_analyst_tpu.engines import wq_cache

    stats, load = wq_cache.cache_stats(), last_load_stats()
    if not (any(stats.values()) or load):
        return {}
    return {**stats, **({"last_load": load} if load else {})}


register_manifest_section("wq_cache", _manifest_section)


def _leaf_bytes(leaf) -> int:
    from music_analyst_tpu.ops.quant import QuantizedParam

    if isinstance(leaf, QuantizedParam):
        return _leaf_bytes(leaf.q) + _leaf_bytes(leaf.scale)
    return int(math.prod(leaf.shape)) * np.dtype(leaf.dtype).itemsize


def _set_tree_path(tree, path: str, leaf) -> None:
    parts = path.split("/")
    node = tree
    for part in parts[:-1]:
        node = node[part]
    if parts[-1] not in node:
        raise KeyError(path)
    node[parts[-1]] = leaf


def _device_put_leaf(leaf, path: str, mesh, axis_names):
    """Place one (possibly quantized) leaf per the TP sharding rules."""
    from music_analyst_tpu.ops.quant import QuantizedParam
    from music_analyst_tpu.parallel import sharding as sh

    if mesh is None:
        return jax.tree_util.tree_map(jax.device_put, leaf)
    from jax.sharding import NamedSharding

    base = sh.spec_for_path(path)
    if isinstance(leaf, QuantizedParam):
        import dataclasses

        specs = sh._quantized_specs(leaf, base)
        return dataclasses.replace(
            leaf,
            q=jax.device_put(
                np.ascontiguousarray(leaf.q),
                NamedSharding(mesh, sh.prune_spec(specs.q, axis_names)),
            ),
            scale=jax.device_put(
                np.ascontiguousarray(leaf.scale),
                NamedSharding(mesh, sh.prune_spec(specs.scale, axis_names)),
            ),
        )
    return jax.device_put(
        np.ascontiguousarray(leaf),
        NamedSharding(mesh, sh.prune_spec(base, axis_names)),
    )


def load_quantized_params(
    params_shape,
    unit_source: Callable[[], Iterable[Tuple[str, List[Tuple[str, Any]]]]],
    scheme: str,
    group_size: Optional[int] = None,
    mesh=None,
    cache_dir: Optional[str] = None,
    cache_key: Optional[str] = None,
    prefetch_depth: Optional[int] = None,
):
    """Stream a checkpoint into a device-resident weight-quantized tree.

    ``params_shape`` — the float param tree's *structure* (arrays or
    ``ShapeDtypeStruct``s; never materialized).  ``unit_source`` — a
    zero-arg callable yielding ``(unit_name, [(tree_path, np_array), …])``
    per layer-sized unit (``models/llama.py`` / ``models/distilbert.py``
    iterators); it is only invoked on a cache miss, so a warm load never
    touches torch.  Returns the param tree with ``QuantizedParam`` leaves
    for every rule-matched kernel, every leaf on device.
    """
    from music_analyst_tpu.engines import wq_cache
    from music_analyst_tpu.ops.quant import (
        WQ_DEFAULT_GROUP,
        quantize_array,
        wq_rule_for_path,
    )
    from music_analyst_tpu.runtime.prefetch import (
        PrefetchPipeline,
        Stage,
        resolve_prefetch_depth,
    )

    group_size = WQ_DEFAULT_GROUP if group_size is None else group_size
    depth = resolve_prefetch_depth(prefetch_depth)
    axis_names = set(mesh.axis_names) if mesh is not None else ()
    t0 = time.monotonic()

    cached = wq_cache.iter_entry_or_none(cache_dir, cache_key)
    cache_state = "off" if not (cache_dir and cache_key) else (
        "hit" if cached is not None else "miss"
    )
    writer = None
    if cached is not None:
        # Warm path: leaves come back quantized (mmap'd) — H2D only.  One
        # pipeline item per leaf keeps the in-flight window bounded just
        # like the cold path's layer units.
        units: Iterable = [(path, [(path, leaf)]) for path, leaf in cached]
    else:
        units = unit_source()
        if cache_dir and cache_key:
            writer = wq_cache.WqCacheWriter(cache_dir, cache_key)

    staged = {"now": 0, "peak": 0, "units": 0, "leaves": 0}

    def _stage_quantize(item):
        unit_name, leaves = item
        # First statement on purpose: an injected checkpoint.load trip
        # raises before any staging/writer side effect, so the prefetch
        # stage retry re-runs the unit from scratch.
        fault_point("checkpoint.load", unit=unit_name)
        float_bytes = sum(_leaf_bytes(leaf) for _, leaf in leaves)
        with _LOAD_LOCK:
            staged["now"] += float_bytes
            staged["peak"] = max(staged["peak"], staged["now"])
            staged["units"] += 1
            staged["leaves"] += len(leaves)
        out = []
        for path, leaf in leaves:
            n_contract = wq_rule_for_path(path)
            if n_contract is not None and not _is_quantized(leaf):
                leaf = quantize_array(
                    np.asarray(leaf), scheme, n_contract, group_size
                )
            if writer is not None:
                writer.add(path, leaf)
            out.append((path, leaf))
        with _LOAD_LOCK:
            staged["now"] -= float_bytes
        return unit_name, out

    def _is_quantized(leaf) -> bool:
        from music_analyst_tpu.ops.quant import QuantizedParam

        return isinstance(leaf, QuantizedParam)

    def _stage_h2d(item):
        unit_name, leaves = item
        fault_point("h2d.transfer", unit=unit_name)
        return unit_name, [
            (path, _device_put_leaf(leaf, path, mesh, axis_names))
            for path, leaf in leaves
        ]

    # None marks a not-yet-loaded slot; built with a plain dict walk (NOT
    # tree_map) because jax treats None as an *empty subtree*, which would
    # make the completeness check below vacuous.
    def _none_like(node):
        if isinstance(node, dict):
            return {k: _none_like(v) for k, v in node.items()}
        return None

    def _missing_paths(node, prefix=""):
        if isinstance(node, dict):
            out = []
            for k, v in node.items():
                out.extend(_missing_paths(v, f"{prefix}{k}/"))
            return out
        return [prefix[:-1]] if node is None else []

    out_tree = _none_like(params_shape)
    pipeline = PrefetchPipeline(
        [
            Stage("wq_quantize", _stage_quantize),
            Stage("wq_h2d", _stage_h2d),
        ],
        depth=depth,
        name="wq_load",
        sink_name="assemble",
    )
    for _, leaves in pipeline.run(units):
        for path, leaf in leaves:
            _set_tree_path(out_tree, path, leaf)
    published = writer.publish() if writer is not None else False

    missing = _missing_paths(out_tree)
    if missing:
        raise ValueError(
            "checkpoint stream did not cover the param tree; missing: "
            + ", ".join(missing[:8])
        )

    stats = {
        "scheme": scheme,
        "group_size": group_size,
        "cache": cache_state,
        "cache_stored": bool(published),
        "peak_host_staging_bytes": staged["peak"],
        "units": staged["units"],
        "leaves": staged["leaves"],
        "prefetch_depth": depth,
        "load_seconds": round(time.monotonic() - t0, 6),
    }
    with _LOAD_LOCK:
        _LAST_LOAD_STATS.clear()
        _LAST_LOAD_STATS.update(stats)
    try:
        from music_analyst_tpu.telemetry import get_telemetry

        tel = get_telemetry()
        tel.gauge("wq_load.peak_host_staging_bytes", staged["peak"])
        tel.gauge("wq_load.seconds", stats["load_seconds"])
        tel.count(f"wq_load.cache_{cache_state}")
    except Exception:
        pass
    return out_tree
