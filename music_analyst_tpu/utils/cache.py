"""Persistent XLA compilation cache.

No reference analogue: the reference recompiles nothing (ahead-of-time C
binary) but also re-does its column-split preprocessing on every run
(``src/parallel_spotify.c:821``); here the expensive per-run artifact is
the XLA program, and it persists.

One rule, applied once per process at the entry point (``cli/main.py``,
``bench.py``, ``chip_smoke.py``'s kernel child):

* ``JAX_COMPILATION_CACHE_DIR`` set — JAX reads it itself; this module
  sets **no** directory in code, so whoever launched the process owns
  where the cache lives.
* unset — one fixed directory inside the checkout, ``<repo>/.jax_cache``
  (git-ignored).  Never the home directory, a temp name, a pid or a
  time: the path is part of the cache key, so a directory that moves
  never hits.

A cache that cannot be enabled raises; it is not an optimization the run
quietly goes without.
"""

from __future__ import annotations

import os

REPO_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)
    ))),
    ".jax_cache",
)


def enable_persistent_compilation_cache() -> str:
    """Apply the rule above; returns the directory in effect."""
    import jax

    cache_dir = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not cache_dir:
        cache_dir = REPO_CACHE_DIR
        os.makedirs(cache_dir, exist_ok=True)
        jax.config.update("jax_compilation_cache_dir", cache_dir)
    # Programs here are many and small (bucketed shapes); JAX's default
    # 1 s floor would leave most of them out of the cache.
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.2)
    return cache_dir
