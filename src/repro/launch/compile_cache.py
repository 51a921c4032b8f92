"""Where the entry points keep JAX's persistent compilation cache.

Called at the start of each entry point's ``main`` (never at library
import), so every process of one checkout shares its compiled crossbar
schedules, megakernel programs and bucket shapes.
"""

from __future__ import annotations

import os

import jax

# src/repro/launch/compile_cache.py -> the checkout root.
CHECKOUT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.dirname(os.path.abspath(__file__)))))


def use_compile_cache() -> str:
    """Place the persistent compilation cache; returns its directory.

    With ``JAX_COMPILATION_CACHE_DIR`` set, JAX reads it itself and
    nothing is set here.  Otherwise the cache is the fixed
    ``<checkout>/.jax_cache``: the path is part of the cache key, so a
    per-run name would never hit.
    """
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    path = os.path.join(CHECKOUT, ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path
