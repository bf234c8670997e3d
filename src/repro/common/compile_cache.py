"""JAX's persistent compilation cache for the program's entry points.

Where `JAX_COMPILATION_CACHE_DIR` is set, JAX keeps its cache there and
nothing here overrides it. Otherwise the cache lives at the fixed path
`<checkout>/.jax_cache`: the path is part of the cache key, so it never
depends on a temp dir, a pid or a time. Entry points call `enable()`;
importing the package never does, so the tests stay cache-free.
"""

from __future__ import annotations

import os

import jax

CHECKOUT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.dirname(os.path.abspath(__file__)))))


def enable() -> str:
    """Turn the persistent compilation cache on; returns its directory."""
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = os.path.join(CHECKOUT, ".jax_cache")
        jax.config.update("jax_compilation_cache_dir", path)
    return path
