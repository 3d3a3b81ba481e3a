"""JAX's persistent compilation cache, placed from outside the program.

Only the entry points that own a chip call ``enable_compile_cache``
(``chip_smoke.py``, ``bench.py`` section children,
``__graft_entry__.py``); importing ``emqx_tpu`` never does, so tests
stay uncached. The directory is part of each entry's key, so it is a
fixed path: ``JAX_COMPILATION_CACHE_DIR`` when set (JAX reads it
itself), else ``<checkout>/.jax_cache``.
"""

from __future__ import annotations

import os

CHECKOUT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def enable_compile_cache() -> str:
    """Turn the cache on; returns its directory."""
    import jax

    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = os.path.join(CHECKOUT, ".jax_cache")
        jax.config.update("jax_compilation_cache_dir", path)
    # the serving path's small programs (patch scatters, the low batch
    # buckets) compile in under JAX's default 1 s floor; keep them too
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    return path
