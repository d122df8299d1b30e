"""JAX's persistent compilation cache for the entry points.

Called by ``chip_smoke.py`` and ``repro.launch.train`` before their first
compile, never at import. Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX
already reads it and nothing here overrides it. Otherwise the cache lives
at ``.jax_cache/`` in the repository root: a fixed path, because the
directory is part of what a cache hit depends on, so a path made from a
temp name, a pid or the time would never be found again.
"""

from __future__ import annotations

import os

DEFAULT_DIR = os.path.abspath(os.path.join(
    os.path.dirname(__file__), "..", "..", "..", ".jax_cache"))


def enable_compile_cache() -> str:
    """Turn the persistent cache on; returns the directory it uses."""
    import jax

    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = DEFAULT_DIR
        jax.config.update("jax_compilation_cache_dir", path)
    return path
