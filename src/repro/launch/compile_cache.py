"""JAX's persistent compilation cache, turned on by the entry points.

Called at the start of ``chip_smoke.py``, ``repro.launch.train`` and
``repro.launch.serve`` — never at import time and never in tests.
"""
from __future__ import annotations

import os
from pathlib import Path

#: the checkout root (``<checkout>/src/repro/launch/compile_cache.py``)
CHECKOUT = Path(__file__).resolve().parents[3]
DEFAULT_CACHE_DIR = CHECKOUT / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn on the persistent compilation cache and return its directory.

    ``JAX_COMPILATION_CACHE_DIR``, when set, is JAX's own setting and is left
    alone.  Otherwise the cache lives at ``<checkout>/.jax_cache``: a fixed
    path, since the directory is part of what a later run must find again.
    """
    import jax
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_CACHE_DIR))
    return str(DEFAULT_CACHE_DIR)
