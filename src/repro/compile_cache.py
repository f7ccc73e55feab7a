"""JAX's persistent compilation cache for the entry points that take the
accelerator (``chip_smoke.py``, ``examples/serve_paged.py``,
``benchmarks/run.py``).

Importing this module does not import JAX.
"""
from __future__ import annotations

import os
from pathlib import Path

# fixed, inside the checkout: the directory is part of what a later run
# must find again, so it never depends on a temporary name, pid or time
CACHE_DIR = Path(__file__).resolve().parents[2] / ".jax_cache"


def enable_compile_cache() -> str:
    """Point JAX's compilation cache at its directory; returns the path.

    ``JAX_COMPILATION_CACHE_DIR``, when set, is JAX's own setting and is
    left alone; otherwise the cache lives in ``<checkout>/.jax_cache``."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    import jax
    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    return str(CACHE_DIR)
