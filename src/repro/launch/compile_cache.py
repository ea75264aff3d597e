"""JAX's persistent compile cache for the entry points.

Called by each entry point's ``main`` (never on ``import repro``), so every
process that serves or trains reuses the programs an earlier one compiled.
"""

from __future__ import annotations

import os
from pathlib import Path

import jax

#: the checkout this package runs from (``src/repro/launch`` → root)
CHECKOUT = Path(__file__).resolve().parents[3]


def enable_compile_cache() -> str:
    """Turn the persistent compile cache on and return its directory.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX already reads it and
    nothing is set here.  Otherwise the cache lives at the fixed
    ``<checkout>/.jax_cache``: a fixed path, because the directory is part
    of what a later process must find again."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    path = str(CHECKOUT / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path
