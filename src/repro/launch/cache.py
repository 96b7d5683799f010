"""Where the launchers keep JAX's persistent compilation cache."""
from __future__ import annotations

import os
import pathlib

# <checkout>/.cache/jax: a fixed path, because the cache key includes it
REPO_CACHE_DIR = pathlib.Path(__file__).resolve().parents[3] / ".cache" / "jax"


def enable_compile_cache() -> str:
    """Turn on the persistent compile cache before the first compile and
    return its directory.  ``JAX_COMPILATION_CACHE_DIR``, when set, is
    read by JAX itself and nothing is changed here; otherwise the cache
    lives at :data:`REPO_CACHE_DIR`."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    import jax
    jax.config.update("jax_compilation_cache_dir", str(REPO_CACHE_DIR))
    return str(REPO_CACHE_DIR)
