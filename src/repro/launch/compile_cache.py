"""Where the entry points keep JAX's persistent compilation cache."""
from __future__ import annotations

import os
from pathlib import Path

import jax

CHECKOUT = Path(__file__).resolve().parents[3]


def use_compile_cache() -> str:
    """Turn the persistent compilation cache on before the first compile and
    return its directory.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and
    nothing is set here.  Otherwise the cache is ``<checkout>/.jax_cache``:
    a fixed path, so that one run finds what an earlier run compiled.
    """
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    path = str(CHECKOUT / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path
