"""Where JAX keeps its persistent compilation cache.

Entry points (`chip_smoke.py`, `launch/train.py`, `launch/serve.py`,
`benchmarks/run.py`) call `enable_compile_cache` once, before they compile.
"""
from __future__ import annotations

import os

import jax

#: The checkout's own cache directory (listed in .gitignore). Fixed, because
#: the path is part of what lets a later run find an entry again.
CHECKOUT_CACHE = os.path.normpath(os.path.join(
    os.path.dirname(os.path.abspath(__file__)), os.pardir, os.pardir,
    os.pardir, ".jax_cache"))


def enable_compile_cache() -> str:
    """Turn on the persistent compilation cache; return its directory.

    When ``JAX_COMPILATION_CACHE_DIR`` is set, JAX already reads it and this
    sets nothing. Otherwise the cache goes to `CHECKOUT_CACHE`."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", CHECKOUT_CACHE)
    return CHECKOUT_CACHE
