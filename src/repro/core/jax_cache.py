"""JAX's persistent compilation cache, kept in one fixed place.

Every new program shape costs a compile, and a cold process pays for all
of them.  :func:`enable_compile_cache` lets a later run find the compiled
programs of an earlier one.  Library code never calls it at import; the
entry points (``chip_smoke.py``, ``benchmarks/run.py``) call it first.
"""
from __future__ import annotations

import os

#: ``<checkout>/.jax_cache`` (this file is ``<checkout>/src/repro/core/``);
#: a fixed path, because the cache directory is part of what a later run
#: must find again — a temp name or a pid would never hit
DEFAULT_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))), ".jax_cache")


def enable_compile_cache() -> str:
    """Turn on the persistent compilation cache; return its directory.

    If ``JAX_COMPILATION_CACHE_DIR`` is set, JAX already reads it and
    nothing is set here.  Otherwise the cache goes to :data:`DEFAULT_DIR`.
    """
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    import jax

    jax.config.update("jax_compilation_cache_dir", DEFAULT_DIR)
    return DEFAULT_DIR
