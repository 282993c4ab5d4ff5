"""Where JAX keeps its persistent compilation cache.

Entry points (chip_smoke.py, examples/) call
`enable_compile_cache()` once before their first compile; importing the
package sets nothing.  When `JAX_COMPILATION_CACHE_DIR` is set, JAX reads it
itself and this module sets nothing.  Otherwise the cache lives at a fixed
path inside the checkout: the path is part of the cache key, so a directory
that moves between runs never hits.
"""

from __future__ import annotations

import os

CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(
        __file__)))), ".jax_cache")


def enable_compile_cache() -> str:
    """Point JAX's persistent compilation cache at its directory; returns
    the directory in use."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    import jax
    jax.config.update("jax_compilation_cache_dir", CACHE_DIR)
    return CACHE_DIR
