"""Where compiled device programs persist between processes.

Every (lanes, cols) bucket of the codec costs seconds of XLA compile on
the chip; without a persistent cache a cold daemon spends minutes
compiling.  The path is part of the cache key, so it must not move: no
temporary name, pid or time in it.
"""

from __future__ import annotations

import os

_CHECKOUT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def ensure_compile_cache() -> str:
    """Point JAX's persistent compilation cache at its directory and
    return it.  With JAX_COMPILATION_CACHE_DIR set nothing is set in
    code (JAX reads the variable itself); otherwise the cache is
    `<checkout>/.jax_cache`."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    import jax

    path = os.path.join(_CHECKOUT, ".jax_cache")
    if jax.config.jax_compilation_cache_dir != path:
        jax.config.update("jax_compilation_cache_dir", path)
    return path
