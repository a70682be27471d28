"""Where JAX keeps compiled programs between processes.

A cold start compiles every step program and kernel (minutes for a 7B
model); the persistent cache turns a restart into reads. The cache key
includes the directory's path, so the path is fixed per checkout: never
a temp name, a pid or a timestamp, any of which would make every entry
a miss.
"""

from __future__ import annotations

import os

_CHECKOUT = os.path.dirname(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
)


def enable_compile_cache() -> str:
    """Turn the persistent compilation cache on; call before the first
    compile. ``JAX_COMPILATION_CACHE_DIR`` wins when set — JAX reads it
    itself, so nothing is touched. Otherwise the cache lives in
    ``<checkout>/.jax_cache``. Returns the directory in use."""
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if path:
        return path
    import jax

    path = os.path.join(_CHECKOUT, ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path
