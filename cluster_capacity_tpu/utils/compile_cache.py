"""Where JAX's persistent compilation cache lives.

One rule for every entry point that turns the cache on (chip_smoke.py,
bench.py): when the caller set ``JAX_COMPILATION_CACHE_DIR``, JAX reads it
itself and this module sets no other directory; otherwise the cache goes to
``<repo>/.jax_cache``.  The path is part of the cache key, so it carries no
host hash, pid or time.
"""

from __future__ import annotations

import os

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
DEFAULT_DIR = os.path.join(REPO_ROOT, ".jax_cache")


def enable() -> str:
    """Turn the persistent cache on for every compile; returns its path."""
    import jax
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = DEFAULT_DIR
        jax.config.update("jax_compilation_cache_dir", path)
    # the kernels compile in about a second: cache them all
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path
