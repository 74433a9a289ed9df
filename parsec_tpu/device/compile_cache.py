"""Where compiled XLA programs persist between processes.

One rule, applied before the first jit of a process by ``Context`` and by
the lowering: a ``JAX_COMPILATION_CACHE_DIR`` from the environment (which
JAX reads itself) or a directory the application already configured is left
alone; otherwise the cache lives at ``<checkout>/.jax_cache``.  The path
is fixed — never a temporary directory, a pid or a date — because a cache
that moves never hits.
"""

from __future__ import annotations

import os

_CHECKOUT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
DEFAULT_DIR = os.path.join(_CHECKOUT, ".jax_cache")


def ensure_compile_cache() -> str:
    """Resolve the persistent compilation cache and return its directory.

    Every program persists, however small or quick to compile: the dynamic
    path's fused per-batch programs and the kernel bodies are exactly
    those, and a machine that keeps nothing would otherwise recompile
    each of them in every process.
    """
    import jax

    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    placed = (os.environ.get("JAX_COMPILATION_CACHE_DIR")
              or jax.config.jax_compilation_cache_dir)
    if placed:
        return placed
    jax.config.update("jax_compilation_cache_dir", DEFAULT_DIR)
    return DEFAULT_DIR
