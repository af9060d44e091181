"""Where this checkout keeps what it builds at run time.

One git-ignored directory, ``<checkout>/.jax_cache``: XLA's persistent
compilation cache and the native helpers built from ``csrc/``. Its path
is fixed by the checkout alone (never a temp dir, a pid or a time) — the
path is part of a compile-cache key, so a directory that moves never
hits — and nothing else around the checkout is read or written.
"""
from __future__ import annotations

import os

__all__ = ["cache_dir", "enable_compile_cache"]


def cache_dir(*parts: str) -> str:
    """``<checkout>/.jax_cache[/parts...]`` (not created)."""
    root = os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    return os.path.join(root, ".jax_cache", *parts)


def enable_compile_cache(min_compile_time_secs: float = 0.0) -> str:
    """Switch on JAX's persistent compilation cache for this process and
    return its directory. Where ``JAX_COMPILATION_CACHE_DIR`` is set JAX
    already uses it and no directory is set in code; otherwise the cache
    is ``cache_dir()``. Every entry point (chip_smoke.py, benchmark/run.py,
    the fleet worker, the test suite) calls this and sets no other."""
    import jax

    placed = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not placed:
        jax.config.update("jax_compilation_cache_dir", cache_dir())
    jax.config.update("jax_persistent_cache_min_compile_time_secs",
                      min_compile_time_secs)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    return placed or cache_dir()
