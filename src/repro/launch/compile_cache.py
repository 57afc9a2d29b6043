"""JAX's persistent compilation cache for entry points that run on a chip.

A cold process on a TPU spends much of a short run compiling: every CARD cut
compiles its own pair of split-step programs. The cache keeps those
executables on disk for the next process. Its directory must not move
between runs, or nothing is found again, so it is never derived from a
temporary name, a pid or the time.
"""
from __future__ import annotations

import os

import jax


def enable_compile_cache(default_dir: str) -> None:
    """Turn the persistent cache on, in ``$JAX_COMPILATION_CACHE_DIR``
    when that is set, else in ``default_dir``.
    Call it from ``main``, before the first compilation, never at import."""
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") or default_dir
    jax.config.update("jax_compilation_cache_dir", path)
