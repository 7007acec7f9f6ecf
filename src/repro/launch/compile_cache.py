"""JAX's persistent compilation cache, placed from outside or at a
fixed path.

A cache hits only when its directory stays where it was, so the default
is ``<repo>/.jax_cache``: never a temporary name, a process id or the
time.  ``JAX_COMPILATION_CACHE_DIR``, when set, is JAX's own setting and
wins; then nothing else is set.
"""
from __future__ import annotations

import os
import pathlib

DEFAULT_DIR = pathlib.Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent compilation cache on; returns its directory.
    Call from an entry point, never on import."""
    import jax
    outside = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if outside:
        return outside
    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_DIR))
    return str(DEFAULT_DIR)
