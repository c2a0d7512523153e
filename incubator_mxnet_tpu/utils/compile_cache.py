"""Persistent XLA compile cache, placed from outside.

Called once by each process that owns a chip (``chip_smoke.py``,
``bench.py``, the examples) before its first compile — NOT on package
import, so the test suite keeps JAX's default (no persistent cache).

The cache directory is part of nothing the program decides at run time:
if ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and this
module sets no other; otherwise the cache lives at ``<checkout>/.jax_cache``
(git-ignored), a fixed path resolved from this file's location. The path
feeds the cache's keys, so a directory that moves (a temp dir, a pid, a
timestamp) would never hit.
"""

from __future__ import annotations

import os

__all__ = ["enable"]

_CHECKOUT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def enable() -> str:
    """Turn the persistent compile cache on; returns the directory in
    force."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    import jax

    path = os.path.join(_CHECKOUT, ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path
