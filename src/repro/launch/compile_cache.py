"""Where the entry points keep JAX's persistent compilation cache.

``enable_compile_cache()`` is called from the ``main()`` of the command-line
entry points and from ``chip_smoke.py``, never at import and never from
tests. Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and
this module sets no other directory; otherwise the cache lives at the fixed
``<repo>/.jax_cache/`` (git-ignored). The path is part of every entry's
key, so it never depends on a temp dir, a pid or a clock.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Point the persistent cache at its directory and return that path.
    Every compilation is cached, however short: the FL steps compile once
    per distinct shape, and many of them compile in well under a second."""
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = str(CACHE_DIR)
        jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    return path
