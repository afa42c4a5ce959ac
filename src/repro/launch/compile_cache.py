"""JAX's persistent compilation cache, set up once by each entry point.

Called from ``main`` of the entry points (``launch.serve``,
``launch.train``, ``benchmarks.run``, ``chip_smoke.py``), never at import:
tests and library callers keep JAX's own default (no persistent cache).
"""

from __future__ import annotations

import os
import pathlib

import jax

#: the checkout root (``src/repro/launch`` -> three levels up)
REPO_ROOT = pathlib.Path(__file__).resolve().parents[3]

#: fixed in-checkout cache path (git-ignored).  The path is part of what a
#: cache hit depends on, so it never contains a temporary name, a PID or
#: the time.
DEFAULT_CACHE_DIR = REPO_ROOT / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn on the persistent compilation cache and return its directory.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX already reads it and
    nothing else is set here.  Otherwise the cache goes to
    :data:`DEFAULT_CACHE_DIR`.  Every executable is cached, however fast
    it compiled, so a second run of the same program compiles nothing.
    """
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = str(DEFAULT_CACHE_DIR)
        jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path
