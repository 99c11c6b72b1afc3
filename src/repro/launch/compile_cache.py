"""JAX's persistent compilation cache, placed for the process entry points.

The scripts that own a process call :func:`enable_compile_cache` first
(``chip_smoke.py``, ``repro.launch.serve``, ``benchmarks/run.py``);
importing ``repro`` never touches the cache.  A TinyLlama-width step
program takes tens of seconds to compile, and the cache turns that into a
read on every later run in the same checkout.
"""

from __future__ import annotations

import os
from pathlib import Path

import jax

# <checkout>/.jax_cache (gitignored).  The path is fixed: the cache is only
# found again at the path it was written to.
DEFAULT_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent compilation cache on; returns its directory.

    ``JAX_COMPILATION_CACHE_DIR``, when set, is JAX's own setting and is
    left to JAX; otherwise the cache goes to :data:`DEFAULT_DIR`."""
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = str(DEFAULT_DIR)
        jax.config.update("jax_compilation_cache_dir", path)
    return path
