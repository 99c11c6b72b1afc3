"""What the benchmark's entry points and its loaders share."""

from __future__ import annotations

import importlib.util
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
# JAX's persistent compilation cache: a fixed directory inside the checkout.
# ``JAX_COMPILATION_CACHE_DIR`` is not followed, so that two checkouts run
# side by side (a parent and a change) never share compiled programs, and
# the path, which is part of each entry's key, never moves.
CACHE_DIR = ROOT / ".jax_cache"


def enable_compile_cache() -> None:
    """Point JAX at ``CACHE_DIR`` and cache every program, however quickly
    it compiled (by default JAX skips those under a second, and a run then
    recompiles them all)."""
    import jax
    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)


def load_module(path: Path, kind: str):
    """The Python file at ``path`` as a module (a metric's reader, a mix's
    generator); ``kind`` names it in the error when the file is missing."""
    if not path.is_file():
        raise FileNotFoundError(f"no {kind} at {path}")
    spec = importlib.util.spec_from_file_location(
        "perfbench_" + path.stem.replace(".", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod
