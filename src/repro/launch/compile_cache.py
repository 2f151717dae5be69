"""JAX's persistent compilation cache for the entry points.

Entry points (``chip_smoke.py``, the ``benchmarks/`` mains) call
:func:`enable_compile_cache` before their first compile. Importing this
module changes nothing, and neither module imports jax.
"""
from __future__ import annotations

import os
import sys
from pathlib import Path

#: the cache when ``JAX_COMPILATION_CACHE_DIR`` is not set: a fixed path,
#: so that every run of this checkout finds what earlier runs compiled
REPO_CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent compilation cache on and return its directory.

    If ``JAX_COMPILATION_CACHE_DIR`` is set, JAX already uses that
    directory and nothing else is set. Otherwise the variable is set to
    :data:`REPO_CACHE_DIR`: jax reads it when it is imported, so a caller
    that has not imported jax (the campaign parent) stays free of it, and
    its child processes inherit the cache. Where jax is already imported,
    its config is updated too."""
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if path:
        return path
    path = str(REPO_CACHE_DIR)
    os.environ["JAX_COMPILATION_CACHE_DIR"] = path
    if "jax" in sys.modules:
        sys.modules["jax"].config.update("jax_compilation_cache_dir", path)
    return path
