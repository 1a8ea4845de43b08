"""Where the entry points keep JAX's persistent compilation cache.

A cache entry is found again only at the path it was written to, so the
default is one fixed directory in the checkout (git-ignored), never a
temporary, per-process or dated one.  Called by the entry points only
(``python -m repro.launch.serve``, ``chip_smoke.py``), never on import.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

DEFAULT_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn on the persistent compilation cache; return its directory.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX already reads it and
    nothing is overridden; otherwise the cache goes to ``DEFAULT_DIR``."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_DIR))
    return str(DEFAULT_DIR)
