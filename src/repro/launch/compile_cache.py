"""JAX's persistent compilation cache, kept at one fixed place.

Entry points (the launchers, the benchmark workers, ``chip_smoke.py``)
call :func:`enable_compile_cache` first thing in ``main``; importing this
module changes nothing.  Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX
has already read it at start-up and that directory is used as it is.
Otherwise the cache lives at ``<checkout>/.jax_cache`` (git-ignored).
The directory is part of what makes a later run find an entry, so it is
never a temporary, per-process or per-run path.
"""
from __future__ import annotations

import os
from pathlib import Path

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"
DEFAULT_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn on the persistent compilation cache; returns its directory."""
    import jax
    if os.environ.get(ENV_VAR):
        return jax.config.jax_compilation_cache_dir
    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_DIR))
    return str(DEFAULT_DIR)
