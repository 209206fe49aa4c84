"""JAX's persistent compilation cache, kept at one fixed place.

Entry points (the launchers, the benchmark workers, ``chip_smoke.py``)
call :func:`enable_compile_cache` first thing in ``main``; importing this
module changes nothing.  Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX
has already read it at start-up and that directory is used as it is.
Otherwise the cache lives at ``<checkout>/.jax_cache`` (git-ignored).
The directory is part of what makes a later run find an entry, so it is
never a temporary, per-process or per-run path.

An entry's key includes each operation's ``op_name``, the path of named
scopes it was traced under.  JAX leaves metadata out of the key by
default, and then a program whose scopes changed loads the executable of
its predecessor, whose operations a device profile attributes by the old
scopes.  Source locations are left out of the metadata (and so of the
key): they hold the caller's frames and the checkout's path, which would
make every entry point and every checkout compile anew.
"""
from __future__ import annotations

import os
from pathlib import Path

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"
DEFAULT_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn on the persistent compilation cache; returns its directory."""
    import jax
    jax.config.update("jax_compilation_cache_include_metadata_in_key", True)
    jax.config.update("jax_traceback_in_locations_limit", 0)
    if os.environ.get(ENV_VAR):
        return jax.config.jax_compilation_cache_dir
    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_DIR))
    return str(DEFAULT_DIR)
