"""Where JAX keeps compiled programs between processes.

One full-width verify program costs tens of seconds to compile per bucket,
so every entry point (``repro`` on the command line, ``chip_smoke.py``)
turns JAX's persistent compilation cache on before its first compile.
Importing ``repro`` never does: a library must not move a caller's cache.

If ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and nothing
here overrides it.  Otherwise the cache lives in ``.jax_cache/`` at the root
of the checkout: a fixed path, because the path is part of what the cache
matches on, and a directory that moves never hits.
"""

from __future__ import annotations

import os
from pathlib import Path

CACHE_DIR = Path(__file__).resolve().parents[2] / ".jax_cache"


def configure_compile_cache() -> str:
    """Turn the persistent compilation cache on; returns its directory."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    import jax

    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    return str(CACHE_DIR)
