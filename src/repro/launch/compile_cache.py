"""JAX's persistent compilation cache for the entry points that run on
the chip (``chip_smoke.py``, ``launch/search_serve.py``).

Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and this
module sets nothing.  Otherwise the cache goes to ``.jax_cache/`` at the
repository root: a fixed path, so a later process of the same checkout
finds what an earlier one compiled (a path that changed per run would
never hit).
"""

from __future__ import annotations

import os
import pathlib

CACHE_DIR = pathlib.Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Point JAX's persistent compilation cache at its directory and
    return that directory."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    import jax
    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    return str(CACHE_DIR)
