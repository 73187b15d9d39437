"""Where JAX keeps its persistent compilation cache.

A cache directory is part of what the cache is keyed on, so it must not
move between runs: a temporary, pid- or time-based path never hits.

* ``JAX_COMPILATION_CACHE_DIR`` set: JAX reads it itself, and this module
  sets nothing.
* Otherwise: ``<checkout>/.jax_cache`` (ignored by git).
"""

from __future__ import annotations

import os
from pathlib import Path

CHECKOUT = Path(__file__).resolve().parents[3]
DEFAULT_DIR = CHECKOUT / ".jax_cache"


def setup_compile_cache() -> str:
    """Turn on the persistent compilation cache; returns its directory."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    import jax

    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_DIR))
    return str(DEFAULT_DIR)
