"""Where JAX keeps its persistent compilation cache for this repository's
entry points (``chip_smoke.py``, ``benchmarks/run.py``).

The cache directory is part of each entry's key, so it is a fixed path:
never a temporary name, a pid or a time.
"""

from __future__ import annotations

import os
from pathlib import Path

import jax

#: The in-checkout default, listed in ``.gitignore``.
CHECKOUT_CACHE_DIR = Path(__file__).resolve().parents[2] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn on the persistent compilation cache and return its directory.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX already reads it and
    nothing is set here; otherwise the cache goes to ``CHECKOUT_CACHE_DIR``.
    Call before the first compilation."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(CHECKOUT_CACHE_DIR))
    return str(CHECKOUT_CACHE_DIR)
