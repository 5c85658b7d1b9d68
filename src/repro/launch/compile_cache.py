"""JAX's persistent compilation cache for the launchers and ``chip_smoke.py``.

Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and this
module leaves it alone.  Otherwise the cache goes to ``.jax_cache/`` at
the root of the checkout: a fixed path, because the directory is part of
every entry's key, and inside the checkout, because the program writes
nothing outside it (``.gitignore`` lists the directory).  Tests do not
call this: their compiles stay in memory.
"""

from __future__ import annotations

import os
from pathlib import Path

import jax

CHECKOUT_CACHE = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent cache on before the first compile -> its path."""
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir", str(CHECKOUT_CACHE))
    return jax.config.jax_compilation_cache_dir
