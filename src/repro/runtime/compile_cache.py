"""JAX's persistent compilation cache at one fixed place.

Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX already reads it and this
module sets nothing else.  Otherwise the cache goes to ``.jax_cache`` at
the root of the checkout: a fixed path, so that a later run in the same
checkout finds what an earlier one compiled, and a directory that
``.gitignore`` lists.
"""

from __future__ import annotations

import os
from pathlib import Path

import jax

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"
CHECKOUT_CACHE = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent cache on; returns the directory in use."""
    path = os.environ.get(ENV_VAR)
    if not path:
        path = str(CHECKOUT_CACHE)
        jax.config.update("jax_compilation_cache_dir", path)
    return path
