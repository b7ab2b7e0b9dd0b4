"""JAX's persistent compilation cache for the launchers and chip scripts.

Called from ``main()`` of each entry point, never at import: library users
and the tests keep JAX's defaults and write no cache.
"""
from __future__ import annotations

import os

import jax

# <checkout>/.cache/jax — src/repro/launch/ is three levels below the root.
_CHECKOUT = os.path.dirname(
    os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
)
DEFAULT_DIR = os.path.join(_CHECKOUT, ".cache", "jax")


def enable_compile_cache() -> str:
    """Turn on the persistent compilation cache; returns its directory.

    ``JAX_COMPILATION_CACHE_DIR``, when set, is JAX's own setting and is
    left alone. Otherwise the cache lives at the fixed ``DEFAULT_DIR``: the
    directory is part of what a later run looks up, so it must not move
    between runs.
    """
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", DEFAULT_DIR)
    return DEFAULT_DIR
