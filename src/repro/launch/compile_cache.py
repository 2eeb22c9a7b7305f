"""JAX's persistent compilation cache, kept in one place.

Every entry point calls :func:`enable` before it compiles.  A compiled
program is found again only under the same directory, so the path is
never built from a temporary name, a process id or the time: it is
``$JAX_COMPILATION_CACHE_DIR`` where that is set, and otherwise
``.jax_cache/`` at the root of the checkout (listed in ``.gitignore``).
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

CHECKOUT_CACHE = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable() -> str:
    """Point JAX's persistent compilation cache at its directory; returns
    the directory."""
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") or str(CHECKOUT_CACHE)
    jax.config.update("jax_compilation_cache_dir", path)
    return path
