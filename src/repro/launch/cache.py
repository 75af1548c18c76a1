"""JAX's persistent compilation cache, at one fixed place.

A run on an accelerator compiles its step programs from cold unless an
earlier process left them in the cache, and the cache is keyed by its
path: a directory that moves never hits.  Entry points call
:func:`enable_compile_cache` once, before they compile anything.  Tests do
not.
"""

from __future__ import annotations

import os
from pathlib import Path

ENV = "JAX_COMPILATION_CACHE_DIR"
CHECKOUT_CACHE = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn on the persistent compilation cache and return its directory.

    When ``JAX_COMPILATION_CACHE_DIR`` is set, JAX already reads it and
    nothing else is set.  Otherwise the cache lives in ``.jax_cache`` at
    the root of the checkout (git-ignored) — never a temporary, per-pid or
    per-time path."""
    import jax

    path = os.environ.get(ENV) or str(CHECKOUT_CACHE)
    if not os.environ.get(ENV):
        jax.config.update("jax_compilation_cache_dir", path)
    return path
