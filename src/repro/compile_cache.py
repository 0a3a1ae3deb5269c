"""Persistent XLA compilation cache for the repo's entry points.

JAX reads ``JAX_COMPILATION_CACHE_DIR`` itself; where it is set, that
directory is the cache and nothing here overrides it.  Where it is not,
an entry point (``chip_smoke.py``, ``benchmarks/``) calls
:func:`use_checkout_cache` to keep the cache at a fixed
``<checkout>/.jax_cache`` (git-ignored).  The path is part of a cache
entry's key, so it must not move between runs.  Importing the library
sets nothing.
"""

from __future__ import annotations

import os
from pathlib import Path


def use_checkout_cache(checkout) -> str:
    """Enable the persistent compilation cache; returns its directory."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    import jax
    path = str(Path(checkout).resolve() / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path
