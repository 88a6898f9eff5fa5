"""Process-level runtime setup shared by the entry points.

Only entry points (`chip_smoke.py`, `launch/serve.py`, `benchmarks/run.py`,
`examples/*`) call this, from their ``main``; importing the library never
changes JAX configuration.
"""
from __future__ import annotations

import os

import jax

#: The checkout root (``src/repro/runtime.py`` -> two levels up from src).
REPO_ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

#: Fixed in-checkout compile-cache path, used when the environment names
#: none. The path is part of the cache key, so it must never move.
DEFAULT_CACHE_DIR = os.path.join(REPO_ROOT, ".jax_cache")


def configure_compile_cache() -> str:
    """Turn on JAX's persistent compilation cache and return its directory.

    ``JAX_COMPILATION_CACHE_DIR``, when set, is JAX's own setting and wins:
    nothing else is configured. Otherwise the cache lives at
    `DEFAULT_CACHE_DIR` (gitignored)."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", DEFAULT_CACHE_DIR)
    return DEFAULT_CACHE_DIR
