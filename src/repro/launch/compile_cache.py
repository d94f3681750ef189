"""JAX's persistent compilation cache for the entry points.

A cold run on the chip compiles the fold and round programs anew; with
the cache a second run in the same checkout reads them back. Where
``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and nothing
is set here. Otherwise the cache lives at a fixed path inside the
checkout, ``<repo>/.jax_cache`` (listed in ``.gitignore``). Call
:func:`enable_compile_cache` from an entry point before its first
compile; importing this module changes nothing.
"""
from __future__ import annotations

import os
from pathlib import Path

REPO_ROOT = Path(__file__).resolve().parents[3]
DEFAULT_CACHE_DIR = REPO_ROOT / ".jax_cache"


def enable_compile_cache() -> str:
    """Point JAX's persistent compilation cache at its directory and
    return that directory."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    import jax
    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_CACHE_DIR))
    return str(DEFAULT_CACHE_DIR)
