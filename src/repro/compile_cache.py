"""Persistent XLA compilation cache, configured once per entry point.

A cold process compiles every program again; the persistent cache lets a
later process on the same machine load them instead.  JAX keys the cache
on the directory too, so the directory must not move between runs.

``JAX_COMPILATION_CACHE_DIR``, when set, wins: JAX reads it itself and
this module sets nothing.  Otherwise the cache lives at a fixed
``.jax_cache/`` in the checkout root (git-ignored), resolved from this
file's location — never from a temporary name, a pid or the clock.
"""

from __future__ import annotations

import os
from pathlib import Path

import jax

#: ``.jax_cache`` in the checkout root (src/repro/compile_cache.py → ../../)
DEFAULT_DIR = Path(__file__).resolve().parents[2] / ".jax_cache"


def configure_compile_cache() -> str:
    """Point JAX's persistent compilation cache at its directory before
    the first compile; returns the directory in use."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_DIR))
    return str(DEFAULT_DIR)
