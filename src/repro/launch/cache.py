"""Where JAX keeps compiled programs between processes.

A cold start on a TPU spends much of its time compiling; JAX's persistent
compilation cache lets the next process skip that. The cache key includes
its directory, so the directory must not move between runs: it is either
the one ``JAX_COMPILATION_CACHE_DIR`` names (JAX reads that variable
itself, and nothing here overrides it) or the fixed ``.jax_cache/`` at the
root of the checkout (listed in ``.gitignore``).
"""
from __future__ import annotations

import os
import pathlib

import jax

CHECKOUT = pathlib.Path(__file__).resolve().parents[3]
DEFAULT_DIR = CHECKOUT / ".jax_cache"


def use_compile_cache() -> str:
    """Turn on the persistent compilation cache; returns its directory.
    Call once, before the first compile."""
    placed = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if placed:
        return placed
    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_DIR))
    return str(DEFAULT_DIR)
