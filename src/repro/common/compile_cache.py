"""Persistent JAX compilation cache for the repository's entry points.

Entry points (``chip_smoke.py``, ``benchmarks/``, ``examples/``) call
:func:`enable_compile_cache` once at start-up; importing a module never
does.  The directory is fixed, so a later run finds what an earlier one
stored: ``JAX_COMPILATION_CACHE_DIR`` when the environment sets it (JAX
reads the variable itself and nothing is configured here), otherwise
``.jax_cache`` at the root of the checkout.
"""

from __future__ import annotations

import os
import pathlib

import jax

REPO_CACHE_DIR = pathlib.Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent compilation cache on; return its directory."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(REPO_CACHE_DIR))
    return str(REPO_CACHE_DIR)
