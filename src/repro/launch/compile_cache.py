"""Persistent compilation cache for the entry points.

Entry points (``chip_smoke.py``, ``python -m repro.launch.serve``) call
:func:`enable_compile_cache` once at start-up; importing the library sets
nothing. Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself
and this module leaves it alone. Otherwise the cache lives at the fixed
path ``<repo>/.jax_cache`` (gitignored), so a later run of the same
programs from the same checkout finds them again.
"""

from __future__ import annotations

import os
import pathlib

import jax

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"
REPO_ROOT = pathlib.Path(__file__).resolve().parents[3]
DEFAULT_DIR = REPO_ROOT / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn on JAX's persistent compilation cache; returns its directory."""
    env = os.environ.get(ENV_VAR)
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_DIR))
    return str(DEFAULT_DIR)
