"""JAX's persistent compilation cache for the launchers.

A fresh machine pays every compile again; the persistent cache lets the
next process on the same machine (and the same path) load them instead.
The path is part of each entry's key, so it is fixed: ``<repo>/.jax_cache``
(git-ignored), unless ``JAX_COMPILATION_CACHE_DIR`` names another — JAX
reads that variable itself, and nothing here overrides it.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

REPO_CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Point JAX's persistent compilation cache at its directory and
    return that directory.  Call at the start of an entry point, before
    the first compile — never at import."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(REPO_CACHE_DIR))
    return str(REPO_CACHE_DIR)
