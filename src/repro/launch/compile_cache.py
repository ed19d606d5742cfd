"""The persistent compilation cache that every entry point shares.

A cold TPU process spends much of its start-up compiling, and JAX can keep
compiled programs on disk.  The cache's path is part of what makes an entry
found again, so it is fixed: ``$JAX_COMPILATION_CACHE_DIR`` when the
environment sets it, else ``<repo>/.jax_cache``.
"""
from __future__ import annotations

import os
import sys
from pathlib import Path

REPO_ROOT = Path(__file__).resolve().parents[3]
DEFAULT_CACHE_DIR = REPO_ROOT / ".jax_cache"


def setup_compile_cache() -> str:
    """Turn the persistent compilation cache on; returns its directory.

    Call before the first compile.  If ``JAX_COMPILATION_CACHE_DIR`` is set,
    JAX reads it when it is imported, and this sets nothing.  Otherwise the
    cache goes to ``<repo>/.jax_cache``: the variable is exported (for a
    JAX imported later, and for spawned workers), and a JAX already
    imported is told directly.  JAX is not imported here, so a worker whose
    backend never needs it does not pay for the import."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    path = str(DEFAULT_CACHE_DIR)
    os.makedirs(path, exist_ok=True)
    os.environ["JAX_COMPILATION_CACHE_DIR"] = path
    if "jax" in sys.modules:
        sys.modules["jax"].config.update("jax_compilation_cache_dir", path)
    return path
