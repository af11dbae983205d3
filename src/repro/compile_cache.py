"""JAX's persistent compilation cache — one rule for every entry point.

A cold process compiles every program again: on a TPU a full-width VGG
step and its kernels take minutes. The persistent cache keeps compiled
executables on disk, keyed by the program AND the cache directory, so
the directory must not move between runs:

  * ``JAX_COMPILATION_CACHE_DIR`` set — JAX already reads it; nothing
    else is configured here;
  * otherwise — ``<repo>/.jax_cache``, one fixed path inside the
    checkout (listed in ``.gitignore``), never a temp-dir, pid- or
    time-derived name.

Call ``enable()`` at the start of a script's run, never at import time.
"""
from __future__ import annotations

import os
from pathlib import Path

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"
DEFAULT_DIR = Path(__file__).resolve().parents[2] / ".jax_cache"


def enable() -> str:
    """Turn the persistent compilation cache on; returns its directory."""
    path = os.environ.get(ENV_VAR)
    if path:
        return path
    import jax
    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_DIR))
    return str(DEFAULT_DIR)
