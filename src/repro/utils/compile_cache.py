"""JAX persistent compilation cache location for the entry points.

Entry points (``chip_smoke.py``, ``examples/train_federated.py``,
``repro.launch.train``, ``benchmarks/run.py``) call ``enable_compile_cache``
first thing in ``main``; library imports and tests never do.  Where
``JAX_COMPILATION_CACHE_DIR`` is set, JAX already reads it and nothing is
changed.  Otherwise the cache goes to ``<repo>/.jax_cache`` (git-ignored):
a fixed path, because the directory is part of what a later process must
find again.
"""
from __future__ import annotations

import os
from pathlib import Path

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"
REPO_CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent cache on; returns its directory:
    ``$JAX_COMPILATION_CACHE_DIR`` if set, else the checkout's
    ``.jax_cache``."""
    path = os.environ.get(ENV_VAR)
    if path:
        return path
    import jax

    path = str(REPO_CACHE_DIR)
    jax.config.update("jax_compilation_cache_dir", path)
    return path
