"""JAX's persistent compilation cache, placed at program start.

Every entry point (``chip_smoke.py``, ``python -m repro.launch.serve``,
``python -m benchmarks.run``) calls ``setup_compile_cache()`` first thing in
its ``main``; importing this module changes nothing.  Where
``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and nothing is set
here.  Otherwise the cache lives at ``<repo>/.jax_cache`` (gitignored): a
fixed path, because the path is part of the cache key and a directory that
moves never hits.
"""
from __future__ import annotations

import os

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"
REPO_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))), ".jax_cache")


def setup_compile_cache() -> str:
    """Return the directory compiled programs persist to, pointing JAX at
    the repository's own when the environment names none."""
    path = os.environ.get(ENV_VAR)
    if path:
        return path
    import jax
    jax.config.update("jax_compilation_cache_dir", REPO_CACHE_DIR)
    return REPO_CACHE_DIR
