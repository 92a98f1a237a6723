"""One place for JAX's persistent compilation cache.

Every entry point that compiles real programs (``repro.launch.serve``,
``repro.launch.train``, ``chip_smoke.py``) calls :func:`enable` before
its first compile.  Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX
already reads it and nothing else is set here.  Otherwise the cache
lives at one fixed path inside the checkout, ``<repo>/.jax_cache/``
(ignored by git): the directory is part of the cache key, so a path
that moves between runs would never hit.
"""

from __future__ import annotations

import os
import pathlib

import jax

ENV = "JAX_COMPILATION_CACHE_DIR"
DEFAULT_DIR = pathlib.Path(__file__).resolve().parents[3] / ".jax_cache"


def enable() -> str:
    """Turn the persistent cache on; returns the directory in use."""
    path = os.environ.get(ENV)
    if not path:
        path = str(DEFAULT_DIR)
        jax.config.update("jax_compilation_cache_dir", path)
    return path
