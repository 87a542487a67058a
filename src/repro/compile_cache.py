"""Placement of JAX's persistent compilation cache for the entry points.

Each scan signature of the batch backend costs tens of seconds to compile,
so ``chip_smoke.py``, ``benchmarks/run.py`` and the megagrid CLI keep
their compiled programs across processes.  Call :func:`enable` from an
entry point's ``main``, never at import: tests and library callers keep
JAX's own default.
"""
from __future__ import annotations

import os

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
# a fixed path inside the checkout: the path is part of the cache key, so
# a per-process or temporary directory would never hit
DEFAULT_DIR = os.path.join(REPO_ROOT, ".jax_cache")


def enable() -> str:
    """Turn the persistent cache on and return its directory.  Where
    ``JAX_COMPILATION_CACHE_DIR`` is set, JAX has already read it and no
    directory is set here; otherwise the cache is ``<repo>/.jax_cache``."""
    import jax

    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = DEFAULT_DIR
        jax.config.update("jax_compilation_cache_dir", path)
    return path
