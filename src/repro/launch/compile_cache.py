"""Persistent XLA compilation cache for the entry points.

Called from a ``main`` (``launch/serve.py``, ``chip_smoke.py``), never at
import: tests and library users keep whatever cache policy they set.
"""
from __future__ import annotations

import os

import jax

#: The checkout root (``src/repro/launch`` -> three levels up).
REPO_ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.dirname(os.path.abspath(__file__)))))


def enable_compile_cache() -> str:
    """Turn JAX's persistent compilation cache on; return its directory.

    ``JAX_COMPILATION_CACHE_DIR``, when set, is the directory and nothing
    else is configured (JAX reads the variable itself).  Otherwise the
    cache lives at ``.jax_cache/`` in the checkout: a fixed path, because
    the path is part of what a later run must find again.
    """
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = os.path.join(REPO_ROOT, ".jax_cache")
        jax.config.update("jax_compilation_cache_dir", path)
    return path
