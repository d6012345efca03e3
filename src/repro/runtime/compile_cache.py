"""JAX's persistent compilation cache, turned on by entry points.

Library code never calls this at import: only scripts that own their
process (``chip_smoke.py``, ``benchmarks/run.py``) do, before their first
compile.
"""

from __future__ import annotations

import os
import pathlib

import jax

CHECKOUT = pathlib.Path(__file__).resolve().parents[3]


def enable_compile_cache() -> str:
    """Keep compiled programs across processes; returns the directory.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX already reads it and
    nothing else is set here.  Otherwise the cache lives at a fixed path
    inside the checkout, ``.jax_cache/`` (git-ignored): the path is part
    of the cache key, so a directory that moves never hits."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    path = str(CHECKOUT / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path
