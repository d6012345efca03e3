"""JAX's persistent compilation cache, turned on by entry points.

Library code never calls this at import: only scripts that own their
process (``chip_smoke.py``, ``benchmarks/run.py``) do, before their first
compile.
"""

from __future__ import annotations

import os
import pathlib
import re

import jax

CHECKOUT = pathlib.Path(__file__).resolve().parents[3]
# the checkout as Python names its source files (symbolic links kept)
SOURCE_ROOT = str(pathlib.Path(os.path.abspath(__file__)).parents[3])


def enable_compile_cache() -> str | None:
    """Keep compiled programs across processes on an accelerator; returns
    the directory, or None on the CPU.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX already reads it and
    no other directory is set here.  Otherwise the cache lives at a fixed
    path inside the checkout, ``.jax_cache/`` (git-ignored): the path is
    part of the cache key, so a directory that moves never hits.

    Either way the program's metadata (``jax.named_scope`` names, source
    lines) joins the cache key.  JAX leaves it out by default, so a
    program that differs only in its scopes would load an executable
    compiled from another version of the source, and a profiler trace
    would name its ops by that version's scopes.  Source paths are kept
    relative to the checkout, so that where it lies does not change the
    key.

    On the CPU the cache is off, whatever the environment says: an
    executable XLA:CPU loads from it runs under another program id than
    the HLO its profiler trace records, so the trace cannot name its ops
    by scope, and XLA warns that a loaded executable may use instructions
    the host lacks.  CPU programs compile in their own process."""
    jax.config.update("jax_compilation_cache_include_metadata_in_key", True)
    jax.config.update("jax_hlo_source_file_canonicalization_regex",
                      "^" + re.escape(SOURCE_ROOT + os.sep))
    if jax.default_backend() == "cpu":
        from jax.experimental.compilation_cache import compilation_cache
        jax.config.update("jax_enable_compilation_cache", False)
        compilation_cache.reset_cache()
        return None
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    path = str(CHECKOUT / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path
