"""JAX's persistent compilation cache for the repo's entry points.

Compiling the planner's chunk program takes seconds per tile geometry,
and every fresh process pays it again unless the compiled executables
are kept on disk.  :func:`enable_compile_cache` is called by the entry
points (``chip_smoke.py``, the benchmarks, the examples) before their
first compile — never at library import, so importing :mod:`repro`
leaves JAX's configuration alone.
"""

from __future__ import annotations

import os
from pathlib import Path

#: the repo-local default, ignored by git (``.cache/``); a fixed path,
#: because the directory is part of every cache entry's identity
DEFAULT_DIR = Path(__file__).resolve().parents[2] / ".cache" / "jax"


def enable_compile_cache() -> str:
    """Turn on the persistent compilation cache and return its directory.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX already reads it and
    nothing is changed; otherwise the cache goes to ``<repo>/.cache/jax``.
    """
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    import jax
    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_DIR))
    return str(DEFAULT_DIR)
