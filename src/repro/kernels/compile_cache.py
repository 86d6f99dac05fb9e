"""JAX's persistent compilation cache, placed from outside.

Entry points (``chip_smoke.py``, ``examples/*_e2e.py``,
``examples/serve_vta.py``) call :func:`enable_compile_cache` before their
first compile; importing a module never touches the cache.

* ``JAX_COMPILATION_CACHE_DIR`` set: JAX reads it itself, and the code sets
  no other directory.
* unset: the cache goes to ``.jax_cache/`` at the root of the checkout
  (git-ignored).  The path is fixed, never temporary or per process: a
  cache whose directory moves is never hit again.
"""

from __future__ import annotations

import os
from pathlib import Path
from typing import Mapping

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"
REPO_CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def compile_cache_dir(environ: Mapping[str, str]) -> Path:
    """Where the cache lives under ``environ``."""
    if ENV_VAR in environ:
        return Path(environ[ENV_VAR])
    return REPO_CACHE_DIR


def enable_compile_cache(environ: Mapping[str, str] = os.environ) -> Path:
    """Point JAX's persistent cache at :func:`compile_cache_dir` and write
    every compile to it; returns the directory."""
    import jax

    path = compile_cache_dir(environ)
    if ENV_VAR not in environ:
        jax.config.update("jax_compilation_cache_dir", str(path))
    # a vta_gemm compile takes about a second, under JAX's default
    # threshold for writing an entry, so none would ever be cached
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    return path
