"""Where JAX keeps its persistent compilation cache.

A cache entry is found again only under the same directory path, so the
path must not move between runs: ``$JAX_COMPILATION_CACHE_DIR`` when it is
set (JAX reads it itself; nothing else is configured), otherwise the fixed
``<repo>/artifacts/jax_cache`` (git-ignored with the rest of
``artifacts/``). Never a temporary, per-process or time-stamped directory.
"""
from __future__ import annotations

import os
import pathlib

ENV = "JAX_COMPILATION_CACHE_DIR"
DEFAULT_DIR = (pathlib.Path(__file__).resolve().parents[3] / "artifacts"
               / "jax_cache")


def setup_compile_cache() -> pathlib.Path:
    """Turn on JAX's persistent compilation cache at its placed directory
    (see module docstring) and return that directory. Call before the
    first compile of the process."""
    import jax

    env = os.environ.get(ENV)
    if env:
        return pathlib.Path(env)
    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_DIR))
    return DEFAULT_DIR
