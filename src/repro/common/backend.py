"""Pallas backend selection and the persistent compile cache (leaf module —
safe to import from anywhere).

Compiled Mosaic kernels on TPU, interpret mode elsewhere (interpret executes
the same kernel body for validation). Lives under ``repro.common`` so model
code can consult it without importing kernel modules (kernels transitively
import core/model code — doing it the other way round is an import cycle).
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

# <repo>/.jax_cache: a fixed path, because the cache directory is part of
# every entry's key — a directory that moves between runs never hits.
DEFAULT_CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def default_interpret() -> bool:
    """Interpret mode exactly when the default backend is not a TPU."""
    return jax.default_backend() != "tpu"


def enable_compile_cache() -> str:
    """Turn on JAX's persistent compilation cache and return its directory.

    ``JAX_COMPILATION_CACHE_DIR`` wins when it is set (JAX reads it itself);
    otherwise the cache lives at ``<repo>/.jax_cache``. Call before the first
    compile of the process: JAX decides once whether the cache is in use.
    """
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_CACHE_DIR))
    return str(DEFAULT_CACHE_DIR)
