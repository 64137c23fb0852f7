"""JAX persistent compilation cache for the entry points.

Entry points (``chip_smoke.py``, ``repro.launch.serve``, the benchmark
harness, the examples) call ``configure_compile_cache()`` once at start-up;
library code never does, so importing ``repro`` changes no JAX setting.

Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and this
sets nothing.  Otherwise the cache goes to a fixed directory inside the
checkout (``<repo>/.jax_cache``, git-ignored): the cache key includes the
path, so a per-run temp directory would never hit.
"""
from __future__ import annotations

import os
from pathlib import Path

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"
DEFAULT_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def configure_compile_cache() -> str:
    """Turn the persistent compile cache on; returns its directory."""
    env = os.environ.get(ENV_VAR)
    if env:
        return env
    import jax
    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_DIR))
    return str(DEFAULT_DIR)
