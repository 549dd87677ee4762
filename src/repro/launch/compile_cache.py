"""Where the entry points keep JAX's persistent compilation cache.

Entry points (`repro.launch.serve`, `benchmarks/run.py`, `chip_smoke.py`)
call `init_compilation_cache()` before their first compile; importing this
module changes nothing.  Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX
has already read its cache directory from it and this sets none.
Otherwise the cache goes to one fixed directory inside the checkout,
``<repo>/.jax_cache``, so that a later run of the same checkout finds
what an earlier one compiled.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

DEFAULT_CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def init_compilation_cache() -> str:
    """Point JAX's persistent compilation cache at its directory and
    return that directory."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_CACHE_DIR))
    return str(DEFAULT_CACHE_DIR)
