"""Where JAX keeps its persistent compilation cache.

Entry points (``chip_smoke.py``, ``repro.launch.train``,
``examples/serve_lm.py``, ``benchmarks/serve_bench.py``) call
``enable_compile_cache()`` from their ``main()``; nothing enables the
cache at import.  The cache key includes the directory, so the default
is a fixed path inside the checkout (listed in ``.gitignore``), never a
temporary or per-process name.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

# <repo>/.jax_cache — this file is <repo>/src/repro/launch/compile_cache.py
DEFAULT_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Return the cache directory in use.  ``JAX_COMPILATION_CACHE_DIR``,
    when set, is read by JAX itself and nothing is changed; otherwise
    the cache goes to ``DEFAULT_DIR``."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_DIR))
    return str(DEFAULT_DIR)
