"""JAX's persistent compilation cache at a fixed place.

A cold fleet segment costs tens of seconds of compiling per program
shape, so entry points (``chip_smoke.py``, ``benchmarks/run.py``,
``benchmarks/bench_fleet.py``, the examples) call ``use_compile_cache()``
at startup.  The cache key includes the directory, so it must not move
between runs: ``JAX_COMPILATION_CACHE_DIR`` when the environment sets it
(JAX reads that itself, and nothing else is set here), else
``<repo>/.jax_cache``, which ``.gitignore`` lists.  Never called at
library import: tests and library users keep JAX's own defaults.
"""

from __future__ import annotations

import os
from pathlib import Path

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"

#: ``<repo>/.jax_cache`` (this file is ``<repo>/src/repro/compile_cache.py``).
DEFAULT_DIR = Path(__file__).resolve().parents[2] / ".jax_cache"


def use_compile_cache() -> str:
    """Turn on the persistent compilation cache; returns its directory."""
    env = os.environ.get(ENV_VAR)
    if env:
        return env
    import jax

    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_DIR))
    return str(DEFAULT_DIR)
