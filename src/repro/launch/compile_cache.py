"""Where JAX keeps its persistent compilation cache for this repo's programs.

A cold process compiles every shape it meets; the persistent cache lets the
next process on the same machine skip that. The cache's location is part of
its key, so it must not move between runs:

- if ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and nothing
  is set in code;
- otherwise the cache goes to ``<checkout>/.jax_cache``, a fixed path.

The entry points (`repro.launch.serve`, `benchmarks.run`, ``chip_smoke.py``)
call `enable` once at start; library code and tests never do.
"""

from __future__ import annotations

import os
from pathlib import Path

ENV = "JAX_COMPILATION_CACHE_DIR"
DEFAULT_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable() -> str:
    """Turn the persistent compilation cache on; returns its directory."""
    placed = os.environ.get(ENV)
    if placed:
        return placed
    import jax

    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_DIR))
    return str(DEFAULT_DIR)
