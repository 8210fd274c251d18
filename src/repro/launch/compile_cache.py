"""Where the entry points keep JAX's persistent compilation cache.

A cache key includes the cache directory, so the directory must not move
between runs: a temp name, a pid or a timestamp would never hit.  The one
rule, applied by every entry point before its first compile
(``launch/serve.py``, ``chip_smoke.py``, ``benchmarks/bench_*.py``):

  * ``JAX_COMPILATION_CACHE_DIR`` set - jax already reads it; nothing else
    is set here;
  * otherwise - ``<checkout>/.jax_cache`` (gitignored).
"""
from __future__ import annotations

import os
from pathlib import Path

CHECKOUT = Path(__file__).resolve().parents[3]


def enable_compile_cache() -> str:
    """Point jax's persistent compilation cache at its one directory;
    returns that directory."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    import jax

    path = str(CHECKOUT / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path
