"""Where the entry points keep JAX's persistent compilation cache.

Compiling the serving executables for a TPU takes tens of seconds each, and
every process starts cold without a persistent cache. The cache key includes
the cache directory, so it must not move between runs: when
``JAX_COMPILATION_CACHE_DIR`` is set JAX already uses it and nothing here
overrides it; otherwise the cache lives at a fixed path inside the checkout
(``<repo>/.jax_cache``, ignored by git). ``chip_smoke.py``,
``python -m repro.launch.serve`` and ``python -m benchmarks.run`` call
:func:`configure_compile_cache` before their first compile.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"
REPO_CACHE = Path(__file__).resolve().parents[3] / ".jax_cache"


def configure_compile_cache() -> str:
    """Point the persistent compilation cache at its directory; return it."""
    path = os.environ.get(ENV_VAR)
    if path:  # jax reads the variable itself at import
        return path
    jax.config.update("jax_compilation_cache_dir", str(REPO_CACHE))
    return str(REPO_CACHE)
