"""JAX's persistent compilation cache, placed from outside or at a fixed path.

Entry points (``chip_smoke.py``, ``python -m repro.serve.market``,
``benchmarks/run.py``) call :func:`configure` once, before they compile.
"""
from __future__ import annotations

import os

import jax

ENV = "JAX_COMPILATION_CACHE_DIR"
# <checkout>/.jax_cache: the path is part of each entry's key, so it must not
# move between runs (no temporary, per-process or timestamped directory)
DEFAULT_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__)))),
    ".jax_cache",
)


def configure() -> str:
    """Turn the cache on and return its directory.

    Where ``$JAX_COMPILATION_CACHE_DIR`` is set JAX reads it itself and
    nothing else is set; otherwise the cache lives in ``<checkout>/.jax_cache``.
    """
    path = os.environ.get(ENV)
    if path:
        return path
    jax.config.update("jax_compilation_cache_dir", DEFAULT_DIR)
    return DEFAULT_DIR
