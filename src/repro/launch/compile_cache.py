"""Where JAX keeps compiled programs between processes.

The entry points (``launch/migrate.py``, ``launch/serve.py``,
``launch/train.py``, ``chip_smoke.py``) call :func:`enable_compile_cache`
from their ``main()``; nothing calls it at import time.
"""
from __future__ import annotations

import os

import jax

CACHE_ENV = "JAX_COMPILATION_CACHE_DIR"
# a fixed path inside the checkout (gitignored): the directory is part of
# what a later run must find again, so it is never built from a temporary
# name, a process id or the time
DEFAULT_CACHE_DIR = os.path.normpath(os.path.join(
    os.path.dirname(os.path.abspath(__file__)), "..", "..", "..",
    ".jax_cache"))


def enable_compile_cache() -> str:
    """Turn on JAX's persistent compilation cache and return its directory:
    ``$JAX_COMPILATION_CACHE_DIR`` where that is set, otherwise
    ``<checkout>/.jax_cache``."""
    path = os.environ.get(CACHE_ENV) or DEFAULT_CACHE_DIR
    jax.config.update("jax_compilation_cache_dir", path)
    return path
