"""JAX's persistent compilation cache, switched on in one place.

`enable()` is called from the ``main()`` of each entry point that compiles
for the chip (`launch/serve.py`, `benchmarks/*.py`, `chip_smoke.py`),
never at import.  Where ``JAX_COMPILATION_CACHE_DIR`` is set the cache is
that directory and no other is set; otherwise it is ``.jax_cache/`` at the
checkout root (gitignored).  The path is part of what a cache entry is
found by, so it is fixed: never a temporary directory, a pid or a time.
"""

from __future__ import annotations

import os
import pathlib

import jax

ENV = "JAX_COMPILATION_CACHE_DIR"
CHECKOUT_DIR = pathlib.Path(__file__).resolve().parents[3] / ".jax_cache"


def cache_dir() -> pathlib.Path:
    """Where the persistent compilation cache lives for this process."""
    env = os.environ.get(ENV)
    return pathlib.Path(env) if env else CHECKOUT_DIR


def enable() -> pathlib.Path:
    """Point JAX's persistent compilation cache at `cache_dir()`."""
    path = cache_dir()
    jax.config.update("jax_compilation_cache_dir", str(path))
    return path
