"""JAX's persistent compilation cache for the chip entry points.

Called by ``chip_smoke.py`` and ``benchmarks/run.py`` at start-up, never
on import.  A cache only hits when its directory is the same from run to
run, so the default is a fixed path inside the checkout: never a
temporary name, a process id or the time.
"""
from __future__ import annotations

import os
import pathlib

import jax

CHECKOUT = pathlib.Path(__file__).resolve().parents[3]


def enable_compile_cache() -> str:
    """Turn the persistent cache on; returns the directory it uses.

    ``JAX_COMPILATION_CACHE_DIR``, when set, is used as it is (JAX reads
    the variable itself) and nothing else is set.  Otherwise the cache is
    ``<checkout>/.jax_cache``.
    """
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    path = str(CHECKOUT / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path
