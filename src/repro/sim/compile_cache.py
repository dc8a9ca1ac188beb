"""Where JAX keeps compiled programs between processes.

Each runner key (node count, step mode, telemetry, chains, failures,
resize) compiles its own program, so a cold process spends much of a
short run compiling.  :func:`enable_compile_cache` turns on JAX's
persistent compilation cache at a fixed place, so the next process with
the same programs loads them instead.  Entry points call it before their
first compile (``chip_smoke.py``, ``benchmarks.run``); importing
``repro`` never does, so tests never write a cache.
"""
from __future__ import annotations

import os
from pathlib import Path

#: the checkout this module was loaded from (``<checkout>/src/repro/sim``)
CHECKOUT = Path(__file__).resolve().parents[3]


def enable_compile_cache() -> str:
    """Turn on the persistent compilation cache; returns its directory.

    ``JAX_COMPILATION_CACHE_DIR``, when set, is the directory (JAX reads
    it itself and no other is set here).  Otherwise the cache lives at
    ``<checkout>/.jax_cache``: a fixed path, never a temp name, a pid or
    the time, so every run from this checkout finds the same cache.
    Programs are cached however fast they compiled."""
    import jax
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = str(CHECKOUT / ".jax_cache")
        jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    return path
