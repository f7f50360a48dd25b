"""Where JAX keeps its persistent compilation cache.

The entry points (`chip_smoke.py`, `launch/anneal_serve.py`,
`benchmarks/run.py`) call `enable_compile_cache` once, before their first
compile; importing the library never does.  Where
``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and nothing is
set here.  Otherwise the cache goes to ``.jax_cache/`` at the root of the
checkout: a fixed path, because the directory is part of what a later run
has to find again.  Either way every compile is kept, however short: on a
TPU v5e each of the service's programs compiles in under a second, below
JAX's default threshold, and a run compiles dozens of them.
"""

from __future__ import annotations

import os
from pathlib import Path

import jax

CHECKOUT_CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Give JAX's persistent compilation cache its directory; returns it."""
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    from_env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if from_env:
        return from_env
    jax.config.update("jax_compilation_cache_dir", str(CHECKOUT_CACHE_DIR))
    return str(CHECKOUT_CACHE_DIR)
