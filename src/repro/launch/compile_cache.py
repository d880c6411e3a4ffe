"""Where JAX keeps its persistent compile cache.

One call before the first compile, from every entry point that runs
on the chip (`chip_smoke.py`, `launch.serve`, `launch.score`,
`launch.train_gbdt`):

  * `JAX_COMPILATION_CACHE_DIR` set: nothing is changed — JAX reads the
    variable itself and caches there;
  * unset: the cache goes to `.jax_cache/` at the checkout root.  The
    path is fixed (no temp name, pid or time) because it is part of the
    cache key a later run must find again.
"""
from __future__ import annotations

import os
import pathlib

CHECKOUT = pathlib.Path(__file__).resolve().parents[3]
DEFAULT_DIR = CHECKOUT / ".jax_cache"
ENV_VAR = "JAX_COMPILATION_CACHE_DIR"


def configure() -> str:
    """Place the compile cache (see module docstring); returns the
    directory JAX will use."""
    placed = os.environ.get(ENV_VAR)
    if placed:
        return placed
    import jax
    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_DIR))
    return str(DEFAULT_DIR)
