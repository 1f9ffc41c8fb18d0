"""JAX runtime settings shared by the engine and every entry point.

Two things live here so no module reaches into jax's configuration on its
own:

  * :func:`enable_x64` — the float64 scope the lattice roll-up and the
    on-device Pareto passes run under (bit-identity with the scalar oracle
    needs f64 end to end);
  * :func:`enable_compile_cache` — JAX's persistent compilation cache, which
    ``chip_smoke.py``, ``repro.launch.serve`` and ``benchmarks/run.py`` turn
    on before their first compile.
"""

from __future__ import annotations

import os
from pathlib import Path

import jax

#: Root of the checkout this package is imported from (``<root>/src/repro``).
CHECKOUT = Path(__file__).resolve().parents[2]

#: Where compiled programs go when ``JAX_COMPILATION_CACHE_DIR`` is unset. A
#: fixed path: the directory is part of the cache key, so it never moves.
DEFAULT_CACHE_DIR = CHECKOUT / ".jax_cache"


def enable_x64():
    """Context manager: float64 arrays inside, the process default outside."""
    return jax.enable_x64(True)


def enable_compile_cache() -> str:
    """Turn on JAX's persistent compilation cache and return its directory.

    When ``JAX_COMPILATION_CACHE_DIR`` is set, JAX already reads it and no
    other path is set here; otherwise the cache goes to
    :data:`DEFAULT_CACHE_DIR`."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_CACHE_DIR))
    return str(DEFAULT_CACHE_DIR)
