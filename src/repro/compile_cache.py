"""JAX's persistent compilation cache for the entry points that run on a chip.

``chip_smoke.py``, ``benchmarks/run.py`` and ``examples/quickstart.py``
call ``use_compile_cache()`` before their first compile, so processes
that compile the same programs share executables.  JAX keys the cache
on its directory, so the directory is fixed: the one
``JAX_COMPILATION_CACHE_DIR`` names when it is set (JAX reads that
variable itself), else ``.jax_cache/`` at the root of the checkout,
which git ignores.  It is never derived from a temporary name, a
process id or the time.
"""

from __future__ import annotations

import os
from pathlib import Path

import jax

CACHE_DIR = Path(__file__).resolve().parents[2] / ".jax_cache"


def use_compile_cache() -> str:
    """Turn on the persistent compilation cache; returns its directory."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    return str(CACHE_DIR)
