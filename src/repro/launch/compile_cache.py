"""JAX's persistent compilation cache for the repo's entry points.

Entry points (``chip_smoke.py``, ``benchmarks/run.py``, the examples) call
``enable_compile_cache()`` before their first compile, so a second run of
the same program on the same device loads its executables instead of
recompiling a 28-layer model from cold. The tests never call it: a test
run must not depend on, or write to, a cache left by an earlier run.

Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and this
module sets nothing. Otherwise the cache lives at one fixed directory in
the checkout (``.jax_cache``, listed in ``.gitignore``): the path is part
of what a later run must find again, so it is never built from a
temporary name, a process id or the time.
"""

from __future__ import annotations

import os
from pathlib import Path

CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Point JAX's persistent compilation cache at its directory and
    return that directory."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    import jax

    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    return str(CACHE_DIR)
