"""Where JAX keeps its persistent compilation cache.

Entry points (``launch/serve.py``, ``chip_smoke.py``, the
``benchmarks/bench_*.py`` drivers) call :func:`use_compile_cache` from
their ``main()``; importing this module changes nothing.

* ``JAX_COMPILATION_CACHE_DIR`` set: JAX reads it itself and this sets
  nothing, so the caller's environment decides.
* Otherwise the cache lives at :data:`CACHE_DIR`, a fixed directory inside
  the checkout (git-ignored).  The path is part of what makes an entry
  found again, so it never depends on a temp name, a process id or the
  time.
"""
from __future__ import annotations

import os
from pathlib import Path

#: ``<checkout>/.jax_cache``
CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def use_compile_cache() -> str:
    """Point JAX's persistent compilation cache at its directory and
    return that directory."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    import jax

    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    return str(CACHE_DIR)
