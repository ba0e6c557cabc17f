"""JAX's persistent compilation cache, kept at a path that does not move.

A cold process compiles every program it runs; on the TPU that is seconds
to minutes per shape.  JAX can keep compiled programs on disk and find them
again in the next process, but only if that process looks in the same
directory.  Every entry point that runs programs on the device
(``chip_smoke.py``, ``benchmarks/run.py``, ``benchmarks/engine_perf.py``,
``repro.launch.train``) calls :func:`enable_compile_cache` before its first
compilation:

  * with ``JAX_COMPILATION_CACHE_DIR`` set, the cache lives there and
    nothing else is configured;
  * otherwise it lives at ``<repo>/.jax_cache`` (listed in ``.gitignore``),
    a fixed path that never depends on a temporary name, a pid or the time.
"""

from __future__ import annotations

import os
from pathlib import Path

__all__ = ["CACHE_ENV", "DEFAULT_CACHE_DIR", "enable_compile_cache"]

CACHE_ENV = "JAX_COMPILATION_CACHE_DIR"
# src/repro/launch/compile_cache.py -> the repository root.
DEFAULT_CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Point JAX's persistent compilation cache at its directory; returns it.

    Call before the process compiles anything: JAX reads the setting when
    it first uses the cache.
    """
    import jax

    path = os.environ.get(CACHE_ENV, "").strip() or str(DEFAULT_CACHE_DIR)
    jax.config.update("jax_compilation_cache_dir", path)
    return path
