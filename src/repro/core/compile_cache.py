"""JAX's persistent compilation cache, at one fixed place per checkout.

Every entry point (``launch/serve.py``, ``launch/train.py``,
``benchmarks/run.py``, ``benchmarks/serving_bench.py``, the fleet
worker child of ``serve/transport.py``, ``chip_smoke.py``) calls
``enable()`` before its first compile, so all of a run's processes and
every later run of the same checkout share one cache.  The directory is
part of the cache's identity, so it is never built from a temporary
directory, a process id or the time.
"""
from __future__ import annotations

import os

ENV = "JAX_COMPILATION_CACHE_DIR"
REPO_ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.dirname(os.path.abspath(__file__)))))
DEFAULT_DIR = os.path.join(REPO_ROOT, ".jax_cache")


def enable() -> str:
    """Turn the cache on and return its directory.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX already reads it
    and no other directory is set here; otherwise the cache lives in
    ``<repo>/.jax_cache`` (git-ignored).  Either way every program is
    cached, however fast it compiled: most of the main path's programs
    (the Table-1 shares, the kernels, the decode step on the host
    lane) compile in under JAX's default one second, and a second run
    would compile them all again."""
    import jax
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    path = os.environ.get(ENV)
    if path:
        return path
    jax.config.update("jax_compilation_cache_dir", DEFAULT_DIR)
    return DEFAULT_DIR
