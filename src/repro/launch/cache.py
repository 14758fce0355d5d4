"""JAX's persistent compilation cache, settable from outside.

Call :func:`enable_compile_cache` before the first compile.  Where
``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads that directory itself and
nothing is set here.  Otherwise the cache goes to ``.jax_cache/`` at the root
of the checkout — a fixed path, because the path is part of every entry's
key: a directory named after a pid, a time or a temporary name never hits.
"""

from __future__ import annotations

import os
from pathlib import Path

import jax

CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent compilation cache on; returns its directory.

    Every compile is cached (the counting kernels compile in well under the
    default one-second threshold) unless
    ``JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS`` says otherwise.

    MLIR locations keep only the innermost user frame unless
    ``JAX_INCLUDE_FULL_TRACEBACKS_IN_LOCATIONS`` says otherwise.  A Pallas
    kernel's body is embedded in its program with its locations, and the
    cache key does not strip them there, so with full tracebacks the key of
    a program holding a kernel depends on the call stack that first traced
    the kernel: the serving program missed the cache in every process that
    skipped the autotune sweep which had traced it first.
    """
    if "JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS" not in os.environ:
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    if "JAX_INCLUDE_FULL_TRACEBACKS_IN_LOCATIONS" not in os.environ:
        jax.config.update("jax_include_full_tracebacks_in_locations", False)
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    return str(CACHE_DIR)
