"""JAX's persistent compilation cache, as the entry points turn it on.

Every bucket, batch slot and grid shape is its own Mosaic/XLA compile,
so a process that starts cold on the chip pays for all of them again
unless compiled executables persist.  The entry points (``chip_smoke.py``,
``benchmarks/run.py``, ``benchmarks/serve.py`` and the PlanServe worker
child) call :func:`enable_compile_cache` once at start-up; importing
``repro`` does not.
"""
from __future__ import annotations

import os
import pathlib

import jax

#: Where the cache lives when ``JAX_COMPILATION_CACHE_DIR`` is not set:
#: a fixed directory of the checkout (the path is part of what JAX
#: keys entries on, so a directory named after a pid or a time would
#: never hit).
DEFAULT_DIR = pathlib.Path(__file__).resolve().parents[2] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn on the persistent compilation cache and return its directory.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and
    no other directory is set here.  Stencil kernels compile in well
    under a second, so every executable is cached, however fast its
    compile was."""
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = str(DEFAULT_DIR)
        jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    return path
