"""Persistent compilation cache for the entry points.

Entry points (``chip_smoke.py``, ``benchmarks/run.py``,
``repro.launch.serve`` and the examples) call :func:`enable` once at start;
library code never does, and neither do the tests.  Where
``JAX_COMPILATION_CACHE_DIR`` is set, JAX already keeps its cache there and
nothing else is set.  Otherwise the cache goes to ``.jax_cache/`` at the
root of the checkout: a fixed path, because the path is part of the cache's
key and a directory that moves never hits.

:func:`off` keeps the persistent cache out of a block, for measurements of
cold compiles and for compiles for a chip that is not there.
"""
from __future__ import annotations

import contextlib
import os
from pathlib import Path

CHECKOUT = Path(__file__).resolve().parents[3]


def enable() -> str:
    """Turn the persistent compilation cache on; returns its directory."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    import jax
    path = str(CHECKOUT / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path


@contextlib.contextmanager
def off():
    """Nothing compiled inside the block is read from or written to the
    persistent cache.  JAX decides whether the cache is in use at the
    process's first compile and keeps that answer, so turning the flag off
    changes nothing by itself: the cache's state is reset on entry, so that
    the block's first compile sees the flag off, and on exit, so that the
    next compile sees it restored."""
    import jax
    from jax.experimental.compilation_cache import compilation_cache
    was = jax.config.jax_enable_compilation_cache
    compilation_cache.reset_cache()
    jax.config.update("jax_enable_compilation_cache", False)
    try:
        yield
    finally:
        jax.config.update("jax_enable_compilation_cache", was)
        compilation_cache.reset_cache()
