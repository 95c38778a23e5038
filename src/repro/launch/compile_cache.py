"""Where JAX keeps compiled programs across processes.

Compiling the kernels and the serving step is a large part of a cold run
on the chip, and the persistent compilation cache lets every process of a
deployment (and the next run on the same disk) reuse them.  The cache's
place is chosen from outside: ``JAX_COMPILATION_CACHE_DIR`` wins, and JAX
reads it on its own.  Without it, the cache is the fixed ``.jax_cache/``
at the checkout root — never a temporary or per-process path, since a
directory that moves never hits.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

__all__ = ["CHECKOUT_CACHE_DIR", "enable_compile_cache"]

#: ``<checkout>/.jax_cache`` (this file is ``<checkout>/src/repro/launch/``)
CHECKOUT_CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent compilation cache on for this process and return
    its directory.  Sets nothing when ``JAX_COMPILATION_CACHE_DIR`` is set;
    otherwise points JAX at :data:`CHECKOUT_CACHE_DIR`."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(CHECKOUT_CACHE_DIR))
    return str(CHECKOUT_CACHE_DIR)
