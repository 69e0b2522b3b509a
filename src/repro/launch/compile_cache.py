"""JAX's persistent compilation cache, in one fixed place.

Every entry point that compiles for the chip (``chip_smoke.py``, the
train and serve launchers, ``benchmarks.run_all``) calls
:func:`enable_compile_cache` before its first compile, so that a second
run with the same programs loads them instead of compiling again.

Where the cache lives:

* ``JAX_COMPILATION_CACHE_DIR``, when it is set — JAX reads that
  variable itself, and no other path is set here;
* otherwise ``<checkout>/.jax_cache``: a fixed path, because the
  directory is part of what makes a later run find the entries.
"""

from __future__ import annotations

import os
import pathlib

__all__ = ["enable_compile_cache", "CHECKOUT_CACHE_DIR"]

#: the cache directory used when ``JAX_COMPILATION_CACHE_DIR`` is unset
CHECKOUT_CACHE_DIR = str(pathlib.Path(__file__).resolve().parents[3]
                         / ".jax_cache")


def enable_compile_cache() -> str:
    """Turn on the persistent compilation cache and return its
    directory."""
    import jax

    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = CHECKOUT_CACHE_DIR
        jax.config.update("jax_compilation_cache_dir", path)
    os.makedirs(path, exist_ok=True)
    return path
