"""Where JAX keeps its persistent compilation cache.

The entry points (``chip_smoke.py``, ``launch/serve.py``,
``benchmarks/run.py``) call ``enable_compile_cache`` before their first
compile, so a second run of the same program loads its executables
instead of compiling them again.  The path is part of each entry's key,
so it must not move between runs: either the directory that
``JAX_COMPILATION_CACHE_DIR`` names (JAX reads that variable itself, and
nothing here overrides it), or a fixed directory inside the checkout.
Tests never call this.
"""
from __future__ import annotations

import os

#: the checkout's own cache directory (listed in ``.gitignore``), used
#: when the environment names none
DEFAULT_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))), ".jax_cache")


def enable_compile_cache() -> str:
    """Turn the persistent compilation cache on; returns its directory."""
    import jax
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = DEFAULT_DIR
        jax.config.update("jax_compilation_cache_dir", path)
    return path
