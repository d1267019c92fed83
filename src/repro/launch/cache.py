"""JAX's persistent compilation cache, configured in one place.

Entry points (``chip_smoke.py``, ``benchmarks/run.py``, the examples) call
``enable_compile_cache()`` once, before they compile anything; library code
and tests never do. A compiled executable is keyed on, among other things,
the cache directory's path, so the directory is fixed: the one
``JAX_COMPILATION_CACHE_DIR`` names if it is set (JAX reads the variable
itself), and otherwise ``.jax_cache/`` at the root of the checkout.
"""
from __future__ import annotations

import os
import pathlib

__all__ = ["enable_compile_cache"]

_CHECKOUT_CACHE = pathlib.Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn on the persistent compilation cache; returns its directory."""
    import jax
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = str(_CHECKOUT_CACHE)
        jax.config.update("jax_compilation_cache_dir", path)
    return path
