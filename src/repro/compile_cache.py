"""Where JAX keeps its persistent compilation cache.

``enable_compile_cache`` is for entry points (``chip_smoke.py``,
``benchmarks/run.py``, the examples) and is never called at library import:
a library that moved the cache would override whatever its caller chose.
"""
from __future__ import annotations

import os
import pathlib

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"
# the checkout root: src/repro/compile_cache.py -> parents[2]
CHECKOUT_ROOT = pathlib.Path(__file__).resolve().parents[2]


def enable_compile_cache() -> str:
    """Turn on JAX's persistent compilation cache; returns its directory.

    When ``JAX_COMPILATION_CACHE_DIR`` is set, JAX read it at import and that
    directory stands: nothing here sets another. Otherwise the cache goes to
    ``.jax_cache/`` at the checkout root — a fixed path, never one built from
    a temporary name, a pid or the time, because a later run finds its
    entries only under the same directory."""
    env = os.environ.get(ENV_VAR)
    if env:
        return env
    import jax
    path = str(CHECKOUT_ROOT / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path
