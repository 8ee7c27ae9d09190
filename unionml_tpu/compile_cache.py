"""Where JAX's persistent compilation cache lives for this checkout.

One rule for every entry point that compiles (``chip_smoke.py``,
``bench.py``, the CLI's ``train``/``serve``, the test harness): an
exported ``JAX_COMPILATION_CACHE_DIR`` is the deployment's choice and
nothing is set in code; otherwise the cache is the fixed directory
``<checkout>/.jax_cache``. The path is part of the cache key, so it is
never a temp name, a pid or a time — a directory that moves never hits.
"""

from __future__ import annotations

import os
import sys
from pathlib import Path

__all__ = ["CACHE_DIR_ENV", "enable_compile_cache"]

CACHE_DIR_ENV = "JAX_COMPILATION_CACHE_DIR"
_CHECKOUT_CACHE = Path(__file__).resolve().parents[1] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent compilation cache on; return its directory.

    With :data:`CACHE_DIR_ENV` exported JAX already reads it (children
    inherit it) and this only reports it. Otherwise the checkout's
    ``.jax_cache`` is exported — JAX reads it at import, and so do child
    processes — and, when JAX was imported before the call, applied to
    the live config too. A parent that only launches runners never
    imports JAX through this call.
    """
    exported = os.environ.get(CACHE_DIR_ENV)
    if exported:
        return exported
    path = str(_CHECKOUT_CACHE)
    os.environ[CACHE_DIR_ENV] = path
    if "jax" in sys.modules:
        sys.modules["jax"].config.update("jax_compilation_cache_dir", path)
    return path
