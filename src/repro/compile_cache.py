"""Where jax keeps its persistent compilation cache.

The command-line entry points (``python -m repro.suite``, ``python -m
repro.study``, ``python -m benchmarks.run``) and ``chip_smoke.py`` call
:func:`enable` before their first compile; library modules never call it
when they are imported.  The rule:

- ``$JAX_COMPILATION_CACHE_DIR`` set: jax reads it on its own, and this
  module sets no other directory;
- otherwise the cache goes to ``.jax_cache/`` at the checkout root.  The
  path is fixed on purpose: it is part of what a cached program is found
  by, so a directory named after a temp dir, a pid or the time would
  never be hit again.
"""

from __future__ import annotations

import importlib.util
import os
from pathlib import Path

__all__ = ["ENV_VAR", "CHECKOUT_CACHE", "enable"]

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"
# src/repro/compile_cache.py -> the checkout root
CHECKOUT_CACHE = Path(__file__).resolve().parents[2] / ".jax_cache"


def enable() -> str | None:
    """Point jax's persistent compilation cache at its directory and
    return that directory (``None`` where jax is not installed: the
    NumPy-only paths compile nothing)."""
    if importlib.util.find_spec("jax") is None:
        return None
    import jax

    if os.environ.get(ENV_VAR):
        return os.environ[ENV_VAR]
    jax.config.update("jax_compilation_cache_dir", str(CHECKOUT_CACHE))
    return str(CHECKOUT_CACHE)
