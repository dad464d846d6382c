"""JAX's persistent compilation cache, placed the same way by every entry
point (``python -m repro``, ``launch/serve.py``, ``chip_smoke.py``).

Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and nothing
is set here.  Otherwise the cache goes to ``.jax_cache/`` at the root of
the checkout: a fixed path, because the path is part of the cache key, so
a run from the same checkout finds what an earlier run compiled.
"""
from __future__ import annotations

import os
from pathlib import Path

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"
CHECKOUT_CACHE = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the cache on; return the directory it uses."""
    if os.environ.get(ENV_VAR):
        return os.environ[ENV_VAR]
    import jax
    jax.config.update("jax_compilation_cache_dir", str(CHECKOUT_CACHE))
    return str(CHECKOUT_CACHE)
