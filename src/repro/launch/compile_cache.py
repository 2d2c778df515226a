"""Where JAX keeps its persistent compilation cache for this checkout.

Every entry point (``chip_smoke.py``, ``launch/serve.py``, the
benchmark scripts) calls :func:`use_compile_cache` first, so repeated
runs on one machine reuse compiled programs.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

# the path is part of what a later run looks up, so it is fixed: the
# checkout's root, never a temporary or per-process name
CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def use_compile_cache() -> str:
    """Point JAX's persistent compilation cache at its directory.

    ``JAX_COMPILATION_CACHE_DIR``, when set, is JAX's own setting and is
    left to JAX.  Otherwise the cache goes to ``.jax_cache/`` at the root
    of this checkout.  Returns the directory in use.
    """
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    return str(CACHE_DIR)
