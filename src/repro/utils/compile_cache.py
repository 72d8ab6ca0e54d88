"""Placement of JAX's persistent compilation cache.

Entry points (``chip_smoke.py``, ``benchmarks/run.py``, the
``repro.launch`` mains) call :func:`use_compile_cache` once at start-up;
library code never does, so importing ``repro`` changes no JAX setting.

Where ``JAX_COMPILATION_CACHE_DIR`` is set, the cache lives there and no
other directory is named in code.  Otherwise it lives at the fixed
``<checkout>/.jax_cache`` (git-ignored): the directory is part of each
entry's key, so a path that moved between runs would never hit.
"""
from __future__ import annotations

import os
import pathlib

CHECKOUT = pathlib.Path(__file__).resolve().parents[3]
DEFAULT_DIR = CHECKOUT / ".jax_cache"


def use_compile_cache() -> str:
    """Point JAX's persistent compilation cache at its directory and
    return that directory."""
    import jax

    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") or str(DEFAULT_DIR)
    jax.config.update("jax_compilation_cache_dir", path)
    return path
