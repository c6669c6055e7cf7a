"""JAX's persistent compilation cache, placed from outside the program.

Every entry point that compiles for the chip (`launch/serve.py`,
`launch/train.py`, `chip_smoke.py`) calls `enable_compile_cache()` once,
before its first compile. Where `JAX_COMPILATION_CACHE_DIR` is set, JAX
reads it itself and that directory is the cache: nothing here overrides
it. Otherwise the cache sits at one fixed, git-ignored path inside the
checkout, so a later run from the same checkout finds what an earlier
one compiled.
"""
from __future__ import annotations

import os
import pathlib

import jax

DEFAULT_DIR = pathlib.Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent compilation cache on; returns its directory."""
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = str(DEFAULT_DIR)
        jax.config.update("jax_compilation_cache_dir", path)
    return path
