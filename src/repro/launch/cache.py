"""Where JAX's persistent compilation cache lives.

Entry points call :func:`use_compile_cache` once, before they compile
anything, so a second run of the same program (or another entry point
compiling the same step) loads the executable instead of compiling it.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

# the checkout root: src/repro/launch/cache.py -> parents[3]
_FIXED_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def use_compile_cache() -> str:
    """Turn on the persistent compilation cache; returns its directory.

    ``JAX_COMPILATION_CACHE_DIR``, when set, is used as is (JAX reads
    the variable itself) and no other directory is set. Otherwise the
    cache is the checkout's fixed, git-ignored ``.jax_cache/``: the
    directory must not move between runs (no temp name, pid or time in
    it), or no run ever finds what an earlier one wrote.
    """
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = str(_FIXED_DIR)
        jax.config.update("jax_compilation_cache_dir", path)
    return path
