"""JAX's persistent compilation cache, as every entry point keeps it.

A full-width detector step takes seconds to compile and a process that
starts cold pays it again. The cache lives in ``$JAX_COMPILATION_CACHE_DIR``
when that is set (JAX reads the variable itself), and otherwise at one
fixed directory of the checkout, ``.jax_cache/`` (ignored by git). The
directory must not move between runs: its path is part of what a later
process looks up.
"""
from __future__ import annotations

import os
from pathlib import Path

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"
CHECKOUT_CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent cache on for this process; returns its path.
    Call before the first compilation."""
    import jax

    path = os.environ.get(ENV_VAR) or str(CHECKOUT_CACHE_DIR)
    jax.config.update("jax_compilation_cache_dir", path)
    return path
