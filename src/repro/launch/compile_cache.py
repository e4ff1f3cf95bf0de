"""JAX's persistent compilation cache, placed by the entry points.

Library modules set nothing on import; each entry point (`chip_smoke.py`,
`repro.launch.serve`, `repro.launch.frontend`, `benchmarks.run`) calls
`enable_compile_cache()` once before it compiles. Where
``JAX_COMPILATION_CACHE_DIR`` is set, JAX already reads it and nothing is
set in code; otherwise the cache lives in one fixed, git-ignored
directory of the checkout — a fixed path, because the path is part of
what a later run must find again.
"""

from __future__ import annotations

import os
import pathlib

import jax

CHECKOUT_CACHE_DIR = pathlib.Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Point JAX's compilation cache at its directory; return that path."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(CHECKOUT_CACHE_DIR))
    return str(CHECKOUT_CACHE_DIR)
