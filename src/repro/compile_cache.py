"""JAX's persistent compilation cache, at a place a checkout finds again.

A cold engine compile takes tens of seconds to minutes, and every fresh
process pays it. The persistent cache keeps the compiled executables on
disk; its directory is part of what a later process must find, so it
never moves: ``JAX_COMPILATION_CACHE_DIR`` when that is set (JAX reads
the variable itself, so nothing else is set here), otherwise
``.jax_cache/`` at the root of this checkout.

Only entry points call :func:`use_persistent_cache` — the CLI's and the
server's ``__main__``, ``chip_smoke.py`` and ``benchmarks/bench_union.py``
— never library code or anything the tests import, so a test process
compiles with the cache as JAX's own defaults leave it.
"""
from __future__ import annotations

import os
from pathlib import Path

CHECKOUT_CACHE = Path(__file__).resolve().parents[2] / ".jax_cache"


def use_persistent_cache() -> str:
    """Turn the cache on for this process; returns its directory."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    import jax

    jax.config.update("jax_compilation_cache_dir", str(CHECKOUT_CACHE))
    return str(CHECKOUT_CACHE)
