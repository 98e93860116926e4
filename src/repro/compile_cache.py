"""Where JAX keeps its persistent compilation cache for this repo's entry
points (chip_smoke.py, `python -m repro.launch.serve`, benchmarks/run.py).

A cache entry is found again only at the same path, so the path is fixed:
`JAX_COMPILATION_CACHE_DIR` when it is set (JAX reads that variable itself,
and nothing here overrides it), otherwise `<checkout>/.jax_cache`, which git
ignores.  Library code never calls this; only `main`-level entry points do.
"""
from __future__ import annotations

from pathlib import Path

import jax

CHECKOUT = Path(__file__).resolve().parents[2]


def use_compile_cache() -> str:
    """Turn on the persistent compilation cache; returns its directory."""
    configured = jax.config.jax_compilation_cache_dir
    if configured:
        return configured
    path = str(CHECKOUT / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path
