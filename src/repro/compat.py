"""Thin names over the installed jax API, so call sites stay version-neutral.

`shard_map` is jax's top-level one; `pvary` tags a replicated value as
device-varying inside it (`jax.lax.pcast(..., to="varying")`).
"""
from __future__ import annotations

import jax

shard_map = jax.shard_map


def pvary(x, axis_names):
    """Mark `x` as varying over `axis_names` inside `shard_map`."""
    return jax.lax.pcast(x, tuple(axis_names), to="varying")
