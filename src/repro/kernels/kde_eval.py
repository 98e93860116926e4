"""Pallas TPU kernel: direct KDE evaluation (paper eq. 3) — the AQP serving
hot spot (numerical integration of f^ evaluates the KDE at many grid points).

Grid: (eval-tile, data-tile).  The (k, 1) output block for an eval tile stays
resident while all data tiles stream through and accumulate

    f^(p) = norm * mean_i exp(-0.5 * ||p - x_i||^2 / h^2)

d is unrolled statically (d <= 16 in the paper's scope), so the (k, k)
squared-distance slab is built with d broadcast-subtract-square passes on the
VPU — no (k, k, d) intermediate.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .tuning import lane_tile, resolve_tile

TILE = 256


def _kernel(p_ref, x_ref, h_ref, out_ref, *, n: int, k: int, d: int):
    j = pl.program_id(1)
    p = p_ref[...]          # (k, d) eval points
    x = x_ref[...]          # (d, k) data chunk, transposed
    inv_h2 = 1.0 / (h_ref[0] * h_ref[0])

    quad = None
    for a in range(d):
        diff = p[:, a:a + 1] - x[a:a + 1, :]
        quad = diff * diff if quad is None else quad + diff * diff
    cols = j * k + jax.lax.broadcasted_iota(jnp.int32, (k, k), 1)
    vals = jnp.where(cols < n, jnp.exp(-0.5 * quad * inv_h2), 0.0)

    @pl.when(j == 0)
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)

    out_ref[...] += jnp.sum(vals, axis=1, keepdims=True)


def kde_eval(points: jax.Array, x: jax.Array, h: jax.Array,
             tile=None, interpret: bool = True) -> jax.Array:
    """f^(points; x, h).  points: (m, d), x: (n, d) -> (m,).

    `tile` resolves at call time: kwarg > REPRO_KDE_EVAL_TILE > module
    default."""
    tile = resolve_tile("REPRO_KDE_EVAL_TILE", TILE, tile)
    return _kde_eval(points, x, h, tile, interpret)


@functools.partial(jax.jit, static_argnames=("tile", "interpret"))
def _kde_eval(points: jax.Array, x: jax.Array, h: jax.Array,
              tile: int, interpret: bool) -> jax.Array:
    if points.ndim == 1:
        points = points[:, None]
    if x.ndim == 1:
        x = x[:, None]
    m, d = points.shape
    n = x.shape[0]
    # eval points on sublanes, data on lanes; one k serves both axes
    k = lane_tile(tile, max(m, n))
    pad_m = (-m) % k
    pad_n = (-n) % k
    pp = jnp.pad(points, ((0, pad_m), (0, 0)))
    xt = jnp.pad(x, ((0, pad_n), (0, 0))).T

    sums = pl.pallas_call(
        functools.partial(_kernel, n=n, k=k, d=d),
        grid=(pp.shape[0] // k, xt.shape[1] // k),
        in_specs=[
            pl.BlockSpec((k, d), lambda i, j: (i, 0)),
            pl.BlockSpec((d, k), lambda i, j: (0, j)),
            pl.BlockSpec(memory_space=pltpu.SMEM),
        ],
        out_specs=pl.BlockSpec((k, 1), lambda i, j: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((pp.shape[0], 1), x.dtype),
        interpret=interpret,
    )(pp, xt, h.reshape(1).astype(x.dtype))

    norm = (2.0 * math.pi) ** (-d / 2.0) * h ** (-d)
    return (norm / n) * sums[:m, 0]
