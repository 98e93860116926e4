"""Pallas TPU kernel: triangular pairwise derivative-kernel reduction (RR_fun).

Computes per-tile partials of   sum_{i<j} K^(r)((x_i - x_j) / g)
— the O(n^2) hot spot of PLUGIN (paper eqs. 16/18, parallel schema §5.4).

TPU adaptation of the paper's Fig. 3 CUDA schema (see DESIGN.md §2):
  * one Pallas grid step per k x k tile of the implicit upper-triangular
    pairwise matrix; the 1-D grid enumerates *only* triangle tiles using the
    paper's Appendix-A index math (eqs. 49/50, `triangle.bx_to_ql`) inside the
    BlockSpec index_maps — no wasted below-diagonal tiles;
  * the grid walks the triangle column by column, so the column chunk l
    changes once per column: it is staged into SMEM as k scalars (fetched
    n/k times in all), and the row chunk q into VMEM lane-dense, k/128 rows
    of 128 lanes (k * 4 bytes per step);
  * each step broadcasts one column scalar x_j against the whole row chunk
    on the VPU (the paper's shared-memory broadcast, Fig. 5) and adds the
    kernel values into a (k/128, 128) float32 register accumulator, folded to
    (8, 128) and written once per tile; the cross-tile sum happens outside
    (XLA tree-reduce), mirroring the paper's two-stage block reduction;
  * only diagonal tiles, and the ragged last column, build a mask; interior
    tiles count every pair they hold, each exactly once.  A diagonal tile
    runs in 1,024-column bands, each against the row vectors up to its own,
    so it evaluates little more than the pairs it keeps.

The kernel works in w = -t^2/2 = (x_i - x_j)^2 * (-1 / (2 g^2)), one scale
hoisted out of the pair loop: K^(r)(t) = c_r P_r(w) exp(w) / sqrt(2 pi), with
c_r and 1/sqrt(2 pi) applied to the sum.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core import gaussian as G
from .triangle import bx_to_ql, n_tri_tiles
from .tuning import resolve_tile

TILE = 4096
_UNROLL = 32                    # columns per loop trip
_LANES = 128
_SUBLANES = 8

# (c_r, P_r) with t^2 = -2 w:
#   K4: t^4 - 6 t^2 + 3                 =  4 (w^2 + 3 w + 0.75)
#   K6: t^6 - 15 t^4 + 45 t^2 - 15      = -8 (w^3 + 7.5 w^2 + 11.25 w + 1.875)
_POLYS = {
    "k4": (4.0, lambda w: (w + 3.0) * w + 0.75),
    "k6": (-8.0, lambda w: ((w + 7.5) * w + 11.25) * w + 1.875),
    "gauss": (1.0, None),
}


def _fold(acc: jax.Array) -> jax.Array:
    """(m, 128) accumulator -> its (8, 128) partial (m <= 8: kept whole)."""
    m = acc.shape[0]
    if m <= _SUBLANES:
        return acc
    return acc.reshape(m // _SUBLANES, _SUBLANES, _LANES).sum(0)


def _kernel(c_ref, col_ref, row_ref, out_ref, *, kind: str, n: int, k: int):
    bx = pl.program_id(0)
    q, l = bx_to_ql(bx)
    c = c_ref[0]                    # -1 / (2 g^2), SMEM scalar
    poly = _POLYS[kind][1]
    n_rows = k // _LANES
    n_cols = jnp.minimum(k, n - l * k)   # real points in column chunk l

    def band(lo: int, hi: int, m: int, lim=None) -> jax.Array:
        """Columns [lo, hi) of chunk l against the first m row vectors of
        chunk q (rows 128 m); `lim(j)`, where given, bounds the rows that
        pair with column j."""
        rows = row_ref[:m, :]       # x_{q k + i}, lane-dense
        i_loc = (jax.lax.broadcasted_iota(jnp.int32, rows.shape, 0) * _LANES
                 + jax.lax.broadcasted_iota(jnp.int32, rows.shape, 1))

        def pair(j, acc):
            d = rows - col_ref[0, j]    # x_j from SMEM, broadcast by the VPU
            w = d * d * c
            t = jnp.exp(w) if poly is None else poly(w) * jnp.exp(w)
            if lim is not None:
                t = jnp.where(i_loc < lim(j), t, 0.0)
            return acc + t

        def trip(u, acc):
            # _UNROLL columns, unrolled as it lowers: the body is traced
            # once (traced _UNROLL times, it cost seconds of set-up)
            base = lo + u * _UNROLL
            return jax.lax.fori_loop(0, _UNROLL,
                                     lambda v, a: pair(base + v, a), acc,
                                     unroll=True)

        acc = jax.lax.fori_loop(0, (hi - lo) // _UNROLL, trip,
                                jnp.zeros(rows.shape, jnp.float32))
        return _fold(acc)

    @pl.when(q == l)
    def _diagonal():
        # pairs i < j only: the columns of each 1024-wide band pair with the
        # row vectors up to the band's own, masked by i < j (and j < n)
        width = min(k, _SUBLANES * _LANES)
        out_ref[...] = sum(
            band(lo, lo + width, min(n_rows, (lo + width) // _LANES),
                 lambda j: jnp.where(j < n_cols, j, 0))
            for lo in range(0, k, width))

    interior = q != l
    if n % k:                       # the last column holds padding
        @pl.when(interior & (l == n // k))
        def _ragged():
            out_ref[...] = band(0, k, n_rows,
                                lambda j: jnp.where(j < n_cols, k, 0))
        interior = interior & (l != n // k)

    @pl.when(interior)
    def _interior():                # every pair counted, no mask
        out_ref[...] = band(0, k, n_rows)


def _tile(tile: int, n: int) -> int:
    """Square tile side: the least power of two from 128 (the lane width)
    that reaches `tile` or covers the n points."""
    k = _LANES
    while k < tile and k < n:
        k *= 2
    return k


def pairwise_scaled_ksum(x: jax.Array, g: jax.Array, kind: str = "k4",
                         tile=None, interpret: bool = True) -> jax.Array:
    """sum_{i<j} fun((x_i - x_j)/g) for 1-D x via the triangular tile kernel.

    `tile` resolves at call time: kwarg > REPRO_PAIRWISE_TILE > module
    default — never frozen into a function default at import."""
    tile = resolve_tile("REPRO_PAIRWISE_TILE", TILE, tile)
    return _pairwise_scaled_ksum(x, g, kind, tile, interpret)


@functools.partial(jax.jit, static_argnames=("kind", "tile", "interpret"))
def _pairwise_scaled_ksum(x: jax.Array, g: jax.Array, kind: str,
                          tile: int, interpret: bool) -> jax.Array:
    n = x.shape[0]
    k = _tile(tile, n)
    xp = jnp.pad(x.astype(jnp.float32), (0, (-n) % k))
    n_tiles = xp.shape[0] // k
    grid = (n_tri_tiles(n_tiles),)
    r = k // _LANES
    fold = min(r, _SUBLANES)
    g = jnp.asarray(g, jnp.float32)
    c = (-0.5 / (g * g)).reshape(1)

    partials = pl.pallas_call(
        functools.partial(_kernel, kind=kind, n=n, k=k),
        grid=grid,
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),                  # c
            pl.BlockSpec((None, 1, k),                               # column
                         lambda bx: (bx_to_ql(bx)[1], 0, 0),
                         memory_space=pltpu.SMEM),                  # chunk l
            pl.BlockSpec((None, r, _LANES),                          # row
                         lambda bx: (bx_to_ql(bx)[0], 0, 0)),        # chunk q
        ],
        out_specs=pl.BlockSpec((None, fold, _LANES), lambda bx: (bx, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((grid[0], fold, _LANES), jnp.float32),
        interpret=interpret,
        name="_pairwise_scaled_ksum",
    )(c, xp.reshape(n_tiles, 1, k), xp.reshape(n_tiles, r, _LANES))
    c_r = _POLYS[kind][0]
    return (c_r * G.INV_SQRT_2PI) * jnp.sum(partials)
