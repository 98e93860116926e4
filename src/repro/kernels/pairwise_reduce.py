"""Pallas TPU kernel: triangular pairwise derivative-kernel reduction (RR_fun).

Computes per-tile partials of   sum_{i<j} K^(r)((x_i - x_j) / g)
— the O(n^2) hot spot of PLUGIN (paper eqs. 16/18, parallel schema §5.4).

TPU adaptation of the paper's Fig. 3 CUDA schema (see DESIGN.md §2):
  * one Pallas grid step per k x k tile of the implicit upper-triangular
    pairwise matrix; the 1-D grid enumerates *only* triangle tiles using the
    paper's Appendix-A index math (eqs. 49/50, `triangle.bx_to_ql`) inside the
    BlockSpec index_maps — no wasted below-diagonal tiles;
  * the E (rows) and F (cols) chunks are staged into VMEM by BlockSpec, the
    analogue of the paper's shared-memory copy (Fig. 5);
  * fun is evaluated on the whole (k, k) tile on the VPU (8x128 lanes >> the
    paper's 4-lane SSE / 32-lane warp);
  * the in-tile reduction is a jnp.sum into a per-tile partial (one lane
    row per tile); the final cross-tile reduction happens outside (XLA
    tree-reduce), mirroring the paper's two-stage block reduction.

k = 256 (2 x 128 lanes, 8-sublane aligned): a (256, 256) fp32 tile is 256 KiB
of VMEM working set (diff + fun values + mask), comfortably inside ~16 MiB.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core import gaussian as G
from .triangle import bx_to_ql, n_tri_tiles
from .tuning import lane_tile, resolve_tile

TILE = 256
_LANES = 128

_FUNS = {"k4": G.k4, "k6": G.k6, "gauss": G.phi}


def _kernel(e_ref, f_ref, g_ref, out_ref, *, kind: str, n: int, k: int):
    bx = pl.program_id(0)
    q, l = bx_to_ql(bx)
    g = g_ref[0]            # SMEM scalar
    e = e_ref[...]          # (k, 1) rows chunk   (global rows q*k + i)
    f = f_ref[...]          # (1, k) cols chunk   (global cols l*k + j)
    diff = (e - f) / g
    vals = _FUNS[kind](diff)
    rows = q * k + jax.lax.broadcasted_iota(jnp.int32, (k, k), 0)
    cols = l * k + jax.lax.broadcasted_iota(jnp.int32, (k, k), 1)
    mask = (rows < cols) & (cols < n) & (rows < n)
    out_ref[...] = jnp.full(out_ref.shape, jnp.sum(jnp.where(mask, vals, 0.0)),
                            out_ref.dtype)


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
    k = lane_tile(tile, n)
    pad = (-n) % k
    xp = jnp.pad(x, (0, pad))
    n_tiles = xp.shape[0] // k
    grid = (n_tri_tiles(n_tiles),)

    # one (1, 1, 128) lane row per triangle tile, its scalar partial
    # broadcast across the lanes (Mosaic has no rank-1 (1,) block)
    partials = pl.pallas_call(
        functools.partial(_kernel, kind=kind, n=n, k=k),
        grid=grid,
        in_specs=[
            pl.BlockSpec((k, 1), lambda bx: (bx_to_ql(bx)[0], 0)),  # E: row chunk q
            pl.BlockSpec((1, k), lambda bx: (0, bx_to_ql(bx)[1])),  # F: col chunk l
            pl.BlockSpec(memory_space=pltpu.SMEM),                 # g (scalar)
        ],
        out_specs=pl.BlockSpec((1, 1, _LANES), lambda bx: (bx, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((grid[0], 1, _LANES), x.dtype),
        interpret=interpret,
    )(xp.reshape(-1, 1), xp.reshape(1, -1), g.reshape(1).astype(x.dtype))
    return jnp.sum(partials[:, 0, 0])
