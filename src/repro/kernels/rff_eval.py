"""Pallas TPU kernel: batched random-Fourier-feature density eval.

One launch evaluates the RFF synopsis dot product for a batch of points:

    raw[p] = sum_j  cos(w_j . x_p + b_j) * z_j

with the fitted state (W, b, z) from `repro.synopses.rff` (z carries the
2/D feature scale and the sample mean; the caller applies the kernel
normaliser and the zero clip).  Grid: (point-tile major, feature-tile
minor) — the (pk, 1) accumulator block stays resident while feature tiles
stream through, the same pattern as aqp_boxes.py.  Padded features
contribute exactly zero because z is zero-padded, so no feature mask is
needed; padded points are sliced off by the caller.

Tile sizes resolve per call (REPRO_RFF_TILE feature tile /
REPRO_RFF_P_TILE point tile, see tuning.resolve_tile); call-site
kwargs win.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from .tuning import lane_tile, resolve_tile, row_tile

TILE = 512     # feature-tile default (env: REPRO_RFF_TILE)
P_TILE = 256   # point-tile default (env: REPRO_RFF_P_TILE)


def _kernel(p_ref, w_ref, b_ref, z_ref, out_ref, *, d: int):
    j = pl.program_id(1)     # feature-tile index (minor: varies fastest)
    p = p_ref[...]           # (pk, d) query points (padded rows harmless)
    w = w_ref[...]           # (d, fk) feature frequencies, transposed
    b = b_ref[...]           # (1, fk) feature phases
    z = z_ref[...]           # (fk, 1) scaled sample feature mean (0 on pad)

    # the projection is d broadcast multiply-adds on the VPU (d is small),
    # exact f32; the feature contraction runs on the MXU at f32 precision
    proj = b
    for a in range(d):
        proj = proj + p[:, a:a + 1] * w[a:a + 1, :]    # (pk, fk)
    partial = jnp.dot(jnp.cos(proj), z,
                      precision=jax.lax.Precision.HIGHEST)   # (pk, 1)

    @pl.when(j == 0)
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)

    out_ref[...] += partial


@functools.partial(jax.jit, static_argnames=("tile", "p_tile", "interpret"))
def _rff_density(points, w, b, z, tile, p_tile, interpret):
    m, d = points.shape
    D = w.shape[0]
    if m == 0 or D == 0:
        return jnp.zeros((m,), points.dtype)

    # Layout: points on sublanes, features on lanes; no operand is 1-D, so
    # no block depends on XLA's tiling of a vector.
    pk = row_tile(p_tile, m)
    fk = lane_tile(tile, D)
    dt = points.dtype
    pp = jnp.pad(points, ((0, (-m) % pk), (0, 0)))
    wt = jnp.pad(w, ((0, (-D) % fk), (0, 0))).astype(dt).T
    bp = jnp.pad(b, (0, (-D) % fk)).astype(dt).reshape(1, -1)
    zp = jnp.pad(z, (0, (-D) % fk)).astype(dt).reshape(-1, 1)

    out = pl.pallas_call(
        functools.partial(_kernel, d=d),
        grid=(pp.shape[0] // pk, wt.shape[1] // fk),
        in_specs=[
            pl.BlockSpec((pk, d), lambda i, j: (i, 0)),
            pl.BlockSpec((d, fk), lambda i, j: (0, j)),
            pl.BlockSpec((1, fk), lambda i, j: (0, j)),
            pl.BlockSpec((fk, 1), lambda i, j: (j, 0)),
        ],
        out_specs=pl.BlockSpec((pk, 1), lambda i, j: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((pp.shape[0], 1), dt),
        interpret=interpret,
    )(pp, wt, bp, zp)
    return out[:m, 0]


def rff_density(points: jax.Array, w: jax.Array, b: jax.Array, z: jax.Array,
                tile: int = None, p_tile: int = None,
                interpret: bool = True):
    """Un-normalised RFF densities: cos(points @ W.T + b) @ z.

    points: (m, d); w: (D, d); b/z: (D,).  Returns (m,) raw feature dots —
    the caller (`RFFSynopsis.eval_batch`) applies the kernel normaliser and
    the max(., 0) clip.
    """
    tile = resolve_tile("REPRO_RFF_TILE", TILE, tile)
    p_tile = resolve_tile("REPRO_RFF_P_TILE", P_TILE, p_tile)
    return _rff_density(points, w, b, z, tile, p_tile, interpret)
