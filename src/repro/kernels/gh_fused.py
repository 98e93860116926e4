"""Pallas TPU kernel: fused LSCV_H objective inner sum (paper §6.3).

For each triangle tile, computes the quadratic forms s = (x_i-x_j)^T H^-1
(x_i-x_j) *and immediately* applies T_H and reduces — because H^-1 changes at
every Nelder-Mead step, S values cannot be precomputed (paper §4.5 last
paragraph), so the paper fuses exponent computation with the T reduction in a
single gpu-kernel.  Same fusion here: one VMEM round-trip per tile, per-tile
scalar partial out.

    T_H(s) = c_kk * exp(-s/4) - 2 * c_k * exp(-s/2)        (eqs. 33-35)

Triangle-only 1-D grid via Appendix-A index math, MXU quadratic-form
expansion S = qe + qf - 2 e M f^T (as in lscv_grid).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .triangle import bx_to_ql, n_tri_tiles
from .tuning import lane_tile, resolve_tile

TILE = 256
_LANES = 128


def _kernel(e_ref, f_ref, m_ref, c_ref, out_ref, *, n: int, k: int):
    bx = pl.program_id(0)
    q, l = bx_to_ql(bx)
    e = e_ref[...]                  # (k, d)
    f = f_ref[...]
    m = m_ref[...]                  # (d, d) = H^-1
    c_k = c_ref[0]                  # SMEM scalars
    c_kk = c_ref[1]

    hp = jax.lax.Precision.HIGHEST
    me = jnp.dot(e, m, precision=hp)
    qe = jnp.sum(me * e, axis=1)
    mf = jnp.dot(f, m, precision=hp)
    qf = jnp.sum(mf * f, axis=1)
    cross = jax.lax.dot_general(me, f, (((1,), (1,)), ((), ())),
                                precision=hp,
                                preferred_element_type=jnp.float32)
    s = qe[:, None] + qf[None, :] - 2.0 * cross.astype(e.dtype)

    t = c_kk * jnp.exp(-0.25 * s) - 2.0 * c_k * jnp.exp(-0.5 * s)
    rows = q * k + jax.lax.broadcasted_iota(jnp.int32, (k, k), 0)
    cols = l * k + jax.lax.broadcasted_iota(jnp.int32, (k, k), 1)
    mask = (rows < cols) & (cols < n) & (rows < n)
    out_ref[...] = jnp.full(out_ref.shape, jnp.sum(jnp.where(mask, t, 0.0)),
                            out_ref.dtype)


def gh_fused_sum(x: jax.Array, h_inv: jax.Array, c_k, c_kk,
                 tile=None, interpret: bool = True) -> jax.Array:
    """sum_{i<j} T_H(x_i - x_j).  x: (n, d), h_inv: (d, d).

    `tile` resolves at call time: kwarg > REPRO_GH_TILE > module default."""
    tile = resolve_tile("REPRO_GH_TILE", TILE, tile)
    return _gh_fused_sum(x, h_inv, c_k, c_kk, tile, interpret)


@functools.partial(jax.jit, static_argnames=("tile", "interpret"))
def _gh_fused_sum(x: jax.Array, h_inv: jax.Array, c_k, c_kk,
                  tile: int, interpret: bool) -> jax.Array:
    n, d = x.shape
    k = lane_tile(tile, n)
    pad = (-n) % k
    xp = jnp.pad(x, ((0, pad), (0, 0)))
    n_tiles = xp.shape[0] // k
    grid = (n_tri_tiles(n_tiles),)
    consts = jnp.stack([jnp.asarray(c_k, x.dtype), jnp.asarray(c_kk, x.dtype)])

    # one (1, 1, 128) lane row per triangle tile holds its scalar partial
    partials = pl.pallas_call(
        functools.partial(_kernel, n=n, k=k),
        grid=grid,
        in_specs=[
            pl.BlockSpec((k, d), lambda bx: (bx_to_ql(bx)[0], 0)),
            pl.BlockSpec((k, d), lambda bx: (bx_to_ql(bx)[1], 0)),
            pl.BlockSpec((d, d), lambda bx: (0, 0)),
            pl.BlockSpec(memory_space=pltpu.SMEM),
        ],
        out_specs=pl.BlockSpec((1, 1, _LANES), lambda bx: (bx, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((grid[0], 1, _LANES), x.dtype),
        interpret=interpret,
        name="_gh_fused_sum",
    )(xp, xp, h_inv.astype(x.dtype), consts)
    return jnp.sum(partials[:, 0, 0])
