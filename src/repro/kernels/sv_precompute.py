"""Pallas TPU kernel: tiled quadratic-form precompute (paper §5.5 / Fig. 6).

Fills the strict upper triangle of  S_{ij} = (x_i - x_j)^T M (x_i - x_j)
— the §4.5 LSCV_h precompute (S(v) values, eq. 39).

Two in-kernel algorithms, selected statically:

  * "paper": a faithful port of the paper's eq. (60) loop nest — for each of
    the d x d (c, a) pairs, a rank-1 broadcast update of the (k, k) tile.
    O(d^2 k^2) VPU flops per tile; this is what the CUDA kernel does.

  * "mxu": the TPU-native beyond-paper formulation.  Expand the quadratic
    form (M symmetric):
        S_{rp} = qe_r + qf_p - 2 e_r^T M f_p
    where qe_r = e_r^T M e_r, qf_p = f_p^T M f_p.  The cross term is a
    (k,d) x (d,d) x (d,k) matmul chain that runs on the MXU instead of the
    VPU, turning the tile body from d^2 elementwise passes into two small
    matmuls + rank-1 broadcasts.  Identical results (validated in tests);
    ~d/2 x fewer VPU ops per tile — the win measured in EXPERIMENTS.md §Perf.

Layout: x is staged as A^T, i.e. (n, d) row-major so a (k, d) chunk is
contiguous — the same row-major-friendly access the paper engineers for its
chunk rows F_{x,:} (end of §5.5).  d rides in the lane dimension (padded to
128 by Mosaic); k = 256 rows in sublanes.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from .tuning import lane_tile, resolve_tile

TILE = 256


def _kernel(e_ref, f_ref, m_ref, out_ref, *, n: int, k: int, d: int, algorithm: str):
    q = pl.program_id(0)
    l = pl.program_id(1)
    e = e_ref[...]          # (k, d) rows-chunk of points
    f = f_ref[...]          # (k, d) cols-chunk of points
    m = m_ref[...]          # (d, d)

    if algorithm == "paper":
        # eq. (60): Y_{r,:} = sum_a (sum_c (e_{c,r} - F_{c,:}) m_{c,a}) (e_{a,r} - F_{a,:})
        y = jnp.zeros((k, k), e.dtype)
        for a in range(d):
            part = jnp.zeros((k, k), e.dtype)
            for c in range(d):
                part = part + m[c, a] * (e[:, c][:, None] - f[:, c][None, :])
            y = y + part * (e[:, a][:, None] - f[:, a][None, :])
    else:
        # "mxu": S = qe[:,None] + qf[None,:] - 2 E M F^T   (M symmetric)
        hp = jax.lax.Precision.HIGHEST               # f32 passes on the MXU
        me = jnp.dot(e, m, precision=hp)             # (k, d) MXU
        qe = jnp.sum(me * e, axis=1)                 # (k,)
        mf = jnp.dot(f, m, precision=hp)
        qf = jnp.sum(mf * f, axis=1)                 # (k,)
        cross = jax.lax.dot_general(me, f, (((1,), (1,)), ((), ())),
                                    precision=hp,
                                    preferred_element_type=jnp.float32)  # (k, k)
        y = qe[:, None] + qf[None, :] - 2.0 * cross.astype(e.dtype)

    rows = q * k + jax.lax.broadcasted_iota(jnp.int32, (k, k), 0)
    cols = l * k + jax.lax.broadcasted_iota(jnp.int32, (k, k), 1)
    mask = (rows < cols) & (cols < n) & (rows < n)
    out_ref[...] = jnp.where(mask, y, 0.0)


def sv_matrix(x: jax.Array, m: jax.Array, tile=None,
              algorithm: str = "mxu", interpret: bool = True) -> jax.Array:
    """Dense masked (n, n) matrix of S(v) values. x: (n, d), m: (d, d).

    `tile` resolves at call time: kwarg > REPRO_SV_TILE > module default."""
    tile = resolve_tile("REPRO_SV_TILE", TILE, tile)
    return _sv_matrix(x, m, tile, algorithm, interpret)


@functools.partial(jax.jit, static_argnames=("tile", "algorithm", "interpret"))
def _sv_matrix(x: jax.Array, m: jax.Array, tile: int,
               algorithm: str, interpret: bool) -> jax.Array:
    n, d = x.shape
    k = lane_tile(tile, n)
    pad = (-n) % k
    xp = jnp.pad(x, ((0, pad), (0, 0)))
    n_tiles = xp.shape[0] // k

    out = pl.pallas_call(
        functools.partial(_kernel, n=n, k=k, d=d, algorithm=algorithm),
        grid=(n_tiles, n_tiles),
        in_specs=[
            pl.BlockSpec((k, d), lambda q, l: (q, 0)),
            pl.BlockSpec((k, d), lambda q, l: (l, 0)),
            pl.BlockSpec((d, d), lambda q, l: (0, 0)),
        ],
        out_specs=pl.BlockSpec((k, k), lambda q, l: (q, l)),
        out_shape=jax.ShapeDtypeStruct((xp.shape[0], xp.shape[0]), x.dtype),
        interpret=interpret,
    )(xp, xp, m.astype(x.dtype))
    return out[:n, :n]
