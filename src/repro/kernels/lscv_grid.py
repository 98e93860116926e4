"""Pallas TPU kernel: the LSCV_h grid sums (paper §4.5, §6.2), streamed over
the upper-triangle tiles of the implicit pairwise matrix.

For every h of the grid it sums T~ over the pairs i < j (eqs. 40-43):

    T~(S; h) = c_kk exp(-S / (4 h^2)) - 2 c_k exp(-S / (2 h^2))
             = c_kk e (1 - r e),   e = exp(-S / (4 h^2)),  r = 2 c_k / c_kk

with S = v^T Sigma^-1 v, v = x_i - x_j (eq. 39).  The grid comes in as its
exponent scales -1 / (4 h^2) (`core.lscv.exp_scales`), which the caller
also takes g's 1 / h from.  The paper's §6.2 scheme
precomputes every S value and reduces them once per h; here no S leaves
VMEM:

  * the 1-D grid enumerates only the upper-triangle k x k tiles, with the
    triangular tile iteration of `pairwise_reduce` (`triangle.bx_to_ql`,
    column by column, `tuning.square_tile` for the side);
  * each step forms its tile's S values once in a VMEM scratch: at d = 1
    (x_i - x_j)^2 / sigma^2 on the VPU, at d > 1 the MXU quadratic form
    qe + qf - 2 e M f^T (M symmetric; the cross term on the MXU).  Pairs with i >= j or j >= n get
    S = +inf, so they add exp(-inf) = 0: one mask per tile, shared by the
    whole grid;
  * the same visit folds the tile into the partials of every h: per h two
    (8, k) float32 register accumulators over the tile's rows, of e and of
    e^2 (one exp and four vector operations per pair), combined as
    sum e - r sum e^2, summed to one 128-lane row and added into its column
    chunk's (n_h, 128) output block, which stays resident while the
    column's tiles stream through;
  * the (column chunk, lane) partials of each h are added outside the kernel
    pairwise with their rounding errors carried, and returned as a float32
    pair (hi, lo): g(h)'s neighbours near its minimum can differ by less
    than one float32 rounding, so the argmin is taken on the pair.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.reductions import pair_sum, two_prod

from .triangle import bx_to_ql, n_tri_tiles
from .tuning import resolve_tile, square_tile

TILE = 1024
_LANES = 128
_SUBLANES = 8
_UNROLL = 8                     # row blocks of 8 per loop trip


def _tile_s(e, f_t, m, d: int):
    """S values of one tile: rows e (k, d) against columns f_t (d, k)."""
    if d == 1:
        v = e - f_t                                   # (k, 1) - (1, k)
        return v * v * m[0, 0]
    hp = jax.lax.Precision.HIGHEST                    # f32 passes on the MXU
    me = jnp.dot(e, m, precision=hp)                  # (k, d)
    qe = jnp.sum(me * e, axis=1, keepdims=True)       # (k, 1)
    mf = jnp.dot(m, f_t, precision=hp)                # (d, k)
    qf = jnp.sum(mf * f_t, axis=0, keepdims=True)     # (1, k)
    cross = jnp.dot(me, f_t, precision=hp,
                    preferred_element_type=jnp.float32)   # (k, k)
    return jnp.maximum(qe + qf - 2.0 * cross, 0.0)


def _kernel(m_ref, c_ref, r_ref, row_ref, col_ref, out_ref, s_ref, *,
            n: int, k: int, d: int, n_h: int):
    q, l = bx_to_ql(pl.program_id(0))
    ii = q * k + jax.lax.broadcasted_iota(jnp.int32, (k, k), 0)
    jj = l * k + jax.lax.broadcasted_iota(jnp.int32, (k, k), 1)
    s = _tile_s(row_ref[...], col_ref[...], m_ref[...], d)
    s_ref[...] = jnp.where((ii < jj) & (jj < n), s, jnp.inf)
    r = r_ref[0]                                      # 2 c_k / c_kk, SMEM

    @pl.when(q == 0)                  # the column's first tile: fresh block
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)

    rows = jax.lax.broadcasted_iota(jnp.int32, (_SUBLANES, _LANES), 0)

    def h_block(b, carry):
        # eight h per trip: one aligned (8, 128) row block of the output
        blk = jnp.zeros((_SUBLANES, _LANES), jnp.float32)
        for t in range(_SUBLANES):
            c = c_ref[b * _SUBLANES + t]              # -1 / (4 h^2), SMEM

            def row_block(u, acc):
                sv = s_ref[pl.ds(pl.multiple_of(u * _SUBLANES, _SUBLANES),
                                 _SUBLANES), :]
                e = jnp.exp(sv * c)
                return acc[0] + e, acc[1] + e * e

            def trip(v, acc):
                # _UNROLL row blocks, unrolled as it lowers (traced once)
                return jax.lax.fori_loop(
                    0, _UNROLL, lambda w, a: row_block(v * _UNROLL + w, a),
                    acc, unroll=True)

            zero = jnp.zeros((_SUBLANES, k), jnp.float32)
            sum_e, sum_e2 = jax.lax.fori_loop(
                0, k // (_SUBLANES * _UNROLL), trip, (zero, zero))
            acc = sum_e - r * sum_e2
            lanes = acc[:, :_LANES]
            for g in range(1, k // _LANES):
                lanes = lanes + acc[:, g * _LANES:(g + 1) * _LANES]
            part = jnp.sum(lanes, axis=0, keepdims=True)   # (1, 128)
            blk = jnp.where(rows == t, part, blk)
        at = pl.multiple_of(b * _SUBLANES, _SUBLANES)
        out_ref[0, pl.ds(at, _SUBLANES), :] += blk
        return carry

    jax.lax.fori_loop(0, n_h // _SUBLANES, h_block, 0)


def lscv_grid_sums(x: jax.Array, sigma_inv: jax.Array, scales: jax.Array,
                   c_k, c_kk, tile=None, interpret: bool = True):
    """For each h on the grid: sum_{i<j} T~(x_i - x_j), as a float32 pair
    (hi, lo) of (n_h,) arrays whose sum carries the digits one float32
    would round away (`core.reductions.pair_sum`).

    x: (n, d) or (n,); sigma_inv: (d, d); scales: (n_h,), -1 / (4 h^2).  `tile` resolves at call time:
    kwarg > REPRO_LSCV_TILE > module default, rounded as `square_tile`
    rounds it."""
    tile = resolve_tile("REPRO_LSCV_TILE", TILE, tile)
    return _lscv_grid_sums(x, sigma_inv, scales, c_k, c_kk, tile, interpret)


@functools.partial(jax.jit, static_argnames=("tile", "interpret"))
def _lscv_grid_sums(x: jax.Array, sigma_inv: jax.Array, scales: jax.Array,
                    c_k, c_kk, tile: int, interpret: bool) -> jax.Array:
    x = x.astype(jnp.float32)
    if x.ndim == 1:
        x = x[:, None]
    n, d = x.shape
    k = square_tile(tile, n)
    xp = jnp.pad(x, ((0, (-n) % k), (0, 0)))
    n_tiles = xp.shape[0] // k
    n_h = scales.shape[0]
    n_hp = -(-n_h // _SUBLANES) * _SUBLANES
    # padded grid points get a finite negative scale (their sums are dropped)
    c = jnp.pad(scales.astype(jnp.float32), (0, n_hp - n_h),
                constant_values=-1.0)
    c_k = jnp.asarray(c_k, jnp.float32)
    c_kk = jnp.asarray(c_kk, jnp.float32)
    r = (2.0 * c_k / c_kk).reshape(1)
    smem = pl.BlockSpec(memory_space=pltpu.SMEM)

    partials = pl.pallas_call(
        functools.partial(_kernel, n=n, k=k, d=d, n_h=n_hp),
        grid=(n_tri_tiles(n_tiles),),
        in_specs=[
            pl.BlockSpec((d, d), lambda bx: (0, 0)),              # Sigma^-1
            smem,                                                 # -1/(4h^2)
            smem,                                                 # r
            pl.BlockSpec((k, d), lambda bx: (bx_to_ql(bx)[0], 0)),   # rows q
            pl.BlockSpec((d, k), lambda bx: (0, bx_to_ql(bx)[1])),   # cols l
        ],
        out_specs=pl.BlockSpec((1, n_hp, _LANES),
                               lambda bx: (bx_to_ql(bx)[1], 0, 0)),
        out_shape=jax.ShapeDtypeStruct((n_tiles, n_hp, _LANES), jnp.float32),
        scratch_shapes=[pltpu.VMEM((k, k), jnp.float32)],
        interpret=interpret,
        name="_lscv_grid_sums",
    )(sigma_inv.astype(jnp.float32), c, r, xp, xp.T)
    hi, lo = pair_sum(
        jnp.transpose(partials, (1, 0, 2)).reshape(n_hp, n_tiles * _LANES))
    t_hi, t_lo = two_prod(hi[:n_h], c_kk)
    return t_hi, t_lo + lo[:n_h] * c_kk
