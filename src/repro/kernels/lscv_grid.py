"""Pallas TPU kernel: LSCV_h grid-search phase over precomputed S values.

The paper's §6.2 GPU scheme launches a 2-D computation grid — one *row of
blocks per tested h* — reducing T~ over the same precomputed S(v) values for
every h.  Here: 2-D Pallas grid (h-tile, S-tile); each step folds one (k, k)
slab of S values into `hk` per-h partials:

    T~(S; h) = c_kk * exp(-S / (4 h^2)) - 2 c_k * exp(-S / (2 h^2))  (eqs. 40-42)

The S matrix (with mask) is read O(n_h / hk) times — exactly the reuse the
§4.5 reformulation buys; the accumulator output revisits the same block across
the S-tile-index dimension (grid minor axis), the standard Pallas accumulation
pattern.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .tuning import lane_tile, resolve_tile

TILE = 256
H_TILE = 8
_LANES = 128


def _kernel(s_ref, w_ref, hinv_ref, c_ref, out_ref, *, hk: int):
    i = pl.program_id(0)   # h-tile index
    j = pl.program_id(1)   # S-tile index (minor: varies fastest)
    s = s_ref[...]         # (k, k) S values (masked entries are 0)
    w = w_ref[...]         # (k, k) mask weights in {0, 1}
    c_k = c_ref[0]         # SMEM scalars
    c_kk = c_ref[1]

    @pl.when(j == 0)
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)

    for t in range(hk):            # unrolled over the h block
        inv_h2 = hinv_ref[i * hk + t]       # 1 / h^2, SMEM
        e2 = jnp.exp(-0.5 * s * inv_h2)
        e4 = jnp.exp(-0.25 * s * inv_h2)
        part = jnp.sum((c_kk * e4 - 2.0 * c_k * e2) * w)
        out_ref[t:t + 1, :] += jnp.full((1, out_ref.shape[1]), part,
                                        out_ref.dtype)


def lscv_grid_sums(x: jax.Array, sigma_inv: jax.Array, h_grid: jax.Array,
                   c_k, c_kk, tile=None, h_tile=None,
                   interpret: bool = True) -> jax.Array:
    """For each h on the grid: sum_{i<j} T~(x_i - x_j).  Returns (n_h,).

    Phase 1 (S precompute) uses the sv_precompute kernel; phase 2 is this one.
    Tiles resolve at call time: kwarg > REPRO_LSCV_TILE / REPRO_LSCV_H_TILE >
    module defaults."""
    tile = resolve_tile("REPRO_LSCV_TILE", TILE, tile)
    h_tile = resolve_tile("REPRO_LSCV_H_TILE", H_TILE, h_tile)
    return _lscv_grid_sums(x, sigma_inv, h_grid, c_k, c_kk, tile, h_tile,
                           interpret)


@functools.partial(jax.jit, static_argnames=("tile", "h_tile", "interpret"))
def _lscv_grid_sums(x: jax.Array, sigma_inv: jax.Array, h_grid: jax.Array,
                    c_k, c_kk, tile: int, h_tile: int,
                    interpret: bool) -> jax.Array:
    from .sv_precompute import _sv_matrix

    n, d = x.shape
    n_h = h_grid.shape[0]
    s = _sv_matrix(x, sigma_inv, tile, "mxu", interpret)

    k = n if n <= tile else lane_tile(tile, n)
    pad = (-n) % k
    sp = jnp.pad(s, ((0, pad), (0, pad)))
    idx = jnp.arange(sp.shape[0])
    w = ((idx[:, None] < idx[None, :]) & (idx[None, :] < n) & (idx[:, None] < n))
    w = w.astype(x.dtype)
    n_tiles = sp.shape[0] // k

    # per-h partials live in an (h, 128) lane-row block (scalar broadcast
    # across lanes); the h block is the whole padded grid or a multiple of 8
    hk = n_h if n_h <= h_tile else -(-h_tile // 8) * 8
    pad_h = (-n_h) % hk
    hinv = jnp.pad(1.0 / (h_grid * h_grid), (0, pad_h)).astype(x.dtype)
    n_h_tiles = hinv.shape[0] // hk
    consts = jnp.stack([jnp.asarray(c_k, x.dtype), jnp.asarray(c_kk, x.dtype)])

    # Grid: (h-tile major, flattened S-tile minor) so the output block for a
    # given h-tile stays resident while all S tiles stream through.
    n_s_tiles = n_tiles * n_tiles
    smem = pl.BlockSpec(memory_space=pltpu.SMEM)

    out = pl.pallas_call(
        functools.partial(_kernel, hk=hk),
        grid=(n_h_tiles, n_s_tiles),
        in_specs=[
            pl.BlockSpec((k, k), lambda i, j: (j // n_tiles, j % n_tiles)),
            pl.BlockSpec((k, k), lambda i, j: (j // n_tiles, j % n_tiles)),
            smem, smem,
        ],
        out_specs=pl.BlockSpec((hk, _LANES), lambda i, j: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((hinv.shape[0], _LANES), x.dtype),
        interpret=interpret,
    )(sp, w, hinv, consts)
    return out[:n_h, 0]
