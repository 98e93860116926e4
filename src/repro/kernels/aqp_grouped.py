"""Pallas TPU kernel: factored GROUP BY box reduction (paper eq. 11).

A GROUP BY over a dictionary column expands to one box per category that
differs from its siblings on exactly ONE axis — the group column's code
window.  Fanning those out through the generic box kernel recomputes the
shared axes' Phi factors once per category: O(n * d * G).  This kernel is
the tiled form of `core/aqp_multid.py:_grouped_box_terms`: each data tile
computes the shared-axes product ONCE and crosses it with all G per-category
group-axis windows in one sweep, O(n * d + n * G):

    count_raw[g] = sum_i  shared_cnt_i * gPhi_ig
    sum_raw[g]   = sum_i  shared_sm_i  * gfac_ig

with shared_cnt_i the product of dPhi over the non-group axes, and the
first-moment factor (eq. 10 per axis) on the target axis — carried by the
shared product when the target is a kept axis, by the group factor when the
query aggregates the group column itself (`tgt_is_group`).

Grid: (category-tile major, data-tile minor) — the (gk, 2) accumulator
block stays resident while data tiles stream through, and the per-tile
cross term is a (gk, k) slab times the shared (1, k) row, summed over lanes.  COUNT/SUM/AVG selection
and the sample->relation scale are applied by the caller
(core/aqp_multid.py); the kernel is a pure two-channel reduction.

Tile sizes resolve per call (REPRO_AQP_GROUPED_TILE /
REPRO_AQP_GROUPED_G_TILE, see tuning.resolve_tile); call-site kwargs win.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .erf import phi_terms
from .tuning import lane_tile, resolve_tile, row_tile

TILE = 128     # data-tile default (env: REPRO_AQP_GROUPED_TILE)
G_TILE = 64    # category-tile default (env: REPRO_AQP_GROUPED_G_TILE)

def _kernel(glo_ref, ghi_ref, x_ref, h_ref, lo_ref, hi_ref, out_ref,
            *, n: int, k: int, d: int, g_axis: int, tgt: int):
    j = pl.program_id(1)     # data-tile index (minor: varies fastest)
    glo = glo_ref[...]       # (gk, 1) per-category window on the group axis
    ghi = ghi_ref[...]
    x = x_ref[...]           # (d, k) sample rows, transposed (padding masked)

    # shared product over the kept axes, (1, k); the first-moment factor
    # rides on the target axis when the target is a kept axis
    valid = j * k + jax.lax.broadcasted_iota(jnp.int32, (1, k), 1) < n
    shared_cnt = jnp.where(valid, 1.0, 0.0)
    shared_sm = shared_cnt
    for a in range(d):
        if a == g_axis:
            continue
        h = h_ref[a]                                   # SMEM scalars
        xa = x[a:a + 1, :]
        za = (lo_ref[a] - xa) * (1.0 / h)
        zb = (hi_ref[a] - xa) * (1.0 / h)
        d_Phi, d_phi = phi_terms(za, zb)
        shared_cnt = shared_cnt * d_Phi
        shared_sm = shared_sm * (xa * d_Phi - h * d_phi if a == tgt
                                 else d_Phi)

    hg = h_ref[g_axis]
    xg = x[g_axis:g_axis + 1, :]                       # (1, k)
    gza = (glo - xg) * (1.0 / hg)                      # (gk, k)
    gzb = (ghi - xg) * (1.0 / hg)
    g_Phi, g_dphi = phi_terms(gza, gzb)
    cnt = jnp.sum(g_Phi * shared_cnt, axis=1, keepdims=True)     # (gk, 1)
    if tgt == g_axis:
        g_moment = xg * g_Phi - hg * g_dphi
        sm = jnp.sum(g_moment * shared_cnt, axis=1, keepdims=True)
    else:
        sm = jnp.sum(g_Phi * shared_sm, axis=1, keepdims=True)

    @pl.when(j == 0)
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)

    out_ref[:, 0:1] += cnt
    out_ref[:, 1:2] += sm


@functools.partial(jax.jit, static_argnames=("g_axis", "tgt", "tile",
                                             "g_tile", "interpret"))
def _aqp_grouped_sums(x, h_diag, lo, hi, glo, ghi, g_axis, tgt, tile,
                      g_tile, interpret):
    n, d = x.shape
    G = glo.shape[0]
    if n == 0 or G == 0:
        # zero grid iterations would leave the output buffer uninitialized
        z = jnp.zeros((G,), x.dtype)
        return z, z

    # Layout: categories on sublanes, samples on lanes (x transposed).
    k = lane_tile(tile, n)
    gk = row_tile(g_tile, G)
    xt = jnp.pad(x, ((0, (-n) % k), (0, 0))).T
    glop = jnp.pad(glo, (0, (-G) % gk)).reshape(-1, 1)
    ghip = jnp.pad(ghi, (0, (-G) % gk)).reshape(-1, 1)
    smem = pl.BlockSpec(memory_space=pltpu.SMEM)

    out = pl.pallas_call(
        functools.partial(_kernel, n=n, k=k, d=d, g_axis=g_axis, tgt=tgt),
        grid=(glop.shape[0] // gk, xt.shape[1] // k),
        in_specs=[
            pl.BlockSpec((gk, 1), lambda i, j: (i, 0)),
            pl.BlockSpec((gk, 1), lambda i, j: (i, 0)),
            pl.BlockSpec((d, k), lambda i, j: (0, j)),
            smem, smem, smem,
        ],
        out_specs=pl.BlockSpec((gk, 2), lambda i, j: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((glop.shape[0], 2), x.dtype),
        interpret=interpret,
        name="_aqp_grouped_sums",
    )(glop, ghip, xt, h_diag.astype(x.dtype), lo.astype(x.dtype),
      hi.astype(x.dtype))
    return out[:G, 0], out[:G, 1]


def aqp_grouped_sums(x: jax.Array, h_diag: jax.Array, lo: jax.Array,
                     hi: jax.Array, glo: jax.Array, ghi: jax.Array,
                     g_axis: int, tgt: int, tile: int = None,
                     g_tile: int = None, interpret: bool = True):
    """Two-channel factored GROUP BY reduction.

    x: (n, d) sample rows; h_diag: (d,); lo/hi: (d,) the family's shared
    box (the group axis' entries are ignored); glo/ghi: (G,) per-category
    interval on axis `g_axis`; tgt: static target axis.  Returns
    (count_raw, sum_raw), each (G,): the *unscaled* eq. 11 integrals —
    identical semantics to `core/aqp_multid.py:_grouped_box_terms`.
    """
    tile = resolve_tile("REPRO_AQP_GROUPED_TILE", TILE, tile)
    g_tile = resolve_tile("REPRO_AQP_GROUPED_G_TILE", G_TILE, g_tile)
    return _aqp_grouped_sums(x, h_diag, lo, hi, glo, ghi, int(g_axis),
                             int(tgt), tile, g_tile, interpret)
