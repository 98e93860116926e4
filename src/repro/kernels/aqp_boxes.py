"""Pallas TPU kernel: batched multi-d box-query reduction (paper eq. 11).

One launch answers a whole batch of axis-aligned box queries against one
joint synopsis with diagonal bandwidth.  For query q (box [lo_q, hi_q],
SUM/AVG target axis t_q) and sample row x_i it accumulates

    count_raw[q] = sum_i  prod_j  dPhi_qij                      (eq. 11)
    sum_raw[q]   = sum_i  m_qit * prod_{j != t_q} dPhi_qij
      with  dPhi_qij = Phi((hi_qj - x_ij)/h_j) - Phi((lo_qj - x_ij)/h_j)
            m_qij    = x_ij dPhi_qij - h_j dphi_qij             (eq. 10/axis)

Grid: (query-tile major, data-tile minor) — the (qk, 2) accumulator block
stays resident while data tiles stream through, the same pattern as
aqp_batch.py.  The dims axis is unrolled (d is small for box predicates),
so the per-axis select-and-product runs on (queries x samples) slabs in
VMEM.  COUNT/SUM/AVG selection and the sample->relation scale are
applied by the caller (core/aqp_multid.py); the kernel is a pure two-channel
reduction.

Tile sizes resolve per call (REPRO_AQP_BOXES_TILE / REPRO_AQP_BOXES_Q_TILE,
see tuning.resolve_tile); call-site kwargs win.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .erf import phi_terms
from .tuning import lane_tile, resolve_tile, row_tile

TILE = 128     # default (env: REPRO_AQP_BOXES_TILE)
Q_TILE = 64    # default (env: REPRO_AQP_BOXES_Q_TILE)

def _kernel(lo_ref, hi_ref, tgt_ref, x_ref, h_ref, out_ref,
            *, n: int, k: int, d: int):
    j = pl.program_id(1)     # data-tile index (minor: varies fastest)
    lo = lo_ref[...]         # (qk, d) box lower corners
    hi = hi_ref[...]         # (qk, d) box upper corners
    tgt = tgt_ref[...]       # (qk, 1) SUM/AVG target axis per query
    x = x_ref[...]           # (d, k) sample rows, transposed (padding masked)

    # d is small for box predicates: unroll it, so every intermediate is a
    # (queries x samples) slab.  SUM factors: axis t_q carries the
    # first-moment term, every other axis its Phi difference — a select
    # beats dividing the full product by dPhi_t, which blows up when a box
    # edge leaves ~zero mass on an axis.
    cnt_i = sum_i = None
    for a in range(d):
        h = h_ref[a]                                   # SMEM scalar
        xa = x[a:a + 1, :]                             # (1, k)
        za = (lo[:, a:a + 1] - xa) * (1.0 / h)         # (qk, k)
        zb = (hi[:, a:a + 1] - xa) * (1.0 / h)
        d_Phi, d_phi = phi_terms(za, zb)
        factor = jnp.where(tgt == a, xa * d_Phi - h * d_phi, d_Phi)
        cnt_i = d_Phi if cnt_i is None else cnt_i * d_Phi
        sum_i = factor if sum_i is None else sum_i * factor

    rows = j * k + jax.lax.broadcasted_iota(jnp.int32, cnt_i.shape, 1)
    valid = rows < n

    @pl.when(j == 0)
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)

    out_ref[:, 0:1] += jnp.sum(jnp.where(valid, cnt_i, 0.0), axis=1,
                               keepdims=True)
    out_ref[:, 1:2] += jnp.sum(jnp.where(valid, sum_i, 0.0), axis=1,
                               keepdims=True)


@functools.partial(jax.jit, static_argnames=("tile", "q_tile", "interpret"))
def _aqp_box_sums(x, h_diag, lo, hi, tgt, tile, q_tile, interpret):
    n, d = x.shape
    q = lo.shape[0]
    if n == 0 or q == 0:
        # zero grid iterations would leave the output buffer uninitialized
        z = jnp.zeros((q,), x.dtype)
        return z, z

    # Layout: queries on sublanes, samples on lanes (x transposed to (d, n)).
    k = lane_tile(tile, n)
    qk = row_tile(q_tile, q)
    xt = jnp.pad(x, ((0, (-n) % k), (0, 0))).T
    lop = jnp.pad(lo, ((0, (-q) % qk), (0, 0)))
    hip = jnp.pad(hi, ((0, (-q) % qk), (0, 0)))
    tgtp = jnp.pad(tgt.astype(jnp.int32), (0, (-q) % qk)).reshape(-1, 1)

    out = pl.pallas_call(
        functools.partial(_kernel, n=n, k=k, d=d),
        grid=(lop.shape[0] // qk, xt.shape[1] // k),
        in_specs=[
            pl.BlockSpec((qk, d), lambda i, j: (i, 0)),
            pl.BlockSpec((qk, d), lambda i, j: (i, 0)),
            pl.BlockSpec((qk, 1), lambda i, j: (i, 0)),
            pl.BlockSpec((d, k), lambda i, j: (0, j)),
            pl.BlockSpec(memory_space=pltpu.SMEM),
        ],
        out_specs=pl.BlockSpec((qk, 2), lambda i, j: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((lop.shape[0], 2), x.dtype),
        interpret=interpret,
        name="_aqp_box_sums",
    )(lop, hip, tgtp, xt, h_diag.astype(x.dtype))
    return out[:q, 0], out[:q, 1]


def aqp_box_sums(x: jax.Array, h_diag: jax.Array, lo: jax.Array, hi: jax.Array,
                 tgt: jax.Array, tile: int = None, q_tile: int = None,
                 interpret: bool = True):
    """Two-channel (queries x samples x dims) reduction.

    x: (n, d) sample rows; h_diag: (d,); lo/hi: (q, d); tgt: (q,) int32.
    Returns (count_raw, sum_raw), each (q,): the *unscaled* eq. 11 box
    integrals summed over the retained sample.
    """
    tile = resolve_tile("REPRO_AQP_BOXES_TILE", TILE, tile)
    q_tile = resolve_tile("REPRO_AQP_BOXES_Q_TILE", Q_TILE, q_tile)
    return _aqp_box_sums(x, h_diag, lo, hi, tgt, tile, q_tile, interpret)
