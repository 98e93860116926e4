"""Pallas TPU kernel: fused QMC box reduction for full-H synopses.

The quasi-MC fallback (`core/aqp_multid.py:_qmc_shared_terms`) answers a
box batch in two dense passes: a (nodes x sample) KDE evaluation producing
the shared density vector f, then a (boxes x nodes) indicator reduction
that re-materializes `f * inside_q` per box.  This kernel fuses both: the
contraction is linear in the sample and node sums, so for box q

    cnt_sums[q] = sum_m sum_i  1_q(node_m) * exp(log_norm - quad_mi)
    sum_sums[q] = sum_m sum_i  1_q(node_m) * node_m[t_q] * exp(...)
      with  quad_mi = 0.5 (node_m - x_i)^T H^-1 (node_m - x_i)

accumulates tile-by-tile without ever holding f — the caller divides by the
node count and applies vol(G) to recover the `_qmc_shared_terms` raw terms.

Grid: (box-tile major, node-tile, data-tile minor).  The (qk, 2)
accumulator block stays resident across both inner loops; the per-tile
kernel slab builds the quadratic form with d(d+1)/2 broadcast
multiply-accumulate passes over per-axis difference slabs (d is small in
the paper's scope — no (mk, k, d) intermediate), and the indicator
contraction is a (qk, mk) @ (mk, 1) matvec on the MXU.

Tile sizes resolve per call (REPRO_QMC_TILE data / REPRO_QMC_M_TILE node /
REPRO_QMC_Q_TILE box, see tuning.resolve_tile); call-site kwargs win.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .tuning import lane_tile, resolve_tile, row_tile

TILE = 256     # data-tile default (env: REPRO_QMC_TILE)
M_TILE = 256   # node-tile default (env: REPRO_QMC_M_TILE)
Q_TILE = 64    # box-tile default (env: REPRO_QMC_Q_TILE)


def _kernel(lo_ref, hi_ref, tgt_ref, nodes_ref, nodes_t_ref, x_ref,
            hinv_ref, ln_ref, out_ref, *, n: int, m: int, mk: int, k: int,
            d: int):
    j = pl.program_id(1)     # node-tile index
    l = pl.program_id(2)     # data-tile index (minor: varies fastest)
    lo = lo_ref[...]         # (qk, d) box lower corners
    hi = hi_ref[...]         # (qk, d) box upper corners
    tgt = tgt_ref[...]       # (qk, 1) SUM/AVG target axis per box
    nodes = nodes_ref[...]   # (mk, d) Halton nodes (padded rows masked)
    nodes_t = nodes_t_ref[...]   # (d, mk) the same nodes, transposed
    x = x_ref[...]           # (d, k) sample rows, transposed (padding masked)

    # quad[m, i] = (node_m - x_i)^T H^-1 (node_m - x_i), d unrolled.
    # Contract v = diff @ H^-1 BEFORE the second dot — the same order as the
    # jnp path's einsum.  An ill-conditioned H (LSCV on near-collinear
    # columns) makes H^-1 entries huge with alternating signs; v absorbs
    # that cancellation at small magnitude, where a symmetric-pair expansion
    # of the quadratic would sum three enormous terms and lose float32 bits.
    diffs = [nodes[:, a:a + 1] - x[a:a + 1, :] for a in range(d)]  # (mk, k)
    quad = None
    for a in range(d):
        v = None
        for e in range(d):
            term = hinv_ref[a * d + e] * diffs[e]      # SMEM scalar
            v = term if v is None else v + term
        quad = v * diffs[a] if quad is None else quad + v * diffs[a]
    vals = jnp.exp(ln_ref[0] - 0.5 * quad)             # (mk, k)

    cols = l * k + jax.lax.broadcasted_iota(jnp.int32, (mk, k), 1)
    f_part = jnp.sum(jnp.where(cols < n, vals, 0.0), axis=1,
                     keepdims=True)                    # (mk, 1)
    node_rows = j * mk + jax.lax.broadcasted_iota(jnp.int32, (mk, 1), 0)
    f_part = jnp.where(node_rows < m, f_part, 0.0)

    inside = None
    tval = jnp.zeros((lo.shape[0], mk), x.dtype)      # (qk, mk)
    for a in range(d):
        na = nodes_t[a:a + 1, :]                       # (1, mk)
        ok = (na >= lo[:, a:a + 1]) & (na <= hi[:, a:a + 1])
        inside = ok if inside is None else inside & ok
        tval += jnp.where(tgt == a, na, 0.0)
    ind = inside.astype(x.dtype)

    hp = jax.lax.Precision.HIGHEST
    cnt = jnp.dot(ind, f_part, precision=hp)           # (qk, 1) MXU
    sm = jnp.dot(ind * tval, f_part, precision=hp)

    @pl.when((j == 0) & (l == 0))
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)

    out_ref[:, 0:1] += cnt
    out_ref[:, 1:2] += sm


@functools.partial(jax.jit, static_argnames=("tile", "m_tile", "q_tile",
                                             "interpret"))
def _qmc_box_reduce(nodes, x, h_inv, log_norm, lo, hi, tgt, tile, m_tile,
                    q_tile, interpret):
    m, d = nodes.shape
    n = x.shape[0]
    q = lo.shape[0]
    if n == 0 or m == 0 or q == 0:
        # zero grid iterations would leave the output buffer uninitialized
        z = jnp.zeros((q,), x.dtype)
        return z, z

    # Layout: samples on lanes; nodes on sublanes of the kernel slab and on
    # lanes of the indicator, so the node tile must be lane-aligned too.
    k = lane_tile(tile, n)
    mk = lane_tile(m_tile, m)
    qk = row_tile(q_tile, q)
    xt = jnp.pad(x, ((0, (-n) % k), (0, 0))).T
    np_ = jnp.pad(nodes, ((0, (-m) % mk), (0, 0)))
    lop = jnp.pad(lo, ((0, (-q) % qk), (0, 0)))
    hip = jnp.pad(hi, ((0, (-q) % qk), (0, 0)))
    tgtp = jnp.pad(tgt.astype(jnp.int32), (0, (-q) % qk)).reshape(-1, 1)
    smem = pl.BlockSpec(memory_space=pltpu.SMEM)

    out = pl.pallas_call(
        functools.partial(_kernel, n=n, m=m, mk=mk, k=k, d=d),
        grid=(lop.shape[0] // qk, np_.shape[0] // mk, xt.shape[1] // k),
        in_specs=[
            pl.BlockSpec((qk, d), lambda i, j, l: (i, 0)),
            pl.BlockSpec((qk, d), lambda i, j, l: (i, 0)),
            pl.BlockSpec((qk, 1), lambda i, j, l: (i, 0)),
            pl.BlockSpec((mk, d), lambda i, j, l: (j, 0)),
            pl.BlockSpec((d, mk), lambda i, j, l: (0, j)),
            pl.BlockSpec((d, k), lambda i, j, l: (0, l)),
            smem, smem,
        ],
        out_specs=pl.BlockSpec((qk, 2), lambda i, j, l: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((lop.shape[0], 2), x.dtype),
        interpret=interpret,
        name="_qmc_box_reduce",
    )(lop, hip, tgtp, np_, np_.T, xt, h_inv.astype(x.dtype).reshape(-1),
      log_norm.reshape(1).astype(x.dtype))
    return out[:q, 0], out[:q, 1]


def qmc_box_reduce(nodes: jax.Array, x: jax.Array, h_inv: jax.Array,
                   log_norm: jax.Array, lo: jax.Array, hi: jax.Array,
                   tgt: jax.Array, tile: int = None, m_tile: int = None,
                   q_tile: int = None, interpret: bool = True):
    """Fused (boxes x nodes x sample) two-channel reduction.

    nodes: (m, d) shared QMC nodes; x: (n, d) sample rows; h_inv: (d, d)
    inverse bandwidth matrix; log_norm: scalar Gaussian log-normaliser;
    lo/hi: (q, d) boxes; tgt: (q,) int32.  Returns (cnt_sums, sum_sums),
    each (q,): raw double sums of the masked kernel values — the caller
    applies vol(G)/m to recover `_qmc_shared_terms` count/sum terms.
    """
    tile = resolve_tile("REPRO_QMC_TILE", TILE, tile)
    m_tile = resolve_tile("REPRO_QMC_M_TILE", M_TILE, m_tile)
    q_tile = resolve_tile("REPRO_QMC_Q_TILE", Q_TILE, q_tile)
    return _qmc_box_reduce(nodes, x, h_inv, jnp.asarray(log_norm), lo, hi,
                           tgt, tile, m_tile, q_tile, interpret)
