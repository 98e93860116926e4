"""Pallas TPU kernel: batched AQP Phi-difference reduction (paper eqs. 9-10).

One launch answers a whole batch of range queries against one synopsis: for
every query q with range [a_q, b_q] and every sample point x_i it accumulates

    count_raw[q] = sum_i  Phi((b_q - x_i)/h) - Phi((a_q - x_i)/h)       (eq. 9)
    sum_raw[q]   = sum_i  x_i [Phi]_q,i - h [phi]_q,i                    (eq. 10)

Grid: (query-tile major, data-tile minor).  The (qk, 2) accumulator block for
a query tile stays resident while all data tiles stream through — the same
accumulation pattern as lscv_grid.py.  COUNT/SUM/AVG selection and the
sample->relation scale factor are applied by the caller (core/aqp.py), so the
kernel stays a pure two-channel reduction.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .erf import phi_terms
from .tuning import lane_tile, resolve_tile, row_tile

# Defaults; resolved per CALL against REPRO_AQP_TILE / REPRO_AQP_Q_TILE so a
# sweep or late env change moves them without a restart; kwargs still win.
TILE = 256
Q_TILE = 128


def _kernel(a_ref, b_ref, x_ref, h_ref, out_ref, *, n: int, k: int):
    j = pl.program_id(1)   # data-tile index (minor: varies fastest)
    a = a_ref[...]         # (qk, 1) lower range bounds
    b = b_ref[...]         # (qk, 1) upper range bounds
    x = x_ref[...]         # (1, k) sample chunk (padded entries masked below)
    h = h_ref[0]           # scalar bandwidth (SMEM)
    inv_h = 1.0 / h

    za = (a - x) * inv_h                                # (qk, k)
    zb = (b - x) * inv_h
    d_Phi, d_phi = phi_terms(za, zb)

    cols = j * k + jax.lax.broadcasted_iota(jnp.int32, za.shape, 1)
    valid = cols < n
    d_Phi = jnp.where(valid, d_Phi, 0.0)
    d_phi = jnp.where(valid, d_phi, 0.0)

    @pl.when(j == 0)
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)

    out_ref[:, 0:1] += jnp.sum(d_Phi, axis=1, keepdims=True)
    out_ref[:, 1:2] += jnp.sum(x * d_Phi - h * d_phi, axis=1, keepdims=True)


@functools.partial(jax.jit, static_argnames=("tile", "q_tile", "interpret"))
def _aqp_batch_sums(x, h, a, b, tile, q_tile, interpret):
    n = x.shape[0]
    q = a.shape[0]
    if n == 0 or q == 0:
        # zero grid iterations would leave the output buffer uninitialized
        z = jnp.zeros((q,), x.dtype)
        return z, z

    # Layout: queries on sublanes, samples on lanes — every operand is 2-D,
    # so each block is (8, 128)-tileable or spans its whole array.
    k = lane_tile(tile, n)
    qk = row_tile(q_tile, q)
    xp = jnp.pad(x, (0, (-n) % k)).reshape(1, -1)
    ap = jnp.pad(a, (0, (-q) % qk)).reshape(-1, 1)
    bp = jnp.pad(b, (0, (-q) % qk)).reshape(-1, 1)

    out = pl.pallas_call(
        functools.partial(_kernel, n=n, k=k),
        grid=(ap.shape[0] // qk, xp.shape[1] // k),
        in_specs=[
            pl.BlockSpec((qk, 1), lambda i, j: (i, 0)),
            pl.BlockSpec((qk, 1), lambda i, j: (i, 0)),
            pl.BlockSpec((1, k), lambda i, j: (0, j)),
            pl.BlockSpec(memory_space=pltpu.SMEM),
        ],
        out_specs=pl.BlockSpec((qk, 2), lambda i, j: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((ap.shape[0], 2), x.dtype),
        interpret=interpret,
    )(ap, bp, xp, h.reshape(1).astype(x.dtype))
    return out[:q, 0], out[:q, 1]


def aqp_batch_sums(x: jax.Array, h: jax.Array, a: jax.Array, b: jax.Array,
                   tile: int = None, q_tile: int = None,
                   interpret: bool = True):
    """Two-channel (queries x sample) reduction.  x: (n,), a/b: (q,).

    Returns (count_raw, sum_raw), each (q,): the *unscaled* closed-form
    integrals of eqs. 9-10 summed over the retained sample.
    """
    tile = resolve_tile("REPRO_AQP_TILE", TILE, tile)
    q_tile = resolve_tile("REPRO_AQP_Q_TILE", Q_TILE, q_tile)
    return _aqp_batch_sums(x, h, a, b, tile, q_tile, interpret)
