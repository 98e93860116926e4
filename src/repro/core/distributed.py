"""Distributed (multi-chip) bandwidth selection — the scale-out layer the
paper's single-device design lacks (DESIGN.md §2, last row of the table).

Decomposition: the implicit n x n upper-triangular pairwise matrix is split by
*strided row ownership* — device p owns rows {p, p+P, p+2P, ...}.  A contiguous
block-row split would give device 0 ~2x the work of device P-1 (triangle);
striding balances each device's pair count to within n/2 pairs (the same
load-balancing concern the paper solves with its eq. 49/50 block-index math —
here solved by ownership pattern instead, no index arithmetic needed).

The sample x is *replicated* (O(n) bytes; even n=4M fp32 is 16 MB — small
against a TPU v5e chip's 16 GB HBM), each device reduces its own rows with
the same chunked
slab computation used on a single chip, and a single `psum` produces the global
sum — one small scalar/vector collective per reduction, which is why these
selectors scale to a full pod essentially linearly (see EXPERIMENTS.md §Perf).
"""
from __future__ import annotations

from functools import partial
from typing import Callable

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

from repro import compat

from . import gaussian as G
from .lscv import h_grid_for
from .reductions import pairwise_reduce

# f32 matmul passes: XLA on a TPU defaults f32 dots to one bf16 pass
_F32 = jax.lax.Precision.HIGHEST


def _strided_pairwise_partial(fun: Callable, x: jax.Array, p: jax.Array, n_dev: int,
                              chunk: int = 256, axes=()) -> jax.Array:
    """Partial sum_{i<j, i mod P == p} fun(x_i - x_j) on one device (1-D x)."""
    n = x.shape[0]
    rows_per_dev = -(-n // n_dev)
    c = min(chunk, rows_per_dev)
    pad_rows = (-rows_per_dev) % c
    cols = jnp.arange(n)

    def body(acc, r):
        local = r * c + jnp.arange(c)                     # local row index
        row_idx = local * n_dev + p                       # strided global rows
        ok = row_idx < n
        rows = jnp.take(x, jnp.where(ok, row_idx, 0), axis=0)
        diff = rows[:, None] - x[None, :]
        vals = fun(diff)
        mask = ok[:, None] & (row_idx[:, None] < cols[None, :])
        return acc + jnp.sum(jnp.where(mask, vals, 0.0)), None

    nsteps = (rows_per_dev + pad_rows) // c
    acc0 = jnp.zeros((), x.dtype)
    if axes:  # carry is device-varying inside shard_map (jax>=0.7 vma typing)
        acc0 = compat.pvary(acc0, axes)
    acc, _ = jax.lax.scan(body, acc0, jnp.arange(nsteps))
    return acc


def sharded_pairwise_reduce(fun: Callable, x: jax.Array, mesh: Mesh,
                            chunk: int = 256) -> jax.Array:
    """RR_fun over every device of `mesh` (all axes flattened)."""
    axes = tuple(mesh.axis_names)
    n_dev = mesh.devices.size

    def shard_fn(x_rep):
        p = jax.lax.axis_index(axes)
        partial_sum = _strided_pairwise_partial(fun, x_rep, p, n_dev, chunk, axes)
        return jax.lax.psum(partial_sum, axes)

    f = compat.shard_map(shard_fn, mesh=mesh, in_specs=P(), out_specs=P())
    return f(x)


def sharded_plugin_psi_sums(x: jax.Array, g1: jax.Array, g2: jax.Array, mesh: Mesh,
                            chunk: int = 256):
    """Distributed Psi6/Psi4 pairwise sums for PLUGIN (the O(n^2) stages)."""
    s6 = sharded_pairwise_reduce(lambda dx: G.k6(dx / g1), x, mesh, chunk)
    s4 = sharded_pairwise_reduce(lambda dx: G.k4(dx / g2), x, mesh, chunk)
    return s6, s4


def sharded_lscv_h_grid(x: jax.Array, sigma_inv: jax.Array, h_grid: jax.Array,
                        c_k: float, c_kk: float, mesh: Mesh, chunk: int = 64,
                        h_chunk: int = 8, algorithm: str = "mxu") -> jax.Array:
    """Distributed fused LSCV_h grid: every device folds its strided rows'
    quadratic-form slabs into per-h partial sums; one psum over the vector.

    algorithm="einsum": per-pair quadratic form (paper's eq. 60 layout,
    O(d^2) VPU work per pair).  "mxu": expansion S = qr + qx - 2 r M x^T —
    the cross term is one (c,d)x(d,n) matmul per slab on the MXU (§Perf
    hillclimb H4; same numbers, validated in tests)."""
    axes = tuple(mesh.axis_names)
    n_dev = mesh.devices.size
    n, d = x.shape
    n_h = h_grid.shape[0]
    pad_h = (-n_h) % h_chunk
    inv2 = jnp.pad(0.5 / (h_grid * h_grid), (0, pad_h)).reshape(-1, h_chunk)
    inv4 = jnp.pad(0.25 / (h_grid * h_grid), (0, pad_h)).reshape(-1, h_chunk)

    def shard_fn(x_rep, hg2, hg4):
        p = jax.lax.axis_index(axes)
        rows_per_dev = -(-n // n_dev)
        c = min(chunk, rows_per_dev)
        nsteps = -(-rows_per_dev // c)
        cols = jnp.arange(n)
        if algorithm == "mxu":
            mx = jnp.dot(x_rep, sigma_inv, precision=_F32)   # (n, d), hoisted
            qx = jnp.sum(mx * x_rep, axis=1)             # (n,)

        def body(acc, r):
            local = r * c + jnp.arange(c)
            row_idx = local * n_dev + p
            ok = row_idx < n
            rows = jnp.take(x_rep, jnp.where(ok, row_idx, 0), axis=0)
            if algorithm == "mxu":
                mr = jnp.dot(rows, sigma_inv, precision=_F32)     # (c, d)
                qr = jnp.sum(mr * rows, axis=1)           # (c,)
                cross = jnp.dot(mr, x_rep.T, precision=_F32)  # (c, n), MXU
                s = qr[:, None] + qx[None, :] - 2.0 * cross
            else:
                v = rows[:, None, :] - x_rep[None, :, :]
                s = jnp.einsum("rnd,de,rne->rn", v, sigma_inv, v,
                               precision=_F32)
            mask = (ok[:, None] & (row_idx[:, None] < cols[None, :])).astype(s.dtype)
            sm = s * mask

            def per_hc(args):   # one h-chunk at a time: (hc, c, n) slab
                i2, i4 = args
                e2 = jnp.exp(-sm[None] * i2[:, None, None]) * mask[None]
                e4 = jnp.exp(-sm[None] * i4[:, None, None]) * mask[None]
                return jnp.sum(c_kk * e4 - 2.0 * c_k * e2, axis=(1, 2))

            contrib = jax.lax.map(per_hc, (hg2, hg4)).reshape(-1)[:n_h]
            return acc + contrib, None

        acc0 = compat.pvary(jnp.zeros((n_h,), x.dtype), axes)
        acc, _ = jax.lax.scan(body, acc0, jnp.arange(nsteps))
        return jax.lax.psum(acc, axes)

    f = compat.shard_map(shard_fn, mesh=mesh, in_specs=(P(), P(), P()), out_specs=P())
    return f(x, inv2, inv4)


def distributed_lscv_h(x: jax.Array, mesh: Mesh, n_h: int = 150, chunk: int = 64):
    """End-to-end distributed LSCV_h (paper §6.2 on a pod instead of a GPU)."""
    from .lscv import covariance
    if x.ndim == 1:
        x = x[:, None]
    n, d = x.shape
    sigma = covariance(x)
    det_sigma = jnp.linalg.det(sigma)
    sigma_inv = jnp.linalg.inv(sigma)
    c_k, c_kk, r_k = G.lscv_h_consts(d, det_sigma)
    h_grid = h_grid_for(n, d, n_h).astype(x.dtype)
    t_sums = sharded_lscv_h_grid(x, sigma_inv, h_grid, c_k, c_kk, mesh, chunk)
    g_values = h_grid ** (-d) * (2.0 / (n * n) * t_sums + r_k / n)
    return h_grid[jnp.argmin(g_values)], h_grid, g_values
