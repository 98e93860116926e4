"""Parallel reduction building blocks (paper §5.2-5.4), TPU-idiomatic.

The paper defines four primitives:

  R(A)          = sum_i A_i                               (§5.2)
  R_fun(A)      = sum_i fun(A_i)                          (§5.3)
  RR_fun(A)     = sum_{i<j} fun(A_i - A_j)                (§5.4)
  RR^v_fun(A)   = sum_{i<j} fun1(fun2(A_:,i - A_:,j))     (§5.5)

On CUDA these are staged through shared memory in k x k tiles; on TPU the same
blocking is expressed either as a Pallas kernel (see repro.kernels) or — for the
pure-JAX reference path used below — as a `lax.scan` over row *chunks* so the
live working set stays O(chunk * n) instead of O(n^2).  XLA's `reduce` is already
a tree reduction, which matches the paper's pairwise-accuracy argument ([17]):
the O(log n) error constant comes for free.  A Kahan-compensated variant is
provided for the accuracy discussion in EXPERIMENTS.md §Paper-validation.
"""
from __future__ import annotations

from functools import partial
from typing import Callable

import jax
import jax.numpy as jnp


def reduce_sum(a: jax.Array) -> jax.Array:
    """R(A) (§5.2).  XLA lowers this to a tree reduction."""
    return jnp.sum(a)


def kahan_sum(a: jax.Array) -> jax.Array:
    """Kahan-compensated sequential sum — O(1) error constant ([22] in paper).

    Used only as an accuracy oracle in tests/benchmarks; it serialises.
    """
    def body(carry, x):
        s, c = carry
        y = x - c
        t = s + y
        c = (t - s) - y
        return (t, c), None

    (s, _), _ = jax.lax.scan(body, (jnp.zeros((), a.dtype), jnp.zeros((), a.dtype)), a.reshape(-1))
    return s


# --- float32 pairs (hi, lo): a value and its rounding error, carried apart so
# that sums of ~n^2 terms keep more digits than one float32 (LSCV_h argmin) ---

def two_sum(a, b):
    """a + b as (rounded sum, its exact rounding error)."""
    s = a + b
    bb = s - a
    return s, (a - (s - bb)) + (b - bb)


def _split(a):
    """Veltkamp split of a float32 into two halves of 12 bits or fewer."""
    c = 4097.0 * a
    hi = c - (c - a)
    return hi, a - hi


def two_prod(a, b):
    """a * b as (rounded product, its exact rounding error), no FMA needed."""
    p = a * b
    ah, al = _split(a)
    bh, bl = _split(b)
    return p, ((ah * bh - p) + ah * bl + al * bh) + al * bl


def pair_sum(p: jax.Array):
    """Row sums of p (rows, m) as float32 pairs (hi, lo): added pairwise,
    every rounding error carried."""
    hi, lo = p, jnp.zeros_like(p)
    while hi.shape[1] > 1:
        if hi.shape[1] % 2:
            hi = jnp.pad(hi, ((0, 0), (0, 1)))
            lo = jnp.pad(lo, ((0, 0), (0, 1)))
        hi, err = two_sum(hi[:, 0::2], hi[:, 1::2])
        lo = lo[:, 0::2] + lo[:, 1::2] + err
    return two_sum(hi[:, 0], lo[:, 0])


def map_reduce(fun: Callable, a: jax.Array, chunk: int = 65536) -> jax.Array:
    """R_fun(A) (§5.3): sum_i fun(A_i), computed on-the-fly without storing fun(A).

    `a` is 1-D.  Chunked so fun values never materialise beyond `chunk` elements
    (the paper's "compute and add on the fly" modification of the reduction).
    """
    n = a.shape[0]
    c = min(chunk, n)
    pad = (-n) % c
    ap = jnp.pad(a, (0, pad))
    valid = jnp.arange(ap.shape[0]) < n
    ap = ap.reshape(-1, c)
    valid = valid.reshape(-1, c)

    def body(acc, xv):
        x, v = xv
        return acc + jnp.sum(jnp.where(v, fun(x), 0.0)), None

    acc0 = jnp.zeros((), ap.dtype)
    acc, _ = jax.lax.scan(body, acc0, (ap, valid))
    return acc


def _row_chunks(n: int, chunk: int) -> int:
    return -(-n // chunk)


def pairwise_reduce(fun: Callable, x: jax.Array, chunk: int = 256) -> jax.Array:
    """RR_fun(A) (§5.4): sum_{i<j} fun(x_i - x_j) for 1-D x.

    TPU adaptation of the paper's triangular tiling (Fig. 3): we scan over row
    chunks of size `chunk`; each step materialises a (chunk, n) difference slab
    (the analogue of one tile *row stripe*), applies `fun` elementwise on the
    VPU, masks the lower triangle + diagonal + padding, and accumulates.  The
    dedicated Pallas kernel (kernels/pairwise_reduce.py) blocks both sides.
    """
    n = x.shape[0]
    c = min(chunk, n)
    pad = (-n) % c
    xp = jnp.pad(x, (0, pad))
    nrows = xp.shape[0] // c
    cols = jnp.arange(xp.shape[0])

    def body(acc, r):
        row_idx = r * c + jnp.arange(c)                       # global row ids
        rows = jax.lax.dynamic_slice_in_dim(xp, r * c, c)
        diff = rows[:, None] - xp[None, :]                    # (c, n_pad)
        vals = fun(diff)
        mask = (row_idx[:, None] < cols[None, :]) & (cols[None, :] < n) & (row_idx[:, None] < n)
        return acc + jnp.sum(jnp.where(mask, vals, 0.0)), None

    acc, _ = jax.lax.scan(body, jnp.zeros((), x.dtype), jnp.arange(nrows))
    return acc


def pairwise_quadform_chunks(x: jax.Array, m: jax.Array, chunk: int = 128):
    """Yields the S(v) slabs of RR^v_fun (§5.5): S_{ij} = (x_i-x_j)^T M (x_i-x_j).

    Returns a function `scan_slabs(consume, init)` that scans over row chunks;
    `consume(acc, s_slab, mask)` folds each masked (chunk, n) slab of quadratic
    forms into the accumulator.  This is the streaming backbone shared by the
    paper-faithful store-S path, the fused LSCV_h grid path and the LSCV_H
    objective (where M = H^-1 changes per evaluation, §6.3).
    """
    n, d = x.shape
    c = min(chunk, n)
    pad = (-n) % c
    xp = jnp.pad(x, ((0, pad), (0, 0)))
    nrows = xp.shape[0] // c
    cols = jnp.arange(xp.shape[0])

    def scan_slabs(consume: Callable, init):
        def body(acc, r):
            row_idx = r * c + jnp.arange(c)
            rows = jax.lax.dynamic_slice_in_dim(xp, r * c, c)
            v = rows[:, None, :] - xp[None, :, :]             # (c, n_pad, d)
            s = jnp.einsum("rnd,de,rne->rn", v, m, v,         # quadratic forms
                           precision=jax.lax.Precision.HIGHEST)
            mask = (row_idx[:, None] < cols[None, :]) & (cols[None, :] < n) & (row_idx[:, None] < n)
            return consume(acc, s, mask), None

        acc, _ = jax.lax.scan(body, init, jnp.arange(nrows))
        return acc

    return scan_slabs


def pairwise_quadform_reduce(fun1: Callable, x: jax.Array, m: jax.Array, chunk: int = 128) -> jax.Array:
    """RR^v_fun (§5.5 + §5.3 fused, as in the paper's LSCV_H GPU kernel §6.3):

        sum_{i<j} fun1( (x_i-x_j)^T M (x_i-x_j) )

    computed in one pass without materialising the S matrix.
    """
    scan_slabs = pairwise_quadform_chunks(x, m, chunk)

    def consume(acc, s, mask):
        return acc + jnp.sum(jnp.where(mask, fun1(s), 0.0))

    return scan_slabs(consume, jnp.zeros((), x.dtype))


def pairwise_sv_matrix(x: jax.Array, m: jax.Array, chunk: int = 128) -> jax.Array:
    """Paper-faithful §4.5 precompute: dense (n, n) matrix of S(v) values with the
    lower triangle + diagonal zeroed.  (The paper packs the upper triangle into a
    flat buffer; on TPU a dense masked matrix keeps layouts trivial and costs 2x
    memory — acceptable because the *stored-S* path is only used at paper scale,
    n <= 8192.  The streaming path above has no such limit.)
    """
    n = x.shape[0]
    scan_slabs = pairwise_quadform_chunks(x, m, chunk)
    c = min(chunk, n)
    pad = (-n) % c

    def consume(rows_acc, s, mask):
        out, r = rows_acc
        out = jax.lax.dynamic_update_slice_in_dim(out, jnp.where(mask, s, 0.0), r * c, axis=0)
        return (out, r + 1)

    out0 = jnp.zeros((n + pad, n + pad), x.dtype)
    out, _ = scan_slabs(consume, (out0, 0))
    return out[:n, :n]
