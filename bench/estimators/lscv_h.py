"""LSCV_h per axis (paper sections 4.4-4.5, 6.2) and the Gaussian product
kernel (eq. 11): the reference for a configuration whose `engine.selector`
is `lscv_h`.

  * each axis of the sample gets its own one-dimensional LSCV_h: sigma from
    eq. 22, h0 from eq. 28 and the 150-point grid on [h0 / 4, 4 h0] from
    eq. 29, and g(h) (eq. 24 in the form of eq. 43) at every grid point,
    from pair sums on the device in plain jax.numpy float32 blocks whose
    partial sums are carried to the host and added in float64.  The axis's
    bandwidth is the grid minimiser times sigma (H = h^2 sigma^2).  g's
    1 / h is taken from the float32 exponent scale the sums used (the
    program does the same), so that neither side's g moves from one grid
    point to the next by that scale's rounding;
  * the answers are the Gaussian product-kernel integrals of `plugin.py` at
    those bandwidths;
  * `h_gap` is the larger of two gaps, per axis: (a) how far the reference's
    g at the program's grid point lies above the reference's minimum,
    relative to it, and (b) the relative gap between the program's
    bandwidth and its grid value times the reference's sigma.

The pair sums run over blocks of rows against every point, as `plugin.py`
does: each ordered pair i != j once, and i = j, whose term is exact (S = 0),
taken off on the host; half the rest is the sum over i < j.

Interface: `bench/estimators/__init__.py`.
"""
from __future__ import annotations

import math

import numpy as np

from bench.estimators import plugin
from bench.reference import F64, Precision

N_H = 150
ROWS = 128              # rows per device block
H_CHUNK = 10            # grid points per pass over a block

_G_FNS = {}


def program_bandwidth(syn) -> dict:
    """The synopsis's per-axis bandwidth and the grid value it was made
    from (`grid_h`; a synopsis without one is read as bandwidth = grid
    value)."""
    d = 1 if syn.x.ndim == 1 else int(syn.x.shape[1])
    bw = np.broadcast_to(np.asarray(syn.h, np.float64), (d,)).copy()
    grid_h = getattr(syn, "grid_h", None)
    grid_h = bw if grid_h is None else np.broadcast_to(
        np.asarray(grid_h, np.float64), (d,)).copy()
    return {"bw": bw, "grid_h": grid_h}


def _g_sums_fn(dtype_name: str, blocks: int):
    """Jitted sums of e (1 - r e), e = exp(c_h (x_i - x_j)^2), over every j,
    per row i and grid point h, in blocks of ROWS rows."""
    key = (dtype_name, blocks)
    if key in _G_FNS:
        return _G_FNS[key]
    import jax
    import jax.numpy as jnp

    dt = jnp.bfloat16 if dtype_name == "bfloat16" else jnp.float32

    @jax.jit
    def fn(x, w, c, r):
        xs = x.astype(dt)
        rs = r.astype(dt)

        def block(args):
            rows, wr = args
            v = rows[:, None] - xs[None, :]                  # (ROWS, n)
            s = v * v
            ww = wr[:, None] * w[None, :]

            def h_chunk(ch):
                e = jnp.exp(ch.astype(dt)[:, None, None] * s[None])
                t = (e * (1.0 - rs * e)).astype(jnp.float32)
                return jnp.sum(t * ww[None], axis=2)        # (H_CHUNK, ROWS)

            return jax.lax.map(h_chunk, c.reshape(-1, H_CHUNK))

        return jax.lax.map(block, (xs.reshape(blocks, ROWS),
                                   w.reshape(blocks, ROWS)))

    _G_FNS[key] = fn
    return fn


def lscv_axis(x: np.ndarray, prec: Precision = F64) -> dict:
    """One axis's LSCV_h: sigma, grid, g at every grid point (float64), and
    the bandwidth grid minimiser x sigma."""
    x = prec.r(np.asarray(x, np.float32)).astype(np.float32)
    n = x.shape[0]
    xd = x.astype(np.float64)
    var = float(np.sum(xd * xd) / (n - 1.0)
                - np.sum(xd) ** 2 / (n * (n - 1.0)))      # eq. 22
    sigma = math.sqrt(var)
    h0 = (4.0 / (3.0 * n)) ** 0.2                           # eq. 28, d = 1
    grid = np.linspace(h0 / 4.0, 4.0 * h0, N_H)             # eq. 29
    c_k = (2.0 * math.pi) ** -0.5 / sigma
    c_kk = (4.0 * math.pi) ** -0.5 / sigma
    pad = (-n) % ROWS
    xp = np.concatenate([x, np.zeros(pad, np.float32)])
    w = np.concatenate([np.ones(n, np.float32), np.zeros(pad, np.float32)])
    dt = "bfloat16" if prec.name == "bfloat16" else "float32"
    c = (-0.25 / (grid * grid * var)).astype(np.float32)
    r = np.float32(2.0 * c_k / c_kk)
    parts = _g_sums_fn(dt, xp.shape[0] // ROWS)(xp, w, c, r)
    total = np.sum(np.asarray(parts, np.float64),
                   axis=(0, 3)).reshape(-1)                 # (h,)
    # each i = j term is e = 1 exactly: 1 - r, as the device rounds it
    diag = float(prec.r(np.float32(1.0) - prec.r(r)))
    t = c_kk * (total - n * diag) / 2.0
    # g at the bandwidth the sums' exponent holds: c's float32 rounding,
    # different at each grid point, moves it by ~3e-8 from grid x sigma
    inv_h = np.sqrt(-4.0 * prec.r(c).astype(np.float64) * var)
    g = (2.0 / (float(n) * n) * t + c_kk / n) * inv_h       # eq. 43
    best = int(np.argmin(g))
    return {"sigma": sigma, "grid": grid, "g": g, "grid_h": grid[best],
            "bw": grid[best] * sigma}


def bandwidth(x: np.ndarray, prec: Precision = F64) -> dict:
    """LSCV_h of every axis of a (m,) or (m, d) sample."""
    x2 = x.reshape(x.shape[0], -1)
    axes = [lscv_axis(x2[:, j], prec) for j in range(x2.shape[1])]
    return {k: np.asarray([a[k] for a in axes]) for k in axes[0]}


def bandwidth_gap(prog: dict, ref: dict) -> float:
    """max over axes of (a) g's relative excess at the program's grid point
    and (b) the program bandwidth's gap to grid value x reference sigma."""
    gap = 0.0
    for j in range(ref["bw"].shape[0]):
        grid, g = ref["grid"][j], ref["g"][j]
        at = int(np.argmin(np.abs(grid - prog["grid_h"][j])))
        g_min = float(np.min(g))
        gap = max(gap, (float(g[at]) - g_min) / abs(g_min))
        want = float(prog["grid_h"][j]) * float(ref["sigma"][j])
        gap = max(gap, abs(float(prog["bw"][j]) - want) / want)
    return gap


def answers(boxes, aggs, x, bw, n_seen, prec: Precision = F64, chunk=16):
    """Product-kernel answers at the per-axis bandwidths of `bw`."""
    return plugin.answers(boxes, aggs, x, bw["bw"], n_seen, prec, chunk)
