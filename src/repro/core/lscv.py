"""LSCV bandwidth selectors (paper §4.4) with the §4.5 reformulation.

LSCV_h  — scalar bandwidth h, any d: brute-force minimisation of g(h) (eq. 24)
          on a 150-point grid over Z(h0) = [h0/4, 4*h0] (eq. 29), using either
          (a) the paper-faithful §4.5 two-phase scheme: precompute all
              S(v) = v^T Sigma^-1 v once, reuse for every h   [store_s=True]
          (b) a beyond-paper *streaming fused* scheme that never materialises
              S: each (chunk x n) slab of quadratic forms is folded into the
              per-h partial sums for the whole grid in one pass.  Same FLOPs as
              (a), O(chunk*n) memory instead of O(n^2)        [store_s=False]
          (c) (b) as one Pallas kernel over the upper-triangle tiles only,
              each pair visited once, S kept in VMEM [backend="pallas"];
              on a tied 1-D sample over its distinct values, weighted by
              their multiplicities, where that takes fewer pairs [ties=]
LSCV_H  — full SPD bandwidth matrix: Nelder-Mead over a log-Cholesky
          parametrisation of H (guarantees SPD — the paper instead rejects
          non-SPD candidates inside NM; see DESIGN.md §2), objective g(H)
          (eq. 32) evaluated with the fused quadratic-form+T_H+reduce pass the
          paper describes for its GPU kernel in §6.3.
"""
from __future__ import annotations

import math
from functools import partial
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np

from . import gaussian as G
from .nelder_mead import minimize as nm_minimize
from .reductions import (pairwise_quadform_chunks, pairwise_quadform_reduce,
                         pairwise_sv_matrix, two_prod, two_sum)

N_H_DEFAULT = 150  # paper §7.1: objective evaluated at a fixed 150 grid points


# ---------------------------------------------------------------------------
# Covariance (eqs. 20-23), in the two-sum form the paper uses for reductions.
# ---------------------------------------------------------------------------

def covariance(x: jax.Array) -> jax.Array:
    """x: (n, d) row-per-sample (the paper stores samples in columns; we use
    rows, the JAX-native layout).  Returns (d, d) Sigma per eqs. (22)/(23)."""
    n = x.shape[0]
    s1 = jnp.sum(x, axis=0)                       # (d,)
    # (d, d) sum of outer products, in f32: a bf16 pass would cost the
    # covariance (and every LSCV objective built on it) ~3 digits on a TPU
    s2 = jnp.dot(x.T, x, precision=jax.lax.Precision.HIGHEST)
    # float denominators: n (n - 1) overflows int32 from n = 46,341
    return s2 / (n - 1.0) - jnp.outer(s1, s1) / (n * (n - 1.0))


def h0_start(n: int, d: int) -> float:
    """eq. (28).  Constants exactly as printed in the paper; for d=1 this
    reduces to Silverman's (4/3)^(1/5) n^(-1/5)."""
    rk_over_mu2 = 1.0 / (2.0 ** d * math.pi ** (d / 2.0) * d ** 2)
    r_f2 = d * (d + 2.0) / (2.0 ** (d + 2) * math.pi ** (d / 2.0))
    return float((rk_over_mu2 / (r_f2 * n)) ** (1.0 / (d + 4)))


def h_grid_for(n: int, d: int, n_h: int = N_H_DEFAULT) -> jax.Array:
    """Z(h0) = [h0/4, 4 h0] (eq. 29), n_h uniform points (paper §7.1)."""
    h0 = h0_start(n, d)
    return jnp.linspace(h0 / 4.0, 4.0 * h0, n_h, dtype=jnp.float32)


# ---------------------------------------------------------------------------
# LSCV_h
# ---------------------------------------------------------------------------

class LSCVhResult(NamedTuple):
    h: jax.Array
    h_grid: jax.Array
    g_values: jax.Array
    sigma: jax.Array       # covariance matrix used by the Mahalanobis kernel
    det_sigma: jax.Array
    h0: jax.Array


def exp_scales(h_grid: jax.Array) -> jax.Array:
    """-1 / (4 h^2) per grid point: e_4 = exp(scale S), e_2 = e_4^2 =
    exp(2 scale S).  Every grid-sum path takes these, and g's 1 / h^d is
    taken from the same values (`_inv_h`)."""
    return -0.25 / (h_grid * h_grid)


def _t_sums_from_S(s_matrix: jax.Array, mask: jax.Array, scales: jax.Array,
                   c_k: jax.Array, c_kk: jax.Array, h_chunk: int = 8) -> jax.Array:
    """Paper-faithful phase 2 (§6.2): for each h on the grid, reduce
    T~(v) = (K~*K~)(v) - 2 K~(v) over the precomputed S values (eqs. 40-42).
    `scales`: `exp_scales(h_grid)`."""
    def per_h(c):
        e2 = jnp.exp(2.0 * c * s_matrix)
        e4 = jnp.exp(c * s_matrix)
        return jnp.sum(jnp.where(mask, c_kk * e4 - 2.0 * c_k * e2, 0.0))

    return jax.lax.map(per_h, scales, batch_size=h_chunk)


def _t_sums_streaming(x: jax.Array, sigma_inv: jax.Array, scales: jax.Array,
                      c_k: jax.Array, c_kk: jax.Array, chunk: int = 128,
                      h_chunk: int = 8) -> jax.Array:
    """Beyond-paper fused grid: one pass over quadratic-form slabs accumulates
    sum_{i<j} T~ for every h simultaneously.  Memory O(chunk * n * h_chunk).
    `scales`: `exp_scales(h_grid)`."""
    scan_slabs = pairwise_quadform_chunks(x, sigma_inv, chunk)
    inv2 = -2.0 * scales   # 1 / (2 h^2), (n_h,)
    inv4 = -scales         # 1 / (4 h^2)

    def consume(acc, s, mask):
        sm = jnp.where(mask, s, 0.0)
        w = mask.astype(s.dtype)

        def per_h_chunk(args):
            i2, i4 = args   # (hc,)
            e2 = jnp.exp(-sm[None, :, :] * i2[:, None, None])
            e4 = jnp.exp(-sm[None, :, :] * i4[:, None, None])
            return jnp.sum((c_kk * e4 - 2.0 * c_k * e2) * w[None, :, :], axis=(1, 2))

        n_h = scales.shape[0]
        pad = (-n_h) % h_chunk
        i2 = jnp.pad(inv2, (0, pad)).reshape(-1, h_chunk)
        i4 = jnp.pad(inv4, (0, pad)).reshape(-1, h_chunk)
        contrib = jax.lax.map(per_h_chunk, (i2, i4)).reshape(-1)[:n_h]
        return acc + contrib

    return scan_slabs(consume, jnp.zeros((scales.shape[0],), x.dtype))


class Ties(NamedTuple):
    """A 1-D sample as its u distinct values with their multiplicities m_a,
    padded to u_p points (a power of two, at least the 128-point least tile
    of the pair kernels) with multiplicity 0.  `tied` is the number of pairs
    at distance 0, sum_a C(m_a, 2), as a float32 pair (hi, lo) that sums
    to it exactly."""
    values: np.ndarray    # (u_p,) float32
    weights: np.ndarray   # (u_p,) float32
    tied: np.ndarray      # (2,) float32


def distinct_values(col: np.ndarray) -> Ties:
    """`col` (n,) as its distinct values, however many there are."""
    v, m = np.unique(np.asarray(col, np.float32), return_counts=True)
    u_p = max(128, 1 << (v.size - 1).bit_length())
    tied = int(np.sum(m * (m - 1) // 2))
    hi = np.float32(tied)
    return Ties(np.pad(v, (0, u_p - v.size), mode="edge"),
                np.pad(m, (0, u_p - v.size)).astype(np.float32),
                np.asarray([hi, tied - int(hi)], np.float32))


def collapse(col: np.ndarray) -> Optional[Ties]:
    """`col`'s distinct values where summing over them takes at most a
    quarter of the pairs (u_p <= n / 2), else None."""
    ties = distinct_values(col)
    return ties if 2 * ties.values.size <= col.size else None


@partial(jax.jit, static_argnames=("n_h", "store_s", "chunk", "backend"))
def lscv_h(x: jax.Array, n_h: int = N_H_DEFAULT, store_s: bool = False,
           chunk: int = 128, backend: str = "jnp",
           ties: Optional[Ties] = None) -> LSCVhResult:
    """Full LSCV_h algorithm (paper §6.2 steps 1-7). x: (n, d).

    `ties` (1-D x, pallas backend): x's distinct values (`collapse(x)`);
    the grid sums then run over them with their multiplicities.  Sigma,
    the grid and n in eq. 43 still come from x."""
    if x.ndim == 1:
        x = x[:, None]
    n, d = x.shape
    if ties is not None and (backend != "pallas" or d != 1):
        raise ValueError("ties take a 1-D sample on the pallas backend")

    # Steps 1-3: covariance, det, inverse (sequential scalar work in the paper).
    sigma = covariance(x)
    det_sigma = jnp.linalg.det(sigma)
    sigma_inv = jnp.linalg.inv(sigma)

    # Steps 4-5: h0 and search range (eqs. 28-29).
    h0 = jnp.asarray(h0_start(n, d), x.dtype)
    h_grid = h_grid_for(n, d, n_h).astype(x.dtype)

    c_k, c_kk, r_k = G.lscv_h_consts(d, det_sigma)
    scales = exp_scales(h_grid)

    # Steps 6-7: S(v) precompute + grid search (paper), or fused streaming.
    t_lo = jnp.zeros_like(h_grid)
    if backend == "pallas":
        from repro.kernels import ops as kops
        if ties is None:
            t_sums, t_lo = kops.lscv_grid_sums(x, sigma_inv, scales, c_k, c_kk)
        else:
            t_sums, t_lo = kops.lscv_grid_sums(
                ties.values, sigma_inv, scales, c_k, c_kk,
                weights=ties.weights, tied=ties.tied)
    elif store_s:
        s_matrix = pairwise_sv_matrix(x, sigma_inv, chunk)
        rows = jnp.arange(n)
        mask = rows[:, None] < rows[None, :]
        t_sums = _t_sums_from_S(s_matrix, mask, scales, c_k, c_kk)
    else:
        t_sums = _t_sums_streaming(x, sigma_inv, scales, c_k, c_kk, chunk)

    inv_hi, inv_lo = _inv_h(scales)
    g_values = (inv_hi + inv_lo) ** d * (2.0 / (n * n) * (t_sums + t_lo)
                                         + r_k / n)               # eq. (43)
    best = _argmin_g(t_sums, t_lo, inv_hi, inv_lo, r_k * (n / 2.0), d)
    return LSCVhResult(h=h_grid[best], h_grid=h_grid, g_values=g_values,
                       sigma=sigma, det_sigma=det_sigma, h0=h0)


def _inv_h(scales):
    """1 / h = 2 sqrt(-scale) as a float32 pair: the h whose exponent the
    sums used.  h's float32 scale carries its own rounding, different at
    each grid point; g's 1 / h^d taken from h itself would move g by ~1e-7
    from one grid point to the next, more than g's two best points can
    differ by."""
    a = -scales
    s = jnp.sqrt(a)
    p, p_err = two_prod(s, s)
    return two_sum(2.0 * s, ((a - p) - p_err) / s)


def _argmin_g(t_hi, t_lo, inv_hi, inv_lo, k, d: int):
    """argmin of eq. 43's g over the grid, from the pair sums as float32
    pairs (t_hi + t_lo): g = (2 / n^2) (t + k) / h^d with k = r_k n / 2,
    and the positive factor 2 / n^2 left out; 1 / h as the pair
    (inv_hi + inv_lo).  Near its minimum g's grid neighbours can differ by
    less than one float32 rounding, so every step carries its error
    (`reductions.two_sum` / `two_prod`) and the candidates are compared by
    their difference from the float32 minimiser."""
    hi, lo = two_sum(t_hi, k)
    lo = lo + t_lo
    for _ in range(d):                     # (hi + lo) (inv_hi + inv_lo)
        p, p_err = two_prod(hi, inv_hi)
        hi, lo = two_sum(p, p_err + hi * inv_lo + lo * inv_hi)
    b = jnp.argmin(hi + lo)
    return jnp.argmin((hi - hi[b]) + (lo - lo[b]))


def g_of_h_sequential(x, h) -> float:
    """Unmodified eq. (24) evaluated naively in float64 numpy — the oracle for
    validating the §4.5 reformulation (recomputes the exponent for every pair
    at every h, i.e. the O(n_h n^2 d^2) path)."""
    import numpy as np

    x = np.asarray(x, dtype=np.float64)
    if x.ndim == 1:
        x = x[:, None]
    n, d = x.shape
    sigma = np.cov(x, rowvar=False, ddof=1).reshape(d, d)
    det = np.linalg.det(sigma)
    inv = np.linalg.inv(sigma)
    c_k = (2 * math.pi) ** (-d / 2) * det ** -0.5
    c_kk = (4 * math.pi) ** (-d / 2) * det ** -0.5
    acc = 0.0
    for i in range(n):
        for j in range(i + 1, n):
            u = (x[i] - x[j]) / h
            s = float(u @ inv @ u)
            acc += c_kk * math.exp(-0.25 * s) - 2.0 * c_k * math.exp(-0.5 * s)
    return float(h ** (-d) * (2.0 / (n * n) * acc + c_kk / n))


# ---------------------------------------------------------------------------
# LSCV_H
# ---------------------------------------------------------------------------

class LSCVHResult(NamedTuple):
    H: jax.Array
    g: jax.Array
    H_start: jax.Array
    it: jax.Array
    nfev: jax.Array


def g_of_H(x: jax.Array, H: jax.Array, chunk: int = 128, backend: str = "jnp") -> jax.Array:
    """Objective g(H) (eq. 32), evaluated with the fused pass of §6.3."""
    if x.ndim == 1:
        x = x[:, None]
    n, d = x.shape
    det_H = jnp.linalg.det(H)
    H_inv = jnp.linalg.inv(H)
    c_k, c_kk, r_k = G.lscv_H_consts(d, det_H)

    if backend == "pallas":
        from repro.kernels import ops as kops
        t_sum = kops.gh_fused_sum(x, H_inv, c_k, c_kk)
    else:
        fun1 = lambda s: c_kk * jnp.exp(-0.25 * s) - 2.0 * c_k * jnp.exp(-0.5 * s)
        t_sum = pairwise_quadform_reduce(fun1, x, H_inv, chunk)
    return 2.0 / (n * n) * t_sum + r_k / n


def matrix_sqrt(a: jax.Array) -> jax.Array:
    """SPD matrix square root via eigendecomposition (paper uses ALGLIB)."""
    w, v = jnp.linalg.eigh(a)
    return jnp.dot(v * jnp.sqrt(jnp.clip(w, 0.0)), v.T,
                   precision=jax.lax.Precision.HIGHEST)


def h_start(x: jax.Array) -> jax.Array:
    """eq. (37): H_start = (4/(d+2))^(1/(d+4)) n^(-1/(d+4)) Sigma^(1/2)."""
    n, d = x.shape
    sigma = covariance(x)
    return (4.0 / (d + 2.0)) ** (1.0 / (d + 4)) * n ** (-1.0 / (d + 4)) * matrix_sqrt(sigma)


def _vech_indices(d: int):
    return jnp.tril_indices(d)


def _theta_to_H(theta: jax.Array, d: int) -> jax.Array:
    """log-Cholesky: theta packs L's lower triangle, diagonal stored as log."""
    il, jl = _vech_indices(d)
    L = jnp.zeros((d, d), theta.dtype).at[il, jl].set(theta)
    L = L.at[jnp.diag_indices(d)].set(jnp.exp(jnp.diagonal(L)))
    return jnp.dot(L, L.T, precision=jax.lax.Precision.HIGHEST)


def _H_to_theta(H: jax.Array) -> jax.Array:
    L = jnp.linalg.cholesky(H)
    L = L.at[jnp.diag_indices(H.shape[0])].set(jnp.log(jnp.diagonal(L)))
    il, jl = _vech_indices(H.shape[0])
    return L[il, jl]


@partial(jax.jit, static_argnames=("max_iter", "chunk", "backend", "multi_start"))
def lscv_H(x: jax.Array, max_iter: int = 150, chunk: int = 128,
           backend: str = "jnp", multi_start: int = 1) -> LSCVHResult:
    """Full LSCV_H: Nelder-Mead over log-Cholesky(vech) of H (d(d+1)/2 dof).

    multi_start > 1 runs that many independent Nelder-Mead instances from
    perturbed H_start points *in parallel* (vmap) and keeps the best — the
    exact parallelisation the paper proposes for this inherently sequential
    optimiser in §6.3 ("start multiple parallel instances ... each from a
    different starting point"); on TPU the instances batch over the MXU.
    """
    if x.ndim == 1:
        x = x[:, None]
    n, d = x.shape
    H0 = h_start(x)
    theta0 = _H_to_theta(H0)

    def objective(theta):
        return g_of_H(x, _theta_to_H(theta, d), chunk=chunk, backend=backend)

    if multi_start == 1:
        res = nm_minimize(objective, theta0, max_iter=max_iter)
        H = _theta_to_H(res.x, d)
        return LSCVHResult(H=H, g=res.fun, H_start=H0, it=res.it, nfev=res.nfev)

    keys = jax.random.split(jax.random.key(0), multi_start - 1)
    jitter = jax.vmap(lambda k: 0.25 * jax.random.normal(k, theta0.shape))(keys)
    starts = jnp.concatenate([theta0[None], theta0[None] + jitter], axis=0)
    runs = jax.vmap(lambda t: nm_minimize(objective, t, max_iter=max_iter))(starts)
    best = jnp.argmin(runs.fun)
    H = _theta_to_H(runs.x[best], d)
    return LSCVHResult(H=H, g=runs.fun[best], H_start=H0,
                       it=runs.it[best], nfev=jnp.sum(runs.nfev))
