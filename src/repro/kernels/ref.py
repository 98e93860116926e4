"""Pure-jnp oracles for every Pallas kernel in this package.

Each function computes exactly what the corresponding kernel computes, with no
tiling, so kernel tests can `assert_allclose` against it across shape/dtype
sweeps.  These are O(n^2)-memory implementations — test scale only.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from repro.core import gaussian as G


def _pair_mask(n: int):
    idx = jnp.arange(n)
    return idx[:, None] < idx[None, :]


def pairwise_scaled_ksum(x: jnp.ndarray, g: jnp.ndarray, kind: str) -> jnp.ndarray:
    """sum_{i<j} K^(r)((x_i - x_j)/g)   (PLUGIN eqs. 16/18 inner sums)."""
    fun = {"k4": G.k4, "k6": G.k6, "gauss": G.phi}[kind]
    diff = (x[:, None] - x[None, :]) / g
    return jnp.sum(jnp.where(_pair_mask(x.shape[0]), fun(diff), 0.0))


def sv_matrix(x: jnp.ndarray, m: jnp.ndarray) -> jnp.ndarray:
    """S_{ij} = (x_i-x_j)^T M (x_i-x_j) on the strict upper triangle, else 0.
    x: (n, d)."""
    v = x[:, None, :] - x[None, :, :]
    s = jnp.einsum("ijd,de,ije->ij", v, m, v)
    return jnp.where(_pair_mask(x.shape[0]), s, 0.0)


def gh_fused_sum(x: jnp.ndarray, h_inv: jnp.ndarray, c_k, c_kk) -> jnp.ndarray:
    """sum_{i<j} T_H(x_i - x_j)  (LSCV_H eq. 32 inner sum, fused §6.3)."""
    s = sv_matrix(x, h_inv)
    t = c_kk * jnp.exp(-0.25 * s) - 2.0 * c_k * jnp.exp(-0.5 * s)
    return jnp.sum(jnp.where(_pair_mask(x.shape[0]), t, 0.0))


def lscv_grid_sums(x: jnp.ndarray, sigma_inv: jnp.ndarray, scales: jnp.ndarray,
                   c_k, c_kk) -> jnp.ndarray:
    """Per-h inner sums of eq. (43): for each h, sum_{i<j} T~(x_i - x_j);
    the grid given as its exponent scales -1 / (4 h^2)."""
    s = sv_matrix(x, sigma_inv)
    mask = _pair_mask(x.shape[0])

    def per_h(c):
        t = c_kk * jnp.exp(c * s) - 2.0 * c_k * jnp.exp(2.0 * c * s)
        return jnp.sum(jnp.where(mask, t, 0.0))

    import jax
    return jax.vmap(per_h)(scales)


def kde_eval(points: jnp.ndarray, x: jnp.ndarray, h) -> jnp.ndarray:
    """f^(points) per eq. (3), Gaussian kernel. points: (m, d), x: (n, d)."""
    import math
    n, d = x.shape
    diff = (points[:, None, :] - x[None, :, :]) / h
    quad = 0.5 * jnp.sum(diff * diff, axis=-1)
    norm = (2.0 * math.pi) ** (-d / 2.0) * h ** (-d)
    return norm * jnp.mean(jnp.exp(-quad), axis=1)


def aqp_box_sums(x: jnp.ndarray, h_diag: jnp.ndarray, lo: jnp.ndarray,
                 hi: jnp.ndarray, tgt: jnp.ndarray):
    """Unscaled eq. 11 box integrals for a query batch (product kernel,
    diagonal bandwidth).  x: (n,d), lo/hi: (q,d), tgt: (q,) int32 ->
    (count_raw, sum_raw), each (q,)."""
    sqrt1_2 = 1.0 / math.sqrt(2.0)
    inv_sqrt_2pi = 1.0 / math.sqrt(2.0 * math.pi)
    za = (lo[:, None, :] - x[None, :, :]) / h_diag[None, None, :]   # (q, n, d)
    zb = (hi[:, None, :] - x[None, :, :]) / h_diag[None, None, :]
    d_Phi = 0.5 * (jax.scipy.special.erf(zb * sqrt1_2)
                   - jax.scipy.special.erf(za * sqrt1_2))
    d_phi = inv_sqrt_2pi * (jnp.exp(-0.5 * zb * zb) - jnp.exp(-0.5 * za * za))
    moment = x[None, :, :] * d_Phi - h_diag[None, None, :] * d_phi
    axis = jnp.arange(x.shape[1])
    factors = jnp.where(axis[None, None, :] == tgt[:, None, None], moment, d_Phi)
    count_raw = jnp.sum(jnp.prod(d_Phi, axis=2), axis=1)
    sum_raw = jnp.sum(jnp.prod(factors, axis=2), axis=1)
    return count_raw, sum_raw


def aqp_grouped_sums(x: jnp.ndarray, h_diag: jnp.ndarray, lo: jnp.ndarray,
                     hi: jnp.ndarray, glo: jnp.ndarray, ghi: jnp.ndarray,
                     g_axis: int, tgt: int):
    """Unscaled factored GROUP BY integrals (eq. 11): shared-axes product
    crossed with G per-category windows on axis `g_axis`.  x: (n,d), lo/hi:
    (d,) shared box (group axis ignored), glo/ghi: (G,) -> (count_raw,
    sum_raw), each (G,)."""
    sqrt1_2 = 1.0 / math.sqrt(2.0)
    inv_sqrt_2pi = 1.0 / math.sqrt(2.0 * math.pi)
    za = (lo[None, :] - x) / h_diag[None, :]                        # (n, d)
    zb = (hi[None, :] - x) / h_diag[None, :]
    d_Phi = 0.5 * (jax.scipy.special.erf(zb * sqrt1_2)
                   - jax.scipy.special.erf(za * sqrt1_2))
    axis = jnp.arange(x.shape[1])
    keep = axis != g_axis
    shared_cnt = jnp.prod(jnp.where(keep[None, :], d_Phi, 1.0), axis=1)

    xg = x[:, g_axis]
    hg = h_diag[g_axis]
    gza = (glo[None, :] - xg[:, None]) / hg                         # (n, G)
    gzb = (ghi[None, :] - xg[:, None]) / hg
    g_Phi = 0.5 * (jax.scipy.special.erf(gzb * sqrt1_2)
                   - jax.scipy.special.erf(gza * sqrt1_2))
    count_raw = jnp.sum(shared_cnt[:, None] * g_Phi, axis=0)

    if tgt == g_axis:
        g_dphi = inv_sqrt_2pi * (jnp.exp(-0.5 * gzb * gzb)
                                 - jnp.exp(-0.5 * gza * gza))
        g_moment = xg[:, None] * g_Phi - hg * g_dphi
        sum_raw = jnp.sum(shared_cnt[:, None] * g_moment, axis=0)
    else:
        d_phi = inv_sqrt_2pi * (jnp.exp(-0.5 * zb * zb)
                                - jnp.exp(-0.5 * za * za))
        moment = x * d_Phi - h_diag[None, :] * d_phi
        factors = jnp.where(axis[None, :] == tgt, moment, d_Phi)
        shared_sm = jnp.prod(jnp.where(keep[None, :], factors, 1.0), axis=1)
        sum_raw = jnp.sum(shared_sm[:, None] * g_Phi, axis=0)
    return count_raw, sum_raw


def qmc_box_reduce(nodes: jnp.ndarray, x: jnp.ndarray, h_inv: jnp.ndarray,
                   log_norm, lo: jnp.ndarray, hi: jnp.ndarray,
                   tgt: jnp.ndarray):
    """Raw double sums of the fused QMC box reduction: for each box q,
    sum over nodes inside the box of the summed (not averaged) Gaussian
    kernel values against the whole sample.  nodes: (m,d), x: (n,d),
    h_inv: (d,d), lo/hi: (q,d), tgt: (q,) -> (cnt_sums, sum_sums)."""
    diff = nodes[:, None, :] - x[None, :, :]                        # (m, n, d)
    quad = 0.5 * jnp.einsum("mnd,de,mne->mn", diff, h_inv, diff)
    f_sums = jnp.sum(jnp.exp(log_norm - quad), axis=1)              # (m,)
    inside = jnp.all((nodes[None, :, :] >= lo[:, None, :])
                     & (nodes[None, :, :] <= hi[:, None, :]), axis=2)
    w = inside * f_sums[None, :]                                    # (q, m)
    cnt_sums = jnp.sum(w, axis=1)
    tvals = nodes.T[tgt]                     # (q, m): node target coordinate
    sum_sums = jnp.sum(w * tvals, axis=1)
    return cnt_sums, sum_sums


def rff_density(points: jnp.ndarray, w: jnp.ndarray, b: jnp.ndarray,
                z: jnp.ndarray) -> jnp.ndarray:
    """Un-normalised RFF density dots: cos(points @ W.T + b) @ z.
    points: (m, d), w: (D, d), b/z: (D,) -> (m,)."""
    return jnp.cos(points @ w.T + b[None, :]) @ z


def aqp_batch_sums(x: jnp.ndarray, h, a: jnp.ndarray, b: jnp.ndarray):
    """Unscaled closed-form integrals of eqs. 9-10 for a query batch.
    x: (n,), a/b: (q,) -> (count_raw, sum_raw), each (q,)."""
    sqrt1_2 = 1.0 / math.sqrt(2.0)
    inv_sqrt_2pi = 1.0 / math.sqrt(2.0 * math.pi)
    za = (a[:, None] - x[None, :]) / h                   # (q, n)
    zb = (b[:, None] - x[None, :]) / h
    d_Phi = 0.5 * (jax.scipy.special.erf(zb * sqrt1_2)
                   - jax.scipy.special.erf(za * sqrt1_2))
    d_phi = inv_sqrt_2pi * (jnp.exp(-0.5 * zb * zb) - jnp.exp(-0.5 * za * za))
    count_raw = jnp.sum(d_Phi, axis=1)
    sum_raw = jnp.sum(x[None, :] * d_Phi - h * d_phi, axis=1)
    return count_raw, sum_raw
