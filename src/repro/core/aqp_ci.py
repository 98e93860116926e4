"""Real error bars for AQP answers — per-path confidence-interval math.

The `rel_width` accuracy proxy (bandwidth-relative box width) says nothing a
caller can act on: it is unitless, path-dependent, and was outright wrong on
the exact paths.  This module computes actual confidence intervals for the
KDE execution paths, per the anytime-accuracy framing of Verdict-style tiered
sampling:

  range1d / box    analytic product-kernel variance.  The estimate is
                   scale * sum_i t_i over the m retained sample points, where
                   t_i is the per-point closed-form term (the Phi-difference
                   product for COUNT, the first-moment product for SUM).  The
                   sample points are an iid draw from the stream, so
                       Var(est) = scale^2 * m * Var(t)
                   and the sample variance of t gives a normal-theory CI.
                   AVG = SUM/COUNT uses the delta method with the exact
                   simplification sum(s_i - r*c_i) = 0 at r = sum(s)/sum(c).
  qmc              no closed form under a full bandwidth matrix; the CI comes
                   from subsample (batch-means) variance: split the retained
                   sample into K equal chunks — reservoir buffers are in
                   random order, so chunks are independent uniform
                   subsamples, the same structure as the tiers of a
                   `TieredReservoir` — answer each chunk on the shared node
                   set, and use the across-chunk spread with a Student-t
                   quantile (K-1 dof).
  exact            zero width (no smoothing, no sampling).
  exact:cm         bounded-error width from the count-min sketch parameters
                   (see `_StoreResolver.try_exact`).

The moment kernels mirror the estimate kernels in aqp.py/aqp_multid.py
(same per-point terms, extended with second moments) but run as a SEPARATE
jitted pass: the estimate passes stay byte-identical to the pre-CI engine,
which the admission bit-identity tests rely on.

Quantiles are closed-form approximations (Acklam's inverse normal CDF,
a Cornish-Fisher expansion for Student-t), accurate to ~1e-4 in the central
range — far below the statistical error of the intervals themselves — so no
scipy dependency is needed.
"""
from __future__ import annotations

import math
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro import obs

from .aqp import AVG_MIN_COUNT, OP_COUNT, OP_SUM, _Phi, _phi

DEFAULT_CI_LEVEL = 0.95

# Subsample count for the quasi-MC batch-means CI.  Small enough that each
# chunk still sees a useful sample, large enough for a usable t quantile.
QMC_SUBSAMPLES = 8


# --- quantiles --------------------------------------------------------------

_A = (-3.969683028665376e+01, 2.209460984245205e+02, -2.759285104469687e+02,
      1.383577518672690e+02, -3.066479806614716e+01, 2.506628277459239e+00)
_B = (-5.447609879822406e+01, 1.615858368580409e+02, -1.556989798598866e+02,
      6.680131188771972e+01, -1.328068155288572e+01)
_C = (-7.784894002430293e-03, -3.223964580411365e-01, -2.400758277161838e+00,
      -2.549732539343734e+00, 4.374664141464968e+00, 2.938163982698783e+00)
_D = (7.784695709041462e-03, 3.224671290700398e-01, 2.445134137142996e+00,
      3.754408661907416e+00)


def norm_ppf(p: float) -> float:
    """Inverse standard-normal CDF (Acklam's rational approximation,
    |error| < 1.2e-9 over (0, 1))."""
    if not 0.0 < p < 1.0:
        if p == 0.0:
            return -math.inf
        if p == 1.0:
            return math.inf
        raise ValueError(f"p must be in [0, 1], got {p}")
    p_low = 0.02425
    if p < p_low:
        q = math.sqrt(-2.0 * math.log(p))
        return ((((((_C[0] * q + _C[1]) * q + _C[2]) * q + _C[3]) * q
                  + _C[4]) * q + _C[5])
                / ((((_D[0] * q + _D[1]) * q + _D[2]) * q + _D[3]) * q + 1.0))
    if p > 1.0 - p_low:
        return -norm_ppf(1.0 - p)
    q = p - 0.5
    r = q * q
    return (((((_A[0] * r + _A[1]) * r + _A[2]) * r + _A[3]) * r + _A[4]) * r
            + _A[5]) * q / \
           (((((_B[0] * r + _B[1]) * r + _B[2]) * r + _B[3]) * r + _B[4]) * r
            + 1.0)


def t_ppf(p: float, dof: int) -> float:
    """Student-t quantile by the Cornish-Fisher expansion around the normal
    quantile — exact enough (<1e-3 for dof >= 4 in the central range) for
    batch-means CIs, whose dominant error is the K-chunk variance estimate."""
    if dof < 1:
        return math.inf
    z = norm_ppf(p)
    if not math.isfinite(z):
        return z
    z2 = z * z
    g1 = z * (z2 + 1.0) / 4.0
    g2 = z * (5.0 * z2 * z2 + 16.0 * z2 + 3.0) / 96.0
    g3 = z * (3.0 * z2 ** 3 + 19.0 * z2 * z2 + 17.0 * z2 - 15.0) / 384.0
    g4 = z * (79.0 * z2 ** 4 + 776.0 * z2 ** 3 + 1482.0 * z2 * z2
              - 1920.0 * z2 - 945.0) / 92160.0
    d = float(dof)
    return z + g1 / d + g2 / d ** 2 + g3 / d ** 3 + g4 / d ** 4


# --- analytic moment kernels (range1d / box paths) --------------------------
#
# Per-query sums over the m sample points of the unscaled closed-form terms:
# (sum c, sum s, sum c^2, sum s^2, sum c*s) with c_i the COUNT term and s_i
# the SUM term.  The same per-point math as _batch_terms/_box_terms, so the
# implied estimates match the estimate pass to float32 rounding.

@jax.jit
def moments_1d(x: jax.Array, h: jax.Array, a: jax.Array, b: jax.Array):
    """x: (m,) sample; a/b: (q,).  Returns five (q,) arrays."""
    def one(aq, bq):
        za = (aq - x) / h
        zb = (bq - x) / h
        c = _Phi(zb) - _Phi(za)
        s = x * c - h * (_phi(zb) - _phi(za))
        return (jnp.sum(c), jnp.sum(s),
                jnp.sum(c * c), jnp.sum(s * s), jnp.sum(c * s))
    return jax.vmap(one)(a, b)


@jax.jit
def moments_box(x: jax.Array, h_diag: jax.Array, lo: jax.Array,
                hi: jax.Array, tgt: jax.Array):
    """x: (m,d) rows; lo/hi: (q,d); tgt: (q,).  Returns five (q,) arrays.
    Queries run in 64-query slabs like `_box_terms` (same cache argument)."""
    axis = jnp.arange(x.shape[1])

    def one(loq, hiq, t):
        za = (loq[None, :] - x) / h_diag[None, :]
        zb = (hiq[None, :] - x) / h_diag[None, :]
        d_Phi = _Phi(zb) - _Phi(za)                               # (m, d)
        moment = x * d_Phi - h_diag[None, :] * (_phi(zb) - _phi(za))
        c = jnp.prod(d_Phi, axis=1)
        factors = jnp.where(axis[None, :] == t, moment, d_Phi)
        s = jnp.prod(factors, axis=1)
        return (jnp.sum(c), jnp.sum(s),
                jnp.sum(c * c), jnp.sum(s * s), jnp.sum(c * s))

    q_chunk = 64
    q, d = lo.shape
    if q <= q_chunk:
        return jax.vmap(one)(lo, hi, tgt)
    pad = (-q) % q_chunk
    lop = jnp.pad(lo, ((0, pad), (0, 0))).reshape(-1, q_chunk, d)
    hip = jnp.pad(hi, ((0, pad), (0, 0))).reshape(-1, q_chunk, d)
    tgtp = jnp.pad(tgt, (0, pad)).reshape(-1, q_chunk)
    out = jax.lax.map(lambda args: jax.vmap(one)(*args), (lop, hip, tgtp))
    return tuple(r.reshape(-1)[:q] for r in out)


def se_from_moments(ops: np.ndarray, moments, scale: float,
                    m: int) -> np.ndarray:
    """Per-query standard error of the scaled estimate from the raw moment
    sums; `ops` selects the COUNT/SUM/AVG formula per query.

    est = scale * sum(t)  =>  SE = scale * sqrt(m/(m-1)) *
                                   sqrt(sum(t^2) - sum(t)^2 / m).
    AVG uses the delta method on r = S/C; at r = sum(s)/sum(c) the residuals
    u_i = s_i - r c_i sum to zero exactly, so the variance term reduces to
    sum(u^2) = sum(s^2) - 2 r sum(cs) + r^2 sum(c^2).  Empty selections
    (scaled count below AVG_MIN_COUNT, where the engine pins AVG to 0) get an
    infinite SE — the estimate is a guard value, not an estimator.
    """
    # the host waits here for the moments pass: one copy per moment sum
    with obs.span("engine.fetch", n=len(ops)):
        m1c, m1s, m2c, m2s, m12 = (np.asarray(v, np.float64)
                                   for v in moments)
    ops = np.asarray(ops)
    if m < 2:
        return np.full(m1c.shape, np.inf)
    corr = m / (m - 1.0)
    se_count = scale * np.sqrt(corr * np.maximum(m2c - m1c * m1c / m, 0.0))
    se_sum = scale * np.sqrt(corr * np.maximum(m2s - m1s * m1s / m, 0.0))
    count = scale * m1c
    ok = count > AVG_MIN_COUNT
    r = np.where(ok, m1s / np.where(m1c != 0.0, m1c, 1.0), 0.0)
    quad = np.maximum(m2s - 2.0 * r * m12 + r * r * m2c, 0.0)
    se_avg = np.where(ok, scale * np.sqrt(corr * quad)
                      / np.maximum(count, AVG_MIN_COUNT), np.inf)
    return np.select([ops == OP_COUNT, ops == OP_SUM],
                     [se_count, se_sum], se_avg)


# --- subsample (batch-means) CI for the quasi-MC path -----------------------

def qmc_subsample_se(x: jax.Array, H: jax.Array, lo: np.ndarray,
                     hi: np.ndarray, tgt: np.ndarray, ops: np.ndarray,
                     n_source: int, n_qmc: int,
                     k_sub: int = QMC_SUBSAMPLES
                     ) -> Tuple[np.ndarray, int]:
    """(per-query SE, t dof) for a full-H group, by batch-means over K equal
    chunks of the retained sample (reservoir order is random, so chunks are
    independent uniform subsamples).  All chunks reduce over the node set
    planned for the FULL sample (`_qmc_plan`), so the deterministic QMC
    integration error is common-mode and the spread isolates sampling
    variance — the error source the CI is for."""
    from .aqp_multid import _halton_unit, _qmc_plan, _qmc_shared_terms

    q = np.asarray(lo).shape[0]
    m = x.shape[0]
    k = min(k_sub, m // 2)
    if k < 2:
        return np.full((q,), np.inf), 1
    plan = _qmc_plan(np.asarray(x, np.float64), np.asarray(H), lo, hi, n_qmc)
    if plan is None:                  # zero-measure boxes: estimate is 0
        return np.zeros((q,), np.float64), k - 1
    glo, ghi, clo, chi, n_nodes = plan
    unit = _halton_unit(n_nodes, x.shape[1])
    glo_d = jnp.asarray(glo, jnp.float32)
    ghi_d = jnp.asarray(ghi, jnp.float32)
    clo_d = jnp.asarray(clo, jnp.float32)
    chi_d = jnp.asarray(chi, jnp.float32)
    tgt_d = jnp.asarray(tgt, jnp.int32)
    ops = np.asarray(ops)
    chunk = m // k
    scale_k = n_source / chunk
    ests = []
    for j in range(k):
        xs = x[j * chunk: (j + 1) * chunk]
        cnt_raw, sum_raw = _qmc_shared_terms(xs, H, glo_d, ghi_d, clo_d,
                                             chi_d, tgt_d, unit)
        counts = scale_k * np.asarray(cnt_raw, np.float64)
        sums = scale_k * np.asarray(sum_raw, np.float64)
        avgs = np.where(counts > AVG_MIN_COUNT,
                        sums / np.maximum(counts, 1e-12), 0.0)
        ests.append(np.select([ops == OP_COUNT, ops == OP_SUM],
                              [counts, sums], avgs))
    e = np.stack(ests)
    return e.std(axis=0, ddof=1) / math.sqrt(k), k - 1
