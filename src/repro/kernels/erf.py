"""The normal CDF inside Pallas kernels, from operations Mosaic lowers.

Mosaic has no lowering for `lax.erf_p`, so the closed-form kernels
(aqp_batch, aqp_boxes, aqp_grouped) evaluate erf with this f32 rational
approximation: x P(x^2) / Q(x^2) on |x| <= 3.8325 (the point where f32 erf
rounds to 1), +-1 beyond, with the coefficients XLA uses for f32 erf.
Against float64 erf over f32 inputs its largest absolute error is 3.5e-7
(`ERF_ABS_ERR` below, checked in tests/test_kernels.py).  `ref.py` keeps
`jax.scipy.special.erf`, so the oracles stay independent of this code.
"""
from __future__ import annotations

import math

import jax.numpy as jnp

SQRT1_2 = 1.0 / math.sqrt(2.0)
INV_SQRT_2PI = 1.0 / math.sqrt(2.0 * math.pi)

# bound on |erf(x) - erf_float64(x)| over f32 inputs (measured: 3.5e-7)
ERF_ABS_ERR = 4e-7

_CLAMP = 3.832506856900711
# numerator (odd, highest power first) and denominator (even) coefficients
_P = (2.2905065861350646e-4, 3.4082910107109506e-3, 5.0955695062380861e-2,
      1.8520832239976145e-1, 1.128379143519084)
_Q = (-1.1791602954361697e-7, 2.3547966471313185e-5, 1.0179625278914885e-3,
      1.4070470171167667e-2, 1.1098505178285362e-1, 4.9746925110067538e-1,
      1.0)


def erf(x):
    """f32 erf from clamp, multiply-add and one divide."""
    xc = jnp.clip(x, -_CLAMP, _CLAMP)
    x2 = xc * xc
    p = jnp.full_like(xc, _P[0])
    for c in _P[1:]:
        p = p * x2 + c
    q = jnp.full_like(xc, _Q[0])
    for c in _Q[1:]:
        q = q * x2 + c
    return jnp.clip(xc * p / q, -1.0, 1.0)


def phi_terms(za, zb):
    """(Phi(zb) - Phi(za), phi(zb) - phi(za)) for standardized bounds: the
    two per-sample factors of the eq. 9-10 closed forms."""
    d_Phi = 0.5 * (erf(zb * SQRT1_2) - erf(za * SQRT1_2))
    d_phi = INV_SQRT_2PI * (jnp.exp(-0.5 * zb * zb) - jnp.exp(-0.5 * za * za))
    return d_Phi, d_phi
